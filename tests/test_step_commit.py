"""The per-step token commit of ``DecodeWorker``.

Covers:
  * ``step()`` across a join, a margin rebuild and a leave gives the
    tokens ``decode_round`` gives on the same residents;
  * the host's ``context_len`` equals the device state's lengths after
    every step, rebuilds included;
  * a commit reads the device once: no per-member indexing of the token
    array, and ``step.commit`` carries ``reads=1`` on the full and the
    layerwise path.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.models.transformer import DecoderLM
from repro.obs import Tracer
from repro.serving.disagg import DisaggService

ARRAY = type(jnp.zeros(1))
STEPS_ALONE = 34  # past A's first page margin: 64 + 32 positions
STEPS_JOINED, STEPS_AFTER_LEAVE = 3, 2


def _toks(cfg, seed, n):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).astype(np.int32)


@contextlib.contextmanager
def _device_access(monkeypatch):
    """Record the device arrays whose values reach the host (by the
    buffer protocol ``np.asarray`` takes, or the ``_value`` that
    ``int()`` and ``__array__`` take), each once, and every per-member
    index into a vector: ``tokens[i]`` or ``context_lens[i]``."""
    seen = {"read": [], "members": []}
    buffer, value, getitem = ARRAY.__buffer__, ARRAY._value, ARRAY.__getitem__

    def note(x):
        if not any(x is y for y in seen["read"]):
            seen["read"].append(x)

    def counted_buffer(self, flags):
        note(self)
        return buffer(self, flags)

    def counted_value(self):
        note(self)
        return value.fget(self)

    def recorded_getitem(self, idx):
        if self.ndim == 1 and isinstance(idx, (int, np.integer)):
            seen["members"].append(idx)
        return getitem(self, idx)

    with monkeypatch.context() as m:
        m.setattr(ARRAY, "__buffer__", counted_buffer)
        m.setattr(ARRAY, "_value", property(counted_value))
        m.setattr(ARRAY, "__getitem__", recorded_getitem)
        yield seen
    seen["reads"] = len(seen.pop("read"))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("deepseek-67b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _admit(svc, tokens):
    h = svc.submit(tokens)
    assert svc.admit_to_decode(h.request)
    return h.request_id


@pytest.fixture(scope="module")
def stepped(setup):
    """A decodes alone past its page margin, B joins, B leaves; every
    ``step()`` runs under the read counter.  Returns the tokens per
    request, each step's reads, member indices and writebacks, and the host
    and device lengths after each step."""
    cfg, model, params = setup
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64,
                        tracer=tracer)
    dw = svc.decode
    dw.step_margin_blocks = 1
    mp = pytest.MonkeyPatch()
    out = {"tokens": {}, "steps": [], "lens": []}

    def run(n):
        for _ in range(n):
            wb = len([s for s in tracer.spans if s.name == "step.writeback"])
            with _device_access(mp) as seen:
                got = dw.step()
            seen["writebacks"] = len(
                [s for s in tracer.spans if s.name == "step.writeback"]) - wb
            out["steps"].append(seen)
            for rid, tok in got.items():
                out["tokens"].setdefault(rid, []).append(tok)
            out["lens"].append(
                ([dw.resident[rid].context_len for rid in dw._step_ids],
                 np.asarray(dw._step_state.context_lens).tolist()))

    a = _admit(svc, _toks(cfg, 1, 2 * model.BLOCK_SIZE))
    run(STEPS_ALONE)
    b = _admit(svc, _toks(cfg, 2, 40))
    run(STEPS_JOINED)
    dw.finish(b)
    run(STEPS_AFTER_LEAVE)
    out["ids"], out["tracer"] = (a, b), tracer
    return out


def test_step_tokens_match_decode_round_across_join_margin_and_leave(setup, stepped):
    cfg, model, params = setup
    reasons = [s.attrs["reason"] for s in stepped["tracer"].spans
               if s.name == "step.rebuild"]
    assert reasons == ["join", "margin", "join", "leave"]

    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64)
    a = _admit(svc, _toks(cfg, 1, 2 * model.BLOCK_SIZE))
    want_a = svc.decode.decode_round(STEPS_ALONE)[a]
    b = _admit(svc, _toks(cfg, 2, 40))
    joined = svc.decode.decode_round(STEPS_JOINED)
    svc.decode.finish(b)
    want_a += joined[a] + svc.decode.decode_round(STEPS_AFTER_LEAVE)[a]

    got_a, got_b = (stepped["tokens"][rid] for rid in stepped["ids"])
    assert got_a == want_a
    assert got_b == joined[b]


def test_host_context_lengths_equal_the_device_state(stepped):
    for host, device in stepped["lens"]:
        assert host == device
    # one per step, from the prompt: A 64 + 39 steps, B 40 + 3 steps
    assert stepped["lens"][-1][0] == [64 + STEPS_ALONE + STEPS_JOINED + STEPS_AFTER_LEAVE]
    assert stepped["lens"][STEPS_ALONE + STEPS_JOINED - 1][0][1] == 40 + STEPS_JOINED


def test_a_step_reads_the_device_once_and_indexes_no_member(stepped):
    for seen in stepped["steps"]:
        # a writeback reads its two planes; the commit reads the tokens once
        assert seen["reads"] == 1 + 2 * seen["writebacks"]
        assert seen["members"] == []
    assert sum(s["writebacks"] for s in stepped["steps"]) == 3


def test_commit_spans_carry_one_read(stepped):
    commits = [s for s in stepped["tracer"].spans if s.name == "step.commit"]
    assert len(commits) == STEPS_ALONE + STEPS_JOINED + STEPS_AFTER_LEAVE
    assert {s.attrs["reads"] for s in commits} == {1}


def test_the_layerwise_commit_reads_once(setup, monkeypatch):
    """A layerwise first step (the pull streams in during the step) and
    the full step after it: one read, one ``step.commit`` with
    ``reads=1`` each."""
    cfg, _, _ = setup
    model = DecoderLM(cfg, unroll=True)
    params = model.init_params(jax.random.PRNGKey(0))
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64,
                        consume="layerwise", tracer=tracer)
    h = svc.submit(_toks(cfg, 3, 40))
    svc.admit_queued()
    dw = svc.decode
    assert h.request_id in dw.inflight
    for _ in range(2):
        with _device_access(monkeypatch) as seen:
            dw.step()
        assert seen["reads"] == 1
        assert seen["members"] == []
    launches = [s for s in tracer.spans if s.name == "step.launch"]
    assert [s.attrs.get("layerwise", False) for s in launches] == [True, False]
    commits = [s for s in tracer.spans if s.name == "step.commit"]
    assert [s.attrs["reads"] for s in commits] == [1, 1]
    assert dw.resident[h.request_id].context_len == 42
    assert np.asarray(dw._step_state.context_lens).tolist() == [42]
