"""Spans inside the decode step, the prefill and the KV pull.

Covers:
  * the ``step.*`` spans nest inside ``tick.step`` on the decode
    worker's track, with ``step.writeback`` and ``step.build`` under
    ``step.rebuild``; rebuild reasons and byte attributes;
  * the layerwise path's ``step.launch`` / ``step.commit``;
  * the ``prefill.*`` spans on the prefill worker's track;
  * ``transfer.copy`` bytes summing to ``HandleMetrics.kv_bytes_pulled``;
  * ``jax.compile`` spans: one for a new decode shape, none for a warm
    one; the listener holds its tracer weakly, is shared per tracer, is
    swept once the tracer is gone and can be closed;
  * a disabled tracer records nothing and registers no listener;
  * the Chrome export's clock base.
"""
import gc

import jax
import numpy as np
import pytest
from jax._src import monitoring as jax_monitoring

from repro.configs import get_smoke_config
from repro.models.registry import build_model
from repro.obs import Tracer
from repro.serving import compiles
from repro.serving.disagg import DisaggService

STEP_CHILDREN = ("step.pump", "step.rebuild", "step.launch", "step.commit")


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("deepseek-67b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _toks(cfg, seed, n):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=n).astype(np.int32)


def _staggered(svc, cfg):
    """A decodes alone, B joins while A decodes (its pull, two reads a
    pump, hides behind A's steps) and leaves first: join, join and leave
    rebuilds."""
    svc.loop.pump_budget = 2
    a = svc.submit(_toks(cfg, 1, 40), max_new=8)
    while len(a.tokens) < 3:
        svc.loop.tick()
    b = svc.submit(_toks(cfg, 2, 56), max_new=3)
    svc.loop.run_until_idle()
    assert a.done and b.done
    return a, b


def _inside(inner, outer) -> bool:
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def _parent(span, spans, name):
    return [p for p in spans if p.name == name and p.track == span.track
            and p.depth == span.depth - 1 and _inside(span, p)]


@pytest.fixture(scope="module")
def staggered(setup):
    cfg, model, params = setup
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1,
                        num_blocks=64, tracer=tracer)
    handles = _staggered(svc, cfg)
    return svc, tracer, handles


class TestDecodeStepSpans:
    def test_step_spans_nest_in_tick_step(self, staggered):
        _, tracer, _ = staggered
        spans = tracer.spans_of(("worker", "d0"))
        children = [s for s in spans if s.name in STEP_CHILDREN]
        assert {s.name for s in children} == set(STEP_CHILDREN)
        for s in children:
            assert len(_parent(s, spans, "tick.step")) == 1, s
        for s in spans:
            if s.name in ("step.writeback", "step.build"):
                assert len(_parent(s, spans, "step.rebuild")) == 1, s
        n_steps = sum(1 for s in spans if s.name == "tick.step")
        assert sum(1 for s in spans if s.name == "step.launch") == n_steps
        assert sum(1 for s in spans if s.name == "step.commit") == n_steps

    def test_rebuilds_carry_reason_batch_and_bytes(self, setup, staggered):
        cfg, model, _ = setup
        svc, tracer, _ = staggered
        spans = tracer.spans_of(("worker", "d0"))
        rebuilds = [s for s in spans if s.name == "step.rebuild"]
        assert [s.attrs["reason"] for s in rebuilds] == ["join", "join", "leave"]
        assert [s.attrs["batch"] for s in rebuilds] == [1, 2, 1]
        wb = [s for s in spans if s.name == "step.writeback"]
        builds = [s for s in spans if s.name == "step.build"]
        assert len(wb) == 2 and len(builds) == 3  # the first has no state yet

        def kv_bytes(r):  # both bf16 planes of a (batch, per_seq) state
            return (2 * cfg.num_layers * r.attrs["batch"] * r.attrs["per_seq"]
                    * model.BLOCK_SIZE * cfg.num_kv_heads * cfg.head_dim * 2)

        for r, b in zip(rebuilds, builds):
            lens_and_tables = 4 * r.attrs["batch"] * (1 + r.attrs["per_seq"])
            assert b.attrs["bytes"] == kv_bytes(r) + lens_and_tables
        # each writeback copies back the state the previous rebuild built
        assert [w.attrs["bytes"] for w in wb] == [kv_bytes(r) for r in rebuilds[:2]]

    def test_a_pull_in_flight_is_pumped_behind_the_launch(self, staggered):
        _, tracer, _ = staggered
        pumps = [s for s in tracer.spans_of(("worker", "d0"))
                 if s.name == "step.pump"]
        overlapped = [s for s in pumps if s.attrs["overlapped"]]
        assert overlapped and any(s.attrs["reads"] > 0 for s in overlapped)
        assert all(s.attrs["bytes"] >= 0 for s in pumps)


class TestPrefillAndTransferSpans:
    def test_prefill_spans_on_the_prefill_worker_track(self, staggered):
        _, tracer, handles = staggered
        spans = tracer.spans_of(("worker", "p0"))
        names = [s.name for s in spans]
        assert names == ["prefill.compute", "prefill.park", "prefill.hash"] * len(handles)
        for c, p, h in zip(spans[::3], spans[1::3], spans[2::3]):
            assert c.t1 <= p.t0 and p.t1 <= h.t0
            assert c.attrs["bytes"] > 0 and p.attrs["blocks"] == h.attrs["blocks"] > 0
        assert not any(s.name.startswith("prefill.")
                       for s in tracer.spans if s.track != ("worker", "p0"))

    def test_quantised_transfer_adds_prefill_quant(self, setup):
        cfg, model, params = setup
        tracer = Tracer()
        svc = DisaggService(model, params, n_prefill=1, n_decode=1,
                            num_blocks=64, quantize_transfer=True, tracer=tracer)
        h = svc.submit(_toks(cfg, 3, 40), max_new=2)
        svc.loop.run_until_idle()
        assert h.done
        names = [s.name for s in tracer.spans_of(("worker", "p0"))]
        assert names == ["prefill.compute", "prefill.park", "prefill.hash",
                         "prefill.quant"]

    def test_copy_bytes_sum_to_the_bytes_pulled(self, staggered):
        svc, tracer, handles = staggered
        copies = [s for s in tracer.spans if s.name == "transfer.copy"]
        assert copies and all(s.track == ("engine", "tensor_centric") for s in copies)
        pulled = sum(h.metrics.kv_bytes_pulled for h in handles)
        assert pulled > 0
        assert sum(s.attrs["bytes"] for s in copies) == pulled
        assert sum(s.attrs["reads"] for s in copies) == svc.engine.stats.reads_executed

    def test_no_submit_instant(self, staggered):
        _, tracer, _ = staggered
        assert "transfer.submit" not in {s.name for s in tracer.instants}


def test_chrome_export_carries_its_clock_base(staggered):
    """An exported event's time on the tracer's clock is the base plus
    its offset: what lines the export up with a JAX profile."""
    _, tracer, _ = staggered
    doc = tracer.to_chrome()
    base = doc["otherData"]["clock_base_s"]
    span = min((s for s in tracer.spans if s.name == "step.build"), key=lambda s: s.t0)
    ev = next(e for e in doc["traceEvents"] if e["name"] == "step.build")
    assert base + ev["ts"] / 1e6 == pytest.approx(span.t0, abs=1e-6)


def test_layerwise_steps_have_launch_and_commit(setup):
    cfg, model, params = setup
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64,
                        consume="layerwise", tracer=tracer)
    _staggered(svc, cfg)
    spans = tracer.spans_of(("worker", "d0"))
    launches = [s for s in spans if s.name == "step.launch"]
    assert any(s.attrs.get("layerwise") for s in launches)
    for s in spans:
        if s.name in STEP_CHILDREN:
            assert len(_parent(s, spans, "tick.step")) == 1, s
    assert sum(1 for s in spans if s.name == "step.commit") >= len(launches)


# ---------------------------------------------------------- compiles
def _compiles(tracer, fun):
    return [s for s in tracer.spans
            if s.name == "jax.compile" and fun in s.attrs["fun_name"]]


def test_a_new_decode_shape_compiles_once_and_a_warm_one_not():
    """A model of its own gives the jitted programs cache entries of
    their own: its first request compiles one decode program, and a
    second request of the same shape compiles nothing."""
    cfg = get_smoke_config("deepseek-67b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64,
                        tracer=tracer)
    t0 = tracer.now()
    svc.generate(svc.submit(_toks(cfg, 4, 40), max_new=3), max_new=3)
    (span,) = _compiles(tracer, "jit_decode_step")
    assert len(_compiles(tracer, "jit_prefill")) == 1
    assert t0 <= span.t0 <= span.t1 <= tracer.now() and span.track == "jax"
    step = next(s for s in tracer.spans if s.name == "step.launch"
                and s.t0 <= span.t0 and span.t1 <= s.t1)
    assert step.track == ("worker", "d0")
    svc.generate(svc.submit(_toks(cfg, 5, 40), max_new=3), max_new=3)
    assert len(_compiles(tracer, "jit_decode_step")) == 1
    assert len(_compiles(tracer, "jit_prefill")) == 1
    svc.compile_spans.close()


def test_the_compile_listener_holds_its_tracer_weakly_and_is_swept(setup):
    _, model, params = setup
    tracer = Tracer()
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=16,
                        tracer=tracer)
    lst = svc.compile_spans
    assert lst.tracer is tracer and lst in compiles._registered
    twin = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=16,
                         tracer=tracer)
    assert twin.compile_spans is lst  # one listener per tracer
    del svc, twin, tracer
    gc.collect()
    assert lst.tracer is None
    lst(compiles.COMPILE_EVENT, 0.0, 1.0, fun_name="f")  # a no-op now
    other = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=16,
                          tracer=Tracer())
    assert lst not in compiles._registered
    assert lst not in jax_monitoring.get_event_time_span_listeners()
    assert all(x.tracer is not None for x in compiles._registered)
    other.compile_spans.close()
    assert other.compile_spans not in compiles._registered
    assert other.compile_spans not in jax_monitoring.get_event_time_span_listeners()


def test_a_disabled_tracer_records_nothing_and_registers_nothing(setup):
    cfg, model, params = setup
    gc.collect()
    compiles.record_compiles(Tracer(enabled=False))  # sweep dead listeners
    before = jax_monitoring.get_event_time_span_listeners()
    tracer = Tracer(enabled=False)
    svc = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64,
                        tracer=tracer)
    plain = DisaggService(model, params, n_prefill=1, n_decode=1, num_blocks=64)
    assert svc.compile_spans is None and plain.compile_spans is None
    assert jax_monitoring.get_event_time_span_listeners() == before
    h = svc.submit(_toks(cfg, 6, 40), max_new=3)
    svc.loop.run_until_idle()
    assert h.done
    assert tracer.spans == [] and tracer.instants == []
