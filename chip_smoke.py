#!/usr/bin/env python3
"""Bring-up run: serve yi-9b through ``DisaggService`` on one TPU chip.

    python chip_smoke.py                 # one chip; fails where JAX finds no TPU
    python chip_smoke.py --chips 4       # 2 prefill + 2 decode workers, one chip each
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

Model: yi-9b at every published width, reduced: num_layers 48→24.  The
cut stands for a deployment in which two pipeline stages share the model
and this chip holds one of them.  The weights are random, drawn from
``--seed`` by a jitted ``init_params``.  ``--rehearse`` swaps in the
tiny smoke config of the same architecture and accepts any backend.

The run goes through the entry points a user calls: ``submit`` returns
handles, ``svc.loop.tick()`` and ``run_until_idle()`` serve them.  Four
requests (prompts of 2,048 and 512 tokens, so both prefill attention
branches run), two of them submitted while the others decode, then one
request served alone.  It fails, with a non-zero exit and no result line,
when

  * a handle ends FAILED or with fewer than ``max_new`` decoded tokens;
  * a first token differs from argmax of a direct ``model.prefill`` of
    the same prompt on the same chip;
  * the request served alone decodes greedy tokens that differ from a
    direct prefill -> decode_step loop, unless the direct loop's top-2
    logit margin at the first differing step is within one bf16
    rounding step of its top logit (and the served token is the
    runner-up);
  * ``--chips 4``: the four workers do not each hold their parameters
    and decode state on a device of their own.

TTFT/TTLT lines are smoke timings, not results.  The last line of
standard output is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.launch.compile_cache import use_compilation_cache  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving.disagg import DisaggService  # noqa: E402
from repro.serving.handle import HandleStatus  # noqa: E402

ARCH = "yi-9b"
SERVED_LAYERS = 24          # reduced: num_layers 48→24
PROMPT_LENS = (2048, 512, 512, 2048)
ALONE_PROMPT_LEN = 512
MAX_NEW = 16
MAX_TICKS = 10_000


class SmokeFailure(RuntimeError):
    """A check of the run failed."""


class CompileSeconds:
    """Sums XLA backend-compile seconds per phase, from JAX's own
    monitoring events.  A program the persistent cache served adds 0."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.by_phase: collections.Counter[str] = collections.Counter()
        self.phase = "setup"

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event == self.EVENT:
            self.by_phase[self.phase] += duration_secs

    def __enter__(self) -> "CompileSeconds":
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


def _device_of(tree) -> jax.Device:
    (device,) = jax.tree.leaves(tree)[0].devices()
    return device


def _config(rehearse: bool):
    if rehearse:
        return get_smoke_config(ARCH), "smoke config (rehearsal)"
    full = get_config(ARCH)
    cfg = dataclasses.replace(full, num_layers=SERVED_LAYERS)
    return cfg, f"reduced: num_layers {full.num_layers}→{cfg.num_layers}"


def _drive(svc, handles, tracked_decodes: dict) -> None:
    """Tick until every handle finished; record the device of each decode
    worker's live decode state after every tick."""
    for _ in range(MAX_TICKS):
        if all(h.finished for h in handles):
            return
        report = svc.loop.tick()
        for wid, dw in svc.decodes.items():
            state = dw._step_state  # the worker's persistent DecodeState
            if state is not None:
                tracked_decodes.setdefault(wid, set()).add(
                    _device_of(state.k_pages))
        if not report.progressed:
            raise SmokeFailure(f"serve loop made no progress: {report.describe()}")
    raise SmokeFailure(f"requests unfinished after {MAX_TICKS} ticks")


def _check_handles(handles) -> None:
    for h in handles:
        if h.status is not HandleStatus.DONE or h.decoded < MAX_NEW:
            raise SmokeFailure(
                f"{h.request_id}: status {h.status.value}, "
                f"{h.decoded}/{MAX_NEW} decoded tokens")


def _bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 numbers (8 significant bits) at ``x``."""
    return 2.0 ** (np.floor(np.log2(abs(x))) - 7) if x else 2.0 ** -133


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny config of the same architecture, any backend")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: two prefill and two decode workers, one chip each")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compilation_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "pass --rehearse to run the smoke config here",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    n_decode = 2 if args.chips == 4 else 1

    cfg, cut = _config(args.rehearse)
    print(f"[smoke] devices: {len(devices)} x {devices[0].device_kind} "
          f"({platform}); compile cache: {cache_dir}")
    print(f"[smoke] model {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}; {cut}")

    with CompileSeconds() as compiles:
        try:
            _run(args, cfg, n_decode, compiles)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
        for phase, secs in compiles.by_phase.items():
            print(f"[smoke] compile seconds, {phase}: {secs}")

    for d in devices[: max(args.chips, 1)]:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"[smoke] {d}: peak_bytes_in_use="
              f"{peak if peak is not None else 'not reported'}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


def _run(args, cfg, n_decode: int, compiles: CompileSeconds) -> None:
    model = build_model(cfg)
    compiles.phase = "init"
    params = jax.jit(model.init_params)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[smoke] weights: {nbytes} bytes on {_device_of(params)}")

    compiles.phase = "serve"
    svc = DisaggService(model, params, n_prefill=2, n_decode=n_decode)
    workers = {**svc.prefills, **svc.decodes}
    for wid, w in workers.items():
        print(f"[smoke] worker {wid}: parameters on {_device_of(w.params)}")

    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS + (ALONE_PROMPT_LEN,)]
    decode_devices: dict[str, set] = {}
    t0 = time.perf_counter()
    handles = [svc.submit(p, max_new=MAX_NEW) for p in prompts[:2]]
    for _ in range(MAX_TICKS):
        if any(h.status is HandleStatus.DECODING and h.decoded
               for h in handles):
            break
        svc.loop.tick()
    decoding = [h.request_id for h in handles
                if h.status is HandleStatus.DECODING]
    if not decoding:
        raise SmokeFailure("no request reached decode before the joins")
    handles += [svc.submit(p, max_new=MAX_NEW) for p in prompts[2:4]]
    print(f"[smoke] {handles[2].request_id}, {handles[3].request_id} "
          f"submitted while {decoding} decoding")
    _drive(svc, handles, decode_devices)
    _check_handles(handles)
    print(f"[smoke] served {len(handles)} requests in "
          f"{time.perf_counter() - t0} s (smoke timing, not a result)")

    compiles.phase = "serve alone"
    alone = svc.submit(prompts[4], max_new=MAX_NEW)
    svc.loop.run_until_idle()
    _check_handles([alone])
    for h in handles + [alone]:
        m = h.metrics
        print(f"[smoke] {h.request_id}: prompt={h.prompt_len} "
              f"prefill@{h.prefill_worker} decode@{h.decode_worker} "
              f"ttft={m.ttft_s}s ttlt={m.ttlt_s}s "
              "(smoke timings, not results)")

    if n_decode > 1:
        _check_placement(svc, workers, handles, decode_devices)

    compiles.phase = "reference"
    ref_prefill = jax.jit(model.prefill,
                          static_argnames=("max_blocks_margin", "remat"))
    ref_decode = jax.jit(model.decode_step)
    vocab = cfg.vocab_size
    for h, prompt in zip(handles + [alone], prompts):
        w = workers[h.prefill_worker]
        logits, _ = ref_prefill(
            w.params, {"tokens": jax.device_put(prompt[None], w.device)},
            max_blocks_margin=0, remat=False)
        want = int(jnp.argmax(logits[0, :vocab]))
        if h.tokens[0] != want:
            raise SmokeFailure(
                f"{h.request_id}: first token {h.tokens[0]} != direct "
                f"prefill argmax {want} on {w.device}")
    print("[smoke] first tokens match direct prefill for all "
          f"{len(handles) + 1} requests")

    dw = workers[alone.decode_worker]
    logits, state = ref_prefill(
        dw.params, {"tokens": jax.device_put(prompts[4][None], dw.device)},
        max_blocks_margin=-(-MAX_NEW // model.BLOCK_SIZE), remat=False)
    direct, steps = [], []
    while True:
        row = np.asarray(logits[0, :vocab].astype(jnp.float32))
        steps.append(row)
        direct.append(int(np.argmax(row)))
        if len(direct) > MAX_NEW:
            break
        tok = jax.device_put(np.asarray(direct[-1:], np.int32), dw.device)
        logits, state = ref_decode(dw.params, state, tok)
    served = alone.tokens[: MAX_NEW + 1]
    _compare_greedy(alone.request_id, served, direct, steps)


def _compare_greedy(rid: str, served: list[int], direct: list[int],
                    steps: list[np.ndarray]) -> None:
    diff = next((i for i, (a, b) in enumerate(zip(served, direct)) if a != b),
                None)
    if diff is None:
        print(f"[smoke] {rid}: served greedy tokens == direct loop "
              f"({len(served)} tokens)")
        return
    row = steps[diff]
    top2 = np.argsort(row)[-2:][::-1]
    margin = float(row[top2[0]] - row[top2[1]])
    ulp = _bf16_ulp(float(row[top2[0]]))
    print(f"[smoke] {rid}: first difference at step {diff}: served "
          f"{served[diff]}, direct {direct[diff]}; top-2 margin {margin} "
          f"vs bf16 step {ulp}")
    if margin > ulp or served[diff] not in top2:
        raise SmokeFailure(
            f"{rid}: served tokens {served} != direct {direct} (margin "
            f"{margin} above one bf16 step {ulp} at step {diff})")
    print(f"[smoke] {rid}: accepted: the direct loop's top-2 logits are "
          "within one bf16 rounding step there")


def _check_placement(svc, workers: dict, handles, decode_devices) -> None:
    param_devices = {wid: _device_of(w.params) for wid, w in workers.items()}
    if len(set(param_devices.values())) != len(workers):
        raise SmokeFailure(f"workers share a device: {param_devices}")
    for wid in svc.decodes:
        seen = decode_devices.get(wid, set())
        if seen != {param_devices[wid]}:
            raise SmokeFailure(
                f"decode worker {wid}: decode state on {seen}, "
                f"parameters on {param_devices[wid]}")
    used = {h.prefill_worker for h in handles} | {h.decode_worker for h in handles}
    print(f"[smoke] placement: {', '.join(f'{w}={d}' for w, d in param_devices.items())}; "
          f"workers that served requests: {sorted(used)}")
    if set(svc.decodes) - used:
        raise SmokeFailure(f"decode workers left idle: {set(svc.decodes) - used}")


if __name__ == "__main__":
    sys.exit(main())
