#!/usr/bin/env python3
"""Chip benchmark: one cell of ``BENCHMARK.json`` served through
``DisaggService`` on TPUs.

    python3 bench/run.py --workload yi9b.docqa --seed 7 --seconds 51 --trace 0

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); per-layer metrics are
read by ``bench/metrics/<metric>.py``.  All three are found by name, so
a later cell, configuration or metric is a new file and an entry in
``BENCHMARK.json``.  The configuration's ``model`` block is passed to
the program's ``ModelConfig`` key by key (``model_config``), and the
configuration names the two modules of ``bench/`` that stand for the
model in the harness: its plain reference (``"reference"``) and its
operation and byte counts (``"counts"``); ``load_module`` states what
each must provide.

A cell's worker layout is ``bench/layouts/<cell>.json``, found by the
cell's name: ``{"prefill": P, "decode": D}``, one of each without it.
``DisaggService`` puts worker ``k`` on device ``k mod n``; the run logs
each worker's device, and a cell of more than one chip must put every
worker on a chip of its own and use every chip it declares.

Set-up (timed as ``setup_s``): weights made on the device from
``--seed``, the service built, every prompt length of the schedule
warmed on every prefill worker's chip and every decode (batch, pages)
shape it can reach on every decode worker's chip, then ``ramp_s`` of
the cell's own traffic.  Then ``--seconds`` of open-loop arrivals are
measured, and the drain waits for the window's requests
while arrivals continue.  A request is timed from when it was due.
``--trace 1`` adds the span tracer and the JAX profiler and prints the
per-layer metrics instead of the end-to-end ones; the device trace is
read over every chip of the cell.

After the drain the service is dropped, and a sample of the window's
requests (the longest among them) is replayed through the configuration's
plain float32 reference: ``correct`` holds when every served token's
reference logit lies within the configuration's limit of the reference's
best.  With ``control=True`` (``bench/control.py``) the same comparison
reads the control instead: the reference in int8, put in the program's
place.
The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

import workload  # noqa: E402


class BenchError(RuntimeError):
    """The cell cannot be run as specified."""


# Keys of a configuration's ``model`` block that ``ModelConfig`` lacks and
# the harness reads itself; every other key is a ``ModelConfig`` field.
HARNESS_KEYS = ("context_length",)

DEFAULT_WORKERS = {"prefill": 1, "decode": 1}

# Warm-up zeros up to this size come from the host: a device zero is one
# compiled program per shape, and the small leaves (lengths, block
# tables) take a shape for every (batch, pages) warmed.
HOST_ZEROS_BYTES = 1 << 20


@dataclasses.dataclass
class Spec:
    cell: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: Path = BENCH  # where the configuration's modules are found
    workers: dict = dataclasses.field(default_factory=lambda: dict(DEFAULT_WORKERS))

    @property
    def sizes(self) -> dict:
        return self.config["model"]

    @property
    def reference(self):
        return load_module(self.bench_dir, self.config["reference"])

    @property
    def counts(self):
        return load_module(self.bench_dir, self.config["counts"])


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(name: str, root: Path = ROOT) -> Spec:
    bench = _json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    metrics = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
    layers = [m for m in bench["per_layer"] if name in m.get("workloads", [name])]
    return Spec(cell, _json(root / cfg["file"]),
                _json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
                metrics, layers, root / "bench",
                load_layout(root / "bench" / "layouts" / f"{name}.json"))


def load_layout(path: Path) -> dict:
    """A cell's prefill and decode workers: its layout file, or one of
    each where it has none."""
    if not path.is_file():
        return dict(DEFAULT_WORKERS)
    layout = _json(path)
    if set(layout) != set(DEFAULT_WORKERS) or not all(
            isinstance(n, int) and n >= 1 for n in layout.values()):
        raise BenchError(f"{path.name}: a layout is a count of at least 1 for each "
                         f"of {sorted(DEFAULT_WORKERS)}; got {layout}")
    return layout


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def model_config(name: str, block: dict):
    """The program's ``ModelConfig`` from a configuration's ``model``
    block: every key but ``HARNESS_KEYS`` is a field of that name, lists
    become tuples (the config is frozen and hashed).  A key that is no
    field is refused by name, never dropped."""
    from repro.models.config import ModelConfig

    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kwargs = {"name": name}
    for key, value in block.items():
        if key in HARNESS_KEYS:
            continue
        if key not in fields:
            raise BenchError(f"model key {key!r} is not a ModelConfig field "
                             f"(nor one of the harness's own: {HARNESS_KEYS})")
        kwargs[key] = _frozen(value)
    return ModelConfig(**kwargs)


_MODULES: dict[Path, object] = {}


def load_module(bench_dir: Path, name: str):
    """``<bench_dir>/<name>.py``, loaded once per process (so its jitted
    functions keep their caches).  A configuration names two:

    - its reference, ``logits(params, sizes, seq, rows, precision="float32")``:
      float32 logits ``[len(rows), vocab]`` of the plain causal forward pass
      over the token ids ``seq``, at positions ``rows``, from the served
      parameter tree ``params`` and the configuration's ``model`` block
      ``sizes``; ``precision="int8"`` is the control, the same pass a step
      below the served precision.  It imports nothing of the program, and
      may import helpers from ``reference.py``.
    - its counts, from shapes alone: ``prefill_flops(sizes, n)`` for one
      prompt of ``n`` tokens, ``decode_flops(sizes, contexts)`` and
      ``decode_bytes(sizes, contexts)`` for one decode step whose sequences
      attend over ``contexts`` keys each, and ``roofline_s(flops, nbytes,
      peak)``, the least time the chip could take."""
    path = (bench_dir / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise BenchError(f"no module {name!r} in {bench_dir}")
        spec = importlib.util.spec_from_file_location(f"bench_module_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def pct(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


# ------------------------------------------------------------ the run
class Run:
    """One run of one cell.  ``require_tpu=False`` lets the CPU tests
    drive every step but the look for a chip."""

    def __init__(self, spec: Spec, seed: int, seconds: float, trace: bool,
                 *, require_tpu: bool = True, t_start: float | None = None,
                 model=None, control: bool = False):
        self.spec, self.seed, self.seconds, self.trace = spec, seed, seconds, trace
        self.require_tpu, self.control = require_tpu, control
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.model = model  # reusing one model keeps the jit caches warm

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    # -------------------------------------------------------- set-up
    def build(self) -> None:
        import jax

        from repro.models.registry import build_model
        from repro.obs.trace import Tracer
        from repro.serving.disagg import DisaggService

        import weights

        s = self.spec.sizes
        if self.model is None:
            self.model = build_model(model_config(self.spec.cell["config"], s))
        self.cfg = self.model.cfg
        shapes = jax.eval_shape(self.model.init_params, jax.random.PRNGKey(0))
        self.params = weights.make(shapes, self.seed)
        jax.block_until_ready(self.params)
        self.tracer = Tracer(clock=time.perf_counter) if self.trace else None
        w = self.spec.workers
        self.svc = DisaggService(self.model, self.params, n_prefill=w["prefill"],
                                 n_decode=w["decode"],
                                 num_blocks=self.spec.config["num_blocks"],
                                 tracer=self.tracer)
        self.devices = self.layout()
        self.sched = workload.schedule(self.spec.traffic, self.seconds)
        ctx = s["context_length"]
        for a in self.sched:
            if a.prompt_len + a.max_new > ctx:
                raise BenchError(f"request of {a.prompt_len}+{a.max_new} tokens "
                                 f"exceeds the context {ctx}")
        rng = np.random.default_rng([self.seed % 2**64, 99])
        self.prompts = [rng.integers(0, s["vocab_size"], a.prompt_len).astype(np.int32)
                        for a in self.sched]

    def layout(self) -> list:
        """The chips the workers compute on, in device order; refuses a
        layout that does not match the cell's ``chips``."""
        where = {wid: w.device for pool in (self.svc.prefills, self.svc.decodes)
                 for wid, w in pool.items()}
        self.placement = {wid: d.id for wid, d in where.items()}
        self.log("workers: " + ", ".join(f"{wid} on {d}" for wid, d in where.items()))
        devices = sorted(set(where.values()), key=lambda d: d.id)
        chips = self.spec.cell["chips"]
        if chips > 1 and len(devices) < len(where):
            raise BenchError(f"a cell of {chips} chips puts two of its "
                             f"{len(where)} workers on one chip")
        if len(devices) != chips:
            raise BenchError(f"the workers use {len(devices)} chip(s); the cell "
                             f"declares {chips}")
        return devices

    def decode_shapes(self) -> list[tuple[int, int]]:
        """Every (batch, pages) state a decode worker can build for this
        schedule: pages are the largest member's valid pages plus the
        worker's margin, and a member spans from its prompt's blocks to
        its last decode input (``prompt + max_new - 1`` positions)."""
        bs = self.model.BLOCK_SIZE
        margin = self._margin()
        pages = set()
        for p, o in workload.all_sizes(self.spec.traffic, self.seconds):
            lo, hi = -(-p // bs), -(-(p + o - 1) // bs)
            pages.update(range(lo + margin, hi + margin + 1))
        bmax = self.spec.traffic["warm_batch_max"]
        return [(b, n) for b in range(1, bmax + 1) for n in sorted(pages)]

    def _margin(self) -> int:
        return next(iter(self.svc.decodes.values())).step_margin_blocks

    def warm(self) -> None:
        """Compile (or load from the persistent cache) every program the
        window can call, the way the engine calls it, on every worker's
        chip: a program compiled for one chip is not the one another runs.
        Decode states are the model's own ``decode_state_shape``, zeroed:
        a leaf of up to ``HOST_ZEROS_BYTES`` is put from the host (no
        program), a larger one is made on the chip once per shape and
        type, so k and v share one buffer."""
        import jax
        import jax.numpy as jnp

        from repro.serving.engine import jit_decode_step, jit_prefill

        V = self.cfg.vocab_size
        lengths = sorted({p for p, _ in workload.all_sizes(self.spec.traffic, self.seconds)})
        for pw in _on_distinct_devices(self.svc.prefills):
            for n in lengths:
                logits, st = jit_prefill(self.model, pw.params,
                                         jax.device_put(np.zeros((1, n), np.int32), pw.device))
                for leaf in jax.tree.leaves(st):  # each sequence's pages, as parked
                    np.asarray(leaf[:, 0] if leaf.ndim > 2 else leaf)
                int(jnp.argmax(logits[0, :V]))
        shapes = self.decode_shapes()
        bs, margin = self.model.BLOCK_SIZE, self._margin()
        for dw in _on_distinct_devices(self.svc.decodes):
            dev = dw.device
            for b, per in shapes:
                zeros = {}

                def zero(x):
                    key = (x.shape, x.dtype)
                    if key not in zeros:
                        small = np.prod(x.shape) * x.dtype.itemsize <= HOST_ZEROS_BYTES
                        zeros[key] = (jax.device_put(np.zeros(x.shape, x.dtype), dev)
                                      if small else jnp.zeros(x.shape, x.dtype, device=dev))
                    return zeros[key]

                st = jax.tree.map(zero, self.model.decode_state_shape(
                    b, (per - margin) * bs, margin=margin))
                logits, new = jit_decode_step(self.model, dw.params, st,
                                              jax.device_put(np.zeros(b, np.int32), dev))
                toks = jnp.argmax(logits[:, :V].astype(jnp.float32), axis=-1).astype(jnp.int32)
                np.asarray(toks).tolist()
                del st, new, zeros
        self.log(f"warmed {len(lengths)} prompt lengths and {len(shapes)} decode "
                 f"shapes (batch <= {self.spec.traffic['warm_batch_max']}) on each "
                 f"worker's chip")

    # -------------------------------------------------------- driving
    def _submit(self, i: int) -> None:
        a = self.sched[i]
        h = self.svc.submit(self.prompts[i], max_new=a.max_new, dispatch="queued")
        self.recs[i] = {"handle": h, "submitted": time.perf_counter(),
                        "due": self.t0 + a.due_s}
        self.by_rid[h.request_id] = i

    def _drive_until(self, done) -> None:
        sched = self.sched
        while True:
            now = time.perf_counter()
            while self.next < len(sched) and self.t0 + sched[self.next].due_s <= now:
                self._submit(self.next)
                self.next += 1
            if done(now):
                return
            if not self.svc.handles:
                due = self.t0 + sched[self.next].due_s if self.next < len(sched) else now + 0.01
                time.sleep(max(0.0, min(due - now, 0.01)))
                continue
            report = self.svc.loop.tick()
            if self.recording:
                self._record(report)

    def _record(self, report) -> None:
        """Shapes of the programs this tick ran, for the FLOP counts: each
        prefill, and each decode worker's step (one per worker with
        tokens this tick)."""
        for rid in report.dispatched:
            self.calls["prefill"].append(self.sched[self.by_rid[rid]].prompt_len)
        steps: dict[str, list[int]] = {}
        for rid in report.tokens:
            i = self.by_rid[rid]
            h = self.recs[i]["handle"]
            steps.setdefault(h.request.decode_worker, []).append(
                self.sched[i].prompt_len + len(h.tokens) - 1)
        for ctx in steps.values():
            self.calls["decode"].append(ctx)
            self.max_batch = max(self.max_batch, len(ctx))

    def serve(self) -> None:
        import jax

        from repro.serving.engine import jit_decode_step, jit_prefill

        self.recs: dict[int, dict] = {}
        self.by_rid: dict[str, int] = {}
        self.calls = {"prefill": [], "decode": []}
        self.next, self.recording, self.max_batch = 0, False, 0
        ramp = self.spec.traffic["ramp_s"]
        self.t0 = time.perf_counter()
        self.w0, self.w1 = self.t0 + ramp, self.t0 + ramp + self.seconds
        self._drive_until(lambda now: now >= self.w0)
        self.setup_s = time.perf_counter() - self.t_start

        counts = _ProgramCounter()
        cache0 = (_programs(jit_prefill), _programs(jit_decode_step))
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.mark_t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.clock"):
                pass
        self.recording = True
        self.trace_t0 = time.perf_counter()
        window = [i for i, a in enumerate(self.sched) if a.phase == "window"]
        self._drive_until(lambda now: now >= self.w1)
        cap = self.w1 + self.spec.traffic["drain_max_s"]
        self._drive_until(lambda now: now >= cap or all(
            i in self.recs and self.recs[i]["handle"].finished for i in window))
        self.drain_end = time.perf_counter()
        self.recording = False
        if self.trace:
            jax.profiler.stop_trace()
        self.trace_t1 = time.perf_counter()
        counts.close()
        self.window_compiles = {
            "jit_prefill": _programs(jit_prefill) - cache0[0],
            "jit_decode_step": _programs(jit_decode_step) - cache0[1],
            "traced_programs": counts.traces, "backend_compiles": counts.compiles}
        self.window = window
        self.log("programs added inside the window and drain: " + ", ".join(
            f"{k} {v}" for k, v in self.window_compiles.items()))
        self.log(f"largest decode batch of a worker in the window and drain: {self.max_batch} "
                 f"(warmed up to {self.spec.traffic['warm_batch_max']}); "
                 f"{len(self.calls['decode'])} decode steps, "
                 f"{len(self.calls['prefill'])} prefills")

    # -------------------------------------------------------- results
    def requests(self) -> list[dict]:
        out = []
        for i in self.window:
            a, r = self.sched[i], self.recs.get(i)
            h = r["handle"] if r else None
            ok = h is not None and h.done and len(h.tokens) == a.max_new + 1
            m = h.metrics if h is not None else None
            end = self.drain_end
            out.append({
                "index": i, "prompt_len": a.prompt_len, "max_new": a.max_new,
                "due": self.t0 + a.due_s, "ok": ok,
                "submitted": r["submitted"] if r else end,
                "first": (m.first_token_at if m and m.first_token_at else end),
                "last": m.last_token_at if ok else end,
                "token_times": list(m.token_times) if m else [],
                "kv_bytes_pulled": m.kv_bytes_pulled if m else 0,
                "rid": h.request_id if h else None,
            })
        return out

    def end_to_end(self, reqs: list[dict]) -> dict[str, float]:
        ttft = [r["first"] - r["due"] for r in reqs]
        lat = [r["last"] - r["due"] for r in reqs]
        gaps = [b - a for r in reqs for a, b in zip(r["token_times"], r["token_times"][1:])]
        out_tokens = sum(1 for r in self.recs.values()
                         for t in r["handle"].metrics.token_times[1:]
                         if self.w0 <= t < self.w1)
        self.log(f"window: {len(reqs)} requests due, {len(gaps)} token gaps, "
                 f"{out_tokens} output tokens; ttft p50 {pct(ttft, 50)} s, "
                 f"latency p50 {pct(lat, 50)} s, gap p50 {pct(gaps, 50)} s; "
                 f"ttft p90 {pct(ttft, 90)} s, latency p90 {pct(lat, 90)} s, "
                 f"gap p99 {pct(gaps, 99)} s")
        slo = self.spec.config["slo"]
        met = sum(1 for r in reqs if r["ok"] and r["first"] - r["due"] <= slo["ttft_s"]
                  and _mean_gap(r["token_times"]) <= slo["tbt_mean_s"])
        self.log(f"slo attainment: {met}/{len(reqs)} = {met / max(1, len(reqs))} "
                 f"(ttft <= {slo['ttft_s']} s and mean tbt <= {slo['tbt_mean_s']} s)")
        self.tails = {"req_latency_p90_s": pct(lat, 90), "ttft_p90_s": pct(ttft, 90),
                      "slo_attainment": met / max(1, len(reqs))}
        return {"tbt_p50_ms": 1e3 * pct(gaps, 50),
                "output_tok_per_s": out_tokens / self.seconds,
                "setup_s": self.setup_s}

    def memory_peak(self) -> int | None:
        """Peak bytes in use on the fullest of the workers' chips."""
        peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices}
        self.log(f"peak bytes in use by chip: {peaks}")
        peaks = [p for p in peaks.values() if p is not None]
        return max(peaks) if peaks else None

    def check(self, reqs: list[dict]) -> dict:
        """Replay a sample of the window's finished requests through the
        reference; the widest gap of a served token below the best (of the
        control's first token, with ``control``)."""
        done = [r for r in reqs if r["ok"]]
        if not done:  # nothing to compare: the result is not correct
            return {"max_logit_gap": None, "requests": 0, "tokens": 0}
        k = min(self.spec.traffic["check_requests"], len(done))
        longest = max(done, key=lambda r: r["prompt_len"] + r["max_new"])
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([self.seed % 2**64, 5])
        picks = [longest] + [rest[j] for j in rng.choice(len(rest), k - 1, replace=False)]
        self.check_picks, self.check_detail = picks, []
        widest, tokens = 0.0, 0
        for r in picks:
            served = np.asarray(self.served[r["index"]], np.int64)
            d = self.gaps_of(r["index"], served)
            self.check_detail.append(d)
            widest = max(widest, d["control_gap" if self.control else "program_gap"])
            tokens += len(served)
        return {"max_logit_gap": widest, "requests": len(picks), "tokens": tokens}

    def gaps_of(self, i: int, served: np.ndarray) -> dict:
        """Reference logit gaps at every position of request ``i`` (first
        token included): the widest of the reference's best minus its logit
        of the served token, with what explains it (the reference's median
        top-2 margin, the distinct tokens served).  With ``control``, also
        the widest gap of the token the int8 reference puts first."""
        reference = self.spec.reference
        p = self.prompts[i]
        seq = np.concatenate([p, served[:-1]]).astype(np.int32)
        rows = np.arange(len(p) - 1, len(p) - 1 + len(served))
        ref = reference.logits(self.params, self.spec.sizes, seq, rows)
        at = np.arange(len(served))
        two = np.sort(ref, axis=1)[:, -2:]
        out = {"program_gap": float(np.max(ref.max(axis=1) - ref[at, served])),
               "margin_p50": float(np.median(two[:, 1] - two[:, 0])),
               "distinct_served": len(set(served.tolist())), "tokens": len(served)}
        if self.control:
            top = reference.logits(self.params, self.spec.sizes, seq, rows,
                                   "int8").argmax(axis=1)
            out["control_gap"] = float(np.max(ref.max(axis=1) - ref[at, top]))
        return out

    def per_layer(self, reqs: list[dict]) -> dict[str, float]:
        import peaks

        import jax

        ctx = LayerContext(
            requests=reqs, tracer=self.tracer, calls=self.calls,
            compiles=self.window_compiles, sizes=self.spec.sizes,
            peak=peaks.peaks(jax.devices()[0].device_kind) if self.require_tpu else None,
            flops=self.spec.counts, trace=self.device_trace,
            t0=self.trace_t0, t1=self.trace_t1)
        out = {}
        for m in self.spec.per_layer:
            v = _reader(m["name"]).read(ctx)
            if v is not None:
                out[m["name"]] = v
        return out

    def reduce_trace(self) -> None:
        """Device busy time, per-program device time and idle gaps of the
        traced window, from the profiler's file, over every chip the
        workers use: busy time is the mean over the chips, program times
        are summed over them, and the top operations and idle gaps are
        taken from all of them, each named with its chip (``d2:``) where
        the cell has more than one."""
        import devtrace

        prof = devtrace.read_profile(devtrace.latest_xplane(self.trace_dir))
        mark = prof["clock_mark_ns"]
        if mark is None:
            raise BenchError("the profile has no bench.clock mark")
        off = mark - int(self.mark_t * 1e9)
        t0, t1 = int(self.trace_t0 * 1e9) + off, int(self.trace_t1 * 1e9) + off
        planes = {f"/device:TPU:{d.id}": d.id for d in self.devices}
        missing = sorted(set(planes) - set(prof["devices"]))
        if missing:
            raise BenchError(f"the profile has no plane {missing}; planes: "
                             f"{sorted(prof['devices'])}")
        host = [(int(s.t0 * 1e9) + off, int(s.t1 * 1e9) + off, s.name)
                for s in (self.tracer.spans if self.tracer else []) if s.t1 is not None]
        host += [(int(self.recs[i]["submitted"] * 1e9) + off,
                  int(self.recs[i]["submitted"] * 1e9) + off + 1, "submit")
                 for i in self.recs]
        busy, prefill_s, decode_s, top, idle, seen = [], 0.0, 0.0, [], [], set()
        for plane, dev_id in planes.items():
            ops = devtrace.clip(prof["devices"][plane]["ops"], t0, t1)
            all_mods = prof["devices"][plane]["modules"]
            mods = devtrace.clip(all_mods, t0, t1)
            tag = f"d{dev_id}:" if len(planes) > 1 else ""
            busy.append(devtrace.union_ns(ops) / 1e9)
            prefill_s += devtrace.module_seconds(mods, "jit_prefill")
            decode_s += devtrace.module_seconds(mods, "jit_decode_step")
            top += [[tag + n, v] for n, v in devtrace.top_ops(ops, modules=all_mods)]
            idle += [[tag + n, v] for n, v in devtrace.label_gaps(
                devtrace.gaps(ops, t0, t1), host)]
            seen |= {tag + n.split("(")[0] for _, _, n in mods}
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.device_trace = {
            "busy_s": sum(busy) / len(busy), "window_s": (t1 - t0) / 1e9,
            "prefill_s": prefill_s, "decode_s": decode_s,
            "breakdown": {"device_ops": sorted(top, key=lambda e: -e[1])[:10],
                          "idle_gaps": sorted(idle, key=lambda e: -e[1])[:10]},
        }
        self.log(f"trace: busy {self.device_trace['busy_s']} s of "
                 f"{self.device_trace['window_s']} s (by chip: {busy}); prefill "
                 f"programs {prefill_s} s, decode programs {decode_s} s; modules "
                 f"seen: {sorted(seen)[:8]}")

    def go(self) -> dict:
        import jax

        self.build()
        t_built = time.perf_counter()
        self.warm()
        t_warm = time.perf_counter()
        self.serve()
        self.log(f"set-up {self.setup_s} s: start to weights and service "
                 f"{t_built - self.t_start} s, warm-up {t_warm - t_built} s, "
                 f"ramp {self.w0 - t_warm} s")
        reqs = self.requests()
        e2e = self.end_to_end(reqs)
        peak = self.memory_peak()
        self.served = {r["index"]: list(self.recs[r["index"]]["handle"].tokens)
                       for r in reqs if r["ok"]}
        self.device_trace = None
        if self.trace:
            if self.require_tpu:
                self.reduce_trace()
            layers = self.per_layer(reqs)
        del self.svc, self.recs
        gc.collect()
        t = time.perf_counter()
        chk = self.check(reqs)
        limit = self.spec.config["correct"]["max_logit_gap"]
        failed = sum(1 for r in reqs if not r["ok"])
        correct = (failed == 0 and limit is not None
                   and chk["max_logit_gap"] is not None and chk["max_logit_gap"] <= limit)
        self.log(f"check: {chk['requests']} requests, {chk['tokens']} served "
                 f"tokens against the reference in {time.perf_counter() - t} s; "
                 f"per request: {getattr(self, 'check_detail', [])}")
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}
        if self.trace:
            metrics = {m["name"]: {"value": layers[m["name"]], "unit": m["unit"]}
                       for m in self.spec.per_layer if m["name"] in layers}
            if self.device_trace:
                device["busy_s"] = self.device_trace["busy_s"]
                device["window_s"] = self.device_trace["window_s"]
        else:
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in self.spec.end_to_end}
        result = {"correct": bool(correct), "attempted": len(reqs), "failed": failed,
                  "metrics": metrics, "device": device}
        if self.device_trace:
            result["breakdown"] = self.device_trace["breakdown"]
        result["check"] = {"max_logit_gap": {"value": chk["max_logit_gap"], "limit": limit},
                           "failed_requests": {"value": failed, "limit": 0}}
        for name, c in result["check"].items():
            print(f"[check] {name} {c['value']} limit {c['limit']}", file=sys.stderr)
        return result


def _on_distinct_devices(workers: dict) -> list:
    """One worker per device among ``workers`` (workers that share a
    device share its compiled programs)."""
    return list({w.device: w for w in workers.values()}.values())


def _programs(jitted) -> int:
    """Programs in a jitted function's in-memory cache (0 for a plain
    function standing in for it)."""
    return jitted._cache_size() if hasattr(jitted, "_cache_size") else 0


def _mean_gap(times) -> float:
    return (times[-1] - times[0]) / (len(times) - 1) if len(times) > 1 else 0.0


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read: the window's requests, the span
    tracer, the shapes of the programs run, the programs added in the
    window, the model's sizes, the chip's peaks, the FLOP/byte counts and
    the reduced device trace."""

    requests: list
    tracer: object
    calls: dict
    compiles: dict
    sizes: dict
    peak: dict | None
    flops: object
    trace: dict | None
    t0: float          # the recorded span: window and drain, host clock
    t1: float

    def spans(self, name: str | None = None, track_kind: str | None = None) -> list:
        """Closed spans that started inside the recorded span."""
        if self.tracer is None:
            return []
        return [s for s in self.tracer.spans
                if s.t1 is not None and self.t0 <= s.t0 < self.t1
                and (name is None or s.name == name)
                and (track_kind is None or (isinstance(s.track, tuple)
                                            and s.track[0] == track_kind))]


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _ProgramCounter:
    """Counts programs traced and XLA backend compiles, from JAX's own
    monitoring events, while open."""

    def __init__(self) -> None:
        import jax

        self.traces = self.compiles = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self)


def main(argv: list[str] | None = None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        import jax

        from repro.launch.compile_cache import use_compilation_cache
    except (BenchError, OSError, KeyError, ImportError) as e:
        print(f"bench: cannot start: {e!r}", file=sys.stderr)
        return 2
    cache = use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.cell["chips"]:
        print(f"bench: needs {spec.cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    print(f"[bench] {args.workload}: {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {cache}", file=sys.stderr)
    result = Run(spec, args.seed, args.seconds, bool(args.trace),
                 t_start=t_start).go()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
