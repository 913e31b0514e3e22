"""Reduction of a JAX profiler trace to device busy time, per-program
device time and the longest idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per program
run, named after the jitted function (``jit_jit_decode_step(...)``).
Times are nanoseconds on the profile's clock, which the host planes
share; ``clock_offset_ns`` maps the harness's ``time.perf_counter`` onto
it through a ``TraceAnnotation`` whose host time is known.

The reduction takes plain lists of ``(start_ns, end_ns, name)`` so that
tests can feed it a synthetic trace.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "bench.clock"


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end, ...)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e, *_ in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def clip(intervals, t0: int, t1: int) -> list:
    return [(max(s, t0), min(e, t1), *rest) for s, e, *rest in intervals
            if e > t0 and s < t1]


def gaps(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    """Idle stretches of ``[t0, t1]`` not covered by any interval."""
    out, cur = [], t0
    for s, e, *_ in sorted(clip(intervals, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def share_pct(part: float, whole: float, what: str) -> float | None:
    """``100 * part / whole``; None where there is nothing to read.  A
    share above 100% means the work was over-counted or the time
    under-counted, and raises."""
    if whole <= 0 or part <= 0:
        return None
    pct = 100.0 * part / whole
    if pct > 100.0:
        raise ValueError(f"{what}: {pct:.3f}% exceeds 100% "
                         f"({part} over {whole})")
    return pct


def module_seconds(modules, marker: str) -> float:
    """Device seconds of the program runs whose name contains ``marker``."""
    return sum(e - s for s, e, name in modules if marker in name) / 1e9


def op_name(text: str) -> str:
    """``%fusion.97 = bf16[...] fusion(...)`` -> ``fusion.97``."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_of(modules, t: int) -> str:
    """Name of the program run (``jit_jit_decode_step(123)`` ->
    ``jit_decode_step``) that covers time ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"))) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        name = modules[i][2].split("(", 1)[0]
        return name[4:] if name.startswith("jit_jit_") else name
    return "?"


def top_ops(ops, n: int = 10, modules=()) -> list[list]:
    """The ``n`` device operations that took most time, each named
    ``<program>/<operation>``."""
    modules = sorted(modules)
    acc: collections.Counter = collections.Counter()
    for s, e, name in ops:
        acc[f"{module_of(modules, s)}/{op_name(name)}" if modules else op_name(name)] += e - s
    return [[name, ns / 1e9] for name, ns in acc.most_common(n)]


def label_gaps(idle, host_spans, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps, each named by the innermost host span
    (``(start_ns, end_ns, name)``, same clock) that covers its midpoint."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        covering = [h for h in host_spans if h[0] <= mid < h[1]]
        name = min(covering, key=lambda h: h[1] - h[0])[2] if covering else "none"
        out.append([name, (e - s) / 1e9])
    return out


# -------------------------------------------------------- profile files
def latest_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def read_profile(path: str) -> dict:
    """Device ops and modules per device plane, and the ``CLOCK_MARK``
    start, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, dict[str, list]] = {}
    mark = None
    names = []
    for plane in data.planes:
        names.append(plane.name)
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {"ops": [], "modules": []}
            for key, line in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
                if line in lines:
                    dev[key] = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                                 ev.name) for ev in lines[line].events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == CLOCK_MARK:
                        mark = int(ev.start_ns)
    if not devices:
        raise ValueError(f"no /device:TPU plane in {path}; planes: {names}")
    return {"devices": devices, "clock_mark_ns": mark}
