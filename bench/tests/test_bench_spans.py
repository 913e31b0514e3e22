"""The readers of the spans inside the decode step, the prefill and the
KV pull: each on synthetic spans with known answers, each silent where
the program records no such span, idle gaps named by the innermost of
them, and all five read from a traced run at smoke sizes."""
import pytest

import smoke
import devtrace
import run
from repro.obs.trace import Span

NEW = ("decode.rebuild_p50_ms", "decode.rebuilds_per_req", "decode.launch_gap_p50_ms",
       "prefill.host_p50_ms", "transfer.copy_gbps")
D0, D1, P0 = ("worker", "d0"), ("worker", "d1"), ("worker", "p0")
ENGINE = ("engine", "tensor_centric")


def _ctx(spans, n_requests=4):
    class _Tracer:
        pass

    tr = _Tracer()
    tr.spans = spans
    return run.LayerContext(requests=[{}] * n_requests, tracer=tr, calls={}, compiles={},
                            sizes={}, peak=None, flops=None, trace=None, t0=0.0, t1=100.0)


def _s(name, track, t0, t1, **attrs):
    return Span(name, track, t0, t1, attrs=attrs)


def _decode_spans():
    """Two workers.  d0: three steps, commits ending at 1.0, 2.0, 3.0
    and launches at 0.5, 1.002, 2.004, 3.010 (gaps 2, 4, 10 ms); a join
    rebuild of 0.8 s and a leave rebuild of 1.2 s.  d1: one step pair
    (gap 6 ms) and a margin rebuild of 0.2 s.  A span before the window
    is not read."""
    out = [_s("step.rebuild", D0, -5.0, -4.0, reason="join")]
    for t in (0.5, 1.002, 2.004, 3.010):
        out.append(_s("step.launch", D0, t, t + 0.001))
    for t in (1.0, 2.0, 3.0):
        out.append(_s("step.commit", D0, t - 0.2, t))
    out += [_s("step.rebuild", D0, 4.0, 4.8, reason="join"),
            _s("step.rebuild", D0, 6.0, 7.2, reason="leave"),
            _s("step.launch", D1, 10.0, 10.001), _s("step.commit", D1, 10.1, 10.2),
            _s("step.launch", D1, 10.206, 10.207),
            _s("step.rebuild", D1, 11.0, 11.2, reason="margin")]
    return out


def test_decode_readers_reduce_to_known_values():
    ctx = _ctx(_decode_spans())
    assert run._reader("decode.rebuild_p50_ms").read(ctx) == pytest.approx(800.0)
    assert run._reader("decode.rebuilds_per_req").read(ctx) == pytest.approx(3 / 4)
    # gaps 2, 4, 10 (d0) and 6 (d1) ms: the median is 5
    assert run._reader("decode.launch_gap_p50_ms").read(ctx) == pytest.approx(5.0)


def test_a_window_without_rebuilds_reads_zero_rebuilds():
    spans = [s for s in _decode_spans() if s.name != "step.rebuild"]
    assert run._reader("decode.rebuilds_per_req").read(_ctx(spans)) == 0.0
    assert run._reader("decode.rebuild_p50_ms").read(_ctx(spans)) is None


def test_prefill_host_time_sums_park_hash_and_quant_per_prefill():
    spans = [
        # a prefill whose compute began before the window: not counted
        _s("prefill.compute", P0, -1.0, -0.5), _s("prefill.park", P0, -0.5, -0.2),
        _s("prefill.compute", P0, 1.0, 1.5), _s("prefill.park", P0, 1.5, 1.7),
        _s("prefill.hash", P0, 1.7, 1.8),
        _s("prefill.compute", P0, 2.0, 2.5), _s("prefill.park", P0, 2.5, 2.9),
        _s("prefill.hash", P0, 2.9, 3.0), _s("prefill.quant", P0, 3.0, 3.1),
        _s("prefill.compute", ("worker", "p1"), 2.0, 2.5),
        _s("prefill.park", ("worker", "p1"), 2.5, 2.6),
        _s("step.commit", D0, 1.0, 9.0),  # another worker's span: not read
    ]
    # per prefill: 300, 600 and 100 ms
    assert run._reader("prefill.host_p50_ms").read(_ctx(spans)) == pytest.approx(300.0)


def test_copy_rate_is_bytes_over_copy_time():
    spans = [_s("transfer.copy", ENGINE, 1.0, 1.01, reads=32, bytes=30_000_000),
             _s("transfer.copy", ENGINE, 2.0, 2.03, reads=32, bytes=50_000_000),
             _s("transfer.copy", ("request", "r0"), 3.0, 4.0, bytes=10**12)]
    assert run._reader("transfer.copy_gbps").read(_ctx(spans)) == pytest.approx(2.0)


def test_nothing_to_read_gives_no_value():
    """A program that records none of these spans (the parent of the
    change that adds them) reads nothing, and no reader raises."""
    for spans in ([], [_s("tick.step", D0, 1.0, 2.0), _s("prefill", ("request", "r0"), 0, 1)]):
        for name in NEW:
            assert run._reader(name).read(_ctx(spans)) is None
    ctx = _ctx([])
    ctx.tracer = None
    for name in NEW:
        assert run._reader(name).read(ctx) is None


def test_a_gap_inside_a_rebuild_is_named_by_its_leaf_span():
    idle = [(100, 300), (400, 420), (500, 530)]
    host = [(0, 1000, "tick"), (50, 900, "tick.step"), (60, 350, "step.rebuild"),
            (65, 120, "step.writeback"), (120, 340, "step.build"),
            (395, 405, "step.launch"), (405, 800, "step.commit"),
            (380, 440, "jax.compile"), (520, 525, "transfer.copy")]
    assert devtrace.label_gaps(idle, host) == [["step.build", 200e-9],
                                               ["step.commit", 30e-9],
                                               ["jax.compile", 20e-9]]


def test_a_traced_run_reads_every_new_metric():
    res = run.Run(smoke.spec("yi"), 2**31 + 7, 1.5, True, require_tpu=False).go()
    assert res["correct"] is True
    vals = {name: res["metrics"].get(name, {}).get("value") for name in NEW}
    assert all(v is not None and v > 0 for v in vals.values()), vals
