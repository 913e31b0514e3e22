"""Trace reduction on a synthetic trace with known answers, and the
per-layer readers that read it."""
import pytest

import smoke  # noqa: F401
import devtrace
import flops
import peaks
import run


def test_union_and_gaps():
    ops = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 38, "d")]
    assert devtrace.union_ns(ops) == 30
    assert devtrace.gaps(ops, 0, 50) == [(20, 30), (40, 50)]
    assert devtrace.union_ns(devtrace.clip(ops, 8, 32)) == 14
    assert devtrace.top_ops(ops, 2) == [["b", 15e-9], ["a", 10e-9]]
    ops = [(0, 4, "%fusion.1 = bf16[8] fusion(%p)"), (6, 9, "%fusion.1 = bf16[8] fusion(%q)"),
           (12, 20, "%copy.2 = bf16[8] copy(%r)")]
    mods = [(0, 10, "jit_jit_decode_step(7)"), (11, 21, "jit_jit_prefill(3)")]
    assert devtrace.top_ops(ops, 5, mods) == [["jit_decode_step/fusion.1", 7e-9],
                                              ["jit_prefill/copy.2", 8e-9]][::-1]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    idle = [(20, 30), (40, 100)]
    host = [(0, 200, "tick"), (35, 120, "tick.step"), (22, 28, "tick.dispatch")]
    assert devtrace.label_gaps(idle, host) == [["tick.step", 60e-9],
                                               ["tick.dispatch", 10e-9]]


def _ctx(trace, calls):
    sizes = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
             "head_dim": 4, "d_ff": 16, "vocab_size": 32, "mlp_type": "swiglu"}
    return run.LayerContext(requests=[], tracer=None, calls=calls, compiles={},
                            sizes=sizes, peak=peaks.peaks("TPU v5 lite"),
                            flops=flops, trace=trace, t0=0.0, t1=1.0), sizes


def test_shares_reduce_to_known_values():
    calls = {"prefill": [16, 8], "decode": [[10, 20], [11]]}
    ctx, sizes = _ctx(None, calls)
    pf = flops.prefill_flops(sizes, 16) + flops.prefill_flops(sizes, 8)
    df = flops.decode_flops(sizes, [10, 20]) + flops.decode_flops(sizes, [11])
    bound = sum(flops.roofline_s(flops.decode_flops(sizes, c), flops.decode_bytes(sizes, c),
                                 ctx.peak) for c in calls["decode"])
    ctx.trace = {"busy_s": 0.75, "window_s": 1.0,
                 "prefill_s": pf / 197e12 * 2, "decode_s": bound * 4}
    assert run._reader("device.idle_share").read(ctx) == pytest.approx(25.0)
    assert run._reader("prefill.mfu").read(ctx) == pytest.approx(50.0)
    assert run._reader("decode_step_roofline").read(ctx) == pytest.approx(25.0)
    assert run._reader("decode.mfu").read(ctx) == pytest.approx(
        100 * df / (bound * 4 * 197e12))


def test_a_share_above_100_percent_raises():
    ctx, sizes = _ctx(None, {"prefill": [16], "decode": []})
    ctx.trace = {"busy_s": 1.0, "window_s": 1.0,
                 "prefill_s": flops.prefill_flops(sizes, 16) / 197e12 / 2, "decode_s": 0.0}
    with pytest.raises(ValueError, match="exceeds 100%"):
        run._reader("prefill.mfu").read(ctx)


def test_nothing_to_read_gives_no_value():
    ctx, _ = _ctx(None, {"prefill": [], "decode": []})
    for name in ("prefill.mfu", "decode.mfu", "decode_step_roofline", "device.idle_share"):
        assert run._reader(name).read(ctx) is None
    ctx.trace = {"busy_s": 1.0, "window_s": 1.0, "prefill_s": 0.0, "decode_s": 0.0}
    assert run._reader("prefill.mfu").read(ctx) is None
    assert run._reader("device.idle_share").read(ctx) is None
