"""The worker layout of a cell: its layout file found by the cell's
name, the device trace reduced over every chip the workers use (to known
shares, and to today's names on one chip), and a run of two prefill and
two decode workers on four virtual CPU devices, each on a device of its
own, correct and with nothing compiled in the window."""
import json
import os
import subprocess
import sys

import pytest

import smoke
import devtrace
import flops
import peaks
import run
from repro.obs.trace import Span

# Two chips over a window of 1,000 ns.  d0: ops 0-100 (prefill) and
# 200-400 (decode), busy 300; d2: one decode op 100-600, busy 500.
PLANES = {
    "/device:TPU:0": {
        "ops": [(0, 100, "%fusion.1 = bf16[8] fusion(%p)"),
                (200, 400, "%fusion.2 = bf16[8] fusion(%q)")],
        "modules": [(0, 150, "jit_jit_prefill(1)"), (200, 400, "jit_jit_decode_step(2)")]},
    "/device:TPU:2": {
        "ops": [(100, 600, "%while.3 = (s32[]) while(%r)")],
        "modules": [(100, 600, "jit_jit_decode_step(3)")]},
}
HOST = [Span("tick", "loop", 0.0, 1000e-9), Span("step.build", ("worker", "d1"), 450e-9, 1000e-9)]


def _cell_root(tmp_path, layout=None):
    """A checkout with one cell, ``tiny.t``, and its layout file if given."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "layouts"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps({"model": {}}))
    (bench / "traffic" / "t.json").write_text(json.dumps(smoke.TRAFFIC))
    if layout is not None:
        (bench / "layouts" / "tiny.t.json").write_text(json.dumps(layout))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": "tiny.t", "config": "tiny", "traffic": "t", "chips": 4}],
        "end_to_end": [], "per_layer": []}))
    return tmp_path


@pytest.mark.parametrize("layout, workers", [
    (None, {"prefill": 1, "decode": 1}),
    ({"prefill": 2, "decode": 2}, {"prefill": 2, "decode": 2}),
    ({"prefill": 3, "decode": 1}, {"prefill": 3, "decode": 1}),
])
def test_the_layout_is_the_file_named_after_the_cell(tmp_path, layout, workers):
    spec = run.load_spec("tiny.t", _cell_root(tmp_path, layout))
    assert spec.workers == workers and "workers" not in spec.traffic


@pytest.mark.parametrize("layout", [{"prefill": 2}, {"prefill": 2, "decode": 0},
                                    {"prefill": 2, "decode": 2, "router": 1}])
def test_a_bad_layout_is_refused(tmp_path, layout):
    with pytest.raises(run.BenchError, match="tiny.t.json"):
        run.load_spec("tiny.t", _cell_root(tmp_path, layout))


def test_the_served_cell_has_the_default_layout():
    assert run.load_spec("yi9b.docqa").workers == {"prefill": 1, "decode": 1}


class _Dev:
    def __init__(self, i):
        self.id = i


def _reduced(monkeypatch, tmp_path, ids):
    monkeypatch.setattr(devtrace, "latest_xplane", lambda d: d)
    monkeypatch.setattr(devtrace, "read_profile",
                        lambda p: {"devices": PLANES, "clock_mark_ns": 0})
    r = run.Run(smoke.spec("yi"), 1, 1.0, True, require_tpu=False)
    r.devices = [_Dev(i) for i in ids]
    r.trace_dir, r.mark_t, r.trace_t0, r.trace_t1 = str(tmp_path / "t"), 0.0, 0.0, 1000e-9
    r.recs = {}

    class _Tracer:
        spans = HOST

    r.tracer = _Tracer()
    r.reduce_trace()
    return r.device_trace


def test_two_chips_sum_program_time_and_name_each_chip(monkeypatch, tmp_path):
    t = _reduced(monkeypatch, tmp_path, [0, 2])
    assert t["busy_s"] == pytest.approx(400e-9) and t["window_s"] == pytest.approx(1e-6)
    assert t["prefill_s"] == pytest.approx(150e-9)
    assert t["decode_s"] == pytest.approx(700e-9)
    assert t["breakdown"]["device_ops"] == [["d2:jit_decode_step/while.3", 500e-9],
                                            ["d0:jit_decode_step/fusion.2", 200e-9],
                                            ["d0:jit_prefill/fusion.1", 100e-9]]
    # idle: d0 100-200 and 400-1000, d2 0-100 and 600-1000
    assert t["breakdown"]["idle_gaps"] == [["d0:step.build", 600e-9], ["d2:step.build", 400e-9],
                                           ["d0:tick", 100e-9], ["d2:tick", 100e-9]]
    sizes = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
             "head_dim": 4, "d_ff": 16, "vocab_size": 32, "mlp_type": "swiglu"}
    calls = {"prefill": [16], "decode": [[10, 20], [11]]}
    ctx = run.LayerContext(requests=[], tracer=None, calls=calls, compiles={},
                           sizes=sizes, peak=peaks.peaks("TPU v5 lite"), flops=flops,
                           trace=t, t0=0.0, t1=1.0)
    bound = sum(flops.roofline_s(flops.decode_flops(sizes, c), flops.decode_bytes(sizes, c),
                                 ctx.peak) for c in calls["decode"])
    assert run._reader("device.idle_share").read(ctx) == pytest.approx(60.0)
    assert run._reader("decode_step_roofline").read(ctx) == pytest.approx(
        100 * bound / 700e-9)
    assert run._reader("prefill.mfu").read(ctx) == pytest.approx(
        100 * flops.prefill_flops(sizes, 16) / (150e-9 * 197e12))


def test_one_chip_keeps_the_plain_names(monkeypatch, tmp_path):
    t = _reduced(monkeypatch, tmp_path, [0])
    assert t["busy_s"] == pytest.approx(300e-9)
    assert (t["prefill_s"], t["decode_s"]) == (pytest.approx(150e-9), pytest.approx(200e-9))
    assert t["breakdown"] == {"device_ops": [["jit_decode_step/fusion.2", 200e-9],
                                             ["jit_prefill/fusion.1", 100e-9]],
                              "idle_gaps": [["step.build", 600e-9], ["tick", 100e-9]]}


def test_a_chip_missing_from_the_profile_is_an_error(monkeypatch, tmp_path):
    with pytest.raises(run.BenchError, match="TPU:1"):
        _reduced(monkeypatch, tmp_path, [0, 1])


CHILD = """
import json, sys
sys.path.insert(0, "bench/tests")
import smoke, run

def spec(chips, prefill, decode):
    s = smoke.spec("yi")
    s.cell["chips"] = chips
    s.workers = {"prefill": prefill, "decode": decode}
    return s

refused = []
for layout in ((4, 1, 1), (4, 3, 2)):
    try:
        run.Run(spec(*layout), 3, 1.5, False, require_tpu=False).build()
    except run.BenchError as e:
        refused.append(str(e))
r = run.Run(spec(4, 2, 2), 2**31 + 21, 1.5, True, require_tpu=False)
res = r.go()
print(json.dumps({"correct": res["correct"], "failed": res["failed"],
                  "attempted": res["attempted"], "compiles": r.window_compiles,
                  "where": r.placement, "decode_steps": r.calls["decode"],
                  "metrics": sorted(res["metrics"]), "refused": refused}))
"""


def test_two_prefill_and_two_decode_workers_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=run.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["compiles"].values()) == {0}
    assert sorted(out["where"]) == ["d0", "d1", "p0", "p1"]
    assert sorted(out["where"].values()) == [0, 1, 2, 3]
    for wid in ("d0", "d1", "p0", "p1"):
        assert f"{wid} on TFRT_CPU_{out['where'][wid]}" in p.stderr
    assert out["decode_steps"] and max(map(len, out["decode_steps"])) <= 4
    assert "decode.compiles" in out["metrics"]
    assert "the workers use 2 chip(s); the cell declares 4" in out["refused"][0]
    assert "puts two of its 5 workers on one chip" in out["refused"][1]
