"""The schedule generator: deterministic, the same work for every seed,
and within each cell's clips and context; the seed draws the tokens."""
import collections
import json

import numpy as np
import pytest

import smoke  # noqa: F401
import run
import workload

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def _spec(name):
    return run.load_spec(name)


@pytest.mark.parametrize("cell", CELLS)
def test_the_schedule_is_fixed_and_the_seed_draws_the_tokens(cell):
    t = _spec(cell).traffic
    a = workload.schedule(t, 51)
    assert a == workload.schedule(dict(t), 51)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    runs = [run.Run(_spec(cell), seed, 51, False) for seed in (3, 2**31 + 12345, 3)]
    toks = []
    for r in runs:
        r.sched = a
        rng = np.random.default_rng([r.seed % 2**64, 99])
        toks.append(rng.integers(0, 10, 5).tolist())
    assert toks[0] == toks[2] != toks[1]


@pytest.mark.parametrize("cell", CELLS)
def test_the_window_holds_rate_times_seconds(cell):
    t = _spec(cell).traffic
    a = workload.schedule(t, 51)
    assert collections.Counter(x.phase for x in a)["window"] == round(t["rate_per_s"] * 51)
    win = [x for x in a if x.phase == "window"]
    assert len(win) == round(t["rate_per_s"] * 51)
    assert all(t["ramp_s"] <= x.due_s < t["ramp_s"] + 51 for x in win)


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_respect_clips_and_context(cell):
    spec = _spec(cell)
    t, ctx = spec.traffic, spec.sizes["context_length"]
    for x in workload.schedule(t, 51):
        assert t["prompt_tokens"]["min"] <= x.prompt_len <= t["prompt_tokens"]["max"]
        assert t["output_tokens"]["min"] <= x.max_new <= t["output_tokens"]["max"]
        assert x.prompt_len + x.max_new <= ctx
    assert set(workload.all_sizes(t, 51)) == {(x.prompt_len, x.max_new)
                                              for x in workload.schedule(t, 51)}
