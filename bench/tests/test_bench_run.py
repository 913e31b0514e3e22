"""The harness end to end on the CPU at smoke sizes: every step of a run
but the look for a chip.  Warm-up leaves nothing to compile in the
window; the comparison with the reference passes on the program as it
is, and fails with the timed path broken underneath; the int8 control
reads wider gaps than the program; and the entry point refuses to run
without a TPU or without the program."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import smoke
import control
import run
from repro.serving import engine

SECONDS = 1.5
_MODELS = {}  # one model per architecture, so the tests share compiled programs


def _run(arch="yi", seed=2**31 + 5, trace=False, spec=None):
    r = run.Run(spec or smoke.spec(arch), seed, SECONDS, trace, require_tpu=False,
                model=_MODELS.get(arch))
    return r


def _go(arch="yi", seed=2**31 + 5, trace=False):
    r = _run(arch, seed, trace)
    res = r.go()
    _MODELS[arch] = r.model
    return res


def test_run_is_correct_and_compiles_nothing_in_the_window(capfd):
    res = _go(trace=True)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] == round(4.0 * SECONDS)
    assert res["metrics"]["decode.compiles"]["value"] == 0
    assert list(res)[-1] == "check"
    err = capfd.readouterr().err
    assert "jit_prefill 0, jit_decode_step 0, traced_programs 0" in err
    assert err.rstrip().splitlines()[-1].startswith("[check] failed_requests 0 limit 0")


def test_end_to_end_metrics_are_reported():
    res = _go(arch="granite")
    names = {m["name"] for m in run._json(run.ROOT / "BENCHMARK.json")["end_to_end"]}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_an_altered_token_is_caught(monkeypatch):
    orig = engine.DecodeWorker._argmax_tokens

    def altered(self, logits):
        return (orig(self, logits) + 1) % self.model.cfg.vocab_size

    monkeypatch.setattr(engine.DecodeWorker, "_argmax_tokens", altered)
    res = _go()
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > res["check"]["max_logit_gap"]["limit"]


def test_a_step_that_returns_its_state_unchanged_is_caught(monkeypatch):
    orig = engine.jit_decode_step

    def stale(model, params, state, tokens):
        logits, _ = orig(model, params, state, tokens)
        return logits, state

    monkeypatch.setattr(engine, "jit_decode_step", stale)
    res = _go()
    assert res["correct"] is False


# At the smoke widths no int8 rounding flips a greedy token, so the control
# runs where near-ties occur: vocabulary 16384, width 256, 4 layers, some
# 150 served tokens compared.  There, on seeds 1-12 (CPU), the program read
# 0.0080-0.0204 and the control 0.0334-0.0944; the limit lies between them.
# The readings that set the chip's limit come from ``control.py`` at the
# served size.
CONTROL_SIZES = {"vocab_size": 16384, "d_model": 256, "num_layers": 4, "head_dim": 32,
                 "d_ff": 768}
CONTROL_LIMIT = 0.028


@pytest.mark.parametrize("seed", [1, 2])
def test_int8_control_reads_wider_gaps_than_the_program(seed):
    """The control goes through the harness's own comparison and comes out
    not correct, while the program's gap on the same requests stays inside
    the limit."""
    spec = smoke.spec("yi", limit=CONTROL_LIMIT)
    spec.config["model"].update(CONTROL_SIZES)
    spec.traffic["check_requests"] = 6
    spec.traffic["output_tokens"] = {"mean": 24, "sigma": 0.6, "min": 8, "max": 40}
    out, _MODELS["control"] = control.readings(spec, seed, 2.0, _MODELS.get("control"),
                                               require_tpu=False)
    assert out["failed"] == 0 and sum(d["tokens"] for d in out["per_request"]) > 100
    assert out["correct"] is False and out["control_gap"] > CONTROL_LIMIT
    assert out["program_gap"] <= CONTROL_LIMIT < out["control_gap"]


def _entry(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi9b.docqa", "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_no_tpu_means_no_result():
    r = _entry(run.ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _entry(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_the_check_compares_the_served_tokens():
    r = _run("granite", 3)
    r.build()
    r.warm()
    r.serve()
    reqs = r.requests()
    r.served = {q["index"]: list(r.recs[q["index"]]["handle"].tokens) for q in reqs}
    i = reqs[0]["index"]
    served = np.asarray(r.served[i])
    assert r.gaps_of(i, served)["program_gap"] < 0.05
    served[1] = (served[1] + 7) % 512
    assert r.gaps_of(i, served)["program_gap"] > 0.05
