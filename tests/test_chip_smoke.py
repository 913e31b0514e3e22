"""The chip bring-up script, rehearsed on the CPU.

``chip_smoke.py --rehearse`` drives the served path at the smoke config
of yi-9b and must end with the result line; without ``--rehearse`` it
must refuse the CPU, and alone in a directory it must fail.  The
four-worker placement runs in a child process with four virtual CPU
devices (the device count is fixed when a process starts JAX).
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


def child_env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_rehearsal_ends_with_the_result_line(chip_smoke, capsys, monkeypatch,
                                             tmp_path):
    # an explicit cache directory: the helper then sets nothing in-process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    assert "first tokens match direct prefill" in out
    assert "served greedy tokens == direct loop" in out
    assert last_json(out) == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_refuses_the_cpu_without_rehearse(chip_smoke, capsys, monkeypatch,
                                          tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert "no TPU" in captured.err
    assert '"ok"' not in captured.out


def test_fails_alone_in_a_directory(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = subprocess.run([sys.executable, str(lone), "--rehearse"],
                          cwd=tmp_path, env=child_env(), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_four_workers_each_on_their_own_device(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(SCRIPT), "--rehearse", "--chips", "4"],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env=child_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                      JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "[smoke] placement:" in proc.stdout
    assert last_json(proc.stdout)["device"]["count"] == 4


def test_compile_cache_goes_to_the_checkout(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.use_compilation_cache()
        assert path == str(SCRIPT.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
