"""Smoke-size specs of the two configurations, for the CPU tests: the
same architectures at the widths of the program's smoke configs, with
traffic scaled down to match."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

MODELS = {
    "yi": {"family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 8,
           "num_kv_heads": 1, "head_dim": 8, "d_ff": 192, "vocab_size": 512,
           "mlp_type": "swiglu", "rope_theta": 10000.0, "norm_eps": 1e-05,
           "context_length": 512},
    "granite": {"family": "dense", "num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 1, "head_dim": 16, "d_ff": 256, "vocab_size": 512,
                "mlp_type": "gelu", "rope_theta": 10000.0, "norm_eps": 1e-05,
                "context_length": 512},
}

TRAFFIC = {
    "arrivals": "poisson", "rate_per_s": 4.0,
    "prompt_tokens": {"mean": 100, "sigma": 0.6, "min": 40, "max": 200},
    "output_tokens": {"mean": 6, "sigma": 0.6, "min": 2, "max": 12},
    "size_seed": 1, "ramp_s": 0.5, "drain_max_s": 3,
    "warm_batch_max": 4, "check_requests": 3,
}


def spec(arch: str = "yi", limit: float | None = 0.05) -> run.Spec:
    bench = run._json(run.ROOT / "BENCHMARK.json")
    config = {"model": copy.deepcopy(MODELS[arch]), "reference": "reference",
              "counts": "flops", "num_blocks": 64,
              "slo": {"ttft_s": 1.0, "tbt_mean_s": 0.1},
              "correct": {"max_logit_gap": limit}}
    cell = {"name": f"smoke.{arch}", "config": arch, "traffic": "smoke", "chips": 1}
    return run.Spec(cell, config, copy.deepcopy(TRAFFIC), bench["end_to_end"],
                    bench["per_layer"])
