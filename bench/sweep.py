#!/usr/bin/env python3
"""Knee sweep: runs of one cell at several fixed arrival rates, to find
the highest rate the served path sustains.

    python3 bench/sweep.py --workload yi9b.docqa --rates 0.15,0.25,0.35 \\
        --seed 2147515001 --seconds 51

In one process (one model, so the compiled programs are shared), each
rate in turn is a whole run of the cell (``run.Run.go``) with only the
traffic's ``rate_per_s`` replaced, on seeds ``seed, seed + 1, ...``.  One
JSON line per rate: the 90th percentile of request latency (from when a
request was due to its last token), of time to first token, the decode
rate and gap, the largest decode batch of a worker, the programs added
in the window and ``correct``.  Above the knee the backlog grows through
the window and the latency percentile jumps.  A cell is then set at a
fixed share of the knee.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import gc
import json
import sys

import run


def point(spec: run.Spec, rate: float, seed: int, seconds: float,
          model=None) -> tuple[dict, object]:
    traffic = copy.deepcopy(spec.traffic)
    traffic["rate_per_s"] = rate
    r = run.Run(dataclasses.replace(spec, traffic=traffic), seed, seconds, False,
                model=model)
    res = r.go()
    out = {"rate_per_s": rate, "seed": seed, **r.tails,
           **{k: v["value"] for k, v in res["metrics"].items()},
           "max_batch": r.max_batch, "window_compiles": r.window_compiles,
           "correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "max_logit_gap": res["check"]["max_logit_gap"]}
    model = r.model
    del r
    gc.collect()
    return out, model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, req/s")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    import jax

    from repro.launch.compile_cache import use_compilation_cache

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < spec.cell["chips"]:
        print(f"sweep: needs {spec.cell['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    model = None
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        out, model = point(spec, rate, args.seed + k, args.seconds, model)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
