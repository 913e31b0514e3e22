#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload yi9b.docqa --seeds 1,2,3 --seconds 20

For each seed, in one process (one model, so the compiled programs are
shared): a run of the cell (``run.Run.go``) over a short window of its
own traffic, with the control in the comparison's place.  The control
is the configuration's reference (the ``bench/`` module its
``"reference"`` key names) computed in int8 (W8A8), read as the gap,
under the float32 reference, of the token the int8 pass puts first at each
position of the same prompts and served tokens.  The run's ``correct``
then judges the control against the configuration's limit, and has to
come out false.  Each line also gives the program's own widest gap on
the same requests (the number a benchmark run compares), with the
reference's median top-2 margin and the distinct tokens served.

The limit lies between the program's largest reading and the control's
smallest.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def readings(spec: run.Spec, seed: int, seconds: float, model=None,
             require_tpu: bool = True) -> tuple[dict, object]:
    r = run.Run(spec, seed, seconds, False, model=model, require_tpu=require_tpu,
                control=True)
    res = r.go()
    out = {"seed": seed, "correct": res["correct"],
           "control_gap": res["check"]["max_logit_gap"]["value"],
           "program_gap": max((d["program_gap"] for d in r.check_detail), default=None),
           "limit": res["check"]["max_logit_gap"]["limit"],
           "per_request": r.check_detail, "failed": res["failed"],
           "window_compiles": r.window_compiles}
    model = r.model
    del r
    gc.collect()
    return out, model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_spec(args.workload)
    import jax

    from repro.launch.compile_cache import use_compilation_cache

    use_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < spec.cell["chips"]:
        print(f"control: needs {spec.cell['chips']} TPU chip(s)", file=sys.stderr)
        return 1
    model = None
    for seed in (int(s) for s in args.seeds.split(",")):
        out, model = readings(spec, seed, args.seconds, model)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
