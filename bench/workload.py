"""Open-loop arrival schedules from a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:
the arrival rate, the lognormal prompt and output lengths with their
clips, and the lengths of the ramp and the drain.  This module is the
one generator that reads them.

Every seed gets the same work.  The request sizes and the arrival gaps
of each phase are drawn once from the file's ``size_seed``; ``--seed``
draws the token ids and the weights (in ``run.py``), which change what
is computed but not how long it takes.  So the prompt lengths to warm,
the pages the decode batch can span and the load of the window are the
same for every seed.  They are in the same order too: with some
ten requests in a window, a different order of the same requests moved
the p99 gap between tokens by 60% and the p90 latency by 50% between two
seeds on the chip, which would have made every bound a measure of
ordering luck.

Arrivals within a phase are a Poisson process conditioned on its count:
``n`` points whose ``n + 1`` spacings are exponential draws scaled to the
phase's length.  The lognormal-with-mean helper is copied from the
simulator's generator (``sim/workloads.py``) so that a change there does
not move this yardstick.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

PHASES = ("ramp", "window", "tail")


@dataclasses.dataclass(frozen=True)
class Arrival:
    phase: str        # "ramp" (set-up), "window" (measured) or "tail" (drain load)
    due_s: float      # seconds after the schedule's start
    prompt_len: int
    max_new: int      # decoded tokens after the prefill's first token


def lognormal_with_mean(rng, mean: float, sigma: float, n: int) -> np.ndarray:
    mu = np.log(mean) - 0.5 * sigma * sigma
    return rng.lognormal(mu, sigma, n)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    raw = lognormal_with_mean(rng, spec["mean"], spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def phase_counts(traffic: dict, seconds: float) -> dict[str, int]:
    rate = traffic["rate_per_s"]
    return {"ramp": round(rate * traffic["ramp_s"]),
            "window": max(1, round(rate * seconds)),
            "tail": math.ceil(rate * traffic["drain_max_s"])}


def phase_sizes(traffic: dict, phase: str, n: int) -> tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, output lengths) of one phase, in the order of
    ``size_seed``."""
    rng = np.random.default_rng([traffic["size_seed"], 1 + PHASES.index(phase)])
    prompts = _lengths(rng, traffic["prompt_tokens"], n)
    outputs = _lengths(rng, traffic["output_tokens"], n)
    return prompts, outputs


def _spacings(traffic: dict, phase: str, n: int) -> np.ndarray:
    rng = np.random.default_rng([traffic["size_seed"], 10 + PHASES.index(phase)])
    return rng.exponential(1.0, n + 1)


def schedule(traffic: dict, seconds: float) -> list[Arrival]:
    """Every arrival of a run, sorted by due time.  The ramp spans
    ``[0, ramp_s)``, the window ``[ramp_s, ramp_s + seconds)`` and the tail
    ``drain_max_s`` more."""
    if traffic.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrival process {traffic.get('arrivals')!r}")
    counts = phase_counts(traffic, seconds)
    spans = {"ramp": traffic["ramp_s"], "window": float(seconds),
             "tail": traffic["drain_max_s"]}
    start = 0.0
    out: list[Arrival] = []
    for phase in PHASES:
        n, span = counts[phase], spans[phase]
        prompts, outputs = phase_sizes(traffic, phase, n)
        gaps = _spacings(traffic, phase, n)
        times = start + span * np.cumsum(gaps[:n]) / gaps.sum()
        out += [Arrival(phase, float(t), int(p), int(o))
                for t, p, o in zip(times, prompts, outputs)]
        start += span
    return sorted(out, key=lambda a: a.due_s)


def all_sizes(traffic: dict, seconds: float) -> list[tuple[int, int]]:
    """Every (prompt, output) pair any seed's schedule can hold."""
    pairs = []
    for phase, n in phase_counts(traffic, seconds).items():
        prompts, outputs = phase_sizes(traffic, phase, n)
        pairs += list(zip(prompts.tolist(), outputs.tolist()))
    return pairs
