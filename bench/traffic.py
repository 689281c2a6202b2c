"""The one generator every traffic mix goes through.

A mix is a data file, ``bench/traffic/<mix>.json``:

    {"arrivals": {"process": "poisson", "rate": 1.6}
              or {"process": "mmpp", "rate": 1.0, "rate_hi": 6.0,
                  "mean_dwell": 5.0},          # rates in requests/s
     "prompt_mix": [[weight, lo, hi], ...],   # tokens, lo..hi inclusive
     "output_mix": [[weight, lo, hi], ...],
     "in_flight": 8,          # slots filled before the window, with aged
                              # requests (see ``in_flight``)
     "schedule_seed": 0,      # fixes arrival times and their sizes
     "straggler": {"device": 0, "slowdown": 20.0},   # optional churn
     "trace_at_s": 4.0, "trace_seconds": 5.0,        # --trace 1 window
     "check_tokens": 300}     # served tokens the comparison covers

The arrival times and the (prompt, output) size of each arrival come from
``schedule_seed`` alone, so every ``--seed`` offers the same work at the
same times; the seed draws every token id.  (Letting the seed reorder the
sizes moved glm4-9b.chat's TTFT p90 by up to 40% from seed to seed, more
than two runs of one seed differ.)

``poisson_arrivals``, ``mmpp_arrivals`` and ``sample_mix`` are copies of
``serving/workload.py``'s generators, kept here so the yardstick does not
move with the program.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float                  # seconds after the window opens
    prompt: np.ndarray        # (L0,) int32
    max_new_tokens: int


def poisson_arrivals(rate: float, horizon: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Homogeneous Poisson: exponential gaps at ``rate``."""
    if rate <= 0:
        raise ValueError(f"rate must be positive, got {rate}")
    out, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= horizon:
            return np.asarray(out, float)
        out.append(t)


def mmpp_arrivals(rate: float, rate_hi: float, mean_dwell: float,
                  horizon: float, rng: np.random.Generator) -> np.ndarray:
    """2-state MMPP: exponential dwells alternate the instantaneous rate
    between ``rate`` (quiet) and ``rate_hi`` (burst)."""
    if min(rate, rate_hi, mean_dwell) <= 0:
        raise ValueError("rate, rate_hi, mean_dwell must be positive")
    out: List[float] = []
    t, burst = 0.0, False
    while t < horizon:
        end = min(t + rng.exponential(mean_dwell), horizon)
        r = rate_hi if burst else rate
        tt = t
        while True:
            tt += rng.exponential(1.0 / r)
            if tt >= end:
                break
            out.append(tt)
        t, burst = end, not burst
    return np.asarray(out, float)


def sample_mix(rng: np.random.Generator,
               mix: Sequence[Tuple[float, int, int]]) -> int:
    w = np.asarray([m[0] for m in mix], float)
    i = int(rng.choice(len(mix), p=w / w.sum()))
    _, lo, hi = mix[i]
    return int(rng.integers(lo, hi + 1))


def arrival_times(spec: dict, horizon: float,
                  rng: np.random.Generator) -> np.ndarray:
    a = spec["arrivals"]
    if a["process"] == "poisson":
        return poisson_arrivals(a["rate"], horizon, rng)
    if a["process"] == "mmpp":
        return mmpp_arrivals(a["rate"], a["rate_hi"], a["mean_dwell"],
                             horizon, rng)
    raise ValueError(f"unknown arrival process {a['process']!r}")


def _token_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def schedule(spec: dict, seconds: float, seed: int,
             vocab: int) -> List[Arrival]:
    """Open-loop arrivals over ``[0, seconds)``."""
    rng = np.random.default_rng(spec.get("schedule_seed", 0))
    times = arrival_times(spec, seconds, rng)
    sizes = [(sample_mix(rng, spec["prompt_mix"]),
              sample_mix(rng, spec["output_mix"])) for _ in times]
    trng = _token_rng(seed, 1)
    return [Arrival(float(t), trng.integers(0, vocab, size=L0,
                                            dtype=np.int32), out)
            for t, (L0, out) in zip(times, sizes)]


def in_flight(spec: dict, seed: int, vocab: int) -> List[Arrival]:
    """Requests already in flight when the window opens.  In steady state
    a slot holds a request with probability in proportion to its length,
    at an age uniform over it: each draw is such a request, given to the
    engine as its prompt plus the tokens it has already produced (random
    ids standing for them) and the budget it has left."""
    n = int(spec.get("in_flight", 0))
    rng = np.random.default_rng([spec.get("schedule_seed", 0), 1])
    sizes = []
    while len(sizes) < n:
        L0, out = sample_mix(rng, spec["prompt_mix"]), \
            sample_mix(rng, spec["output_mix"])
        hi = max(m[2] for m in spec["output_mix"])
        if rng.random() < out / hi:                 # length-biased
            age = int(rng.integers(0, out))
            sizes.append((L0 + age, out - age))
    trng = _token_rng(seed, 2)
    return [Arrival(0.0, trng.integers(0, vocab, size=L, dtype=np.int32), b)
            for L, b in sizes]
