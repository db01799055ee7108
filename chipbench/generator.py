"""The one traffic generator: a mix file's parameters -> requests.

A serving mix gives an arrival ``rate_per_s``, an arrival ``process``
(``poisson``, or ``gamma`` with ``shape`` < 1 for bursts at the same mean
rate) and a distribution for prompt and output lengths (``lognormal``
with ``median``/``sigma``, or ``uniform``; each clipped to
``min``/``max``). The lengths and inter-arrival gaps of each phase are
drawn by stratified quantiles and put in order from the mix's
``sizes_seed``, so every seed gets the same schedule of sizes and
arrivals; the run's seed draws the token ids alone. A seed that
reordered the schedule would change which requests prefill together,
and with it the step times and the tails: the same set of 23 requests
in the seed's order spread a chat window's TTFT p95 by 9.5 % and its
ITL p95 by 4.7 % (one v5e), where the fixed order spread them by 0.8 %
and 0.4 %.

Three phases follow each other on one schedule. ``warmup_s`` seconds of
requests fall due before the window, so that it starts with the server
as full as the load keeps it; the window's ``round(rate * seconds)``
requests fall due in ``[0, seconds)``; after them the same process keeps
sending for ``tail_s`` more seconds to hold the load. The warm-up's and
the window's gaps are scaled to fill their phase exactly.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Planned:
    due_s: float          # offset from the window's start
    prompt: List[int]
    max_new: int
    in_window: bool


def _quantiles(n: int, rng) -> np.ndarray:
    """n stratified uniforms, one in each of n equal bins."""
    return (np.arange(n) + rng.random(n)) / n


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    u = _quantiles(n, rng)
    if spec["dist"] == "lognormal":
        from scipy.stats import norm

        x = spec["median"] * np.exp(spec["sigma"] * norm.ppf(u))
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(int)


def gaps(mix: dict, n: int, rng) -> np.ndarray:
    """n inter-arrival gaps of mean 1 / rate."""
    u = _quantiles(n, rng)
    rate = mix["rate_per_s"]
    process = mix.get("process", "poisson")
    if process == "poisson":
        g = -np.log1p(-u)
    elif process == "gamma":
        from scipy.stats import gamma

        k = mix["shape"]
        g = gamma.ppf(u, k, scale=1.0 / k)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g / rate


def plan(mix: dict, vocab: int, seconds: float, seed: int) -> List[Planned]:
    rate = mix["rate_per_s"]
    warm = mix.get("warmup_s", 0.0)
    # (requests, phase start, seconds the gaps fill or 0, in the window)
    phases = ((int(round(rate * warm)), -warm, warm, False),
              (max(1, int(round(rate * seconds))), 0.0, seconds, True),
              (int(np.ceil(rate * mix.get("tail_s", 0.0))), seconds, 0.0,
               False))
    base = np.random.default_rng(mix.get("sizes_seed", 0))
    run = np.random.default_rng(int(seed))
    out: List[Planned] = []
    for n, start, fill, in_window in phases:
        if n == 0:
            continue
        p = base.permutation(lengths(mix["prompt"], n, base))
        o = base.permutation(lengths(mix["output"], n, base))
        g = base.permutation(gaps(mix, n, base))
        if fill:
            g = g * (fill / g.sum())
            due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        else:
            due = start + np.cumsum(g)
        for i in range(n):
            out.append(Planned(float(due[i]),
                               run.integers(0, vocab, int(p[i])).tolist(),
                               int(o[i]), in_window))
    return out
