"""Fixed sets of sizes that every seed shares, in an order the seed picks.

Runs with different seeds must do the same amount of work, or the seed
and not the program moves the metric.  So lengths and gaps are not
sampled: each block of ``n`` requests takes the ``n`` quantiles
``(i + 0.5) / n`` of its distribution, and the seed only shuffles the
order inside each block.  Any stretch of a few blocks then holds nearly
the same sizes in every run.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def exponential_quantiles(n: int, rate: float) -> np.ndarray:
    """``n`` gaps (seconds) at the quantiles of an exponential of ``rate``."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])


def length_pairs(spec: dict) -> np.ndarray:
    """One block of (prompt, output) pairs: prompt and output quantiles
    paired by a fixed permutation (the same for every seed), each pair's
    output cut so that prompt + output <= ``max_total``."""
    n = spec["block"]
    p, o = spec["prompt"], spec["output"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
    outputs = outputs[np.random.default_rng(0).permutation(n)]
    outputs = np.minimum(outputs, spec["max_total"] - prompts)
    if (outputs < 1).any():
        raise SystemExit("bench: traffic leaves a request no output token")
    return np.stack([prompts, outputs], axis=1)


def blocks(rng: np.random.Generator, block: np.ndarray, count: int) -> np.ndarray:
    """``count`` rows made of shuffled copies of ``block``."""
    out, n = [], len(block)
    while sum(len(b) for b in out) < count:
        out.append(block[rng.permutation(n)])
    return np.concatenate(out)[:count]
