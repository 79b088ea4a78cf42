"""Open-loop Poisson arrivals of independent users (chat).

Parameters as ``backlog``, plus ``rate_per_s``.  The gaps of each block
are the quantiles of the exponential, shuffled, so every block spans
the same time and every seed offers the same load.  With ``order_seed``
the order of lengths and gaps is that seed's for every run, and the
run's seed draws only the token ids: near the knee the p90 of time to
first token turns on where the few long prompts fall, and runs of
different orders differ far more than runs of one.
"""
from __future__ import annotations

import numpy as np

from bench import seeds
from bench.generators import strata

DRIVER = "serve"


def generate(traffic: dict, seed: int, vocab_size: int) -> list:
    order = seeds.rng(traffic.get("order_seed", seed), "traffic.order")
    tokens = seeds.rng(seed, "traffic.tokens")
    n = traffic["requests"]
    pairs = strata.blocks(order, strata.length_pairs(traffic), n)
    gaps = strata.exponential_quantiles(traffic["block"], traffic["rate_per_s"])
    arrivals = np.cumsum(strata.blocks(order, gaps, n)) - gaps.min()
    return [{"uid": i,
             "prompt": tokens.integers(0, vocab_size, int(p), dtype=np.int32),
             "max_new_tokens": int(o), "arrival_s": float(t)}
            for i, ((p, o), t) in enumerate(zip(pairs, arrivals))]
