"""A backlog due at t=0: offline batch generation, the queue never empty.

Parameters (traffic file): ``prompt`` and ``output`` lognormals
(``median``, ``sigma``, ``min``, ``max``), ``max_total``, ``block``
(requests per stratified block), ``requests`` (how many) and, where
given, ``order_seed``: the seed of the order of the lengths, in place
of the run's (which then draws only the token ids).
"""
from __future__ import annotations

import numpy as np

from bench import seeds
from bench.generators import strata

DRIVER = "serve"


def generate(traffic: dict, seed: int, vocab_size: int) -> list:
    order = seeds.rng(traffic.get("order_seed", seed), "traffic.order")
    tokens = seeds.rng(seed, "traffic.tokens")
    pairs = strata.blocks(order, strata.length_pairs(traffic),
                          traffic["requests"])
    return [{"uid": i,
             "prompt": tokens.integers(0, vocab_size, int(p), dtype=np.int32),
             "max_new_tokens": int(o), "arrival_s": 0.0}
            for i, (p, o) in enumerate(pairs)]
