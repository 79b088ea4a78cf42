"""Every random draw of a run comes from ``--seed`` through here.

The driver's seeds can exceed 32 bits, so a seed is spread by numpy's
``SeedSequence`` into independent streams, one per named purpose: the
weights, the traffic's token ids, the order of the traffic's lengths,
the sample that the correctness check reads.
"""
from __future__ import annotations

import zlib

import numpy as np


def _entropy(seed: int, purpose: str):
    if seed < 0:
        raise SystemExit(f"bench: --seed must be >= 0, got {seed}")
    return [seed & 0xFFFFFFFF, seed >> 32, zlib.crc32(purpose.encode())]


def rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(_entropy(seed, purpose)))


def key_words(seed: int, purpose: str) -> np.ndarray:
    """Two uint32 words for a threefry ``jax.random`` key."""
    ss = np.random.SeedSequence(_entropy(seed, purpose))
    return ss.generate_state(2, dtype=np.uint32)


def jax_key(seed: int, purpose: str):
    import jax

    return jax.random.wrap_key_data(key_words(seed, purpose), impl="threefry2x32")
