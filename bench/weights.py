"""Random weights from the seed, made on the device in one jitted call.

The arrays are named by the benchmark, with the names and shapes of the
configuration's form (``bench/arch``), in float32 (the configuration's
``param_dtype``, the type the program keeps and serves them in).
``bench/program.py`` places them in the program's tree; the reference
reads them by these names.  Both get the same arrays from the same
seed, and neither makes them.

Matrices are normal with the configuration's ``initializer_range``;
norm scales are 1 plus that noise and biases that noise, so that a norm
that ignored its scale or bias would show.  The embedding has as many
rows as the program's table: the vocabulary rounded up to a multiple of
``VOCAB_MULTIPLE``.  Rows past ``vocab_size`` are never read.
"""
from __future__ import annotations

from bench import arch, seeds

VOCAB_MULTIPLE = 256


def maker(mc: dict):
    """A jitted ``key -> {name: float32 array}`` for configuration ``mc``;
    compiled once, whatever the seed."""
    import jax
    import jax.numpy as jnp

    form = arch.load(mc)
    shp = form.shapes(mc)
    std = mc["initializer_range"]
    names = sorted(shp)

    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            noise = std * jax.random.normal(k, shp[name], jnp.float32)
            out[name] = 1.0 + noise if form.is_scale(name) else noise
        return out

    return jax.jit(make)


def make(mc: dict, seed: int) -> dict:
    return maker(mc)(seeds.jax_key(seed, "weights"))
