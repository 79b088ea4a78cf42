"""Pre-training batches made on the device from the seed.

Parameters: ``batch`` rows of ``text_len`` token ids each, plus the
features the configuration's form places before the text
(``train_prefix``, ``bench/arch``: M6's image features), random at the
embedding's scale, in place of the image encoder's output.  Step
``i`` gets its own rows: the key is folded with ``i``, so no two steps
share a batch.
"""
from __future__ import annotations

from bench import arch

DRIVER = "train"


def batch_fn(traffic: dict, mc: dict):
    """A jitted ``(key, step) -> batch`` for the program's train step."""
    import jax
    import jax.numpy as jnp

    B, S = traffic["batch"], traffic["text_len"]
    P, d = arch.load(mc).train_prefix(mc)
    V = mc["vocab_size"]
    std = mc["initializer_range"]

    def make(key, step):
        kt, ki = jax.random.split(jax.random.fold_in(key, step))
        toks = jax.random.randint(kt, (B, S + 1), 0, V, jnp.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if P:
            batch["patch_embeds"] = std * jax.random.normal(
                ki, (B, P, d), jnp.float32)
        return batch

    return jax.jit(make)
