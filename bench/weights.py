"""Random weights from the seed, made on the device in one jitted call.

The arrays are named by the benchmark (``wq``, ``w_up``, ...), stacked
over layers, in float32 (the configuration's ``param_dtype``, the type
the program keeps and serves them in).  ``bench/program.py`` places
them in the program's tree; the reference reads them by these names.
Both get the same arrays from the same seed, and neither makes them.

Matrices are normal with the configuration's ``initializer_range``;
norm scales are 1 plus that noise and biases that noise, so that a norm
that ignored its scale or bias would show.  The embedding has as many
rows as the program's table: the vocabulary rounded up to a multiple of
256.  Rows past ``vocab_size`` are never read.
"""
from __future__ import annotations

from bench import seeds

VOCAB_MULTIPLE = 256


def shapes(mc: dict) -> dict:
    L, d = mc["num_hidden_layers"], mc["hidden_size"]
    H, Hkv, D = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    E, F = mc["num_experts"], mc["intermediate_size"]
    V = -(-mc["vocab_size"] // VOCAB_MULTIPLE) * VOCAB_MULTIPLE
    out = {"embed": (V, d), "final_scale": (d,),
           "ln1_scale": (L, d), "ln2_scale": (L, d),
           "wq": (L, d, H * D), "wk": (L, d, Hkv * D), "wv": (L, d, Hkv * D),
           "wo": (L, H * D, d), "router": (L, d, E),
           "w_up": (L, E, d, F), "w_down": (L, E, F, d)}
    if mc["norm"] == "layernorm":
        out.update(final_bias=(d,), ln1_bias=(L, d), ln2_bias=(L, d))
    if mc["position_embedding"] == "learned":
        out["pos_embed"] = (mc["max_position_embeddings"], d)
    if not mc["tie_word_embeddings"]:
        out["unembed"] = (V, d)
    if mc["qk_norm"]:
        out.update(q_norm=(L, D), k_norm=(L, D))
    if mc["hidden_act"] == "silu":
        out["w_gate"] = (L, E, d, F)
    return out


def _is_scale(name: str) -> bool:
    return name.endswith("_scale") or name in ("q_norm", "k_norm")


def maker(mc: dict):
    """A jitted ``key -> {name: float32 array}`` for configuration ``mc``;
    compiled once, whatever the seed."""
    import jax
    import jax.numpy as jnp

    shp = shapes(mc)
    std = mc["initializer_range"]
    names = sorted(shp)

    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            noise = std * jax.random.normal(k, shp[name], jnp.float32)
            out[name] = 1.0 + noise if _is_scale(name) else noise
        return out

    return jax.jit(make)


def make(mc: dict, seed: int) -> dict:
    return maker(mc)(seeds.jax_key(seed, "weights"))
