"""Model operations a token needs, from the configuration's sizes.

``row_flops``: one token row's forward through every layer, attending
to ``length`` positions: the projections, the router, its ``k``
experts, and attention over its context.  ``unembed_flops``: the output
projection, counted only for rows whose next token is sampled.
``train_step_flops``: forward and backward (three times the forward's
products) of a training batch; attention causal, recomputation not
counted.
"""
from __future__ import annotations


def n_mats(mc: dict) -> int:
    return 3 if mc["hidden_act"] == "silu" else 2


def layer_matmul_flops(mc: dict) -> int:
    """Products of one token in one layer, attention scores excluded."""
    d, H, Hkv, D = (mc["hidden_size"], mc["num_attention_heads"],
                    mc["num_key_value_heads"], mc["head_dim"])
    proj = 2 * d * (H * D + 2 * Hkv * D) + 2 * H * D * d
    router = 2 * d * mc["num_experts"]
    experts = (mc["num_experts_per_tok"] * 2 * d * mc["intermediate_size"]
               * n_mats(mc))
    return proj + router + experts


def attention_flops(mc: dict, length: int) -> int:
    return 4 * length * mc["num_attention_heads"] * mc["head_dim"]


def row_flops(mc: dict, length: int) -> int:
    L = mc["num_hidden_layers"]
    return L * (layer_matmul_flops(mc) + attention_flops(mc, length))


def unembed_flops(mc: dict) -> int:
    return 2 * mc["hidden_size"] * mc["vocab_size"]


def train_step_flops(mc: dict, batch: int, text_len: int) -> int:
    T = mc["num_image_tokens"] + text_len
    L = mc["num_hidden_layers"]
    per_seq = T * L * layer_matmul_flops(mc)
    per_seq += L * sum(attention_flops(mc, t + 1) for t in range(T))
    per_seq += text_len * unembed_flops(mc)
    return 3 * batch * per_seq
