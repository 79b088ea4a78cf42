"""Decoder-only language model with a sparse expert FFN in every layer:
M6 (the M6-T paper, Table 5) and OLMoE (arXiv:2409.02060).

Every layer is alike, its weights stacked over layers: pre-norm
attention of ``num_attention_heads`` query and ``num_key_value_heads``
key/value heads of ``head_dim``, with per-head QK-norm where the file
says ``"per_head"`` and learned or rotary positions; then a softmax
router over ``num_experts`` experts of ``intermediate_size``, each token
to its top ``num_experts_per_tok``, gelu (two matrices) or swiglu
(three).  Layer norm with biases or RMSNorm; the output table tied to
the embedding or its own.  The plain reference is
``bench/reference/moe_lm.py``.
"""
from __future__ import annotations

from bench import weights
from bench.counts import attention, moe_ffn


def shapes(mc: dict) -> dict:
    L, d = mc["num_hidden_layers"], mc["hidden_size"]
    H, Hkv, D = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    E, F = mc["num_experts"], mc["intermediate_size"]
    V = -(-mc["vocab_size"] // weights.VOCAB_MULTIPLE) * weights.VOCAB_MULTIPLE
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


def is_scale(name: str) -> bool:
    return name.endswith("_scale") or name in ("q_norm", "k_norm")


# benchmark weight name -> path in the program's parameter tree
LAYOUT = {
    "embed": ("embed", "table"),
    "unembed": ("unembed", "table"),
    "pos_embed": ("pos_embed",),
    "final_scale": ("final_norm", "scale"),
    "final_bias": ("final_norm", "bias"),
    "ln1_scale": ("blocks", "ln_attn", "scale"),
    "ln1_bias": ("blocks", "ln_attn", "bias"),
    "ln2_scale": ("blocks", "ln_ffn", "scale"),
    "ln2_bias": ("blocks", "ln_ffn", "bias"),
    "wq": ("blocks", "attn", "wq", "kernel"),
    "wk": ("blocks", "attn", "wk", "kernel"),
    "wv": ("blocks", "attn", "wv", "kernel"),
    "wo": ("blocks", "attn", "wo", "kernel"),
    "q_norm": ("blocks", "attn", "q_norm"),
    "k_norm": ("blocks", "attn", "k_norm"),
    "router": ("blocks", "ffn", "router"),
    "w_up": ("blocks", "ffn", "up"),
    "w_gate": ("blocks", "ffn", "gate"),
    "w_down": ("blocks", "ffn", "down"),
}


def _norm_eps(mc: dict) -> float:
    return mc.get("layer_norm_eps", mc.get("rms_norm_eps"))


def model_config(mc: dict, mode: str, cfg):
    """The registry's ``cfg`` with every size from the file and the MoE
    settings of ``mode``; the registry's family and layer kinds must
    agree with what the file states."""
    if mc["qk_norm"] not in (False, "per_head"):
        raise SystemExit(f"bench: {mc['name']} states qk_norm="
                         f"{mc['qk_norm']!r}; the program's QK-norm is per head")
    cfg = cfg.replace(
        num_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        num_heads=mc["num_attention_heads"],
        num_kv_heads=mc["num_key_value_heads"], head_dim=mc["head_dim"],
        d_ff=mc["intermediate_size"], vocab_size=mc["vocab_size"],
        max_seq_len=mc["max_position_embeddings"],
        num_image_tokens=mc["num_image_tokens"], norm_eps=_norm_eps(mc),
        rope_theta=mc.get("rope_theta", cfg.rope_theta),
        tie_embeddings=mc["tie_word_embeddings"], qk_norm=bool(mc["qk_norm"]),
        initializer_range=mc["initializer_range"], dtype=mc["dtype"],
        param_dtype=mc["param_dtype"])
    moe = dict(num_experts=mc["num_experts"], top_k=mc["num_experts_per_tok"],
               normalize_gates=mc["norm_topk_prob"], routing="topk")
    if "group_size" in mc:
        moe["group_size"] = mc["group_size"]
    if "capacity_factor" in mc:
        moe["capacity_factor"] = mc["capacity_factor"]
    run = mc.get(mode, {})
    if "moe_impl" in run:
        moe["impl"] = run["moe_impl"]
    if "capacity_factor" in run:
        moe["capacity_factor"] = run["capacity_factor"]
    cfg = cfg.replace_moe(**moe)
    act = {"gelu_tanh": "gelu", "silu": "swiglu"}[mc["hidden_act"]]
    pos = {"learned": "learned", "rope": "rope"}[mc["position_embedding"]]
    stated = {"ffn_activation": act, "norm": mc["norm"], "pos_embed": pos}
    for key, want in stated.items():
        if getattr(cfg, key) != want:
            raise SystemExit(f"bench: {mc['name']} states {key}={want!r}, "
                             f"the program's {mc['registry_id']} runs "
                             f"{getattr(cfg, key)!r}")
    return cfg


def train_prefix(mc: dict) -> tuple:
    return mc["num_image_tokens"], mc["hidden_size"]


MATMUL_WEIGHTS = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "embed",
                  "unembed")


def grad_parts(names) -> list:
    """Leaves whose gradients are taken in one pass: each expert matrix
    alone, then the rest together."""
    big = [(n,) for n in ("w_up", "w_gate", "w_down") if n in names]
    rest = tuple(n for n in names if (n,) not in big)
    return big + [rest]


# Model operations a token needs, from the configuration's sizes.
# ``row_flops``: one token row's forward through every layer, attending
# to ``length`` positions: the projections, the router, its ``k``
# experts, and attention over its context.  ``unembed_flops``: the
# output projection, counted only for rows whose next token is sampled.
# ``train_step_flops``: forward and backward (three times the forward's
# products) of a training batch; attention causal, recomputation not
# counted.

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


def _attention_work(mc: dict, rows) -> list:
    """(flops, bytes) of the paged attention kernel in each layer, for
    one step's live rows [(slot, length)]: every layer attends alike."""
    work = attention.paged_attention(rows, mc["num_attention_heads"],
                                     mc["num_key_value_heads"], mc["head_dim"])
    return [work] * mc["num_hidden_layers"]


def _expert_work(mc: dict, rows, experts_hit) -> list:
    """(flops, bytes) of the ragged expert FFN in each MoE layer: every
    row's ``num_experts_per_tok`` choices, and the weights of the
    experts that layer's rows hit."""
    k = mc["num_experts_per_tok"]
    return [moe_ffn.ragged_ffn(len(rows) * k, hit, mc["hidden_size"],
                               mc["intermediate_size"], n_mats(mc))
            for hit in experts_hit]


KERNELS = {"paged_attn": "paged_decode_attention", "moe_ffn": "ragged_ffn"}


def kernel_work(kernel: str, mc: dict, step: dict):
    if kernel == "paged_attn":
        return _attention_work(mc, step["rows"])
    if step["experts_hit"] is None:
        return None
    return _expert_work(mc, step["rows"], step["experts_hit"])
