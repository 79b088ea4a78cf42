"""Dense decoder-only language model: grouped-query attention with rotary
positions, RMSNorm, a swiglu FFN of ``intermediate_size`` in every layer,
the output table its own.  The program runs it as its ``decoder_lm``
family with no experts.  The plain reference is
``reference/dense_lm.py`` beside this directory.

A form brought as new files: the tests put this directory on
``bench.arch``'s search path and the reference's on
``bench.reference``'s, and the harness finds both by the one name the
configuration states.
"""
from __future__ import annotations

from bench import weights
from bench.counts import attention


def shapes(mc: dict) -> dict:
    L, d = mc["num_hidden_layers"], mc["hidden_size"]
    H, Hkv, D = (mc["num_attention_heads"], mc["num_key_value_heads"],
                 mc["head_dim"])
    F = mc["intermediate_size"]
    V = -(-mc["vocab_size"] // weights.VOCAB_MULTIPLE) * weights.VOCAB_MULTIPLE
    return {"embed": (V, d), "unembed": (V, d), "final_scale": (d,),
            "ln1_scale": (L, d), "ln2_scale": (L, d),
            "wq": (L, d, H * D), "wk": (L, d, Hkv * D), "wv": (L, d, Hkv * D),
            "wo": (L, H * D, d),
            "w_up": (L, d, F), "w_gate": (L, d, F), "w_down": (L, F, d)}


def is_scale(name: str) -> bool:
    return name.endswith("_scale")


LAYOUT = {
    "embed": ("embed", "table"),
    "unembed": ("unembed", "table"),
    "final_scale": ("final_norm", "scale"),
    "ln1_scale": ("blocks", "ln_attn", "scale"),
    "ln2_scale": ("blocks", "ln_ffn", "scale"),
    "wq": ("blocks", "attn", "wq", "kernel"),
    "wk": ("blocks", "attn", "wk", "kernel"),
    "wv": ("blocks", "attn", "wv", "kernel"),
    "wo": ("blocks", "attn", "wo", "kernel"),
    "w_up": ("blocks", "ffn", "up", "kernel"),
    "w_gate": ("blocks", "ffn", "gate", "kernel"),
    "w_down": ("blocks", "ffn", "down", "kernel"),
}


def model_config(mc: dict, mode: str, cfg):
    cfg = cfg.replace(
        num_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        num_heads=mc["num_attention_heads"],
        num_kv_heads=mc["num_key_value_heads"], head_dim=mc["head_dim"],
        d_ff=mc["intermediate_size"], vocab_size=mc["vocab_size"],
        max_seq_len=mc["max_position_embeddings"], num_image_tokens=0,
        norm_eps=mc["rms_norm_eps"], rope_theta=mc["rope_theta"],
        tie_embeddings=False, qk_norm=False,
        initializer_range=mc["initializer_range"], dtype=mc["dtype"],
        param_dtype=mc["param_dtype"])
    stated = {"ffn_activation": "swiglu", "norm": "rmsnorm", "pos_embed": "rope",
              "family": "decoder_lm"}
    for key, want in stated.items():
        if getattr(cfg, key) != want:
            raise SystemExit(f"bench: {mc['name']} is a {want!r} {key}; the "
                             f"program's {mc['registry_id']} runs "
                             f"{getattr(cfg, key)!r}")
    if cfg.moe.num_experts:
        raise SystemExit(f"bench: {mc['registry_id']} has experts; this form "
                         f"has none")
    return cfg


def layer_matmul_flops(mc: dict) -> int:
    d, H, Hkv, D = (mc["hidden_size"], mc["num_attention_heads"],
                    mc["num_key_value_heads"], mc["head_dim"])
    proj = 2 * d * (H * D + 2 * Hkv * D) + 2 * H * D * d
    return proj + 3 * 2 * d * mc["intermediate_size"]


def attention_flops(mc: dict, length: int) -> int:
    return 4 * length * mc["num_attention_heads"] * mc["head_dim"]


def row_flops(mc: dict, length: int) -> int:
    return mc["num_hidden_layers"] * (layer_matmul_flops(mc)
                                      + attention_flops(mc, length))


def unembed_flops(mc: dict) -> int:
    return 2 * mc["hidden_size"] * mc["vocab_size"]


KERNELS = {"paged_attn": "paged_decode_attention"}


def kernel_work(kernel: str, mc: dict, step: dict):
    if kernel != "paged_attn":
        return None
    work = attention.paged_attention(step["rows"], mc["num_attention_heads"],
                                     mc["num_key_value_heads"], mc["head_dim"])
    return [work] * mc["num_hidden_layers"]
