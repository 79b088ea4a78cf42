"""DeepSeek-V3-form language model (``model_type`` deepseek_v3):
Moonlight-16B-A3B.

Multi-head latent attention with no query LoRA: ``num_attention_heads``
heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim`` query and key lanes
and ``v_head_dim`` value lanes, keys and values from one RMS-normed
latent of ``kv_lora_rank`` lanes plus one roped key shared by the heads.
The first ``first_k_dense_replace`` layers have a dense swiglu FFN of
``intermediate_size``; every later layer routes over the router's
experts of ``moe_intermediate_size`` (sigmoid scores, top
``num_experts_per_tok`` on the scores plus a correction bias, gates
renormalised and scaled by ``routed_scaling_factor``) beside
``n_shared_experts`` always-on experts.  RMSNorm, rotary positions on
interleaved pairs, an untied output layer.

The file describes one chip's share of an expert-parallel deployment
(``expert_parallel``): ``n_routed_experts`` is how many of the router's
``expert_parallel.n_routed_experts`` this chip holds, from
``held_first``; ``vocab_size`` is this chip's slice of the vocabulary.
The program serves it with the absorbed latent cache
(``repro.serving.continuous``); the plain reference is
``bench/reference/mla_moe_lm.py``.
"""
from __future__ import annotations

from bench import weights
from bench.counts import moe_ffn

GROUPS = (("dense", "dense_blocks"), ("moe", "blocks"))


def _sizes(mc: dict):
    nd = mc["first_k_dense_replace"]
    return nd, mc["num_hidden_layers"] - nd


def shapes(mc: dict) -> dict:
    d, H = mc["hidden_size"], mc["num_attention_heads"]
    n, rp, r, dv = (mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                    mc["kv_lora_rank"], mc["v_head_dim"])
    E, F = mc["n_routed_experts"], mc["moe_intermediate_size"]
    Fs = mc["n_shared_experts"] * F
    Fd = mc["intermediate_size"]
    R = mc["expert_parallel"]["n_routed_experts"]
    V = -(-mc["vocab_size"] // weights.VOCAB_MULTIPLE) * weights.VOCAB_MULTIPLE
    out = {"embed": (V, d), "unembed": (V, d), "final_scale": (d,)}
    for (g, _), L in zip(GROUPS, _sizes(mc)):
        out.update({f"{g}_ln1_scale": (L, d), f"{g}_ln2_scale": (L, d),
                    f"{g}_wq": (L, d, H * (n + rp)),
                    f"{g}_wkv_a": (L, d, r + rp), f"{g}_kv_norm": (L, r),
                    f"{g}_wkv_b": (L, r, H * (n + dv)),
                    f"{g}_wo": (L, H * dv, d)})
    nd, nm = _sizes(mc)
    out.update({"dense_w_gate": (nd, d, Fd), "dense_w_up": (nd, d, Fd),
                "dense_w_down": (nd, Fd, d),
                "router": (nm, d, R), "router_bias": (nm, R),
                "w_gate": (nm, E, d, F), "w_up": (nm, E, d, F),
                "w_down": (nm, E, F, d),
                "shared_w_gate": (nm, d, Fs), "shared_w_up": (nm, d, Fs),
                "shared_w_down": (nm, Fs, d)})
    return out


def is_scale(name: str) -> bool:
    return name.endswith("_scale") or name.endswith("kv_norm")


# benchmark weight name -> path in the program's parameter tree
LAYOUT = {
    "embed": ("embed", "table"),
    "unembed": ("unembed", "table"),
    "final_scale": ("final_norm", "scale"),
    "dense_w_gate": ("dense_blocks", "ffn", "gate", "kernel"),
    "dense_w_up": ("dense_blocks", "ffn", "up", "kernel"),
    "dense_w_down": ("dense_blocks", "ffn", "down", "kernel"),
    "router": ("blocks", "ffn", "router"),
    "router_bias": ("blocks", "ffn", "router_bias"),
    "w_gate": ("blocks", "ffn", "gate"),
    "w_up": ("blocks", "ffn", "up"),
    "w_down": ("blocks", "ffn", "down"),
    "shared_w_gate": ("blocks", "ffn", "shared", "gate", "kernel"),
    "shared_w_up": ("blocks", "ffn", "shared", "up", "kernel"),
    "shared_w_down": ("blocks", "ffn", "shared", "down", "kernel"),
}
for _g, _key in GROUPS:
    LAYOUT.update({
        f"{_g}_ln1_scale": (_key, "ln_attn", "scale"),
        f"{_g}_ln2_scale": (_key, "ln_ffn", "scale"),
        f"{_g}_wq": (_key, "attn", "wq", "kernel"),
        f"{_g}_wkv_a": (_key, "attn", "wkv_a", "kernel"),
        f"{_g}_kv_norm": (_key, "attn", "kv_norm"),
        f"{_g}_wkv_b": (_key, "attn", "wkv_b", "kernel"),
        f"{_g}_wo": (_key, "attn", "wo", "kernel"),
    })

# what the file must state for the program's form to be what it runs
FORM = {"model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
        "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "hidden_act": "silu", "moe_layer_freq": 1, "attention_bias": False,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


def model_config(mc: dict, mode: str, cfg):
    """The registry's ``cfg`` with every size from the file and the MoE
    settings of ``mode``, refusing a form the program does not run."""
    for key, want in FORM.items():
        if mc.get(key, want) != want:
            raise SystemExit(f"bench: {mc['name']} states {key}="
                             f"{mc.get(key)!r}; the program runs only "
                             f"{key}={want!r}")
    if mc.get("rope_scaling") is not None:
        raise SystemExit(f"bench: {mc['name']} states rope_scaling; the "
                         f"program has no rope scaling for latent attention")
    if mc["first_k_dense_replace"] < 1:
        raise SystemExit(f"bench: {mc['name']} has no leading dense layer; "
                         f"the program's layer groups need one")
    ep = mc["expert_parallel"]
    cfg = cfg.replace(
        num_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        num_heads=mc["num_attention_heads"], num_kv_heads=1,
        head_dim=mc["v_head_dim"], kv_lora_rank=mc["kv_lora_rank"],
        qk_nope_head_dim=mc["qk_nope_head_dim"],
        qk_rope_head_dim=mc["qk_rope_head_dim"], v_head_dim=mc["v_head_dim"],
        d_ff=mc["moe_intermediate_size"],
        first_dense_layers=mc["first_k_dense_replace"],
        dense_d_ff=mc["intermediate_size"], vocab_size=mc["vocab_size"],
        max_seq_len=mc["max_position_embeddings"],
        norm_eps=mc["rms_norm_eps"], rope_theta=float(mc["rope_theta"]),
        tie_embeddings=False, initializer_range=mc["initializer_range"],
        dtype=mc["dtype"], param_dtype=mc["param_dtype"])
    moe = dict(num_experts=ep["n_routed_experts"],
               top_k=mc["num_experts_per_tok"],
               normalize_gates=mc["norm_topk_prob"],
               routed_scaling_factor=mc["routed_scaling_factor"],
               shared_d_ff=mc["n_shared_experts"] * mc["moe_intermediate_size"],
               held_experts=mc["n_routed_experts"],
               held_offset=ep["held_first"], routing="sigmoid_bias")
    run = mc.get(mode, {})
    if "moe_impl" in run:
        moe["impl"] = run["moe_impl"]
    if "capacity_factor" in run:
        moe["capacity_factor"] = run["capacity_factor"]
    cfg = cfg.replace_moe(**moe)
    stated = {"attention": "mla", "ffn_activation": "swiglu",
              "norm": "rmsnorm", "pos_embed": "rope"}
    for key, want in stated.items():
        if getattr(cfg, key) != want:
            raise SystemExit(f"bench: {mc['name']} is a {want!r} "
                             f"{key}; the program's {mc['registry_id']} runs "
                             f"{getattr(cfg, key)!r}")
    return cfg


# Model operations a token needs in the absorbed algorithm the program
# serves.  Per layer: the projections (q 2·d·H·(nope+rope), kv_a
# 2·d·(r+rope), the query absorption 2·H·nope·r, the value
# up-projection 2·H·r·v, o 2·H·v·d) and latent attention over the
# context, 2·H·(r+rope + r) a position; the dense FFN in the leading
# layers; in each expert layer the router, the shared FFN and the routed
# choices that land on the held experts, k·held/E a token on average.

N_MATS = 3


def attention_layer_flops(mc: dict) -> int:
    d, H = mc["hidden_size"], mc["num_attention_heads"]
    n, rp, r, dv = (mc["qk_nope_head_dim"], mc["qk_rope_head_dim"],
                    mc["kv_lora_rank"], mc["v_head_dim"])
    return (2 * d * H * (n + rp) + 2 * d * (r + rp) + 2 * H * n * r
            + 2 * H * r * dv + 2 * H * dv * d)


def latent_attention_flops(mc: dict, length: int) -> int:
    H, rp, r = (mc["num_attention_heads"], mc["qk_rope_head_dim"],
                mc["kv_lora_rank"])
    return 2 * H * (r + rp + r) * length


def held_choices(mc: dict) -> float:
    """Routed choices a token makes on average on the held experts."""
    return (mc["num_experts_per_tok"] * mc["n_routed_experts"]
            / mc["expert_parallel"]["n_routed_experts"])


def ffn_flops(mc: dict, moe_layer: bool) -> float:
    d = mc["hidden_size"]
    if not moe_layer:
        return 2 * d * mc["intermediate_size"] * N_MATS
    F = mc["moe_intermediate_size"]
    router = 2 * d * mc["expert_parallel"]["n_routed_experts"]
    shared = 2 * d * mc["n_shared_experts"] * F * N_MATS
    return router + shared + held_choices(mc) * 2 * d * F * N_MATS


def row_flops(mc: dict, length: int) -> float:
    nd, nm = _sizes(mc)
    per = attention_layer_flops(mc) + latent_attention_flops(mc, length)
    return ((nd + nm) * per + nd * ffn_flops(mc, False)
            + nm * ffn_flops(mc, True))


def unembed_flops(mc: dict) -> int:
    return 2 * mc["hidden_size"] * mc["vocab_size"]


def latent_attention_work(mc: dict, rows, entry_bytes: int = 2) -> tuple:
    """(flops, bytes) of the latent paged op in one layer for one step's
    live rows [(slot, length)]: each row's scores over r + rope lanes
    and values over r lanes at every context position; each slot's
    entries (r + rope lanes) read once, whatever the number of its rows,
    plus each row's query (H × (r + rope)) and output (H × r)."""
    H, rp, r = (mc["num_attention_heads"], mc["qk_rope_head_dim"],
                mc["kv_lora_rank"])
    flops = sum(latent_attention_flops(mc, length) for _, length in rows)
    context = {}
    for slot, length in rows:
        context[slot] = max(context.get(slot, 0), length)
    nbytes = sum(context.values()) * (r + rp) * entry_bytes
    nbytes += len(rows) * H * ((r + rp) + r) * entry_bytes
    return flops, nbytes


KERNELS = {"paged_attn": "paged_decode_attention", "moe_ffn": "ragged_ffn"}


def kernel_work(kernel: str, mc: dict, step: dict):
    if kernel == "paged_attn":
        return [latent_attention_work(mc, step["rows"])] * mc["num_hidden_layers"]
    if step["experts_hit"] is None:
        return None
    nd, _ = _sizes(mc)
    choices = len(step["rows"]) * held_choices(mc)
    return [moe_ffn.ragged_ffn(choices, hit, mc["hidden_size"],
                               mc["moe_intermediate_size"], N_MATS)
            for hit in step["experts_hit"][nd:]]
