"""The seam between the benchmark and the system under test.

Everything the benchmark knows about the program's own API sits here:
how a configuration file becomes the program's ``ModelConfig``, how an
engine section becomes a ``ServeConfig``, and where each of the
benchmark's weight arrays goes in the program's parameter tree.  The
reference (``bench/reference``) never imports this module.
"""
from __future__ import annotations

import os
import sys

from bench import weights

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put the checkout's ``src`` on the path; fail where it is missing."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def _norm_eps(mc: dict) -> float:
    return mc.get("layer_norm_eps", mc.get("rms_norm_eps"))


def model_config(mc: dict, mode: str):
    """The program's ``ModelConfig`` for configuration file ``mc`` run as
    ``mode`` ("serve" or "train").  Every size comes from the file; the
    program's registry supplies the family and layer kinds, which must
    agree with what the file states, and its parameters must have the
    shapes of the benchmark's weights."""
    import_program()
    from repro.configs.registry import get_config

    if mc["qk_norm"] not in (False, "per_head"):
        raise SystemExit(f"bench: {mc['name']} states qk_norm="
                         f"{mc['qk_norm']!r}; the program's QK-norm is per head")
    cfg = get_config(mc["registry_id"])
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
    if program_shapes(cfg) != weights.shapes(mc):
        raise SystemExit("bench: the program's parameter shapes differ from "
                         "the benchmark's weights")
    return cfg


def serve_config(engine: dict):
    import_program()
    from repro.configs.base import ServeConfig

    return ServeConfig(max_slots=engine["max_slots"],
                       kv_block_size=engine["kv_block_size"],
                       prefill_chunk=engine["prefill_chunk"],
                       max_len=engine["max_len"],
                       num_blocks=engine.get("num_blocks"))


# benchmark weight name -> path in the program's parameter tree
_LAYOUT = {
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


def program_shapes(cfg) -> dict:
    """{benchmark weight name: shape} of the program's parameter tree,
    checked leaf for leaf against ``_LAYOUT``."""
    import jax

    from repro.models.registry import get_family
    from repro.nn import abstract

    tree = abstract(get_family(cfg).specs(cfg))
    flat = {tuple(getattr(k, "key", k) for k in path): leaf.shape
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
    names = {v: k for k, v in _LAYOUT.items()}
    unknown = sorted("/".join(p) for p in flat if p not in names)
    if unknown:
        raise SystemExit(f"bench: program parameters the benchmark does not "
                         f"make: {unknown}")
    return {names[p]: tuple(s) for p, s in flat.items()}


def program_params(arrays: dict) -> dict:
    """The program's parameter tree holding the benchmark's arrays."""
    tree: dict = {}
    for name, arr in arrays.items():
        node = tree
        path = _LAYOUT[name]
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = arr
    return tree


def leaf_names(tree) -> list:
    """Benchmark weight names in the program tree's leaf order."""
    import jax

    names = {v: k for k, v in _LAYOUT.items()}
    return [names[tuple(getattr(k, "key", k) for k in path)]
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
