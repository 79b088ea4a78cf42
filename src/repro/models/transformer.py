"""Decoder-only transformer LM: dense or MoE FFN, optional MoE attention.

Layers are stacked and executed with ``jax.lax.scan`` (HLO size O(1) in
depth — required to compile 64-layer configs with 512 virtual devices),
with optional rematerialisation.  Supports three entry points:

* ``lm_apply``      — full-sequence forward (training / loss).
* ``prefill_apply`` — full-sequence forward that also fills a KV cache.
* ``decode_apply``  — single-token step against a KV cache.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.context import MoEContext
from repro.core.metrics import empty_aux
from repro.core.moe import moe_ffn_apply, moe_ffn_specs
from repro.core.moe_attention import moe_attention_apply, moe_attention_specs
from repro.distributed.sharding import shard
from repro.models import layers as L
from repro.models.attention import (
    KVCache,
    abstract_cache,
    attention_apply,
    attention_specs,
    init_cache,
    mla_attention_apply,
    mla_specs,
)
from repro.nn.spec import stack_specs


def _is_moe_layer(cfg: ModelConfig, layer_idx: int) -> bool:
    return cfg.moe.num_experts > 0 and (layer_idx % cfg.moe_layer_period == 0)


def block_specs(cfg: ModelConfig, moe_layer: bool):
    specs = {
        "ln_attn": L.norm_specs(cfg),
        "ln_ffn": L.norm_specs(cfg),
    }
    if cfg.moe.moe_attention and moe_layer:
        specs["attn"] = moe_attention_specs(cfg)
    elif cfg.mla:
        specs["attn"] = mla_specs(cfg)
    else:
        specs["attn"] = attention_specs(cfg)
    if moe_layer:
        specs["ffn"] = moe_ffn_specs(cfg)
    else:
        specs["ffn"] = L.ffn_specs(cfg, cfg.dense_d_ff or None)
    return specs


def block_apply(params, x, cfg: ModelConfig, *, positions, moe_layer: bool,
                cache: Optional[KVCache] = None, use_flash: bool = False,
                ctx: Optional[MoEContext] = None):
    """Pre-norm block. Returns (x, aux, new_cache).

    ``ctx`` is the MoE side-channel (token ids, absolute positions, PRNG,
    step, train flag) threaded to routers and dispatchers; dense layers
    ignore it.
    """
    h = L.norm_apply(params["ln_attn"], x, cfg)
    if cfg.moe.moe_attention and moe_layer and cache is None:
        attn_out, attn_aux = moe_attention_apply(params["attn"], h, cfg,
                                                 positions=positions, ctx=ctx)
        new_cache = None
    elif cfg.mla:
        if cache is not None:
            raise NotImplementedError(
                "latent attention decodes through the paged engine "
                "(repro.serving.continuous.ContinuousEngine) only")
        attn_out = mla_attention_apply(params["attn"], h, cfg,
                                       positions=positions)
        new_cache = attn_aux = None
    else:
        attn_out, new_cache = attention_apply(
            params["attn"], h, cfg, positions=positions, cache=cache, use_flash=use_flash)
        attn_aux = None
    x = x + attn_out
    x = shard(x, "batch", "seq", "embed")

    h = L.norm_apply(params["ln_ffn"], x, cfg)
    if moe_layer:
        ffn_out, aux = moe_ffn_apply(params["ffn"], h, cfg, ctx=ctx)
        if attn_aux is not None:
            aux = {k: aux[k] + attn_aux[k] if k.endswith("_loss") else aux[k]
                   for k in aux}
    else:
        with jax.named_scope("dense_ffn"):
            ffn_out = L.ffn_apply(params["ffn"], h, cfg)
        aux = empty_aux(cfg.moe.held)
    x = x + ffn_out
    x = shard(x, "batch", "seq", "embed")
    return x, aux, new_cache


def lm_specs(cfg: ModelConfig):
    uniform = cfg.moe.num_experts == 0 or cfg.moe_layer_period == 1
    specs = {
        "embed": L.embedding_specs(cfg),
        "final_norm": L.norm_specs(cfg),
    }
    if cfg.pos_embed == "learned":
        from repro.nn import ParamSpec, truncated_normal_init

        specs["pos_embed"] = ParamSpec(
            (cfg.max_seq_len, cfg.d_model), jnp.dtype(cfg.param_dtype),
            (None, "embed"), truncated_normal_init(cfg.initializer_range))
    if cfg.first_dense_layers:
        # a leading dense group, then the MoE group, each stacked
        for key, n in cfg.layer_groups:
            specs[key] = stack_specs(block_specs(cfg, key == "blocks"), n)
    elif cfg.scan_layers and uniform:
        specs["blocks"] = stack_specs(block_specs(cfg, _is_moe_layer(cfg, 0)), cfg.num_layers)
    else:
        specs["blocks"] = [block_specs(cfg, _is_moe_layer(cfg, i)) for i in range(cfg.num_layers)]
    if not cfg.tie_embeddings:
        specs["unembed"] = L.embedding_specs(cfg)
    return specs


def _run_blocks(params, x, cfg: ModelConfig, *, positions, caches=None,
                use_flash: bool = False, ctx: Optional[MoEContext] = None):
    """Run all layers; returns (x, aux_stacked, new_caches).

    ``ctx`` is layer-invariant, so under scan it rides in the body
    closure (broadcast), not through xs.
    """
    uniform = cfg.moe.num_experts == 0 or cfg.moe_layer_period == 1
    decode = caches is not None

    def scan_layers(x, blocks, moe_layer: bool):
        def body(h, bp):
            h, aux, _ = block_apply(bp, h, cfg, positions=positions,
                                    moe_layer=moe_layer, cache=None,
                                    use_flash=use_flash, ctx=ctx)
            return h, aux

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        return jax.lax.scan(body, x, blocks)

    if cfg.first_dense_layers:
        if decode:
            raise NotImplementedError(
                "layer groups decode through the paged engine only")
        auxes = []
        for key, _ in cfg.layer_groups:
            x, aux = scan_layers(x, params[key], moe_layer=key == "blocks")
            auxes.append(aux)
        # per-layer metrics, the dense group's zeros first; a key only
        # the MoE group reports (the held-choice count) reads 0 there
        sizes = [n for _, n in cfg.layer_groups]
        ref = auxes[-1]
        aux = {k: (sum(jnp.sum(a[k]) for a in auxes if k in a)
                   if k.endswith("_loss") else jnp.concatenate(
                       [a[k] if k in a else jnp.zeros((n,) + ref[k].shape[1:],
                                                      ref[k].dtype)
                        for a, n in zip(auxes, sizes)]))
               for k in ref}
        return x, aux, None

    if isinstance(params["blocks"], list):  # unrolled (mixed layer kinds)
        auxes, new_caches = [], []
        for i, bp in enumerate(params["blocks"]):
            c = caches_index(caches, i) if decode else None
            x, aux, nc = block_apply(bp, x, cfg, positions=positions,
                                     moe_layer=_is_moe_layer(cfg, i), cache=c,
                                     use_flash=use_flash, ctx=ctx)
            auxes.append(aux)
            new_caches.append(nc)
        aux = {k: sum(a[k] for a in auxes) if k.endswith("_loss")
               else jnp.stack([a[k] for a in auxes]) for k in auxes[0]}
        nc = stack_caches(new_caches) if decode else None
        return x, aux, nc

    moe_layer = _is_moe_layer(cfg, 0)

    if decode:
        # Caches flow through scan xs/ys (layer-sliced): GSPMD keeps each
        # layer's K/V sharded in place; a carry-based in-place update was
        # tried and triggered pathological per-layer resharding (see
        # EXPERIMENTS.md S Perf).
        def body(h, scanned):
            bp, layer_cache = scanned
            h, aux, new_cache = block_apply(bp, h, cfg, positions=positions,
                                            moe_layer=moe_layer, cache=layer_cache,
                                            use_flash=use_flash, ctx=ctx)
            return h, (aux, new_cache)

        x, (aux, new_caches) = jax.lax.scan(body, x, (params["blocks"], caches))
    else:
        x, aux = scan_layers(x, params["blocks"], moe_layer)
        new_caches = None
    aux = dict(aux)
    for k in list(aux):
        if k.endswith("_loss"):
            aux[k] = jnp.sum(aux[k])
    return x, aux, new_caches


def caches_index(caches, i):
    if caches is None:
        return None
    return jax.tree_util.tree_map(lambda a: a[i], caches)


def stack_caches(cache_list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *cache_list)


def lm_apply(params, tokens, cfg: ModelConfig, *, positions=None,
             use_flash: bool = False, extra_embeds: Optional[jax.Array] = None,
             ctx: Optional[MoEContext] = None):
    """tokens: (B, S) int32 -> (logits (B,S,V_pad), aux).

    ``extra_embeds``: optional (B, P, d_model) prefix embeddings (image
    patches / audio frames for the VLM / audio / M6 stubs) prepended to
    the token embeddings.  ``ctx`` carries caller-side MoE context
    (train flag / step / PRNG); token ids and positions are filled here,
    with prefix rows marked identity-unknown (-1).
    """
    x = L.embedding_apply(params["embed"], tokens, cfg)
    prefix = 0
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        prefix = extra_embeds.shape[1]
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ctx = (ctx or MoEContext()).with_tokens(tokens, positions, prefix_len=prefix)
    if cfg.pos_embed == "learned":
        x = x + params["pos_embed"][:S].astype(x.dtype)[None]
    x = shard(x, "batch", "seq", "embed")
    x, aux, _ = _run_blocks(params, x, cfg, positions=positions,
                            use_flash=use_flash, ctx=ctx)
    x = L.norm_apply(params["final_norm"], x, cfg)
    unembed = params.get("unembed", params["embed"])
    logits = L.unembed_apply(unembed, x, cfg)
    logits = shard(logits, "batch", "seq", "vocab")
    return logits, aux


def init_caches(cfg: ModelConfig, batch: int, max_len: int, abstract: bool = False):
    fn = abstract_cache if abstract else init_cache
    one = fn(cfg, batch, max_len)
    if abstract:
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((cfg.num_layers,) + s.shape, s.dtype), one)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_layers,) + a.shape).copy(), one)


def decode_apply(params, tokens, caches, cfg: ModelConfig,
                 ctx: Optional[MoEContext] = None):
    """tokens: (B, 1) -> (logits (B,1,V_pad), new_caches).

    The MoE context carries the *absolute* decode positions (from the
    cache length) and the current token ids, so content/identity routing
    is consistent between prefill and decode.
    """
    x = L.embedding_apply(params["embed"], tokens, cfg)
    B, S, _ = x.shape
    length = caches.length[0] if hasattr(caches, "length") else caches[0].length
    positions = jnp.broadcast_to(length + jnp.arange(S)[None, :], (B, S))
    ctx = (ctx or MoEContext()).with_tokens(tokens, positions)
    if cfg.pos_embed == "learned":
        pos_tab = params["pos_embed"].astype(x.dtype)
        x = x + jax.lax.dynamic_slice_in_dim(pos_tab, length, S, axis=0)[None]
    x = shard(x, "batch", "seq", "embed")
    x, aux, new_caches = _run_blocks(params, x, cfg, positions=positions,
                                     caches=caches, ctx=ctx)
    x = L.norm_apply(params["final_norm"], x, cfg)
    unembed = params.get("unembed", params["embed"])
    logits = L.unembed_apply(unembed, x, cfg)
    return logits, new_caches


def prefill_apply(params, tokens, cfg: ModelConfig, *, max_len: int,
                  use_flash: bool = False, ctx: Optional[MoEContext] = None):
    """Full forward + build KV caches for subsequent decode.

    Implemented as full-sequence attention followed by writing K/V into a
    fresh cache (single pass, no chunking — chunked prefill lives in
    ``repro.serving.engine``).
    """
    caches = init_caches(cfg, tokens.shape[0], max_len)
    caches = jax.tree_util.tree_map(lambda a: a, caches)
    # reuse decode path with S = seq_len: dynamic_update at index 0
    x = L.embedding_apply(params["embed"], tokens, cfg)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    ctx = (ctx or MoEContext()).with_tokens(tokens, positions)
    x = shard(x, "batch", "seq", "embed")
    x, aux, new_caches = _run_blocks(params, x, cfg, positions=positions,
                                     caches=caches, ctx=ctx)
    x = L.norm_apply(params["final_norm"], x, cfg)
    unembed = params.get("unembed", params["embed"])
    logits = L.unembed_apply(unembed, x, cfg)
    return logits, new_caches, aux
