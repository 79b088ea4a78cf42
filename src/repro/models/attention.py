"""Multi-head attention with GQA, qk-norm, RoPE and a KV cache.

Reference (pure jnp) path used everywhere; the Pallas flash kernel in
``repro.kernels.flash_attention`` is an optional drop-in for the causal
full-sequence case (``use_flash=True``); numerics are tested against this
reference.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import ParamSpec, ones_init
from repro.configs.base import ModelConfig
from repro.models.layers import (
    apply_rope,
    apply_rope_interleaved,
    dense_specs,
    dense_apply,
    head_rmsnorm_apply,
    rope,
)


class KVCache(NamedTuple):
    k: jax.Array  # (B, S_max, H_kv, D)
    v: jax.Array  # (B, S_max, H_kv, D)
    length: jax.Array  # scalar int32: number of valid positions


def attention_specs(cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    specs = {
        "wq": dense_specs(cfg, d, cfg.num_heads * hd, ("embed", "heads"), bias=cfg.qkv_bias),
        "wk": dense_specs(cfg, d, cfg.num_kv_heads * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias),
        "wv": dense_specs(cfg, d, cfg.num_kv_heads * hd, ("embed", "kv_heads"), bias=cfg.qkv_bias),
        "wo": dense_specs(cfg, cfg.num_heads * hd, d, ("heads", "embed")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), jnp.float32, (None,), ones_init)
        specs["k_norm"] = ParamSpec((hd,), jnp.float32, (None,), ones_init)
    return specs


def _project_qkv(params, x, cfg: ModelConfig, positions):
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = dense_apply(params["wq"], x, cfg).reshape(B, -1, cfg.num_heads, hd)
    k = dense_apply(params["wk"], x, cfg).reshape(B, -1, cfg.num_kv_heads, hd)
    v = dense_apply(params["wv"], x, cfg).reshape(B, -1, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = head_rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    if cfg.pos_embed == "rope":
        sin, cos = rope(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    return q, k, v


def _sdpa_reference(q, k, v, cfg: ModelConfig, mask) -> jax.Array:
    """Materialised-scores attention. q:(B,S,Hq,D) k/v:(B,T,Hkv,D)."""
    B, S, Hq, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    scale = D ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, Hq, D).astype(q.dtype)


_CHUNK_THRESHOLD = 1 << 21  # S*T above this -> chunked path under "auto"


def _sdpa(q, k, v, cfg: ModelConfig, mask, *, causal_offset=None) -> jax.Array:
    """Grouped SDPA with automatic chunked (flash-semantics) dispatch.

    ``mask`` is only honoured by the reference path; the chunked path
    handles causal masking itself via ``causal_offset`` (None => full
    bidirectional, array/int => causal with query offset).
    """
    B, S, Hq, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    impl = cfg.attention_impl
    if impl == "auto":
        impl = "chunked" if (S * T > _CHUNK_THRESHOLD) else "reference"
    if impl != "chunked":
        return _sdpa_reference(q, k, v, cfg, mask)
    from repro.models.chunked_attention import chunked_attention

    qg = q.reshape(B, S, Hkv, Hq // Hkv, D)
    causal = causal_offset is not None
    static_off = causal_offset if isinstance(causal_offset, int) else None
    dyn_off = None if isinstance(causal_offset, (int, type(None))) else causal_offset
    out = chunked_attention(qg, k, v, causal, static_off, cfg.attention_block,
                            cfg.attn_logit_softcap, q_offset=dyn_off)
    return out.reshape(B, S, Hq, D)


def causal_mask(S: int, T: int, offset: int = 0):
    """mask[s, t] = t <= s + offset, broadcast to (1,1,1,S,T)."""
    rows = jnp.arange(S)[:, None] + offset
    cols = jnp.arange(T)[None, :]
    return (cols <= rows)[None, None, None, :, :]


def attention_apply(
    params,
    x,
    cfg: ModelConfig,
    *,
    positions,
    causal: bool = True,
    cache: Optional[KVCache] = None,
    use_flash: bool = False,
    kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    kv_positions=None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Returns (output, updated_cache).

    * train/prefill: ``cache is None`` -> full self-attention over x.
      If a cache template is wanted, call ``init_cache`` + prefill path in
      the serving engine instead.
    * decode: ``cache`` holds K/V for past positions; x is (B, 1, d).
    * cross-attention: pass precomputed ``kv=(k, v)`` (already headed).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim

    if kv is not None:  # cross-attention: queries from x, fixed memory kv
        q = dense_apply(params["wq"], x, cfg).reshape(B, S, cfg.num_heads, hd)
        if cfg.qk_norm:
            q = head_rmsnorm_apply(params["q_norm"], q, cfg.norm_eps)
        k, v = kv
        out = _sdpa(q, k, v, cfg, mask=None)
        return dense_apply(params["wo"], out.reshape(B, S, -1), cfg), None

    q, k, v = _project_qkv(params, x, cfg, positions)

    if cache is not None:
        # Decode (or chunked prefill): append k/v at cache.length.
        idx = cache.length
        new_k = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), idx, axis=1)
        new_v = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), idx, axis=1)
        new_cache = KVCache(new_k, new_v, cache.length + S)
        T = cache.k.shape[1]
        valid = jnp.arange(T)[None, :] <= (idx + jnp.arange(S)[:, None])
        mask = valid[None, None, None, :, :]
        out = _sdpa(q, new_k, new_v, cfg, mask, causal_offset=idx)
    else:
        new_cache = None
        if use_flash:
            from repro.kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(q, k, v, causal=causal)
        else:
            mask = causal_mask(S, S) if causal else None
            out = _sdpa(q, k, v, cfg, mask, causal_offset=0 if causal else None)

    y = dense_apply(params["wo"], out.reshape(B, S, -1), cfg)
    return y, new_cache


def project_kv(params, memory, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder memory."""
    B, T, _ = memory.shape
    hd = cfg.resolved_head_dim
    k = dense_apply(params["wk"], memory, cfg).reshape(B, T, cfg.num_kv_heads, hd)
    v = dense_apply(params["wv"], memory, cfg).reshape(B, T, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        k = head_rmsnorm_apply(params["k_norm"], k, cfg.norm_eps)
    return k, v


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    hd = cfg.resolved_head_dim
    dtype = dtype or cfg.activation_dtype
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.zeros((), jnp.int32))


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    hd = cfg.resolved_head_dim
    dtype = dtype or cfg.activation_dtype
    shape = (batch, max_len, cfg.num_kv_heads, hd)
    return KVCache(
        jax.ShapeDtypeStruct(shape, dtype),
        jax.ShapeDtypeStruct(shape, dtype),
        jax.ShapeDtypeStruct((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3, no query LoRA)
# ---------------------------------------------------------------------------
#
#   q = x Wq, split per head into q_nope (nope) and q_pe (rope lanes);
#   [c | k_pe] = x Wkv_a, c of kv_lora_rank lanes through an RMSNorm;
#   [k_nope | v] = c Wkv_b per head; rope (interleaved pairs) on q_pe and
#   on the one k_pe all heads share; softmax scale (nope + rope)^-0.5;
#   o = attn Wo.
#
# The full-sequence form builds k and v per head.  The absorbed form,
# which the paged engine caches, keeps only [c | k_pe] per token: the
# query's nope part is taken into the latent (q_nope Wuk, ``mla_q_absorb``)
# so that a score is one dot product over kv_lora_rank + rope lanes with
# the cached entry, the value is the entry's leading kv_lora_rank lanes,
# and each head's latent output goes through Wuv (``mla_v_up``).

def mla_specs(cfg: ModelConfig):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq": dense_specs(cfg, d, H * qk, ("embed", "heads")),
        "wkv_a": dense_specs(cfg, d, r + cfg.qk_rope_head_dim, ("embed", None)),
        "kv_norm": ParamSpec((r,), jnp.float32, (None,), ones_init),
        "wkv_b": dense_specs(cfg, r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                             (None, "heads")),
        "wo": dense_specs(cfg, H * cfg.v_head_dim, d, ("heads", "embed")),
    }


def mla_project(params, x, cfg: ModelConfig, positions):
    """x: (B, S, d) -> q_nope (B, S, H, nope), q_pe (B, S, H, rope) with
    rope applied, and the cached entry (B, S, kv_lora_rank + rope): the
    normed latent followed by the roped shared key."""
    B, S, _ = x.shape
    n, rp, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q = dense_apply(params["wq"], x, cfg).reshape(B, S, cfg.num_heads, n + rp)
    kv = dense_apply(params["wkv_a"], x, cfg)
    c = head_rmsnorm_apply(params["kv_norm"], kv[..., :r], cfg.norm_eps)
    sin, cos = rope(positions, rp, cfg.rope_theta)
    q_pe = apply_rope_interleaved(q[..., n:], sin, cos)
    k_pe = apply_rope_interleaved(kv[..., None, r:], sin, cos)[..., 0, :]
    return q[..., :n], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _mla_up(params, cfg: ModelConfig):
    """Wkv_b as (kv_lora_rank, H, nope) key and (kv_lora_rank, H, v)
    value up-projections."""
    w = params["wkv_b"]["kernel"].astype(cfg.activation_dtype)
    w = w.reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_absorb_q(params, q_nope, q_pe, cfg: ModelConfig):
    """The absorbed query: q_nope Wuk per head, then the roped part:
    (..., H, kv_lora_rank + rope), one dot product with a cached entry."""
    w_uk, _ = _mla_up(params, cfg)
    with jax.named_scope("mla_q_absorb"):
        q_lat = jnp.einsum("...hn,rhn->...hr", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)


def mla_v_up(params, o_lat, cfg: ModelConfig):
    """Each head's latent output (..., H, kv_lora_rank) through Wuv:
    (..., H, v_head_dim)."""
    _, w_uv = _mla_up(params, cfg)
    with jax.named_scope("mla_v_up"):
        return jnp.einsum("...hr,rhv->...hv", o_lat.astype(w_uv.dtype), w_uv)


def mla_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_attention_apply(params, x, cfg: ModelConfig, *, positions,
                        causal: bool = True) -> jax.Array:
    """Full-sequence latent attention in its plain (non-absorbed) form:
    keys and values per head from the latent, as in training."""
    B, S, _ = x.shape
    H, n, r = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, entry = mla_project(params, x, cfg, positions)
    kv = jnp.einsum("bsr,rhe->bshe", entry[..., :r],
                    params["wkv_b"]["kernel"].astype(cfg.activation_dtype)
                    .reshape(r, H, -1))
    k_pe = jnp.broadcast_to(entry[..., None, r:], q_pe.shape)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :n], k_pe], axis=-1)
    v = kv[..., n:]
    dv = v.shape[-1]
    # _sdpa scales by q's width, (nope + rope)^-0.5, and returns q's
    # width: pad the values with zero lanes and drop them after
    v = jnp.pad(v, ((0, 0),) * 3 + ((0, q.shape[-1] - dv),))
    mask = causal_mask(S, S) if causal else None
    out = _sdpa(q, k, v, cfg, mask, causal_offset=0 if causal else None)
    return dense_apply(params["wo"], out[..., :dv].reshape(B, S, H * dv), cfg)
