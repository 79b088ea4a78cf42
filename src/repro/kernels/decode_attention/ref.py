"""Pure-jnp oracles for single-token GQA decode attention: dense cache
and paged (block-table) cache variants."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # finite mask: rows with length 0 must not produce NaN


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         lengths: jax.Array) -> jax.Array:
    """q: (B, Hq, D) one query per sequence; k/v: (B, T, Hkv, D) cache;
    lengths: (B,) valid prefix per sequence.  Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32)) * (D ** -0.5)
    valid = jnp.arange(T)[None, :] < lengths[:, None]            # (B,T)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(q.dtype)


def paged_decode_attention_ref(q: jax.Array, k_pool: jax.Array,
                               v_pool, block_tables: jax.Array,
                               lengths: jax.Array, *, scale: float,
                               v_dim=None, layer=None) -> jax.Array:
    """q: (N, Hq, D) one query row per (slot | prefill-chunk |
    speculative-verify) token;
    k_pool/v_pool: (P, Hkv, bs, D) the shared block pool; block_tables:
    (N, MB) int32 pool block ids covering each row's context in order;
    lengths: (N,) valid context per row (0 => inactive row, output 0);
    ``scale`` multiplies the scores.  Returns (N, Hq, D).

    Each row attends to positions [0, length) of its own slot's context,
    read through the block table — scattered pool blocks, no dense
    per-slot slab.  Masking uses a finite NEG_INF so fully-masked rows
    stay NaN-free (NaN would poison other tokens through the einsum
    dispatcher's zero-weight combine products).

    Latent mode (``v_pool`` None, ``v_dim`` set): the values are the
    leading ``v_dim`` lanes of the keys; returns (N, Hq, v_dim).  With
    ``layer``, ``k_pool`` is a layer group's pool (L, P, Hkv, bs, D) and
    its layer ``layer`` is read.
    """
    if layer is not None:
        k_pool = jax.lax.dynamic_index_in_dim(k_pool, layer, keepdims=False)
    N, Hq, D = q.shape
    _, Hkv, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = Hq // Hkv
    # gather this row's context: (N, MB, Hkv, bs, D) -> (N, Hkv, MB*bs, D)
    k = jnp.transpose(k_pool[block_tables], (0, 2, 1, 3, 4)).reshape(N, Hkv, MB * bs, D)
    if v_pool is None:
        v = k[..., :v_dim]
    else:
        v = jnp.transpose(v_pool[block_tables], (0, 2, 1, 3, 4)).reshape(N, Hkv, MB * bs, D)
    Dv = v.shape[-1]
    qg = q.reshape(N, Hkv, G, D).astype(jnp.float32)
    scores = jnp.einsum("nkgd,nktd->nkgt", qg, k.astype(jnp.float32)) * scale
    valid = jnp.arange(MB * bs)[None, :] < lengths[:, None]         # (N, T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgt,nktd->nkgd", probs, v.astype(jnp.float32))
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(N, Hq, Dv).astype(q.dtype)


def quantized_paged_decode_attention_ref(q, k_pool, v_pool, k_scales,
                                         v_scales, block_tables, lengths,
                                         *, policy, scale: float):
    """Quantized-pool oracle: identical math to
    :func:`paged_decode_attention_ref` after dequantizing the gathered
    tiles.  k_pool/v_pool hold int8 codes; k_scales/v_scales are
    (P, Hkv) float32 per-block-per-head absmax scales keyed by the same
    block ids, so value = policy.decode(code) * scale.
    """
    N, Hq, D = q.shape
    _, Hkv, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    G = Hq // Hkv
    # (N, MB, Hkv, bs, D) codes * (N, MB, Hkv, 1, 1) scales
    k = policy.decode(k_pool[block_tables]) * \
        k_scales[block_tables][..., None, None]
    v = policy.decode(v_pool[block_tables]) * \
        v_scales[block_tables][..., None, None]
    k = jnp.transpose(k, (0, 2, 1, 3, 4)).reshape(N, Hkv, MB * bs, D)
    v = jnp.transpose(v, (0, 2, 1, 3, 4)).reshape(N, Hkv, MB * bs, D)
    qg = q.reshape(N, Hkv, G, D).astype(jnp.float32)
    scores = jnp.einsum("nkgd,nktd->nkgt", qg, k) * scale
    valid = jnp.arange(MB * bs)[None, :] < lengths[:, None]         # (N, T)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("nkgt,nktd->nkgd", probs, v)
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(N, Hq, D).astype(q.dtype)
