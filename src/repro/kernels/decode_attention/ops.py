"""jit'd wrappers: dense (B, T, Hkv, D) cache layout and the paged
(block-pool + block-table) layout used by the continuous-batching
serving engine."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_kernel,
    paged_decode_attention_kernel,
    quantized_paged_decode_attention_kernel,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
    quantized_paged_decode_attention_ref,
)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("block_kv",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, block_kv: int = 1024) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, T, Hkv, D); lengths: (B,). -> (B, Hq, D)."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    kt = jnp.transpose(k, (0, 2, 1, 3))     # (B, Hkv, T, D)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = decode_attention_kernel(qg, kt, vt, lengths.reshape(B, 1).astype(jnp.int32),
                                  block_kv=block_kv, interpret=_interpret())
    return out.reshape(B, Hq, D)


@partial(jax.jit, static_argnames=("scale", "v_dim"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           scale=None, v_dim=None, layer=None) -> jax.Array:
    """q: (N, Hq, D) one query per row (decode slot, prefill-chunk
    token, or speculative verify row — rows are position-addressed, so
    several rows of one slot at consecutive positions sharing a block
    table are just more rows); k_pool/v_pool: (P, Hkv, bs, D) shared
    block pool; block_tables: (N, MB) int32 pool block ids covering each
    row's context in order; lengths: (N,) valid context per row (0 =>
    masked row, output 0); ``scale`` multiplies the scores (None:
    ``D ** -0.5``).  Returns (N, Hq, D).

    Latent mode (multi-head latent attention): ``v_pool`` is None, the
    pool holds one entry per token (Hkv = 1, D = latent + rope lanes)
    that every query head reads, and the values are its leading
    ``v_dim`` lanes; returns (N, Hq, v_dim).  With ``layer`` (an int32
    scalar) ``k_pool`` is a layer group's pool (L, P, 1, bs, D), read at
    that layer in place.

    On TPU the Pallas kernel streams only the table-addressed pool
    blocks (no dense gather); elsewhere the pure-jnp gather reference
    runs (the kernel's scalar-prefetch indirection is a TPU
    memory-system question — interpret mode would re-derive the
    reference semantics through a full pool gather anyway).
    """
    N, Hq, D = q.shape
    Hkv = k_pool.shape[-3]
    scale = D ** -0.5 if scale is None else scale
    if jax.default_backend() != "tpu":
        return paged_decode_attention_ref(q, k_pool, v_pool, block_tables,
                                          lengths, scale=scale, v_dim=v_dim,
                                          layer=layer)
    G = Hq // Hkv
    qg = q.reshape(N, Hkv, G, D)
    out = paged_decode_attention_kernel(qg, k_pool, v_pool, block_tables,
                                        lengths, scale=scale, v_dim=v_dim,
                                        layer=layer)
    return out.reshape(N, Hq, -1)


def paged_update_attention(q, k, v, k_pool, v_pool, write_blocks,
                           write_offsets, block_tables, lengths, *,
                           scale=None, v_dim=None, layer=None):
    """One serving step's K/V write + paged attention, fused at the op
    level: scatter this step's per-row K/V at ``(write_blocks, :,
    write_offsets)``, then attend through the block tables.  The write
    lands before the read, so a prefill-chunk row sees its same-step
    predecessors (exact causal prefill).  Returns ``(out, k_pool,
    v_pool)`` — pools flow through so callers can donate them.

    q: (N, Hq, D); k/v: (N, Hkv, D); pools: (P, Hkv, bs, D);
    write_blocks/write_offsets: (N,) pool coords (masked rows target the
    garbage block); block_tables: (N, MB); lengths: (N,).  Latent mode:
    ``v`` and ``v_pool`` None, ``k`` each row's latent entry, ``v_dim``
    the value lanes, and with ``layer`` the write and the read go to
    that layer of a layer group's pool (L, P, 1, bs, D), in place (see
    :func:`paged_decode_attention`).
    """
    if layer is not None:
        k_pool = k_pool.at[layer, write_blocks, :, write_offsets].set(
            k.astype(k_pool.dtype))
        out = paged_decode_attention(q, k_pool, None, block_tables, lengths,
                                     scale=scale, v_dim=v_dim, layer=layer)
        return out, k_pool, None
    k_pool = k_pool.at[write_blocks, :, write_offsets].set(k.astype(k_pool.dtype))
    if v_pool is not None:
        v_pool = v_pool.at[write_blocks, :, write_offsets].set(
            v.astype(v_pool.dtype))
    out = paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                                 scale=scale, v_dim=v_dim)
    return out, k_pool, v_pool


@partial(jax.jit, static_argnames=("policy",))
def quantized_paged_decode_attention(q, k_pool, v_pool, k_scales, v_scales,
                                     block_tables, lengths, *, policy):
    """:func:`paged_decode_attention` over a quantized pool: k_pool /
    v_pool hold int8 codes, k_scales/v_scales (P, Hkv) float32 absmax
    scales keyed by the same block ids (``value = policy.decode(code) *
    scale``).  ``policy`` is a :class:`repro.quant.KVQuantPolicy`
    singleton riding in the jit static args.  On TPU the Pallas kernel
    dequantizes tiles in-register inside the online-softmax loop;
    elsewhere the pure-jnp gather reference runs."""
    N, Hq, D = q.shape
    Hkv = k_pool.shape[1]
    if jax.default_backend() != "tpu":
        return quantized_paged_decode_attention_ref(
            q, k_pool, v_pool, k_scales, v_scales, block_tables, lengths,
            policy=policy, scale=D ** -0.5)
    G = Hq // Hkv
    qg = q.reshape(N, Hkv, G, D)
    out = quantized_paged_decode_attention_kernel(
        qg, k_pool, v_pool, k_scales, v_scales, block_tables, lengths,
        decode=policy.decode, scale=D ** -0.5)
    return out.reshape(N, Hq, D)


def quantized_paged_update_attention(q, k, v, k_pool, v_pool, k_scales,
                                     v_scales, write_blocks, write_offsets,
                                     block_tables, lengths, *, policy):
    """Quantized :func:`paged_update_attention`: quantize-scatter this
    step's per-row K/V (maintaining the per-block absmax scales — fresh
    blocks restart at 0, grown blocks rescale their resident codes),
    then attend through the block tables with fused dequant.  Returns
    ``(out, k_pool, v_pool, k_scales, v_scales)`` so callers can donate
    all four pool buffers."""
    from repro.quant.policy import quant_write_kv

    k_pool, k_scales = quant_write_kv(k_pool, k_scales, k, write_blocks,
                                      write_offsets, policy=policy)
    v_pool, v_scales = quant_write_kv(v_pool, v_scales, v, write_blocks,
                                      write_offsets, policy=policy)
    out = quantized_paged_decode_attention(
        q, k_pool, v_pool, k_scales, v_scales, block_tables, lengths,
        policy=policy)
    return out, k_pool, v_pool, k_scales, v_scales


def sharded_paged_update_attention(q, k, v, k_pool, v_pool, write_blocks,
                                   write_offsets, block_tables, lengths,
                                   *, mesh, axis="data"):
    """:func:`paged_update_attention` under shard_map over the mesh's
    data axis.

    Every operand partitions on its leading dimension: rows (the engine
    lays step rows out shard-major, each shard's rows covering its own
    slots) and the stacked pool (each shard owns a contiguous
    ``(shard_blocks + 1)``-row slice ending in its private garbage
    block).  Block tables and write coords carry *shard-local* ids, so
    each body indexes only its own pool slice — attention never reads
    another shard's blocks, and no unsharded ``(num_blocks, ...)`` pool
    appears inside the mapped computation.
    """
    from jax.sharding import PartitionSpec as P

    dx = P(axis)
    fn = jax.shard_map(paged_update_attention, mesh=mesh, in_specs=(dx,) * 9,
                       out_specs=(dx, dx, dx), check_vma=False)
    return fn(q, k, v, k_pool, v_pool, write_blocks, write_offsets,
              block_tables, lengths)


def sharded_quantized_paged_update_attention(q, k, v, k_pool, v_pool,
                                             k_scales, v_scales,
                                             write_blocks, write_offsets,
                                             block_tables, lengths, *,
                                             policy, mesh, axis="data"):
    """:func:`quantized_paged_update_attention` under shard_map over the
    mesh's data axis — the same leading-dimension partitioning as
    :func:`sharded_paged_update_attention`, with the scale pools sharded
    alongside their code pools (both are keyed by shard-local ids)."""
    from jax.sharding import PartitionSpec as P

    dx = P(axis)
    body = partial(quantized_paged_update_attention, policy=policy)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(dx,) * 11,
                       out_specs=(dx,) * 5, check_vma=False)
    return fn(q, k, v, k_pool, v_pool, k_scales, v_scales, write_blocks,
              write_offsets, block_tables, lengths)


__all__ = ["decode_attention", "decode_attention_ref",
           "paged_decode_attention", "paged_decode_attention_ref",
           "paged_update_attention", "sharded_paged_update_attention",
           "quantized_paged_decode_attention",
           "quantized_paged_decode_attention_ref",
           "quantized_paged_update_attention",
           "sharded_quantized_paged_update_attention"]
