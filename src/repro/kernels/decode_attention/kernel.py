"""Pallas TPU kernel: single-token GQA decode attention over a KV cache.

Decode is bandwidth-bound: the whole valid cache streams HBM->VMEM once
per step.  Grid (B, Hkv, T/bkv), KV innermost ("arbitrary") with online-
softmax scratch; all G grouped q-heads for a kv head are processed
together so the streamed K/V block is reused G times (the GQA bandwidth
win).  Valid-length masking comes from a (B, 1) lengths operand.

VMEM per step (bf16, bkv=1024, D=128, G=8): k/v 0.5MB, q (G,D) tiny,
f32 acc (G,D) tiny — far under VMEM; bandwidth is the limit by design.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, bkv, n_kv):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0, 0]
    # skip whole blocks past the valid prefix
    @pl.when(t * bkv < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,bkv)
        cols = t * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, lengths, *, block_kv: int = 1024,
                            interpret: bool = False):
    """q: (B, Hkv, G, D); k/v: (B, Hkv, T, D); lengths: (B, 1) int32.
    Returns (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    T = k.shape[2]
    bkv = min(block_kv, T)
    while T % bkv:
        bkv //= 2
    n_kv = T // bkv
    grid = (B, Hkv, n_kv)

    kernel = functools.partial(_kernel, scale=D ** -0.5, bkv=bkv, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, t: (b, 0)),          # lengths
            pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, t: (b, h, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)


# ---------------------------------------------------------------------------
# Paged variant: KV lives in a shared block pool, per-row block tables
# ---------------------------------------------------------------------------

def _held_tables(block_tables, lengths, *, bs):
    """Each row's table with the entries past its last valid block set
    to that block (an empty row: entry 0).  Grid step (i, b) reads entry
    b, so past a row's length the block index repeats, the pipeline
    issues no DMA, and no entry past the length is ever read.  Computed
    once per call, outside the kernel: the index maps stay one SMEM
    load a step."""
    last = jnp.maximum(lengths - 1, 0) // bs
    entry = jnp.minimum(jnp.arange(block_tables.shape[1], dtype=jnp.int32),
                        last[:, None])
    return jnp.take_along_axis(block_tables, entry, axis=1)


def _vpu_tile(q, k, v, cols, m_prev, *, length):
    """One query per KV head (G = 1): scores as a lane reduction of
    ``q * k`` over D and ``p . v`` as a sublane reduction over the block,
    on the VPU.  A (1, D) x (D, bs) MXU product per head would latch a
    whole tile for one row.  q: (Hkv, 1, D); k: (Hkv, bs, D); v: (Hkv,
    bs, Dv); m_prev: (Hkv, 1, 1).  Returns (m_new, p summed, p . v)."""
    s = jnp.sum(q * k, axis=-1, keepdims=True)                   # (Hkv, bs, 1)
    s = jnp.where(cols[..., None] < length, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    return (m_new, jnp.sum(p, axis=1, keepdims=True),
            jnp.sum(p * v, axis=1, keepdims=True))


def _mxu_tile(q, k, v, cols, m_prev, *, length):
    """Several queries per KV head (GQA, latent mode): the head's K tile
    is reused by its G queries on the MXU, one head at a time.  Shapes as
    ``_vpu_tile`` with G queries: q: (Hkv, G, D); m_prev: (Hkv, G, 1)."""
    m_new, l_blk, pv = [], [], []
    for h in range(q.shape[0]):
        s = jax.lax.dot_general(q[h], k[h], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bs)
        s = jnp.where(cols < length, s, NEG_INF)
        m = jnp.maximum(m_prev[h], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m)
        m_new.append(m)
        l_blk.append(jnp.sum(p, axis=-1, keepdims=True))
        pv.append(jnp.dot(p, v[h], preferred_element_type=jnp.float32))
    return jnp.stack(m_new), jnp.stack(l_blk), jnp.stack(pv)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, *refs, scale, bs, n_b,
                  v_dim):
    """Same online-softmax recurrence as ``_kernel``, for every KV head of
    row ``i`` at once: grid step (i, b) holds pool block ``tbl[i, b]``
    whole, (Hkv, bs, D) contiguous, DMA'd through the BlockSpec index
    maps that read the scalar-prefetched (held) table from SMEM (the
    MegaBlocks-style trick the dropless FFN kernel uses for weights).
    Blocks past the row's length are skipped; the online softmax state is
    (Hkv, G, 1) and (Hkv, G, Dv) float32.

    The tile arithmetic follows G, a shape the kernel sees: with one
    query per KV head (``_vpu_tile``) the VPU computes the scores and
    ``p . v``; with several (``_mxu_tile``) the MXU does.  Both run in
    float32 on the upcast tiles.

    With ``v_dim`` (latent attention) there is no V pool: the values are
    the leading ``v_dim`` lanes of the K tile, read once for both dots."""
    if v_dim is None:
        v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[i]
    @pl.when(b * bs < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale             # (Hkv, G, D)
        k = k_ref[0].astype(jnp.float32)                     # (Hkv, bs, D)
        v = (v_ref[0].astype(jnp.float32) if v_dim is None
             else k[..., :v_dim])
        tile = _vpu_tile if q.shape[1] == 1 else _mxu_tile
        cols = b * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        m_prev = m_ref[...]
        m_new, l_blk, pv = tile(q, k, v, cols, m_prev, length=length)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + l_blk
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(b == n_b - 1)
    def _finish():
        # length-0 rows never accumulate: l stays 0 -> output exactly 0
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables, lengths, *,
                                  scale: float, v_dim=None, layer=None,
                                  interpret: bool = False):
    """q: (N, Hkv, G, D); k_pool/v_pool: (P, Hkv, bs, D); block_tables:
    (N, MB) int32 pool block ids per row; lengths: (N,) int32; ``scale``
    multiplies the scores.  Returns (N, Hkv, G, D).

    Grid (N, MB): one step per row and table entry, over all KV heads
    (a K/V block of (1, Hkv, bs, D), the query and output blocks (1, Hkv,
    G, D)).  The tables are held at each row's last valid entry
    (``_held_tables``), so steps past its length fetch nothing.  G = 1
    runs the tile on the VPU, G > 1 on the MXU (``_paged_kernel``).

    Latent mode (``v_pool`` None, ``v_dim`` set): the values are the
    leading ``v_dim`` lanes of each K tile; returns (N, Hkv, G, v_dim).
    With ``layer`` (an int32 scalar, latent mode only) ``k_pool`` is a
    layer group's whole pool (L, P, Hkv, bs, D) and the blocks are read
    from its layer ``layer`` in place, the index riding the scalar
    prefetch beside the tables."""
    N, Hkv, G, D = q.shape
    bs = k_pool.shape[-2]
    MB = block_tables.shape[1]
    grid = (N, MB)
    Dv = D if v_pool is not None else v_dim

    kernel = functools.partial(_paged_kernel, scale=scale, bs=bs, n_b=MB,
                               v_dim=None if v_pool is not None else v_dim)
    lengths = lengths.astype(jnp.int32)
    prefetch = [_held_tables(block_tables.astype(jnp.int32), lengths, bs=bs),
                lengths]
    if layer is None:
        kv_spec = pl.BlockSpec((1, Hkv, bs, D),
                               lambda i, b, tbl, lens: (tbl[i, b], 0, 0, 0))
    else:
        prefetch.append(jnp.reshape(layer, (1,)).astype(jnp.int32))
        kv_spec = pl.BlockSpec(
            (None, 1, Hkv, bs, D),
            lambda i, b, tbl, lens, lyr: (lyr[0], tbl[i, b], 0, 0, 0))
        kernel = functools.partial(_skip_layer_ref, kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # held tables, lengths[, layer]
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda i, b, *_: (i, 0, 0, 0)),
            kv_spec,
        ] + ([kv_spec] if v_pool is not None else []),
        out_specs=pl.BlockSpec((1, Hkv, G, Dv), lambda i, b, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),
        ],
    )
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, q, *pools)


def _skip_layer_ref(kernel, tbl_ref, len_ref, layer_ref, *refs):
    """``kernel`` under a grid that also prefetches the layer index: only
    the index maps read it."""
    return kernel(tbl_ref, len_ref, *refs)


# ---------------------------------------------------------------------------
# Quantized paged variant: int8 code pools + per-(block, head) f32 scales
# ---------------------------------------------------------------------------

def _quant_paged_kernel(tbl_ref, len_ref, q_ref, k_ref, ks_ref, v_ref,
                        vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                        scale, bs, n_b, decode):
    """``_paged_kernel`` with in-register dequant: the K/V tiles arrive
    as int8 codes (half the HBM->VMEM bytes of bf16 — the decode
    bandwidth win), the block's per-head scale row rides the same
    ``tbl[i, b]`` index map into SMEM (a ``(1, 1, Hkv)`` block over the
    scales viewed as ``(P, 1, Hkv)``: a ``(1, 1)`` block of ``(P, Hkv)``
    breaks the TPU tiling rule), this head's scalar is read from it, and
    ``value = decode(code) * scale`` is materialised in VMEM registers
    inside the online-softmax loop — never written back anywhere."""
    i = pl.program_id(0)
    h = pl.program_id(1)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[i]
    @pl.when(b * bs < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
        k = decode(k_ref[0, 0]) * ks_ref[0, 0, h]            # (bs, D) f32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bs)
        cols = b * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = decode(v_ref[0, 0]) * vs_ref[0, 0, h]            # (bs, D) f32
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(b == n_b - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def quantized_paged_decode_attention_kernel(q, k_pool, v_pool, k_scales,
                                            v_scales, block_tables, lengths,
                                            *, decode, scale: float,
                                            interpret: bool = False):
    """q: (N, Hkv, G, D); k_pool/v_pool: (P, Hkv, bs, D) int8 codes;
    k_scales/v_scales: (P, Hkv) float32; block_tables: (N, MB) int32;
    lengths: (N,) int32; decode: the policy's code -> f32 map (must be
    Pallas-traceable — the built-ins are astype / bitcast); ``scale``
    multiplies the scores.  Returns (N, Hkv, G, D)."""
    N, Hkv, G, D = q.shape
    _, _, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    grid = (N, Hkv, MB)

    kernel = functools.partial(_quant_paged_kernel, scale=scale, bs=bs,
                               n_b=MB, decode=decode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda i, h, b, tbl, lens: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, D), lambda i, h, b, tbl, lens: (tbl[i, b], h, 0, 0)),
            pl.BlockSpec((1, 1, Hkv), lambda i, h, b, tbl, lens: (tbl[i, b], 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bs, D), lambda i, h, b, tbl, lens: (tbl[i, b], h, 0, 0)),
            pl.BlockSpec((1, 1, Hkv), lambda i, h, b, tbl, lens: (tbl[i, b], 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda i, h, b, tbl, lens: (i, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, k_scales.reshape(-1, 1, Hkv), v_pool,
      v_scales.reshape(-1, 1, Hkv))
