"""Pallas TPU flash attention (causal, GQA) — online-softmax blocked.

Grid: (B, Hq, Sq/bq, Skv/bkv) with the KV axis innermost ("arbitrary");
running max/denominator/accumulator live in VMEM scratch across KV steps.
Causality skips whole KV blocks above the diagonal (work ~halves).

VMEM per step (bf16, bq=bkv=512, D=128): q 0.13 + k 0.13 + v 0.13 MB +
f32 acc (bq, D) 0.25MB — comfortably under VMEM; block sizes are 128-
aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, bq, bkv, n_kv, causal):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv block strictly above the diagonal contributes nothing
    run = (not causal) or (kb * bkv <= qb * bq + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bkv, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bkv)
        if causal:
            rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            cols = kb * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)               # (bkv, D)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == n_kv - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool = False):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D) -> (B, Hq, S, D)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    bq = min(block_q, S)
    bkv = min(block_kv, S)
    assert S % bq == 0 and S % bkv == 0
    n_kv = S // bkv
    grid = (B, Hq, S // bq, n_kv)

    kernel = functools.partial(_kernel, scale=D ** -0.5, bq=bq, bkv=bkv,
                               n_kv=n_kv, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, qb, kb: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, qb, kb: (b, h // G, kb, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, qb, kb: (b, h // G, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, qb, kb: (b, h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
