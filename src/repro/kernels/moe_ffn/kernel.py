"""Pallas TPU kernel: grouped (per-expert) FFN on dispatched MoE buffers.

The paper's appendix attributes ~98% of MoE-layer forward FLOPs to the
two expert matmuls (EdCM x eMI and back).  This kernel fuses
up-projection, activation (swiglu/gelu/relu) and down-projection for all
experts in one pallas_call:

  grid = (E, X/bx, I/bi)   — experts and row-blocks parallel; the
                             intermediate dimension is the innermost
                             (arbitrary) axis, accumulated in VMEM scratch.

VMEM working set per step (bf16):
  x block (bx, M) + w_up/w_gate (M, bi) + w_down (bi, M) + f32 acc (bx, M)
  for bx=128, bi=512, M=2048: 0.5 + 2*2 + 2 + 1 MB ~= 7.5MB < 16MB VMEM.
MXU alignment: bx, bi multiples of 128; M is the contraction dim.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(h, g, activation: str):
    if g is not None:
        if activation == "swiglu":
            return jax.nn.silu(g) * h
        return jax.nn.gelu(g) * h
    if activation == "gelu":
        return jax.nn.gelu(h)
    return jnp.maximum(h, 0.0)


def _kernel_gated(x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, *, activation, n_i):
    _body(x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, activation, n_i)


def _kernel_plain(x_ref, up_ref, down_ref, o_ref, acc_ref, *, activation, n_i):
    _body(x_ref, up_ref, None, down_ref, o_ref, acc_ref, activation, n_i)


def _body(x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, activation, n_i):
    ib = pl.program_id(2)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)          # (bx, M)
    h = jnp.dot(x, up_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)          # (bx, bi)
    g = None
    if gate_ref is not None:
        g = jnp.dot(x, gate_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    h = _act(h, g, activation)
    acc_ref[...] += jnp.dot(h, down_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)  # (bx, M)

    @pl.when(ib == n_i - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_ffn_kernel(x: jax.Array, w_up: jax.Array, w_gate: Optional[jax.Array],
                   w_down: jax.Array, activation: str = "swiglu",
                   block_x: int = 128, block_i: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x: (E, X, M) dispatched tokens; returns (E, X, M)."""
    E, X, M = x.shape
    I = w_up.shape[-1]
    bx = min(block_x, X)
    bi = min(block_i, I)
    assert X % bx == 0 and I % bi == 0, (X, bx, I, bi)
    n_i = I // bi
    grid = (E, X // bx, n_i)

    in_specs = [
        pl.BlockSpec((1, bx, M), lambda e, xb, ib: (e, xb, 0)),
        pl.BlockSpec((1, M, bi), lambda e, xb, ib: (e, 0, ib)),
    ]
    args = [x, w_up]
    if w_gate is not None:
        in_specs.append(pl.BlockSpec((1, M, bi), lambda e, xb, ib: (e, 0, ib)))
        args.append(w_gate)
    in_specs.append(pl.BlockSpec((1, bi, M), lambda e, xb, ib: (e, ib, 0)))
    args.append(w_down)

    kernel = functools.partial(
        _kernel_gated if w_gate is not None else _kernel_plain,
        activation=activation, n_i=n_i)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bx, M), lambda e, xb, ib: (e, xb, 0)),
        out_shape=jax.ShapeDtypeStruct((E, X, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((bx, M), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
