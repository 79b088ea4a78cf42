"""Pallas TPU kernel: ragged/blocked grouped FFN over a sorted token buffer.

The dropless execution path sorts tokens by expert id and pads each
expert's segment to a multiple of ``block_x`` rows (the RaggedView
layout), so every fixed-size row block belongs to exactly one expert.
This kernel is the MegaBlocks idea on TPU: iterate row blocks over the
sorted buffer and look the block's expert id up from a scalar-prefetched
``block_expert`` array — the weight BlockSpec index map reads it from
SMEM, so each block DMAs only its own expert's weight tiles.  There is
no capacity dimension anywhere: compute is proportional to the number of
sorted rows, not to ``E * C``.

  grid = (N/bx, I/bi)  — row blocks outer; the intermediate dimension is
                         innermost (arbitrary), accumulated in VMEM
                         scratch exactly like the capacity-ful
                         ``moe_ffn`` kernel.

VMEM working set per step matches ``repro.kernels.moe_ffn`` (the weight
tiles are per-block instead of per-expert-grid-step, but the same
shapes); consecutive blocks of the same expert re-use the resident tiles
because their index maps resolve to the same blocks.
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


def _kernel_gated(be_ref, x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, *,
                  activation, n_i):
    _body(x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, activation, n_i)


def _kernel_plain(be_ref, x_ref, up_ref, down_ref, o_ref, acc_ref, *,
                  activation, n_i):
    _body(x_ref, up_ref, None, down_ref, o_ref, acc_ref, activation, n_i)


def _body(x_ref, up_ref, gate_ref, down_ref, o_ref, acc_ref, activation, n_i):
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)        # (bx, M)
    h = jnp.dot(x, up_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)              # (bx, bi)
    g = None
    if gate_ref is not None:
        g = jnp.dot(x, gate_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    h = _act(h, g, activation)
    acc_ref[...] += jnp.dot(h, down_ref[0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)  # (bx, M)

    @pl.when(ib == n_i - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def ragged_ffn_kernel(x: jax.Array, block_expert: jax.Array, w_up: jax.Array,
                      w_gate: Optional[jax.Array], w_down: jax.Array,
                      activation: str = "swiglu", block_x: int = 128,
                      block_i: int = 512, interpret: bool = False) -> jax.Array:
    """x: (N, M) sorted token rows, N % block_x == 0; block_expert:
    (N/block_x,) int32 expert id per row block.  Returns (N, M)."""
    N, M = x.shape
    E, _, I = w_up.shape
    bx = block_x
    bi = min(block_i, I)
    assert N % bx == 0 and I % bi == 0, (N, bx, I, bi)
    n_i = I // bi
    nb = N // bx
    assert block_expert.shape == (nb,), (block_expert.shape, nb)

    in_specs = [
        pl.BlockSpec((bx, M), lambda b, ib, be: (b, 0)),
        pl.BlockSpec((1, M, bi), lambda b, ib, be: (be[b], 0, ib)),
    ]
    args = [x, w_up]
    if w_gate is not None:
        in_specs.append(pl.BlockSpec((1, M, bi), lambda b, ib, be: (be[b], 0, ib)))
        args.append(w_gate)
    in_specs.append(pl.BlockSpec((1, bi, M), lambda b, ib, be: (be[b], ib, 0)))
    args.append(w_down)

    kernel = functools.partial(
        _kernel_gated if w_gate is not None else _kernel_plain,
        activation=activation, n_i=n_i)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, n_i),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bx, M), lambda b, ib, be: (b, 0)),
        scratch_shapes=[pltpu.VMEM((bx, M), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # under shard_map the output varies over whatever axes the
        # operands vary over
        out_shape=jax.ShapeDtypeStruct(
            (N, M), x.dtype,
            vma=frozenset().union(*(jax.typeof(a).vma for a in args))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_expert, *args)
