"""jit'd public wrapper for the ragged grouped-FFN kernel.

Forward runs the Pallas kernel on TPU; on every other backend the
pure-jnp sorted-gather reference runs instead (the issue of streaming
weight tiles per row block is a TPU memory-system question — interpret
mode would only re-derive the reference semantics, more slowly).  The
wrapper carries a ``custom_vjp`` whose backward always differentiates
the reference (same math, f32 accumulation), so the dropless backend is
trainable on any platform.

``block_expert`` is integer routing metadata: its cotangent is the empty
``float0`` tangent type, never a real gradient.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moe_dropless.kernel import ragged_ffn_kernel
from repro.kernels.moe_dropless.ref import ragged_ffn_ref


def pick_block_rows(n_choices: int, num_experts: int, max_block: int = 128) -> int:
    """Row-block granularity for the ragged layout: largest power of two
    <= max_block whose worst-case segment padding (one block per expert)
    does not exceed the real rows.  Keeps small-T execution — decode
    steps route a handful of choices — from paying E*max_block padded
    rows; floor 8 preserves TPU sublane alignment."""
    bx = max_block
    while bx > 8 and num_experts * bx > max(n_choices, 1):
        bx //= 2
    return bx


def padded_rows(n_choices: int, num_experts: int, block_rows: int) -> int:
    """Static row count of the sorted+padded ragged buffer (the same
    bound RoutingPlan._ragged_index_view allocates)."""
    n = n_choices + num_experts * (block_rows - 1)
    return -(-n // block_rows) * block_rows


# Mosaic's default scoped-VMEM limit is 16 MiB; the budget leaves room
# for the compiler's own scratch.  Compiled for v5e: a 13 MiB working set
# (granite widths, f32, bx=128, bi=256) fits, 18.3 MiB (bx=8, bi=512)
# is refused.
VMEM_BUDGET_BYTES = 14 * 2**20


def kernel_vmem_bytes(block_x: int, block_i: int, d_model: int, n_mats: int,
                      x_bytes: int, w_bytes: int) -> int:
    """Per-step VMEM working set of ``ragged_ffn_kernel``: double-buffered
    x / out row tiles and weight tiles, the f32 accumulator, and the f32
    (bx, bi) intermediates."""
    rows = 2 * 2 * block_x * d_model * x_bytes + block_x * d_model * 4
    weights = 2 * n_mats * d_model * block_i * w_bytes
    inter = 2 * block_x * block_i * 4
    return rows + weights + inter


def pick_block_i(block_x: int, d_model: int, d_ff: int, n_mats: int,
                 x_bytes: int, w_bytes: int, max_block: int = 512) -> int:
    """Largest intermediate tile <= max_block that divides d_ff, is
    lane-aligned (a multiple of 128, or d_ff itself) and whose working
    set fits ``VMEM_BUDGET_BYTES``.  When none fits, the smallest aligned
    tile is returned and the compiler has the last word."""
    tiles = [t for t in range(d_ff, 0, -1)
             if d_ff % t == 0 and (t == d_ff or t % 128 == 0)]
    tiles = [t for t in tiles if t <= max_block] or tiles[-1:]
    for t in tiles:
        if kernel_vmem_bytes(block_x, t, d_model, n_mats, x_bytes,
                             w_bytes) <= VMEM_BUDGET_BYTES:
            return t
    return tiles[-1]


def _run(x, block_expert, w_up, w_gate, w_down, activation, block_x, block_i):
    if jax.default_backend() != "tpu":
        return ragged_ffn_ref(x, block_expert, w_up, w_gate, w_down, activation)
    bi = pick_block_i(block_x, x.shape[1], w_up.shape[-1],
                      3 if w_gate is not None else 2, x.dtype.itemsize,
                      w_up.dtype.itemsize, max_block=block_i)
    return ragged_ffn_kernel(x, block_expert, w_up, w_gate, w_down, activation,
                             block_x=block_x, block_i=bi)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ragged_ffn(x, block_expert, w_up, w_gate, w_down, activation, block_x, block_i):
    return _run(x, block_expert, w_up, w_gate, w_down, activation, block_x, block_i)


def _ragged_ffn_fwd(x, block_expert, w_up, w_gate, w_down, activation, block_x, block_i):
    y = _run(x, block_expert, w_up, w_gate, w_down, activation, block_x, block_i)
    return y, (x, block_expert, w_up, w_gate, w_down)


def _ragged_ffn_bwd(activation, block_x, block_i, res, g):
    x, block_expert, w_up, w_gate, w_down = res
    ct_be = np.zeros(block_expert.shape, jax.dtypes.float0)
    if w_gate is None:
        _, vjp = jax.vjp(
            lambda xx, up, down: ragged_ffn_ref(xx, block_expert, up, None,
                                                down, activation),
            x, w_up, w_down)
        dx, dup, ddown = vjp(g)
        return dx, ct_be, dup, None, ddown
    _, vjp = jax.vjp(
        lambda xx, up, gate, down: ragged_ffn_ref(xx, block_expert, up, gate,
                                                  down, activation),
        x, w_up, w_gate, w_down)
    dx, dup, dgate, ddown = vjp(g)
    return dx, ct_be, dup, dgate, ddown


_ragged_ffn.defvjp(_ragged_ffn_fwd, _ragged_ffn_bwd)


@partial(jax.jit, static_argnames=("activation", "block_x", "block_i"))
def ragged_ffn(x: jax.Array, block_expert: jax.Array, w_up: jax.Array,
               w_gate: Optional[jax.Array], w_down: jax.Array,
               activation: str = "swiglu", block_x: int = 128,
               block_i: int = 512) -> jax.Array:
    return _ragged_ffn(x, block_expert, w_up, w_gate, w_down, activation,
                       block_x, block_i)


__all__ = ["ragged_ffn", "ragged_ffn_ref", "pick_block_rows", "padded_rows",
           "pick_block_i", "kernel_vmem_bytes"]
