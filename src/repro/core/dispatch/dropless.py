"""Capacity-free dropless dispatch: sorted ragged grouped GEMM.

Capacity-ful backends allocate an ``(E, C)`` slot buffer per group and
*drop* whatever overflows it — the paper's central quality/efficiency
lever, and the reason capacity-factor tuning exists at all.  This
backend removes the capacity dimension instead (MegaBlocks-style):

1. take the plan's :class:`~repro.core.routers.base.RaggedView` — valid
   choices sorted by expert id, each expert's segment padded to a
   multiple of ``block_rows`` so a row block never straddles experts;
2. gather the sorted token rows (``O(R*M)`` movement, R = valid choices
   + block padding — proportional to actual load, no ``gamma`` slack and
   no ``(G, T, E, C)`` intermediate anywhere);
3. run the expert FFN as a ragged/blocked grouped GEMM
   (``repro.kernels.moe_dropless``: Pallas scalar-prefetch kernel on
   TPU, sorted-gather reference elsewhere; ``custom_vjp`` so it trains);
4. combine by gate-weighted scatter-add back into token order.

With ``capacity_factor=None`` every routed choice is valid, so the
execution quality is exactly the capacity-infinity limit of the router.
With a finite capacity the plan's overflowed choices carry gate 0 and
empty rows, so outputs (including which tokens drop) match the einsum
reference bit-for-bit in assignment — the cross-backend contract holds.

Expert parallelism: under an expert-sharded ``Rules`` mesh (same
placement test as the ``alltoall`` backend), :func:`ragged_ep_dispatch`
runs *explicit* EP — a padded variable-size ``lax.all_to_all`` over the
ragged layout ships each expert shard exactly its own experts' sorted
row segments (``jax.lax.ragged_all_to_all`` would drop the padding; the
exchange is already O(load), never O(E*C)).  Where the groups do not
split over the device grid, the grouped FFN still runs under
``shard_map`` (GSPMD cannot partition a Pallas kernel; see
:func:`_ragged_ffn`); the gather and combine around it stay implicit
(GSPMD over the sharded group axis, like ``gather``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.context import MoEContext
from repro.core.dispatch import register_dispatcher
from repro.core.dispatch.alltoall import _expert_mesh_plan
from repro.core.routers.base import RoutingPlan
from repro.distributed.sharding import active_rules, shard
from repro.kernels.moe_dropless import ops as dropless_ops
from repro.kernels.moe_dropless.ops import pick_block_rows


def plan_block_rows(plan: RoutingPlan, max_block: int = 128) -> int:
    """Row-block granularity for a plan's ragged view: scales down with
    the choice count so segment padding never dwarfs real rows (a decode
    step routes a handful of choices; a training group routes thousands)."""
    if plan.token_at_slot is not None:
        n = plan.token_at_slot.shape[1] * plan.token_at_slot.shape[2]
    else:
        n = plan.expert_index.shape[1] * plan.expert_index.shape[2]
    return pick_block_rows(n, plan.num_experts, max_block)


def ragged_ep_dispatch(params, xg: jax.Array, plan: RoutingPlan,
                       cfg: ModelConfig, block_rows: int, placed) -> jax.Array:
    """Explicit expert parallelism over the ragged (sorted) layout.

    The :class:`~repro.core.routers.base.RaggedView` is expert-major with
    every segment boundary block-aligned, so the rows bound for expert
    shard ``s`` (local experts ``[s*E/ne, (s+1)*E/ne)``) form one
    contiguous block-aligned range ``[offsets[s*Epl], offsets[(s+1)*Epl])``
    per group.  Each device packs those ne ranges into a fixed ``(ne, R)``
    row budget (padding parked on a zero row — the variable-size
    all_to_all, per the ragged_all_to_all recipe on padded buffers), one
    ``lax.all_to_all`` ships them, the local-expert ragged FFN runs with
    *local* expert ids, and the reverse all_to_all + positional unpack
    restore the original layout for the usual gate-weighted scatter-add
    combine.

    Packing moves whole row *blocks* (segment starts and lengths are all
    multiples of ``block_rows``), so every FFN block holds exactly the
    rows it holds in the single-device layout — the grouped GEMM computes
    identical per-row results and the combine is bit-identical, which is
    what the mesh-parity serving tests assert end to end.
    """
    mesh, e_ax, dp_axes = placed
    ne = mesh.shape[e_ax]
    E = plan.num_experts
    epl = E // ne
    dt = cfg.activation_dtype
    G, T, M = xg.shape
    bx = block_rows
    rag = plan.ragged(bx)
    R = rag.token.shape[1]
    act = cfg.ffn_activation

    p_names = [k for k in ("up", "gate", "down") if k in params]
    p_local = {k: params[k] for k in p_names}
    w_spec = {k: P(e_ax) for k in p_names}
    grp = P((*dp_axes, e_ax))

    def run(p, xl, token, gate, offsets, bexp):
        Gl = xl.shape[0]
        toks = jnp.maximum(token, 0)                           # -1 -> row 0
        xs = jnp.take_along_axis(xl, toks[..., None], axis=1).astype(dt)
        e_row = jnp.repeat(bexp, bx, axis=1)                   # (Gl, R) global ids
        # destination boundaries: offsets at local-expert-count strides
        offd = offsets[:, ::epl]                               # (Gl, ne + 1)
        start, seglen = offd[:, :-1], offd[:, 1:] - offd[:, :-1]
        j = jnp.arange(R, dtype=offsets.dtype)
        src = start[:, :, None] + j                            # (Gl, ne, R)
        valid = j < seglen[:, :, None]
        srcp = jnp.where(valid, src, R)                        # park on pad row
        gi = jnp.arange(Gl)[:, None, None]
        xpad = jnp.concatenate([xs, jnp.zeros((Gl, 1, M), dt)], axis=1)
        buf = xpad[gi, srcp]                                   # (Gl, ne, R, M)
        epad = jnp.concatenate([e_row, jnp.zeros((Gl, 1), e_row.dtype)], axis=1)
        e_src = jnp.take_along_axis(
            epad, srcp.reshape(Gl, ne * R), axis=1).reshape(Gl, ne, R)
        dest = jnp.arange(ne, dtype=e_src.dtype)[None, :, None]
        ebuf = jnp.where(valid, e_src - dest * epl, 0)         # local expert ids
        # ship: leading axis = destination expert shard
        recv = jax.lax.all_to_all(jnp.swapaxes(buf, 0, 1), e_ax,
                                  split_axis=0, concat_axis=0, tiled=True)
        erecv = jax.lax.all_to_all(jnp.swapaxes(ebuf, 0, 1), e_ax,
                                   split_axis=0, concat_axis=0, tiled=True)
        out = dropless_ops.ragged_ffn(
            recv.reshape(ne * Gl * R, M),
            erecv.reshape(-1, bx)[:, 0].astype(jnp.int32),
            p["up"].astype(dt),
            p["gate"].astype(dt) if "gate" in p else None,
            p["down"].astype(dt), act, block_x=bx)
        back = jax.lax.all_to_all(out.reshape(ne, Gl, R, M), e_ax,
                                  split_axis=0, concat_axis=0, tiled=True)
        back = jnp.swapaxes(back, 0, 1)                        # (Gl, ne, R, M)
        back = jnp.where(valid[..., None], back, 0)
        res = jnp.zeros((Gl, R + 1, M), dt).at[gi, srcp].add(back)[:, :R]
        vals = res * gate[..., None].astype(dt)
        g2 = jnp.arange(Gl)[:, None]
        return jnp.zeros((Gl, T, M), dt).at[g2, toks].add(vals)

    args = (p_local, xg, rag.token, rag.gate, rag.expert_offsets,
            rag.block_expert)
    specs = (w_spec, grp, grp, grp, grp, grp)
    return jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=grp,
                         check_vma=False)(*args)


def _ragged_ffn(xs, block_expert, w, act: str, bx: int, rules):
    """The grouped FFN over sorted rows, under whatever mesh is active.

    GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh the
    call runs under ``shard_map`` with the rows replicated.  When the
    experts are sharded, each expert shard runs the row blocks of its own
    experts (other blocks point at a local expert and are zeroed) and a
    psum over the expert axis combines them: every row comes from exactly
    one shard, so the sum only adds zeros.  This is the path of a step
    with too few groups to split for :func:`ragged_ep_dispatch` — a decode
    step routes one group.
    """
    def ffn(p, x, be):
        return dropless_ops.ragged_ffn(x, be, p["up"], p.get("gate"),
                                       p["down"], act, block_x=bx)

    if rules is None or rules.mesh.size == 1:
        return ffn(w, xs, block_expert)
    mesh = rules.mesh
    e_ax = rules.params.get("expert")
    E = w["up"].shape[0]
    if isinstance(e_ax, tuple) or e_ax is None or E % mesh.shape[e_ax]:
        e_ax = None                               # experts replicated
    w_spec = {k: P(e_ax) for k in w}

    def run(p, x, be):
        if e_ax is None:
            return ffn(p, x, be)
        epl = p["up"].shape[0]
        lo = jax.lax.axis_index(e_ax) * epl
        mine = (be >= lo) & (be < lo + epl)
        y = ffn(p, x, jnp.where(mine, be - lo, 0))
        y = jnp.where(jnp.repeat(mine, bx)[:, None], y, 0)
        return jax.lax.psum(y, e_ax)

    return jax.shard_map(run, mesh=mesh, in_specs=(w_spec, P(), P()),
                         out_specs=P())(w, xs, block_expert)


def dropless_dispatch(params, xg: jax.Array, plan: RoutingPlan,
                      cfg: ModelConfig, block_rows: int = 0) -> jax.Array:
    dt = cfg.activation_dtype
    G, T, M = xg.shape
    block_rows = block_rows or plan_block_rows(plan)
    placed = _expert_mesh_plan(plan, G)
    if placed is not None:
        return ragged_ep_dispatch(params, xg, plan, cfg, block_rows, placed)
    rag = plan.ragged(block_rows)
    R = rag.token.shape[1]

    tok = jnp.maximum(rag.token, 0)                      # (G, R); -1 -> row 0
    xs = jnp.take_along_axis(xg, tok[..., None], axis=1).astype(dt)
    xs = shard(xs, "groups", None, None)

    w = {k: params[k].astype(dt) for k in ("up", "gate", "down")
         if k in params}
    out = _ragged_ffn(xs.reshape(G * R, M), rag.block_expert.reshape(-1), w,
                      cfg.ffn_activation, block_rows, active_rules())
    out = out.reshape(G, R, M)

    # Empty rows (padding / capacity-dropped choices) carry gate 0, so
    # their garbage outputs vanish in the scatter-add combine.
    vals = out * rag.gate[..., None].astype(dt)
    gi = jnp.arange(G)[:, None]
    return jnp.zeros((G, T, M), dt).at[gi, tok].add(vals)


@register_dispatcher
class DroplessDispatcher:
    name = "dropless"
    supports_dropless = True          # consulted by MoEConfig.__post_init__
    max_block_rows = 128              # ceiling for the adaptive block size

    def __call__(self, params, xg, plan: RoutingPlan, cfg: ModelConfig,
                 ctx: Optional[MoEContext] = None) -> jax.Array:
        return dropless_dispatch(
            params, xg, plan, cfg,
            block_rows=plan_block_rows(plan, self.max_block_rows))
