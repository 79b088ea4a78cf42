"""Explicit expert-parallel dispatch: ``shard_map`` + ``lax.all_to_all``.

The ``einsum``/``gather`` dispatchers get expert parallelism *implicitly*
— they annotate buffers with ``with_sharding_constraint`` and trust GSPMD
to insert the Fig. 7 all-to-alls.  This backend writes the Switch
Transformer / GShard execution model down explicitly, the form that
carries trillion-parameter scale (paper Fig. 7: 1T params on 480 GPUs):

1. tokens (groups) are sharded over *every* mesh device — the data axes
   and the expert axis jointly — so each device routes only ``G/(Nd*Ne)``
   local groups;
2. each device scatters its local tokens into a full ``(E, rows, M)``
   buffer by the plan's flat slot ids (index view only — the dense
   ``(G,T,E,C)`` tensor is never built, structurally asserted in tests);
3. ``jax.lax.all_to_all`` over the expert mesh axis exchanges buffer
   slices: afterwards each device holds *its* ``E/Ne`` experts' rows from
   every peer;
4. the grouped FFN runs on the local expert shard of the weights;
5. a second ``all_to_all`` returns the rows, and each device combines its
   local tokens by gate-weighted gather (token-choice) or scatter-add
   (slot-major plans).

Because the :class:`RoutingPlan` is computed once outside the dispatcher,
per-group capacity semantics are *identical* to every other backend —
the collective schedule changes, the assignment does not — which is what
makes the cross-dispatcher equivalence tests exact.

When no expert-sharded mesh is active (no ``Rules`` context, experts not
divisible over the mesh axis, or a degenerate 1-way expert axis), the
backend degrades to the ``gather`` dispatch so the same config runs
unchanged on a laptop.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.context import MoEContext
from repro.core.dispatch import register_dispatcher
from repro.core.dispatch.base import expert_ffn
from repro.core.dispatch.gather import flat_slot_ids, gather_dispatch
from repro.core.routers.base import RoutingPlan
from repro.distributed.sharding import active_rules


def _expert_mesh_plan(plan: RoutingPlan, G: int) -> Optional[Tuple]:
    """(mesh, expert_axis, group_axes) when explicit EP can run, else None."""
    rules = active_rules()
    if rules is None:
        return None
    e_ax = rules.params.get("expert")
    if e_ax is None or isinstance(e_ax, tuple):
        return None  # unsharded experts (or multi-axis EP: not supported)
    mesh = rules.mesh
    ne = mesh.shape[e_ax]
    if ne <= 1 or plan.num_experts % ne != 0:
        return None
    dp = rules.acts.get("groups")
    dp_axes = () if dp is None else (dp if isinstance(dp, tuple) else (dp,))
    dp_axes = tuple(a for a in dp_axes if a != e_ax)
    nd = math.prod(mesh.shape[a] for a in dp_axes)
    if G % (nd * ne) != 0:
        return None  # tokens can't split across the joint device grid
    return mesh, e_ax, dp_axes


def alltoall_dispatch(params, xg: jax.Array, plan: RoutingPlan,
                      cfg: ModelConfig) -> jax.Array:
    placed = _expert_mesh_plan(plan, xg.shape[0])
    if placed is None:
        return gather_dispatch(params, xg, plan, cfg)
    mesh, e_ax, dp_axes = placed
    joint = (*dp_axes, e_ax)          # group axis sharded over ALL devices
    ne = mesh.shape[e_ax]
    dt = cfg.activation_dtype
    E, C = plan.num_experts, plan.capacity
    M = xg.shape[-1]

    p_names = [k for k in ("up", "gate", "down") if k in params]
    p_local = {k: params[k] for k in p_names}
    w_spec = {k: P(e_ax) for k in p_names}  # expert dim sharded, rest replicated
    grp = P(joint)

    if plan.token_at_slot is not None:
        # Slot-major plans (expert-choice): dispatch is a gather by
        # token_at_slot, combine a scatter-add over tokens.
        Cs = plan.token_at_slot.shape[-1]

        def run(p, xl, tok, gate):
            Gl, T, _ = xl.shape
            filled = tok >= 0                                  # (Gl,E,Cs)
            tok_safe = jnp.clip(tok, 0, T - 1).reshape(Gl, E * Cs, 1)
            buf = jnp.take_along_axis(xl, tok_safe, axis=1).reshape(Gl, E, Cs, M)
            buf = jnp.where(filled[..., None], buf, 0.0).astype(dt)
            buf = jnp.transpose(buf, (1, 0, 2, 3)).reshape(E, Gl * Cs, M)
            out = _exchange_ffn(p, buf)
            out = out.reshape(E, Gl, Cs, M).transpose(1, 0, 2, 3)  # (Gl,E,Cs,M)
            g = jnp.where(filled, gate, 0.0).astype(dt)
            vals = (out * g[..., None]).reshape(Gl, E * Cs, M)
            gi = jnp.arange(Gl)[:, None]
            return jnp.zeros((Gl, T, M), dt).at[gi, tok_safe[..., 0]].add(vals)

        args = (p_local, xg, plan.token_at_slot, plan.gate_at_slot)
        specs = (w_spec, grp, grp, grp)
    else:

        def run(p, xl, flat_slot, gates):
            Gl = xl.shape[0]
            T = xl.shape[1]
            K = flat_slot.shape[1] // T
            n_slots = E * C
            gi = jnp.arange(Gl)[:, None]
            tok = jnp.repeat(jnp.arange(T), K)
            buf = jnp.zeros((Gl, n_slots + 1, M), dt)
            buf = buf.at[gi, flat_slot].add(xl[:, tok, :].astype(dt))
            buf = buf[:, :n_slots].reshape(Gl, E, C, M)
            buf = jnp.transpose(buf, (1, 0, 2, 3)).reshape(E, Gl * C, M)
            out = _exchange_ffn(p, buf)
            out = out.reshape(E, Gl, C, M).transpose(1, 0, 2, 3)
            out = out.reshape(Gl, n_slots, M)
            picked = jnp.take_along_axis(
                out, jnp.minimum(flat_slot, n_slots - 1)[..., None], axis=1)
            y = (picked * gates.astype(dt)[..., None]).reshape(Gl, T, K, M)
            return jnp.sum(y, axis=2)

        G, T, K = plan.expert_index.shape
        args = (p_local, xg, flat_slot_ids(plan),
                plan.masked_gate.reshape(G, T * K))
        specs = (w_spec, grp, grp, grp)

    def _exchange_ffn(p, buf):
        """(E, rows, M) local buffer -> all_to_all -> local-expert FFN ->
        all_to_all back.  rows-per-expert grows x ne in between (each peer
        contributes its shard of the tokens)."""
        recv = jax.lax.all_to_all(buf, e_ax, split_axis=0, concat_axis=1,
                                  tiled=True)                  # (E/ne, ne*rows, M)
        out = expert_ffn(p, recv, cfg)
        return jax.lax.all_to_all(out, e_ax, split_axis=1, concat_axis=0,
                                  tiled=True)                  # (E, rows, M)

    return jax.shard_map(run, mesh=mesh, in_specs=specs, out_specs=grp,
                         check_vma=False)(*args)


@register_dispatcher
class AllToAllDispatcher:
    name = "alltoall"
    # Dropless plans run the sorted-ragged machinery: explicit EP via the
    # padded variable-size all_to_all over the RaggedView when an
    # expert-sharded mesh is active, the GSPMD dropless path otherwise —
    # never the (E, C)-buffered exchange above, and never gather.
    supports_dropless = True

    def __call__(self, params, xg, plan: RoutingPlan, cfg: ModelConfig,
                 ctx: Optional[MoEContext] = None) -> jax.Array:
        if cfg.moe.dropless:
            from repro.core.dispatch.dropless import (
                dropless_dispatch,
                plan_block_rows,
            )

            return dropless_dispatch(params, xg, plan, cfg,
                                     block_rows=plan_block_rows(plan))
        return alltoall_dispatch(params, xg, plan, cfg)
