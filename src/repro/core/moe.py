"""The MoE FFN layer (paper Fig. 7), as a thin composition of two
registries plus parameter specs and token grouping:

* **Routing** (:mod:`repro.core.routers`, keyed by ``MoEConfig.routing``)
  decides *which* expert gets which token and emits a compact index-view
  :class:`~repro.core.routers.base.RoutingPlan`.
* **Dispatch** (:mod:`repro.core.dispatch`, keyed by ``MoEConfig.impl``)
  decides *how* that plan executes: ``einsum`` (paper-faithful GShard
  one-hot einsums, dense ``(G,T,E,C)`` view, implicit GSPMD parallelism),
  ``gather`` (flat slot-id scatter/gather off the index view, O(k*T*M)
  token movement), ``pallas`` (gather dispatch + the Pallas grouped-GEMM
  expert-FFN kernel), ``alltoall`` (explicit expert parallelism:
  ``shard_map`` over the mesh's expert axis with ``lax.all_to_all``
  collectives — Fig. 7's system design written down as collectives
  rather than recovered by the compiler), and ``dropless``
  (capacity-free: the plan's sorted ragged view feeding a blocked
  grouped GEMM — with ``capacity_factor=None`` no token is ever
  dropped and no ``(E, C)`` buffer exists).

Every (router, dispatcher) pair composes: the plan is computed once, so
all backends execute the same assignment and are numerically
interchangeable — asserted forward and backward by the test-suite.

``moe_ffn_apply`` additionally accepts a
:class:`~repro.core.context.MoEContext` carrying token ids, absolute
positions, PRNG key, step, and train/eval mode.  The layer regroups the
per-sequence fields to the router's ``(G, T)`` layout and hands the
context to both registries, which is what lets the ``hash`` router hash
token *identity* (true Hash Layers) instead of position.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.context import MoEContext
from repro.core.dispatch import get_dispatcher
from repro.core.metrics import gate_entropy
from repro.core.routers import get_router
from repro.core.routers.base import index_load_metrics
from repro.core.routing import RoutingPlan, route
from repro.distributed.sharding import shard
from repro.models.layers import ffn_apply, ffn_specs
from repro.nn import ParamSpec, truncated_normal_init


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def moe_ffn_specs(cfg: ModelConfig, d_model: Optional[int] = None):
    """Expert stacks of the held experts (all of them unless the layer
    holds a share), the router at its full width, the router's bias
    where it has one, and the shared experts where the config has them."""
    m = cfg.moe
    d = d_model or cfg.d_model
    dff = cfg.d_ff
    E = m.held
    wdt = jnp.dtype(cfg.param_dtype)
    init = truncated_normal_init(cfg.initializer_range)
    specs = {
        "up": ParamSpec((E, d, dff), wdt, ("expert", "embed", "mlp"), init),
        "down": ParamSpec((E, dff, d), wdt, ("expert", "mlp", "embed"), init),
    }
    router = get_router(m.routing)
    spec = router.param_spec(m, d, init)
    if spec is not None:
        specs["router"] = spec
    if hasattr(router, "bias_spec"):
        specs["router_bias"] = router.bias_spec(m)
    if cfg.ffn_activation in ("swiglu", "geglu"):
        specs["gate"] = ParamSpec((E, d, dff), wdt, ("expert", "embed", "mlp"), init)
    if m.shared_d_ff:
        specs["shared"] = ffn_specs(cfg, m.shared_d_ff)
    return specs


def hold_share(plan: RoutingPlan, first: int, count: int) -> RoutingPlan:
    """The plan of a layer that holds experts [first, first + count) of
    the ``plan.num_experts`` it routed over: choices that land on them
    keep their gates (normalised over all of the token's choices) and
    take local expert ids; every other choice becomes invalid, so it is
    computed nowhere here and adds nothing.  Loads are the held
    experts'; the drop fraction's denominator is the choices held here."""
    local = plan.expert_index - first
    mine = (local >= 0) & (local < count)
    valid = plan.valid & mine
    expert_index = jnp.where(mine, local, 0).astype(jnp.int32)
    metrics = index_load_metrics(expert_index, valid, count,
                                 plan.expert_index.size)
    held = jnp.sum(mine.astype(jnp.float32))
    metrics.update(routed_choices=held, dropped_fraction=jnp.maximum(
        held - jnp.sum(metrics["expert_loads"]), 0.0) / jnp.maximum(held, 1.0))
    return dataclasses.replace(plan, expert_index=expert_index, valid=valid,
                               num_experts=count, metrics=metrics)


# ---------------------------------------------------------------------------
# Grouping
# ---------------------------------------------------------------------------

def group_tokens(x: jax.Array, m: MoEConfig) -> Tuple[jax.Array, int]:
    """(B,S,M) -> (G,T,M).  Group count is a divisor of B*S close to
    B*S/group_size so capacity semantics stay per-group (GShard)."""
    B, S, M = x.shape
    total = B * S
    target_groups = max(total // m.group_size, 1)
    g = _largest_divisor_leq(total, target_groups)
    return x.reshape(g, total // g, M), g


def _largest_divisor_leq(n: int, k: int) -> int:
    k = min(max(k, 1), n)
    for g in range(k, 0, -1):
        if n % g == 0:
            return g
    return 1


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def moe_ffn_apply(params, x, cfg: ModelConfig,
                  ctx: Optional[MoEContext] = None) -> Tuple[jax.Array, dict]:
    """x: (B, S, M) -> (y, aux) where aux carries losses + load metrics.

    ``ctx`` is optional — ``None`` means "no side information" and every
    router/dispatcher must cope (the pre-context signature).
    """
    m = cfg.moe
    B, S, M = x.shape
    xg, G = group_tokens(x, m)
    T = xg.shape[1]
    capacity = m.capacity(T)
    xg = shard(xg, "groups", None, None)
    gctx = ctx.grouped(G, T) if ctx is not None else None

    router_w = params.get("router")
    if router_w is not None:
        router_w = router_w.astype(jnp.float32)
    bias = params.get("router_bias")
    with jax.named_scope("moe_route"):
        plan = route(xg, router_w, m, capacity, ctx=gctx,
                     bias=None if bias is None else bias.astype(jnp.float32))
        if m.held_experts:
            plan = hold_share(plan, m.held_offset, m.held_experts)

    with jax.named_scope(f"moe_dispatch_{m.impl}"):
        y = get_dispatcher(m.impl)(params, xg, plan, cfg, ctx=gctx)

    y = y.reshape(B, S, M).astype(x.dtype)
    if m.shared_d_ff:
        with jax.named_scope("shared_experts"):
            y = y + ffn_apply(params["shared"], x, cfg).astype(y.dtype)
    aux = {
        "moe_aux_loss": plan.aux_loss,
        "moe_z_loss": plan.z_loss,
        "moe_cv": plan.metrics["cv"],
        "moe_dropped_fraction": plan.metrics["dropped_fraction"],
        # live telemetry (repro.obs): per-expert kept-choice counts, the
        # kept-gate entropy, and the drop denominator — all derived from
        # the plan the dispatcher actually executed
        "moe_expert_tokens":
            plan.metrics["expert_loads"].astype(jnp.float32),
        "moe_gate_entropy": gate_entropy(plan.gate, plan.valid),
        "moe_routed_choices": plan.metrics.get(
            "routed_choices",
            jnp.asarray(float(plan.expert_index.size), jnp.float32)),
    }
    if m.held_experts:
        # the routed choices this layer computes: those on its experts
        aux["moe_held_choices"] = plan.metrics["routed_choices"]
    return y, aux
