"""Token -> expert routing, dispatched through the pluggable Router API.

The strategies themselves live in :mod:`repro.core.routers` (one module
per router, registered by name); this module is the stable entry point:

* :func:`route` — look up ``cfg.routing`` in the registry and build a
  :class:`~repro.core.routers.base.RoutingPlan` (the compact index view;
  dense GShard ``combine``/``dispatch`` tensors are lazy properties).
* ``topk_gating`` / ``prototype_gating`` — the paper's gating functions
  (Figs. 7-8) operating on precomputed logits, kept for tests and direct
  experimentation.

Tokens are routed inside *groups* (the ``d``/worker dimension in the
paper's pseudo-code generalised to G groups): capacity and the
position-in-expert cumulative sum are per group, matching GShard
semantics where each worker routes its local tokens.

All routing math runs in float32 regardless of activation dtype.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.context import MoEContext
from repro.core.routers import available_routers, get_router, register_router
from repro.core.routers.base import RoutingPlan
from repro.core.routers.expert_choice import expert_choice_plan
from repro.core.routers.hashed import hash_plan
from repro.core.routers.prototype import prototype_logits, prototype_plan
from repro.core.routers.topk import topk_logits, topk_plan

# Back-compat aliases (pre-Router-API names).
RoutingResult = RoutingPlan
router_logits_topk = topk_logits
router_logits_prototype = prototype_logits
topk_gating = topk_plan
prototype_gating = prototype_plan


def route(
    x: jax.Array,                    # (G, T, M) tokens (any float dtype)
    router_w: Optional[jax.Array],   # router weights, None for stateless routers
    cfg: MoEConfig,
    capacity: int,
    ctx: Optional[MoEContext] = None,  # (G, T)-grouped side information
    bias: Optional[jax.Array] = None,  # the router's bias leaf, if it has one
) -> RoutingPlan:
    """Build the routing plan for ``cfg.routing`` via the registry.

    ``ctx`` (token ids / positions regrouped to the (G, T) layout, PRNG
    key, step, train flag) is optional side information; routers that
    don't consume it ignore it.
    """
    x32 = x.astype(jnp.float32)
    cd = jnp.float32 if cfg.combine_dtype == "float32" else jnp.dtype(x.dtype)
    router = get_router(cfg.routing)
    if bias is not None:
        return router.plan(x32, router_w, cfg, capacity, combine_dtype=cd,
                           ctx=ctx, bias=bias)
    return router.plan(x32, router_w, cfg, capacity, combine_dtype=cd, ctx=ctx)


__all__ = [
    "RoutingPlan", "RoutingResult", "route",
    "register_router", "get_router", "available_routers",
    "topk_gating", "prototype_gating",
    "topk_plan", "prototype_plan", "expert_choice_plan", "hash_plan",
    "router_logits_topk", "router_logits_prototype",
]
