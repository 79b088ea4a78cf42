"""Sigmoid scoring with a learned correction bias (DeepSeek-V3's
``noaux_tc`` with one group).

Each expert's score is ``s = sigmoid(x · W_r)``; a token's ``k`` experts
are the top ``k`` of ``s + b``, where ``b`` is the correction bias that
balances load in place of an auxiliary loss.  The bias only selects:
the gates are the unbiased ``s`` of the chosen experts, divided by
their sum where ``normalize_gates`` is set, then multiplied by
``routed_scaling_factor``.  So per-token gate mass is the scaling
factor, not 1.

The scores are computed in full float32 (``Precision.HIGHEST``), as
DeepSeek-V3's gate computes them: at the TPU's default precision a
float32 product runs as one bfloat16 pass, which rounds the router's
weights before they choose among near-tied sigmoid scores.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.routers import base, register_router
from repro.core.routers.base import RoutingPlan
from repro.nn import ParamSpec, zeros_init


def sigmoid_bias_plan(logits: jax.Array, bias: jax.Array, cfg: MoEConfig,
                      capacity: int, combine_dtype=jnp.float32) -> RoutingPlan:
    """Plan from precomputed logits (G, T, E) and the bias (E,)."""
    G, T, E = logits.shape
    k = cfg.top_k
    scores = jax.nn.sigmoid(logits)
    _, choice = jax.lax.top_k(scores + bias, k)                   # (G,T,k)
    gate = jnp.take_along_axis(scores, choice, axis=-1)
    if cfg.normalize_gates:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg.routed_scaling_factor

    count = jnp.zeros((G, E), jnp.float32)
    slots = []
    for j in range(k):                  # buffer positions, choice rank order
        mask = base.one_hot_f32(choice[..., j], E)
        pos, count = base.slot_positions(mask, count, token_axis=1)
        slots.append(pos.astype(jnp.int32))
    slot_index = jnp.stack(slots, axis=-1)
    expert_index = choice.astype(jnp.int32)
    valid = slot_index < capacity
    zl = base.z_loss(logits, cfg.router_z_loss_coef)
    metrics = base.index_load_metrics(expert_index, valid, E, G * T * k)
    return RoutingPlan(expert_index, slot_index, gate, valid, E, capacity,
                       jnp.zeros((), jnp.float32), zl, metrics, combine_dtype)


@register_router
class SigmoidBiasRouter:
    name = "sigmoid_bias"

    def param_spec(self, m: MoEConfig, d_model: int, init):
        return ParamSpec((d_model, m.num_experts), jnp.float32,
                         ("embed", "expert"), init)

    def bias_spec(self, m: MoEConfig):
        return ParamSpec((m.num_experts,), jnp.float32, ("expert",),
                         zeros_init)

    def plan(self, x32, w, m: MoEConfig, capacity: int,
             combine_dtype=jnp.float32, ctx=None, bias=None) -> RoutingPlan:
        logits = jnp.einsum("gtm,me->gte", x32, w.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        if bias is None:
            bias = jnp.zeros((m.num_experts,), jnp.float32)
        return sigmoid_bias_plan(logits, bias, m, capacity, combine_dtype)
