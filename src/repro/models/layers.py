"""Shared neural-net layers: norms, embeddings, RoPE, dense/GLU FFN.

Every module is a pair of pure functions:
  ``<name>_specs(cfg, ...) -> ParamSpec tree``
  ``<name>_apply(params, x, ...) -> array``
Mixed precision: parameters are stored in ``cfg.param_dtype`` and cast to
``cfg.dtype`` at use; norms and routers compute in float32.  The paged
serving engine (``repro.serving.continuous``) holds the ``param_dtype``
leaves already in ``cfg.dtype``, cast once when it is built, so there the
cast at use is a no-op.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import ParamSpec, ones_init, zeros_init, truncated_normal_init
from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_specs(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    specs = {"scale": ParamSpec((d,), jnp.float32, ("embed",), ones_init)}
    if cfg.norm == "layernorm":
        specs["bias"] = ParamSpec((d,), jnp.float32, ("embed",), zeros_init)
    return specs


def norm_apply(params, x, cfg: ModelConfig):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * params["scale"] + params["bias"]
    else:  # rmsnorm
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + cfg.norm_eps) * params["scale"]
    return y.astype(dtype)


def head_rmsnorm_apply(scale, x, eps: float):
    """Per-head RMSNorm over the last (head_dim) axis (qwen3 qk-norm)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def padded_vocab(vocab_size: int, multiple: int = 256) -> int:
    """Pad vocab to a multiple so the table shards evenly over `model`."""
    return -(-vocab_size // multiple) * multiple


def embedding_specs(cfg: ModelConfig):
    v = padded_vocab(cfg.vocab_size)
    init = truncated_normal_init(cfg.initializer_range)
    return {"table": ParamSpec((v, cfg.d_model), jnp.dtype(cfg.param_dtype), ("vocab", "embed"), init)}


def embedding_apply(params, token_ids, cfg: ModelConfig):
    table = params["table"].astype(cfg.activation_dtype)
    return jnp.take(table, token_ids, axis=0)


def unembed_apply(params, x, cfg: ModelConfig):
    """Logits over the *padded* vocab; padded entries are masked to -inf."""
    table = params["table"].astype(cfg.activation_dtype)
    logits = jnp.einsum("...d,vd->...v", x, table)
    v_pad = table.shape[0]
    if v_pad != cfg.vocab_size:
        mask = jnp.arange(v_pad) < cfg.vocab_size
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    return logits


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """Return (sin, cos) of shape positions.shape + (head_dim//2,)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., S, H, D); sin/cos: (..., S, D//2) broadcast over heads."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    s = sin[..., None, :]
    c = cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# Dense projections / FFN
# ---------------------------------------------------------------------------

def dense_specs(cfg: ModelConfig, d_in: int, d_out: int, axes=("embed", "mlp"), bias: bool = False):
    init = truncated_normal_init(cfg.initializer_range)
    specs = {"kernel": ParamSpec((d_in, d_out), jnp.dtype(cfg.param_dtype), axes, init)}
    if bias:
        specs["bias"] = ParamSpec((d_out,), jnp.float32, (axes[1],), zeros_init)
    return specs


def dense_apply(params, x, cfg: ModelConfig):
    w = params["kernel"].astype(cfg.activation_dtype)
    y = x @ w
    if "bias" in params:
        y = y + params["bias"].astype(y.dtype)
    return y


def _activation(name: str, x, gate=None):
    if name == "swiglu":
        return jax.nn.silu(x) * gate
    if name == "geglu":
        return jax.nn.gelu(x) * gate
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name}")


def ffn_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    specs = {
        "up": dense_specs(cfg, cfg.d_model, d_ff, ("embed", "mlp")),
        "down": dense_specs(cfg, d_ff, cfg.d_model, ("mlp", "embed")),
    }
    if cfg.ffn_activation in ("swiglu", "geglu"):
        specs["gate"] = dense_specs(cfg, cfg.d_model, d_ff, ("embed", "mlp"))
    return specs


def ffn_apply(params, x, cfg: ModelConfig):
    up = dense_apply(params["up"], x, cfg)
    gate = None
    if "gate" in params:
        # Note: HF convention names the silu() input "gate"; we match math,
        # not naming: act(gate_proj(x)) * up_proj(x).
        gate = up
        up = dense_apply(params["gate"], x, cfg)
    h = _activation(cfg.ffn_activation, up, gate)
    return dense_apply(params["down"], h, cfg)
