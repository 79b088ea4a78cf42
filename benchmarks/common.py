"""Shared benchmark helpers."""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import make_pipeline
from repro.models.registry import get_family
from repro.nn import init
from repro.optim import make_optimizer, warmup_constant
from repro.train.state import init_train_state
from repro.train.trainer import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments")


def bench_config(layers=3, d_model=128, d_ff=256, experts=16, vocab=4096,
                 **moe_kw) -> ModelConfig:
    """CPU-scale stand-in for the paper's 'base' MoE model: same topology
    (MoE FFN every layer, LayerNorm/gelu/learned positions), reduced dims."""
    from repro.configs.base import MoEConfig

    moe = dict(num_experts=experts, routing="topk", top_k=1, group_size=256,
               capacity_factor=1.25, aux_loss_coef=0.0)
    moe.update(moe_kw)
    return ModelConfig(
        name="bench", family="decoder_lm", num_layers=layers, d_model=d_model,
        num_heads=4, num_kv_heads=4, d_ff=d_ff, vocab_size=vocab,
        max_seq_len=512, norm="layernorm", ffn_activation="gelu",
        pos_embed="learned", tie_embeddings=True, dtype="float32",
        remat=False, moe=MoEConfig(**moe))


# The ablation-grid helper is shared with the paper configs — one
# definition keeps benchmark cells and config variants in sync.
from repro.configs.m6 import variant  # noqa: E402,F401


def train_run(cfg: ModelConfig, steps: int, batch: int, seq: int, lr=3e-3,
              seed=0, log_every=1) -> List[Dict]:
    fam = get_family(cfg)
    tc = TrainConfig(optimizer="adamw", learning_rate=lr,
                     warmup_steps=max(steps // 10, 1))
    params = init(fam.specs(cfg), jax.random.PRNGKey(seed))
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
    state = init_train_state(params, opt, tc.grad_compression)
    step = jax.jit(make_train_step(cfg, tc, opt))
    pipe = make_pipeline(cfg, batch, seq, seed=seed)
    logs = []
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        t0 = time.time()
        state, m = step(state, b)
        m["loss"].block_until_ready()
        if i % log_every == 0 or i == steps - 1:
            logs.append({"step": i, "loss": float(m["loss"]), "ce": float(m["ce"]),
                         "cv": float(jnp.mean(m.get("moe_cv", jnp.zeros(())))),
                         "cv_per_layer": [float(x) for x in jnp.atleast_1d(
                             m.get("moe_cv", jnp.zeros(())))],
                         "dropped": float(jnp.mean(m.get("moe_dropped_fraction",
                                                         jnp.zeros(())))),
                         "t": time.time() - t0})
    return logs


def time_step(cfg: ModelConfig, batch: int, seq: int, iters: int = 8, seed=0) -> Dict:
    """Median wall-clock per train step (ms), post-warmup."""
    fam = get_family(cfg)
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    params = init(fam.specs(cfg), jax.random.PRNGKey(seed))
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, 10))
    state = init_train_state(params, opt, tc.grad_compression)
    step = jax.jit(make_train_step(cfg, tc, opt))
    pipe = make_pipeline(cfg, batch, seq, seed=seed)
    b = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    state, m = step(state, b)  # compile + warmup
    m["loss"].block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.time()
        state, m = step(state, b)
        m["loss"].block_until_ready()
        times.append((time.time() - t0) * 1e3)
    times.sort()
    return {"ms_per_step": times[len(times) // 2], "min_ms": times[0]}


def train_flops(cfg: ModelConfig, batch: int, seq: int) -> float:
    """Compiled-HLO FLOPs of one (unrolled) train step — Table 1's metric."""
    from repro.train.losses import total_loss
    from repro.nn import abstract
    from repro.configs.base import ShapeConfig

    cfgp = cfg.replace(scan_layers=False, remat=False)
    fam = get_family(cfgp)
    shape = ShapeConfig("probe", seq_len=seq, global_batch=batch, kind="train")
    params = abstract(fam.specs(cfgp))
    b = fam.input_specs(cfgp, shape)

    def f(p, bb):
        logits, aux = fam.forward(p, bb, cfgp)
        return total_loss(logits, bb["labels"], aux)[0]

    c = jax.jit(jax.grad(f)).lower(params, b).compile()
    return float(c.cost_analysis()["flops"])


def save_result(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path
