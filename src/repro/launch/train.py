"""End-to-end training driver.

Runs any registered arch (full or smoke config) on the local devices with
the full production stack: sharded params (pjit), ZeRO-1 optimizer state,
checkpoint/restart (atomic + async), straggler watchdog, seekable data.

  PYTHONPATH=src python -m repro.launch.train --arch m6-base --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt --routing prototype
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import TrainConfig
from repro.configs.registry import ALL_IDS, get_config, get_smoke_config
from repro.data.pipeline import make_pipeline
from repro.distributed.fault import StepWatchdog, run_with_restarts
from repro.distributed.sharding import make_rules, param_shardings, use_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.registry import get_family
from repro.nn import init as init_params
from repro.optim import make_optimizer, warmup_constant
from repro.train.state import TrainState, init_train_state
from repro.train.trainer import make_train_step

# the wall clock of the logged step time and token rate
_clock = time.perf_counter


def build(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.routing and cfg.moe.num_experts:
        if args.routing == "prototype":
            cfg = cfg.replace_moe(routing="prototype",
                                  num_prototypes=args.k)
        else:  # any other registry key routes k-way via top_k
            cfg = cfg.replace_moe(routing=args.routing, top_k=args.k)
    if args.capacity:
        cfg = cfg.replace_moe(capacity_mode=args.capacity)
    if args.moe_impl and cfg.moe.num_experts:
        cfg = cfg.replace_moe(impl=args.moe_impl)
    if args.capacity_factor is not None and cfg.moe.num_experts:
        cfg = cfg.replace_moe(capacity_factor=parse_capacity_factor(args.capacity_factor))
    if args.aux_loss_coef is not None:
        cfg = cfg.replace_moe(aux_loss_coef=args.aux_loss_coef)
    return cfg


def parse_capacity_factor(value: str):
    """'none' => dropless (capacity_factor=None); otherwise a float gamma."""
    return None if value.lower() in ("none", "dropless", "inf") else float(value)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="m6-base", choices=ALL_IDS)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--optimizer", default=None, choices=[None, "adamw", "adafactor"])
    from repro.core.dispatch import available_dispatchers
    from repro.core.routers import available_routers
    ap.add_argument("--routing", default=None,
                    choices=[None, *available_routers()])
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--capacity", default=None, choices=[None, "k", "one"])
    ap.add_argument("--capacity-factor", default=None,
                    help="gamma, or 'none' for dropless (requires a "
                         "capacity-free --moe-impl such as 'dropless')")
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, *available_dispatchers()])
    ap.add_argument("--aux-loss-coef", type=float, default=None)
    ap.add_argument("--grad-compression", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-file", default=None)
    ap.add_argument("--data", default=1, type=int, help="data mesh axis")
    ap.add_argument("--model", default=1, type=int, help="model mesh axis")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restarts from the latest checkpoint after a failed "
                         "step (needs --ckpt-dir: without a checkpoint a "
                         "restart would replay the same steps and fail the "
                         "same way)")
    # observability (repro.obs)
    ap.add_argument("--trace-out", default=None,
                    help="write per-train-step spans here: Chrome-trace "
                         "JSON (Perfetto), or span JSONL for .jsonl paths")
    ap.add_argument("--metrics-out", default=None,
                    help="write registry snapshots (one row per logged "
                         "step) as metrics JSONL")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of the train "
                         "loop into this directory")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = build(args)
    fam = get_family(cfg)
    tc = TrainConfig(
        optimizer=args.optimizer or ("adafactor" if cfg.name == "m6-1t" else "adamw"),
        learning_rate=args.lr or (5e-3 if args.optimizer == "adafactor" else 8e-5),
        grad_compression=args.grad_compression,
        microbatches=args.microbatches,
        warmup_steps=min(500, args.steps // 4 + 1),
    )
    mesh = make_debug_mesh(args.data, args.model)
    rules = make_rules(cfg, mesh)

    specs = fam.specs(cfg)
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
    step_fn = make_train_step(cfg, tc, opt)

    def wrapped(state, batch):
        with use_rules(rules):
            return step_fn(state, batch)

    p_shard = param_shardings(specs, rules)
    jit_step = jax.jit(wrapped, donate_argnums=(0,))

    pipeline = make_pipeline(cfg, args.batch, args.seq, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    watchdog = StepWatchdog()
    logs = []

    from repro.obs import Observability
    obs = Observability(tracing=args.trace_out is not None)
    if args.metrics_out:
        obs.metrics_every = max(args.log_every, 1)

    def fresh_state():
        params = init_params(specs, jax.random.PRNGKey(args.seed))
        params = jax.device_put(params, p_shard)
        return init_train_state(params, opt, tc.grad_compression)

    def resume_step():
        if ckpt is None or ckpt.latest_step() is None:
            return 0
        return ckpt.latest_step()

    def loop(start_step: int) -> int:
        state = fresh_state()
        if ckpt is not None and start_step > 0:
            state = ckpt.restore(start_step, jax.eval_shape(lambda: state))
        t_tokens = args.batch * args.seq
        reg = obs.metrics
        # a log step reports the interval since the previous one (or the
        # loop's start), read after its device_get: dispatch is
        # asynchronous, so one step's own host time says nothing
        t_log, tokens, steps = _clock(), 0, 0
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in pipeline.batch_at(step).items()}
            with obs.tracer.span("train_step", cat="train", step=step,
                                 tokens=t_tokens):
                state, metrics = jit_step(state, batch)
            reg.counter("train_steps_total").inc()
            reg.counter("train_tokens_total").inc(t_tokens)
            tokens, steps = tokens + t_tokens, steps + 1
            if step % args.log_every == 0 or step == args.steps - 1:
                m = {k: float(np.mean(jax.device_get(v))) for k, v in metrics.items()}
                now = _clock()
                interval = now - t_log
                rate, dt = tokens / interval, interval / steps
                t_log, tokens, steps = now, 0, 0
                watchdog.observe(dt)
                m.update(step=step, step_time_s=round(dt, 3),
                         tokens_per_s=round(rate, 1))
                logs.append(m)
                for key in ("loss", "ce", "moe_cv", "moe_dropped_fraction",
                            "moe_aux_loss", "moe_z_loss"):
                    if key in m:
                        reg.gauge(f"train_{key}").set(m[key])
                reg.gauge("train_tokens_per_s").set(rate)
                reg.histogram("train_step_ms").observe(dt * 1e3)
                if args.metrics_out:
                    obs.metrics_row(step=step)
                print(f"step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                      f"cv {m.get('moe_cv', 0):.3f} drop {m.get('moe_dropped_fraction', 0):.3f} "
                      f"({m['tokens_per_s']:.0f} tok/s)", flush=True)
            if ckpt is not None and step > 0 and step % args.ckpt_every == 0:
                ckpt.save_async(step, state)
        if ckpt is not None:
            ckpt.wait()
            ckpt.save(args.steps, state)
        return args.steps

    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        with mesh:
            run_with_restarts(loop, resume_step,
                              max_restarts=args.max_restarts if ckpt else 0)
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()

    if args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            obs.tracer.write_jsonl(args.trace_out)
        else:
            obs.tracer.write_chrome_trace(args.trace_out)
    if args.metrics_out:
        obs.write_metrics_jsonl(args.metrics_out)
    if args.log_file:
        with open(args.log_file, "w") as f:
            json.dump(logs, f, indent=1)
    return logs


if __name__ == "__main__":
    main()
