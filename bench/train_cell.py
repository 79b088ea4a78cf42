"""One run of a training cell: set-up, the window, the correctness check.

Set-up builds one object, the program's jitted train step with its
state, and drives it from the seed through its first three steps, on
the same call and the same batch function as the window, each step its
own rows.  It reads what the check compares before step 4 can overwrite
it: each step's loss, the norm of each leaf's first gradient as the
optimizer got it (clipped), worked out from the optimizer's state after
step 1, and the norm of each leaf's change over the three steps.  The
same object then runs the warm-up and the window.  After the window the
state is freed and the float32 reference (``bench/check_train.py``)
takes the same three steps from the same weights and batches.
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from bench import check_train, program, seeds, weights
from bench.serve import TRACE_SECONDS
from bench.trace import annotate, load_events, reduce

STEP_PROGRAM = "train_step"
CHECK_STEPS = 3


def build(mc: dict):
    program.import_program()
    import jax

    from repro.configs.base import TrainConfig
    from repro.optim import make_optimizer, warmup_constant
    from repro.train.trainer import make_train_step

    cfg = program.model_config(mc, "train")
    tc = TrainConfig(optimizer=mc["optimizer"],
                     learning_rate=mc["learning_rate"],
                     warmup_steps=mc["warmup_steps"],
                     grad_clip_norm=mc["grad_clip_norm"])
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
    step = jax.jit(make_train_step(cfg, tc, opt), donate_argnums=(0,))
    return step, opt


def grad_norms_from_state(opt_state, names):
    """Per-leaf norms of the gradient the optimizer got at step 1, from
    Adafactor's second moments after it: at step 1 the decay is 0, so a
    matrix's row moments are mean(g², last axis) and a vector's moment
    is g² (plus 1e-30)."""
    import jax
    import jax.numpy as jnp

    def norm(v):
        if "vr" in v:
            n = v["vc"].shape[-1]
            return jnp.sqrt(jnp.sum(v["vr"]) * n)
        return jnp.sqrt(jnp.sum(v["v"]))

    leaves = jax.tree_util.tree_leaves(
        opt_state["v"], is_leaf=lambda x: isinstance(x, dict) and (
            "vr" in x or "v" in x))
    vals = jax.device_get(jax.jit(lambda ls: [norm(v) for v in ls])(leaves))
    return dict(zip(names, map(float, vals)))


def change_norms(params, w0: dict, names):
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(params)
    ref = [w0[n] for n in names]
    fn = jax.jit(lambda a, b: [jnp.linalg.norm((x - y).ravel())
                               for x, y in zip(a, b)])
    return dict(zip(names, map(float, jax.device_get(fn(leaves, ref)))))


def run(cell: str, seed: int, seconds: float, mc: dict, traffic: dict,
        limits: dict, gen, trace_dir, counter, setup_from: float) -> dict:
    import jax

    step, opt = build(mc)
    from repro.train.state import init_train_state

    make_w = weights.maker(mc)
    wkey = seeds.jax_key(seed, "weights")
    params = program.program_params(mc, make_w(wkey))
    names = program.leaf_names(mc, params)
    state = init_train_state(params, opt, "none")
    del params
    batch = gen.batch_fn(traffic, mc)
    bkey = seeds.jax_key(seed, "traffic.batches")

    losses, grad_norms = [], None
    for i in range(CHECK_STEPS):
        state, m = step(state, batch(bkey, i))
        losses.append(m["loss"])
        if i == 0:
            grad_norms = grad_norms_from_state(state.opt_state, names)
    w0 = make_w(wkey)
    deltas = change_norms(state.params, w0, names)
    del w0
    losses = [float(x) for x in jax.device_get(losses)]

    i = CHECK_STEPS
    for _ in range(traffic["warmup_steps"]):
        state, m = step(state, batch(bkey, i))
        i += 1
    jax.block_until_ready(m)
    compiles_open = counter.count
    span = TRACE_SECONDS if trace_dir else seconds
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        window = annotate("bench.window")
        window.__enter__()
    t_open = time.perf_counter()
    setup_s = t_open - setup_from
    n, pending = 0, []
    while True:
        with annotate("bench.step"):
            state, m = step(state, batch(bkey, i))
        i += 1
        n += 1
        pending.append(m["loss"])
        if len(pending) > 2:
            pending.pop(0).block_until_ready()
        if time.perf_counter() - t_open >= span:
            break
    jax.block_until_ready(pending)
    t_close = time.perf_counter()
    if trace_dir:
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = counter.count - compiles_open
    final_loss = float(pending[-1])

    run_rec = {"setup_s": setup_s,
               "train": {"steps": n, "t_open": t_open, "t_close": t_close,
                         "tokens_per_step": traffic["batch"] * traffic["text_len"],
                         "batch": traffic["batch"],
                         "text_len": traffic["text_len"]}}
    device = {}
    if trace_dir:
        tr = reduce(*load_events(trace_dir), STEP_PROGRAM, {})
        run_rec["trace"] = tr
        device = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    del state, m, pending
    gc.collect()

    program_readings = {"losses": losses, "grad_norms": grad_norms,
                        "change_norms": deltas}
    batches = [batch(bkey, j) for j in range(CHECK_STEPS)]
    ref = check_train.reference_readings(mc, make_w(wkey), batches)
    ref["change_norms"] = check_train.change_norms(ref.pop("params"),
                                                   make_w(wkey))
    worst = {}
    got = check_train.compare(program_readings, ref, worst)
    print(f"check: worst leaves {worst}; losses {losses} against "
          f"{ref['losses']}", file=sys.stderr)
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    finite = bool(np.isfinite(final_loss))
    checks["final_loss_finite"] = {"value": int(finite), "limit": 1}
    correct = (all(got[k] <= limits[k] for k in limits) and compiles == 0
               and finite)
    return {"run": run_rec, "correct": bool(correct), "attempted": n,
            "failed": 0 if finite else n, "device": device, "checks": checks,
            "program_readings": program_readings, "reference_readings": ref}
