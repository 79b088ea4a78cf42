"""Training stack: optimizers, compression, checkpoint/restart, fault
tolerance, Adafactor memory sublinearity."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, TrainConfig
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import SyntheticLM
from repro.distributed.fault import StepWatchdog, run_with_restarts
from repro.models.registry import get_family
from repro.nn import init
from repro.optim import make_optimizer, warmup_constant
from repro.train.state import init_train_state
from repro.train.trainer import make_train_step


def _tiny_cfg():
    return ModelConfig(num_layers=2, d_model=48, num_heads=4, num_kv_heads=2,
                       d_ff=64, vocab_size=101, dtype="float32",
                       moe=MoEConfig(num_experts=4, routing="prototype",
                                     num_prototypes=2, group_size=64))


def _setup(tc, cfg=None, seed=0):
    cfg = cfg or _tiny_cfg()
    fam = get_family(cfg)
    params = init(fam.specs(cfg), jax.random.PRNGKey(seed))
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
    state = init_train_state(params, opt, tc.grad_compression)
    step = jax.jit(make_train_step(cfg, tc, opt))
    return cfg, state, step


@pytest.mark.parametrize("opt,lr", [("adamw", 1e-2), ("adafactor", 1e-1)])
def test_loss_decreases(opt, lr):
    tc = TrainConfig(optimizer=opt, learning_rate=lr, warmup_steps=5)
    cfg, state, step = _setup(tc)
    pipe = SyntheticLM(cfg.vocab_size, batch=8, seq_len=32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    first = last = None
    for i in range(25):
        state, m = step(state, batch)
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.9


def test_microbatch_equivalence():
    """grad accumulation over 2 microbatches == full batch (linear grads)."""
    tc1 = TrainConfig(optimizer="adamw", learning_rate=1e-3, microbatches=1)
    tc2 = TrainConfig(optimizer="adamw", learning_rate=1e-3, microbatches=2)
    cfg, state1, step1 = _setup(tc1)
    _, state2, step2 = _setup(tc2)
    pipe = SyntheticLM(cfg.vocab_size, batch=8, seq_len=16, seed=1)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    s1, m1 = step1(state1, batch)
    s2, m2 = step2(state2, batch)
    # parameters end up close (not exact: loss normalisation per microbatch)
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               s1.params, s2.params)
    assert max(jax.tree_util.tree_leaves(d)) < 5e-4


def test_adafactor_state_sublinear():
    cfg = _tiny_cfg()
    fam = get_family(cfg)
    params = init(fam.specs(cfg), jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    ada = make_optimizer(TrainConfig(optimizer="adafactor"), warmup_constant(1e-3))
    adam = make_optimizer(TrainConfig(optimizer="adamw"), warmup_constant(1e-3))
    n_ada = sum(s.size for s in jax.tree_util.tree_leaves(ada.init(params)))
    n_adam = sum(s.size for s in jax.tree_util.tree_leaves(adam.init(params)))
    assert n_adam == 2 * n_params
    assert n_ada < 0.25 * n_adam  # sublinear second moments


def test_checkpoint_restart_exact_resume():
    """Train 6 steps straight == train 3, checkpoint, restore, train 3."""
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    cfg, state, step = _setup(tc)
    pipe = SyntheticLM(cfg.vocab_size, batch=4, seq_len=16, seed=2)
    batches = [{k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()} for i in range(6)]

    s = state
    for i in range(6):
        s, _ = step(s, batches[i])
    straight = s

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        s = state
        for i in range(3):
            s, _ = step(s, batches[i])
        ck.save(3, s)
        template = jax.eval_shape(lambda: s)
        restored = ck.restore(3, template)
        for i in range(3, 6):
            restored, _ = step(restored, batches[i])

    for a, b in zip(jax.tree_util.tree_leaves(straight.params),
                    jax.tree_util.tree_leaves(restored.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_checkpoint_async_and_keep_last():
    tc = TrainConfig()
    cfg, state, step = _setup(tc)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        for s_i in [1, 2, 3, 4]:
            ck.save_async(s_i, {"x": jnp.full((4,), s_i)})
        ck.wait()
        assert ck.all_steps() == [3, 4]
        got = ck.restore(4, jax.eval_shape(lambda: {"x": jnp.zeros((4,))}))
        np.testing.assert_array_equal(np.asarray(got["x"]), 4.0)


def test_run_with_restarts_resumes_after_failure():
    attempts = []

    def resume():
        return len(attempts)  # "latest checkpoint" advances per attempt

    def loop(start):
        attempts.append(start)
        if len(attempts) < 3:
            raise RuntimeError("simulated worker failure")
        return 99

    assert run_with_restarts(loop, resume, max_restarts=5) == 99
    assert attempts == [0, 1, 2]


def test_watchdog_flags_stragglers():
    wd = StepWatchdog(threshold=2.0, warmup=2)
    for _ in range(10):
        wd.observe(1.0)
    assert wd.observe(5.0) is True
    assert wd.straggler_events == 1
    assert wd.observe(1.0) is False


def test_grad_compression_int8_error_feedback_converges():
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-2, grad_compression="int8")
    cfg, state, step = _setup(tc)
    pipe = SyntheticLM(cfg.vocab_size, batch=8, seq_len=32, seed=3)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    first = last = None
    # 35 steps: at 25 this sits right on the 10% bar — headroom, not a
    # weaker claim.
    for _ in range(35):
        state, m = step(state, batch)
        first = first if first is not None else float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.9
