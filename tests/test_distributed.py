"""Sharding rules, ZeRO specs, HLO parsing, costs validation, and a
small-mesh end-to-end pjit train step (run through conftest's shared
`run_sub` fixture: a subprocess with 8 virtual devices, so the main test
process keeps 1 device)."""
import jax
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_smoke_config
from repro.distributed.costs import flops_for


def test_rules_divisibility_fallbacks(run_sub):
    """granite: 40 experts / 24 heads don't divide 16 -> replicated, with
    expert-TP fallback sharding the per-expert ffn dim instead."""
    code = """
    import jax
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.distributed.sharding import make_rules
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    # 6 experts / 5 heads do not divide the 4-way model axis (the granite-
    # on-16 situation, scaled to this 8-device test mesh)
    cfg = ModelConfig(num_heads=5, num_kv_heads=2, head_dim=10, d_model=60,
                      d_ff=32, moe=MoEConfig(num_experts=6, top_k=2))
    rules = make_rules(cfg, mesh)
    assert rules.params["expert"] is None, rules.params
    assert rules.params["mlp"] == "model"      # expert-TP fallback
    assert rules.params["heads"] is None       # 5*10 % 4 != 0
    from repro.configs.registry import get_config
    cfg2 = get_config("olmoe-1b-7b")           # 64 experts divide 4
    rules2 = make_rules(cfg2, mesh)
    assert rules2.params["expert"] == "model"
    assert rules2.params["mlp"] is None        # EP consumes the axis
    # pure-DP mode folds the model axis into DP
    rules3 = make_rules(cfg, mesh, expert_axis="dp")
    assert rules3.acts["batch"] == ("data", "model")
    assert rules3.params["mlp"] is None
    print("rules-ok")
    """
    assert "rules-ok" in run_sub(code)


def test_pjit_train_step_multidevice_matches_single(run_sub):
    """2x4 mesh pjit train step == single-device step (same batch/seed)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig, TrainConfig
    from repro.models.registry import get_family
    from repro.nn import init
    from repro.optim import make_optimizer, warmup_constant
    from repro.train.state import init_train_state
    from repro.train.trainer import make_train_step
    from repro.distributed.sharding import make_rules, param_shardings, use_rules
    from repro.launch.mesh import make_mesh

    cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                      d_ff=64, vocab_size=128, dtype="float32",
                      moe=MoEConfig(num_experts=8, routing="prototype",
                                    num_prototypes=2, group_size=32,
                                    capacity_factor=8.0))
    fam = get_family(cfg)
    tc = TrainConfig(optimizer="adamw", learning_rate=1e-3)
    params = init(fam.specs(cfg), jax.random.PRNGKey(0))
    opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
    step = make_train_step(cfg, tc, opt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 128)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    # single device
    s1 = init_train_state(params, opt, "none")
    s1, m1 = jax.jit(step)(s1, batch)

    # 2x4 mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(cfg, mesh)
    p_shard = param_shardings(fam.specs(cfg), rules)
    def wrapped(state, b):
        with use_rules(rules):
            return step(state, b)
    sharded_params = jax.device_put(params, p_shard)
    s2 = init_train_state(sharded_params, opt, "none")
    with mesh:
        s2, m2 = jax.jit(wrapped)(s2, batch)
    print("LOSS", float(m1["loss"]), float(m2["loss"]))
    d = jax.tree_util.tree_map(lambda a, b: float(jnp.abs(a - b).max()),
                               s1.params, jax.device_get(s2.params))
    print("MAXDIFF", max(jax.tree_util.tree_leaves(d)))
    """
    out = run_sub(code)
    loss_line = [l for l in out.splitlines() if l.startswith("LOSS")][0]
    l1, l2 = map(float, loss_line.split()[1:])
    assert abs(l1 - l2) < 1e-4
    maxdiff = float([l for l in out.splitlines() if l.startswith("MAXDIFF")][0].split()[1])
    assert maxdiff < 1e-4


def test_elastic_checkpoint_reshard(run_sub):
    """Save on a (2,4) mesh, restore on (4,2) — elastic restart."""
    code = """
    import jax, jax.numpy as jnp, numpy as np, tempfile, os
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.launch.mesh import make_mesh
    d = tempfile.mkdtemp()
    mesh1 = make_mesh((2, 4), ("data", "model"))
    x = jnp.arange(64.0).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh1, P("data", "model")))
    ck = Checkpointer(d)
    ck.save(1, {"x": xs})
    mesh2 = make_mesh((4, 2), ("data", "model"))
    sh2 = {"x": NamedSharding(mesh2, P("model", "data"))}
    got = ck.restore(1, {"x": jax.eval_shape(lambda: x)}, shardings=sh2)
    np.testing.assert_array_equal(np.asarray(got["x"]), np.asarray(x))
    assert got["x"].sharding.spec == P("model", "data")
    print("elastic-ok")
    """
    assert "elastic-ok" in run_sub(code)


def test_hlo_collective_parser_trip_counts(run_sub):
    code = """
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.hlo import collective_bytes
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    def f(x, ws):
        def body(c, w):
            y = c @ w
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("data", None))), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                    NamedSharding(mesh, P(None, None, "model")))
                   ).lower(x, ws).compile()
    cb = collective_bytes(comp.as_text())
    assert cb["all-gather"] == 6 * 64 * 64 * 4, cb   # trip-count weighted
    print("parser-ok")
    """
    assert "parser-ok" in run_sub(code)


@pytest.mark.parametrize("arch", ["qwen3-8b", "olmoe-1b-7b", "zamba2-7b",
                                  "seamless-m4t-large-v2", "xlstm-125m"])
def test_analytic_flops_vs_unrolled_cost_analysis(arch):
    """The roofline's analytic FLOPs agree with XLA cost_analysis on
    unrolled reduced-depth probes (within napkin tolerance)."""
    cfg = get_smoke_config(arch).replace(scan_layers=False, remat=False)
    from repro.models.registry import get_family
    from repro.nn import abstract
    from repro.train.losses import total_loss

    fam = get_family(cfg)
    shape = ShapeConfig("probe", seq_len=128, global_batch=4, kind="train")
    params = abstract(fam.specs(cfg))
    batch = fam.input_specs(cfg, shape)

    def f(p, b):
        logits, aux = fam.forward(p, b, cfg)
        return total_loss(logits, b["labels"], aux)[0]

    compiled = jax.jit(jax.grad(f)).lower(params, batch).compile()
    measured = compiled.cost_analysis()["flops"]
    analytic = flops_for(cfg, shape)
    ratio = analytic / measured
    assert 0.6 < ratio < 1.7, (arch, ratio)


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table for the named chip; an unknown chip is
    an error, never a borrowed default."""
    from repro.distributed.roofline import CHIP_PEAKS, Roofline

    rl = Roofline(flops=197e12, bytes_accessed=819e9, collective_bytes=0.0,
                  chips=1, device_kind="TPU v5 lite")
    assert rl.t_compute == rl.t_memory == 1.0
    assert rl.peaks is CHIP_PEAKS["TPU v5 lite"]
    with pytest.raises(ValueError, match="no published peaks"):
        Roofline(flops=1.0, bytes_accessed=1.0, collective_bytes=0.0,
                 chips=1, device_kind="TPU v9 imaginary")
