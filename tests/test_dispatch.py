"""Dispatcher API: registry, cross-backend equivalence (fwd+bwd, every
router), MoEContext threading, the explicit expert-parallel ``alltoall``
backend on a multi-device host mesh, and the capacity-free ``dropless``
backend (ragged grouped GEMM) including conservation guarantees.

Shared fixtures (toy configs/batches, the 8-device subprocess runner,
the jaxpr structural probe) live in conftest.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.core.context import MoEContext
from repro.core.dispatch import (
    available_dispatchers,
    get_dispatcher,
    register_dispatcher,
)
from repro.core.moe import group_tokens, moe_ffn_apply, moe_ffn_specs
from repro.core.routing import route
from repro.nn import init

ALL_ROUTERS = ("topk", "prototype", "expert_choice", "hash")
ALL_DISPATCHERS = ("alltoall", "dropless", "einsum", "gather", "pallas")
NON_REFERENCE = ("gather", "pallas", "alltoall", "dropless")


class TestRegistry:
    def test_builtin_keys(self):
        assert set(ALL_DISPATCHERS) <= set(available_dispatchers())

    def test_resolves_all_backends(self):
        for name in ALL_DISPATCHERS:
            assert get_dispatcher(name).name == name

    def test_unknown_key_lists_registry(self):
        with pytest.raises(ValueError, match="alltoall.*einsum"):
            get_dispatcher("nope")

    def test_config_validates_impl_key(self):
        with pytest.raises(ValueError, match="unknown moe impl"):
            MoEConfig(num_experts=4, impl="definitely-not-registered")
        # dense configs (num_experts=0) skip validation entirely
        MoEConfig(num_experts=0, impl="whatever")

    def test_plugin_registration(self):
        from repro.core.dispatch import _REGISTRY
        from repro.core.dispatch.gather import GatherDispatcher

        try:
            @register_dispatcher
            class MyDispatcher(GatherDispatcher):
                name = "my_backend"

            assert get_dispatcher("my_backend").name == "my_backend"
            MoEConfig(num_experts=4, impl="my_backend")
        finally:
            _REGISTRY.pop("my_backend", None)

    def test_dropless_requires_capable_backend(self):
        """capacity_factor=None is validated against the registry: only
        backends declaring supports_dropless may execute it."""
        for impl in ("einsum", "gather", "pallas"):
            with pytest.raises(ValueError, match="dropless"):
                MoEConfig(num_experts=4, impl=impl, capacity_factor=None)
        m = MoEConfig(num_experts=4, impl="dropless", capacity_factor=None)
        assert m.dropless
        # alltoall routes dropless plans through the ragged expert-parallel
        # exchange (falling back to the single-device ragged layout off a
        # mesh), so it declares supports_dropless too.
        assert MoEConfig(num_experts=4, impl="alltoall",
                         capacity_factor=None).dropless
        # dropless capacity is the per-group token count: a token's K
        # choices target distinct experts, so nothing can ever overflow.
        assert m.capacity(64) == 64
        # a finite capacity_factor on the dropless backend is also legal
        # (the backend executes any plan, drops included)
        MoEConfig(num_experts=4, impl="dropless", capacity_factor=1.25)
        # moe_attention runs the dense einsum path unconditionally, whose
        # (G,T,E,C=T) view would be quadratic in T — rejected up front
        with pytest.raises(ValueError, match="moe_attention"):
            MoEConfig(num_experts=4, impl="dropless", capacity_factor=None,
                      moe_attention=True)


# ---------------------------------------------------------------------------
# Cross-dispatcher equivalence: every backend == the einsum reference,
# forward and backward, for every registered router.
# ---------------------------------------------------------------------------

class TestEquivalence:
    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    @pytest.mark.parametrize("impl", NON_REFERENCE)
    def test_forward_matches_einsum(self, routing, impl, moe_model_cfg, toy_batch):
        cfg_e, cfg_o = moe_model_cfg(routing), moe_model_cfg(routing, impl=impl)
        params = init(moe_ffn_specs(cfg_e), jax.random.PRNGKey(0))
        x = toy_batch()
        y0, a0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)
        y1, a1 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_o))(params, x)
        tol = 1e-4 if impl == "pallas" else 1e-5
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=tol)
        # routing metrics are dispatcher-independent (the plan is shared)
        assert float(a0["moe_cv"]) == pytest.approx(float(a1["moe_cv"]))
        assert float(a0["moe_dropped_fraction"]) == pytest.approx(
            float(a1["moe_dropped_fraction"]))

    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    @pytest.mark.parametrize("impl", NON_REFERENCE)
    def test_backward_matches_einsum(self, routing, impl, moe_model_cfg, toy_batch):
        cfg_e, cfg_o = moe_model_cfg(routing), moe_model_cfg(routing, impl=impl)
        params = init(moe_ffn_specs(cfg_e), jax.random.PRNGKey(0))
        x = toy_batch()

        def grads(cfg):
            return jax.grad(
                lambda p: jnp.mean(moe_ffn_apply(p, x, cfg)[0] ** 2))(params)

        g_e, g_o = grads(cfg_e), grads(cfg_o)
        for k in g_e:
            a, b = np.asarray(g_e[k]), np.asarray(g_o[k])
            np.testing.assert_allclose(
                a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9), err_msg=k)

    @pytest.mark.parametrize("impl", NON_REFERENCE)
    def test_dropped_token_parity(self, impl, moe_model_cfg):
        """Under heavy capacity pressure every backend drops the *same*
        tokens (zero rows in identical places) as the einsum reference —
        including `dropless`, which executes the shared plan's assignment
        (its no-drop guarantee comes from capacity_factor=None, not from
        overriding a finite-capacity plan)."""
        cfg_e = moe_model_cfg("topk", capacity_factor=0.05)
        cfg_o = moe_model_cfg("topk", impl=impl, capacity_factor=0.05)
        params = init(moe_ffn_specs(cfg_e), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
        y0, a0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)
        y1, a1 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_o))(params, x)
        assert float(a0["moe_dropped_fraction"]) > 0.3
        assert float(a1["moe_dropped_fraction"]) == pytest.approx(
            float(a0["moe_dropped_fraction"]))
        z0 = np.linalg.norm(np.asarray(y0)[0], axis=-1) == 0.0
        z1 = np.linalg.norm(np.asarray(y1)[0], axis=-1) == 0.0
        np.testing.assert_array_equal(z0, z1)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-4)


# ---------------------------------------------------------------------------
# Dropless conservation: every routed token is processed exactly once —
# no drops, no duplicates — and the execution matches the einsum
# reference in the no-drop regime, forward and backward.
# ---------------------------------------------------------------------------

def _plan_and_params(cfg, x):
    m = cfg.moe
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    xg, G = group_tokens(x, m)
    T = xg.shape[1]
    w = params.get("router")
    plan = route(xg, None if w is None else w.astype(jnp.float32),
                 m, m.capacity(T))
    return plan, params, xg


class TestDroplessConservation:
    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    def test_ragged_view_processes_each_routed_token_once(
            self, routing, moe_model_cfg, toy_batch):
        """The ragged view holds exactly the plan's valid choices — as a
        multiset of (expert, token, gate) triples — each inside its
        expert's block-aligned segment. No token is dropped, none is
        duplicated."""
        bx = 8
        cfg = moe_model_cfg(routing, impl="dropless", capacity_factor=None)
        plan, _, xg = _plan_and_params(cfg, toy_batch())
        rag = plan.ragged(block_rows=bx)
        G = xg.shape[0]
        E = plan.num_experts

        e = np.asarray(plan.expert_index)
        v = np.asarray(plan.valid)
        g = np.asarray(plan.masked_gate)
        K = e.shape[-1]
        tok_rag = np.asarray(rag.token)
        gate_rag = np.asarray(rag.gate)
        off = np.asarray(rag.expert_offsets)
        be = np.asarray(rag.block_expert)

        for gi in range(G):
            # expectation straight off the index view
            tv, kv = np.nonzero(v[gi])
            want = sorted(zip(e[gi][tv, kv], tv, np.round(g[gi][tv, kv], 5)))
            # realisation from the ragged view
            rows = np.nonzero(tok_rag[gi] >= 0)[0]
            row_e = np.searchsorted(off[gi], rows, side="right") - 1
            got = sorted(zip(row_e, tok_rag[gi][rows],
                             np.round(gate_rag[gi][rows], 5)))
            assert got == want                      # exactly once, each
            # empty rows carry gate 0; segments are block-aligned and
            # block_expert agrees with the offsets
            assert (gate_rag[gi][tok_rag[gi] < 0] == 0.0).all()
            assert (off[gi] % bx == 0).all()
            for b, eb in enumerate(be[gi]):
                blk = np.arange(b * bx, (b + 1) * bx)
                filled = blk[tok_rag[gi][blk] >= 0]
                block_experts = np.searchsorted(off[gi], filled, side="right") - 1
                assert (block_experts == eb).all()

    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    def test_sort_order_is_a_partial_permutation(self, routing, moe_model_cfg,
                                                 toy_batch):
        """sort_order holds each valid flat choice index exactly once."""
        cfg = moe_model_cfg(routing, impl="dropless", capacity_factor=None)
        plan, _, _ = _plan_and_params(cfg, toy_batch())
        rag = plan.ragged(block_rows=8)
        so = np.asarray(rag.sort_order)
        v = np.asarray(plan.valid)
        n_valid = int(v.sum())
        real = so[so >= 0]
        assert real.size == n_valid
        assert np.unique(real).size == real.size    # no duplicates
        # every row's choice index is consistent with its token
        if plan.token_at_slot is None:
            K = plan.expert_index.shape[-1]
            tok = np.asarray(rag.token)
            assert (tok[so >= 0] == real // K).all()

    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    def test_matches_einsum_in_no_drop_regime(self, routing, moe_model_cfg,
                                              toy_batch):
        """capacity_factor=None (dropless) == einsum with a capacity
        large enough to drop nothing: identical assignment, identical
        numerics, fwd + bwd."""
        # gamma = E makes C >= k*T: overflow is impossible for the
        # einsum reference, so both execute the capacity-infinity plan.
        cfg_e = moe_model_cfg(routing, capacity_factor=8.0)
        cfg_d = moe_model_cfg(routing, impl="dropless", capacity_factor=None)
        params = init(moe_ffn_specs(cfg_e), jax.random.PRNGKey(0))
        x = toy_batch()
        y0, a0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)
        y1, a1 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_d))(params, x)
        assert float(a0["moe_dropped_fraction"]) == 0.0
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)

        def grads(cfg):
            return jax.grad(
                lambda p: jnp.mean(moe_ffn_apply(p, x, cfg)[0] ** 2))(params)

        g_e, g_d = grads(cfg_e), grads(cfg_d)
        for k in g_e:
            a, b = np.asarray(g_e[k]), np.asarray(g_d[k])
            np.testing.assert_allclose(
                a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9), err_msg=k)

    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    def test_dropped_fraction_identically_zero(self, routing, moe_model_cfg,
                                               toy_batch):
        cfg = moe_model_cfg(routing, impl="dropless", capacity_factor=None)
        params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
        y, aux = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg))(params, toy_batch())
        assert float(aux["moe_dropped_fraction"]) == 0.0   # exact, not approx

    @pytest.mark.parametrize("routing", ALL_ROUTERS)
    def test_no_dense_or_capacity_intermediate(self, routing, moe_model_cfg,
                                               toy_batch, dense_shape_present):
        """The dropless path never builds the (G,T,E,C) one-hot tensors
        nor an (E, G*C, M) capacity buffer — fwd or bwd."""
        cfg = moe_model_cfg(routing, impl="dropless", capacity_factor=None)
        params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
        x = toy_batch()
        xg, G = group_tokens(x, cfg.moe)
        T = xg.shape[1]
        E, C = cfg.moe.num_experts, cfg.moe.capacity(T)
        for shape in [(G, T, E, C), (E, G * C, cfg.d_model)]:
            assert not dense_shape_present(
                lambda p, xx: moe_ffn_apply(p, xx, cfg)[0], (params, x), shape)
            assert not dense_shape_present(
                jax.grad(lambda p, xx: jnp.sum(moe_ffn_apply(p, xx, cfg)[0] ** 2)),
                (params, x), shape)

    def test_dropless_rescues_dropped_tokens(self, moe_model_cfg):
        """The point of the backend: where a tight capacity factor zeroes
        token rows, capacity_factor=None processes every token."""
        cfg_tight = moe_model_cfg("topk", capacity_factor=0.05)
        cfg_d = moe_model_cfg("topk", impl="dropless", capacity_factor=None)
        params = init(moe_ffn_specs(cfg_tight), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
        y0, a0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_tight))(params, x)
        y1, a1 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_d))(params, x)
        assert float(a0["moe_dropped_fraction"]) > 0.3
        assert float(a1["moe_dropped_fraction"]) == 0.0
        zeroed = np.linalg.norm(np.asarray(y0)[0], axis=-1) == 0.0
        assert zeroed.any()
        assert (np.linalg.norm(np.asarray(y1)[0], axis=-1) > 0.0).all()

    def test_end_to_end_train_step(self, moe_model_cfg):
        """A dropless MoE LM takes a full train step (losses finite)."""
        from repro.configs.base import TrainConfig
        from repro.models.registry import get_family
        from repro.optim import make_optimizer, warmup_constant
        from repro.train.state import init_train_state
        from repro.train.trainer import make_train_step

        cfg = ModelConfig(num_layers=2, d_model=32, d_ff=48, num_heads=4,
                          num_kv_heads=4, vocab_size=64, dtype="float32",
                          moe=MoEConfig(num_experts=8, routing="topk", top_k=2,
                                        group_size=32, impl="dropless",
                                        capacity_factor=None))
        fam = get_family(cfg)
        tc = TrainConfig(optimizer="adamw", learning_rate=1e-3)
        params = init(fam.specs(cfg), jax.random.PRNGKey(0))
        opt = make_optimizer(tc, warmup_constant(tc.learning_rate, tc.warmup_steps))
        state = init_train_state(params, opt, "none")
        step = jax.jit(make_train_step(cfg, tc, opt))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        # per-layer trace: exactly zero drops in every MoE layer
        assert (np.asarray(metrics["moe_dropped_fraction"]) == 0.0).all()


# ---------------------------------------------------------------------------
# MoEContext threading
# ---------------------------------------------------------------------------

class TestContext:
    def test_context_is_a_pytree(self):
        ctx = MoEContext(token_ids=jnp.zeros((2, 8), jnp.int32),
                         positions=jnp.zeros((2, 8), jnp.int32),
                         is_training=True)
        leaves, treedef = jax.tree_util.tree_flatten(ctx)
        ctx2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert ctx2.is_training and ctx2.token_ids.shape == (2, 8)

    def test_layer_regroups_context(self, moe_model_cfg, toy_batch):
        """Identity-routing (hash) changes when token ids are provided —
        proof the context reaches the router through the layer."""
        cfg = moe_model_cfg("hash")
        params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
        x = toy_batch()
        ids = jnp.full((2, 50), 7, jnp.int32)   # all the same token id
        ctx = MoEContext(token_ids=ids)
        y0, a0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg))(params, x)
        y1, a1 = jax.jit(
            lambda p, xx, c: moe_ffn_apply(p, xx, cfg, ctx=c))(params, x, ctx)
        # all-identical ids hash to ONE expert pair -> drops under capacity
        assert float(a1["moe_dropped_fraction"]) > float(a0["moe_dropped_fraction"])

    def test_lm_apply_threads_token_ids(self):
        """End to end: a decoder LM with hash routing routes by token id
        (two prompts with permuted tokens produce identical expert loads)."""
        from repro.models import transformer as TF

        cfg = ModelConfig(num_layers=1, d_model=32, d_ff=48, num_heads=4,
                          num_kv_heads=4, vocab_size=64, dtype="float32",
                          moe=MoEConfig(num_experts=8, routing="hash", top_k=1,
                                        group_size=256, capacity_factor=8.0))
        params = init(TF.lm_specs(cfg), jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 64)
        perm = toks[:, ::-1]
        # capture the plan via the aux cv metric: same multiset of ids ->
        # same expert loads -> identical cv, which position-hash (fixed
        # pseudo-random permutation over positions) would not give.
        _, a1 = TF.lm_apply(params, toks, cfg)
        _, a2 = TF.lm_apply(params, perm, cfg)
        assert float(jnp.sum(a1["moe_cv"])) == pytest.approx(
            float(jnp.sum(a2["moe_cv"])), abs=1e-6)

    def test_serving_engine_threads_decode_context(self):
        """ServingEngine threads a MoEContext into prefill and decode
        (smoke: hash-routed MoE generates without NaNs; the layout
        invariance of the absolute-position fallback itself is asserted
        in test_routers.TestHashGolden)."""
        from repro.serving.engine import ServingEngine

        cfg = ModelConfig(num_layers=2, d_model=32, d_ff=48, num_heads=4,
                          num_kv_heads=4, vocab_size=64, dtype="float32",
                          max_seq_len=64,
                          moe=MoEConfig(num_experts=4, routing="hash", top_k=1,
                                        group_size=32, capacity_factor=4.0))
        from repro.models.registry import get_family

        params = init(get_family(cfg).specs(cfg), jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_len=32)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        out, _ = eng.generate(prompts, num_tokens=4)
        assert out.shape == (2, 4)
        assert not bool(jnp.isnan(out.astype(jnp.float32)).any())

    def test_serving_engine_dropless(self):
        """A dropless-configured model serves end to end."""
        from repro.models.registry import get_family
        from repro.serving.engine import ServingEngine

        cfg = ModelConfig(num_layers=2, d_model=32, d_ff=48, num_heads=4,
                          num_kv_heads=4, vocab_size=64, dtype="float32",
                          max_seq_len=64,
                          moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                        group_size=32, impl="dropless",
                                        capacity_factor=None))
        params = init(get_family(cfg).specs(cfg), jax.random.PRNGKey(0))
        eng = ServingEngine(cfg, params, max_len=32)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        out, _ = eng.generate(prompts, num_tokens=4)
        assert out.shape == (2, 4)


# ---------------------------------------------------------------------------
# Structural guarantee: the alltoall backend never materialises the dense
# (G,T,E,C) tensors — in fallback mode here, under shard_map below.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("routing", ALL_ROUTERS)
def test_alltoall_no_dense_intermediate(routing, moe_model_cfg, toy_batch,
                                        dense_shape_present):
    cfg = moe_model_cfg(routing, impl="alltoall")
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = toy_batch()
    xg, G = group_tokens(x, cfg.moe)
    T = xg.shape[1]
    dense = (G, T, cfg.moe.num_experts, cfg.moe.capacity(T))
    assert not dense_shape_present(
        lambda p, xx: moe_ffn_apply(p, xx, cfg)[0], (params, x), dense)
    assert not dense_shape_present(
        jax.grad(lambda p, xx: jnp.sum(moe_ffn_apply(p, xx, cfg)[0] ** 2)),
        (params, x), dense)


# ---------------------------------------------------------------------------
# The real thing: shard_map + all_to_all on an 8-device host mesh.
# ---------------------------------------------------------------------------

def test_alltoall_in_process_on_8_devices(mesh8, moe_model_cfg):
    """When the test process itself owns >= 8 devices (the CI mesh-8
    job), run the shard_map path in-process: Rules sharding + explicit
    all_to_all against the einsum reference."""
    from repro.distributed.sharding import make_rules, use_rules

    mesh = mesh8
    cfg = moe_model_cfg("topk", impl="alltoall", group_size=32)
    rules = make_rules(cfg, mesh)
    assert rules.params["expert"] == "model"
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
    cfg_e = cfg.replace_moe(impl="einsum")
    y0, _ = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)

    def fwd(p, xx):
        with use_rules(rules):
            return moe_ffn_apply(p, xx, cfg)[0]

    with mesh:
        y1 = jax.jit(fwd)(params, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(jax.device_get(y1)),
                               atol=2e-5)

    def loss(c, r):
        def g(p):
            with use_rules(r):
                return jnp.sum(moe_ffn_apply(p, x, c)[0] ** 2)
        return g

    g_e = jax.grad(loss(cfg_e, None))(params)
    with mesh:
        g_a = jax.jit(jax.grad(loss(cfg, rules)))(params)
    for k in g_e:
        a, b = np.asarray(g_e[k]), np.asarray(jax.device_get(g_a[k]))
        np.testing.assert_allclose(a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                                   err_msg=k)


def test_dropless_in_process_on_8_devices(mesh8, moe_model_cfg):
    """Dropless conservation holds under a sharded (2, 4) mesh: with the
    expert axis 4-way sharded and G divisible by the device grid, the
    backend runs the *ragged expert-parallel* exchange (structurally
    asserted: all_to_all in the jaxpr) and still matches the einsum
    reference with zero drops, fwd + bwd."""
    from repro.distributed.sharding import make_rules, use_rules

    mesh = mesh8
    cfg = moe_model_cfg("topk", impl="dropless", capacity_factor=None,
                        group_size=32)
    rules = make_rules(cfg, mesh)
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
    cfg_e = cfg.replace_moe(impl="einsum", capacity_factor=8.0)
    y0, _ = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)

    def fwd(p, xx):
        with use_rules(rules):
            return moe_ffn_apply(p, xx, cfg)

    with mesh:
        y1, aux = jax.jit(fwd)(params, x)
    assert float(jax.device_get(aux["moe_dropped_fraction"])) == 0.0
    np.testing.assert_allclose(np.asarray(y0), np.asarray(jax.device_get(y1)),
                               atol=2e-5)
    # the expert-sharded mesh must engage the ragged EP exchange, not
    # fall back to the GSPMD path (let alone gather)
    with use_rules(rules):
        assert "all_to_all" in str(jax.make_jaxpr(
            lambda p, xx: moe_ffn_apply(p, xx, cfg)[0])(params, x))

    def loss(c, r):
        def g(p):
            with use_rules(r):
                return jnp.sum(moe_ffn_apply(p, x, c)[0] ** 2)
        return g

    g_e = jax.grad(loss(cfg_e, None))(params)
    with mesh:
        g_d = jax.jit(jax.grad(loss(cfg, rules)))(params)
    for k in g_e:
        a, b = np.asarray(g_e[k]), np.asarray(jax.device_get(g_d[k]))
        np.testing.assert_allclose(a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                                   err_msg=k)


def test_ragged_ep_alltoall_impl_in_process(mesh8, moe_model_cfg,
                                            dense_shape_present):
    """capacity_factor=None on the ``alltoall`` backend: dropless plans
    route through the ragged EP dispatch (the (E,C)-buffered exchange
    has no capacity dimension to buffer) and match the single-device
    dropless path fwd + bwd; the jaxpr holds the all_to_all exchange and
    no dense capacity tensor, global or per-shard."""
    from repro.distributed.sharding import make_rules, use_rules

    mesh = mesh8
    cfg = moe_model_cfg("topk", impl="alltoall", capacity_factor=None,
                        group_size=32)
    rules = make_rules(cfg, mesh)
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))   # G = 8
    cfg_d = cfg.replace_moe(impl="dropless")
    y0, _ = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_d))(params, x)

    def fwd(p, xx):
        with use_rules(rules):
            return moe_ffn_apply(p, xx, cfg)[0]

    with mesh:
        y1 = jax.jit(fwd)(params, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(jax.device_get(y1)),
                               atol=2e-5)

    def loss(c, r):
        def g(p):
            with use_rules(r):
                return jnp.sum(moe_ffn_apply(p, x, c)[0] ** 2)
        return g

    g_d = jax.grad(loss(cfg_d, None))(params)
    with mesh:
        g_a = jax.jit(jax.grad(loss(cfg, rules)))(params)
    for k in g_d:
        a, b = np.asarray(g_d[k]), np.asarray(jax.device_get(g_a[k]))
        np.testing.assert_allclose(a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                                   err_msg=k)

    xg, G = group_tokens(x, cfg.moe)
    T = xg.shape[1]
    E, C = cfg.moe.num_experts, cfg.moe.capacity(T)
    with use_rules(rules):
        closed = jax.make_jaxpr(fwd)(params, x)
    assert "all_to_all" in str(closed)
    from conftest import _walk_avals
    shapes = {getattr(a, "shape", None) for a in _walk_avals(closed.jaxpr)}
    assert (G, T, E, C) not in shapes           # global dense
    assert (G // 8, T, E, C) not in shapes      # per-shard dense


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="multi-device parent runs the in-process mesh test "
                           "instead; the subprocess variant belongs to the "
                           "single-device CI job")
def test_alltoall_on_mesh_matches_einsum_all_routers(run_sub):
    """2x4 (data, model) mesh: the explicit expert-parallel dispatch
    matches the einsum reference forward AND backward for every router,
    and its jaxpr (including the shard_map body) holds no dense
    (G,T,E,C) or per-shard (Gl,T,E,C) tensor."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core.moe import group_tokens, moe_ffn_apply, moe_ffn_specs
    from repro.distributed.sharding import make_rules, use_rules
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import init

    assert jax.device_count() == 8
    mesh = make_debug_mesh(2, 4)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield v.aval
            for p in eqn.params.values():
                for pv in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(pv, "jaxpr", pv)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    for routing in ("topk", "prototype", "expert_choice", "hash"):
        cfg = ModelConfig(d_model=32, d_ff=48, dtype="float32",
                          moe=MoEConfig(num_experts=8, routing=routing,
                                        top_k=2, num_prototypes=2,
                                        group_size=32, capacity_factor=2.0,
                                        impl="alltoall"))
        rules = make_rules(cfg, mesh)
        assert rules.params["expert"] == "model"
        params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))  # 8 groups
        cfg_e = cfg.replace_moe(impl="einsum")

        def fwd(p, xx):
            with use_rules(rules):
                return moe_ffn_apply(p, xx, cfg)[0]

        def loss(c, r):
            def g(p, xx):
                with use_rules(r):
                    return jnp.sum(moe_ffn_apply(p, xx, c)[0] ** 2)
            return g

        y0 = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e)[0])(params, x)
        with mesh:
            y1 = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(jax.device_get(y1)),
                                   atol=2e-5)

        g_e = jax.grad(loss(cfg_e, None))(params, x)
        with mesh:
            g_a = jax.jit(jax.grad(loss(cfg, rules)))(params, x)
        for k in g_e:
            a = np.asarray(g_e[k]); b = np.asarray(jax.device_get(g_a[k]))
            np.testing.assert_allclose(a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                                       err_msg=routing + "/" + k)

        # structural: no dense one-hot tensors, global or per-shard
        xg, G = group_tokens(x, cfg.moe)
        T = xg.shape[1]
        E, C = cfg.moe.num_experts, cfg.moe.capacity(T)
        with use_rules(rules):
            closed = jax.make_jaxpr(lambda p, xx: moe_ffn_apply(p, xx, cfg)[0])(params, x)
        shapes = {getattr(a, "shape", None) for a in walk(closed.jaxpr)}
        assert (G, T, E, C) not in shapes, (routing, "global dense")
        assert (G // 8, T, E, C) not in shapes, (routing, "per-shard dense")
        # the shard_map body must actually contain the two all_to_alls
        txt = str(closed)
        assert "all_to_all" in txt, routing
        print(routing, "mesh-ok")
    """
    # 4 routers x (fwd + bwd) compiles are heavy on a 2-core CI box:
    # give the subprocess real headroom over the ~8 min observed runtime.
    out = run_sub(code, timeout=1500)
    for routing in ALL_ROUTERS:
        assert f"{routing} mesh-ok" in out


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="multi-device parent runs the in-process mesh test "
                           "instead; the subprocess variant belongs to the "
                           "single-device CI job")
def test_dropless_on_mesh_conserves_tokens(run_sub):
    """8-virtual-device mesh: the dropless backend under Rules sharding
    matches the no-drop einsum reference and reports exactly zero
    dropped tokens (fwd + bwd)."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core.moe import moe_ffn_apply, moe_ffn_specs
    from repro.distributed.sharding import make_rules, use_rules
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import init

    assert jax.device_count() == 8
    mesh = make_debug_mesh(2, 4)
    cfg = ModelConfig(d_model=32, d_ff=48, dtype="float32",
                      moe=MoEConfig(num_experts=8, routing="topk", top_k=2,
                                    group_size=32, impl="dropless",
                                    capacity_factor=None))
    rules = make_rules(cfg, mesh)
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
    cfg_e = cfg.replace_moe(impl="einsum", capacity_factor=8.0)
    y0, _ = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_e))(params, x)

    def fwd(p, xx):
        with use_rules(rules):
            return moe_ffn_apply(p, xx, cfg)

    with mesh:
        y1, aux = jax.jit(fwd)(params, x)
    assert float(jax.device_get(aux["moe_dropped_fraction"])) == 0.0
    np.testing.assert_allclose(np.asarray(y0), np.asarray(jax.device_get(y1)),
                               atol=2e-5)

    def loss(c, r):
        def g(p):
            with use_rules(r):
                return jnp.sum(moe_ffn_apply(p, x, c)[0] ** 2)
        return g

    g_e = jax.grad(loss(cfg_e, None))(params)
    with mesh:
        g_d = jax.jit(jax.grad(loss(cfg, rules)))(params)
    for k in g_e:
        a = np.asarray(g_e[k]); b = np.asarray(jax.device_get(g_d[k]))
        np.testing.assert_allclose(a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                                   err_msg=k)
    print("dropless-mesh-ok")
    """
    assert "dropless-mesh-ok" in run_sub(code)


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="the subprocess variant belongs to the "
                           "single-device CI job")
def test_dropless_one_group_on_mesh_matches_single_device(run_sub):
    """(2, 4) mesh, one routing group (a serving step's shape): explicit EP
    cannot split the group, so the grouped FFN runs under shard_map with
    the rows replicated and the expert shards psum'd — GSPMD cannot
    partition the TPU kernel.  Outputs and grads equal the single-device
    dropless layer exactly."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core.moe import moe_ffn_apply, moe_ffn_specs
    from repro.distributed.sharding import make_rules, use_rules
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import init

    mesh = make_debug_mesh(2, 4)
    cfg = ModelConfig(d_model=32, d_ff=48, dtype="float32",
                      moe=MoEConfig(num_experts=8, routing="topk", top_k=2,
                                    group_size=64, impl="dropless",
                                    capacity_factor=None))
    rules = make_rules(cfg, mesh)
    params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))   # G = 1

    def loss(r):
        def f(p, xx):
            with use_rules(r):
                y = moe_ffn_apply(p, xx, cfg)[0]
            return jnp.sum(y ** 2), y
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    (_, y0), g0 = loss(None)(params, x)
    with mesh:
        (_, y1), g1 = loss(rules)(params, x)
        text = loss(rules).lower(params, x).as_text()
    assert "all_to_all" not in text and "manual_computation" in text
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("one-group-mesh-ok")
    """
    assert "one-group-mesh-ok" in run_sub(code)


@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="multi-device parent runs the in-process ragged-EP "
                           "tests instead; the subprocess variant belongs to "
                           "the single-device CI job")
def test_ragged_ep_on_mesh_matches_dropless(run_sub):
    """8-virtual-device (2, 4) mesh: the ragged expert-parallel dispatch
    (dropless plans on the ``dropless`` AND ``alltoall`` backends)
    matches the single-device dropless reference fwd + bwd, engages the
    all_to_all exchange and builds no dense capacity tensor."""
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.core.moe import group_tokens, moe_ffn_apply, moe_ffn_specs
    from repro.distributed.sharding import make_rules, use_rules
    from repro.launch.mesh import make_debug_mesh
    from repro.nn import init

    assert jax.device_count() == 8
    mesh = make_debug_mesh(2, 4)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield v.aval
            for p in eqn.params.values():
                for pv in (p if isinstance(p, (list, tuple)) else [p]):
                    inner = getattr(pv, "jaxpr", pv)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    for routing, impl in (("topk", "alltoall"), ("hash", "dropless")):
        cfg = ModelConfig(d_model=32, d_ff=48, dtype="float32",
                          moe=MoEConfig(num_experts=8, routing=routing,
                                        top_k=2, group_size=32,
                                        capacity_factor=None, impl=impl))
        rules = make_rules(cfg, mesh)
        params = init(moe_ffn_specs(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))  # G = 8
        cfg_d = cfg.replace_moe(impl="dropless")
        y0, _ = jax.jit(lambda p, xx: moe_ffn_apply(p, xx, cfg_d))(params, x)

        def fwd(p, xx):
            with use_rules(rules):
                return moe_ffn_apply(p, xx, cfg)[0]

        with mesh:
            y1 = jax.jit(fwd)(params, x)
        np.testing.assert_allclose(np.asarray(y0),
                                   np.asarray(jax.device_get(y1)), atol=2e-5)

        def loss(c, r):
            def g(p):
                with use_rules(r):
                    return jnp.sum(moe_ffn_apply(p, x, c)[0] ** 2)
            return g

        g_d = jax.grad(loss(cfg_d, None))(params)
        with mesh:
            g_a = jax.jit(jax.grad(loss(cfg, rules)))(params)
        for k in g_d:
            a = np.asarray(g_d[k]); b = np.asarray(jax.device_get(g_a[k]))
            np.testing.assert_allclose(
                a, b, atol=1e-4 * max(np.abs(a).max(), 1e-9),
                err_msg=routing + "/" + k)

        xg, G = group_tokens(x, cfg.moe)
        T = xg.shape[1]
        E, C = cfg.moe.num_experts, cfg.moe.capacity(T)
        with use_rules(rules):
            closed = jax.make_jaxpr(fwd)(params, x)
        assert "all_to_all" in str(closed), (routing, impl)
        shapes = {getattr(a, "shape", None) for a in walk(closed.jaxpr)}
        assert (G, T, E, C) not in shapes, (routing, impl)
        assert (G // 8, T, E, C) not in shapes, (routing, impl)
        print(routing, impl, "ragged-ep-ok")
    """
    out = run_sub(code, timeout=1500)
    assert "topk alltoall ragged-ep-ok" in out
    assert "hash dropless ragged-ep-ok" in out
