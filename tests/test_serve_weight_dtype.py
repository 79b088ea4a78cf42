"""The continuous engine holds its paged-mode weights in the compute dtype:
every leaf stored in ``param_dtype`` is cast to ``dtype`` once, at
construction, and every other leaf stays float32.  The step then computes
with the values it computed with before — the same rounding, done once."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig, ServeConfig
from repro.models.registry import get_family
from repro.nn import init
from repro.serving.continuous import ContinuousEngine, serving_params


def _base(**kw) -> ModelConfig:
    base = dict(name="t", family="decoder_lm", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, head_dim=16, d_ff=96,
                vocab_size=200, max_seq_len=64, dtype="bfloat16",
                param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


CASES = {
    # dense kernels with biases, untied embed/unembed tables
    "dense": _base(qkv_bias=True, tie_embeddings=False),
    # M6-style: top-1 gelu experts, learned positions, LayerNorm, tied table
    "m6_top1_gelu": _base(
        family="m6", norm="layernorm", pos_embed="learned",
        ffn_activation="gelu", tie_embeddings=True, num_heads=4,
        num_kv_heads=4,
        moe=MoEConfig(num_experts=4, routing="topk", top_k=1, group_size=1,
                      impl="dropless", capacity_factor=None)),
    # OLMoE-style: swiglu top-k experts with per-head qk-norm and rope
    "swiglu_topk_qknorm": _base(
        qk_norm=True, ffn_activation="swiglu", tie_embeddings=False,
        moe=MoEConfig(num_experts=8, routing="topk", top_k=2, group_size=1,
                      impl="dropless", capacity_factor=None)),
}

# leaves the layers cast at use (declared in param_dtype), by path suffix
_CAST = ("kernel", "table", "pos_embed", "up", "gate", "down")


def _serve():
    return ServeConfig(max_slots=4, kv_block_size=4, prefill_chunk=4,
                       max_len=32)


def _build(cfg, seed=0):
    return init(get_family(cfg).specs(cfg), jax.random.PRNGKey(seed))


def _paths(tree):
    return {jax.tree_util.keystr(p): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_cast_leaf(path: str) -> bool:
    return path.rstrip("]'").split("'")[-1] in _CAST


@pytest.mark.parametrize("name", sorted(CASES))
def test_engine_holds_compute_dtype_at_param_dtype_leaves(name):
    cfg = CASES[name]
    params = _build(cfg)
    eng = ContinuousEngine(cfg, params, _serve())
    got, src = _paths(eng.params), _paths(params)
    assert got.keys() == src.keys()
    n_cast = 0
    for path, x in got.items():
        assert src[path].dtype == jnp.float32
        want = jnp.bfloat16 if _is_cast_leaf(path) else jnp.float32
        assert x.dtype == want, (path, x.dtype)
        assert x.sharding == src[path].sharding
        if want == jnp.bfloat16:
            n_cast += 1
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(src[path].astype(jnp.bfloat16)))
        else:
            assert x is src[path]           # passed through untouched
    assert n_cast >= 5
    if cfg.moe.num_experts:
        assert got["['blocks']['ffn']['router']"].dtype == jnp.float32


@pytest.mark.parametrize("name", sorted(CASES))
def test_step_on_cast_tree_matches_f32_tree(name):
    """Every mixed and decode step of a short run, through the raw step
    function, gives the same samples, logits and K/V pools on the
    engine's tree as on the caller's float32 tree: the rounding is the
    one the step did itself."""
    cfg = CASES[name]
    params = _build(cfg)
    taps = []
    eng = ContinuousEngine(
        cfg, params, _serve(),
        logit_tap=lambda lg, *_: taps.append(np.asarray(lg)))
    raw = jax.jit(eng._step_fn_raw)
    seen = []

    def run(p, args):
        out = jax.block_until_ready(raw(p, *args))
        jax.effects_barrier()
        return out, taps.pop()

    def spy(p, *args):
        (out, lg), (ref, ref_lg) = run(p, args), run(params, args)
        seen.append(args[3].shape[0])
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
        np.testing.assert_array_equal(lg, ref_lg)
        for a, b in zip(out[1:3], ref[1:3]):            # K/V pools
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return out

    eng._step_fn = spy
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                 cfg.vocab_size)
    eng.generate(prompts, 3)
    S, C = eng.serve.max_slots, eng.serve.prefill_chunk
    assert {S, S + C} <= set(seen)                  # decode and mixed steps


def test_cast_tree_compiles_each_step_shape_once():
    """The cast leaves keep the caller's placement: an uncommitted tree
    stays uncommitted, so the step does not compile a second variant
    once its KV pools come back from the first call."""
    cfg = CASES["swiglu_topk_qknorm"]
    eng = ContinuousEngine(cfg, _build(cfg), _serve())
    prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                 cfg.vocab_size)
    eng.generate(prompts, 3)
    assert eng.compiled_variants() == eng._expected_variants == 2
    assert eng.obs.metrics.get("engine_recompiles_total") == 0


def test_param_dtype_equal_to_dtype_passes_through():
    cfg = CASES["swiglu_topk_qknorm"].replace(param_dtype="bfloat16")
    params = _build(cfg)
    eng = ContinuousEngine(cfg, params, _serve())
    assert eng.params is params
    f32 = _base(dtype="float32")
    p32 = _build(f32)
    assert serving_params(get_family(f32).specs, f32, p32) is p32


def test_recurrent_mode_keeps_callers_params():
    cfg = ModelConfig(name="x", family="xlstm", num_layers=2, d_model=64,
                      num_heads=4, vocab_size=64, max_seq_len=64,
                      dtype="bfloat16", xlstm_slstm_period=2)
    params = _build(cfg)
    eng = ContinuousEngine(cfg, params, _serve())
    assert eng.params is params
