"""Deterministic goldens for the quantized KV-cache subsystem
(repro.quant) plus the operation-sequence checker the hypothesis
harness in test_quant_properties.py randomises.

Covers, bottom-up:

* the policy registry (none / int8 / fp8) and ``ServeConfig.kv_quant``
  validation;
* ``check_quant_roundtrip`` — the single-pass error-bound law per
  policy, including the fp8 clip-before-cast edge (|x| > 448 must not
  produce nan codes);
* ``quant_write_kv`` — block-fill scale reset, scale growth rescaling
  resident codes, the no-growth rewrite bit-identity, and the
  ``block_size * error_bound`` pool-residency bound;
* kernel vs reference — the fused-dequant Pallas decode kernel in
  ``interpret=True`` mode against the pure-jnp reference, int8 and fp8;
* cache variants — pool/scale shapes, ``block_bytes`` accounting, the
  published-block write guard covering scale rows, COW scale copies;
* engine level — the ``kv_quant="none"`` bitwise identity matrix
  (dense and dropless-hash MoE x prefix on/off x mesh 1x1), int8
  end-to-end under ``check_invariants=True``, swap-restore and
  warm-prefix byte preservation, deadline-aware shedding, and the
  ``kv_pool_bytes`` occupancy metric.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ServeConfig, SLOConfig
from repro.quant import (
    available_kv_quants,
    check_quant_roundtrip,
    get_kv_quant,
    quant_write_kv,
)
from repro.quant.kv_cache import (
    QuantizedPagedKVCache,
    QuantizedPrefixCachingKVCache,
)


def _cfg(**kw):
    base = dict(name="t", family="decoder_lm", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                max_seq_len=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def _params(cfg, seed=0):
    from repro.models.registry import get_family
    from repro.nn import init

    return init(get_family(cfg).specs(cfg), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Policy registry
# ---------------------------------------------------------------------------

def test_registry_and_config_validation():
    names = available_kv_quants()
    assert "none" in names and "int8" in names and "fp8" in names
    assert not get_kv_quant("none").quantized
    assert get_kv_quant("int8").quantized
    assert get_kv_quant("int8") is get_kv_quant("int8")   # singleton: jit-static
    with pytest.raises(ValueError):
        get_kv_quant("int4")
    with pytest.raises(ValueError):
        ServeConfig(max_slots=2, kv_block_size=4, max_len=16, num_blocks=8,
                    kv_quant="bf8")


def test_roundtrip_bounds_golden():
    x = np.array([0.0, 1.0, -1.0, 0.3, 127.0, -63.5, 1e-8], np.float32)
    for name in ("int8", "fp8"):
        deq, scale, max_err = check_quant_roundtrip(x, get_kv_quant(name))
        assert max_err <= float(get_kv_quant(name).error_bound(scale))


def test_fp8_large_values_do_not_nan():
    """e4m3 saturates at 448; casting beyond gives nan — the encoder
    must clip first, so huge inputs produce finite codes."""
    policy = get_kv_quant("fp8")
    x = jnp.asarray([1e4, -1e4, 500.0, 448.0], jnp.float32)
    scale = jnp.abs(x).max() / policy.qmax
    deq = policy.decode(policy.encode(x / jnp.maximum(scale, 1e-30))) * scale
    assert bool(jnp.isfinite(deq).all())


# ---------------------------------------------------------------------------
# quant_write_kv (checker randomised by test_quant_properties.py)
# ---------------------------------------------------------------------------

def check_quant_write_sequence(bs, hkv, hd, name, writes):
    """writes: list of (block, offset, values) partial-row writes into a
    tiny pool.  A host model keeps every row's exact f32 value; after
    every write, each resident row must decode to within
    ``bs * error_bound(scale)`` of its model value (the scale-growth
    compounding law: one extra bound per growth, at most bs - 1 growths
    in a block's lifetime), and scales never shrink except at a
    block-fill (offset 0), which starts a new block lifetime."""
    policy = get_kv_quant(name)
    P = 4
    codes = jnp.zeros((P, hkv, bs, hd), policy.pool_dtype)
    scales = jnp.zeros((P, hkv), jnp.float32)
    model = {}                     # (block, offset) -> (hkv, hd) f32 row
    for blk, off, vals in writes:
        blk, off = blk % P, off % bs
        x = np.asarray(vals, np.float32).reshape(1, hkv, hd)
        before = np.asarray(scales)
        codes, scales = quant_write_kv(
            codes, scales, jnp.asarray(x),
            jnp.asarray([blk], jnp.int32), jnp.asarray([off], jnp.int32),
            policy=policy)
        if off == 0:               # block-fill: prior rows are dead
            model = {k: v for k, v in model.items() if k[0] != blk}
        model[(blk, off)] = x[0]
        after = np.asarray(scales)
        if off != 0:
            assert (after >= before - 1e-30).all()
        deq = np.asarray(policy.decode(codes)) * after[:, :, None, None]
        for (b, o), row in model.items():
            bound = bs * np.asarray(policy.error_bound(jnp.asarray(after[b])))
            err = np.abs(deq[b, :, o] - row)
            assert (err <= bound[:, None] + 1e-6).all(), (b, o, err.max())
    return codes, scales


def test_quant_write_fixed_grid():
    for name in ("int8", "fp8"):
        check_quant_write_sequence(4, 2, 2, name, [
            (0, 0, [1.0, -2.0, 3.0, -4.0]),
            (0, 1, [100.0, 0.5, -0.25, 7.0]),   # scale growth -> rescale
            (0, 2, [0.1, 0.2, 0.3, 0.4]),       # no growth
            (1, 0, [0.0, 0.0, 0.0, 0.0]),       # all-zero block
            (0, 0, [5.0, 5.0, 5.0, 5.0])])      # block refill resets scale


def test_no_growth_rewrite_is_bit_identity():
    """Rewriting with values inside the block's current absmax does not
    touch any other row's codes: decode -> divide by the same scale ->
    re-encode reproduces them exactly."""
    policy = get_kv_quant("int8")
    codes = jnp.zeros((2, 1, 4, 2), policy.pool_dtype)
    scales = jnp.zeros((2, 1), jnp.float32)
    big = np.full((1, 1, 2), 8.0, np.float32)
    codes, scales = quant_write_kv(
        codes, scales, jnp.asarray(big), jnp.asarray([0], jnp.int32),
        jnp.asarray([0], jnp.int32), policy=policy)
    snap = np.asarray(codes[0, :, 0])
    small = np.full((1, 1, 2), 1.5, np.float32)       # within absmax 8
    codes2, scales2 = quant_write_kv(
        codes, scales, jnp.asarray(small), jnp.asarray([0], jnp.int32),
        jnp.asarray([1], jnp.int32), policy=policy)
    assert np.array_equal(np.asarray(scales2), np.asarray(scales))
    assert np.array_equal(np.asarray(codes2[0, :, 0]), snap)


# ---------------------------------------------------------------------------
# Fused-dequant kernel vs pure-jnp reference (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_quantized_kernel_matches_ref(name):
    from repro.kernels.decode_attention.kernel import (
        quantized_paged_decode_attention_kernel,
    )
    from repro.kernels.decode_attention.ref import (
        quantized_paged_decode_attention_ref,
    )

    policy = get_kv_quant(name)
    key = jax.random.PRNGKey(0)
    N, H, G, D, P, bs, n_b = 3, 2, 2, 8, 9, 4, 2
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (N, H, G, D), jnp.float32)
    kf = jax.random.normal(ks[1], (P, H, bs, D), jnp.float32)
    vf = jax.random.normal(ks[2], (P, H, bs, D), jnp.float32)

    def enc(x):                    # per-(block, head) absmax quantization
        s = jnp.abs(x).max(axis=(2, 3)) / policy.qmax
        codes = policy.encode(x / jnp.maximum(s, 1e-30)[:, :, None, None])
        return jnp.moveaxis(codes, 1, 1).astype(policy.pool_dtype), s

    k_pool, k_scales = enc(kf)
    v_pool, v_scales = enc(vf)
    # pool layout is (P, H, bs, D) / scales (P, H)
    tbl = jnp.asarray([[1, 2], [3, 4], [5, 0]], jnp.int32)
    lens = jnp.asarray([5, 8, 3], jnp.int32)
    # ref takes flat (N, Hq, D) queries, the kernel grouped (N, Hkv, G, D)
    ref = quantized_paged_decode_attention_ref(
        q.reshape(N, H * G, D), k_pool, v_pool, k_scales, v_scales, tbl,
        lens, policy=policy, scale=D ** -0.5)
    out = quantized_paged_decode_attention_kernel(
        q, k_pool, v_pool, k_scales, v_scales, tbl, lens,
        decode=policy.decode, scale=D ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(out).reshape(N, H * G, D),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_quantized_update_attention_end_to_end():
    from repro.kernels.decode_attention import quantized_paged_update_attention

    policy = get_kv_quant("int8")
    N, H, G, D, P, bs, n_b = 2, 2, 1, 8, 5, 4, 2
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (N, H * G, D), jnp.float32)
    k_new = jax.random.normal(ks[1], (N, H, D), jnp.float32)
    v_new = jax.random.normal(ks[2], (N, H, D), jnp.float32)
    k_pool = jnp.zeros((P, H, bs, D), policy.pool_dtype)
    v_pool = jnp.zeros_like(k_pool)
    k_sc = jnp.zeros((P, H), jnp.float32)
    v_sc = jnp.zeros_like(k_sc)
    tbl = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([1, 1], jnp.int32)
    wb = jnp.asarray([1, 3], jnp.int32)
    wo = jnp.asarray([0, 0], jnp.int32)
    out, k_pool, v_pool, k_sc, v_sc = quantized_paged_update_attention(
        q, k_new, v_new, k_pool, v_pool, k_sc, v_sc, wb, wo, tbl, lens,
        policy=policy)
    assert out.shape == (N, H * G, D)
    assert bool(jnp.isfinite(out).all())
    # written blocks got scales; untouched blocks stayed zero
    assert float(k_sc[1].min()) > 0 and float(k_sc[3].min()) > 0
    assert float(k_sc[0].max()) == 0 and float(k_sc[2].max()) == 0


# ---------------------------------------------------------------------------
# Cache variants: shapes, byte accounting, write guards, COW
# ---------------------------------------------------------------------------

def _qserve(prefix=False, num_blocks=16, **kw):
    return ServeConfig(max_slots=4, kv_block_size=4, max_len=64,
                       num_blocks=num_blocks, prefix_cache=prefix,
                       kv_quant=kw.pop("kv_quant", "int8"), **kw)


def test_quantized_cache_pools_and_block_bytes():
    from repro.serving.kv_cache import PagedKVCache

    cfg = _cfg()
    cache = QuantizedPagedKVCache(cfg, _qserve())
    assert cache.k_pool.dtype == jnp.int8
    assert cache.k_scales.shape == (2, 17, 2)       # (L, blocks+1, Hkv)
    base = PagedKVCache(cfg, ServeConfig(max_slots=4, kv_block_size=4,
                                         max_len=64, num_blocks=16))
    # int8 + f32 scales vs f32 codes: quarter the bytes, plus epsilon
    assert cache.block_bytes < 0.30 * base.block_bytes
    assert cache.occupancy()[0]["block_bytes"] == cache.block_bytes
    cache.check_conservation()


def test_published_block_scale_double_write_raises():
    """A published block is immutable codes + an immutable scale: the
    write guard rejects any coordinate into it, so its scale row can
    never be rewritten while the block is matchable."""
    cfg = _cfg(num_layers=1)
    cache = QuantizedPrefixCachingKVCache(cfg, _qserve(prefix=True))
    prompt = np.arange(9, dtype=np.int32)
    cache.allocate_slot(0, 12, prompt=prompt)
    cache.ensure_capacity(0, 9)
    cache.commit(0, prompt)                    # publishes blocks 0..1
    held = cache._slot_blocks[0]
    assert cache.index.published(held[0])
    with pytest.raises(RuntimeError):
        cache.write_coords(0, 2)               # inside a published block
    # a fresh binder must not be able to write the shared blocks either
    cache.allocate_slot(1, 12, prompt=prompt)
    with pytest.raises(RuntimeError):
        cache.write_coords(1, 0)
    cache.check_conservation()


def test_cow_detach_copies_scale_rows():
    cfg = _cfg(num_layers=1)
    cache = QuantizedPrefixCachingKVCache(cfg, _qserve(prefix=True))
    prompt = np.arange(12, dtype=np.int32)
    cache.allocate_slot(0, 16, prompt=prompt)
    cache.ensure_capacity(0, 12)
    cache.commit(0, prompt)                    # publishes blocks 0..2
    held0 = list(cache._slot_blocks[0])
    # stamp recognisable scales on the block the COW edge will hit
    cache.k_scales = cache.k_scales.at[:, held0[1]].set(7.0)
    cache.v_scales = cache.v_scales.at[:, held0[1]].set(3.0)
    cache.allocate_slot(1, 16, prompt=prompt)  # binds blocks 0..1 (8 cached)
    assert cache._slot_bound[1] == 2
    cache.truncate_slot(0, 5)                  # COW: slot 0 detaches block 1
    new1 = cache._slot_blocks[0][1]
    assert new1 != held0[1]
    assert (np.asarray(cache.k_scales[:, new1]) == 7.0).all()
    assert (np.asarray(cache.v_scales[:, new1]) == 3.0).all()
    cache.check_conservation()


# ---------------------------------------------------------------------------
# Engine level: the quant=none identity matrix, int8 e2e, swap, prefix
# ---------------------------------------------------------------------------

TRIVIAL_MESH = (("data", 1), ("expert", 1))


def _requests(gen=6, vocab=128):
    from repro.serving.request import Request

    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    prompt=rng.integers(1, vocab, int(l)).astype(np.int32),
                    max_new_tokens=gen)
            for i, l in enumerate([5, 9, 13, 7])]


def _trace(cfg, params, *, kv_quant="none", prefix=False, mesh=None,
           slo=None, check=True, num_blocks=48, requests=None, obs=None):
    from repro.serving.continuous import ContinuousEngine

    serve = ServeConfig(max_slots=3, kv_block_size=4, prefill_chunk=4,
                        max_len=64, num_blocks=num_blocks,
                        prefix_cache=prefix, kv_quant=kv_quant, slo=slo,
                        mesh=mesh)
    eng = ContinuousEngine(cfg, params, serve, check_invariants=check,
                           obs=obs)
    toks, stats = eng.run(requests if requests is not None else _requests())
    return toks, stats, eng


def test_none_identity_matrix_dense():
    """kv_quant='none' is bitwise token-identical to the pre-quant
    engine path across prefix on/off and the 1x1 mesh."""
    cfg = _cfg()
    params = _params(cfg)
    base, _, _ = _trace(cfg, params, check=False)
    for prefix in (False, True):
        toks, _, _ = _trace(cfg, params, prefix=prefix)
        assert toks == base
    mesh_toks, _, _ = _trace(cfg, params, mesh=TRIVIAL_MESH)
    assert mesh_toks == base


def test_none_identity_dropless_hash():
    cfg = _cfg().replace_moe(impl="dropless", num_experts=4,
                             routing="hash", capacity_factor=None)
    params = _params(cfg)
    base, _, _ = _trace(cfg, params, check=False)
    warm, _, _ = _trace(cfg, params, prefix=True)
    assert warm == base
    mesh_toks, _, _ = _trace(cfg, params, mesh=TRIVIAL_MESH)
    assert mesh_toks == base


def test_int8_end_to_end_with_invariants():
    cfg = _cfg()
    params = _params(cfg)
    toks, _, eng = _trace(cfg, params, kv_quant="int8")
    assert all(len(t) == 6 for t in toks.values())
    assert eng.cache.k_pool.dtype == jnp.int8
    eng.cache.check_conservation()
    # deterministic: same trace, same tokens
    toks2, _, _ = _trace(cfg, params, kv_quant="int8")
    assert toks == toks2


def test_int8_mesh_matches_single_device():
    cfg = _cfg()
    params = _params(cfg)
    single, _, _ = _trace(cfg, params, kv_quant="int8")
    mesh, _, eng = _trace(cfg, params, kv_quant="int8", mesh=TRIVIAL_MESH)
    assert mesh == single
    from repro.serving.kv_cache import ShardedPagedKVCache

    assert isinstance(eng.cache, ShardedPagedKVCache)
    assert eng.cache.k_scales is not None


def test_int8_warm_prefix_preserves_published_bytes():
    """Warm reuse serves the published blocks' quantized bytes exactly:
    the warm run is token-identical to cold and actually binds blocks."""
    cfg = _cfg()
    params = _params(cfg)
    reqs = _requests()
    # two tenants sharing a prompt so the warm run has something to bind
    for r in reqs[1:]:
        r.prompt[:4] = reqs[0].prompt[:4]
    cold, _, _ = _trace(cfg, params, kv_quant="int8", requests=reqs)
    warm, s, eng = _trace(cfg, params, kv_quant="int8", prefix=True,
                          requests=reqs)
    assert cold == warm
    assert s["cached_tokens"] > 0
    eng.cache.check_conservation()


def test_int8_swap_restore_token_identical():
    """Preempt + restore under int8: host pools hold codes + scales
    verbatim, so the resumed request is token-identical to an
    un-preempted run (no re-quantization in flight)."""
    from repro.serving.request import Priority, Request

    cfg = _cfg()
    params = _params(cfg)
    rng = np.random.default_rng(1)
    low = Request(uid=0, prompt=rng.integers(1, 128, 12).astype(np.int32),
                  max_new_tokens=10, arrival_ms=0.0, priority=Priority.LOW)
    high = Request(uid=1, prompt=rng.integers(1, 128, 8).astype(np.int32),
                   max_new_tokens=4, arrival_ms=1.0, priority=Priority.HIGH)

    def run(reqs, slo):
        from repro.serving.continuous import ContinuousEngine

        serve = ServeConfig(max_slots=1, kv_block_size=4, prefill_chunk=4,
                            max_len=64, num_blocks=32, kv_quant="int8",
                            slo=slo)
        eng = ContinuousEngine(cfg, params, serve, check_invariants=True)
        return eng.run(reqs)

    toks, stats = run([low, high], SLOConfig(preemption=True))
    assert stats["preemptions"] >= 1
    solo, _ = run([Request(uid=0, prompt=low.prompt, max_new_tokens=10)],
                  None)
    assert toks[0] == solo[0]


def test_quantized_swap_manager_preserves_bytes():
    """Direct store/load round trip: codes and scale rows come back to
    the device bit-identical."""
    from repro.serving.slo.swap import SwapManager

    cfg = _cfg(num_layers=1)
    cache = QuantizedPagedKVCache(cfg, _qserve(num_blocks=8))
    cache.allocate_slot(0, 8)
    cache.ensure_capacity(0, 8)
    blocks = list(cache._slot_blocks[0])
    key = jax.random.PRNGKey(0)
    cache.k_pool = jax.random.randint(key, cache.k_pool.shape, -127, 128,
                                      jnp.int8)
    cache.v_pool = jax.random.randint(key, cache.v_pool.shape, -127, 128,
                                      jnp.int8)
    cache.k_scales = jax.random.uniform(key, cache.k_scales.shape)
    cache.v_scales = jax.random.uniform(key, cache.v_scales.shape) + 1.0
    k_snap = np.asarray(cache.k_pool[:, blocks]).copy()
    ks_snap = np.asarray(cache.k_scales[:, blocks]).copy()
    vs_snap = np.asarray(cache.v_scales[:, blocks]).copy()
    swap = SwapManager(cache, host_blocks=8)
    rec = cache.swap_out(0, swap, uid=0, total_len=8, context_len=8)
    # (swap_out released the slot)  clobber the device rows, then
    # restore into a fresh slot
    cache.k_pool = jnp.zeros_like(cache.k_pool)
    cache.k_scales = jnp.zeros_like(cache.k_scales)
    cache.v_scales = jnp.zeros_like(cache.v_scales)
    resume = cache.restore_slot(1, rec, swap)
    swap.release(rec)
    assert resume == 8
    new_blocks = list(cache._slot_blocks[1])
    assert np.array_equal(np.asarray(cache.k_pool[:, new_blocks]), k_snap)
    assert np.array_equal(np.asarray(cache.k_scales[:, new_blocks]), ks_snap)
    assert np.array_equal(np.asarray(cache.v_scales[:, new_blocks]), vs_snap)
    swap.check_conservation()
    cache.check_conservation()


# ---------------------------------------------------------------------------
# Deadline-aware shedding (PR 7 follow-on)
# ---------------------------------------------------------------------------

def _shed_requests(vocab=128):
    from repro.serving.request import Request

    rng = np.random.default_rng(0)
    mk = lambda uid, gen, **kw: Request(
        uid=uid, prompt=rng.integers(1, vocab, 8).astype(np.int32),
        max_new_tokens=gen, **kw)
    return [mk(0, 8, arrival_ms=0.0),                  # establishes the EMA
            mk(1, 8, arrival_ms=1.0, deadline_ms=1.5),  # provably unmeetable
            mk(2, 4, arrival_ms=1.0)]                   # deadline-free


def _shed_trace(cfg, params, slo):
    """One slot serialises the queue: request 0 finishes (measuring the
    decode rate) while 1 and 2 wait — only then can shedding judge 1's
    deadline against evidence."""
    from repro.serving.continuous import ContinuousEngine

    serve = ServeConfig(max_slots=1, kv_block_size=4, prefill_chunk=4,
                        max_len=64, num_blocks=32, slo=slo)
    eng = ContinuousEngine(cfg, params, serve, check_invariants=True)
    toks, stats = eng.run(_shed_requests())
    return toks, stats, eng


def test_shed_provably_unmeetable():
    cfg = _cfg()
    params = _params(cfg)
    toks, stats, eng = _shed_trace(cfg, params,
                                   SLOConfig(preemption=False, shed=True))
    assert stats["requests_shed"] == 1
    assert toks[1] == []                     # shed: no tokens at all
    assert len(toks[0]) == 8 and len(toks[2]) == 4
    assert eng.obs.metrics.get("requests_shed_total") == 1


def test_shed_off_by_default():
    cfg = _cfg()
    params = _params(cfg)
    toks, stats, _ = _shed_trace(cfg, params, SLOConfig(preemption=False))
    assert "requests_shed" not in stats
    assert len(toks[1]) > 0                  # served (late), never rejected


def test_shed_needs_measured_rate():
    """Nothing is shed before the first finish establishes ms/token —
    a request whose deadline passed before any measurement exists is
    still served."""
    from repro.serving.scheduler import Scheduler

    sched = Scheduler(2, 64, None, slo=SLOConfig(preemption=False, shed=True))
    assert sched._decode_ms_ema is None
    assert sched.shed_unmeetable(1e9) == []


# ---------------------------------------------------------------------------
# Observability: kv_pool_bytes + the 1x1-mesh trace (PR 9 follow-on)
# ---------------------------------------------------------------------------

def test_kv_pool_bytes_metric_shrinks_under_int8(tmp_path):
    cfg = _cfg()
    params = _params(cfg)

    def pool_bytes(kv_quant):
        _, _, eng = _trace(cfg, params, kv_quant=kv_quant)
        return eng.obs.metrics.get("kv_pool_bytes", shard=0)

    none_b, int8_b = pool_bytes("none"), pool_bytes("int8")
    assert none_b > 0 and int8_b > 0
    assert int8_b <= 0.55 * none_b


def test_mesh_trace_validates_with_require(tmp_path):
    """A 1x1-mesh serve run carries each shard's live-row census in its
    engine_step spans' args; the written Chrome trace validates, and
    the metrics file validates with --require for the new gauge."""
    from repro.obs import Observability
    from repro.obs.validate import (
        validate_chrome_trace,
        validate_metrics_jsonl,
    )

    cfg = _cfg()
    params = _params(cfg)
    obs = Observability(tracing=True)
    _, _, eng = _trace(cfg, params, kv_quant="int8", mesh=TRIVIAL_MESH,
                       obs=obs)
    spans = [e for e in obs.tracer.events()
             if e.get("name") == "engine_step" and e["args"]["rows"]]
    assert spans, "mesh path emitted no engine_step spans"
    # one data shard: its census is the whole step's
    assert all(e["args"]["live_rows_by_shard"] == str(e["args"]["live_rows"])
               for e in spans)
    trace_path = str(tmp_path / "trace.json")
    metrics_path = str(tmp_path / "metrics.jsonl")
    obs.tracer.write_chrome_trace(trace_path)
    eng.obs.write_metrics_jsonl(metrics_path)
    counts = validate_chrome_trace(trace_path)
    assert counts["X"] > 0
    info = validate_metrics_jsonl(metrics_path,
                                  require=("kv_pool_bytes", "kv_blocks"))
    assert info["rows"] >= 1
