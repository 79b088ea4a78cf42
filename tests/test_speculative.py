"""Speculative decoding subsystem: drafter registry + drafters,
acceptance rules (greedy token-identity, rejection sampling preserving
the target distribution), paged-cache rollback, verify-shape paged
attention, scheduler admission policies, and end-to-end greedy parity
of the speculative continuous engine against the static engine."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig, ServeConfig, SpecConfig
from repro.models.registry import get_family
from repro.nn import init
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import ServingEngine
from repro.serving.kv_cache import PagedKVCache
from repro.serving.request import Request
from repro.serving.scheduler import Scheduler, available_policies, get_policy
from repro.serving.speculative import (
    available_drafters,
    get_drafter_cls,
    make_drafter,
)
from repro.serving.speculative.accept import (
    accept_greedy,
    accept_rejection,
    softmax_rows,
)
from repro.serving.speculative.base import DraftItem
from repro.serving.speculative.ngram import lookup_continuation


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="t", family="decoder_lm", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                max_seq_len=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def build(cfg, seed=0):
    return init(get_family(cfg).specs(cfg), jax.random.PRNGKey(seed))


def draft_pair(cfg, seed=5):
    """A tiny draft model sharing the target's vocab."""
    dcfg = cfg.replace(name="draft", num_layers=1, d_model=32, d_ff=64,
                       num_heads=2, num_kv_heads=2, moe=MoEConfig())
    return dcfg, build(dcfg, seed=seed)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

def test_drafter_registry():
    assert {"ngram", "model"} <= set(available_drafters())
    assert get_drafter_cls("ngram").name == "ngram"
    with pytest.raises(ValueError, match="registered drafters"):
        get_drafter_cls("nope")
    with pytest.raises(ValueError):
        SpecConfig(drafter="nope")
    with pytest.raises(ValueError):
        SpecConfig(gamma=0)


def test_policy_registry():
    assert {"fcfs", "sjf", "prefill_first"} <= set(available_policies())
    with pytest.raises(ValueError, match="registered policies"):
        get_policy("nope")
    with pytest.raises(ValueError):
        ServeConfig(sched_policy="nope")


def test_model_drafter_requires_shared_vocab():
    cfg = tiny_cfg()
    dcfg = cfg.replace(vocab_size=cfg.vocab_size * 2)
    with pytest.raises(ValueError, match="vocab"):
        make_drafter(SpecConfig(drafter="model"), cfg, ServeConfig(),
                     draft_model=(dcfg, None))


# ---------------------------------------------------------------------------
# ngram (prompt-lookup) drafter
# ---------------------------------------------------------------------------

def test_ngram_lookup_repetition():
    # cycle ABCABC... : the trailing trigram recurs one period back, and
    # the longest-suffix match continues the cycle
    ctx = np.array([1, 2, 3, 1, 2, 3, 1, 2, 3, 1, 2], np.int32)
    out = lookup_continuation(ctx, max_tokens=4, max_ngram=3)
    assert out.tolist() == [3, 1, 2, 3]
    # shorter context: the earliest match still yields what is available
    short = np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int32)
    assert lookup_continuation(short, 4, 3).tolist() == [3, 1, 2]


def test_ngram_lookup_prefers_full_continuation():
    # suffix [9] matches at positions 0 and 3; only the first leaves a
    # 3-token continuation, so it must win over the more recent one
    ctx = np.array([9, 5, 6, 9, 7, 9], np.int32)
    out = lookup_continuation(ctx, max_tokens=3, max_ngram=1)
    assert out.tolist() == [5, 6, 9]


def test_ngram_lookup_no_match_and_budget():
    assert lookup_continuation(np.arange(10, 20), 4, 3).size == 0
    assert lookup_continuation(np.array([7]), 4, 3).size == 0
    ctx = np.array([1, 2, 1, 2], np.int32)
    assert lookup_continuation(ctx, 0, 3).size == 0
    # budget respected even when more continuation is available
    ctx = np.array([1, 2, 3, 4, 1], np.int32)
    assert lookup_continuation(ctx, 2, 1).tolist() == [2, 3]


# ---------------------------------------------------------------------------
# Acceptance rules
# ---------------------------------------------------------------------------

def test_accept_greedy_prefix():
    rows = np.zeros((4, 8), np.float32)
    rows[0, 3] = rows[1, 5] = rows[2, 1] = rows[3, 6] = 10.0  # argmax 3,5,1,6
    # draft matches argmax for 2 rows then diverges
    emitted, n = accept_greedy(np.array([3, 5, 2]), rows)
    assert (emitted, n) == ([3, 5, 1], 2)
    # full acceptance earns the bonus token
    emitted, n = accept_greedy(np.array([3, 5, 1]), rows)
    assert (emitted, n) == ([3, 5, 1, 6], 3)
    # immediate rejection still emits the row-0 argmax
    emitted, n = accept_greedy(np.array([0, 0, 0]), rows)
    assert (emitted, n) == ([3], 0)


def test_rejection_sampling_preserves_target_distribution():
    """With a point-mass draft, the first emitted token must be
    distributed exactly as the target softmax regardless of what the
    drafter proposed (the speculative-sampling theorem)."""
    rng = np.random.default_rng(0)
    V, temp = 6, 0.7
    logits = rng.standard_normal((1, V)).astype(np.float32) * 2.0
    p = softmax_rows(logits, temp)[0]
    trials = 20_000
    for d in (int(np.argmax(p)), int(np.argmin(p))):  # likely + unlikely draft
        counts = np.zeros(V)
        for t in range(trials):
            gen = np.random.default_rng(t)
            emitted, _ = accept_rejection(
                np.array([d]), np.vstack([logits, logits]), temp,
                lambda j, g=gen: g)
            counts[emitted[0]] += 1
        np.testing.assert_allclose(counts / trials, p, atol=0.015)


def test_rejection_sampling_deterministic_per_key():
    logits = np.random.default_rng(1).standard_normal((3, 8)).astype(np.float32)
    draft = np.array([2, 5])

    def rngs(j):
        return np.random.default_rng(
            np.random.SeedSequence(entropy=[0, 4, 10 + j]))

    a = accept_rejection(draft, logits, 0.8, rngs)
    b = accept_rejection(draft, logits, 0.8, rngs)
    assert a == b


# ---------------------------------------------------------------------------
# Paged-cache rollback (truncate_slot)
# ---------------------------------------------------------------------------

def test_truncate_slot_returns_blocks_and_conserves():
    cfg = tiny_cfg()
    serve = ServeConfig(max_slots=2, kv_block_size=8, max_len=64)
    cache = PagedKVCache(cfg, serve)
    cache.allocate_slot(0, 40)                  # reserves 5 blocks, holds 0
    assert cache.held_blocks(0) == 0
    cache.ensure_capacity(0, 20)                # 3 blocks
    held3 = cache.allocator.allocated_count
    assert cache.held_blocks(0) == 3 == held3
    cache.ensure_capacity(0, 33)                # grow to 5
    assert cache.held_blocks(0) == 5
    cache.truncate_slot(0, 17)                  # rollback to 3 blocks
    assert cache.held_blocks(0) == 3
    assert cache.allocator.free_count == cache.num_blocks - 3
    cache.check_conservation()
    # table rows never dangle: freed tail points at garbage again
    assert (cache.block_table[0, 3:] == cache.garbage_block).all()
    cache.ensure_capacity(0, 40)                # grow back within reservation
    assert cache.held_blocks(0) == 5
    cache.truncate_slot(0, 0)                   # full rewind
    assert cache.held_blocks(0) == 0
    cache.free_slot(0)
    cache.check_conservation()
    assert cache.allocator.free_count == cache.num_blocks


def test_ensure_capacity_respects_reservation():
    cfg = tiny_cfg()
    cache = PagedKVCache(cfg, ServeConfig(max_slots=2, kv_block_size=8,
                                          max_len=64))
    cache.allocate_slot(0, 16)                  # 2 blocks reserved
    with pytest.raises(AssertionError):
        cache.ensure_capacity(0, 17)            # 3rd block not reserved


# ---------------------------------------------------------------------------
# Verify-shape paged attention (gamma+1 consecutive rows per slot)
# ---------------------------------------------------------------------------

def _verify_shape_case(rng, B=3, T=48, Hq=8, Hkv=4, D=16, bs=8, gamma=3):
    """Rows = (slot, consecutive positions c..c+gamma) — the speculative
    verify layout: every row of a slot shares one block table, lengths
    ascend by one."""
    from tests.test_serving import _pack_pool

    k = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    k_pool, v_pool, tables = _pack_pool(k, v, bs, rng)
    c = np.array([5, 17, 0], np.int32)          # per-slot base context
    N = B * (gamma + 1)
    q = rng.standard_normal((N, Hq, D)).astype(np.float32)
    row_tables = np.zeros((N, tables.shape[1]), np.int32)
    lengths = np.zeros(N, np.int32)
    for b in range(B):
        for j in range(gamma + 1):
            r = b * (gamma + 1) + j
            row_tables[r] = tables[b]
            lengths[r] = c[b] + j + 1
    return q, k, v, k_pool, v_pool, row_tables, lengths


def test_paged_attention_verify_shape_matches_dense():
    from repro.kernels.decode_attention import (
        decode_attention_ref,
        paged_decode_attention,
    )

    rng = np.random.default_rng(2)
    gamma = 3
    q, k, v, k_pool, v_pool, row_tables, lengths = _verify_shape_case(rng)
    # dense oracle: replicate each slot's cache per row
    B = k.shape[0]
    reps = np.repeat(np.arange(B), gamma + 1)
    dense = decode_attention_ref(jnp.asarray(q), jnp.asarray(k[reps]),
                                 jnp.asarray(v[reps]), jnp.asarray(lengths))
    paged = paged_decode_attention(jnp.asarray(q), jnp.asarray(k_pool),
                                   jnp.asarray(v_pool),
                                   jnp.asarray(row_tables),
                                   jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense), atol=1e-5)


def test_paged_kernel_interpret_verify_shape():
    from repro.kernels.decode_attention.kernel import paged_decode_attention_kernel
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    rng = np.random.default_rng(3)
    q, _, _, k_pool, v_pool, row_tables, lengths = _verify_shape_case(rng)
    N, Hq, D = q.shape
    Hkv = k_pool.shape[1]
    out = paged_decode_attention_kernel(
        jnp.asarray(q).reshape(N, Hkv, Hq // Hkv, D), jnp.asarray(k_pool),
        jnp.asarray(v_pool), jnp.asarray(row_tables), jnp.asarray(lengths),
        scale=D ** -0.5, interpret=True).reshape(N, Hq, D)
    ref = paged_decode_attention_ref(jnp.asarray(q), jnp.asarray(k_pool),
                                     jnp.asarray(v_pool),
                                     jnp.asarray(row_tables),
                                     jnp.asarray(lengths), scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# Admission policies
# ---------------------------------------------------------------------------

def _policy_sched(policy):
    cfg = tiny_cfg()
    # 6 blocks of 8: uid 0 needs 4 blocks, uid 1 needs 3, uid 2 needs 1
    serve = ServeConfig(max_slots=2, kv_block_size=8, max_len=48, num_blocks=6)
    cache = PagedKVCache(cfg, serve)
    sched = Scheduler(serve.max_slots, serve.max_len, cache, policy=policy)
    sched.add(Request(uid=0, prompt=np.arange(20), max_new_tokens=10))  # 30 tok
    sched.add(Request(uid=1, prompt=np.arange(12), max_new_tokens=8))   # 20 tok
    sched.add(Request(uid=2, prompt=np.arange(4), max_new_tokens=4))    # 8 tok
    return sched


def test_sjf_admits_shortest_first():
    sched = _policy_sched("sjf")
    assert [st.request.uid for st in sched.admit(0.0)] == [2, 1]
    sched.check_conservation()


def test_prefill_first_backfills_past_blocked_head():
    sched = _policy_sched("prefill_first")
    # head (uid 0, 4 blocks) admitted; uid 1 (3 blocks) no longer fits
    # but uid 2 (1 block) backfills — fcfs would stall behind uid 1
    assert [st.request.uid for st in sched.admit(0.0)] == [0, 2]
    sched.check_conservation()


def test_fcfs_head_blocks_queue():
    sched = _policy_sched("fcfs")
    assert [st.request.uid for st in sched.admit(0.0)] == [0]
    assert sched.admit(0.0) == []               # uid 1 blocked, uid 2 waits


def test_policies_respect_arrival_times():
    cfg = tiny_cfg()
    serve = ServeConfig(max_slots=2, kv_block_size=8, max_len=48)
    for policy in available_policies():
        sched = Scheduler(2, 48, PagedKVCache(cfg, serve), policy=policy)
        sched.add(Request(uid=0, prompt=np.arange(4), max_new_tokens=4,
                          arrival_ms=50.0))
        assert sched.admit(0.0) == []
        assert [st.request.uid for st in sched.admit(50.0)] == [0]


def test_engine_runs_with_each_policy():
    cfg = tiny_cfg(num_layers=1)
    params = build(cfg)
    from repro.serving.trace import synthetic_trace

    reqs = synthetic_trace(5, cfg.vocab_size, seed=1, qps=1e6,
                           prompt_lens=(3, 10), gen_lens=(2, 5))
    outs = {}
    for policy in available_policies():
        eng = ContinuousEngine(
            cfg, params, ServeConfig(max_slots=2, kv_block_size=8,
                                     prefill_chunk=8, max_len=32,
                                     sched_policy=policy),
            check_invariants=True)
        outs[policy], _ = eng.run(reqs)
        eng.scheduler.check_conservation()
    # greedy decode: per-request outputs are policy-invariant
    assert outs["sjf"] == outs["fcfs"] == outs["prefill_first"]


# ---------------------------------------------------------------------------
# End-to-end greedy parity: speculative == non-speculative == static
# ---------------------------------------------------------------------------

def _spec_parity(cfg, B, S, gen, serve, drafter, draft_model=None, seed=0):
    import dataclasses

    params = build(cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    toks_s, _ = ServingEngine(cfg, params, max_len=S + gen + 1).generate(prompts, gen)
    sv = dataclasses.replace(serve, spec=SpecConfig(drafter=drafter, gamma=3))
    eng = ContinuousEngine(cfg, params, sv, draft_model=draft_model,
                           check_invariants=True)
    toks_c, stats = eng.generate(prompts, gen)
    np.testing.assert_array_equal(np.asarray(toks_s), np.asarray(toks_c))
    return eng, stats


def test_spec_parity_dense_ngram_slot_reuse():
    # 4 requests on 2 slots: slot reuse + queueing under speculation
    eng, stats = _spec_parity(
        tiny_cfg(num_layers=1), B=4, S=9, gen=8,
        serve=ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                          max_len=32), drafter="ngram")
    assert stats["steps"] > 0 and eng.spec_stats["verify_steps"] > 0


def test_spec_parity_dense_model_drafter():
    cfg = tiny_cfg(num_layers=1)
    _spec_parity(cfg, B=3, S=7, gen=7,
                 serve=ServeConfig(max_slots=2, kv_block_size=8,
                                   prefill_chunk=4, max_len=32),
                 drafter="model", draft_model=draft_pair(cfg))


def test_spec_parity_moe_dropless_hash():
    cfg = tiny_cfg(d_ff=96,
                   moe=MoEConfig(num_experts=4, routing="hash", top_k=2,
                                 impl="dropless", capacity_factor=None,
                                 group_size=64))
    _spec_parity(cfg, B=2, S=9, gen=7,
                 serve=ServeConfig(max_slots=2, kv_block_size=8,
                                   prefill_chunk=4, max_len=64),
                 drafter="ngram")


def test_spec_parity_moe_dropless_hash_model():
    cfg = tiny_cfg(d_ff=96,
                   moe=MoEConfig(num_experts=4, routing="hash", top_k=2,
                                 impl="dropless", capacity_factor=None,
                                 group_size=64))
    _spec_parity(cfg, B=2, S=8, gen=6,
                 serve=ServeConfig(max_slots=2, kv_block_size=8,
                                   prefill_chunk=4, max_len=32),
                 drafter="model", draft_model=draft_pair(cfg))


def test_spec_parity_moe_dropless_topk_ngram():
    cfg = tiny_cfg(d_ff=96,
                   moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                 impl="dropless", capacity_factor=None,
                                 group_size=64))
    _spec_parity(cfg, B=2, S=9, gen=7,
                 serve=ServeConfig(max_slots=2, kv_block_size=8,
                                   prefill_chunk=4, max_len=64),
                 drafter="ngram")


def test_spec_parity_moe_dropless_topk_model():
    cfg = tiny_cfg(d_ff=96,
                   moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                 impl="dropless", capacity_factor=None,
                                 group_size=64))
    _spec_parity(cfg, B=2, S=8, gen=6,
                 serve=ServeConfig(max_slots=2, kv_block_size=8,
                                   prefill_chunk=8, max_len=32),
                 drafter="model", draft_model=draft_pair(cfg))


def test_spec_multi_token_bursts_and_conservation():
    """A repetitive prompt makes the ngram drafter productive: some step
    must emit > 1 token for a slot, and slot/block/reservation
    conservation holds after every step (check_invariants=True asserts
    in-step; re-assert the drained end state)."""
    cfg = tiny_cfg(num_layers=1)
    params = build(cfg)
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=8,
                        max_len=64, spec=SpecConfig(drafter="ngram", gamma=4))
    eng = ContinuousEngine(cfg, params, serve, check_invariants=True)
    prompt = np.tile(np.array([5, 9, 7], np.int32), 5)      # strongly cyclic
    out, stats = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=24),
                          Request(uid=1, prompt=prompt[:7], max_new_tokens=20)])
    assert len(out[0]) == 24 and len(out[1]) == 20
    assert stats["spec_tokens_per_step"] > 1.0
    assert eng.spec_stats["accepted"] > 0
    eng.scheduler.check_conservation()
    assert eng.cache.allocator.free_count == serve.resolved_num_blocks


def test_spec_eos_mid_burst():
    """EOS inside an accepted burst truncates the emission at (and
    including) the EOS token, exactly like sequential decoding."""
    cfg = tiny_cfg(num_layers=1)
    params = build(cfg)

    def run(spec):
        sv = ServeConfig(max_slots=1, kv_block_size=8, prefill_chunk=8,
                         max_len=64, spec=spec)
        eng = ContinuousEngine(cfg, params, sv, check_invariants=True)
        return eng.run([Request(uid=0, prompt=np.arange(5),
                                max_new_tokens=16)])[0][0]

    base = run(None)
    eos = base[2]
    sv = SpecConfig(drafter="ngram", gamma=4)
    eng = ContinuousEngine(cfg, params,
                           ServeConfig(max_slots=1, kv_block_size=8,
                                       prefill_chunk=8, max_len=64, spec=sv),
                           check_invariants=True)
    out, _ = eng.run([Request(uid=0, prompt=np.arange(5), max_new_tokens=16,
                              eos_id=int(eos))])
    assert out[0] == base[:base.index(eos) + 1]
    # acceptance accounting counts only draft tokens actually used: the
    # EOS cut discards accepted-but-dropped drafts
    assert eng.spec_stats["accepted"] <= eng.spec_stats["emitted"]
    eng.scheduler.check_conservation()


def test_spec_temperature_runs_and_is_reproducible():
    cfg = tiny_cfg(num_layers=1)
    params = build(cfg)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 6),
                                            0, cfg.vocab_size))

    def run():
        sv = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                         max_len=32, spec=SpecConfig(drafter="ngram", gamma=3))
        eng = ContinuousEngine(cfg, params, sv, temperature=0.8, seed=3,
                               check_invariants=True)
        reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=6)
                for i in range(3)]
        return eng.run(reqs)[0]

    out1, out2 = run(), run()
    assert out1 == out2                        # per-(slot, position) keys
    assert all(len(v) == 6 for v in out1.values())
    assert all(0 <= t < cfg.vocab_size for v in out1.values() for t in v)


def test_empty_drafts_fall_back_to_decode_step():
    """A drafter that never proposes must cost (nearly) nothing: the
    engine falls through to the ordinary decode step instead of paying
    a (gamma+1)x verify forward for one token per slot.  Also exercises
    the registry plugin path."""
    from repro.serving.speculative import register_drafter

    @register_drafter
    class NullDrafter:
        name = "null-test"

        def __init__(self, spec, target_cfg, serve, *, seed=0,
                     draft_model=None):
            pass

        def propose(self, items):
            return [np.empty(0, np.int32) for _ in items]

    cfg = tiny_cfg(num_layers=1)
    params = build(cfg)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 6),
                                            0, cfg.vocab_size))
    base, _ = ContinuousEngine(
        cfg, params, ServeConfig(max_slots=2, kv_block_size=8,
                                 prefill_chunk=4, max_len=32)
    ).generate(prompts, 6)
    eng = ContinuousEngine(
        cfg, params, ServeConfig(max_slots=2, kv_block_size=8,
                                 prefill_chunk=4, max_len=32,
                                 spec=SpecConfig(drafter="null-test")),
        check_invariants=True)
    toks, _ = eng.generate(prompts, 6)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(toks))
    assert eng.spec_stats["verify_steps"] == 0   # every step fell through


def test_spec_requires_paged_mode():
    cfg = ModelConfig(name="x", family="xlstm", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32")
    with pytest.raises(NotImplementedError, match="rollback"):
        ContinuousEngine(cfg, {}, ServeConfig(
            spec=SpecConfig(drafter="ngram")))


# ---------------------------------------------------------------------------
# Example smoke (CI satellite)
# ---------------------------------------------------------------------------

def test_example_serve_decode_smoke():
    """examples/serve_decode.py --fast: static + continuous + speculative
    demo end-to-end at tiny scale."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "serve_decode.py"),
         "--fast"],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "speculative" in proc.stdout
