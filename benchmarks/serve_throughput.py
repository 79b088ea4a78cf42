"""Static vs. continuous batching on a mixed-length request trace, plus
the speculative-decoding sweep.

The static engine pays lockstep: every batch member decodes until the
batch's *longest* generation finishes, so a long-tailed gen-length mix
leaves most slots doing useless work.  The continuous engine evicts on
completion and refills the slot from the queue.  Same model, same
requests, same useful-token count — the artifact records tokens/s and
latency percentiles for both.

The speculative sweep then runs the continuous engine speculative
off / ngram-drafter / model-drafter on the same synthetic mixed-length
trace (greedy, so every cell is token-identical by construction),
recording acceptance rate, mean emitted tokens per verify step and the
throughput speedup over non-speculative continuous batching.  Every
speculative run re-asserts slot/block/reservation conservation after
*every* engine step (``check_invariants=True``).

The prefix-caching sweep serves a multi-tenant trace off/cold/warm on a
block-starved pool, and the SLO sweep serves a 2x-overload bursty
mixed-priority trace under fcfs vs the SLO-aware policies with
preemption + KV swap-to-host.

The mesh sweep serves one mixed trace on 1 vs 8 virtual devices
(single-device engine vs 1x1 / 2x4 / 8x1 ``(data, expert)`` serving
meshes, dropless throughout) and asserts token identity across every
cell — mesh sharding must be invisible in outputs.

The KV-quantization sweep serves the same trace with the KV cache at
none / int8 / fp8 (greedy token-match rate + max logit divergence per
cell), then re-serves a block-starved trace on pools sized to one
fixed byte budget — the capacity int8 quantization buys.

  PYTHONPATH=src python benchmarks/serve_throughput.py
  -> experiments/BENCH_serve_throughput.json
  -> experiments/BENCH_spec_decode.json
  -> experiments/BENCH_prefix_cache.json
  -> experiments/BENCH_slo_sched.json
  -> experiments/BENCH_kv_quant.json
  -> experiments/BENCH_mesh_serve.json   (re-execs itself with 8
     virtual devices when the parent owns fewer; --mesh-sweep runs it alone)
"""
from __future__ import annotations

import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

import dataclasses

import jax
import numpy as np

from common import bench_config, save_result
from repro.configs.base import ServeConfig, SLOConfig, SpecConfig
from repro.obs import Observability
from repro.models.registry import get_family
from repro.nn import init
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import ServingEngine
from repro.serving.trace import (
    run_trace_static,
    static_max_len,
    synthetic_multitenant,
    synthetic_priority,
    synthetic_trace,
)

MAX_SLOTS = 4
TRACE_KW = dict(seed=0, qps=1e6,                # saturated: measure batching, not arrivals
                prompt_lens=(8, 24),
                gen_lens=(8, 8, 8, 64))         # long tail: lockstep's worst case
SPEC_GAMMA = 4


def obs_sweep(cfg, params, requests, serve: ServeConfig):
    """Instrumentation overhead: the same trace served with observability
    at its default level (registry only) vs fully on (span tracing +
    periodic metrics snapshots).  Greedy, so the cells must be
    token-identical (asserted) — instrumentation reads engine state, it
    never steers it.  The artifact records the throughput ratio; the
    in-code bound is deliberately loose (>= 0.75) because toy-model CPU
    steps are microseconds — the acceptance target (within 5%) applies
    at realistic step times where the fixed per-step cost amortises."""
    eng_off = ContinuousEngine(cfg, params, serve)
    eng_off.run(requests)                           # warmup/compile
    out_off, stats_off = eng_off.run(requests)

    obs = Observability(tracing=True)
    obs.metrics_every = 10
    eng_on = ContinuousEngine(cfg, params, serve, obs=obs)
    eng_on.run(requests)                            # warmup/compile
    out_on, stats_on = eng_on.run(requests)

    assert out_on == out_off, "observability changed generated tokens"
    ratio = (stats_on["generated_tokens_per_s"]
             / stats_off["generated_tokens_per_s"])
    assert ratio >= 0.75, f"observability overhead too high ({ratio:.2f}x)"
    return {
        "off": stats_off,
        "on": stats_on,
        "tokens_per_s_ratio_on_over_off": ratio,
        "trace_events": len(obs.tracer.events()),
        "trace_dropped_events": obs.tracer.dropped_events,
        "metrics": obs.metrics.snapshot(),
    }


def spec_sweep(cfg, params, requests, serve: ServeConfig):
    """Speculative off / ngram / model on one trace; greedy throughout,
    so outputs are token-identical across cells (asserted).

    The sweep serves with the ``dropless`` dispatcher: token-identity
    needs batch-composition-invariant routing, and a finite
    capacity_factor computes per-expert capacity from the row count —
    which differs between decode (max_slots rows) and verify
    (max_slots*(gamma+1) rows) steps, so capacity-limited cells could
    legitimately diverge (see docs/serving.md).  Same params either
    way: dispatchers are execution backends, not parameters."""
    cfg = cfg.replace_moe(impl="dropless", capacity_factor=None)
    # a deliberately tiny draft model (shared vocab, ~1/4 the target's
    # width): what the "model" drafter buys depends entirely on how well
    # it predicts the target — with both randomly initialised they
    # disagree, so this cell is the honest floor (the ngram cell needs
    # no such luck: it drafts from the slot's own context)
    dcfg = cfg.replace(name="draft", num_layers=1, d_model=32, d_ff=64,
                       num_heads=2, num_kv_heads=2,
                       moe=dataclasses.replace(cfg.moe, num_experts=0))
    dparams = init(get_family(dcfg).specs(dcfg), jax.random.PRNGKey(7))
    cells = {
        "off": (None, None),
        "ngram": (SpecConfig(drafter="ngram", gamma=SPEC_GAMMA), None),
        "model": (SpecConfig(drafter="model", gamma=SPEC_GAMMA), (dcfg, dparams)),
    }
    results, outs = {}, {}
    for name, (spec, draft_model) in cells.items():
        sv = dataclasses.replace(serve, spec=spec)
        eng = ContinuousEngine(cfg, params, sv, draft_model=draft_model,
                               check_invariants=True)
        eng.run(requests)                       # warmup/compile
        outs[name], stats = eng.run(requests)
        results[name] = stats
    results["metrics"] = eng.obs.metrics.snapshot()
    for name in ("ngram", "model"):             # greedy => identical outputs
        assert outs[name] == outs["off"], f"{name} diverged from baseline"
        results[name]["speedup_vs_off"] = (
            results[name]["generated_tokens_per_s"]
            / results["off"]["generated_tokens_per_s"])
    return results


def prefix_sweep(cfg, params):
    """Prefix caching off / cold / warm on a saturated multi-tenant
    trace over a deliberately block-starved pool (12 blocks; a request's
    cold footprint is 5, its exclusive footprint once the 3-block tenant
    system prompt is shared is 2 — so sharing admits more concurrent
    requests, not just fewer prefill steps).  Greedy + dropless
    dispatch, so all three cells are token-identical (asserted); every
    cell re-asserts refcount/reservation conservation after every
    engine step.

    "cold" is the first serve of these tenants on a compiled engine
    (within-trace live sharing only); "warm" re-serves the same trace
    with the cache populated.  Compilation is paid beforehand on a
    disjoint-tenant trace whose blocks cannot match this one."""
    cfg = cfg.replace_moe(impl="dropless", capacity_factor=None)
    trace_kw = dict(qps=1e6, num_tenants=2, system_prompt_len=48,
                    suffix_lens=(2, 12), gen_lens=(4, 8, 16))
    requests = synthetic_multitenant(16, cfg.vocab_size, seed=0, **trace_kw)
    serve = ServeConfig(max_slots=MAX_SLOTS, kv_block_size=16,
                        prefill_chunk=16, num_blocks=12,
                        max_len=max(r.total_len for r in requests))

    results = {"trace": {
        "num_requests": len(requests), **trace_kw,
        "num_blocks": serve.num_blocks,
        "prompt_lens": [r.prompt_len for r in requests],
        "gen_lens": [r.max_new_tokens for r in requests],
    }}
    outs = {}

    eng_off = ContinuousEngine(cfg, params, serve, check_invariants=True)
    eng_off.run(requests)                                  # warmup/compile
    outs["off"], results["off"] = eng_off.run(requests)

    sv = dataclasses.replace(serve, prefix_cache=True)
    eng = ContinuousEngine(cfg, params, sv, check_invariants=True)
    eng.run(synthetic_multitenant(16, cfg.vocab_size, seed=99, **trace_kw))
    outs["cold"], results["cold"] = eng.run(requests)
    outs["warm"], results["warm"] = eng.run(requests)
    results["cache_stats"] = dict(eng.cache.stats)
    results["metrics"] = eng.obs.metrics.snapshot()
    eng.cache.check_conservation()

    for name in ("cold", "warm"):
        assert outs[name] == outs["off"], f"{name} diverged from baseline"
        results[name]["speedup_vs_off"] = (
            results[name]["generated_tokens_per_s"]
            / results["off"]["generated_tokens_per_s"])
    results["effective_capacity_multiplier"] = (
        results["warm"]["peak_running"] / results["off"]["peak_running"])
    return results


def slo_sweep(cfg, params):
    """SLO scheduling under 2x overload: fcfs vs the SLO-aware policies
    (each with preemption + KV swap-to-host) on one bursty
    mixed-priority trace.

    Calibration first: a saturated fcfs run measures the engine's
    serving capacity (tokens/s), and the benchmark trace's arrival rate
    is set so the *offered* load averages twice that — the regime where
    scheduling policy decides who eats the queueing delay.  Greedy +
    dropless dispatch, so every cell is token-identical per request
    (asserted — preemption/restore must be invisible in outputs); every
    cell re-asserts slot/block/reservation conservation after every
    step, including the host-swap-pool bijection
    (``check_invariants=True`` with ``ServeConfig.slo`` set).

    Headline numbers: the fcfs→priority_strict ratio of HIGH-class p95
    latency (how much tail the priority classes buy the paying tier)
    and the throughput ratio (what the swap traffic costs)."""
    from repro.serving.request import Priority

    cfg = cfg.replace_moe(impl="dropless", capacity_factor=None)
    # the classic tiered shape: sparse short interactive HIGH requests
    # against long batch NORMAL/LOW ones — queue-jumping (and evicting a
    # long decode mid-flight) is exactly what buys HIGH its tail
    trace_kw = dict(prompt_lens=(8, 24), gen_lens=(16, 32, 64),
                    gen_lens_by_class={Priority.HIGH: (4, 8)},
                    class_weights=(0.125, 0.5, 0.375),
                    burst_len=8, system_prompt_len=16, num_tenants=2)
    # 28 blocks = 4 slots x 7-block worst case: slots, not blocks, are
    # the binding constraint, so the policies differ by *ordering* and
    # swap overhead, not by how well they pack a starved pool
    serve_kw = dict(max_slots=MAX_SLOTS, kv_block_size=16,
                    prefill_chunk=16, num_blocks=28, prefix_cache=True)

    calib = synthetic_priority(24, cfg.vocab_size, seed=1, qps=1e6,
                               **trace_kw)
    serve = ServeConfig(**serve_kw, max_len=max(r.total_len for r in calib))
    eng = ContinuousEngine(cfg, params, serve, check_invariants=True)
    eng.run(calib)                                        # warmup/compile
    _, cstats = eng.run(calib)
    cap_tok_s = cstats["generated_tokens_per_s"]
    mean_gen = float(np.mean([r.max_new_tokens for r in calib]))
    # bursts alternate q / 3q every burst_len requests: mean offered
    # rate is 1.5q, so q = (4/3) * capacity gives 2x overload overall
    qps = (4.0 / 3.0) * cap_tok_s / mean_gen
    requests = synthetic_priority(128, cfg.vocab_size, seed=0, qps=qps,
                                  burst_qps=3.0 * qps, **trace_kw)
    max_len = max(r.total_len for r in requests)
    # per-cell warmup trace: disjoint seed, so compilation is paid
    # without warming the benchmark trace's tenant prompts in the cache
    warmup = synthetic_priority(16, cfg.vocab_size, seed=99, qps=1e6,
                                **trace_kw)

    results = {"trace": {
        "num_requests": len(requests), "qps": qps, "burst_qps": 3.0 * qps,
        "capacity_tokens_per_s": cap_tok_s, "overload_factor": 2.0,
        "class_counts": {p.name.lower():
                         sum(r.priority is p for r in requests)
                         for p in sorted({r.priority for r in requests})},
    }}
    outs = {}
    for name in ("fcfs", "priority_strict", "edf", "cache_aware"):
        # host pool sized for several concurrent victims: a mirror-size
        # pool fills after a few preempted working sets, after which
        # preemption declines and HIGH waits
        slo = (SLOConfig(preemption=True, host_blocks=2 * 28)
               if name != "fcfs" else None)
        sv = ServeConfig(**serve_kw, max_len=max_len, sched_policy=name,
                         slo=slo)
        cell = ContinuousEngine(cfg, params, sv, check_invariants=True)
        cell.run(warmup)                                  # warmup/compile
        outs[name], results[name] = cell.run(requests)
    results["metrics"] = cell.obs.metrics.snapshot()
    for name in ("priority_strict", "edf", "cache_aware"):
        assert outs[name] == outs["fcfs"], (
            f"{name} diverged from fcfs outputs — preemption must be "
            f"invisible under greedy decoding")
        results[name]["tokens_per_s_vs_fcfs"] = (
            results[name]["generated_tokens_per_s"]
            / results["fcfs"]["generated_tokens_per_s"])
    results["high_p95_ratio_fcfs_over_strict"] = (
        results["fcfs"]["high_p95_ms"]
        / max(results["priority_strict"]["high_p95_ms"], 1e-9))
    return results


def _token_match(ref, got):
    """Fraction of greedy tokens identical to the baseline, per position
    per request (missing/extra positions count as mismatches)."""
    tot = hit = 0
    for uid in ref:
        a, b = ref[uid], got.get(uid, [])
        tot += max(len(a), len(b))
        hit += sum(1 for x, y in zip(a, b) if x == y)
    return hit / max(tot, 1)


def quant_sweep(cfg, params):
    """KV-cache quantization none / int8 / fp8 (repro.quant) on one
    saturated mixed-length trace, answering two questions.

    Fidelity: what does storing K/V as int8 codes + per-block scales
    cost in outputs?  Each cell records tokens/s and the greedy
    token-match rate against the f32 baseline, plus the maximum
    per-row logit divergence measured on a single-request replay
    through the engine's ``logit_tap`` (rows matched by (slot,
    position); padding rows excluded).

    Capacity: what do the saved bytes buy?  The capacity cell re-serves
    a block-starved trace on pools sized to one fixed device byte
    budget — int8 codes + scales pack ~3.9x the blocks of f32 into the
    same bytes, so block reservations stop gating admission and peak
    concurrency rises (>= 1.3x asserted) while greedy outputs stay
    >= 98% token-identical (asserted; dropless dispatch keeps routing
    batch-composition-invariant, so the only divergence source is
    quantization error itself).  Every cell re-asserts conservation
    after every step, including the code-pool/scale-pool bijection
    (``check_invariants=True``)."""
    cfg = cfg.replace_moe(impl="dropless", capacity_factor=None)
    requests = synthetic_trace(16, cfg.vocab_size, **TRACE_KW)
    serve = ServeConfig(max_slots=MAX_SLOTS, kv_block_size=16,
                        prefill_chunk=16,
                        max_len=max(r.total_len for r in requests))

    results = {"trace": {
        "num_requests": len(requests),
        "prompt_lens": [r.prompt_len for r in requests],
        "gen_lens": [r.max_new_tokens for r in requests],
    }}
    outs = {}
    for name in ("none", "int8", "fp8"):
        sv = dataclasses.replace(serve, kv_quant=name)
        eng = ContinuousEngine(cfg, params, sv, check_invariants=True)
        eng.run(requests)                       # warmup/compile
        outs[name], results[name] = eng.run(requests)
        occ = eng.cache.occupancy()[0]
        results[name]["block_bytes"] = occ["block_bytes"]
        results[name]["kv_pool_bytes"] = (occ["block_bytes"]
                                          * eng.cache.num_blocks)
        eng.cache.check_conservation()
    results["metrics"] = eng.obs.metrics.snapshot()

    # -- logit divergence: single-request greedy replay under the tap ------
    probe = synthetic_trace(1, cfg.vocab_size, seed=3, qps=1e6,
                            prompt_lens=(24, 24), gen_lens=(64, 64))

    def replay(name):
        rows = {}

        def tap(lg, slots, pos, lens):
            for i, ln in enumerate(lens):
                if ln > 0:                      # length 0 = padding row
                    rows[int(slots[i]), int(pos[i])] = np.array(lg[i])

        sv = dataclasses.replace(serve, max_slots=1, kv_quant=name,
                                 max_len=max(r.total_len for r in probe))
        eng = ContinuousEngine(cfg, params, sv, logit_tap=tap)
        return eng.run(probe)[0], rows

    base_out, base_rows = replay("none")
    results["none"]["token_match_rate"] = 1.0
    results["none"]["max_logit_divergence"] = 0.0
    for name in ("int8", "fp8"):
        out, rows = replay(name)
        common = base_rows.keys() & rows.keys()
        results[name]["max_logit_divergence"] = max(
            float(np.abs(rows[k] - base_rows[k]).max()) for k in common)
        results[name]["logit_rows_compared"] = len(common)
        results[name]["token_match_rate"] = _token_match(outs["none"],
                                                         outs[name])
        results[name]["tokens_per_s_vs_none"] = (
            results[name]["generated_tokens_per_s"]
            / results["none"]["generated_tokens_per_s"])

    # -- capacity at one fixed device byte budget ---------------------------
    # shorter generations than TRACE_KW: a 3-block worst-case footprint
    # lets the block-rich int8 pool actually run many requests at once
    # instead of queueing on slots
    cap_kw = dict(seed=0, qps=1e6, prompt_lens=(8, 16), gen_lens=(16, 24))
    cap_req = synthetic_trace(24, cfg.vocab_size, **cap_kw)
    bs = 16
    per_entry = cfg.num_kv_heads * bs * cfg.resolved_head_dim
    bbytes = {"none": 2 * cfg.num_layers * per_entry * 4,
              "int8": 2 * cfg.num_layers * (per_entry
                                            + 4 * cfg.num_kv_heads)}
    budget = 8 * bbytes["none"]                 # an 8-f32-block pool
    cap = {"trace": {"num_requests": len(cap_req), **cap_kw,
                     "budget_bytes": budget}}
    cap_outs = {}
    for name in ("none", "int8"):
        nblocks = budget // bbytes[name]
        sv = ServeConfig(max_slots=8, kv_block_size=bs, prefill_chunk=16,
                         num_blocks=nblocks, kv_quant=name,
                         max_len=max(r.total_len for r in cap_req))
        eng = ContinuousEngine(cfg, params, sv, check_invariants=True)
        assert eng.cache.block_bytes == bbytes[name], "budget math drifted"
        eng.run(cap_req)                        # warmup/compile
        cap_outs[name], cap[name] = eng.run(cap_req)
        cap[name]["num_blocks"] = nblocks
        cap[name]["kv_pool_bytes"] = nblocks * bbytes[name]
        eng.cache.check_conservation()
    cap["int8"]["token_match_rate"] = _token_match(cap_outs["none"],
                                                   cap_outs["int8"])
    cap["peak_running_multiplier"] = (
        cap["int8"]["peak_running"] / max(cap["none"]["peak_running"], 1e-9))
    assert cap["peak_running_multiplier"] >= 1.3, (
        f"equal-byte int8 pool should lift peak concurrency "
        f"({cap['peak_running_multiplier']:.2f}x)")
    assert cap["int8"]["token_match_rate"] >= 0.98, (
        f"int8 capacity cell drifted from f32 outputs "
        f"({cap['int8']['token_match_rate']:.3f} match)")
    results["capacity"] = cap
    return results


def mesh_sweep(cfg, params):
    """Single-device vs mesh-sharded serving on one mixed-length trace:
    the trivial 1x1 mesh, a (data 2, expert 4) mesh and a pure-data
    (8, 1) mesh, all dropless (the ragged EP path where the shape
    divides the device grid).  Greedy, so every cell must be
    token-identical to the unsharded engine (asserted) — on virtual CPU
    devices the collectives are pure overhead, so the artifact records
    the *cost* of sharding at toy scale next to the identity guarantee,
    not a speedup."""
    # group_size=1 keeps G = row count, which divides the 8-device grid
    # for every compiled step shape here — the ragged EP path engages on
    # the expert-sharded cells rather than falling back to GSPMD
    cfg = cfg.replace_moe(impl="dropless", capacity_factor=None, group_size=1)
    requests = synthetic_trace(16, cfg.vocab_size, **TRACE_KW)
    serve_kw = dict(max_slots=8, kv_block_size=16, prefill_chunk=16,
                    max_len=max(r.total_len for r in requests))
    cells = {
        "single": None,
        "mesh_1x1": (("data", 1), ("expert", 1)),
        "mesh_2x4": (("data", 2), ("expert", 4)),
        "mesh_8x1": (("data", 8), ("expert", 1)),
    }
    results = {"trace": {
        "num_requests": len(requests),
        "devices": jax.device_count(),
        "prompt_lens": [r.prompt_len for r in requests],
        "gen_lens": [r.max_new_tokens for r in requests],
    }}
    outs = {}
    for name, spec in cells.items():
        need = 1 if spec is None else spec[0][1] * spec[1][1]
        if jax.device_count() < need:
            results[name] = {"skipped": f"needs {need} devices"}
            continue
        sv = ServeConfig(**serve_kw, mesh=spec)
        eng = ContinuousEngine(cfg, params, sv, check_invariants=True)
        eng.run(requests)                       # warmup/compile
        outs[name], results[name] = eng.run(requests)
        eng.cache.check_conservation()
        results["metrics"] = eng.obs.metrics.snapshot()
    for name in outs:
        if name == "single":
            continue
        assert outs[name] == outs["single"], (
            f"{name} diverged from the single-device engine — mesh "
            f"sharding must be token-invisible under greedy decoding")
        results[name]["tokens_per_s_vs_single"] = (
            results[name]["generated_tokens_per_s"]
            / results["single"]["generated_tokens_per_s"])
    return results


def main_mesh():
    """The mesh sweep alone — run in an 8-virtual-device process (main()
    re-execs this when the parent owns fewer)."""
    cfg = bench_config(layers=2, d_model=64, d_ff=128, experts=8, vocab=512,
                       impl="dropless", capacity_factor=None)
    params = init(get_family(cfg).specs(cfg), jax.random.PRNGKey(0))
    res = mesh_sweep(cfg, params)
    for name in ("single", "mesh_1x1", "mesh_2x4", "mesh_8x1"):
        c = res[name]
        if "skipped" in c:
            print(f"mesh[{name}]: skipped ({c['skipped']})")
            continue
        extra = (f" ({c['tokens_per_s_vs_single']:.2f}x vs single)"
                 if "tokens_per_s_vs_single" in c else "")
        print(f"mesh[{name}]: {c['generated_tokens_per_s']:.1f} tok/s, "
              f"p50 {c['p50_ms']:.0f}ms p95 {c['p95_ms']:.0f}ms{extra}")
    path = save_result("BENCH_mesh_serve", res)
    print("wrote", path)


def main():
    cfg = bench_config(layers=2, d_model=64, d_ff=128, experts=8, vocab=512,
                       impl="gather")
    fam = get_family(cfg)
    params = init(fam.specs(cfg), jax.random.PRNGKey(0))
    requests = synthetic_trace(16, cfg.vocab_size, **TRACE_KW)
    max_total = max(r.total_len for r in requests)
    static_len = static_max_len(requests)
    serve = ServeConfig(max_slots=MAX_SLOTS, kv_block_size=16,
                        prefill_chunk=16, max_len=max_total)

    results = {"trace": {
        "num_requests": len(requests),
        "prompt_lens": [r.prompt_len for r in requests],
        "gen_lens": [r.max_new_tokens for r in requests],
    }}

    static = ServingEngine(cfg, params, max_len=static_len)
    run_trace_static(static, requests, MAX_SLOTS)          # warmup/compile
    _, results["static"] = run_trace_static(static, requests, MAX_SLOTS)

    cont = ContinuousEngine(cfg, params, serve)
    cont.run(requests)                                     # warmup/compile
    _, results["continuous"] = cont.run(requests)          # engine drains clean
    results["metrics"] = cont.obs.metrics.snapshot()

    s, c = results["static"], results["continuous"]
    results["speedup_tokens_per_s"] = (
        c["generated_tokens_per_s"] / s["generated_tokens_per_s"])
    print(f"static:     {s['generated_tokens_per_s']:.1f} tok/s, "
          f"p50 {s['p50_ms']:.0f}ms p95 {s['p95_ms']:.0f}ms")
    print(f"continuous: {c['generated_tokens_per_s']:.1f} tok/s, "
          f"p50 {c['p50_ms']:.0f}ms p95 {c['p95_ms']:.0f}ms "
          f"({results['speedup_tokens_per_s']:.2f}x)")
    results["obs"] = obs_sweep(cfg, params, requests, serve)
    print(f"obs overhead: "
          f"{results['obs']['tokens_per_s_ratio_on_over_off']:.2f}x tok/s "
          f"with tracing+metrics on "
          f"({results['obs']['trace_events']} trace events)")
    path = save_result("BENCH_serve_throughput", results)
    print("wrote", path)

    # -- speculative decoding sweep (same trace, continuous engine) --------
    spec_results = {
        "trace": results["trace"],
        "gamma": SPEC_GAMMA,
        "cells": spec_sweep(cfg, params, requests, serve),
    }
    for name in ("ngram", "model"):
        c = spec_results["cells"][name]
        print(f"spec[{name}]: {c['generated_tokens_per_s']:.1f} tok/s "
              f"({c['speedup_vs_off']:.2f}x), acceptance "
              f"{c['acceptance_rate']:.2f}, "
              f"{c['spec_tokens_per_step']:.2f} tok/verify-step")
    path = save_result("BENCH_spec_decode", spec_results)
    print("wrote", path)

    # -- prefix caching sweep (multi-tenant trace, constrained pool) -------
    pres = prefix_sweep(cfg, params)
    for name in ("off", "cold", "warm"):
        c = pres[name]
        extra = ""
        if name != "off":
            extra = (f" ({c['speedup_vs_off']:.2f}x, "
                     f"{c['cached_token_ratio']:.0%} prompt tokens cached)")
        print(f"prefix[{name}]: {c['generated_tokens_per_s']:.1f} tok/s, "
              f"p50 {c['p50_ms']:.0f}ms p95 {c['p95_ms']:.0f}ms, "
              f"peak {c['peak_running']:.0f} running{extra}")
    print(f"effective capacity multiplier "
          f"{pres['effective_capacity_multiplier']:.2f}x")
    path = save_result("BENCH_prefix_cache", pres)
    print("wrote", path)

    # -- SLO scheduling sweep (2x-overload mixed-priority trace) -----------
    sres = slo_sweep(cfg, params)
    for name in ("fcfs", "priority_strict", "edf", "cache_aware"):
        c = sres[name]
        pre = (f", {c['preemptions']:.0f} preemptions "
               f"({c['swapped_blocks']:.0f} blocks swapped)"
               if "preemptions" in c else "")
        print(f"slo[{name}]: {c['generated_tokens_per_s']:.1f} tok/s, "
              f"high p95 {c['high_p95_ms']:.0f}ms, "
              f"goodput {c.get('goodput', 0):.0%}{pre}")
    print(f"high-class p95: fcfs/priority_strict = "
          f"{sres['high_p95_ratio_fcfs_over_strict']:.2f}x")
    path = save_result("BENCH_slo_sched", sres)
    print("wrote", path)

    # -- KV-quantization sweep (fidelity + equal-byte capacity) ------------
    qres = quant_sweep(cfg, params)
    for name in ("none", "int8", "fp8"):
        c = qres[name]
        extra = ""
        if name != "none":
            extra = (f", match {c['token_match_rate']:.1%}, "
                     f"max logit drift {c['max_logit_divergence']:.3g}")
        print(f"quant[{name}]: {c['generated_tokens_per_s']:.1f} tok/s, "
              f"{c['block_bytes']} B/block{extra}")
    qc = qres["capacity"]
    print(f"quant capacity: {qc['int8']['num_blocks']} int8 vs "
          f"{qc['none']['num_blocks']} f32 blocks in "
          f"{qc['trace']['budget_bytes']} B -> "
          f"{qc['peak_running_multiplier']:.2f}x peak running, "
          f"match {qc['int8']['token_match_rate']:.1%}")
    path = save_result("BENCH_kv_quant", qres)
    print("wrote", path)

    # -- mesh-sharded serving sweep (needs 8 virtual devices) --------------
    if jax.device_count() >= 8:
        main_mesh()
    else:
        # A CPU sweep by design: the child is pinned to the CPU so it never
        # competes with this process for an accelerator.
        import subprocess
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"
        subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--mesh-sweep"], check=True, env=env)


if __name__ == "__main__":
    if "--mesh-sweep" in sys.argv:
        main_mesh()
    else:
        main()
