"""Serving driver: static lockstep batching or continuous batching, over
synthetic prompts or a request trace.

  # static lockstep batch (the original smoke mode)
  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --smoke \
      --engine static --batch 4 --prompt-len 16 --gen 32

  # continuous batching over a synthetic Poisson mixed-length trace
  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --smoke \
      --engine continuous --requests 16 --qps 40

  # trace-driven (JSONL of {"prompt_len", "gen_len", "arrival_ms"})
  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --smoke \
      --engine continuous --trace trace.jsonl
"""
from __future__ import annotations

import argparse
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ServeConfig, SLOConfig, SpecConfig
from repro.configs.registry import ALL_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_family
from repro.nn import abstract, init as init_params
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import ServingEngine
from repro.serving.trace import (
    latency_line,
    load_trace,
    run_trace_static,
    static_max_len,
    slo_class_line,
    synthetic_multitenant,
    synthetic_priority,
    synthetic_trace,
)


class ServeResult(NamedTuple):
    """What :func:`main` served: the engine, the trace (None in batch
    mode), the generated tokens (``{uid: tokens}`` for a trace, a
    ``(batch, gen)`` array in batch mode) and the run's stats."""
    engine: Any
    requests: Optional[List]
    outputs: Any
    stats: Dict[str, float]


def parse_mesh(text: str):
    """'data=2,expert=4' or bare '2,4' -> ServeConfig.mesh tuples."""
    sizes = {}
    parts = [p.strip() for p in text.split(",") if p.strip()]
    if all("=" in p for p in parts):
        for p in parts:
            name, _, n = p.partition("=")
            sizes[name.strip()] = int(n)
    else:
        if len(parts) > 2:
            raise SystemExit(f"--mesh {text!r}: at most data,expert sizes")
        for name, n in zip(("data", "expert"), parts):
            sizes[name] = int(n)
    unknown = set(sizes) - {"data", "expert"}
    if unknown:
        raise SystemExit(
            f"--mesh {text!r}: unknown axes {sorted(unknown)} "
            "(serving meshes have axes data, expert)")
    return (("data", sizes.get("data", 1)), ("expert", sizes.get("expert", 1)))


def _write_obs(engine, args) -> None:
    """Flush the engine's tracer / metrics registry to the requested
    output files (docs/observability.md documents both formats)."""
    if args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            engine.obs.tracer.write_jsonl(args.trace_out)
        else:
            engine.obs.tracer.write_chrome_trace(args.trace_out)
        print(f"trace -> {args.trace_out} "
              f"({len(engine.obs.tracer.events())} events, "
              f"{engine.obs.tracer.dropped_events} dropped)")
    if args.metrics_out:
        engine.obs.write_metrics_jsonl(args.metrics_out)
        print(f"metrics -> {args.metrics_out}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b", choices=ALL_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", default="static", choices=["static", "continuous"])
    ap.add_argument("--batch", type=int, default=4,
                    help="static engine batch size (trace groups / smoke batch)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    # trace-driven mode
    ap.add_argument("--trace", default=None,
                    help="JSONL trace of {prompt_len, gen_len, arrival_ms}; "
                         "omit for a synthetic mixed-length Poisson trace")
    ap.add_argument("--requests", type=int, default=0,
                    help="synthesize a trace of this many requests (>0 "
                         "switches to trace mode without --trace)")
    ap.add_argument("--qps", type=float, default=50.0,
                    help="synthetic trace Poisson arrival rate")
    ap.add_argument("--trace-kind", default="mixed",
                    choices=["mixed", "multitenant", "priority"],
                    help="synthetic trace family: mixed-length Poisson, "
                         "multi-tenant shared-system-prompt (the workload "
                         "--prefix-cache targets), or bursty mixed-priority "
                         "overload with deadlines (the workload --slo-preempt "
                         "and the slo policies target)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="multitenant/priority trace: distinct system prompts")
    ap.add_argument("--system-prompt-len", type=int, default=48,
                    help="multitenant trace: shared system-prompt length")
    ap.add_argument("--burst-qps", type=float, default=None,
                    help="priority trace: arrival rate during bursts "
                         "(default 4x --qps)")
    # continuous-batching shapes
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--kv-block", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--mesh", default=None,
                    help="shard the continuous engine over a (data, expert) "
                         "device mesh: 'data=2,expert=4' (or bare '2,4'). "
                         "Slots and KV block pools partition over the data "
                         "axis, expert FFN weights over the expert axis "
                         "(ragged all-to-all dropless dispatch)")
    from repro.serving.scheduler import available_policies
    ap.add_argument("--sched-policy", default="fcfs",
                    choices=available_policies(),
                    help="admission policy (fcfs | sjf | prefill_first)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="content-addressed block-level prefix caching: "
                         "admission binds cached prompt-prefix blocks and "
                         "skips their prefill (continuous engine only)")
    ap.add_argument("--slo-preempt", action="store_true",
                    help="SLO-aware preemption: let a higher-priority arrival "
                         "evict a running lower-priority request, swapping its "
                         "KV blocks to a host pool for later restore "
                         "(continuous engine only)")
    ap.add_argument("--slo-shed", action="store_true",
                    help="deadline-aware admission shedding: reject a queued "
                         "request at the door once its deadline is provably "
                         "unmeetable from the measured decode rate "
                         "(continuous engine only)")
    ap.add_argument("--host-blocks", type=int, default=None,
                    help="host swap pool size in KV blocks "
                         "(default: mirror the device pool)")
    from repro.quant import available_kv_quants
    ap.add_argument("--kv-quant", default="none",
                    choices=available_kv_quants(),
                    help="KV-cache pool representation: quantized pools store "
                         "int8 codes + per-block-per-head f32 scales, with "
                         "fused dequant in paged attention "
                         "(continuous engine only)")
    # speculative decoding (continuous engine only)
    from repro.serving.speculative import available_drafters
    ap.add_argument("--spec-drafter", default=None,
                    choices=[None, *available_drafters()],
                    help="enable speculative decoding with this drafter")
    ap.add_argument("--spec-gamma", type=int, default=4,
                    help="max draft tokens per slot per verify step")
    ap.add_argument("--spec-draft", default=None, choices=[None, *ALL_IDS],
                    help="draft model config id for --spec-drafter model "
                         "(smoke-sized; must share the target vocab)")
    ap.add_argument("--spec-draft-ckpt", default=None,
                    help="checkpoint dir for the draft model's params "
                         "(params-only restore; without it the draft model "
                         "is randomly initialised, which costs — not buys — "
                         "throughput)")
    from repro.core.dispatch import available_dispatchers
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, *available_dispatchers()],
                    help="override the MoE execution backend for serving")
    ap.add_argument("--capacity-factor", default=None,
                    help="gamma, or 'none' for dropless serving")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    # observability (repro.obs; continuous engine only)
    ap.add_argument("--trace-out", default=None,
                    help="write request-lifecycle + engine-step spans here: "
                         "Chrome-trace JSON (open in Perfetto), or span "
                         "JSONL when the path ends in .jsonl")
    ap.add_argument("--metrics-out", default=None,
                    help="write registry snapshots as metrics JSONL "
                         "(periodic rows per --metrics-every + a final row)")
    ap.add_argument("--metrics-every", type=int, default=50,
                    help="snapshot the registry every N engine steps")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler device trace of the serve "
                         "run into this directory (view with TensorBoard "
                         "or Perfetto)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh_spec = None
    if args.mesh is not None:
        if args.engine != "continuous":
            raise SystemExit("--mesh needs --engine continuous")
        mesh_spec = parse_mesh(args.mesh)

    obs = None
    if args.trace_out or args.metrics_out or args.profile_dir:
        if args.engine != "continuous":
            raise SystemExit("--trace-out/--metrics-out/--profile-dir need "
                             "--engine continuous")
        from repro.obs import Observability

        obs = Observability(tracing=args.trace_out is not None)
        if args.metrics_out:
            obs.metrics_every = max(args.metrics_every, 1)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.moe_impl and cfg.moe.num_experts:
        cfg = cfg.replace_moe(impl=args.moe_impl)
    if args.capacity_factor is not None and cfg.moe.num_experts:
        from repro.launch.train import parse_capacity_factor
        cfg = cfg.replace_moe(
            capacity_factor=parse_capacity_factor(args.capacity_factor))
    fam = get_family(cfg)
    specs = fam.specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        # params-only restore: no throwaway optimizer, no TrainState —
        # the Checkpointer maps the params subtree out of a train.py
        # checkpoint (or a bare-params one) directly.
        ckpt = Checkpointer(args.ckpt_dir)
        restored, step = ckpt.restore_params_latest(abstract(specs))
        if restored is not None:
            params = restored
            print(f"restored params-only from checkpoint step {step}")

    spec = None
    draft_model = None
    if args.spec_drafter is not None:
        if args.engine != "continuous":
            raise SystemExit("--spec-drafter needs --engine continuous")
        spec = SpecConfig(drafter=args.spec_drafter, gamma=args.spec_gamma,
                          draft=args.spec_draft)
        if args.spec_draft_ckpt:
            if args.spec_drafter != "model":
                raise SystemExit("--spec-draft-ckpt needs --spec-drafter model")
            if args.spec_draft is None:
                raise SystemExit("--spec-draft-ckpt needs --spec-draft")
            dcfg = get_smoke_config(args.spec_draft) if args.smoke else (
                get_config(args.spec_draft))
            dparams = init_params(get_family(dcfg).specs(dcfg),
                                  jax.random.PRNGKey(args.seed + 1))
            restored, dstep = Checkpointer(args.spec_draft_ckpt) \
                .restore_params_latest(abstract(get_family(dcfg).specs(dcfg)))
            if restored is not None:
                dparams = restored
                print(f"restored draft params from checkpoint step {dstep}")
            draft_model = (dcfg, dparams)

    trace_mode = args.trace is not None or args.requests > 0

    if not trace_mode:
        # original smoke mode: one uniform batch
        max_len = args.prompt_len + args.gen + 1
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size)
        if args.engine == "static":
            engine = ServingEngine(cfg, params, max_len=max_len)
            toks, stats = engine.generate(prompts, args.gen,
                                          temperature=args.temperature,
                                          seed=args.seed)
        else:
            serve = ServeConfig(max_slots=args.max_slots,
                                kv_block_size=args.kv_block,
                                prefill_chunk=args.prefill_chunk,
                                max_len=max(args.max_len, max_len),
                                spec=spec, sched_policy=args.sched_policy,
                                kv_quant=args.kv_quant, mesh=mesh_spec)
            engine = ContinuousEngine(cfg, params, serve,
                                      temperature=args.temperature,
                                      seed=args.seed, draft_model=draft_model,
                                      obs=obs)
            if args.profile_dir:
                jax.profiler.start_trace(args.profile_dir)
            try:
                toks, stats = engine.generate(prompts, args.gen)
            finally:
                if args.profile_dir:
                    jax.profiler.stop_trace()
            _write_obs(engine, args)
        print("generated:", np.asarray(toks)[:, :16])
        print({k: round(float(v), 4) for k, v in stats.items()})
        return ServeResult(engine, None, toks, stats)

    # trace-driven serving
    if args.trace is not None:
        requests = load_trace(args.trace, cfg.vocab_size, seed=args.seed)
    elif args.trace_kind == "multitenant":
        requests = synthetic_multitenant(
            args.requests, cfg.vocab_size, seed=args.seed, qps=args.qps,
            num_tenants=args.tenants,
            system_prompt_len=args.system_prompt_len)
    elif args.trace_kind == "priority":
        requests = synthetic_priority(
            args.requests, cfg.vocab_size, seed=args.seed, qps=args.qps,
            burst_qps=args.burst_qps, num_tenants=args.tenants,
            system_prompt_len=args.system_prompt_len if args.prefix_cache else 0)
    else:
        requests = synthetic_trace(args.requests, cfg.vocab_size,
                                   seed=args.seed, qps=args.qps)
    longest = max(r.total_len for r in requests)
    static_len = static_max_len(requests)
    print(f"serving {len(requests)} requests "
          f"({'trace ' + args.trace if args.trace else 'synthetic ' + args.trace_kind}), "
          f"engine={args.engine}")

    if args.engine == "static":
        engine = ServingEngine(cfg, params, max_len=static_len)
        outputs, stats = run_trace_static(engine, requests, args.batch,
                                          temperature=args.temperature,
                                          seed=args.seed)
    else:
        slo = (SLOConfig(preemption=args.slo_preempt,
                         host_blocks=args.host_blocks, shed=args.slo_shed)
               if (args.slo_preempt or args.slo_shed) else None)
        serve = ServeConfig(max_slots=args.max_slots,
                            kv_block_size=args.kv_block,
                            prefill_chunk=args.prefill_chunk,
                            max_len=max(args.max_len, longest),
                            spec=spec, sched_policy=args.sched_policy,
                            prefix_cache=args.prefix_cache, slo=slo,
                            kv_quant=args.kv_quant, mesh=mesh_spec)
        engine = ContinuousEngine(cfg, params, serve,
                                  temperature=args.temperature, seed=args.seed,
                                  draft_model=draft_model, obs=obs)

        def stream(st):
            head = st.generated[:8]
            print(f"  req {st.request.uid}: {len(st.generated)} tokens, "
                  f"latency {st.latency_ms():.0f}ms, first {head}")

        if args.profile_dir:
            jax.profiler.start_trace(args.profile_dir)
        try:
            outputs, stats = engine.run(requests, on_finish=stream)
        finally:
            if args.profile_dir:
                jax.profiler.stop_trace()
        _write_obs(engine, args)
        if spec is not None:
            print(f"speculative[{spec.drafter}]: acceptance "
                  f"{stats['acceptance_rate']:.2f}, "
                  f"{stats['spec_tokens_per_step']:.2f} tokens/verify-step")
        if args.prefix_cache:
            cs = engine.cache.stats
            print(f"prefix cache: {stats['cached_tokens']:.0f}/"
                  f"{stats['prompt_tokens']:.0f} prompt tokens cached "
                  f"({stats['cached_token_ratio']:.0%}), "
                  f"{cs['bound_blocks']} blocks bound shared, "
                  f"{cs['published_blocks']} published, "
                  f"{cs['cow_copies']} COW copies, "
                  f"{cs['evicted_blocks']} evicted")
        line = slo_class_line(stats)
        if line:
            print(line)
    print(latency_line(stats))
    return ServeResult(engine, requests, outputs, stats)


if __name__ == "__main__":
    main()
