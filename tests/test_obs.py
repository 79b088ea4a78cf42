"""Observability subsystem: span tracer, metrics registry, and their
integration with the continuous-batching engine (request-lifecycle
spans, registry-derived run() stats, recompile detector, MoE routing
telemetry)."""
import json

import numpy as np
import pytest

import jax

from repro.configs.base import ModelConfig, MoEConfig, ServeConfig
from repro.models.registry import get_family
from repro.nn import init
from repro.obs import Observability
from repro.obs.registry import MetricsRegistry
from repro.obs.tracer import SpanTracer
from repro.obs.validate import validate_chrome_trace, validate_metrics_jsonl
from repro.serving.continuous import ContinuousEngine
from repro.serving.request import Request
from repro.serving.trace import synthetic_trace


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(name="t", family="decoder_lm", num_layers=2, d_model=64,
                num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                max_seq_len=128, dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


def build(cfg, seed=0):
    fam = get_family(cfg)
    return init(fam.specs(cfg), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_monotonic_randomized():
    """Counters only ever move up, under a random op sequence; every
    negative inc / decreasing set_to raises and leaves the value alone."""
    rng = np.random.default_rng(0)
    reg = MetricsRegistry()
    total = 0.0
    for _ in range(300):
        op = rng.integers(0, 3)
        c = reg.counter("ops_total", kind=int(rng.integers(0, 3)))
        before = c.value
        if op == 0:
            v = float(rng.integers(0, 10))
            c.inc(v)
            assert c.value == before + v
            total += v
        elif op == 1:
            with pytest.raises(ValueError):
                c.inc(-float(rng.integers(1, 5)))
            assert c.value == before
        else:
            with pytest.raises(ValueError):
                c.set_to(before - 1.0)
            assert c.value == before
    assert reg.get("ops_total") == total  # unlabeled get sums label sets


def test_counter_set_to_mirrors_external_totals():
    reg = MetricsRegistry()
    c = reg.counter("cache_hits_total")
    c.set_to(5)
    c.set_to(5)        # no movement is fine
    c.set_to(9)
    assert reg.get("cache_hits_total") == 9
    with pytest.raises(ValueError):
        c.set_to(8)


def test_label_order_is_canonical():
    reg = MetricsRegistry()
    reg.counter("x_total", a=1, b=2).inc(3)
    reg.counter("x_total", b=2, a=1).inc(4)
    assert reg.get("x_total", a=1, b=2) == 7
    assert reg.get("x_total") == 7  # one series, not two


def test_gauge_set_and_set_max():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    g.set(2)
    assert reg.get("depth") == 2
    p = reg.gauge("peak")
    p.set_max(3)
    p.set_max(1)
    assert reg.get("peak") == 3


def test_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("n_total")
    with pytest.raises(TypeError):
        reg.gauge("n_total")


def test_histogram_accounting_and_prometheus():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 3.0, 3.0, 50.0, 5000.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["lat_ms_count"] == 5
    assert snap["lat_ms_sum"] == pytest.approx(5056.5)
    assert snap["lat_ms_bucket{le=1.0}"] == 1       # per-bucket in snapshot
    assert snap["lat_ms_bucket{le=10.0}"] == 2
    text = reg.to_prometheus()
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="10.0"} 3' in text     # cumulative in prom text
    assert 'lat_ms_bucket{le="+Inf"} 5' in text
    assert "lat_ms_count 5" in text


def test_mark_delta_accounting():
    reg = MetricsRegistry()
    reg.counter("steps_total", kind="mixed").inc(2)
    mark = reg.mark()
    reg.counter("steps_total", kind="mixed").inc(3)
    reg.counter("steps_total", kind="decode").inc(5)
    assert reg.delta(mark, "steps_total") == 8
    assert reg.delta(mark, "steps_total", kind="mixed") == 3
    assert reg.delta(mark, "steps_total", kind="decode") == 5
    assert reg.delta(mark, "never_seen_total") == 0


def test_metrics_jsonl_row_is_schema_valid(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total").inc(1)
    reg.gauge("b", shard=0).set(2.5)
    p = tmp_path / "m.jsonl"
    with open(p, "w") as fh:
        fh.write(reg.jsonl_row(step=1) + "\n")
        fh.write(reg.jsonl_row(final=True) + "\n")
    counts = validate_metrics_jsonl(str(p), require=("a_total", "b"))
    assert counts["rows"] == 2


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_noop():
    tr = SpanTracer(enabled=False)
    with tr.span("work") as sp:
        sp.args["rows"] = 8          # late args are accepted, ring or not
    tr.begin("request", 1, "queued")
    tr.instant("preempt")
    assert tr.events() == []


def test_tracer_span_wellformed(tmp_path):
    tr = SpanTracer(enabled=True)
    with tr.span("engine_step", kind="mixed", step=0) as sp:
        sp.args["rows"] = 8
    tr.begin("request", 7, "request", prompt_len=3)
    tr.begin("request", 7, "queued")
    tr.end("request", 7, "queued")
    tr.begin("request", 7, "decode")
    tr.instant("preempt", uid=7)
    tr.end("request", 7, "decode")
    tr.end("request", 7, "request")
    evs = tr.events()
    x = [e for e in evs if e["ph"] == "X"]
    assert x[0]["name"] == "engine_step" and x[0]["args"]["rows"] == 8
    assert x[0]["dur"] >= 0
    # monotone timestamps, async ids stringified for Chrome-trace nesting
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    assert all(e["id"] == "7" for e in evs if e["ph"] in ("b", "e"))
    p = tmp_path / "t.json"
    tr.write_chrome_trace(str(p))
    counts = validate_chrome_trace(str(p))
    assert counts == {"X": 1, "b": 3, "e": 3, "i": 1, "events": 8}


def test_tracer_ring_buffer_wrap():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(10):
        tr.instant("tick", n=i)
    evs = tr.events()
    assert len(evs) == 4
    assert [e["args"]["n"] for e in evs] == [6, 7, 8, 9]  # oldest first
    assert tr.dropped_events == 6


def test_validator_rejects_unbalanced_async(tmp_path):
    tr = SpanTracer(enabled=True)
    tr.begin("request", 1, "request")
    p = tmp_path / "bad.json"
    tr.write_chrome_trace(str(p))
    with pytest.raises(ValueError):
        validate_chrome_trace(str(p))


def test_observability_request_lifecycle():
    obs = Observability(tracing=True)
    obs.request_arrived(3, prompt_len=5, max_new_tokens=4)
    obs.request_phase(3, "prefill", slot=0)
    obs.request_phase(3, "prefill")             # same phase: no-op
    obs.request_phase(3, "decode", slot=0)
    obs.request_phase(3, "preempted")
    obs.request_phase(3, "decode", slot=1)
    obs.request_finished(3)
    evs = obs.tracer.events()
    names = [(e["ph"], e["name"]) for e in evs]
    assert names == [("b", "request"), ("b", "queued"),
                     ("e", "queued"), ("b", "prefill"),
                     ("e", "prefill"), ("b", "decode"),
                     ("e", "decode"), ("b", "preempted"),
                     ("e", "preempted"), ("b", "decode"),
                     ("e", "decode"), ("e", "request")]
    # balanced per (cat, id): nothing left open
    depth = 0
    for e in evs:
        depth += {"b": 1, "e": -1}[e["ph"]]
        assert depth >= 0
    assert depth == 0


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

def _run(cfg, serve, n=4, obs=None, seed=3):
    params = build(cfg)
    eng = ContinuousEngine(cfg, params, serve, obs=obs)
    reqs = synthetic_trace(n, cfg.vocab_size, seed=seed, qps=1e6,
                           prompt_lens=(3, 9), gen_lens=(2, 5))
    out, stats = eng.run(reqs)
    return eng, out, stats


def test_run_stats_contract_from_registry():
    """run() stats are registry-derived but keep the legacy keys."""
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng, out, stats = _run(tiny_cfg(num_layers=1), serve)
    assert stats["steps"] > 0 and stats["steps"] == eng.steps
    assert stats["peak_running"] >= 1
    m = eng.obs.metrics
    assert m.get("engine_steps_total") == eng.steps
    assert m.get("sched_requests_total") == 4
    assert m.get("sched_finished_total") == 4
    assert m.get("generated_tokens_total") == sum(len(v) for v in out.values())
    # rows split: live + padded = total, both tracked
    live = m.get("engine_rows_total", state="live")
    pad = m.get("engine_rows_total", state="padded")
    assert live > 0 and pad >= 0
    # per-shard KV occupancy gauges exist and end fully free
    occ = eng.cache.occupancy()
    assert m.get("kv_blocks", shard=0, state="free") == occ[0]["free"]


def test_recompile_detector_variant_set():
    """Non-speculative paged engine compiles exactly {mixed, decode}."""
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng, _, _ = _run(tiny_cfg(num_layers=1), serve)
    assert eng._expected_variants == 2
    assert eng.compiled_variants() <= 2
    m = eng.obs.metrics
    assert m.get("engine_recompiles_total") == 0
    if eng.compiled_variants():          # _cache_size available on this jax
        assert m.get("engine_compiled_variants") == 2.0


def test_recompile_detector_fires_on_excess_variants():
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    params = build(tiny_cfg(num_layers=1))
    eng = ContinuousEngine(tiny_cfg(num_layers=1), params, serve,
                           obs=Observability(tracing=True))
    if eng.compiled_variants() is None or not hasattr(
            eng._step_fn, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    reqs = synthetic_trace(3, 128, seed=1, qps=1e6, prompt_lens=(3, 9),
                           gen_lens=(2, 4))
    eng._expected_variants = 1           # pretend mixed steps are unexpected
    eng.run(reqs)
    assert eng.obs.metrics.get("engine_recompiles_total") > 0
    assert any(e["name"] == "recompile" for e in eng.obs.tracer.events()
               if e["ph"] == "i")


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_engine_weight_bytes_gauge(dtype):
    """``engine_weight_bytes{dtype}`` sums to the bytes of the tree the
    engine holds; stored in float32 and served in bf16, its bf16 share is
    exactly the expert stacks, dense kernels and embedding table."""
    cfg = tiny_cfg(dtype=dtype, d_ff=96,
                   moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                 impl="dropless", capacity_factor=None))
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng = ContinuousEngine(cfg, build(cfg), serve)
    m = eng.obs.metrics
    leaves = jax.tree_util.tree_leaves_with_path(eng.params)
    total = sum(x.size * x.dtype.itemsize for _, x in leaves)
    assert sum(m.get("engine_weight_bytes", dtype=d)
               for d in ("bfloat16", "float32")) == total
    low = sum(x.size * 2 for p, x in leaves
              if getattr(p[-1], "key", None) in
              ("up", "gate", "down", "kernel", "table"))
    if dtype == "bfloat16":
        assert m.get("engine_weight_bytes", dtype="bfloat16") == low > 0
        assert m.get("engine_weight_bytes", dtype="float32") == total - low
    else:
        assert m.get("engine_weight_bytes", dtype="float32") == total
        assert m.get("engine_weight_bytes", dtype="bfloat16") == 0


def test_moe_dropless_dropped_fraction_exact_zero():
    cfg = tiny_cfg(d_ff=96, num_layers=2,
                   moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                 impl="dropless", capacity_factor=None,
                                 group_size=64))
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    _, _, stats = _run(cfg, serve, n=3)
    assert stats["moe_dropped_fraction"] == 0.0   # exact, not approx
    assert stats["moe_gate_entropy"] >= 0.0
    assert stats["moe_load_entropy"] >= 0.0


def test_moe_capacity_drops_surface_in_stats():
    cfg = tiny_cfg(d_ff=96, num_layers=2,
                   moe=MoEConfig(num_experts=4, routing="topk", top_k=2,
                                 impl="einsum", capacity_factor=0.25,
                                 group_size=64))
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng, _, stats = _run(cfg, serve, n=3)
    assert stats["moe_dropped_fraction"] > 0.0
    m = eng.obs.metrics
    # per-layer expert-load shares exist and sum to ~1 per MoE layer
    for layer in range(cfg.num_layers):
        shares = [m.get("moe_expert_load_share", layer=layer, expert=e)
                  for e in range(4)]
        assert sum(shares) == pytest.approx(1.0, abs=1e-5)
        assert m.get("moe_dropped_fraction", layer=layer) > 0.0


def test_obs_on_off_token_identity(tmp_path):
    """Tracing + periodic metrics rows must not change generated tokens."""
    cfg = tiny_cfg(num_layers=1)
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    _, out_off, _ = _run(cfg, serve)
    obs = Observability(tracing=True)
    obs.metrics_every = 2
    eng, out_on, _ = _run(cfg, serve, obs=obs)
    assert out_on == out_off
    # artifacts from the instrumented run validate end to end
    tp, mp = tmp_path / "trace.json", tmp_path / "metrics.jsonl"
    obs.tracer.write_chrome_trace(str(tp))
    obs.write_metrics_jsonl(str(mp))
    tc = validate_chrome_trace(str(tp))
    assert tc["b"] == tc["e"] > 0 and tc["X"] >= eng.steps
    mc = validate_metrics_jsonl(
        str(mp), require=("engine_steps_total", "kv_blocks",
                          "engine_rows_total", "sched_finished_total"))
    assert mc["rows"] >= 2  # periodic rows + final


def test_legacy_readthrough_views():
    """spec_stats / swap.stats / scheduler ints still read correctly."""
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng, _, _ = _run(tiny_cfg(num_layers=1), serve)
    assert set(eng.spec_stats) == {"verify_steps", "proposed", "accepted",
                                   "emitted"}
    assert eng.scheduler.preemptions == 0
    swap = eng.scheduler.swap
    if swap is not None:
        assert set(swap.stats) == {"swap_outs", "swap_ins", "swapped_blocks",
                                   "restored_blocks"}


def test_queue_and_latency_histograms_populate():
    serve = ServeConfig(max_slots=1, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    eng, _, _ = _run(tiny_cfg(num_layers=1), serve, n=3)
    snap = eng.obs.metrics.snapshot()
    assert snap["request_queue_ms_count"] == 3
    assert snap["request_latency_ms_count"] == 3
    assert snap["request_latency_ms_sum"] >= snap["request_queue_ms_sum"]


# ---------------------------------------------------------------------------
# Profiler bridge: engine spans on the profiler's clock
# ---------------------------------------------------------------------------

PHASES = ["engine_schedule", "engine_rows", "engine_launch", "engine_wait",
          "engine_samples", "engine_publish"]


def _host_events(profile_dir, prefix):
    """(name, start_ns, end_ns, stats) of the host events of the trace in
    ``profile_dir`` whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    (path,) = profile_dir.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


def _profiled_run(tmp_path, obs):
    cfg = tiny_cfg(num_layers=1)
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, out, _ = _run(cfg, serve, obs=obs)
    finally:
        jax.profiler.stop_trace()
    return eng, out, _host_events(tmp_path, "engine_")


def test_engine_spans_reach_the_profiler(tmp_path):
    """Each serving step is an engine_step annotation holding the six
    phase spans in order, its args (and the ring's) as profiler stats."""
    eng, _, evs = _profiled_run(tmp_path, Observability(tracing=True))
    steps = [e for e in evs
             if e[0] == "engine_step" and e[3]["kind"] != "idle"]
    assert len(steps) == eng.steps > 0
    ring = [e["args"] for e in eng.obs.tracer.events()
            if e["name"] == "engine_step" and e["args"]["kind"] != "idle"]
    assert [st for _, _, _, st in steps] == ring
    for _, s, e, stats in steps:
        assert stats["kind"] in ("mixed", "decode")
        assert 0 < stats["live_rows"] <= stats["rows"]
        inner = sorted((ps, pe, n) for n, ps, pe, _ in evs
                       if n != "engine_step" and s <= ps and pe <= e)
        assert [n for _, _, n in inner] == PHASES
        assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_engine_spans_reach_the_profiler_with_the_ring_off(tmp_path):
    eng, _, evs = _profiled_run(tmp_path, Observability(tracing=False))
    assert eng.obs.tracer.events() == []
    steps = [e for e in evs
             if e[0] == "engine_step" and e[3]["kind"] != "idle"]
    assert len(steps) == eng.steps > 0
    for name in PHASES:
        assert sum(n == name for n, _, _, _ in evs) == eng.steps


def test_profiler_on_off_token_identity(tmp_path):
    """A profiler session around the engine must not change tokens."""
    cfg = tiny_cfg(num_layers=1)
    serve = ServeConfig(max_slots=2, kv_block_size=8, prefill_chunk=4,
                        max_len=32)
    _, out_off, _ = _run(cfg, serve)
    _, out_on, evs = _profiled_run(tmp_path, None)
    assert evs and out_on == out_off


def test_train_rate_is_tokens_over_the_log_interval(tmp_path, monkeypatch):
    """A log step's token rate covers every step since the previous log
    step, over the clock read after each log step's device_get; the
    train_step spans reach the profiler."""
    import itertools

    from repro.launch import train

    ticks = itertools.count()                 # one second a clock read
    monkeypatch.setattr(train, "_clock", lambda: float(next(ticks)))
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    metrics, prof = tmp_path / "m.jsonl", tmp_path / "prof"
    batch, seq = 2, 16
    train.main(["--arch", "m6-base", "--smoke", "--steps", "5",
                "--batch", str(batch), "--seq", str(seq), "--log-every", "2",
                "--metrics-out", str(metrics), "--profile-dir", str(prof)])
    rows = [json.loads(line) for line in open(metrics)]
    got = {r["step"]: r["metrics"]["train_tokens_per_s"]
           for r in rows if "step" in r}
    tokens = batch * seq
    # logged at steps 0, 2, 4: 1, 2 and 2 steps in 1 s each
    assert got == {0: 1.0 * tokens, 2: 2.0 * tokens, 4: 2.0 * tokens}
    spans = _host_events(prof, "train_step")
    assert sorted(st["step"] for _, _, _, st in spans) == list(range(5))
