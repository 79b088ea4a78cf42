"""One run of a serving cell: set-up, the window, the correctness check."""
from __future__ import annotations

import gc
import sys

from bench import arch, check_serve, seeds, serve, weights
from bench.trace import load_events, reduce

STEP_PROGRAM = "step_fn"


def run(cell: str, seed: int, seconds: float, mc: dict, traffic: dict,
        limits: dict, gen, trace_dir, counter, setup_from: float) -> dict:
    import jax

    engine = serve.build(mc, traffic, seed)
    serve.warm_compile(engine)
    requests = gen.generate(traffic, seed, mc["vocab_size"])
    rec = serve.drive(engine, requests, traffic, seconds, trace_dir, counter)
    rec["seconds"] = seconds
    setup_s = rec["clock0"] + rec["t_open"] - setup_from
    compiles = rec["compiles_in_window"]
    (v0, r0), (v1, r1) = rec["compiled_variants"]
    print(f"window: engine step variants {v0} -> {v1}, engine recompiles "
          f"{r0} -> {r1}, backend compilations {compiles}", file=sys.stderr)

    run_rec = {"serve": rec, "setup_s": setup_s, "census": rec["census"]}
    device = {}
    if trace_dir:
        tr = reduce(*load_events(trace_dir), STEP_PROGRAM, arch.load(mc).KERNELS)
        run_rec["trace"] = tr
        device = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")

    lo, hi = rec["t_open"], rec["t_close"]
    # attempted: requests due in the window or served a token in it
    active = [uid for uid, times in rec["token_times"].items()
              if lo <= rec["requests"][uid]["arrival_s"] < hi
              or any(lo < t <= hi for t in times)]
    bad = [uid for uid, st in rec["finished"].items()
           if len(st.generated) != st.request.max_new_tokens
           or min(st.generated) < 0 or max(st.generated) >= mc["vocab_size"]]
    check = traffic["check"]
    sample = serve.served_sample(rec, seeds.rng(seed, "check"),
                                 check["tokens_min"], check["requests_max"])
    del engine
    gc.collect()
    w = weights.make(mc, seed)
    got = check_serve.readings(mc, w, sample, traffic["engine"]["max_len"])
    print(f"check: widest logit gap {got['logit_gap']!r}, mean "
          f"{got['mean_logit_gap']!r} over {got['served_tokens']} served "
          f"tokens", file=sys.stderr)
    checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    checks.update({
        "served_tokens": {"value": got["served_tokens"],
                          "limit": check["tokens_min"]},
        "failed_requests": {"value": len(bad), "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    })
    correct = (all(got[k] <= limits[k] for k in limits)
               and got["served_tokens"] >= check["tokens_min"]
               and got["short_requests"] == 0 and not bad and compiles == 0)
    return {"run": run_rec, "correct": bool(correct),
            "attempted": len(active), "failed": len(bad), "device": device,
            "checks": checks, "sample": sample, "weights": w}
