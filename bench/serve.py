"""Serving cells: drive ``ContinuousEngine.step`` on the benchmark's own
open-loop clock and time every token when the host holds it.

The engine's ``run`` is not used: its clock skips idle gaps and its
first-token time is the clock at the start of the step.  Here the
schedule's time zero is a ``perf_counter`` reading; a request is due at
``arrival_s`` after it, whatever the engine is doing; a token's time is
the host's clock when ``step`` returns with it.  The window opens at
the end of the step in which the traffic's ``warmup_finished``-th
request finished, so that slot ages are staggered as in steady
serving, and closes at the end of the first step that ends ``seconds``
after it opened (``TRACE_SECONDS`` in a traced run).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from bench import program, weights
from bench.trace import annotate

TRACE_SECONDS = 10.0


def build(mc: dict, traffic: dict, seed: int):
    """The engine at the traffic's shapes, with the seed's weights."""
    program.import_program()
    import jax

    from repro.serving.continuous import ContinuousEngine

    cfg = program.model_config(mc, "serve")
    params = program.program_params(mc, weights.make(mc, seed))
    jax.block_until_ready(params)
    return ContinuousEngine(cfg, params, program.serve_config(traffic["engine"]))


def warm_compile(engine) -> None:
    """Compile both step shapes the traffic uses (mixed, decode-only) on
    a throwaway one-token request, before the schedule's clock starts."""
    from repro.serving.request import Request

    engine.scheduler.add(Request(uid=-1, prompt=np.zeros(1, np.int32),
                                 max_new_tokens=2))
    while engine.scheduler.has_work():
        engine.step(0.0)


class Census:
    """Live rows of each traced step, worked out from the request states
    before and after it: ``(slot, context length)`` per row, whether the
    row was sampled, and the experts each layer routed rows to."""

    def __init__(self, engine):
        self.engine = engine
        self.steps: List[dict] = []
        self._acc = None

    @staticmethod
    def snapshot(sched) -> Dict[int, tuple]:
        return {st.request.uid: (st.status.value, st.prefill_pos,
                                 len(st.generated), st.context_len, st.slot)
                for st in sched.running.values()}

    def record(self, before: dict, states: list):
        rows, sampled = [], 0
        for st in states:
            uid = st.request.uid
            status, pos0, gen0, ctx0, _ = before.get(uid, ("queued", 0, 0, 0, -1))
            if st.prefill_pos > pos0 and status != "decode":
                rows += [(st.slot, p + 1) for p in range(pos0, st.prefill_pos)]
                sampled += len(st.generated) > gen0
            elif status == "decode" and len(st.generated) > gen0:
                rows.append((st.slot, ctx0 + 1))
                sampled += 1
        acc = self.engine._moe_acc
        hits = None
        if acc is not None:
            tok = np.asarray(acc["expert_tokens"])
            step_tok = tok if self._acc is None else tok - self._acc
            self._acc = tok
            hits = (step_tok > 0).sum(axis=1).tolist()
        self.steps.append({"rows": rows, "sampled": sampled,
                           "experts_hit": hits})


def drive(engine, requests: List[dict], traffic: dict, seconds: float,
          trace_dir: Optional[str] = None, counter=None) -> dict:
    """Serve ``requests`` open loop until the window closes.  Returns the
    run record: per-request arrival and token times (seconds on the
    schedule's clock), the window, and with ``trace_dir`` the census of
    the traced stretch.  ``counter.count`` (compilations so far) is read
    as the window opens and closes."""
    import jax

    from repro.serving.request import Request

    sched = engine.scheduler
    for r in requests:
        sched.add(Request(uid=r["uid"], prompt=r["prompt"],
                          max_new_tokens=r["max_new_tokens"],
                          arrival_ms=r["arrival_s"] * 1e3))
    token_times: Dict[int, List[float]] = {r["uid"]: [] for r in requests}
    finished: Dict[int, object] = {}
    census = Census(engine) if trace_dir else None
    metrics = engine.obs.metrics
    span = TRACE_SECONDS if trace_dir else seconds
    t_open = t_close = None
    window_variants = None
    clock0 = time.perf_counter()
    while True:
        now = time.perf_counter() - clock0
        if not sched.running:
            nxt = sched.next_arrival_ms()
            if nxt is None:
                raise SystemExit("bench: the traffic ran out before the "
                                 "window closed; give it more requests")
            if nxt / 1e3 > now:
                with annotate("bench.wait_arrival"):
                    time.sleep(nxt / 1e3 - now)
                continue
        before = census.snapshot(sched) if census else None
        kind_before = metrics.get("engine_steps_total", kind="mixed")
        with annotate("bench.step"):
            done = engine.step(now * 1e3)
        t = time.perf_counter() - clock0
        with annotate("bench.collect"):
            states = list(sched.running.values()) + done
            for st in states:
                times = token_times[st.request.uid]
                n = len(st.generated) - len(times)
                if n > 0:
                    times.extend([t] * n)
            for st in done:
                finished[st.request.uid] = st
            if census is not None and t_open is not None:
                census.record(before, states)
                census.steps[-1]["kind"] = (
                    "mixed" if metrics.get("engine_steps_total", kind="mixed")
                    > kind_before else "decode")
        if t_open is None:
            if len(finished) >= traffic["warmup_finished"]:
                t_open = t
                compiles_open = counter.count if counter else 0
                window_variants = (engine.compiled_variants(),
                                   metrics.get("engine_recompiles_total"))
                if trace_dir:
                    jax.profiler.start_trace(trace_dir)
                    t_open = time.perf_counter() - clock0
                    window_span = annotate("bench.window")
                    window_span.__enter__()
            continue
        if t >= t_open + span:
            t_close = t
            compiles = (counter.count if counter else 0) - compiles_open
            if trace_dir:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            break
    return {
        "requests": {r["uid"]: r for r in requests},
        "token_times": token_times,
        "finished": finished,
        "t_open": t_open, "t_close": t_close,
        "compiles_in_window": compiles,
        "clock0": clock0,
        "compiled_variants": [window_variants,
                              (engine.compiled_variants(),
                               metrics.get("engine_recompiles_total"))],
        "census": census.steps if census else None,
    }


def served_sample(record: dict, rng, tokens_min: int, requests_max: int):
    """Finished requests for the correctness check: the longest one and
    others drawn by ``rng`` until ``tokens_min`` served tokens or
    ``requests_max`` requests."""
    done = list(record["finished"].values())
    if not done:
        return []
    longest = max(done, key=lambda st: (st.request.prompt_len + len(st.generated),
                                        st.request.uid))
    rest = [st for st in done if st is not longest]
    order = rng.permutation(len(rest))
    out, served = [longest], len(longest.generated)
    for i in order:
        if served >= tokens_min or len(out) >= requests_max:
            break
        out.append(rest[i])
        served += len(rest[i].generated)
    return [(st.request.prompt.copy(), list(st.generated),
             st.request.max_new_tokens) for st in out]
