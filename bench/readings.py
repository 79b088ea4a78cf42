"""Arithmetic shared by the metric readers in ``bench/metrics``.

A run record (``run``) holds the configuration (``mc``), the traffic,
the chip's peaks, the host-clock record of the window (``serve`` or
``train``) and, in a ``--trace 1`` run, the reduced trace (``trace``)
and the census of each traced step (``census``).  Every reader returns
None where the record holds nothing for it to read.
"""
from __future__ import annotations

import numpy as np

from bench import arch


def percentile(values, q: float):
    return float(np.percentile(np.asarray(values, np.float64), q)) if values else None


def traced_steps(run, kind=None):
    """(census entry, trace entry) of every traced engine step, in order;
    only those of ``kind`` ("decode" or "mixed") where given."""
    census, trace = run.get("census"), run.get("trace")
    if not census or not trace or len(census) != len(trace["steps"]):
        return []
    pairs = zip(census, trace["steps"])
    return [(c, t) for c, t in pairs if kind is None or c["kind"] == kind]


def step_flops(mc: dict, c: dict) -> float:
    form = arch.load(mc)
    rows = sum(form.row_flops(mc, length) for _, length in c["rows"])
    return rows + c["sampled"] * form.unembed_flops(mc)


def mfu(run, kind=None):
    """Model operations of the live rows over the traced window (or, for
    one kind of step, over those steps' device time) at the bf16 peak."""
    steps = traced_steps(run, kind)
    if not steps:
        return None
    flops = sum(step_flops(run["mc"], c) for c, _ in steps)
    seconds = (run["trace"]["window_s"] if kind is None
               else sum(t["device_s"] for _, t in steps))
    return 100.0 * flops / (seconds * run["peak"]["bf16_flops"]) if seconds else None


def roofline(run, kernel: str):
    """Least time the chip needs for the kernel's work, over its time in
    the trace: Σ max(flops / peak, bytes / bandwidth) over Σ kernel time.
    The work of each layer's call is counted by the configuration's form
    (``kernel_work``, ``bench/arch``)."""
    mc, peak = run["mc"], run["peak"]
    least, spent = 0.0, 0.0
    for c, t in traced_steps(run):
        if not t["kernel_calls"].get(kernel):
            continue
        per_layer = arch.load(mc).kernel_work(kernel, mc, c)
        if per_layer is None:
            return None
        least += sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])
                     for f, b in per_layer)
        spent += t["kernel_s"][kernel]
    return 100.0 * least / spent if spent else None


def step_ms(run, kind: str):
    steps = traced_steps(run, kind)
    times = [t["device_s"] for _, t in steps if t["device_s"] > 0]
    return 1e3 * float(np.mean(times)) if times else None


def device_idle(run):
    tr = run.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def host_gap_ms(run):
    tr = run.get("trace")
    mods = tr["modules"] if tr else []
    gaps = [b[0] - a[1] for a, b in zip(mods, mods[1:])]
    return 1e3 * float(np.mean(gaps)) if gaps else None
