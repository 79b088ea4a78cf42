"""Host spans on the profiler's clock, and the reduction of a profiler
trace to device busy time, idle gaps, step programs and kernel calls.

The benchmark's host spans are ``jax.profiler.TraceAnnotation``s, so
they land in the same trace, on the same clock, as the device's
operations.  ``reduce`` reads the ``.xplane.pb`` that
``jax.profiler.stop_trace`` writes, with nothing but JAX.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[float, float]


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace is on)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def union(intervals: List[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, end), min(e, hi)
        if e > s:
            total += e - s
            end = e
    return total


def gaps(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, end = [], lo
    for s, e in sorted(intervals):
        if s > end and s > lo:
            out.append((end, min(s, hi)))
        end = max(end, e)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(s, e) for s, e in out if e > s]


def load_events(profile_dir: str):
    """(device events by line name, host events) of the newest trace in
    ``profile_dir``; times in seconds on the trace's clock.  Device
    events are those of the first TPU (``/device:TPU:0``)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise SystemExit(f"bench: no profiler trace under {profile_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                device[line.name] = [
                    (ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9)
                         for ev in line.events if ev.name.startswith("bench.")]
    return device, host


OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ops that only contain others (a scan's loop): left out of the ranking
CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """``%ragged_ffn.7 = bf16[...] custom-call(...)`` -> ``ragged_ffn.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_label(hlo: str) -> str:
    """Short name plus the start of its result type and operation."""
    head, _, rest = hlo.partition(" = ")
    return f"{head.lstrip('%')} = {rest[:90]}"


def reduce(device: Dict[str, list], host: list, step_program: str,
           kernels: Dict[str, str]) -> dict:
    """The traced window's numbers.

    ``step_program``: a substring of the name of the jitted step's XLA
    module; ``kernels``: {short name: prefix of the kernel's HLO op name}.
    Returns the window, the busy time (union of every device operation),
    the idle gaps labelled by the benchmark span open at their middle,
    the runs of the step program that ended in the window, each host
    ``bench.step`` span (in order) with the step programs and kernel time
    inside it, and the device operations that took most time."""
    window = [(s, e) for n, s, e in host if n == "bench.window"]
    if not window:
        raise SystemExit("bench: the trace holds no bench.window span")
    lo, hi = window[0]
    ops = sorted(((n, s, e) for n, s, e in device.get(OPS_LINE, [])
                  if e > lo and s < hi), key=lambda o: o[1])
    if not ops:
        raise SystemExit("bench: no device operation ran in the traced window")
    busy = union([(s, e) for _, s, e in ops], lo, hi)
    spans = sorted((s, e, n) for n, s, e in host if n != "bench.window")
    labelled = []
    for s, e in gaps([(s, e) for _, s, e in ops], lo, hi):
        mid = 0.5 * (s + e)
        open_ = [n for hs, he, n in spans if hs <= mid <= he]
        labelled.append((open_[-1] if open_ else "host (no bench span)", e - s))
    modules = sorted((s, e) for n, s, e in device.get(MODULES_LINE, [])
                     if step_program in n and lo <= e <= hi)
    starts = [s for _, s, _ in ops]
    steps = []
    for s, e in sorted((s, e) for n, s, e in host if n == "bench.step"
                       and s >= lo and e <= hi):
        mods = [(ms, me) for ms, me in modules if s <= ms < e]
        k = {name: 0.0 for name in kernels}
        calls = {name: 0 for name in kernels}
        for n, os_, oe in ops[bisect.bisect_left(starts, s):
                              bisect.bisect_left(starts, e)]:
            short = op_name(n)
            for name, prefix in kernels.items():
                if short.startswith(prefix):
                    k[name] += oe - os_
                    calls[name] += 1
        steps.append({"host": (s, e), "modules": mods,
                      "device_s": sum(me - ms for ms, me in mods),
                      "kernel_s": k, "kernel_calls": calls})
    by_name: Dict[str, float] = {}
    for n, s, e in ops:
        if op_name(n).split(".")[0] in CONTAINERS:
            continue
        label = op_label(n)
        by_name[label] = by_name.get(label, 0.0) + (min(e, hi) - max(s, lo))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(labelled, key=lambda kv: -kv[1])[:10]
    return {"window": (lo, hi), "window_s": hi - lo, "busy_s": busy,
            "modules": modules, "steps": steps, "idle_gaps": labelled,
            "breakdown": {"device_ops": [list(kv) for kv in top],
                          "idle_gaps": [list(kv) for kv in idle]}}
