"""The device-idle gaps between step programs, put down to the serving
engine's phase spans.

The engine's ``SpanTracer`` opens a ``jax.profiler.TraceAnnotation``
for each ``engine_step`` and, nested in it, one for each phase
(``engine_schedule``, ``engine_rows``, ``engine_launch``,
``engine_wait``, ``engine_samples``, ``engine_publish``), so they land
in the traced window's profile beside the device's operations.  For
each gap between two consecutive step programs, every instant in which
no device operation runs is given to the innermost engine span open on
the host then:

* ``schedule``, ``rows``, ``launch``: their phase span;
* ``post``: ``engine_wait``, ``engine_samples``, ``engine_publish``, or
  ``engine_step`` under none of its phases;
* ``driver``: no engine span at all (the benchmark's own loop).

The five add up to the gap's device-idle time.  A program that opens no
engine span (one older than the spans) gives nothing to read.
"""
from __future__ import annotations

import bisect
import glob
import os
import sys
from typing import Dict, List, Optional, Tuple

from bench.trace import OPS_LINE, gaps, union

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# where bench/run.py has a --trace 1 run's profile written
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")

PART = {"engine_schedule": "schedule", "engine_rows": "rows",
        "engine_launch": "launch", "engine_wait": "post",
        "engine_samples": "post", "engine_publish": "post",
        "engine_step": "post"}
PARTS = ("schedule", "rows", "launch", "post", "driver")

Span = Tuple[str, float, float]


def load(profile_dir: str):
    """(engine spans, device operations) of the newest trace under
    ``profile_dir``, or None where it holds none: ``(name, start, end)``
    host events named in ``PART`` and ``(start, end)`` of the first
    TPU's operations, in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    engine: List[Span] = []
    ops: List[Tuple[float, float]] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9)
                            for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                engine += [(ev.name, ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events if ev.name in PART]
    return engine, ops


def innermost(spans: List[Span], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi] under each part: at each instant the open span
    that opened last (on one thread, the innermost) decides."""
    cands = [sp for sp in spans if sp[1] < hi and sp[2] > lo]
    cuts = sorted({lo, hi, *(t for _, s, e in cands for t in (s, e)
                             if lo < t < hi)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [sp for sp in cands if sp[1] <= a and sp[2] >= b]
        part = (PART[max(open_, key=lambda sp: (sp[1], -sp[2]))[0]]
                if open_ else "driver")
        out[part] = out.get(part, 0.0) + (b - a)
    return out


def engine_phases(engine: List[Span], ops: List[Tuple[float, float]],
                  modules: List[Tuple[float, float]]) -> Optional[dict]:
    """Device-idle seconds between consecutive step programs
    (``modules``, in order), split by part: ``{part: seconds}`` summed
    over the gaps, with ``gaps`` (their number) and ``idle_s`` (the gaps
    less the union of the operations in them, worked out apart from the
    split).  None where no engine span falls in the gaps."""
    if len(modules) < 2:
        return None
    lo, hi = modules[0][1], modules[-1][0]
    engine = [sp for sp in engine
              if sp[0] in PART and sp[1] < hi and sp[2] > lo]
    if not engine:
        return None
    ops = sorted(ops)
    starts = [s for s, _ in ops]
    reach, far = [], float("-inf")          # latest end of ops[:i + 1]
    for _, e in ops:
        far = max(far, e)
        reach.append(far)
    out = dict.fromkeys(PARTS, 0.0)
    idle = 0.0
    for (_, a), (b, _) in zip(modules, modules[1:]):
        if b <= a:
            continue
        i, j = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        busy = ops[i:j]
        if i and reach[i - 1] > a:          # an earlier op runs into the gap
            busy.append((a, min(reach[i - 1], b)))
        idle += (b - a) - union(busy, a, b)
        for s, e in gaps(busy, a, b):
            for part, secs in innermost(engine, s, e).items():
                out[part] += secs
    return dict(out, gaps=len(modules) - 1, idle_s=idle)


def phases_of(run) -> Optional[dict]:
    """The split of a ``--trace 1`` run, read from its profile once and
    kept on the run record for the other readers."""
    tr = run.get("trace")
    if not tr:
        return None
    if "engine_phases" not in run:
        got = load(TRACE_ROOT)
        ph = engine_phases(*got, tr["modules"]) if got else None
        if ph:
            split = ", ".join(f"{p} {1e3 * ph[p] / ph['gaps']!r}"
                              for p in PARTS)
            print(f"engine phases: {ph['gaps']} gaps, device idle "
                  f"{1e3 * ph['idle_s'] / ph['gaps']!r} ms a gap: {split}",
                  file=sys.stderr)
        run["engine_phases"] = ph
    return run["engine_phases"]


def idle_ms(run, part: str) -> Optional[float]:
    """Mean device-idle ms a gap between step programs under ``part``."""
    ph = phases_of(run)
    return 1e3 * ph[part] / ph["gaps"] if ph else None
