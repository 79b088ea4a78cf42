"""Low-overhead span tracer: the one span system of ``repro``.

Every complete span is a ``jax.profiler.TraceAnnotation``, so it lands
in a ``jax.profiler`` trace on the same clock as the device's
operations, its args attached as stats at span exit; with no profiler
session on, that costs one annotation enter/exit.  Opt-in, the same
spans (and the async spans and instants below) are also recorded into
a preallocated ring buffer of Chrome-trace events over a monotonic
clock (``time.perf_counter_ns``).  Three event shapes cover the
serving taxonomy (see ``docs/observability.md``):

* **Complete spans** (``ph="X"``) — synchronous work with a duration:
  one per engine step (``engine_step``, args carry the step kind and
  live/padded row split) with its phase spans nested inside.
* **Async spans** (``ph="b"``/``"e"``, paired by ``(cat, id)``) — the
  request lifecycle: an outer ``request`` span per uid with nested
  phase spans (``queued`` → ``prefill`` → ``decode`` →
  ``preempted`` → …) sharing the same async id, which is exactly how
  Perfetto renders nesting.  Ring only: the profiler has no async track.
* **Instants** (``ph="i"``) — point events: ``preempt``, ``restore``,
  ``recompile``.  Ring only.

With the ring disabled every async/instant emit path is a constant-time
no-op (one attribute check), so instrumentation can stay in place
unconditionally.  The ring buffer never grows: past ``capacity`` events
the oldest are overwritten and ``dropped_events`` counts the loss.

Export: :meth:`write_chrome_trace` writes a Perfetto-loadable
``{"traceEvents": [...]}`` JSON; :meth:`write_jsonl` writes the same
events one per line.
"""
from __future__ import annotations

import functools
import json
import time
from typing import Dict, List, Optional


@functools.cache
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    ``repro.obs`` imports without jax."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


class _Span:
    """Context manager for one complete span: a profiler annotation
    always, a ``ph="X"`` ring event when the ring is enabled.  ``args``
    is mutable until exit — fill in values discovered mid-span; the
    profiler receives them all at exit (its encoding cuts a value at
    the first comma, so give sequences as space-separated strings)."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tr: "SpanTracer", name: str, cat: str, args: Dict):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "_Span":
        self._ann = _trace_annotation()(self.name)
        self._ann.__enter__()
        if self._tr.enabled:
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._tr.enabled:
            t1 = time.perf_counter_ns()
            self._tr._emit({"name": self.name, "cat": self.cat, "ph": "X",
                            "ts": self._t0 / 1e3,
                            "dur": (t1 - self._t0) / 1e3,
                            "pid": 0, "tid": 0, "args": self.args})
        if self.args and self._ann.is_enabled():
            self._ann.set_metadata(**self.args)
        self._ann.__exit__(*exc)


class SpanTracer:
    def __init__(self, capacity: int = 65536, enabled: bool = False):
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self._ring: List[Optional[Dict]] = [None] * self.capacity
        self._n = 0                       # total events ever emitted

    # -- emit ---------------------------------------------------------------

    def _emit(self, ev: Dict) -> None:
        self._ring[self._n % self.capacity] = ev
        self._n += 1

    def _ts(self) -> float:
        return time.perf_counter_ns() / 1e3          # microseconds

    def span(self, name: str, cat: str = "engine", **args) -> _Span:
        """``with tracer.span("engine_step", kind="mixed") as sp: ...``
        — a profiler annotation whether or not the ring is enabled, so
        ``sp.args`` takes late values either way."""
        return _Span(self, name, cat, args)

    def begin(self, cat: str, id: object, name: str, **args) -> None:
        """Open an async span (``ph="b"``) under ``(cat, id)``."""
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "b", "id": str(id),
                    "ts": self._ts(), "pid": 0, "tid": 0, "args": args})

    def end(self, cat: str, id: object, name: str, **args) -> None:
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "e", "id": str(id),
                    "ts": self._ts(), "pid": 0, "tid": 0, "args": args})

    def instant(self, name: str, cat: str = "engine", **args) -> None:
        if not self.enabled:
            return
        self._emit({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": self._ts(), "pid": 0, "tid": 0, "args": args})

    # -- inspect / export ----------------------------------------------------

    @property
    def dropped_events(self) -> int:
        return max(0, self._n - self.capacity)

    def events(self) -> List[Dict]:
        """Buffered events, oldest first."""
        if self._n <= self.capacity:
            return [e for e in self._ring[:self._n]]
        start = self._n % self.capacity
        return self._ring[start:] + self._ring[:start]

    def chrome_trace(self) -> Dict:
        return {"traceEvents": self.events(),
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped_events}}

    def write_chrome_trace(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh)

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as fh:
            for ev in self.events():
                fh.write(json.dumps(ev) + "\n")
