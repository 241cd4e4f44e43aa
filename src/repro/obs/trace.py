"""Span tracing: host-side wall-clock phases as Chrome-trace-format JSON.

A :class:`Tracer` collects complete ("ph": "X") events for the phases the
simulator goes through — data staging, XLA compiles (their spans carry the
FLOP/byte estimates of the compiled executable), initial state, round-block
execution, drain. ``Tracer.export`` writes a file loadable by
``chrome://tracing`` or https://ui.perfetto.dev. Each span is also a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows it on the thread that ran it, on the profiler's own clock. The
simulator's :class:`repro.obs.record.RunRecorder` owns its own tracer
instance so concurrent simulations don't interleave.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

import jax


class Span:
    """Handle yielded by ``span(...)``: attach late args, read the duration
    after the block exits."""

    __slots__ = ("name", "args", "duration_s")

    def __init__(self, name: str, args: Dict[str, Any]):
        self.name = name
        self.args = args
        self.duration_s: Optional[float] = None

    def set(self, **args: Any) -> None:
        """Add args discovered while the span is open."""
        self.args.update(args)


class Tracer:
    """Collects Chrome-trace events. ``clock`` is injectable so tests can
    produce deterministic timestamps."""

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        self.events: List[Dict[str, Any]] = []

    def _now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "phase", **args: Any):
        """Context manager recording a complete event around the block,
        and a profiler annotation of the same name."""
        t0 = self._now_us()
        sp = Span(name, dict(args))
        try:
            with jax.profiler.TraceAnnotation(name):
                yield sp
        finally:
            dur = self._now_us() - t0
            sp.duration_s = dur / 1e6
            self.events.append({"name": name, "cat": cat, "ph": "X",
                                "ts": t0, "dur": dur, "pid": 0, "tid": 0,
                                "args": sp.args})

    # ------------------------------------------------------------- export

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        return {"traceEvents": list(self.events), "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, sort_keys=True)
            f.write("\n")
