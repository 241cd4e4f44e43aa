"""Reduction of a profiler trace to device busy time, idle gaps and top ops.

The benchmark traces its window with ``jax.profiler`` and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``. Device operations are
the events of the ``XLA Ops`` line of each ``/device:`` plane; where a trace
has no device plane (the CPU backend) they are the host events that carry an
``hlo_op`` stat. Host spans are the benchmark's own ``TraceAnnotation``s
plus the program's recorder spans, which one anchor annotation maps from
the recorder's clock onto the profiler's. Times here are nanoseconds on the
profiler's clock unless a name says otherwise.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Summary:
    busy_s: float                 # mean over devices of busy time in window
    window_s: float
    device_ops: List[List]        # [[name, seconds], ...] largest first
    idle_gaps: List[List]         # [[label, seconds], ...] longest first
    n_devices: int = 1


# ------------------------------------------------------------ interval math

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def busy(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(b - a for a, b in merge(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for a, b in merge(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, spans: Sequence[Span]) -> str:
    """The innermost span open at the gap's midpoint, or ``no_span``."""
    mid = 0.5 * (gap[0] + gap[1])
    open_ = [s for s in spans if s.start <= mid <= s.end]
    if not open_:
        return "no_span"
    return min(open_, key=lambda s: s.end - s.start).name


def top_ops(events: Iterable[Tuple[str, float, float]], lo: float,
            hi: float, n: int = 10) -> List[List]:
    """[[name, seconds], ...]: the ``n`` op names with the most device time
    inside [lo, hi], summed over their events."""
    tot: Dict[str, float] = {}
    for name, a, b in events:
        for ca, cb in clip([(a, b)], lo, hi):
            tot[name] = tot.get(name, 0.0) + (cb - ca)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def summarize(device_events: Dict[str, List[Tuple[str, float, float]]],
              spans: Sequence[Span], lo: float, hi: float,
              n: int = 10) -> Summary:
    """Busy time averaged over devices, the top ops of all of them, and the
    longest idle gaps of the first device, each labelled by a host span."""
    if not device_events or hi <= lo:
        raise ValueError("no device events or an empty window")
    names = sorted(device_events)
    busy_ns = [busy([(a, b) for _, a, b in device_events[d]], lo, hi)
               for d in names]
    all_ev = [e for d in names for e in device_events[d]]
    first = [(a, b) for _, a, b in device_events[names[0]]]
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return Summary(
        busy_s=sum(busy_ns) / len(busy_ns) / 1e9,
        window_s=(hi - lo) / 1e9,
        device_ops=top_ops(all_ev, lo, hi, n),
        idle_gaps=[[label(g, spans), (g[1] - g[0]) / 1e9] for g in idle],
        n_devices=len(names))


# ------------------------------------------------------------ xplane reading

def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def op_name(name: str, stats: Dict) -> str:
    """A short label for a device op. TPU traces name each op by its HLO
    text (``%fusion.12 = f32[32,64]{1,0} fusion(...)``): the label is the
    op's name and result type without layouts. CPU traces carry the name in
    an ``hlo_op`` stat."""
    if " = " in name:
        head, rest = name.split(" = ", 1)
        rest = re.sub(r"\{[^}]*\}", "", rest)
        shape = re.match(r"(\(.*?\)|\S+)", rest)
        return f"{head.lstrip('%')} {shape.group(1)[:48] if shape else ''}"
    return str(stats.get("hlo_op") or name)


def read_profile(path: str):
    """(device events by device name, host spans) from one ``.xplane.pb``.
    Host spans are the events of the host thread that holds the
    ``bench.anchor`` annotation (the benchmark's own thread), less the
    Python tracer's ``$``-prefixed calls; device events are
    ``(op name, start ns, end ns)``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    host_ops: List[Tuple[str, float, float]] = []
    spans: List[Span] = []
    for plane in pd.planes:
        pname = plane.name
        for line in plane.lines:
            if pname.startswith("/device:") and line.name == "XLA Ops":
                devices.setdefault(pname, []).extend(
                    (op_name(e.name, _stats(e)), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
            elif pname.startswith("/host:"):
                events = list(line.events)
                if any(e.name == "bench.anchor" for e in events):
                    spans = [Span(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                             for e in events if not e.name.startswith("$")]
                for e in events:
                    if e.duration_ns > 0 and not e.name.startswith("end:"):
                        st = _stats(e)
                        if "hlo_op" in st:
                            host_ops.append((op_name(e.name, st), e.start_ns,
                                             e.start_ns + e.duration_ns))
    if not devices and host_ops:
        devices["/host:CPU"] = host_ops
    return devices, spans


def find_span(spans: Sequence[Span], name: str) -> Optional[Span]:
    hits = [s for s in spans if s.name == name]
    return hits[0] if hits else None


def recorder_spans(events: Sequence[Dict], tracer_t0: float,
                   anchor_perf: float, anchor_ns: float) -> List[Span]:
    """The program's recorder spans (Chrome events, microseconds from the
    tracer's start ``tracer_t0`` on ``time.perf_counter``) on the profiler's
    clock, through one anchor seen on both clocks."""
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        t = tracer_t0 + e["ts"] / 1e6
        a = anchor_ns + (t - anchor_perf) * 1e9
        out.append(Span(e["name"], a, a + e["dur"] * 1e3))
    return out
