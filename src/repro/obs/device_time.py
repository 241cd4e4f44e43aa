"""Device time of a profiler trace, by the program's named scopes.

A ``jax.profiler`` trace (``.xplane.pb``) times every device op; the map
of :meth:`FederatedSimulation.device_scopes` (:func:`repro.obs.scopes.op_scopes`
per compiled module) says which phase and model layer each op ran in.
:func:`read_device_ops` reads each device op with its module, and
:func:`scope_time` sums the ops' time by scope::

    sim.run(method)                          # compiles
    jax.profiler.start_trace(log_dir)
    sim.run(method)
    jax.profiler.stop_trace()
    seconds = scope_time(read_device_ops(xplane_path), sim.device_scopes())

Loop ops (``while``) span the ops of their bodies, so each instant of
device time goes to the innermost op covering it and is counted once.
Times are nanoseconds on the profiler's clock unless a name says
otherwise.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

# {device: [(module, op, start ns, end ns), ...]}
DeviceOps = Dict[str, List[Tuple[str, str, float, float]]]


def innermost(events: Iterable[Tuple[object, float, float]],
              lo: float = float("-inf"), hi: float = float("inf")
              ) -> Dict[object, float]:
    """``{key: ns}``: each instant of [lo, hi] that some ``(key, start,
    end)`` event covers, given to the innermost event covering it, the one
    that started last (of two that start together, the one that ends
    first). A loop op and the ops of its body are then counted once."""
    ev = sorted((max(a, lo), min(b, hi), k) for k, a, b in events
                if b > lo and a < hi)
    bounds = sorted({t for a, b, _ in ev for t in (a, b)})
    out: Dict[object, float] = defaultdict(float)
    heap: List[Tuple[float, float, int]] = []
    i = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while i < len(ev) and ev[i][0] <= t0:
            heapq.heappush(heap, (-ev[i][0], ev[i][1], i))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            out[ev[heap[0][2]][2]] += t1 - t0
    return dict(out)


def scope_time(device_ops: DeviceOps, scopes: Dict[str, Dict[str, Tuple]],
               lo: float = float("-inf"), hi: float = float("inf")
               ) -> Dict[str, float]:
    """Seconds of device time in [lo, hi] by phase and by layer, averaged
    over devices. Each instant goes to its innermost op (:func:`innermost`),
    and the op to ``scopes[module][op]``'s (phase, layer); an op with no
    phase counts as ``unscoped``, an op absent from ``scopes`` as
    ``unmatched``. The phases, ``unscoped`` and ``unmatched`` add up to the
    busy time; a layer's time is also inside its phase's."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in device_ops.values():
        own = innermost((((m, op), a, b) for m, op, a, b in ops), lo, hi)
        for (module, op), ns in own.items():
            found = scopes.get(module, {}).get(op)
            if found is None:
                tot["unmatched"] += ns
                continue
            phase, layer = found
            tot[phase or "unscoped"] += ns
            if layer:
                tot[layer] += ns
    n = max(len(device_ops), 1)
    return {k: v / n / 1e9 for k, v in tot.items()}


# ------------------------------------------------------------ xplane reading

def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def _module(name: str) -> str:
    """A module event's name less the program id the TPU trace appends:
    ``jit_pfedwn_block_r1(12)`` -> ``jit_pfedwn_block_r1``."""
    return re.sub(r"\(\d+\)$", "", name)


def _enclosing(modules: List[Tuple[float, float, str]], a: float,
               b: float) -> Optional[str]:
    """The module (events sorted by start) running over [a, b]."""
    mid = 0.5 * (a + b)
    i = bisect.bisect_right(modules, (mid, float("inf"), "")) - 1
    if i >= 0 and modules[i][1] >= mid:
        return modules[i][2]
    return None


def read_device_ops(path: str) -> DeviceOps:
    """``{device: [(module, op, start ns, end ns), ...]}`` from one
    ``.xplane.pb``: the events of each ``/device:`` plane's ``XLA Ops``
    line, or, where a trace has no device plane (the CPU backend), the
    host events that carry an ``hlo_op`` stat. ``op`` is the bare HLO name
    (``while.232``). An op's module is its ``hlo_module`` stat (the CPU
    trace); where an op has none (the TPU trace), the event of the device
    plane's ``XLA Modules`` line that encloses it."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: DeviceOps = {}
    host_ops: List[Tuple[str, str, float, float]] = []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:"):
            lines = list(plane.lines)
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              _module(e.name))
                             for line in lines if line.name == "XLA Modules"
                             for e in line.events)
            for line in lines:
                if line.name != "XLA Ops":
                    continue
                out = devices.setdefault(pname, [])
                for e in line.events:
                    st = _stats(e)
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    module = (st.get("hlo_module")
                              or _enclosing(modules, a, b))
                    op = (st.get("hlo_op")
                          or e.name.split(" = ")[0].lstrip("%"))
                    out.append((str(module), str(op), a, b))
        elif pname.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and not e.name.startswith("end:"):
                        st = _stats(e)
                        if "hlo_op" in st:
                            host_ops.append(
                                (str(st.get("hlo_module")), str(st["hlo_op"]),
                                 e.start_ns, e.start_ns + e.duration_ns))
    if not devices and host_ops:
        devices["/host:CPU"] = host_ops
    return devices
