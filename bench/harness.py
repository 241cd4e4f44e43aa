"""One run of one benchmark cell.

Set-up builds the cell's federation from the seed, one
``FederatedSimulation`` with the benchmark's own ``RunRecorder``, and makes
one untimed ``run(method)`` call, which compiles (or loads from the compile
cache) every block the window uses. The window then calls
``FederatedSimulation.run(method)`` until the first whole call that ends
past ``seconds``; each call simulates ``rounds_per_call`` rounds from the
seed's initial state, so every call does the same work. After the window
the peak device memory is read, the program's state is freed, and the plain
reference replays the rounds: each call's outputs (every client's tapped
train loss, π, the link success rate, the accuracies) are compared with it.

Everything that belongs to one configuration, traffic mix, per-layer metric
or model kind is a file of its own under the benchmark's directory, found by
the name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import compare, tracing
from bench import data as bdata
from bench import flops as bflops

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Catalog:
    """The benchmark's files, found by name: ``configs/<name>.json``,
    ``traffic/<name>.json``, ``limits/<cell>.json``, ``metrics/<name>.py``,
    ``models/<kind>.py`` and ``peaks.json`` under ``bench_dir``, and the
    cells and metrics of ``BENCHMARK.json``."""

    def __init__(self, bench_dir: str = BENCH,
                 benchmark: Optional[Dict] = None):
        self.dir = bench_dir
        self.benchmark = benchmark if benchmark is not None else _load(
            os.path.join(ROOT, "BENCHMARK.json"))

    def file(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def workload(self, name: str) -> Dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return _load(self.file("configs", f"{name}.json"))

    def traffic(self, name: str) -> Dict:
        return _load(self.file("traffic", f"{name}.json"))

    def limits(self, cell: str) -> Dict[str, float]:
        return _load(self.file("limits", f"{cell}.json"))

    def peak_flops(self, device_kind: str) -> float:
        peaks = _load(self.file("peaks.json"))["devices"]
        if device_kind not in peaks:
            raise KeyError(f"device kind {device_kind!r} is not in the "
                           f"peaks table")
        return float(peaks[device_kind]["flops_per_s"])

    def metrics(self, kind: str, cell: str) -> List[str]:
        """Names of the ``end_to_end`` or ``per_layer`` metrics this cell
        reports."""
        return [m["name"] for m in self.benchmark[kind]
                if cell in m.get("workloads", [cell])]

    def reader(self, name: str) -> Callable:
        path = self.file("metrics", f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


@dataclass
class Reading:
    """What a per-layer metric reader gets. Recorder spans are Chrome
    events (``ts``/``dur`` in microseconds), split at the window's start."""
    setup_spans: List[Dict]
    window_spans: List[Dict]
    window_rounds: int
    window_s: float
    flops_per_round: float
    peak_flops: float
    trace: Optional[tracing.Summary] = None


def check_devices(chips: int):
    """The devices the cell runs on; raises :class:`NoChip` unless JAX
    finds an accelerator with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip(f"JAX found no accelerator, only {devs[0].platform} "
                     f"({len(devs)} device(s))")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def build_sim(cfg: Dict, traffic: Dict, fed: bdata.Federation, seed: int,
              recorder):
    """The program's ``FederatedSimulation`` for this configuration and
    traffic, fed the federation's data."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.core.fedsim import FederatedSimulation, FedSimConfig
    from repro.data.synthetic import SyntheticImageDataset

    mc, tr = cfg["model_config"], cfg["train"]
    n_classes = mc["n_classes"]
    model_cfg = CNNConfig(name=cfg["name"], image_size=mc["image_size"],
                          channels=mc["channels"], n_classes=n_classes,
                          widths=tuple(mc["widths"]), hidden=mc["hidden"])
    engine = traffic.get("engine", "fused")
    sim_cfg = FedSimConfig(
        rounds=traffic["rounds_per_call"], eval_every=traffic["eval_every"],
        batch_size=tr["batch_size"], lr=tr["lr"], alpha=tr["alpha"],
        em_iters=tr["em_iters"], em_component_steps=tr["em_component_steps"],
        em_subset=tr["em_subset"], adapt_subset=tr["adapt_subset"],
        maml_inner_lr=tr["maml_inner_lr"], seed=seed,
        fused=engine in ("fused", "sharded"), sharded=engine == "sharded",
        shard_devices=traffic.get("chips") if engine == "sharded" else None)

    def sets(parts):
        return [SyntheticImageDataset(x, y, n_classes) for x, y in parts]

    return FederatedSimulation(model_cfg, sets(fed.train), sets(fed.test),
                               fed.participants, fed.p_err, sim_cfg,
                               recorder=recorder)


def call_outputs(recorder, run_id: str) -> List[Dict]:
    """Per round of one ``run`` call: the tapped train losses, the link
    success rate, and the eval's accuracies and π."""
    rounds = [e for e in recorder.events
              if e["type"] == "round" and e["run_id"] == run_id]
    evals = {e["round"]: e for e in recorder.events
             if e["type"] == "eval" and e["run_id"] == run_id}
    out = []
    for r in rounds:
        ev = evals.get(r["round"], {})
        out.append({"train_loss": np.asarray(r["train_loss"], np.float64),
                    "link_success_rate": r["link_success_rate"],
                    "pi": (None if ev.get("pi") is None
                           else np.asarray(ev["pi"], np.float64)),
                    "target_acc": ev.get("target_acc"),
                    "mean_participant_acc": ev.get("mean_participant_acc")})
    return out


@dataclass
class Window:
    """The measured window: its calls' outputs and its clock readings."""
    calls: List[List[Dict]]
    rounds: int
    t0: float                  # time.perf_counter at its first round
    t1: float
    anchor_perf: float         # time.perf_counter at the anchor annotation
    compiles: int              # recorder compile events inside it


def measure(sim, recorder, method: str, rounds_per_call: int,
            seconds: float) -> Window:
    """Call ``sim.run(method)`` until the first whole call that ends past
    ``seconds``. Each call and the window are ``TraceAnnotation``s; an
    anchor annotation ties the recorder's clock to the profiler's."""
    import jax
    n_compiles = sum(e["type"] == "compile" for e in recorder.events)
    anchor_perf = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.anchor"):
        pass
    calls, rounds = [], 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                sim.run(method)
            rounds += rounds_per_call
            calls.append(call_outputs(recorder, recorder.run_id))
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    compiles = sum(e["type"] == "compile"
                   for e in recorder.events) - n_compiles
    return Window(calls, rounds, t0, t1, anchor_perf, compiles)


def read_trace(profile_dir: str, events: List[Dict], tracer_t0: float,
               anchor_perf: float) -> tracing.Summary:
    """The traced window reduced to busy time, top ops and idle gaps, with
    the recorder's spans mapped onto the profiler's clock."""
    try:
        devices, spans = tracing.read_profile(
            tracing.find_xplane(profile_dir))
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)
    win = tracing.find_span(spans, "bench.window")
    anchor = tracing.find_span(spans, "bench.anchor")
    if win is None or anchor is None:
        raise RuntimeError("the trace holds no bench.window or bench.anchor")
    spans = spans + tracing.recorder_spans(events, tracer_t0, anchor_perf,
                                           anchor.start)
    return tracing.summarize(devices, spans, win.start, win.end)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             catalog: Optional[Catalog] = None, require_chip: bool = True,
             t_start: Optional[float] = None,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                          flush=True)
             ) -> Dict[str, Any]:
    """One run of ``workload``; returns the result object the benchmark
    prints. With ``require_chip`` False the device check is skipped (the
    CPU rehearsal of the tests); nothing else changes."""
    t_start = time.perf_counter() if t_start is None else t_start
    cat = catalog or Catalog()
    wl = cat.workload(workload)
    cfg, traffic = cat.config(wl["config"]), cat.traffic(wl["traffic"])
    limits = cat.limits(workload)
    method, rounds_per_call = traffic["method"], traffic["rounds_per_call"]

    import jax
    devs = check_devices(wl["chips"]) if require_chip else jax.devices()
    dev = devs[0]
    peak = cat.peak_flops(dev.device_kind)
    from repro import obs

    sizes = bdata.layout_sizes(cfg)
    fed = bdata.make_federation(cfg, seed)
    key_seed = bdata.derived_seed(seed)
    # the program runs at the matmul precision its configuration states
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        tracer_t0 = time.perf_counter()
        recorder = obs.RunRecorder(tracer=obs.Tracer(clock=time.perf_counter))
        sim = build_sim(cfg, traffic, fed, key_seed, recorder)
        if sim.steps_per_round != sizes["steps_per_round"]:
            raise bdata.LayoutError(
                f"the program takes {sim.steps_per_round} steps per round, "
                f"the configuration expects {sizes['steps_per_round']}")
        sim.run(method)                              # compiles, warms up
        warm = call_outputs(recorder, recorder.run_id)
        if trace:
            profile_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        win = measure(sim, recorder, method, rounds_per_call, seconds)
        if trace:
            jax.profiler.stop_trace()
    log(f"compiles inside the window: {win.compiles}")

    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devs[:wl["chips"]])
    events = list(recorder.tracer.events)
    del sim, recorder
    gc.collect()

    if trace:
        win_us = (win.t0 - tracer_t0) * 1e6
        reading = Reading(
            setup_spans=[e for e in events if e["ts"] < win_us],
            window_spans=[e for e in events if e["ts"] >= win_us],
            window_rounds=win.rounds, window_s=win.t1 - win.t0,
            flops_per_round=bflops.round_flops(cfg, method), peak_flops=peak,
            trace=read_trace(profile_dir, events, tracer_t0, win.anchor_perf))
        metrics = {}
        for name in cat.metrics("per_layer", workload):
            v = cat.reader(name)(reading)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": _unit(cat, name)}
    else:
        metrics = {
            "round_ms": {"value": (win.t1 - win.t0) / win.rounds * 1e3,
                         "unit": "ms"},
            "peak_hbm_gb": {"value": peak_bytes / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        wanted = set(cat.metrics("end_to_end", workload))
        metrics = {k: v for k, v in metrics.items() if k in wanted}

    t_ref = time.perf_counter()
    from bench import reference
    ref = reference.Reference(cfg, method, fed, key_seed).run(rounds_per_call)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
        f"{rounds_per_call} rounds")
    per_call = [compare.numbers(c, ref) for c in [warm] + win.calls]
    numbers = compare.worst(per_call)
    for k in sorted(set(numbers) - set(limits)):
        log(f"not compared {k}: {numbers[k]!r}")
    numbers["compiles_in_window"] = float(win.compiles)
    checks = compare.checks(numbers, {**limits, "compiles_in_window": 0.0})

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result: Dict[str, Any] = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": win.rounds,
        "failed": sum(rounds_per_call for c in per_call[1:]
                      if not compare.passes(c, limits)),
        "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reading.trace.busy_s
        device["window_s"] = reading.trace.window_s
        result["breakdown"] = {"device_ops": reading.trace.device_ops,
                               "idle_gaps": reading.trace.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _unit(cat: Catalog, name: str) -> str:
    for m in cat.benchmark["per_layer"] + cat.benchmark["end_to_end"]:
        if m["name"] == name:
            return m["unit"]
    raise KeyError(name)
