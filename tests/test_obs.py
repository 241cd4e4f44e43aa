"""Telemetry subsystem: metrics-core determinism (byte-identical JSONL),
Chrome-trace schema validity, fused/sharded RunRecord parity, and the
single-executable regression for the instrumented fused block."""
import json

import numpy as np
import pytest

from repro import obs
from repro.lint import hlo as lint_hlo
from repro.configs.paper_cnn import CNNConfig
from repro.core.fedsim import PHASES, FederatedSimulation, FedSimConfig
from repro.obs import report as obs_report
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import Tracer
from repro.models import cnn
from repro.data import (dirichlet_partition, make_client_datasets,
                        synthetic_image_dataset, train_test_split)


# --------------------------------------------------------------- fixtures

def _tiny_setup(n_clients=4, seed=0):
    model_cfg = CNNConfig(image_size=8, widths=(4,), hidden=16, n_classes=4)
    base = synthetic_image_dataset(seed, 400, image_size=8, n_classes=4)
    parts = dirichlet_partition(base.y, n_clients, alpha=0.3, seed=seed)
    train = make_client_datasets(
        base, [train_test_split(p, seed=1)[0] for p in parts])
    test = make_client_datasets(
        base, [train_test_split(p, seed=1)[1] for p in parts])
    pm = np.array([True] * (n_clients - 1) + [False])
    p_err = np.linspace(0.0, 0.2, n_clients).astype(np.float32)
    return model_cfg, train, test, pm, p_err


def _cfg(**kw):
    base = dict(rounds=3, batch_size=16, lr=0.05, em_iters=2, em_subset=64,
                adapt_subset=32, eval_every=2, seed=0)
    base.update(kw)
    return FedSimConfig(**base)


@pytest.fixture(scope="module")
def recorded_pair():
    """(fused, sharded on a one-device client mesh) tiny sims, pfedwn
    already run on both."""
    model_cfg, train, test, pm, p_err = _tiny_setup()
    fused = FederatedSimulation(model_cfg, train, test, pm, p_err, _cfg())
    sharded = FederatedSimulation(model_cfg, train, test, pm, p_err,
                                  _cfg(sharded=True, shard_devices=1))
    fused.run("pfedwn")
    sharded.run("pfedwn")
    return fused, sharded


# ---------------------------------------------------------- metrics core

def _drive(rec: obs.RunRecorder) -> None:
    rec.begin_run(method="pfedwn", engine="fused",
                  meta={"n_clients": 4, "rounds": 3})
    rec.record_compile("pfedwn/block1",
                       cost={"flops": 1e6, "bytes accessed": 2e5},
                       seconds=1.5)
    for rnd in range(3):
        rec.record_round(rnd, train_loss=[1.5 - 0.1 * rnd, 1.2, 0.9, 1.1],
                         em_entropy=1.0 - 0.2 * rnd,
                         link_success_rate=2.0 / 3.0,
                         effective_neighbors=1.8)
        rec.observe_round_latency(12.5)
    rec.record_eval(2, target_acc=0.75, mean_participant_acc=0.6,
                    pi=[0.5, 0.3, 0.2])
    rec.end_run(method="pfedwn", engine="fused", rounds=3,
                max_target_acc=0.75, final_target_acc=0.75)


def test_metrics_core_byte_identical_jsonl():
    """Identical update sequences serialize to byte-identical JSONL (clock
    injected, so even the meta timestamp is reproducible)."""
    out = []
    for _ in range(2):
        rec = obs.RunRecorder(clock=lambda: 1234.5)
        _drive(rec)
        out.append(rec.memory.to_jsonl())
    assert out[0] == out[1]
    assert out[0].encode() == out[1].encode()
    # and every line passes the schema validator
    assert obs.validate_jsonl_lines(out[0].splitlines()) == []


def test_metrics_registry_instruments():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(2)
    m.gauge("g").set(0.5)
    m.timeseries("t").append(0, 1.0)
    m.timeseries("t").append(2, 3.0)
    h = m.histogram("h")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 0.5
    assert snap["timeseries"]["t"] == {"steps": [0, 2], "values": [1.0, 3.0]}
    assert snap["histograms"]["h"]["count"] == 5
    assert snap["histograms"]["h"]["p50"] == 3.0
    assert snap["histograms"]["h"]["p99"] == 100.0
    m.reset()
    assert m.snapshot()["counters"] == {}


def test_histogram_weighted_observe_and_empty():
    h = Histogram()
    assert h.snapshot() == {"count": 0}
    h.observe(10.0, n=4)
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["p90"] == 10.0


def test_validate_event_catches_violations():
    assert obs.validate_event({"type": "nope"}) != []
    assert any("missing key" in e
               for e in obs.validate_event({"type": "round"}))
    bad_engine = {"type": "meta", "schema": obs.SCHEMA_VERSION,
                  "run_id": "x", "method": "local", "engine": "warp",
                  "time_unix": 0.0, "meta": {}}
    assert any("engine" in e for e in obs.validate_event(bad_engine))
    assert obs.validate_jsonl_lines(["not json"]) != []


# ---------------------------------------------------------- span tracing

def test_chrome_trace_schema(tmp_path):
    fake = iter(range(100))
    tracer = Tracer(clock=lambda: next(fake) * 1e-3)
    with tracer.span("outer", method="pfedwn") as sp:
        sp.set(rounds=3)
        with tracer.span("inner", cat="compile"):
            pass
    path = tmp_path / "t.trace.json"
    tracer.export(str(path))
    doc = json.loads(path.read_text())
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert isinstance(ev["name"], str)
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert "pid" in ev and "tid" in ev
        assert ev["dur"] >= 0
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["inner", "outer"]
    outer = next(e for e in doc["traceEvents"] if e["name"] == "outer")
    assert outer["args"]["rounds"] == 3


def test_compile_span_carries_the_cost_estimate(recorded_pair):
    """The compile span holds the executable's FLOP/byte estimate, beside
    the RunRecord compile event; no second, backdated trace event."""
    fused, _ = recorded_pair
    spans = [e for e in fused.recorder.tracer.events
             if e["name"] == "compile"]
    records = [e for e in fused.recorder.events if e["type"] == "compile"]
    assert len(spans) == len(records) == 2          # block lengths 1 and 2
    for sp, rec in zip(spans, records):
        assert sp["args"]["flops"] == rec["flops"] > 0
        assert sp["args"]["bytes_accessed"] == rec["bytes_accessed"] > 0
    assert all(":" not in e["name"] for e in fused.recorder.tracer.events)


# ------------------------------------------------------- device scopes

@pytest.mark.parametrize("method,engine,phases", [
    ("pfedwn", "fused", ("local_sgd", "em", "mix", "post_sgd", "eval")),
    ("fedavg", "fused", ("local_sgd", "mix", "eval")),
    ("pfedwn", "sharded", ("local_sgd", "em", "mix", "post_sgd", "eval")),
])
def test_compiled_blocks_carry_their_scopes(method, engine, phases):
    """Every block a run compiled is named for its method, engine and
    length, and its ops carry each phase the method runs and both model
    layers."""
    model_cfg, train, test, pm, p_err = _tiny_setup()
    sharded = engine == "sharded"
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg(sharded=sharded,
                                   shard_devices=1 if sharded else None))
    sim.run(method)
    scopes = sim.device_scopes()
    infix = "sharded_" if sharded else ""
    assert set(scopes) == {f"jit_{method}_{infix}block_r{n}" for n in (1, 2)}
    assert set(phases) <= set(PHASES)
    for ops in scopes.values():
        found = set(ops.values())
        assert {p for p, _ in found if p} == set(phases)
        assert {l for _, l in found if l} == set(cnn.SCOPES)
        # backward ops count with their phase and layer
        assert ("local_sgd", "conv") in found and ("local_sgd", "pool") in found


def test_op_scopes_reads_transform_wrapped_names():
    hlo = "\n".join([
        "HloModule jit_pfedwn_block_r1, is_scheduled=true",
        "ENTRY %main.3 (p: f32[4]) -> f32[4] {",
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name='
        '"jit(pfedwn_block_r1)/while/body/local_sgd/vmap()/while/body/'
        'transpose(jvp(conv))/dot_general" stack_frame_id=3}',
        '  %dot.2 = f32[4]{0} dot(%p, %p), metadata={op_name='
        '"jit(pfedwn_block_r1)/eval/vmap(pool)/reduce_window_max"}',
        '  %copy.3 = f32[4]{0} copy(%p), metadata={op_name='
        '"jit(pfedwn_block_r1)/while/body/add"}',
        "  ROOT %tuple.4 = (f32[4]{0}) tuple(%copy.3)",
        "}"])
    assert obs.hlo_module_name(hlo) == "jit_pfedwn_block_r1"
    assert obs.op_scopes(hlo, PHASES, cnn.SCOPES) == {
        "fusion.1": ("local_sgd", "conv"),
        "dot.2": ("eval", "pool"),
        "copy.3": (None, None),
        "tuple.4": (None, None)}


def test_op_scopes_fills_what_the_compiler_dropped():
    """An op the compiler built without metadata takes its scopes from the
    ops fused into it, then from the loop op whose body holds it."""
    hlo = "\n".join([
        "HloModule jit_fedavg_block_r1, is_scheduled=true",
        "%fused_views (p0: bf16[8,16], p1: bf16[8,4]) -> bf16[8,16] {",
        "  %p0 = bf16[8,16]{1,0} parameter(0)",
        "  %p1 = bf16[8,4]{1,0} parameter(1)",
        '  %slice.7 = bf16[8,4]{1,0} slice(%p1), slice={[0:8], [0:4]}, '
        'metadata={op_name="jit(fedavg_block_r1)/local_sgd/vmap()/while/'
        'body/jvp(conv)/slice"}',
        "  ROOT %dynamic-update-slice.8 = bf16[8,16]{1,0} "
        "dynamic-update-slice(%p0, %slice.7, %c, %c)",
        "}",
        "%body.1 (t: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {",
        "  %t = (s32[], bf16[8,16]{1,0}) parameter(0)",
        "  %dus_fusion.2 = bf16[8,16]{1,0} fusion(%a, %b), kind=kLoop, "
        "calls=%fused_views",
        "  %copy.3 = bf16[8,16]{1,0} copy(%dus_fusion.2)",
        "  ROOT %tuple.4 = (s32[], bf16[8,16]{1,0}) tuple(%i, %copy.3)",
        "}",
        "ENTRY %main.5 (p: bf16[8,16]) -> bf16[8,16] {",
        "  %p = bf16[8,16]{1,0} parameter(0)",
        "  %while.6 = (s32[], bf16[8,16]{1,0}) while(%init), "
        "condition=%cond.0, body=%body.1, metadata={op_name="
        '"jit(fedavg_block_r1)/local_sgd/vmap()/while"}',
        "  ROOT %copy.9 = bf16[8,16]{1,0} copy(%p)",
        "}"])
    got = obs.op_scopes(hlo, PHASES, cnn.SCOPES)
    assert got["dus_fusion.2"] == ("local_sgd", "conv")   # its fused ops
    assert got["copy.3"] == ("local_sgd", None)           # its loop op
    assert got["while.6"] == ("local_sgd", None)
    assert got["copy.9"] == (None, None)                  # no scope at all
    assert got["slice.7"] == ("local_sgd", "conv")


def test_op_scopes_keeps_a_named_fusion_s_own_scopes():
    """A fusion whose own ``op_name`` names a phase and no layer (a relu
    rooted fusion) keeps no layer, though a conv op is fused into it."""
    hlo = "\n".join([
        "HloModule jit_pfedwn_block_r1, is_scheduled=true",
        "%fused_relu (p0: f32[8], p1: f32[8]) -> f32[8] {",
        "  %p0 = f32[8]{0} parameter(0)",
        "  %p1 = f32[8]{0} parameter(1)",
        '  %add.2 = f32[8]{0} add(%p0, %p1), metadata={op_name='
        '"jit(pfedwn_block_r1)/while/body/local_sgd/vmap()/while/body/'
        'jvp(conv)/add"}',
        '  ROOT %maximum.3 = f32[8]{0} maximum(%add.2, %p1), metadata={'
        'op_name="jit(pfedwn_block_r1)/while/body/local_sgd/vmap()/while/'
        'body/jvp()/max"}',
        "}",
        "ENTRY %main.5 (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %add_maximum_fusion = f32[8]{0} fusion(%p, %p), kind=kLoop, "
        "calls=%fused_relu, metadata={op_name="
        '"jit(pfedwn_block_r1)/while/body/local_sgd/vmap()/while/body/'
        'jvp()/max"}',
        "}"])
    got = obs.op_scopes(hlo, PHASES, cnn.SCOPES)
    assert got["add_maximum_fusion"] == ("local_sgd", None)
    assert got["add.2"] == ("local_sgd", "conv")


def test_run_spans_are_profiler_events(tmp_path):
    """A profiled ``run`` shows the recorder's spans as host events of the
    ``.xplane.pb``, on the profiler's clock, each block's ops inside its
    ``block_exec``."""
    import glob

    import jax
    model_cfg, train, test, pm, p_err = _tiny_setup(n_clients=3)
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg(rounds=2, eval_every=1))
    sim.run("local")                                # compiles outside
    jax.profiler.start_trace(str(tmp_path))
    sim.run("local")
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {}
    ops = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                host.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
                if dict(e.stats).get("hlo_module") == "jit_local_block_r1":
                    ops.append((e.start_ns, e.start_ns + e.duration_ns))
    assert len(host["init_state"]) == 1
    assert len(host["block_exec"]) == len(host["drain"]) == 2
    assert ops and all(any(a <= s and t <= b for a, b in host["block_exec"])
                       for s, t in ops)


# ------------------------------------------------- device time by scope

def _profile(log_dir, fn):
    """Run ``fn`` under the profiler; the ``.xplane.pb`` it wrote."""
    import glob

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    return path


def _busy_s(device_ops, lo=float("-inf"), hi=float("inf")):
    """Mean over devices of the union of the ops' intervals in [lo, hi]."""
    tot = 0.0
    for ops in device_ops.values():
        end = float("-inf")
        for a, b in sorted((max(a, lo), min(b, hi)) for _, _, a, b in ops
                           if b > lo and a < hi):
            tot += max(b - max(a, end), 0.0)
            end = max(end, b)
    return tot / len(device_ops) / 1e9


def test_innermost_op_takes_each_instant():
    from repro.obs.device_time import innermost
    # a loop op (0-100) whose body ops run 10-40 and 50-60, one of them
    # holding a nested op 20-30; another op 120-130 outside every scope map
    ev = [("loop", 0, 100), ("body_a", 10, 40), ("inner", 20, 30),
          ("body_b", 50, 60), ("stray", 120, 130)]
    assert innermost(ev, 0, 200) == {
        "loop": 60, "body_a": 20, "inner": 10, "body_b": 10, "stray": 10}
    assert innermost(ev, 25, 55) == {
        "inner": 5, "body_a": 10, "loop": 10, "body_b": 5}
    ops = {"d0": [("m", op, a * 1e9, b * 1e9) for op, a, b in ev[:4]]
           + [("other", "stray", 120e9, 130e9)],
           "d1": [("m", "loop", 0, 40e9)]}
    scopes = {"m": {"loop": (None, None), "body_a": ("local_sgd", None),
                    "inner": ("local_sgd", "conv"),
                    "body_b": ("em", "pool")}}
    got = obs.scope_time(ops, scopes, 0, 200e9)
    assert got == {"unscoped": 50.0, "local_sgd": 15.0, "conv": 5.0,
                   "em": 5.0, "pool": 5.0, "unmatched": 5.0}
    assert got["local_sgd"] + got["em"] + got["unscoped"] \
        + got["unmatched"] == _busy_s(ops) == 75.0


def test_scope_time_of_a_recorded_cpu_trace(tmp_path):
    """Two named scopes of a jitted function each read device time from a
    CPU trace, and with the rest they add up to the busy time of the
    window."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        with jax.named_scope("local_sgd"):
            x = jnp.tanh(x @ x)
        with jax.named_scope("em"):
            return jnp.sin(x @ x.T).sum()

    f = jax.jit(fn)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()

    def window():
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(2):
                f(x).block_until_ready()

    path = _profile(tmp_path, window)
    (win,) = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in jax.profiler.ProfileData.from_file(path).planes
              for line in p.lines for e in line.events if e.name == "window"]
    text = f.lower(x).compile().as_text()
    scopes = {obs.hlo_module_name(text): obs.op_scopes(
        text, ("local_sgd", "em"))}
    ops = obs.read_device_ops(path)
    assert set(ops) == {"/host:CPU"}
    got = obs.scope_time(ops, scopes, *win)
    assert got["local_sgd"] > 0 and got["em"] > 0
    assert got.get("unmatched", 0.0) == 0.0
    assert sum(got.values()) == pytest.approx(_busy_s(ops, *win), rel=1e-9)


@pytest.mark.parametrize("method,phases", [
    ("pfedwn", ("local_sgd", "em", "mix", "post_sgd", "eval")),
    ("fedavg", ("local_sgd", "mix", "eval")),
])
def test_profiled_run_reads_each_scope(tmp_path, method, phases):
    """A profiled ``run`` of the tiny federation, reduced with the
    simulation's own ``device_scopes()``: every phase the method runs and
    both model layers took device time, and the phases with ``unscoped``
    and ``unmatched`` (ops of no round block) add up to the busy time."""
    model_cfg, train, test, pm, p_err = _tiny_setup()
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg())
    sim.run(method)                                 # compiles outside
    ops = obs.read_device_ops(_profile(tmp_path, lambda: sim.run(method)))
    got = obs.scope_time(ops, sim.device_scopes())
    for name in phases + cnn.SCOPES:
        assert got.get(name, 0.0) > 0, name
    assert set(got) - set(phases) - set(cnn.SCOPES) <= {"unscoped",
                                                        "unmatched"}
    whole = sum(got.get(k, 0.0) for k in phases + ("unscoped", "unmatched"))
    assert whole == pytest.approx(_busy_s(ops), rel=1e-9)


# ----------------------------------------------- engine record integration

def test_fused_sharded_record_schema_parity(recorded_pair):
    """Both placements emit schema-valid records with the same event
    sequence and keys, and the device-tap scalars agree numerically (same
    index stream)."""
    fused, sharded = recorded_pair
    ef = fused.recorder.events
    el = sharded.recorder.events
    assert [e["type"] for e in ef if e["type"] != "compile"] == \
        [e["type"] for e in el if e["type"] != "compile"]
    by_type_f = {e["type"]: e for e in ef}
    by_type_l = {e["type"]: e for e in el}
    for etype in ("meta", "round", "eval", "summary"):
        assert set(by_type_f[etype]) == set(by_type_l[etype]), etype
    rf = [e for e in ef if e["type"] == "round"]
    rl = [e for e in el if e["type"] == "round"]
    assert len(rf) == len(rl) == 3
    for a, b in zip(rf, rl):
        np.testing.assert_allclose(a["train_loss"], b["train_loss"],
                                   atol=5e-3)
        np.testing.assert_allclose(a["em_entropy"], b["em_entropy"],
                                   atol=1e-3)
        assert a["link_success_rate"] == pytest.approx(
            b["link_success_rate"])
        np.testing.assert_allclose(a["effective_neighbors"],
                                   b["effective_neighbors"], atol=1e-3)
    # schema valid end-to-end
    for events in (ef, el):
        lines = [obs.encode_event(e) for e in events]
        assert obs.validate_jsonl_lines(lines) == []


def test_fused_round_events_deterministic(recorded_pair):
    """Same seed => byte-identical round/eval events from a fresh sim (the
    tap path carries no wall-clock)."""
    fused, _ = recorded_pair
    model_cfg, train, test, pm, p_err = _tiny_setup()
    again = FederatedSimulation(model_cfg, train, test, pm, p_err,
                                _cfg())
    again.run("pfedwn")

    def tap_lines(sim):
        return [obs.encode_event(e) for e in sim.recorder.events
                if e["type"] in ("round", "eval")]

    assert tap_lines(fused) == tap_lines(again)


def test_instrumented_block_still_single_executable(recorded_pair):
    """With taps ON (the default), a round block still lowers to one
    executable with no host callbacks — the tap rides the scan outputs."""
    fused, _ = recorded_pair
    assert fused.sim.taps
    block = fused.block_fn("pfedwn")
    lowered = block.lower(fused.initial_state(), fused.staged_data(), 3)
    lint_hlo.assert_round_block(lowered, expect_collectives=False)
    # ...and the run really synced only at the two eval boundaries
    assert fused.last_run_stats["device_calls"] == 2


def test_taps_off_drops_round_events():
    model_cfg, train, test, pm, p_err = _tiny_setup(n_clients=3)
    sim = FederatedSimulation(model_cfg, train, test, pm, p_err,
                              _cfg(taps=False, rounds=2,
                                   eval_every=1))
    sim.run("local")
    types = [e["type"] for e in sim.recorder.events]
    assert "round" not in types
    assert "eval" in types and "summary" in types


def test_run_record_files_and_report_cli(tmp_path, capsys):
    model_cfg, train, test, pm, p_err = _tiny_setup(n_clients=3)
    sim = FederatedSimulation(
        model_cfg, train, test, pm, p_err,
        _cfg(rounds=2, eval_every=1,
             record_dir=str(tmp_path), run_name="rec"))
    sim.run("local")
    jsonl = tmp_path / "rec.jsonl"
    trace = tmp_path / "rec.trace.json"
    assert jsonl.exists() and trace.exists()
    assert obs.validate_jsonl_lines(
        jsonl.read_text().splitlines()) == []
    assert json.loads(trace.read_text())["traceEvents"]
    assert obs_report.main([str(jsonl)]) == 0
    out = capsys.readouterr().out
    assert "local" in out and "fused" in out


def test_report_cli_rejects_schema_violations(tmp_path, capsys):
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type":"round","run_id":"x"}\n')
    assert obs_report.main([str(bad)]) == 2
    assert "SCHEMA VIOLATIONS" in capsys.readouterr().err
