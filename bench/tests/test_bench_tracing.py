"""The reduction from a profiler trace to busy time, idle share, top ops
and labelled gaps: on hand-made intervals, and on a small trace recorded on
the CPU with a recorder span anchored onto the profiler's clock."""
import time

import jax
import jax.numpy as jnp

from bench import tracing
from repro import obs


def test_interval_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 60)]
    assert tracing.merge(iv) == [(0, 20), (30, 40), (50, 60)]
    assert tracing.busy(iv, 0, 100) == 40
    assert tracing.busy(iv, 15, 55) == 20          # 15-20, 30-40, 50-55
    assert tracing.gaps(iv, -5, 70) == [(-5, 0), (20, 30), (40, 50),
                                        (60, 70)]


def test_gap_labels_and_top_ops():
    spans = [tracing.Span("bench.call", 0, 100),
             tracing.Span("drain", 40, 60)]
    assert tracing.label((45, 55), spans) == "drain"
    assert tracing.label((10, 20), spans) == "bench.call"
    assert tracing.label((150, 160), spans) == "no_span"
    ev = [("a", 0, 10e9), ("b", 10e9, 12e9), ("a", 20e9, 25e9)]
    assert tracing.top_ops(ev, 0, 30e9) == [["a", 15.0], ["b", 2.0]]
    s = tracing.summarize({"d0": ev}, spans, 0, 30e9)
    assert s.busy_s == 17.0 and s.window_s == 30.0
    assert s.idle_gaps[0][1] == 8.0                 # 12 s to 20 s


def test_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tracer_t0 = time.perf_counter()
    tracer = obs.Tracer(clock=time.perf_counter)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    anchor_perf = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.anchor"):
        pass
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
        with tracer.span("drain"):
            time.sleep(0.2)
        f(x).block_until_ready()
    jax.profiler.stop_trace()

    devices, spans = tracing.read_profile(tracing.find_xplane(str(tmp_path)))
    win = tracing.find_span(spans, "bench.window")
    anchor = tracing.find_span(spans, "bench.anchor")
    mapped = tracing.recorder_spans(tracer.events, tracer_t0, anchor_perf,
                                    anchor.start)
    (drain,) = mapped
    assert win.start < drain.start < drain.end < win.end
    s = tracing.summarize(devices, spans + mapped, win.start, win.end)
    assert 0 < s.busy_s < s.window_s
    assert 100 * (1 - s.busy_s / s.window_s) > 50      # mostly the sleep
    assert any("dot" in name for name, _ in s.device_ops)
    label, seconds = s.idle_gaps[0]
    assert label == "drain" and seconds > 0.15
