"""Telemetry subsystem: metrics core, span tracing, RunRecords and scopes.

Four layers (see ``docs/observability.md``):

  - :mod:`repro.obs.metrics` — counters / gauges / histograms / per-round
    timeseries behind a :class:`MetricsRegistry`.
  - :mod:`repro.obs.trace` — ``Tracer.span(...)`` host-side wall-clock
    spans, exported as Chrome-trace JSON (Perfetto-loadable) and entered
    as profiler annotations, with compile spans carrying FLOP/byte
    estimates.
  - :mod:`repro.obs.record` — the :class:`RunRecorder` facade writing the
    structured JSONL ``RunRecord`` consumed by ``python -m
    repro.obs.report``.
  - :mod:`repro.obs.scopes` — the named scopes (phases, model layers)
    of a compiled round block's ops, and :mod:`repro.obs.device_time`,
    which sums a profiler trace's device time by them.

The federated simulator owns a recorder per instance; device-side metric
taps ride the round engine's scan outputs and drain only at eval
boundaries, so recording never adds host syncs to the round loop.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               Timeseries)
from repro.obs.record import (SCHEMA_VERSION, JsonlSink, MemorySink,
                              RunRecorder, encode_event, validate_event,
                              validate_jsonl_lines)
from repro.obs.device_time import read_device_ops, scope_time
from repro.obs.scopes import hlo_module_name, op_scopes
from repro.obs.trace import Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Timeseries",
    "SCHEMA_VERSION", "JsonlSink", "MemorySink", "RunRecorder",
    "encode_event", "validate_event", "validate_jsonl_lines",
    "Span", "Tracer", "hlo_module_name", "op_scopes", "read_device_ops",
    "scope_time",
]
