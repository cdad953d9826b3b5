"""Unified telemetry tier: structured tracing, metrics, flight recorder.

Zero-dependency (stdlib-only core; jax touched lazily: kernel names as
``jax.named_scope``, always open, and host spans as profiler
annotations where jax is loaded), off by default, threaded through every
layer of the stack:

  state.py    master switches (``REPRO_OBS=1`` env or ``obs.enable()``)
  trace.py    span/event tracer -> Chrome-trace/Perfetto JSON; renders
              scheduler Traces as per-worker tracks (compute, uplink,
              downlink, gossip, faults) with exact ledger accounting
  metrics.py  counters/gauges/histograms with named scopes (wire bytes
              by codec tier, staleness distributions, retry/drop/dup/
              quorum counts, per-bucket quant range)
  flight.py   bounded ring buffer of recent events, dumped to disk on
              fault-ledger validation failure or uncaught scheduler
              exception; jax.named_scope names for the Pallas kernels
  runinfo.py  run_id (git SHA + seed) + schema version stamped on every
              BENCH row, timeline, and flight dump
  export.py   ``python -m repro.obs.export trace`` — openable timeline

Instrumentation contract: every recording call site guards on
``obs.enabled(...)`` (one dict lookup when off); the kernel scopes and
profiler annotations, which record nothing themselves, are not guarded.
Values inside ``jit`` are never recorded at trace time — they ride out
as auxiliary outputs and are observed on the host
(``metrics.observe_array`` skips tracers).
"""
from repro.obs.flight import (kernel_scope, record as flight_record,
                              recorder as flight_recorder)
from repro.obs.metrics import (counter, gauge, histogram, observe_array,
                               registry as metrics_registry)
from repro.obs.runinfo import SCHEMA_VERSION, run_id, stamp_rows
from repro.obs.state import disable, enable, enabled
from repro.obs.trace import span, timeline_from_trace, tracer

__all__ = [
    "SCHEMA_VERSION", "counter", "disable", "enable", "enabled",
    "flight_record", "flight_recorder", "gauge", "histogram",
    "kernel_scope", "metrics_registry", "observe_array", "run_id",
    "span", "stamp_rows", "timeline_from_trace", "tracer",
]
