"""Observability for the IMC engine/server stack.

Three small modules, one import surface:

  registry — named Counter/Gauge/Histogram (fixed log-spaced buckets,
             p50/p95/p99 summaries), a process-global Registry, and a
             disabled mode whose record path is a no-op branch.
  spans    — ``clock()`` (the runtime's one monotonic wall-clock source) and
             ``span()``: host regions written into the JAX profiler's trace,
             on the device's clock, only while a profiler session runs.
  export   — explicit JSON / markdown snapshots and the serving SLO row.

The hard rule every instrumentation site obeys: **recording is host-side
only** — no jax arrays, no device reads, no trace inputs — so telemetry can
never add a host<->device sync or a retrace to a compiled step.  The
zero-steady-state-retrace serving guarantees hold with telemetry enabled
(pinned by tests/test_telemetry.py).
"""
from repro.telemetry.export import (serving_slos, snapshot, to_markdown,
                                    write_json)
from repro.telemetry.registry import (Counter, Gauge, Histogram, Registry,
                                      get_registry, set_enabled)
from repro.telemetry.spans import clock, span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry",
    "set_enabled", "clock", "span", "serving_slos", "snapshot",
    "to_markdown", "write_json",
]
