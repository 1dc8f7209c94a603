"""The runtime's one wall clock, and host spans on the profiler's clock.

``clock()`` is the ONE wall-clock source for the whole runtime
(``time.perf_counter``: monotonic, high-resolution, immune to NTP steps —
``time.time`` is neither).  Every timing site in the Engine/Server/runtime
loops goes through it, so durations are comparable across modules.

``span(name, **args)`` marks a host region in the JAX profiler's trace: it
is a ``jax.profiler.TraceAnnotation``, so the region lands in the same
``.xplane.pb`` as the device's operations, on the same clock, and a gap in
the device's work can be read against what the host was doing meanwhile.
Spans nest by containment on the host thread's timeline; ``args`` become
the event's stats (keep them to ints and short names)::

    with span("server.decode", tick=3, rows=8):
        ...

A span records only while a profiler session is active
(``jax.profiler.start_trace`` / ``jax.profiler.trace``).  Otherwise it is
one check and a shared null context — no args are formatted, nothing is
kept — so hot decode loops keep their spans.  Spans do not follow
:attr:`Registry.enabled`; counters and histograms do.  The profiler writes
the Perfetto view (``jax.profiler.trace(dir, create_perfetto_trace=True)``).
"""
from __future__ import annotations

import contextlib
import time

from jax.profiler import TraceAnnotation

__all__ = ["clock", "span"]

clock = time.perf_counter

_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """``with span("server.admit", rid=4, bucket=128, len=57): ...``"""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return _OFF
