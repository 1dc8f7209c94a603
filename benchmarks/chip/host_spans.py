"""The program's own spans in a profiler trace, and what they say of the
device's idle time.

``repro``'s ``Server`` and ``Engine`` write ``server.*`` and ``engine.*``
spans into the JAX profiler's trace (``repro.telemetry.span``), on the
device's clock.  :func:`load` reads a trace as :func:`trace.load` does and
adds those spans, with their args, to the host events, so
:func:`trace.host_label` names an idle gap by the innermost program span.
The reductions below read them over a window:

  ``launch_ms``        mean per decode tick of ``server.decode.inputs`` +
                       ``server.decode.dispatch``
  ``logits_fetch_ms``  mean ``server.decode.fetch``
  ``sample_ms``        mean ``server.sample``
  ``admit_host_ms``    mean per admission of ``server.admit`` less its
                       ``server.admit.wait``
  :func:`idle_split`   the device's idle seconds by the innermost host span
                       covering them

Each counts the spans that start inside the window, and gives ``None``
where there are none (a program that writes no such span).  Of a trace
kept by ``run.py --trace 1 --trace-dir <dir>``::

    python3 -c "import sys; sys.path[:0] = ['.', 'src']; \\
        from benchmarks.chip.host_spans import main; main(sys.argv[1:])" <dir>

prints them as one JSON line.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchmarks.chip import trace as trace_mod
from benchmarks.chip.trace import Event, Trace

PREFIXES = ("server.", "engine.")
# spans whose own time is not a leaf: their children name the work
PARENTS = ("server.poll", "server.decode", "server.admit")


@dataclass
class Span(Event):
    args: Dict[str, object] = field(default_factory=dict)


def program_spans(trace_dir: str) -> List[Span]:
    """Every ``server.*`` / ``engine.*`` event on the host planes of the
    newest ``.xplane.pb`` under ``trace_dir``, sorted by start."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    out.append(Span(ev.name, ev.start_ns * 1e-9,
                                    ev.duration_ns * 1e-9,
                                    {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda e: e.start)


def load(trace_dir: str) -> Trace:
    """:func:`trace.load`, with the program's spans among the host events."""
    tr = trace_mod.load(trace_dir)
    tr.host.extend(program_spans(trace_dir))
    return tr


# ------------------------------------------------------------- reductions
def starting_in(tr: Trace, name: str, lo: float, hi: float) -> List[Event]:
    return [e for e in tr.host if e.name == name and lo <= e.start < hi]


def _inside(inner: List[Event], outer: Event) -> List[Event]:
    return [e for e in inner
            if outer.start <= e.start and e.end <= outer.end]


def _mean_ms(total_s: float, n: int) -> Optional[float]:
    return None if n == 0 else 1e3 * total_s / n


def launch_ms(tr: Trace, lo: float, hi: float) -> Optional[float]:
    ticks = starting_in(tr, "server.decode", lo, hi)
    parts = starting_in(tr, "server.decode.inputs", lo, hi) + \
        starting_in(tr, "server.decode.dispatch", lo, hi)
    return _mean_ms(sum(e.dur for e in parts), len(ticks))


def logits_fetch_ms(tr: Trace, lo: float, hi: float) -> Optional[float]:
    ev = starting_in(tr, "server.decode.fetch", lo, hi)
    return _mean_ms(sum(e.dur for e in ev), len(ev))


def sample_ms(tr: Trace, lo: float, hi: float) -> Optional[float]:
    ev = starting_in(tr, "server.sample", lo, hi)
    return _mean_ms(sum(e.dur for e in ev), len(ev))


def admit_host_ms(tr: Trace, lo: float, hi: float) -> Optional[float]:
    admits = starting_in(tr, "server.admit", lo, hi)
    waits = [e for e in tr.host if e.name == "server.admit.wait"]
    host = sum(a.dur - sum(w.dur for w in _inside(waits, a)) for a in admits)
    return _mean_ms(host, len(admits))


def is_leaf(name: str) -> bool:
    """A program span whose own time names the work: not a parent's self
    time, not the benchmark's annotation, not uncovered time."""
    return name.startswith(PREFIXES) and name not in PARENTS


def idle_split(tr: Trace, ops: List[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Seconds of [lo, hi] in which no op in ``ops`` ran, by the innermost
    host span covering each instant (``bench.idle`` where none does)."""
    spans = sorted((e for e in tr.host if e.name != "bench.window"
                    and e.end > lo and e.start < hi), key=lambda e: e.start)
    starts = [e.start for e in spans]
    longest = max((e.dur for e in spans), default=0.0)
    out: Dict[str, float] = {}
    for a, b in trace_mod.gaps(ops, lo, hi):
        i = bisect.bisect_left(starts, a - longest)
        near = [e for e in spans[i:bisect.bisect_right(starts, b)]
                if e.end > a]
        cuts = sorted({a, b} | {t for e in near for t in (e.start, e.end)
                                if a < t < b})
        for s, t in zip(cuts, cuts[1:]):
            mid = (s + t) / 2
            cover = [e for e in near if e.start <= mid <= e.end]
            name = min(cover, key=lambda e: e.dur).name if cover \
                else "bench.idle"
            out[name] = out.get(name, 0.0) + (t - s)
    return out


def report(tr: Trace) -> dict:
    """The reductions over the trace's window, the idle split per poll and
    the share of the idle time under a leaf span."""
    lo, hi = trace_mod.window(tr)
    dev = tr.devices[0]
    ops = tr.ops[dev]
    polls = len(starting_in(tr, "server.poll", lo, hi))
    split = idle_split(tr, ops, lo, hi)
    idle = sum(split.values())
    leaf = sum(v for k, v in split.items() if is_leaf(k))
    per_poll = {k: 1e3 * v / max(polls, 1) for k, v in
                sorted(split.items(), key=lambda kv: -kv[1])}
    return {
        "window_s": hi - lo, "polls": polls,
        "decode_ticks": len(starting_in(tr, "server.decode", lo, hi)),
        "admissions": len(starting_in(tr, "server.admit", lo, hi)),
        "device_idle_pct": 100.0 * idle / (hi - lo),
        "launch_ms": launch_ms(tr, lo, hi),
        "logits_fetch_ms": logits_fetch_ms(tr, lo, hi),
        "sample_ms": sample_ms(tr, lo, hi),
        "admit_host_ms": admit_host_ms(tr, lo, hi),
        "idle_ms_per_poll": per_poll,
        "idle_under_leaf_pct": 100.0 * leaf / idle if idle else None,
        "idle_gaps": [[trace_mod.host_label(tr, (a + b) / 2), b - a]
                      for a, b in sorted(trace_mod.gaps(ops, lo, hi),
                                         key=lambda g: g[0] - g[1])[:10]],
    }


def main(argv):
    print(json.dumps(report(load(argv[0]))), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
