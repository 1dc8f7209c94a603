"""The program's spans in a trace (``host_spans``): their reductions on a
synthetic trace, the idle split, that they leave the accepted readers'
numbers alone, and a round trip through the profiler on the CPU."""
import copy
from pathlib import Path

import jax
import pytest

from benchmarks.chip import harness, host_spans, peaks
from benchmarks.chip import trace as tr
from benchmarks.chip.host_spans import Span
from benchmarks.chip.trace import Event

BENCH = Path(harness.__file__).resolve().parent
DEV = "/device:TPU:0"


def _bench_trace():
    """A 10 s window of two polls.  Poll 1 admits a request (prefill on the
    device over [0.4, 0.8]) and decodes ([1.4, 3.0]); poll 2 decodes
    ([5.4, 7.5])."""
    t = tr.Trace()
    t.ops[DEV] = [Event("fusion.1", 0.4, 0.4), Event("fusion.2", 1.4, 1.6),
                  Event("fusion.3", 5.4, 2.1)]
    t.modules[DEV] = [Event("jit_prefill_step(1)", 0.4, 0.4),
                      Event("jit_serve_step(2)", 1.4, 1.6),
                      Event("jit_serve_step(2)", 5.4, 2.1)]
    t.host = [Event("bench.window", 0.0, 10.0), Event("bench.poll", 0.0, 5.0),
              Event("bench.poll", 5.0, 5.0)]
    return t


def _spans(start, end, name, **args):
    return Span(name, start, end - start, args)


PROGRAM = [
    _spans(0.1, 4.9, "server.poll", tick=0),
    _spans(0.2, 1.0, "server.admit", rid=0, bucket=128, len=57),
    _spans(0.2, 0.4, "server.admit.launch"),
    _spans(0.4, 0.8, "server.admit.wait"),
    _spans(0.8, 1.0, "server.admit.sample"),
    _spans(1.0, 4.0, "server.decode", tick=0, rows=1),
    _spans(1.0, 1.2, "server.decode.inputs"),
    _spans(1.2, 1.5, "server.decode.dispatch"),
    _spans(1.5, 3.0, "server.decode.wait"),
    _spans(3.0, 3.6, "server.decode.fetch"),
    _spans(4.0, 4.5, "server.sample", tick=0, rows=1),
    _spans(5.1, 9.9, "server.poll", tick=1),
    _spans(5.2, 8.2, "server.decode", tick=1, rows=1),
    _spans(5.2, 5.3, "server.decode.inputs"),
    _spans(5.3, 5.5, "server.decode.dispatch"),
    _spans(5.5, 7.5, "server.decode.wait"),
    _spans(7.5, 8.0, "server.decode.fetch"),
    _spans(8.2, 9.0, "server.sample", tick=1, rows=1),
    # after the window: never counted
    _spans(10.5, 11.0, "server.sample", tick=2, rows=1),
    _spans(10.5, 11.0, "server.admit", rid=1, bucket=128, len=9),
]


def _trace():
    t = _bench_trace()
    t.host += copy.deepcopy(PROGRAM)
    return t


@pytest.mark.parametrize("name,want_ms", [
    ("launch_ms", (0.2 + 0.3 + 0.1 + 0.2) / 2 * 1e3),  # per decode tick
    ("logits_fetch_ms", (0.6 + 0.5) / 2 * 1e3),
    ("sample_ms", (0.5 + 0.8) / 2 * 1e3),
    ("admit_host_ms", (0.8 - 0.4) * 1e3),  # the admission less its wait
])
def test_program_span_reductions_on_a_synthetic_trace(name, want_ms):
    fn = getattr(host_spans, name)
    assert fn(_trace(), 0.0, 10.0) == pytest.approx(want_ms)
    # a program that writes no such span: nothing to read
    assert fn(_bench_trace(), 0.0, 10.0) is None


def test_idle_split_names_the_innermost_span():
    split = host_spans.idle_split(_trace(), _bench_trace().ops[DEV], 0, 10)
    want = {"bench.poll": 0.4, "server.poll": 1.5,
            "server.admit.launch": 0.2, "server.admit.sample": 0.2,
            "server.decode.inputs": 0.3, "server.decode.dispatch": 0.3,
            "server.decode.fetch": 1.1, "server.decode": 0.6,
            "server.sample": 1.3}
    assert split == pytest.approx(want)
    assert sum(split.values()) == pytest.approx(10.0 - 4.1)
    rep = host_spans.report(_trace())
    assert rep["polls"] == 2 and rep["decode_ticks"] == 2
    assert rep["admissions"] == 1
    assert rep["idle_under_leaf_pct"] == pytest.approx(100 * 3.4 / 5.9)
    assert rep["device_idle_pct"] == pytest.approx(59.0)
    assert rep["idle_ms_per_poll"]["server.sample"] == pytest.approx(650.0)
    # the longest gaps, [7.5, 10] and [3.0, 5.4], are named at their middle
    assert rep["idle_gaps"][:2] == [["server.sample", pytest.approx(2.5)],
                                    ["server.sample", pytest.approx(2.4)]]
    assert not host_spans.is_leaf("server.decode")
    assert host_spans.is_leaf("server.decode.fetch")
    assert not host_spans.is_leaf("bench.poll")


def _ctx(t):
    cell = harness.load_cell("float-batch")
    polls = [harness.Poll(0.0, 5.0, admitted=[57], ctx_lens=[58]),
             harness.Poll(5.0, 10.0, ctx_lens=[59])]
    ctx = harness.Ctx(cell, harness.weights_for(cell).sizes(cell.config),
                      peaks.peaks_for("TPU v5 lite"), 1.0,
                      harness.Window(0.0, 10.0, 0, 10.0), [], polls)
    ctx.trace = t
    ctx.trace_lo, ctx.trace_hi = tr.window(t)
    ctx.device_plane = DEV
    return ctx


def test_program_spans_leave_the_accepted_readers_unchanged():
    """With the program's spans among the host events, the window and every
    accepted reader read what they read without them; only the idle gaps'
    labels change, to the leaf span."""
    bare, full = _ctx(_bench_trace()), _ctx(_trace())
    assert tr.window(full.trace) == tr.window(bare.trace) == (0.0, 10.0)
    for name in ("device_idle.batch", "decode_step_ms.batch", "mfu.batch"):
        read = harness.load_module(BENCH / "metrics" / f"{name}.py").read
        assert read(full) == read(bare), name
    assert tr.host_label(bare.trace, 3.3) == "bench.poll"
    assert tr.host_label(full.trace, 3.3) == "server.decode.fetch"
    assert tr.host_label(full.trace, 5.25) == "server.decode.inputs"
    assert tr.host_label(full.trace, 4.95) == "bench.poll"


def test_load_reads_program_spans_with_their_args(tmp_path):
    """A profiler session on the CPU: the loader keeps the benchmark's and
    the program's spans, with the program's args."""
    from repro.telemetry import span

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("server.decode", tick=7, rows=3):
                with span("server.decode.fetch"):
                    jax.numpy.ones(4).block_until_ready()
            with span("other.thing"):
                pass
    finally:
        jax.profiler.stop_trace()
    t = host_spans.load(str(tmp_path))
    names = [e.name for e in t.host]
    assert {"bench.window", "server.decode", "server.decode.fetch"} <= \
        set(names) and "other.thing" not in names
    dec = next(e for e in t.host if e.name == "server.decode")
    fetch = next(e for e in t.host if e.name == "server.decode.fetch")
    assert dec.args == {"tick": 7, "rows": 3}
    assert dec.start <= fetch.start and fetch.end <= dec.end
    lo, hi = tr.window(t)
    assert lo <= dec.start and dec.end <= hi
