"""Telemetry subsystem tests.

Registry mechanics (histogram percentile accuracy, disabled-mode no-ops and
their cost), spans on the profiler's clock (what a Server writes into a
profiler trace, and the cost of a span with no profiler session),
snapshot/markdown exporters, the straggler monitor's true-median regression,
and the serving SLO integration: TTFT/TPOT/queue wait/occupancy recorded on
the mixed-length ragged schedule WITHOUT breaking the
zero-steady-state-retrace guarantee.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.launch.engine import Engine
from repro.launch.server import Request, Server
from repro.models.model import init_params
from repro.runtime.straggler import StragglerConfig, StragglerMonitor, _median
from repro.telemetry import (Registry, clock, get_registry, serving_slos,
                             snapshot, span, to_markdown)

LENGTHS = (7, 16, 33, 12, 5)  # same ragged schedule the paged-KV tests pin
MAX_NEW = 6


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("qwen2.5-3b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.key(0), cfg)


# ---------------------------------------------------------------- registry
def test_histogram_percentiles_track_known_distribution():
    reg = Registry()
    h = reg.histogram("t")
    vals = np.arange(1, 1001) / 1000.0  # uniform 1 ms .. 1 s
    for v in vals:
        h.observe(float(v))
    assert h.count == 1000 and h.min == 0.001 and h.max == 1.0
    for q, true in ((50, 0.5), (95, 0.95), (99, 0.99)):
        est = h.percentile(q)
        assert abs(est - true) / true < 0.15, \
            f"p{q}: {est} vs true {true} (log-bucket error bound exceeded)"
    s = h.summary()
    assert s["count"] == 1000 and abs(s["mean"] - vals.mean()) < 1e-9


def test_histogram_single_sample_is_exact_and_outliers_clamp():
    reg = Registry()
    h = reg.histogram("t")
    h.observe(0.0123)
    # min/max clamping makes the covering bucket degenerate -> exact
    assert h.percentile(50) == pytest.approx(0.0123)
    h2 = reg.histogram("wild")
    h2.observe(1e-9)  # below lo
    h2.observe(1e6)  # above hi
    assert h2.count == 2 and h2.min == 1e-9 and h2.max == 1e6
    assert reg.histogram("empty").percentile(50) is None


def test_registry_names_are_typed_and_stable():
    reg = Registry()
    c = reg.counter("x")
    c.inc(3)
    assert reg.counter("x") is c and c.value == 3
    g = reg.gauge("depth")
    g.set(2.0)
    g.set(1.0)
    assert g.value == 1.0 and g.hwm == 2.0
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")
    snap = reg.snapshot()
    assert snap["counters"]["x"] == 3
    assert snap["gauges"]["depth"] == {"value": 1.0, "hwm": 2.0}


def test_disabled_mode_records_nothing():
    reg = Registry()
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc()
    h.observe(0.5)
    with reg.disabled():
        c.inc(100)
        g.set(9.0)
        h.observe(0.5)
    assert reg.enabled  # context restores the flag
    assert c.value == 1 and g.value == 0.0 and h.count == 1
    off = Registry(enabled=False)
    off.counter("n").inc()
    assert off.counter("n").value == 0


def test_reset_zeroes_in_place_without_orphaning_handles():
    """Components cache metric handles at construction; reset() must zero
    them, not replace them (or post-reset records vanish from snapshots)."""
    reg = Registry()
    c, h = reg.counter("c"), reg.histogram("h")
    c.inc(5)
    h.observe(0.5)
    reg.reset()
    assert reg.counter("c") is c and c.value == 0
    assert h.count == 0 and h.percentile(50) is None
    c.inc()
    h.observe(0.25)  # the cached handles still feed the snapshot
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 1
    assert snap["histograms"]["h"]["count"] == 1


def test_disabled_record_path_is_cheap_enough_for_decode_loops():
    """Acceptance bound: per-record cost with telemetry off stays under 2%
    of a (very fast) 1 ms decode step."""
    reg = Registry(enabled=False)
    h, c = reg.histogram("h"), reg.counter("c")
    n = 100_000
    t0 = clock()
    for _ in range(n):
        h.observe(1e-3)
        c.inc()
    per_record = (clock() - t0) / (2 * n)
    assert per_record < 0.02 * 1e-3, \
        f"disabled record path costs {per_record * 1e9:.0f} ns"


# ------------------------------------------------------------------- spans
SPAN_TREE = {  # span -> the span it nests in
    "server.admit": "server.poll",
    "server.admit.launch": "server.admit",
    "server.admit.wait": "server.admit",
    "server.admit.sample": "server.admit",
    "server.decode": "server.poll",
    "server.decode.inputs": "server.decode",
    "server.decode.dispatch": "server.decode",
    "server.decode.wait": "server.decode",
    "server.decode.fetch": "server.decode",
    "server.sample": "server.poll",
}


def _host_spans(trace_dir):
    """Every ``server.*`` / ``engine.*`` event of the trace's host planes:
    (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("server.", "engine.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


def test_server_spans_share_the_profiler_clock(cfg, params, tmp_path):
    """A Server's spans land in the profiler's own trace: every span of the
    poll tree, each inside its parent, with its args as stats."""
    reg = Registry()
    eng = Engine(registry=reg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with eng.activate():
            server = Server(cfg, params, engine=eng, slots=2, block_size=8,
                            buckets=(16,), max_seq_len=16 + 4)
            for n in (7, 12):
                server.submit(Request(np.arange(n, dtype=np.int32) % 50,
                                      max_new_tokens=4))
            server.drain()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert set(SPAN_TREE) | {"server.poll", "engine.trace"} <= names, names
    polls = [s for s in spans if s[0] == "server.poll"]
    for name, start, end, _ in spans:
        if name not in SPAN_TREE:
            continue
        parents = [p for p in spans if p[0] == SPAN_TREE[name]
                   and p[1] <= start and end <= p[2]]
        assert parents, f"{name} at {start} lies in no {SPAN_TREE[name]}"
        if name.startswith("server.decode."):  # ... and that in a poll
            d = parents[0]
            assert any(p[1] <= d[1] and d[2] <= p[2] for p in polls)
    admits = [s[3] for s in spans if s[0] == "server.admit"]
    assert sorted(a["rid"] for a in admits) == [0, 1]
    assert {a["bucket"] for a in admits} == {16}
    assert sorted(a["len"] for a in admits) == [7, 12]
    ticks = [s[3]["tick"] for s in spans if s[0] == "server.decode"]
    assert ticks == list(range(len(ticks))) and ticks
    assert {s[3]["kind"] for s in spans if s[0] == "engine.trace"} >= \
        {"prefill", "admit", "decode"}


def test_span_without_a_profiler_session_is_cheap_and_records_nothing(
        tmp_path):
    """With no profiler session a span is one check: well under 2 us, and
    nothing of it reaches a trace started afterwards."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    n = 100_000
    t0 = clock()
    for i in range(n):
        with span("test.quiet", tick=i, rows=8):
            pass
    per_span = (clock() - t0) / n
    assert per_span < 2e-6, f"span with the profiler off: {per_span * 1e6} us"
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("engine.marker"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [s[0] for s in _host_spans(str(tmp_path))] == ["engine.marker"]


# --------------------------------------------------------------- exporters
def test_markdown_lists_counters_gauges_and_histograms():
    reg = Registry()
    reg.counter("server.admitted").inc(4)
    reg.gauge("server.queue_depth").set(2)
    reg.histogram("server.ttft_s").observe(0.05)
    md = to_markdown(registry=reg)
    assert "server.admitted" in md and "server.ttft_s" in md
    assert "server.queue_depth" in md and "| 50 |" in md


def test_serving_slos_are_none_without_a_server():
    slos = serving_slos(Registry())
    assert slos == {"ttft_ms": None, "tpot_ms": None, "queue_ms": None,
                    "occupancy_peak": None}


# ----------------------------------------------------------- registry merge
def test_merge_histogram_percentiles_are_exact_bucket_addition():
    """Fleet percentiles must equal percentiles of the union of samples —
    bucket counts add exactly; nothing is approximated at merge time."""
    rng = np.random.default_rng(7)
    samples = rng.uniform(1e-4, 5.0, size=240)
    ref, parts = Registry(), [Registry() for _ in range(3)]
    for i, v in enumerate(samples):
        ref.histogram("step_s").observe(float(v))
        parts[i % 3].histogram("step_s").observe(float(v))
    merged = Registry.merge(*[p.snapshot() for p in parts])
    m = merged.snapshot()["histograms"]["step_s"]
    r = ref.snapshot()["histograms"]["step_s"]
    assert m["count"] == r["count"] == 240
    for q in ("p50", "p95", "p99"):
        assert m[q] == r[q], f"{q}: merged {m[q]} != as-if-one {r[q]}"
    assert m["min"] == r["min"] and m["max"] == r["max"]
    assert m["mean"] == pytest.approx(r["mean"])
    assert m["buckets"] == r["buckets"]


def test_merge_counters_sum_and_gauge_high_water_is_max():
    a, b = Registry(), Registry()
    a.counter("served").inc(3)
    b.counter("served").inc(5)
    a.gauge("depth").set(4.0)
    a.gauge("depth").set(1.0)  # a: value 1.0, hwm 4.0
    b.gauge("depth").set(2.5)  # b: value 2.5, hwm 2.5
    snap = Registry.merge(a.snapshot(), b.snapshot()).snapshot()
    assert snap["counters"]["served"] == 8
    assert snap["gauges"]["depth"] == {"value": 3.5, "hwm": 4.0}


def test_merge_identity_under_single_snapshot():
    reg = Registry()
    reg.counter("c").inc(4)
    reg.gauge("g").set(1.5)
    for v in (0.001, 0.01, 0.1, 7.0):
        reg.histogram("h").observe(v)
    reg.histogram("empty")  # zero-count histograms survive the round trip
    merged = Registry.merge(reg.snapshot())
    assert merged.snapshot() == reg.snapshot()


def test_merge_rejects_mismatched_histogram_layouts():
    a, b = Registry(), Registry()
    a.histogram("h").observe(0.5)
    b.histogram("h", lo=1e-3, hi=10.0).observe(0.5)
    with pytest.raises(ValueError, match="layout"):
        Registry.merge(a.snapshot(), b.snapshot())


# ------------------------------------------------- straggler true median
def test_straggler_median_is_true_median():
    assert _median([0.1, 0.4]) == pytest.approx(0.25)
    assert _median([0.1, 0.1, 0.2, 0.3]) == pytest.approx(0.15)
    assert _median([0.3, 0.1, 0.2]) == 0.2


@pytest.mark.parametrize("times,slow", [
    ({0: 0.1, 1: 0.4}, 1),  # 2 hosts: upper-middle "median" (0.4) hides it
    ({0: 0.1, 1: 0.1, 2: 0.2, 3: 0.3}, 3),  # 4 hosts: 0.2 vs true 0.15
])
def test_straggler_flags_slow_host_in_even_fleets(times, slow):
    """Regression: with the old upper-middle median the threshold lands at
    or above the straggler's own EWMA and it is never flagged."""
    mon = StragglerMonitor(cfg=StragglerConfig(threshold=1.5, patience=3))
    for _ in range(mon.cfg.patience + 2):
        flagged = mon.record_step(dict(times))
    assert mon.swaps == [slow] and flagged == []
    old_median = sorted(times.values())[len(times) // 2]
    assert times[slow] <= mon.cfg.threshold * old_median, \
        "test vector no longer distinguishes true median from upper-middle"


def test_replace_host_drops_stats_and_reseeds_from_first_sample():
    """Regression: replace_host used to reset to HostStats(ewma_time=0.0),
    which (a) biased the fleet median low until the EWMA warmed back up and
    (b) left the per-host EWMA gauge showing the dead host's last estimate.
    The entry must be DROPPED: the swapped-in host re-seeds from its first
    post-swap sample and the gauge reads 0 until then."""
    mon = StragglerMonitor(cfg=StragglerConfig(threshold=1.5, patience=3))
    times = {0: 0.1, 1: 0.1, 2: 0.12, 3: 0.5}
    for _ in range(mon.cfg.patience + 2):
        mon.record_step(dict(times))
    assert mon.swaps == [3]
    mon.replace_host(3)
    assert 3 not in mon.hosts, "entry must be dropped, not zeroed"
    assert get_registry().gauge("straggler.ewma_s.host3").value == 0.0
    # until the spare reports, the median covers the survivors only — a
    # zeroed entry would drag it to 0.055 and mask host 2 as a "straggler"
    assert _median([s.ewma_time for s in mon.hosts.values()]) == \
        pytest.approx(0.1)
    # first post-swap sample seeds the EWMA at the sample itself, not at
    # ewma * 0 + (1 - ewma) * sample
    mon.record_step({**times, 3: 0.1})
    st = mon.hosts[3]
    assert st.ewma_time == pytest.approx(0.1)
    assert st.strikes == 0 and not st.flagged
    # a healthy replacement never re-flags (and nobody else does either)
    for _ in range(mon.cfg.patience + 2):
        mon.record_step({**times, 3: 0.1})
    assert mon.swaps == [3]


# ------------------------------------------- serving SLOs, end to end
def test_server_slos_on_ragged_schedule_without_retraces(cfg, params):
    reg = Registry()
    eng = Engine(registry=reg)
    assert reg.enabled
    with eng.activate():
        server = Server(cfg, params, engine=eng, slots=2, block_size=8,
                        buckets=(16, 48), max_seq_len=48 + MAX_NEW)
        prompts = [np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=n).astype(np.int32) for n in LENGTHS]
        for p in prompts:
            server.submit(Request(p, max_new_tokens=MAX_NEW))
        server.drain()
        warm = eng.stats.traces
        for p in reversed(prompts):
            server.submit(Request(p, max_new_tokens=MAX_NEW))
        handles = server.drain()
    assert all(h.done for h in handles)
    # telemetry-on steady state stays data-only (the hard constraint)
    assert eng.stats.traces == warm, \
        "telemetry recording must not retrace the compiled steps"

    n = 2 * len(LENGTHS)
    snap = snapshot(reg)
    assert snap["counters"]["server.admitted"] == n
    assert snap["histograms"]["server.ttft_s"]["count"] == n
    assert snap["histograms"]["server.tpot_s"]["count"] == n
    occ = snap["gauges"]["server.block_occupancy"]
    assert 0.0 < occ["hwm"] <= 1.0
    assert occ["value"] == 0.0, "drained pool must read empty"
    assert snap["counters"]["server.decode_tokens"] == n * (MAX_NEW - 1)
    assert snap["histograms"]["server.queue_wait_s"]["count"] == n

    slos = serving_slos(reg)
    assert slos["ttft_ms"] > 0 and slos["tpot_ms"] > 0
    assert slos["queue_ms"] is not None and slos["queue_ms"] <= slos["ttft_ms"]
    assert slos["occupancy_peak"] == round(occ["hwm"], 3)
    # engine-side instrumentation rode along on the same registry
    assert snap["counters"]["engine.compiles"] >= 3


def test_global_registry_is_the_default_feed():
    eng = Engine()
    assert eng.registry is get_registry()
