"""Fleet subsystem tests: virtual-fleet coordinator, merged telemetry,
fleet serving vs the single-host oracle, and straggler shrink + resume.

The device-hungry tests run on a LocalCoordinator virtual fleet of 2 hosts x
4 CPU devices and skip when the process has fewer than 2 devices; the
slow-marked subprocess smoke re-runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the full tier-1
suite exercises the fleet even on a 1-device box (CI's fleet-smoke tier sets
the flag directly).  The elastic-planner and telemetry-merge tests are pure
host-side logic and always run.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro
from repro.fleet import (FleetEngine, FleetServer, LocalCoordinator,
                         fleet_slos, merge_tagged, tagged_snapshot)
from repro.launch.mesh import make_submesh, partition_devices
from repro.runtime.elastic import (plan_for_fleet, plan_mesh,
                                   shrink_after_failure)
from repro.telemetry import Registry, get_registry

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (CI fleet-smoke forces 8 via XLA_FLAGS; the "
           "slow subprocess smoke below covers 1-device runs)")

LENGTHS = (7, 16, 33, 12, 5)  # the ragged schedule the paged-KV tests pin
MAX_NEW = 6


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import get_config, reduce_config

    return reduce_config(get_config("qwen2.5-3b"))


@pytest.fixture(scope="module")
def params(cfg):
    from repro.models.model import init_params

    return init_params(jax.random.key(0), cfg)


# ------------------------------------------------------------ elastic plans
def test_plan_mesh_pod_axis_threshold_boundary():
    """The pod axis splits off at EXACTLY the multi-pod threshold (512)."""
    below = plan_mesh(256, model_parallel=2, base_batch=256)
    assert below.axes == ("data", "model") and below.shape == (128, 2)
    at = plan_mesh(512, model_parallel=2, base_batch=512)
    assert at.axes == ("pod", "data", "model") and at.shape == (2, 128, 2)
    assert at.n_devices == 512


def test_plan_mesh_odd_dp_stays_flat_above_threshold():
    """dp must be even to split a pod axis of 2; odd dp stays 2D even when
    the device count crosses the threshold."""
    plan = plan_mesh(512, model_parallel=512, base_batch=8)
    assert plan.axes == ("data", "model") and plan.shape == (1, 512)
    assert plan.global_batch == 8  # dp=1: per-replica IS the base batch


def test_shrink_preserves_per_replica_batch():
    plan = plan_mesh(16, model_parallel=2, base_batch=64)
    assert plan.shape == (8, 2) and plan.global_batch == 64  # 8 per replica
    shrunk = shrink_after_failure(plan, 4, model_parallel=2)
    assert shrunk.shape == (6, 2) and shrunk.n_devices == 12
    assert shrunk.global_batch == 48  # 6 replicas x the SAME 8 per replica
    assert shrunk.global_batch // 6 == plan.global_batch // 8


def test_plan_mesh_rejects_too_few_devices_for_tp():
    with pytest.raises(ValueError, match="TP"):
        plan_mesh(1, model_parallel=2, base_batch=8)


def test_plan_for_fleet_is_whole_host_sugar():
    assert plan_for_fleet(2, 4, model_parallel=2, base_batch=8) == \
        plan_mesh(8, model_parallel=2, base_batch=8)


# ------------------------------------------------------------- coordinator
def test_partition_devices_is_contiguous_and_checks_divisibility():
    fake = [f"d{i}" for i in range(8)]
    groups = partition_devices(2, devices=fake)
    assert groups == [tuple(fake[:4]), tuple(fake[4:])]
    with pytest.raises(ValueError):
        partition_devices(3, devices=fake)
    with pytest.raises(ValueError):
        partition_devices(0, devices=fake)


@multi_device
def test_local_coordinator_partitions_disjoint_submeshes():
    n = 2
    coord = LocalCoordinator(n)
    hosts = coord.hosts()
    assert [h.index for h in hosts] == list(range(n))
    seen = set()
    for h in hosts:
        assert h.n_devices == len(jax.devices()) // n
        assert set(h.devices).isdisjoint(seen)
        seen |= set(h.devices)
        assert tuple(h.mesh.axis_names) == ("data", "model")
        assert h.mesh.size == h.n_devices
    assert coord.is_controller() and coord.controller == 0
    coord.barrier("test")  # no-op, must not raise
    assert coord.all_gather({0: "x"}) == {0: "x"}


def test_make_submesh_falls_back_to_pure_dp_when_tp_does_not_divide():
    devs = jax.devices()[:1]
    mesh = make_submesh(devs, model_parallel=2)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


# -------------------------------------------------------- telemetry merge
def test_merged_fleet_percentiles_match_single_registry():
    """Acceptance (b): percentiles off the merged per-host registries equal
    a single registry fed the same samples — exact, not averaged."""
    rng = np.random.default_rng(3)
    samples = rng.uniform(5e-4, 2.0, size=200)
    per_host = {0: Registry(), 1: Registry()}
    ref = Registry()
    for i, v in enumerate(samples):
        per_host[i % 2].histogram("server.tpot_s").observe(float(v))
        per_host[i % 2].counter("server.admitted").inc()
        ref.histogram("server.tpot_s").observe(float(v))
        ref.counter("server.admitted").inc()
    merged, by_host = merge_tagged(
        [tagged_snapshot(reg, h) for h, reg in per_host.items()])
    assert sorted(by_host) == [0, 1]  # per-host drill-down survives
    m = merged.snapshot()["histograms"]["server.tpot_s"]
    r = ref.snapshot()["histograms"]["server.tpot_s"]
    for q in ("p50", "p95", "p99"):
        assert m[q] == r[q], f"{q}: fleet {m[q]} != as-if-one {r[q]}"
    assert merged.snapshot()["counters"]["server.admitted"] == 200
    slos = fleet_slos(per_host)
    assert slos["n_hosts"] == 2
    assert slos["tpot_ms"] == round(r["p50"] * 1e3, 3)


# ----------------------------------------------- fleet serving vs oracle
@multi_device
def test_fleet_serve_is_bit_identical_to_single_host_oracle(cfg, params):
    """Acceptance (a): mixed-length decode through a 2-host virtual fleet
    produces bit-identical token streams to one Server fed the same
    requests, and steady-state waves stay trace-free on every host."""
    from repro.launch.engine import Engine
    from repro.launch.server import Request, Server

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in LENGTHS]
    kw = dict(slots=3, block_size=8, buckets=(16, 48),
              max_seq_len=48 + MAX_NEW)

    coord = LocalCoordinator(2)
    fleet = FleetEngine(coord, noise_seed=0)
    fsrv = FleetServer(cfg, params, fleet, **kw)
    fleet_handles = [fsrv.submit(Request(p, max_new_tokens=MAX_NEW))
                     for p in prompts]
    fsrv.drain()
    assert {h.host for h in fleet_handles} == {0, 1}, \
        "round-robin must actually use both hosts"
    for h, srv in fsrv.servers.items():  # each host computes on its own
        devs = set(coord.hosts()[h].devices)
        for leaf in jax.tree.leaves((srv.params, srv.cache)):
            assert leaf.devices() <= devs, (h, leaf.devices())

    # an odd wave size over 2 hosts alternates which host gets which
    # buckets, so warmup takes n_hosts waves; wave 3 must retrace nowhere
    wave2 = [fsrv.submit(Request(p, max_new_tokens=MAX_NEW))
             for p in prompts]
    fsrv.drain()
    warm = dict(fleet.traces_by_host())
    wave3 = [fsrv.submit(Request(p, max_new_tokens=MAX_NEW))
             for p in prompts]
    fsrv.drain()
    assert fleet.traces_by_host() == warm, \
        f"steady-state retrace: {warm} -> {fleet.traces_by_host()}"

    # oracle: ONE Server on a mesh of host 0's shape, same noise seed
    oracle = Engine(mesh=coord.hosts()[0].mesh, noise_seed=0,
                    registry=Registry())
    with oracle.activate():
        osrv = Server(cfg, params, engine=oracle, **kw)
        oracle_handles = [osrv.submit(Request(p, max_new_tokens=MAX_NEW))
                          for p in prompts]
        osrv.drain()

    for wave in (fleet_handles, wave2, wave3):
        for fh, oh in zip(wave, oracle_handles):
            assert fh.tokens == oh.tokens, \
                f"req{oh.rid}: fleet {fh.tokens} != oracle {oh.tokens}"

    # merged SLOs read as-if-one-registry over BOTH hosts' traffic
    slos = fsrv.slos()
    assert slos["n_hosts"] == 2
    assert slos["ttft_ms"] > 0 and slos["tpot_ms"] > 0
    merged = fleet.merged_registry().snapshot()
    assert merged["counters"]["server.admitted"] == 3 * len(prompts)
    assert merged["histograms"]["server.ttft_s"]["count"] == 3 * len(prompts)


# --------------------------------------- straggler -> shrink -> resume
@multi_device
def test_fleet_straggler_shrinks_plan_and_resumes_from_checkpoint(
        tmp_path, monkeypatch):
    """Acceptance (c): an injected slow host is flagged from per-host
    entries, the plan shrinks in whole-host units with per-replica batch
    preserved, and the loop resumes from the latest checkpoint with no
    further retraces on the survivors."""
    from repro.configs import get_config, reduce_config
    from repro.fleet import fleet_engine
    from repro.launch.train import train_fleet

    # The loop's clock stands still, so each host's observed time is the
    # injected skew alone: compile times and machine load cannot sway the
    # monitor's verdict.
    monkeypatch.setattr(fleet_engine, "clock", lambda: 0.0)
    tcfg = reduce_config(get_config("imc-paper-110m"))
    resumes0 = get_registry().snapshot()["counters"].get("fault.resumes", 0)
    (params, _), hist, fleet, loop = train_fleet(
        tcfg, n_hosts=2, steps=8, global_batch=4, seq_len=32,
        ckpt_root=str(tmp_path), ckpt_every=2, seed=0,
        # host 1 turns into a straggler from step 3 on (observed-time skew
        # only: no real sleeping)
        delay=lambda h, s: 5.0 if (h == 1 and s >= 3) else 0.0)

    # flagged from per-host entries -> removed from fleet AND monitor
    assert fleet.removed == [1] and fleet.active_hosts() == [0]
    assert 1 not in fleet.monitor.hosts
    assert get_registry().gauge("straggler.ewma_s.host1").value == 0.0

    # the shrink re-planned in whole-host device units, per-replica batch
    # preserved (at 8 devices: dp=4 @ 1/replica -> 4 devices, dp=2)
    assert len(loop.shrinks) == 1
    shrunk, per_host = loop.shrinks[0], fleet.host(0).n_devices
    assert shrunk is loop.plan and shrunk.n_devices == per_host
    mp = 2 if per_host % 2 == 0 else 1
    orig = plan_for_fleet(2, per_host, model_parallel=mp, base_batch=4)
    assert shrunk == shrink_after_failure(orig, per_host, model_parallel=mp)
    assert orig.global_batch // (orig.n_devices // mp) == \
        shrunk.global_batch // (shrunk.n_devices // mp), \
        "per-replica batch must survive the shrink"

    # resumed from the latest committed checkpoint, replaying some steps
    resumes = get_registry().snapshot()["counters"]["fault.resumes"]
    assert resumes == resumes0 + 1
    assert len(hist) > 8, "resume must replay post-checkpoint steps"

    # survivor replays from its compiled-step cache: warmup traces only
    # (one numpy-input trace + one committed-replica trace), none added by
    # the resume
    assert fleet.traces_by_host()[0] == 2

    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(params))


@multi_device
def test_fleet_engine_observe_step_times_feeds_monitor_once():
    """record_step must see the FULL per-host dict once per step — per-host
    calls would multiply the strike cadence by the fleet size."""
    from repro.runtime.straggler import StragglerConfig

    fleet = FleetEngine(LocalCoordinator(2),
                        straggler_cfg=StragglerConfig(patience=3))
    for _ in range(3):
        flagged = fleet.observe_step_times({0: 0.1, 1: 0.9})
    assert flagged == [1]
    assert fleet.monitor.hosts[1].strikes == 3, \
        "strikes must advance once per fleet step, not once per host"


# ------------------------------------------------------- subprocess smoke
@pytest.mark.slow
def test_fleet_suite_under_forced_device_count():
    """1-device boxes still exercise the virtual fleet: re-run this file in
    a subprocess with 8 forced CPU devices (2 hosts x 4 devices)."""
    if os.environ.get("FLEET_SUBPROCESS") == "1":
        pytest.skip("already inside the forced-device subprocess")
    if len(jax.devices()) >= 2:
        pytest.skip("devices already forced; fleet tests ran in-process")
    src = os.path.dirname(list(repro.__path__)[0])  # namespace pkg: no __file__
    env = dict(
        os.environ, FLEET_SUBPROCESS="1", JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            p for p in (src, os.environ.get("PYTHONPATH")) if p),
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=8").strip())
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"fleet subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    assert "passed" in proc.stdout, proc.stdout
