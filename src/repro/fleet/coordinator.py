"""Coordinator: who am I in the fleet, and how do hosts rendezvous.

The paper's 8x8 macro is one tile; one Engine on one host is the serving
analogue.  Fleet scale means many identical Engines under one controller —
this module is that controller's substrate.  Two implementations of one
small :class:`Coordinator` protocol:

  * :class:`DistributedCoordinator` — a thin wrapper over
    ``jax.distributed.initialize`` for REAL multi-process fleets: process
    index/count, a barrier (``sync_global_devices``), a host-0 controller
    election, and an object all-gather (JSON over a padded uint8
    ``process_allgather``) used to ship per-host telemetry snapshots to the
    controller.  Each process drives exactly one :class:`FleetHost` whose
    mesh spans the *global* device set (normal SPMD).
  * :class:`LocalCoordinator` — an in-process **virtual fleet**: the local
    devices are partitioned into ``n_hosts`` contiguous groups, each with
    its own (data, model) sub-mesh.  One Python process drives every
    virtual host, so the multi-host control flow — per-host step times into
    the straggler monitor, telemetry merge on the controller, shrink/resume
    after a flagged host — is exercisable in CI on CPU
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) without
    spawning processes.

Both sides agree on the contract the fleet engine/server layers consume:
``hosts()`` (the hosts THIS process drives), ``process_count``,
``controller`` / ``is_controller``, ``barrier(tag)``, and
``all_gather(per_host)`` returning the full fleet view on every caller.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.launch.mesh import make_mesh, make_submesh, partition_devices


@dataclass(frozen=True)
class FleetHost:
    """One host's identity: its fleet-wide index and its mesh/devices."""

    index: int
    devices: Tuple[Any, ...]
    mesh: Any = field(hash=False, compare=False)

    @property
    def n_devices(self) -> int:
        return len(self.devices)


class Coordinator:
    """Protocol (duck-typed; both implementations subclass for isinstance
    convenience, but the fleet layers only rely on the methods below)."""

    def hosts(self) -> List[FleetHost]:
        """The hosts this process drives (1 for distributed, N for local)."""
        raise NotImplementedError

    @property
    def process_count(self) -> int:
        raise NotImplementedError

    @property
    def controller(self) -> int:
        """Host index elected controller (host 0 by convention)."""
        return 0

    def is_controller(self) -> bool:
        """Does this process drive the controller host?"""
        return any(h.index == self.controller for h in self.hosts())

    def barrier(self, tag: str) -> None:
        raise NotImplementedError

    def all_gather(self, per_host: Dict[int, Any]) -> Dict[int, Any]:
        """Combine each process's {host_index: obj} into the fleet view."""
        raise NotImplementedError


class LocalCoordinator(Coordinator):
    """In-process virtual fleet: N sub-meshes over the local devices.

    ``LocalCoordinator(2)`` with 8 forced CPU devices yields two virtual
    hosts of 4 devices each, meshes ``(2, 2)`` over disjoint device groups.
    Every cross-host primitive is trivial (one process, synchronous), which
    is the point: the *control flow* above it — per-host Engines, merged
    registries, straggler shrink — is identical to the distributed path.
    """

    def __init__(self, n_hosts: int, *, devices: Optional[Sequence] = None,
                 model_parallel: int = 2):
        groups = partition_devices(n_hosts, devices)
        self._hosts = [
            FleetHost(i, devs, make_submesh(devs, model_parallel))
            for i, devs in enumerate(groups)]

    def hosts(self) -> List[FleetHost]:
        return list(self._hosts)

    @property
    def process_count(self) -> int:
        return 1

    def barrier(self, tag: str) -> None:  # one process: always in sync
        return None

    def all_gather(self, per_host: Dict[int, Any]) -> Dict[int, Any]:
        return dict(per_host)

    def drop_host(self, index: int) -> FleetHost:
        """Remove a virtual host from the fleet (straggler shrink)."""
        for i, h in enumerate(self._hosts):
            if h.index == index:
                return self._hosts.pop(i)
        raise KeyError(f"no virtual host {index}")


class DistributedCoordinator(Coordinator):
    """Thin wrapper over ``jax.distributed`` for real multi-process fleets.

    ``initialize=True`` calls ``jax.distributed.initialize`` (env-driven or
    with the explicit coordinator address); pass ``initialize=False`` when
    the runtime already did (or in single-process smoke runs, where every
    primitive degenerates to the local case and stays cheap).
    """

    def __init__(self, *, initialize: bool = False,
                 coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 model_parallel: int = 2):
        if initialize:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        self._index = jax.process_index()
        self._count = jax.process_count()
        # normal SPMD: every process runs the same program over the GLOBAL
        # mesh; the per-host identity is the process index.
        n = len(jax.devices())
        mp = model_parallel if n % model_parallel == 0 else 1
        mesh = make_mesh((n // mp, mp), ("data", "model"))
        self._host = FleetHost(self._index, tuple(jax.local_devices()), mesh)

    def hosts(self) -> List[FleetHost]:
        return [self._host]

    @property
    def process_count(self) -> int:
        return self._count

    def barrier(self, tag: str) -> None:
        if self._count == 1:
            return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)

    def all_gather(self, per_host: Dict[int, Any]) -> Dict[int, Any]:
        """Gather one JSON-able object per process (telemetry snapshots)."""
        if self._count == 1:
            return dict(per_host)
        import numpy as np
        from jax.experimental import multihost_utils

        payload = json.dumps(per_host.get(self._index)).encode()
        # fixed-width lane: pad to the fleet max so allgather shapes agree
        n = np.asarray([len(payload)], np.int32)
        max_n = int(multihost_utils.process_allgather(n).max())
        buf = np.zeros((max_n,), np.uint8)
        buf[:len(payload)] = np.frombuffer(payload, np.uint8)
        lens = multihost_utils.process_allgather(n)[:, 0]
        bufs = multihost_utils.process_allgather(buf)
        return {i: json.loads(bytes(bufs[i, :int(lens[i])]).decode())
                for i in range(self._count)}
