"""Production mesh builders.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Production topology (TPU v5e):
  single-pod: 16 x 16 = 256 chips, axes ("data", "model")
  multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model")
The "pod" axis carries pure DP (hierarchical gradient all-reduce over the
slower cross-pod links); ZeRO/FSDP sharding stays intra-pod on "data".

Every mesh has Auto axes: the model's ``shard_hint`` constraints are hints
for the partitioner, which under Explicit axes (``jax.make_mesh``'s default)
would instead be asserts on the operands' types.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes, over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, model_parallel: int = 2):
    """Small mesh over whatever devices exist (unit tests)."""
    n = n_devices or len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"))


def make_submesh(devices, model_parallel: int = 2):
    """(data, model) mesh over an explicit device subset.

    The virtual-fleet coordinator partitions the local devices into per-host
    groups; each group gets its own mesh built here.
    """
    n = len(devices)
    mp = model_parallel if n % model_parallel == 0 else 1
    return make_mesh((n // mp, mp), ("data", "model"), devices=devices)


def partition_devices(n_hosts: int, devices=None):
    """Split the local devices into ``n_hosts`` equal contiguous groups."""
    devices = list(devices if devices is not None else jax.devices())
    if n_hosts < 1 or len(devices) % n_hosts != 0:
        raise ValueError(
            f"cannot split {len(devices)} devices into {n_hosts} equal "
            f"virtual hosts")
    per = len(devices) // n_hosts
    return [tuple(devices[i * per:(i + 1) * per]) for i in range(n_hosts)]


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"


def dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n
