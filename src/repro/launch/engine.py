"""Engine: the one sharded, key-threaded step runtime for train/serve/dryrun.

Before this module every launcher hand-rolled the same four things — mesh
construction, axis-context install, step compilation, and (nowhere at all)
PRNG-key plumbing for noisy fabrics.  The Engine owns them once:

  * **mesh + axis context** — built via :mod:`repro.launch.compat` (the
    ``jax.set_mesh`` / ``use_mesh`` / ``with mesh:`` API drift shim), entered
    with :meth:`Engine.activate` so model-side ``shard_hint`` constraints
    resolve against the ambient mesh.
  * **compiled-step cache** — :meth:`train_step` / :meth:`prefill_step` /
    :meth:`decode_step` are memoized on ``(ModelConfig, kind, extras,
    FabricSpec, autotune geometry token)``; equal configs under an unchanged
    kernel-tuning state return the *same* jitted callable, so a
    server admitting its 100th request or a trainer resuming from a
    checkpoint never re-traces.  :attr:`Engine.stats` counts cache hits,
    distinct compiles, and XLA traces (the recompile detector the serve
    tests assert on).
  * **sharding** — param/opt/batch/cache placement from
    :mod:`repro.launch.sharding`, applied either at runtime
    (:meth:`shard_params` / :meth:`shard_batch`) or ahead-of-time
    (:meth:`aot_compile`, the dry-run path: explicit ``in_shardings`` +
    ``lower().compile()``).
  * **noise keys** — one base key per Engine (``noise_seed``), folded per
    step and per slot (:meth:`noise_key`) and passed as the trailing traced
    argument of every step, so noisy FabricSpecs are seed-reproducible at
    training/serving scale instead of per-matmul.
  * **runtime hooks** — an optional :class:`StragglerMonitor` fed by
    :meth:`observe_step_time`; flagged hosts accumulate in
    :attr:`swap_requests` for the serving/training loop to act on.
  * **telemetry** — cache hit/compile/trace counters land in a
    :class:`repro.telemetry.Registry` (``engine.cache_hits`` /
    ``engine.compiles`` / ``engine.traces``); every (re)trace of a cached
    step runs under an ``engine.trace`` span (``kind``), and AOT
    lower/compile under ``engine.aot.*`` spans, in the profiler's trace.
    :attr:`stats` remains the cheap in-process mirror the serve tests
    assert on.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.configs.base import ModelConfig, ShapeConfig
from repro.kernels import autotune
from repro.launch.compat import mesh_context
from repro.launch.mesh import dp_axes, make_test_mesh, tp_axis
from repro.launch.sharding import (partition_batch, partition_inputs,
                                   partition_params)
from repro.launch.steps import (input_specs, make_prefill_step,
                                make_serve_step, make_train_step, step_fn_for)
from repro.models.common import AxisCtx, axis_ctx
from repro.optim.adamw import AdamWConfig
from repro.runtime.straggler import StragglerMonitor
from repro.telemetry import Registry, clock, get_registry, span


@jax.jit
def _fold_key(base, step, slot):
    return jax.random.fold_in(jax.random.fold_in(base, step), slot)


@dataclass
class AotResult:
    """One dry-run cell: the lowered/compiled step and how long each took."""

    lowered: object
    compiled: object
    lower_s: float
    compile_s: float


@dataclass
class EngineStats:
    """Compilation/caching counters (the serve tests' recompile detector)."""

    compiles: int = 0  # distinct jitted step functions built
    traces: int = 0  # XLA traces through those functions (re-trace = +1)
    hits: int = 0  # compiled-step cache hits


@dataclass
class Engine:
    """One mesh, one compiled-step cache, one noise-key stream.

    ``mesh=None`` builds the small test mesh over whatever devices exist;
    pass :func:`repro.launch.mesh.make_production_mesh` for the real
    topology.  The Engine is cheap to construct; executables materialize
    lazily on first use of each ``(cfg, kind)``.
    """

    mesh: Optional[object] = None
    noise_seed: int = 0
    monitor: Optional[StragglerMonitor] = None
    stats: EngineStats = field(default_factory=EngineStats)
    registry: Optional[Registry] = None  # None -> the process-global one

    def __post_init__(self):
        if self.mesh is None:
            self.mesh = make_test_mesh()
        if self.registry is None:
            self.registry = get_registry()
        self._steps: Dict[Tuple, Callable] = {}
        self._base_key = None
        self.swap_requests: List[int] = []

    # ------------------------------------------------------------- context
    @contextlib.contextmanager
    def activate(self):
        """Install the mesh + axis context (shard_hint resolves inside)."""
        ctx = AxisCtx(dp_axes(self.mesh), tp_axis(self.mesh))
        with mesh_context(self.mesh), axis_ctx(ctx):
            yield self

    # ---------------------------------------------------------- noise keys
    def noise_key(self, step: int, slot: int = 0):
        """Per-(step, slot) PRNG key: fold_in(fold_in(base, step), slot).

        Deterministic in ``noise_seed`` — two Engines with the same seed
        replay identical noise streams (the seed-reproducibility contract
        the noisy-serve tests pin down).  One compiled call rather than
        two eager ``fold_in`` dispatches: every serving tick makes it.
        """
        if self._base_key is None:
            self._base_key = jax.random.key(self.noise_seed)
        return _fold_key(self._base_key, step, slot)

    # ------------------------------------------------- compiled-step cache
    def _counted(self, fn, kind: str):
        @functools.wraps(fn)
        def wrapper(*args):
            self.stats.traces += 1
            self.registry.counter("engine.traces").inc()
            with span("engine.trace", kind=kind):
                return fn(*args)

        return wrapper

    def _cached_step(self, cfg: ModelConfig, kind: str, extras: Tuple,
                     build: Callable[[], Callable]):
        # The autotuner's geometry token rides the key: a re-tune (or a
        # REPRO_TUNE_* pin change) changes the tile geometry baked into the
        # step's kernels, so the cached executable must not be reused.  The
        # token is stable in steady state — zero retraces while nobody tunes.
        key = (cfg, kind, extras, cfg.imc_fabric,
               autotune.geometry_token())
        step = self._steps.get(key)
        if step is None:
            step = self._steps[key] = build()
            self.stats.compiles += 1
            self.registry.counter("engine.compiles").inc()
        else:
            self.stats.hits += 1
            self.registry.counter("engine.cache_hits").inc()
        return step

    def train_step(self, cfg: ModelConfig,
                   opt_cfg: AdamWConfig = AdamWConfig(), *,
                   donate: bool = True):
        """Jitted ``(params, opt_state, batch, key) -> (params, opt, metrics)``."""
        donate_argnums = (0, 1) if donate else ()
        return self._cached_step(
            cfg, "train", (opt_cfg, donate),
            lambda: jax.jit(self._counted(make_train_step(cfg, opt_cfg),
                                            "train"),
                            donate_argnums=donate_argnums))

    def prefill_step(self, cfg: ModelConfig, max_new_tokens: int = 0,
                     bucket: Optional[int] = None):
        """Jitted ``(params, batch, key) -> (last_logits, cache)``.

        ``bucket`` keys one executable per padded prompt length: ragged
        admission pads each prompt up to its bucket and reuses that bucket's
        executable, so mixed-length traffic compiles ``len(buckets)`` prefill
        steps up front and never again (the zero-steady-state-recompile
        guarantee the serve tests assert via :attr:`stats`).
        """
        extras = (max_new_tokens,) if bucket is None \
            else (max_new_tokens, bucket)
        return self._cached_step(
            cfg, "prefill", extras,
            lambda: jax.jit(self._counted(
                make_prefill_step(cfg, max_new_tokens), "prefill")))

    def decode_step(self, cfg: ModelConfig):
        """Jitted ``(params, cache, token, key) -> (logits, cache)``.

        The same callable serves the ring cache and the paged cache (pass
        ``block_table=`` for the latter) — distinct cache pytrees are
        distinct traces of one cached step.
        """
        return self._cached_step(
            cfg, "decode", (),
            lambda: jax.jit(self._counted(make_serve_step(cfg), "decode")))

    def admit_step(self, cfg: ModelConfig):
        """Jitted paged admission: ``(batch_cache, one_cache, table_row,
        slot) -> batch_cache`` — pure pytree surgery (scatter one request's
        freshly prefilled ring cache into the shared pools), compiled once
        so steady-state admits are data-only.
        """
        from repro.models.kv_cache import merge_prefill_cache

        return self._cached_step(
            cfg, "admit", (),
            lambda: jax.jit(self._counted(merge_prefill_cache, "admit")))

    # ------------------------------------------------------------ sharding
    def shard_params(self, cfg: ModelConfig, params):
        """Place a params pytree per the TP/FSDP partitioning rules."""
        return jax.device_put(params, partition_params(params, cfg, self.mesh))

    def shard_batch(self, cfg: ModelConfig, shape: ShapeConfig, batch):
        """Place a batch pytree (DP over the batch axis where divisible)."""
        return jax.device_put(batch,
                              partition_batch(batch, cfg, shape, self.mesh))

    def aot_compile(self, cfg: ModelConfig, shape: ShapeConfig, *,
                    donate: bool = True, paged_kv: bool = False) -> AotResult:
        """Dry-run path: lower + compile one (cfg, shape) cell ahead of time.

        Explicit ``in_shardings`` come from the partitioning rules — sharding
        mismatches, non-divisible layouts, and partitioner failures surface
        as hard errors here.  ``paged_kv`` lowers decode cells against the
        paged pool + block-table state instead of the per-slot ring.
        """
        specs = input_specs(cfg, shape, paged_kv=paged_kv)
        shardings = partition_inputs(specs, cfg, shape, self.mesh)
        step = step_fn_for(cfg, shape)
        donate_argnums = (0, 1) if (donate and shape.kind != "prefill") else ()
        t0 = clock()
        with self.activate():
            jitted = jax.jit(step, in_shardings=shardings,
                             donate_argnums=donate_argnums)
            with span("engine.aot.lower", arch=cfg.name, shape=shape.name):
                lowered = jitted.lower(*specs)
            t_lower = clock() - t0
            with span("engine.aot.compile", arch=cfg.name, shape=shape.name):
                compiled = lowered.compile()
        t_compile = clock() - t0 - t_lower
        self.registry.histogram("engine.aot.lower_s").observe(t_lower)
        self.registry.histogram("engine.aot.compile_s").observe(t_compile)
        return AotResult(lowered, compiled, t_lower, t_compile)

    # --------------------------------------------------------------- hooks
    def observe_step_time(self, dt: float, host: int = 0) -> List[int]:
        """Feed one step's wall time to the straggler monitor (if any).

        Returns hosts newly flagged for a hot-spare swap; they also
        accumulate in :attr:`swap_requests`.
        """
        if self.monitor is None:
            return []
        flagged = self.monitor.record_step({host: dt})
        self.swap_requests.extend(flagged)
        return flagged
