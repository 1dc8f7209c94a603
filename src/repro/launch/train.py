"""End-to-end trainer: data -> sharded train_step -> checkpoints, fault-tolerant.

Single-process entry point that scales down to 1 CPU device (examples/tests)
and up to the production mesh (same code path the dry-run lowers).  All mesh,
sharding, compilation, and noise-key concerns live in
:class:`repro.launch.engine.Engine`; this file is just the loop.

    python -m repro.launch.train --arch imc-paper-110m --steps 200 \
        --ckpt /tmp/ckpt --batch 8 --seq 256
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduce_config
from repro.configs.base import ShapeConfig
from repro.core.fabric import add_fabric_cli, apply_fabric_cli
from repro.data.pipeline import DataConfig, SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import Engine
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.models.model import init_params
from repro.runtime.fault_tolerance import FaultTolerantLoop
from repro.runtime.straggler import StragglerMonitor
from repro.telemetry import clock


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          ckpt_root: str | None = None, ckpt_every: int = 50,
          lr: float = 3e-4, seed: int = 0, engine: Engine | None = None,
          log_every: int = 10, fail_at=None):
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 10 + 1),
                          total_steps=steps)
    engine = engine or Engine(noise_seed=seed, monitor=StragglerMonitor())
    shape = ShapeConfig("runtime", seq_len, global_batch, "train")
    stream = SyntheticStream(DataConfig(
        cfg.vocab_size, seq_len, global_batch, seed=seed,
        frontend_dim=cfg.frontend_dim if cfg.frontend != "none" else 0))

    params = init_params(jax.random.key(seed), cfg)
    opt_state = init_adamw(params)
    metrics_hist = []

    with engine.activate():
        params = engine.shard_params(cfg, params)
        jitted = engine.train_step(cfg, opt_cfg)

        def step_fn(state, batch, step):
            params, opt_state = state
            batch = engine.shard_batch(cfg, shape,
                                       jax.tree.map(jnp.asarray, batch))
            params, opt_state, metrics = jitted(params, opt_state, batch,
                                                engine.noise_key(step))
            metrics_hist.append({k: float(v) for k, v in metrics.items()})
            return (params, opt_state)

        if ckpt_root:
            loop = FaultTolerantLoop(
                ckpt_root, step_fn, lambda s: stream.batch(s),
                ckpt_every=ckpt_every, fail_at=fail_at,
                monitor=engine.monitor or StragglerMonitor())
            state = loop.run((params, opt_state), steps)
        else:
            state = (params, opt_state)
            for s in range(steps):
                t0 = clock()
                state = step_fn(state, stream.batch(s), s)
                engine.observe_step_time(clock() - t0)
                if s % log_every == 0:
                    m = metrics_hist[-1]
                    print(f"step {s:5d} loss={m['loss']:.4f} "
                          f"ce={m['ce']:.4f} gnorm={m['grad_norm']:.2f} "
                          f"({clock()-t0:.2f}s)", flush=True)
    return state, metrics_hist


def train_fleet(cfg, *, n_hosts: int, steps: int, global_batch: int,
                seq_len: int, ckpt_root: str, ckpt_every: int = 10,
                lr: float = 3e-4, seed: int = 0, model_parallel: int = 2,
                delay=None, log_every: int = 10):
    """Virtual-fleet trainer: one Engine per coordinator host, fleet monitor,
    straggler shrink + checkpoint resume (see :mod:`repro.fleet`).

    Every host steps a replica of the full state on its own sub-mesh; the
    controller's replica is what gets checkpointed and returned.  ``delay``
    injects synthetic per-host skew into observed times (chaos drills).
    """
    from repro.fleet import FleetEngine, FleetTrainLoop, LocalCoordinator
    from repro.runtime.elastic import plan_for_fleet

    coord = LocalCoordinator(n_hosts, model_parallel=model_parallel)
    fleet = FleetEngine(coord, noise_seed=seed)
    per_host = coord.hosts()[0].n_devices
    mp = model_parallel if per_host % model_parallel == 0 else 1
    plan = plan_for_fleet(n_hosts, per_host, model_parallel=mp,
                          base_batch=global_batch)

    opt_cfg = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 10 + 1),
                          total_steps=steps)
    shape = ShapeConfig("runtime", seq_len, global_batch, "train")
    stream = SyntheticStream(DataConfig(
        cfg.vocab_size, seq_len, global_batch, seed=seed,
        frontend_dim=cfg.frontend_dim if cfg.frontend != "none" else 0))
    init_state = jax.tree.map(
        jax.device_get,
        (init_params(jax.random.key(seed), cfg),
         init_adamw(init_params(jax.random.key(seed), cfg))))
    metrics_hist = {}

    def make_step(engine, host):
        jitted = engine.train_step(cfg, opt_cfg, donate=False)

        def step_fn(state, batch, step):
            params, opt_state = state
            batch = engine.shard_batch(cfg, shape,
                                       jax.tree.map(jnp.asarray, batch))
            params, opt_state, metrics = jitted(params, opt_state, batch,
                                                engine.noise_key(step))
            metrics_hist.setdefault(host, []).append(
                {k: float(v) for k, v in metrics.items()})
            if host == fleet.controller and step % log_every == 0:
                m = metrics_hist[host][-1]
                print(f"[fleet {len(fleet.active_hosts())}h] step {step:5d} "
                      f"loss={m['loss']:.4f}", flush=True)
            return (params, opt_state)

        return step_fn

    loop = FleetTrainLoop(fleet, ckpt_root, make_step,
                          lambda s: stream.batch(s), plan,
                          model_parallel=mp, ckpt_every=ckpt_every,
                          delay=delay)
    state = loop.run(init_state, steps)
    return state, metrics_hist.get(fleet.controller, []), fleet, loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="imc-paper-110m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reduce", action="store_true",
                    help="use the smoke-scale config variant")
    ap.add_argument("--fleet-hosts", type=int, default=1,
                    help="virtual fleet: partition local devices into N "
                         "hosts and train via repro.fleet (needs a device "
                         "count divisible by N)")
    add_fabric_cli(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    cfg = apply_fabric_cli(ap, args, cfg, jitted_what="trainer")
    if args.fleet_hosts > 1:
        import tempfile
        ckpt_root = args.ckpt or tempfile.mkdtemp(prefix="fleet_ckpt_")
        (params, _), hist, fleet, _ = train_fleet(
            cfg, n_hosts=args.fleet_hosts, steps=args.steps,
            global_batch=args.batch, seq_len=args.seq, ckpt_root=ckpt_root,
            lr=args.lr, seed=args.seed)
        print(f"fleet: {len(fleet.active_hosts())} hosts, "
              f"{fleet.total_traces()} traces total")
    else:
        (params, _), hist = train(
            cfg, steps=args.steps, global_batch=args.batch,
            seq_len=args.seq, ckpt_root=args.ckpt, lr=args.lr,
            seed=args.seed)
    losses = [m["loss"] for m in hist]
    print(f"\nfinal loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
          f"params = {sum(np.asarray(x).size for x in jax.tree.leaves(params)):,}")


if __name__ == "__main__":
    main()
