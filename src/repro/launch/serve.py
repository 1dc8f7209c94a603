"""Serving CLI — drives :class:`repro.launch.server.Server` from the shell.

The pre-paging ``BatchedServer`` (fixed-ring slots, uniform prompt length,
batch-style ``run(requests)``) finished its deprecation cycle and is gone;
``Server(kv="ring")`` reproduces the same fixed-ring geometry behind the
typed ``submit``/``poll``/``drain`` API, with ragged admission, per-request
budgets, and block-pool memory accounting on the ``kv="paged"`` path.

    python -m repro.launch.serve --arch qwen2.5-3b --requests 6
    python -m repro.launch.serve --arch qwen2.5-3b --requests 6 \
        --imc-mode sim --imc-noise-sigma 0.05 --seed 7

``--reduce`` (the default) serves the same-family smoke variant;
``--no-reduce`` serves the published widths.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core.fabric import add_fabric_cli, apply_fabric_cli
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.engine import Engine
from repro.models.model import init_params
from repro.runtime.straggler import StragglerMonitor
from repro.telemetry import clock


def main():
    from repro.launch.server import Request, Server

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduce", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the smoke variant (--no-reduce: full width)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--kv", default="paged", choices=["paged", "ring"],
                    help="paged block-table cache or the legacy fixed ring")
    ap.add_argument("--attn-impl", default=None,
                    choices=["jnp", "pallas"],
                    help="paged-decode attention engine (default: pallas on "
                         "TPU, jnp elsewhere)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0,
                    help="noise-key seed (noisy serve is reproducible in it)")
    ap.add_argument("--fleet-hosts", type=int, default=1,
                    help="virtual fleet: partition local devices into N "
                         "hosts, round-robin requests, report merged SLOs")
    add_fabric_cli(ap)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduce_config(cfg)
    cfg = apply_fabric_cli(ap, args, cfg, jitted_what="server")
    rng = np.random.default_rng(0)
    params = init_params(jax.random.key(0), cfg)
    bucket = max(16, args.prompt_len)
    server_kw = dict(slots=args.slots, kv=args.kv,
                     block_size=args.block_size, buckets=(bucket,),
                     attn_impl=args.attn_impl,
                     max_seq_len=bucket + args.max_new)
    requests = [Request(
        rng.integers(0, cfg.vocab_size,
                     size=args.prompt_len).astype(np.int32),
        max_new_tokens=args.max_new) for _ in range(args.requests)]
    t0 = clock()
    if args.fleet_hosts > 1:
        from repro.fleet import FleetEngine, FleetServer, LocalCoordinator

        fleet = FleetEngine(LocalCoordinator(args.fleet_hosts),
                            noise_seed=args.seed)
        server = FleetServer(cfg, params, fleet, **server_kw)
        handles = [server.submit(r) for r in requests]
        server.drain()
        dt = clock() - t0
        slos = server.slos()
        traces = fleet.total_traces()
    else:
        engine = Engine(noise_seed=args.seed, monitor=StragglerMonitor())
        with engine.activate():
            server = Server(cfg, params, engine=engine, **server_kw)
            handles = [server.submit(r) for r in requests]
            server.drain()
        dt = clock() - t0
        slos = None
        traces = engine.stats.traces
    ntok = sum(len(h.tokens) for h in handles)
    for h in handles:
        print(f"req{h.rid}: {len(h.tokens)} tokens -> {h.tokens[:8]}...")
    print(f"throughput: {ntok / max(dt, 1e-9):.1f} tok/s "
          f"({args.kv} lockstep decode, attn={server.attn_impl}; "
          f"{traces} traces)")
    if slos is not None:
        print(f"fleet SLOs (n_hosts={slos.get('n_hosts')}): "
              f"ttft_ms={slos['ttft_ms']} tpot_ms={slos['tpot_ms']} "
              f"occupancy_peak={slos['occupancy_peak']}")


if __name__ == "__main__":
    main()
