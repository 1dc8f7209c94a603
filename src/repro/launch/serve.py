"""Serving CLI — drives :class:`repro.launch.server.Server` from the shell.

The server keeps KV in paged block pools behind the typed
``submit``/``poll``/``drain`` API, with ragged admission, per-request
budgets, and block-pool memory accounting; decode attention runs on the
engine the platform picks.

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
from repro.telemetry import clock, serving_slos


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
    server_kw = dict(slots=args.slots, block_size=args.block_size,
                     buckets=(bucket,), max_seq_len=bucket + args.max_new)
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
        slos = serving_slos(engine.registry, attn_impl=server.attn_impl)
        traces = engine.stats.traces
    ntok = sum(len(h.tokens) for h in handles)
    for h in handles:
        print(f"req{h.rid}: {len(h.tokens)} tokens -> {h.tokens[:8]}...")
    print(f"throughput: {ntok / max(dt, 1e-9):.1f} tok/s "
          f"(paged lockstep decode, attn={server.attn_impl}; "
          f"{traces} traces)")
    where = f"fleet (n_hosts={slos['n_hosts']})" if "n_hosts" in slos \
        else "server"
    print(f"{where} SLOs p50: ttft_ms={slos['ttft_ms']} "
          f"tpot_ms={slos['tpot_ms']} queue_ms={slos['queue_ms']} "
          f"occupancy_peak={slos['occupancy_peak']}")


if __name__ == "__main__":
    main()
