"""Batched serving example on the typed Server API (submit / poll / drain):
ragged prompts are right-padded to per-bucket prefill executables, KV lives in
a paged block pool with per-slot block tables, and decode runs all slots in
lockstep through ONE compiled step.  Runs a reduced qwen2.5 config so it
finishes on CPU in seconds.

The example serves ``--waves`` identical waves of mixed-length requests and
asserts that every wave after the first is trace-free: the compiled-step
cache plus block-table-as-data design means steady-state traffic never
recompiles, which ``Engine.stats.traces`` pins down.

Run:  PYTHONPATH=src python examples/serve_batched.py [--lengths 7,16,33]
Add ``--imc-mode sim --imc-noise-sigma 0.05`` for a noisy fabric.
``--trace-out DIR`` runs the serving under the JAX profiler and writes its
trace to DIR: the Server's spans (admission, decode launch, wait, logits
fetch, sampling) beside the device's ops, on one clock; drop
``DIR/**/perfetto_trace.json.gz`` into https://ui.perfetto.dev to see the
serving timeline.  ``--telemetry`` prints the metric snapshot as markdown.
"""
import argparse
import contextlib
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.core.fabric import add_fabric_cli, apply_fabric_cli
from repro.launch.engine import Engine
from repro.launch.server import Request, Server
from repro.models.model import init_params
from repro.runtime.straggler import StragglerMonitor
from repro.telemetry import serving_slos, to_markdown


def serve_fleet(cfg, params, rng, lengths, buckets, args):
    """The same waves through an N-host virtual fleet: round-robin routing,
    per-host Engines, and SLOs off the merged (exact) fleet registry.
    Steady-state trace-freeness holds per host, so the FLEET trace total is
    flat across waves 2+ too."""
    from repro.fleet import FleetEngine, FleetServer, LocalCoordinator

    fleet = FleetEngine(LocalCoordinator(args.fleet_hosts),
                        noise_seed=args.seed)
    server = FleetServer(cfg, params, fleet, slots=args.slots,
                         block_size=args.block_size, buckets=buckets,
                         max_seq_len=max(buckets) + args.max_new)
    warm_traces = None
    total_tokens, t0 = 0, time.perf_counter()
    for wave in range(args.waves):
        handles = [server.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=args.max_new)) for n in lengths]
        server.drain()
        assert all(h.done for h in handles), \
            [(h.status, h.reason) for h in handles]
        total_tokens += sum(len(h.tokens) for h in handles)
        # round-robin rotates which host sees which bucket, so warmup takes
        # n_hosts waves; every later wave must be trace-free fleet-wide
        if wave == args.fleet_hosts - 1:
            warm_traces = fleet.total_traces()
        elif wave >= args.fleet_hosts:
            assert fleet.total_traces() == warm_traces, (
                f"steady-state recompile: fleet traces went {warm_traces} "
                f"-> {fleet.total_traces()} on wave {wave}")
    dt = time.perf_counter() - t0
    for h in server.handles:
        print(f"req{h.rid}@host{h.host} (len={len(h.request.prompt)}): "
              f"generated {h.tokens}")
    slos = server.slos()
    print(f"{len(server.handles)} requests over {server.n_hosts} virtual "
          f"hosts; {total_tokens / dt:.1f} tok/s end-to-end; "
          f"fleet traces {fleet.total_traces()} "
          f"(per host {fleet.traces_by_host()}), waves 2+ trace-free")
    print(f"merged SLOs (n_hosts={slos['n_hosts']}): ttft p50 "
          f"{slos['ttft_ms']} ms, tpot p50 {slos['tpot_ms']} ms, queue p50 "
          f"{slos['queue_ms']} ms, peak block occupancy "
          f"{slos['occupancy_peak']}")
    if args.telemetry:
        print(to_markdown(registry=fleet.merged_registry()))
    print("serve_batched OK (fleet)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--lengths", default="7,16,33",
                    help="comma-separated ragged prompt lengths; one request "
                         "per length per wave")
    ap.add_argument("--waves", type=int, default=2,
                    help="identical request waves; waves after the first "
                         "must be trace-free")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=10)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="write a profiler trace of the serving to DIR: the "
                         "Server's spans beside the device's ops, with a "
                         "Perfetto-loadable perfetto_trace.json.gz")
    ap.add_argument("--telemetry", action="store_true",
                    help="print the telemetry snapshot as markdown tables")
    ap.add_argument("--fleet-hosts", type=int, default=1,
                    help="virtual fleet: partition local devices into N "
                         "hosts (device count must divide), route requests "
                         "round-robin, and report merged-registry SLOs")
    add_fabric_cli(ap)
    args = ap.parse_args()

    cfg = reduce_config(get_config(args.arch))
    cfg = apply_fabric_cli(ap, args, cfg, jitted_what="server")
    lengths = [int(x) for x in args.lengths.split(",")]
    buckets = sorted({-(-n // 16) * 16 for n in lengths})
    rng = np.random.default_rng(0)
    params = init_params(jax.random.key(0), cfg)

    if args.fleet_hosts > 1:
        serve_fleet(cfg, params, rng, lengths, buckets, args)
        return

    engine = Engine(noise_seed=args.seed, monitor=StragglerMonitor())
    profile = contextlib.nullcontext()
    if args.trace_out:  # no Python tracer: its events would bury the spans
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profile = jax.profiler.trace(args.trace_out, create_perfetto_trace=True,
                                     profiler_options=opts)
    with profile, engine.activate():
        server = Server(cfg, params, engine=engine, slots=args.slots,
                        block_size=args.block_size, buckets=buckets,
                        max_seq_len=max(buckets) + args.max_new)
        warm_traces = None
        total_tokens, t0 = 0, time.perf_counter()
        for wave in range(args.waves):
            handles = [server.submit(Request(
                prompt=rng.integers(0, cfg.vocab_size, size=n)
                          .astype(np.int32),
                max_new_tokens=args.max_new)) for n in lengths]
            server.drain()
            assert all(h.done for h in handles), \
                [(h.status, h.reason) for h in handles]
            assert all(len(h.tokens) == args.max_new for h in handles)
            total_tokens += sum(len(h.tokens) for h in handles)
            if wave == 0:
                warm_traces = engine.stats.traces
            else:  # steady state: same length mix -> zero new traces
                assert engine.stats.traces == warm_traces, (
                    f"steady-state recompile: traces went {warm_traces} -> "
                    f"{engine.stats.traces} on wave {wave}")
    dt = time.perf_counter() - t0

    for h in server.handles:
        print(f"req{h.rid} (len={len(h.request.prompt)}): "
              f"generated {h.tokens}")
    print(f"{len(server.handles)} requests ({args.waves} waves, lengths "
          f"{lengths}) through {args.slots} slots "
          f"[paged, attn={server.attn_impl}]; "
          f"{total_tokens / dt:.1f} tok/s end-to-end; "
          f"{engine.stats.compiles} compiled steps, {engine.stats.traces} "
          f"traces, waves 2+ trace-free")
    slos = serving_slos(engine.registry, attn_impl=server.attn_impl)
    print(f"SLOs: ttft p50 {slos['ttft_ms']} ms, tpot p50 {slos['tpot_ms']} "
          f"ms, queue p50 {slos['queue_ms']} ms, peak block occupancy "
          f"{slos['occupancy_peak']}")
    if args.telemetry:
        print(to_markdown(registry=engine.registry))
    if args.trace_out:
        print(f"profiler trace -> {args.trace_out} (open its "
              f"perfetto_trace.json.gz in https://ui.perfetto.dev)")
    print("serve_batched OK")


if __name__ == "__main__":
    main()
