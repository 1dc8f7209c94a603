"""Benchmark runner: one function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV (optionally teeing to ``--out`` for CI
artifact upload).  ``--smoke`` runs the reduced matrix — small shapes, fewer
iterations — so a CPU CI runner finishes in a couple of minutes while still
seeding the perf trajectory.  Roofline rows appear when dry-run records exist
under experiments/dryrun/.

``--json [PATH]`` additionally runs the Engine-backed continuous-batching
serve bench per (FabricSpec x KV geometry) — float / exact / sim / noisy-sim
(both the keyed jnp engine and the in-kernel-PRNG ``sim/pallas+noise`` fast
path), each under the legacy fixed ring AND the paged block pool, plus one
ragged-admission paged row and paged-kernel (``attn_impl='pallas'``) siblings
of the float paged rows — and writes rows (tokens/s, steady-state
decode-step ms, attn_impl tag) to ``PATH`` (default ``BENCH_imc.json``).
``--autotune`` first resolves the standard kernel-geometry cells through
``repro.kernels.autotune`` (trial-free on the committed cache).

``--compare OLD NEW`` diffs two such JSON files (tokens/s, step ms, % delta)
as a markdown table keyed by (spec, kv, mix, attn_impl) — jnp-path numbers
are never diffed against kernel-path numbers — and CI posts it against the
previous main artifact.

The CSV path includes ``paged_decode_attn/*`` rows (bench_decode_attn): the
decode-attention op swept over context length, one row per attn_impl.
"""
from __future__ import annotations

import argparse
import inspect
import json


def _rows_from(fn, smoke: bool):
    if "smoke" in inspect.signature(fn).parameters:
        return fn(smoke=smoke)
    return fn()


def _serve_once(cfg, params, lengths, max_new, kv, attn_impl=None):
    """One Server run: warmup wave (compiles) + timed wave; returns a row.

    Each run gets its OWN telemetry Registry (no cross-row contamination),
    and the row carries the serving SLO trio (TTFT/TPOT/occupancy peak) plus
    the full telemetry snapshot for BENCH_imc.json.  Every row is tagged
    with the decode-attention engine that produced it (``attn_impl``), and
    paged-kernel rows run off-TPU carry ``interpret: true`` — interpreter
    throughput is an oracle-mode number, not perf.
    """
    import jax
    import numpy as np

    from repro.launch.engine import Engine
    from repro.launch.server import Request, Server
    from repro.runtime.straggler import StragglerMonitor
    from repro.telemetry import Registry, clock, serving_slos, snapshot

    buckets = sorted({-(-n // 16) * 16 for n in lengths})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    registry = Registry()
    engine = Engine(monitor=StragglerMonitor(), registry=registry)
    with engine.activate():
        server = Server(cfg, params, engine=engine, slots=4, kv=kv,
                        block_size=8, buckets=buckets, attn_impl=attn_impl,
                        max_seq_len=max(buckets) + max_new)
        for p in prompts:  # warmup wave: traces + compiles land here
            server.submit(Request(p, max_new_tokens=max_new))
        server.drain()
        warm = engine.stats.traces
        registry.reset()  # SLOs cover the timed (steady-state) waves only
        timed = []
        d0, t0 = server.decode_s, clock()
        for _ in range(4):  # several timed waves: averages out host jitter
            wave = [server.submit(Request(p, max_new_tokens=max_new))
                    for p in prompts]
            server.drain()
            timed += wave
        dt = clock() - t0
        decode_dt = server.decode_s - d0
    assert engine.stats.traces == warm, "steady-state recompile in bench"
    # tokens/s is LOCKSTEP-DECODE throughput: each handle's first token comes
    # from prefill logits, the rest from decode ticks timed device-side via
    # Server.decode_s.
    tokens = sum(len(h.tokens) - 1 for h in timed)
    host = engine.monitor.hosts.get(0)
    row = {
        "tokens_per_s": round(tokens / decode_dt, 2),
        "e2e_tokens_per_s": round(sum(len(h.tokens) for h in timed) / dt, 2),
        "step_ms": round(host.ewma_time * 1e3, 3) if host else None,
        "compiled_steps": engine.stats.compiles,
        "traces": engine.stats.traces,
        **serving_slos(registry, attn_impl=server.attn_impl, n_hosts=1),
        "telemetry": snapshot(registry),
    }
    if server.attn_impl == "pallas" and jax.default_backend() != "tpu":
        row["interpret"] = True  # CPU interpreter row: exempt from perf bars
    return row


def _serve_fleet_once(cfg, params, lengths, max_new, kv, n_hosts,
                      attn_impl=None):
    """One FleetServer run over an N-host virtual fleet; returns a row.

    Same warmup + timed-waves protocol as :func:`_serve_once`, with SLOs read
    off the MERGED per-host registry view (exact fleet percentiles) and the
    row tagged ``n_hosts=N`` so ``--compare`` never diffs it against a
    single-host sibling.
    """
    import numpy as np

    from repro.fleet import FleetEngine, FleetServer, LocalCoordinator
    from repro.launch.server import Request
    from repro.telemetry import clock, serving_slos, snapshot

    buckets = sorted({-(-n // 16) * 16 for n in lengths})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lengths]
    fleet = FleetEngine(LocalCoordinator(n_hosts))
    server = FleetServer(cfg, params, fleet, slots=4, kv=kv, block_size=8,
                         buckets=buckets, attn_impl=attn_impl,
                         max_seq_len=max(buckets) + max_new)
    for p in prompts:  # warmup wave: traces + compiles land here
        server.submit(Request(p, max_new_tokens=max_new))
    server.drain()
    warm = fleet.total_traces()
    for h in fleet.active_hosts():  # SLOs cover steady-state waves only
        fleet.engine(h).registry.reset()
    timed = []
    d0, t0 = server.total_decode_s(), clock()
    for _ in range(4):
        wave = [server.submit(Request(p, max_new_tokens=max_new))
                for p in prompts]
        server.drain()
        timed += wave
    dt = clock() - t0
    decode_dt = server.total_decode_s() - d0
    assert fleet.total_traces() == warm, "steady-state recompile in bench"
    tokens = sum(len(h.tokens) - 1 for h in timed)
    merged = fleet.merged_registry()
    ewmas = [fleet.monitor.hosts[h].ewma_time
             for h in fleet.active_hosts() if h in fleet.monitor.hosts]
    row = {
        "tokens_per_s": round(tokens / decode_dt, 2),
        "e2e_tokens_per_s": round(sum(len(h.tokens) for h in timed) / dt, 2),
        "step_ms": round(1e3 * sum(ewmas) / len(ewmas), 3) if ewmas else None,
        "compiled_steps": sum(fleet.engine(h).stats.compiles
                              for h in fleet.active_hosts()),
        "traces": fleet.total_traces(),
        **serving_slos(merged, attn_impl=server.attn_impl, n_hosts=n_hosts),
        "telemetry": snapshot(merged),
    }
    return row


def serve_spec_rows(smoke: bool = True):
    """Serve throughput per (FabricSpec x kv geometry), reduced arch.

    Every spec runs under both ``kv='ring'`` (the legacy fixed-ring oracle)
    and ``kv='paged'`` at one uniform prompt length — the paged row must not
    regress tokens/s vs its ring sibling.  One extra ragged-mix paged row
    (prompt lengths 7/16/33) covers the admission path ring cannot serve.

    The float spec additionally runs its paged rows (uniform + ragged) with
    ``attn_impl='pallas'`` — the fused flash-decode kernel vs the jnp gather
    path on identical traffic.  On TPU the kernel row must meet or beat its
    jnp sibling at long contexts; on CPU it is an interpreter-correctness
    row (tagged ``interpret: true``).
    """
    import dataclasses

    import jax

    from repro.configs import get_config, reduce_config
    from repro.core.fabric import FabricSpec, NoiseSpec
    from repro.models.model import init_params

    cfg0 = reduce_config(get_config("qwen2.5-3b"))
    specs = [
        ("float", None),
        (None, FabricSpec(mode="exact", backend="jnp")),
        (None, FabricSpec(bits_a=4, bits_w=4, mode="sim", backend="jnp")),
        (None, FabricSpec(bits_a=4, bits_w=4, mode="sim", backend="jnp",
                          noise=NoiseSpec(mismatch_sigma=0.05))),
        # noisy Pallas fast path: the same NoiseSpec drawn by the in-kernel
        # PRNG inside the fused bitplane_mac kernel (one pallas_call).  Off
        # TPU this serves through the interpreter — a correctness row, not
        # perf — and is tagged ``interpret: true`` below.
        (None, FabricSpec(bits_a=4, bits_w=4, mode="sim", backend="pallas",
                          noise=NoiseSpec(mismatch_sigma=0.05))),
    ]
    n_req, max_new = (4, 6) if smoke else (8, 16)
    uniform = [16] * n_req
    ragged = [(7, 16, 33)[i % 3] for i in range(n_req)]
    params = init_params(jax.random.key(0), cfg0)
    matrix = [(label, spec, kv, mix, lens, None)
              for label, spec in specs
              for kv, mix, lens in (("ring", "uniform", uniform),
                                    ("paged", "uniform", uniform))]
    matrix.append(("float", None, "paged", "ragged", ragged, None))
    # paged-kernel siblings of the float paged rows: same traffic, fused
    # flash-decode attention instead of the dense gather
    matrix.append(("float", None, "paged", "uniform", uniform, "pallas"))
    matrix.append(("float", None, "paged", "ragged", ragged, "pallas"))
    rows = []
    for label, spec, kv, mix, lens, attn_impl in matrix:
        cfg = dataclasses.replace(cfg0, fabric=spec, imc_mode="off")
        row = _serve_once(cfg, params, lens, max_new, kv,
                         attn_impl=attn_impl)
        if (spec is not None and spec.backend == "pallas"
                and jax.default_backend() != "tpu"):
            row["interpret"] = True  # fabric kernel ran in the interpreter
        rows.append({"spec": label or spec.label, "kv": kv, "mix": mix,
                     "arch": cfg0.name, **row})
    # virtual-fleet sibling of the float paged uniform row: same traffic
    # split over 2 hosts, SLOs off the merged registry (needs >= 2 devices;
    # CI forces them with --xla_force_host_platform_device_count)
    if len(jax.devices()) >= 2:
        cfg = dataclasses.replace(cfg0, fabric=None, imc_mode="off")
        row = _serve_fleet_once(cfg, params, uniform, max_new, "paged", 2)
        rows.append({"spec": "float", "kv": "paged", "mix": "uniform",
                     "arch": cfg0.name, **row})
    return rows


def compare(old_path: str, new_path: str) -> None:
    """Diff two BENCH_imc.json runs row-by-row (markdown table to stdout).

    Rows are keyed by (spec, noise_engine, kv, mix, attn_impl, n_hosts) — a
    jnp-path row is never diffed against a kernel-path row, a noisy row
    drawn by the in-kernel PRNG (``sim/pallas+noise``) is never diffed
    against one drawn by the keyed jnp engine (``sim/jnp+noise``), and a
    single-host row is never diffed against a fleet row.  Files predating
    the ``attn_impl`` / ``n_hosts`` tags default to what they actually ran:
    ``ring`` geometry or the jnp gather path, and one host.
    """
    def impl_of(r):
        kv = r.get("kv", "ring")
        return r.get("attn_impl", "ring" if kv == "ring" else "jnp")

    def noise_of(r):
        # the noise ENGINE is the backend half of a noisy spec label
        # ("sim/jnp+noise" -> "jnp", "sim/pallas+noise" -> "pallas");
        # noise-free rows key as "-" so they only ever diff against each
        # other.
        label = r.get("spec", "")
        if "+noise" not in label:
            return "-"
        return label.split("/", 1)[-1].split("+", 1)[0]

    def load(p):
        with open(p) as f:
            rec = json.load(f)
        return {(r["spec"], noise_of(r), r.get("kv", "ring"),
                 r.get("mix", "uniform"), impl_of(r),
                 r.get("n_hosts", 1) or 1): r
                for r in rec["rows"]}

    def pct(old, new):
        if not old or old in (None, 0) or new is None:
            return "n/a"
        return f"{100.0 * (new - old) / old:+.1f}%"

    old, new = load(old_path), load(new_path)
    print("| spec | noise | kv | mix | attn | hosts | tok/s old | tok/s new "
          "| Δ | step ms old | step ms new | Δ | ttft ms old | ttft ms new "
          "| Δ | tpot ms old | tpot ms new | Δ |")
    print("|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|"
          "---|---|---|")
    for key in sorted(set(old) | set(new)):
        o, n = old.get(key, {}), new.get(key, {})
        attn = key[4] + (" (interpret)" if (o.get("interpret")
                                            or n.get("interpret")) else "")
        cells = [key[0], key[1], key[2], key[3], attn, key[5]]
        for field in ("tokens_per_s", "step_ms", "ttft_ms", "tpot_ms"):
            ov, nv = o.get(field), n.get(field)
            cells += [ov if ov is not None else "—",
                      nv if nv is not None else "—", pct(ov, nv)]
        print("| " + " | ".join(str(c) for c in cells) + " |")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced bench matrix (CI smoke; seeds perf CSV)")
    ap.add_argument("--out", default=None,
                    help="also write the CSV to this path")
    ap.add_argument("--json", nargs="?", const="BENCH_imc.json", default=None,
                    metavar="PATH",
                    help="run the per-spec serve bench and write JSON rows "
                         "(tokens/s, step ms) to PATH")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"), default=None,
                    help="diff two BENCH_imc.json runs (tokens/s, step ms, "
                         "%% delta) as a markdown table; runs nothing else")
    ap.add_argument("--autotune", action="store_true",
                    help="(re-)tune the standard kernel cells before "
                         "benching; cached cells resolve trial-free, so on "
                         "a warm cache this is a no-op assertion")
    args = ap.parse_args(argv)

    if args.compare:
        compare(*args.compare)
        return

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.autotune:
        from repro.kernels import autotune
        for kernel, bucket, geom, backend in autotune.tune_standard(
                smoke=args.smoke):
            print(f"autotune/{kernel}/{bucket}/{backend},"
                  f"{' '.join(f'{k}={v}' for k, v in sorted(geom.items()))}",
                  flush=True)

    from benchmarks import (bench_decode_attn, bench_imc_throughput,
                            bench_paper_tables, roofline)

    lines = ["name,us_per_call,derived"]
    print(lines[0])
    for fn in (*bench_paper_tables.ALL, *bench_imc_throughput.ALL,
               *bench_decode_attn.ALL):
        for r in _rows_from(fn, args.smoke):
            lines.append(r)
            print(r, flush=True)
    for r in roofline.csv_rows(roofline.load()):
        lines.append(r)
        print(r, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
    if args.json:
        rows = serve_spec_rows(smoke=args.smoke)
        rec = {"benchmark": "continuous_batching_serve", "smoke": args.smoke,
               "rows": rows}
        with open(args.json, "w") as f:
            json.dump(rec, f, indent=1)
        for r in rows:
            print(f"serve/{r['spec']}/{r['kv']}/{r['mix']}/{r['attn_impl']},"
                  f"{r['step_ms']},{r['tokens_per_s']} tok/s", flush=True)


if __name__ == "__main__":
    main()
