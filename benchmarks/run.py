"""Benchmark runner: one function per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV (optionally teeing to ``--out`` for CI
artifact upload).  ``--smoke`` runs the reduced matrix — small shapes, fewer
iterations — so a CPU CI runner finishes in a couple of minutes while still
seeding the perf trajectory.  Roofline rows appear when dry-run records exist
under experiments/dryrun/.

``--autotune`` first resolves the standard kernel-geometry cells through
``repro.kernels.autotune`` (trial-free on the committed cache).

The CSV path includes ``paged_decode_attn/*`` rows (bench_decode_attn): the
decode-attention op swept over context length, one row per attn_impl.
"""
from __future__ import annotations

import argparse
import inspect


def _rows_from(fn, smoke: bool):
    if "smoke" in inspect.signature(fn).parameters:
        return fn(smoke=smoke)
    return fn()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced bench matrix (CI smoke; seeds perf CSV)")
    ap.add_argument("--out", default=None,
                    help="also write the CSV to this path")
    ap.add_argument("--autotune", action="store_true",
                    help="(re-)tune the standard kernel cells before "
                         "benching; cached cells resolve trial-free, so on "
                         "a warm cache this is a no-op assertion")
    args = ap.parse_args(argv)

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


if __name__ == "__main__":
    main()
