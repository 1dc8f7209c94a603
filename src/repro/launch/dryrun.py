import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be the entry point of a fresh process (the XLA flag above is read at
first jax init).  For each cell, ``Engine.aot_compile`` lowers + compiles the
step under the production mesh with explicit in_shardings,
and records memory_analysis / cost_analysis / collective traffic to JSON under
experiments/dryrun/.  Success here proves the distribution config is coherent:
sharding mismatches, non-divisible layouts, and partitioner failures all
surface as hard errors.

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k --mesh multi
    python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import traceback  # noqa: E402

from repro.configs import ASSIGNED_ARCHS, LONG_CONTEXT_ARCHS, SHAPES, get_config  # noqa: E402
from repro.launch.engine import Engine  # noqa: E402
from repro.launch.hlo_analysis import collective_bytes, roofline_terms  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def applicable(arch: str, shape_name: str) -> bool:
    if shape_name == "long_500k" and arch not in LONG_CONTEXT_ARCHS:
        return False  # pure full-attention archs skip 500k decode (DESIGN.md)
    return True


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, overrides: dict | None = None,
             tag: str = "", paged_kv: bool = False,
             fleet_hosts: int = 1) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    if fleet_hosts > 1:
        # per-host cell: lower/compile on ONE virtual host's sub-mesh — what
        # every process of an N-host fleet would actually run (global batch
        # still divides across hosts upstream of this step's shapes).
        from repro.launch.mesh import make_submesh, partition_devices

        host0 = partition_devices(fleet_hosts)
        mesh = make_submesh(list(host0[0]), model_parallel=16)
        engine = Engine(mesh=mesh)
    else:
        engine = Engine(mesh=make_production_mesh(multi_pod=multi_pod))
    n_dev = engine.mesh.size
    if paged_kv and shape.kind != "decode":
        raise ValueError("--paged-kv applies to decode shapes only")
    aot = engine.aot_compile(cfg, shape, paged_kv=paged_kv)
    compiled = aot.compiled
    t_lower, t_compile = aot.lower_s, aot.compile_s

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)  # reference: per-occurrence (no trip scaling)
    # full-module cost model with while-trip multiplication (hlo_costs):
    from repro.launch.hlo_costs import analyze

    costs = analyze(hlo)
    # useful model flops: 6*N*D for train, 2*N*D for inference steps
    n_active = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens
    rl = roofline_terms(
        {"flops": costs.flops, "bytes accessed": costs.hbm_bytes,
         "flops_int8": costs.flops_int8},
        dict(costs.coll_by_type), model_flops_total=mf, n_devices=n_dev)

    rec = {
        "arch": arch, "shape": shape_name,
        "variant": (tag or "baseline") + ("+paged_kv" if paged_kv else ""),
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
        "mesh": (f"fleet{fleet_hosts}_host0" if fleet_hosts > 1
                 else "2x16x16" if multi_pod else "16x16"),
        "n_devices": n_dev, "kind": shape.kind,
        "fleet_hosts": fleet_hosts,
        "params_total": cfg.n_params(), "params_active": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
                3),
        },
        "cost": {k: cost[k] for k in ("flops", "bytes accessed")
                 if k in cost},
        "collectives": coll,
        "roofline": rl.as_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    stem = f"{arch}__{shape_name}__{rec['mesh']}{suffix}"
    with open(os.path.join(out_dir, stem + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    import gzip

    with gzip.open(os.path.join(out_dir, stem + ".hlo.gz"), "wt") as f:
        f.write(hlo)  # enables offline re-analysis without recompiling
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (repeatable)")
    ap.add_argument("--tag", default="", help="variant tag for the output file")
    ap.add_argument("--paged-kv", action="store_true",
                    help="lower decode cells against the paged KV pool + "
                         "block table instead of the per-slot ring")
    ap.add_argument("--fleet-hosts", type=int, default=1,
                    help="lower the cell on ONE virtual host's sub-mesh of "
                         "an N-host fleet instead of the global mesh")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        overrides[k] = v

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            if not applicable(arch, shape_name):
                print(f"SKIP  {arch} x {shape_name} (long-context N/A)")
                continue
            if args.paged_kv and SHAPES[shape_name].kind != "decode":
                continue
            for mp in meshes:
                mesh_tag = (f"fleet{args.fleet_hosts}_host0"
                            if args.fleet_hosts > 1
                            else "2x16x16" if mp else "16x16")
                suffix = f"__{args.tag}" if args.tag else ""
                tag = f"{arch}__{shape_name}__{mesh_tag}{suffix}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"HAVE  {tag}")
                    continue
                try:
                    rec = run_cell(arch, shape_name, mp, args.out,
                                   overrides=overrides, tag=args.tag,
                                   paged_kv=args.paged_kv,
                                   fleet_hosts=args.fleet_hosts)
                    r = rec["roofline"]
                    print(f"PASS  {tag}: {rec['memory']['peak_per_device_gb']}"
                          f" GiB/dev, dominant={r['dominant']}, "
                          f"t=({r['t_compute_s']:.2e},{r['t_memory_s']:.2e},"
                          f"{r['t_collective_s']:.2e})s, "
                          f"compile={rec['compile_s']}s", flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"FAIL  {tag}: {e!r}", flush=True)
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
