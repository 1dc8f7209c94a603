#!/usr/bin/env python3
"""Bring-up check of the serving path on TPU chips, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four one-chip replicas behind a router

One chip:
  1. fails unless JAX finds a TPU;
  2. runs the main path's Pallas kernels, compiled, at Qwen2.5-3B projection
     and attention shapes against their references: ``bitplane_mac`` 8x8 and
     ``imc_mac`` bit for bit against the integer matmul, ``paged_attn`` (bf16
     and int8 pools) against the jnp gather oracle, and ``bitplane_mac_noisy``
     at the calibrated NoiseSpec (finite, keyed, moments of the keyed jnp
     engine);
  3. serves seeded mixed-length requests through ``Server`` at the full width
     of ``qwen2.5-3b`` (random weights from a seed) under the float path and
     the fabric specs exact, sim and calibrated-noise sim (8x8, backend
     ``auto``): noise-free sim must emit exact's greedy tokens, no request is
     rejected, and a second wave compiles nothing.

``--chips 4`` runs only ``FleetServer`` over ``LocalCoordinator(4)`` (one
chip per replica) and the one-chip ``Server`` it must match token for token.

Every phase asserts; the last line of standard output is the JSON result,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
SLOTS = 8
MAX_SEQ_LEN = 1024  # 8 slots x 1024 tokens of KV: ~300 MB at full width
BLOCK_SIZE = 16
BUCKETS = (32, 128)
# A sim projection runs 64 plane-pair contractions; at full width a sim
# forward costs about a second per layer, so the fabric phases serve the
# first FABRIC_LAYERS layers of the same full-width model.
FABRIC_LAYERS = 4
# Tolerances tests/test_paged_attn.py pins for the kernel against the oracle.
PAGED_ATOL = {"bf16": 1.6e-2, "int8": 1e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling the Engine's
    jitted steps (a step's time includes the kernels and nested jits it
    traces; a persistent-cache hit shows as a short compile)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = defaultdict(float)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event in self.EVENTS:  # lowering names the function "jit(f)"
            name = kw.get("fun_name", "?").removeprefix("jit(").rstrip(")")
            self.seconds[name] += duration

    def take(self) -> dict:
        """Seconds since the last take, by step kind."""
        kinds = {"prefill_step": "prefill", "serve_step": "decode",
                 "merge_prefill_cache": "admit"}
        out = {k: round(self.seconds.get(name, 0.0), 1)
               for name, k in kinds.items()}
        self.seconds.clear()
        return out


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))


# ------------------------------------------------------------- kernels
def check_kernels(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.bitserial import bitserial_matmul_unsigned
    from repro.core.fabric import NoiseSpec, int_matmul
    from repro.kernels.bitplane_mac.ops import bitplane_mac, bitplane_mac_noisy
    from repro.kernels.imc_mac.ops import imc_mac
    from repro.kernels.paged_attn.ops import paged_attention
    from repro.models.attention import _kv_quant

    rng = np.random.default_rng(seed)
    d, ff = cfg.d_model, cfg.d_ff

    def exact_ref(a, w):  # exact in float64: |sums| < 2^53
        return (np.asarray(a, np.float64) @ np.asarray(w, np.float64)
                ).astype(np.int64)

    for k, n in ((d, ff), (ff, d)):  # up/gate and down projections
        ua = rng.integers(0, 256, size=(SLOTS, k), dtype=np.int32)
        uw = rng.integers(0, 256, size=(k, n), dtype=np.int32)
        t0 = time.perf_counter()
        out = np.asarray(bitplane_mac(jnp.asarray(ua), jnp.asarray(uw)))
        dt = time.perf_counter() - t0
        assert np.array_equal(out, exact_ref(ua, uw)), "bitplane_mac 8x8"
        log(f"kernel bitplane_mac 8x8 {SLOTS}x{k}@{k}x{n}: bit-exact vs the "
            f"integer matmul ({dt:.1f} s incl. compile)")

    qa = jnp.asarray(rng.integers(-127, 128, size=(SLOTS, d)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 128, size=(d, ff)), jnp.int8)
    out = np.asarray(imc_mac(qa, qw))
    assert np.array_equal(out, np.asarray(int_matmul(qa, qw))), "imc_mac"
    assert np.array_equal(out, exact_ref(qa, qw)), "imc_mac vs numpy"
    log(f"kernel imc_mac {SLOTS}x{d}@{d}x{ff}: bit-exact vs int_matmul")

    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mb = MAX_SEQ_LEN // BLOCK_SIZE
    nb = SLOTS * mb
    pos = rng.integers(0, MAX_SEQ_LEN, size=SLOTS).astype(np.int32)
    pos[0], pos[1] = 0, MAX_SEQ_LEN - 1
    perm = iter(rng.permutation(nb))
    table = np.full((SLOTS, mb), -1, np.int32)
    for i, p in enumerate(pos):
        for j in range(p // BLOCK_SIZE + 1):
            table[i, j] = next(perm)
    q = jnp.asarray(rng.standard_normal((SLOTS, 1, h, hd)), jnp.bfloat16)
    kf, vf = (jnp.asarray(rng.standard_normal((nb, BLOCK_SIZE, kv, hd)),
                          jnp.float32) for _ in range(2))
    pools = {"bf16": (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16), {}),
             "int8": (*(_kv_quant(x)[0] for x in (kf, vf)),
                      dict(k_scale=_kv_quant(kf)[1],
                           v_scale=_kv_quant(vf)[1]))}
    for name, (kp, vp, kw) in pools.items():
        args = (q, kp, vp, jnp.asarray(table), jnp.asarray(pos))
        ref = paged_attention(*args, impl="jnp", **kw)
        out = paged_attention(*args, impl="pallas", **kw)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert err <= PAGED_ATOL[name], f"paged_attn {name}: {err}"
        log(f"kernel paged_attn {name} pools {nb}x{BLOCK_SIZE}x{kv}x{hd}, "
            f"{SLOTS} slots up to {MAX_SEQ_LEN}: max |err| {err:.2e} <= "
            f"{PAGED_ATOL[name]}")

    sigma = NoiseSpec.calibrated().mismatch_sigma
    ua = jnp.asarray(rng.integers(0, 256, size=(SLOTS, d)), jnp.int32)
    uw = jnp.asarray(rng.integers(0, 256, size=(d, ff)), jnp.int32)
    y1, y2 = (np.asarray(bitplane_mac_noisy(ua, uw, jax.random.key(seed),
                                            mismatch_sigma=sigma))
              for _ in range(2))
    y3 = np.asarray(bitplane_mac_noisy(ua, uw, jax.random.key(seed + 1),
                                       mismatch_sigma=sigma))
    assert np.isfinite(y1).all() and np.array_equal(y1, y2), "noisy keyed"
    dev = y1 - exact_ref(ua, uw)
    log(f"kernel bitplane_mac_noisy calibrated sigma={sigma} "
        f"{SLOTS}x{d}@{d}x{ff}: same key identical; deviation mean "
        f"{dev.mean():.3f} std {dev.std():.3f}; another key differs in "
        f"{int((y1 != y3).sum())} of {y1.size}")
    # Moments against the keyed jnp engine, as tests/test_bitplane_noise.py
    # pins them: replicated rows make every output row an iid trial.
    sigmas = dict(mismatch_sigma=0.3, comparator_offset_sigma=0.03)
    row = rng.integers(0, 16, size=(1, 64), dtype=np.int32)
    ta = jnp.asarray(np.repeat(row, 256, axis=0))
    tw = jnp.asarray(rng.integers(0, 16, size=(64, 8), dtype=np.int32))
    exact = exact_ref(ta, tw)
    dk = (np.asarray(bitplane_mac_noisy(ta, tw, jax.random.key(0), bits_a=4,
                                        bits_w=4, **sigmas)) - exact).ravel()
    dj = (np.asarray(bitserial_matmul_unsigned(
        ta, tw, bits_a=4, bits_w=4, mode="sim", key=jax.random.key(1),
        rbl_mode="physics", **sigmas)) - exact).ravel()
    s = dj.std()
    assert s > 0 and abs(dk.mean() - dj.mean()) < 0.15 * s, (dk.mean(), s)
    assert 0.85 < dk.std() / s < 1.15, (dk.std(), s)
    for pct in (10, 25, 50, 75, 90):
        assert abs(np.percentile(dk, pct) - np.percentile(dj, pct)) < 0.15 * s
    log(f"kernel bitplane_mac_noisy vs keyed jnp engine: std {dk.std():.3f} "
        f"vs {s:.3f}, mean {dk.mean():.3f} vs {dj.mean():.3f}")


# --------------------------------------------------------------- serving
def make_requests(cfg, seed: int, n: int, max_new: int):
    import numpy as np

    from repro.launch.server import Request

    rng = np.random.default_rng(seed)
    lengths = [5, BUCKETS[0], BUCKETS[-1], 77, 19, 100, 33, 64][:n]
    return [Request(rng.integers(0, cfg.vocab_size, size=n_tok)
                    .astype(np.int32), max_new_tokens=max_new)
            for n_tok in lengths]


def server_kw():
    return dict(slots=SLOTS, kv="paged", block_size=BLOCK_SIZE,
                buckets=BUCKETS, max_seq_len=MAX_SEQ_LEN)


def serve_two_waves(server, requests, *, replay: bool = True):
    """Serve ``requests`` twice; the second wave must compile nothing and,
    with ``replay`` (no noise: each tick draws fresh noise keys), emit the
    first wave's greedy tokens again."""
    waves = []
    traces = []
    for _ in range(2):
        handles = [server.submit(r) for r in requests]
        server.drain()
        bad = [h.reason for h in handles if h.status != "done"]
        assert not bad, f"rejected: {bad}"
        waves.append([list(h.tokens) for h in handles])
        traces.append(server.engine.stats.traces)
    assert traces[1] == traces[0], f"steady-state retraces: {traces}"
    assert not replay or waves[1] == waves[0], \
        "second wave changed the greedy tokens"
    return waves[0], traces[0]


def slice_layers(cfg, params, n_layers: int):
    """The first ``n_layers`` layers of a scanned single-pattern stack."""
    import jax

    assert len(cfg.pattern) == 1 and not cfg.tail
    blocks = dict(params["blocks"])
    blocks["groups"] = jax.tree.map(lambda x: x[:n_layers], blocks["groups"])
    return (dataclasses.replace(cfg, n_layers=n_layers),
            {**params, "blocks": blocks})


def serve_specs(cfg, params, seed: int, clock: CompileClock) -> None:
    import jax

    from repro.core.fabric import FabricSpec, NoiseSpec
    from repro.launch.engine import Engine
    from repro.launch.server import Server

    device = jax.devices()[0]
    fcfg, fparams = slice_layers(cfg, params, FABRIC_LAYERS)
    log(f"fabric phases serve layers 0..{FABRIC_LAYERS - 1} of "
        f"{cfg.n_layers} (layer count cut for time; widths unchanged)")
    phases = [
        ("float", cfg, params, None, 6, 8),
        ("exact", fcfg, fparams, FabricSpec(mode="exact"), 4, 3),
        ("sim", fcfg, fparams, FabricSpec(mode="sim"), 4, 3),
        ("sim+noise", fcfg, fparams,
         FabricSpec(mode="sim", noise=NoiseSpec.calibrated()), 4, 3),
    ]
    tokens = {}
    for name, pcfg, pparams, spec, n_req, max_new in phases:
        pcfg = dataclasses.replace(pcfg, fabric=spec)
        label = spec.label if spec is not None else "float"
        assert spec is None or label.split("+")[0].endswith("/pallas"), label
        requests = make_requests(pcfg, seed, n_req, max_new)
        engine = Engine(noise_seed=seed)
        t0 = time.perf_counter()
        with engine.activate():
            server = Server(pcfg, pparams, engine=engine, **server_kw())
            assert server.attn_impl == "pallas", server.attn_impl
            toks, traces = serve_two_waves(
                server, requests, replay=spec is None or not spec.noisy)
        dt = time.perf_counter() - t0
        n_tok = sum(len(t) for t in toks)
        tokens[name] = toks
        log(f"serve {name}: spec={label} attn={server.attn_impl} "
            f"layers={pcfg.n_layers} d_model={pcfg.d_model} d_ff={pcfg.d_ff} "
            f"vocab={pcfg.vocab_size}; {len(requests)} requests x 2 waves, "
            f"{n_tok} tokens per wave, traces={traces} (none after warm-up); "
            f"compile s {clock.take()}; {dt:.1f} s total; "
            f"peak_bytes_in_use={peak_bytes(device)}")
        assert all(0 <= t < pcfg.vocab_size for s in toks for t in s)
        del server, engine
    assert tokens["sim"] == tokens["exact"], \
        f"sim {tokens['sim']} != exact {tokens['exact']}"
    same = sum(a == b for x, y in zip(tokens["sim+noise"], tokens["exact"])
               for a, b in zip(x, y))
    log(f"noise-free sim greedy tokens equal exact's; calibrated-noise sim "
        f"agrees on {same} of {sum(map(len, tokens['exact']))}")


def serve_fleet(cfg, params, seed: int, clock: CompileClock) -> None:
    import jax

    from repro.fleet import FleetEngine, FleetServer, LocalCoordinator
    from repro.launch.engine import Engine
    from repro.launch.mesh import make_submesh
    from repro.launch.server import Server

    devices = jax.devices()
    requests = make_requests(cfg, seed, 8, 8)
    engine = Engine(mesh=make_submesh(devices[:1]), noise_seed=seed)
    with engine.activate():
        one = Server(cfg, params, engine=engine, **server_kw())
        ref = [one.submit(r) for r in requests]
        one.drain()
    log(f"serve one-chip oracle on {devices[0]}: compile s {clock.take()}")
    coord = LocalCoordinator(len(devices))
    fleet = FleetServer(cfg, params, FleetEngine(coord, noise_seed=seed),
                        **server_kw())
    handles = [fleet.submit(r) for r in requests]
    fleet.drain()
    assert {h.host for h in handles} == set(range(len(devices)))
    for h, r in zip(handles, ref):
        assert h.status == "done" and h.tokens == r.tokens, \
            (h.rid, h.host, h.tokens, r.tokens)
    for host in coord.hosts():
        srv = fleet.servers[host.index]
        placed = set()
        for leaf in jax.tree.leaves((srv.params, srv.cache)):
            placed |= leaf.devices()
        assert placed == set(host.devices), (host.index, placed)
        log(f"replica {host.index}: params and KV pools on {host.devices}")
    log(f"FleetServer over {len(devices)} one-chip replicas: "
        f"{len(handles)} requests, token streams identical to the one-chip "
        f"Server; compile s {clock.take()}; peak_bytes_in_use by device "
        f"{[peak_bytes(d) for d in devices]}")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the FleetServer path and its one-chip "
                         "oracle")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")
    if d0.platform != "tpu":
        log("no TPU: nothing to check")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices")
        return 1

    from repro.configs import get_config
    from repro.models.model import init_params

    clock = CompileClock()
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # One jitted init: the stacked f32 draws then never sit on the device
    # all at once, and init compiles once instead of op by op.
    params = jax.block_until_ready(
        jax.jit(init_params, static_argnums=1)(jax.random.key(args.seed), cfg))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv x {cfg.hd}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}; params {n_bytes} bytes, "
        f"init {time.perf_counter() - t0:.1f} s (compile s {clock.take()})")

    if args.chips == 4:
        serve_fleet(cfg, params, args.seed, clock)
    else:
        check_kernels(cfg, args.seed)
        serve_specs(cfg, params, args.seed, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
