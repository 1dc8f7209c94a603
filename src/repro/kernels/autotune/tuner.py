"""Kernel autotuner: search-measure-cache for Pallas tile geometry.

The kernels ship with hardcoded tile guesses (``bitplane_mac``'s
(bm, bn, bk) = (128, 128, 256), ``paged_attn``'s one pool panel per grid
step).  This module replaces guesses with measurements:

  * :func:`tune` times REAL ``pallas_call``s over a candidate space and
    caches the winner per ``(kernel, shape-bucket, dtype, backend)``.
  * :func:`lookup` is what the kernel ``ops`` wrappers call at trace time:
    defaults <- cached winner <- ``REPRO_TUNE_<KERNEL>`` env pin, most
    specific wins.  A lookup NEVER runs trials — tuning is explicit
    (``benchmarks.run --autotune`` or :func:`tune` directly).
  * the cache is a JSON file committed to the repo
    (``src/repro/kernels/autotune/tuned.json``), so CI runs are
    deterministic and trial-free; re-tuning on new hardware rewrites it
    (``REPRO_AUTOTUNE_CACHE`` points elsewhere without touching the
    committed file).
  * :func:`geometry_token` is a tiny hashable snapshot of "which geometry
    would lookups resolve to right now" — the launch Engine folds it into
    its compiled-step cache key, so a re-tune (or an env pin change) can
    never reuse a stale executable, while a stable cache keeps steady state
    at zero retraces.

Telemetry: every measured candidate increments ``autotune.trials`` and each
``tune`` call runs under an ``autotune.tune`` span — a warm (fully cached)
run is observable as zero trials.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

from repro.telemetry import clock, get_registry, span

# Hardcoded fallbacks == the pre-autotuner kernel defaults, so a missing
# cache entry reproduces historical behavior exactly.
DEFAULTS: Dict[str, Dict[str, int]] = {
    "bitplane_mac": {"bm": 128, "bn": 128, "bk": 256},
    "paged_attn": {"bps": 1},
}

_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_PIN_PREFIX = "REPRO_TUNE_"  # REPRO_TUNE_BITPLANE_MAC="bm=64,bn=128,bk=128"

# Bumped on every cache mutation (store/load/clear) — the cheap global the
# geometry token watches so Engine step caches notice re-tunes.
_VERSION = 0


def _bump() -> None:
    global _VERSION
    _VERSION += 1


def default_cache_path() -> str:
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    return os.path.join(os.path.dirname(__file__), "tuned.json")


def _pow2_bucket(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def shape_bucket(shapes: Dict[str, int]) -> str:
    """Canonical bucket string: each dim rounded up to a power of two.

    Nearby shapes share one tuned geometry (tile choice is insensitive to
    e.g. m=500 vs m=512), keeping the cache small and lookups exact-match.
    """
    return "_".join(f"{k}{_pow2_bucket(int(v))}"
                    for k, v in sorted(shapes.items()))


def backend_key(interpret: bool) -> str:
    """Cache axis for the execution engine: interpret mode is its own
    backend (interpreter-optimal tiles are NOT Mosaic-optimal tiles)."""
    import jax

    b = jax.default_backend()
    return f"{b}+interpret" if interpret else b


def _parse_pin(text: str) -> Dict[str, int]:
    out = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def env_pins() -> Dict[str, Dict[str, int]]:
    """{kernel: geometry} pinned via REPRO_TUNE_<KERNEL> env vars."""
    pins = {}
    for name, val in os.environ.items():
        if name.startswith(_ENV_PIN_PREFIX) and name != _ENV_CACHE:
            kernel = name[len(_ENV_PIN_PREFIX):].lower()
            try:
                pins[kernel] = _parse_pin(val)
            except ValueError:
                raise ValueError(
                    f"malformed {name}={val!r}; expected 'k=v,k=v' ints")
    return pins


class AutotuneCache:
    """Persistent JSON store of tuned geometries.

    Entries: ``{key: {"geometry": {...}, "us": float, "trials": int}}`` with
    ``key = kernel|bucket|dtype|backend``.  ``store`` persists immediately
    (atomic-enough single write) and bumps the global geometry version.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self.entries: Dict[str, Dict] = {}
        if os.path.exists(self.path):
            self.load()

    @staticmethod
    def key(kernel: str, bucket: str, dtype: str, backend: str) -> str:
        return "|".join((kernel, bucket, dtype, backend))

    def load(self) -> None:
        with open(self.path) as f:
            rec = json.load(f)
        self.entries = rec.get("entries", {})
        _bump()

    def save(self) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        with open(self.path, "w") as f:
            json.dump({"format": 1, "entries": self.entries}, f, indent=1,
                      sort_keys=True)
            f.write("\n")

    def lookup(self, kernel: str, bucket: str, dtype: str,
               backend: str) -> Optional[Dict[str, int]]:
        e = self.entries.get(self.key(kernel, bucket, dtype, backend))
        return dict(e["geometry"]) if e else None

    def store(self, kernel: str, bucket: str, dtype: str, backend: str,
              geometry: Dict[str, int], us: float, trials: int) -> None:
        self.entries[self.key(kernel, bucket, dtype, backend)] = {
            "geometry": dict(geometry), "us": round(float(us), 2),
            "trials": int(trials)}
        self.save()
        _bump()


_CACHE: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    global _CACHE
    if _CACHE is None or _CACHE.path != default_cache_path():
        _CACHE = AutotuneCache()
    return _CACHE


def set_cache(cache: Optional[AutotuneCache]) -> None:
    """Swap the process cache (tests; ``None`` re-resolves from env)."""
    global _CACHE
    _CACHE = cache
    _bump()


def geometry_token() -> Tuple:
    """Hashable snapshot of the ambient tuning state.

    Equal tokens guarantee every ``lookup`` resolves identically, so
    compiled steps keyed on the token retrace exactly when a re-tune (or a
    pin change) could alter kernel geometry — and never otherwise.
    """
    pins = tuple(sorted((k, tuple(sorted(v.items())))
                        for k, v in env_pins().items()))
    return (_VERSION, pins)


def lookup(kernel: str, shapes: Dict[str, int], *, dtype: str = "int8",
           interpret: bool = False,
           cache: Optional[AutotuneCache] = None) -> Dict[str, int]:
    """Resolve geometry for one kernel call (trace-time; never measures).

    Precedence: :data:`DEFAULTS` <- cached tune winner <- env pin.
    """
    geom = dict(DEFAULTS.get(kernel, {}))
    c = cache if cache is not None else get_cache()
    hit = c.lookup(kernel, shape_bucket(shapes), dtype,
                   backend_key(interpret))
    if hit:
        geom.update(hit)
    pin = env_pins().get(kernel)
    if pin:
        geom.update(pin)
    return geom


# ------------------------------------------------------------- measurement
def _time_call(fn, *args, repeats: int, warmup: int, **kw) -> float:
    """Best-of wall time per call in microseconds (device-complete)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kw))
    best = float("inf")
    for _ in range(repeats):
        t0 = clock()
        jax.block_until_ready(fn(*args, **kw))
        best = min(best, clock() - t0)
    return best * 1e6


def _measure_bitplane_mac(shapes: Dict[str, int], geom: Dict[str, int],
                          interpret: bool, repeats: int, warmup: int) -> float:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.bitplane_mac.ops import bitplane_mac

    m, k, n = shapes["m"], shapes["k"], shapes["n"]
    ba, bw = shapes.get("ba", 8), shapes.get("bw", 8)
    rng = np.random.default_rng(0)
    ua = jnp.asarray(rng.integers(0, 1 << ba, size=(m, k)).astype(np.int32))
    uw = jnp.asarray(rng.integers(0, 1 << bw, size=(k, n)).astype(np.int32))
    return _time_call(bitplane_mac, ua, uw, bits_a=ba, bits_w=bw,
                      bm=geom["bm"], bn=geom["bn"], bk=geom["bk"],
                      interpret=interpret, repeats=repeats, warmup=warmup)


def _measure_paged_attn(shapes: Dict[str, int], geom: Dict[str, int],
                        interpret: bool, repeats: int, warmup: int) -> float:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attn.ops import paged_attention

    b = shapes.get("b", 4)
    kv = shapes.get("kv", 2)
    h = kv * shapes.get("rep", 2)
    hd = shapes.get("hd", 64)
    bs = shapes.get("bs", 16)
    mb = shapes.get("mb", 8)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)).astype(np.float32))
    # int8 pools + scale pools: the serving quantized layout (and the cache
    # cell's dtype key).
    pools = rng.integers(-127, 128, size=(2, b * mb, bs, kv, hd))
    kp, vp = (jnp.asarray(p, jnp.int8) for p in pools)
    sc = jnp.asarray(rng.uniform(0.01, 0.02, size=(b * mb, bs, kv)),
                     jnp.float32)
    table = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    pos = jnp.full((b,), mb * bs - 1, jnp.int32)
    return _time_call(paged_attention, q, kp, vp, table, pos, k_scale=sc,
                      v_scale=sc, impl="pallas",
                      blocks_per_step=geom["bps"], interpret=interpret,
                      repeats=repeats, warmup=warmup)


_MEASURE = {
    "bitplane_mac": _measure_bitplane_mac,
    "paged_attn": _measure_paged_attn,
}

# Default candidate spaces (small on purpose: tune() is explicit, and a
# committed cache makes CI trial-free).
SPACES: Dict[str, List[Dict[str, int]]] = {
    "bitplane_mac": [
        {"bm": bm, "bn": bn, "bk": bk}
        for bm in (64, 128) for bn in (64, 128) for bk in (128, 256)
    ],
    "paged_attn": [{"bps": bps} for bps in (1, 2, 4)],
}


def tune(kernel: str, shapes: Dict[str, int],
         space: Optional[List[Dict[str, int]]] = None, *,
         dtype: str = "int8", interpret: Optional[bool] = None,
         repeats: int = 3, warmup: int = 1,
         cache: Optional[AutotuneCache] = None,
         registry=None) -> Dict[str, int]:
    """Measure every candidate and cache the winner; returns its geometry.

    Already-cached (kernel, bucket, dtype, backend) cells return instantly
    with ZERO trials — delete the cache entry (or point
    ``REPRO_AUTOTUNE_CACHE`` at a fresh file) to force a re-tune.
    """
    from repro.kernels.compat import resolve_interpret

    it = resolve_interpret(interpret)
    c = cache if cache is not None else get_cache()
    reg = registry if registry is not None else get_registry()
    bucket = shape_bucket(shapes)
    backend = backend_key(it)
    cached = c.lookup(kernel, bucket, dtype, backend)
    if cached is not None:
        return cached
    measure = _MEASURE[kernel]
    space = space if space is not None else SPACES[kernel]
    if not space:
        raise ValueError(f"empty candidate space for {kernel!r}")
    trials = reg.counter("autotune.trials")
    best_geom, best_us = None, float("inf")
    with span("autotune.tune", kernel=kernel, bucket=bucket,
              backend=backend):
        for cand in space:
            geom = {**DEFAULTS.get(kernel, {}), **cand}
            us = measure(shapes, geom, it, repeats, warmup)
            trials.inc()
            reg.histogram("autotune.trial_us").observe(us)
            if us < best_us:
                best_geom, best_us = geom, us
    c.store(kernel, bucket, dtype, backend, best_geom, best_us, len(space))
    return dict(best_geom)


# The reduced-arch serving GEMMs the ``sim/pallas+noise`` serve bench rows
# push through the fabric (qkv/o/mlp projections at decode m=4 slots and
# prefill m=16 bucket), and a small-tile space for them: at these shapes the
# win is minimizing padded volume, not MXU occupancy — on the interpreter
# the big default tiles are ~100x slower.
SERVE_CELLS: List[Dict[str, int]] = [
    {"m": m, "k": k, "n": n, "ba": 4, "bw": 4}
    for m in (4, 16)
    for k, n in ((64, 32), (64, 64), (64, 128), (128, 64))
]
SERVE_SPACE: List[Dict[str, int]] = [
    {"bm": 8, "bn": 32, "bk": 64},
    {"bm": 16, "bn": 64, "bk": 64},
    {"bm": 8, "bn": 64, "bk": 128},
]


def tune_standard(smoke: bool = True, registry=None) -> List[Tuple[str, str,
                                                                   Dict, str]]:
    """The bench CLI's ``--autotune`` entry: tune the serving-relevant cells.

    Covers the paper's 8x8 macro / 8-bit GEMM shape for ``bitplane_mac``,
    the reduced-arch serve-projection buckets (:data:`SERVE_CELLS`, what the
    noisy-pallas serve bench rows hit), and the pool-panel sweep for
    ``paged_attn``.  Returns (kernel, bucket, geometry, backend) rows for
    the CSV.
    """
    from repro.kernels.compat import resolve_interpret

    backend = backend_key(resolve_interpret(None))
    rows = []
    bitplane_shapes = [{"m": 64, "k": 512, "n": 64, "ba": 8, "bw": 8}]
    paged_shapes = [{"b": 4, "kv": 2, "rep": 2, "hd": 64, "bs": 16, "mb": 8}]
    if not smoke:
        bitplane_shapes.append(
            {"m": 256, "k": 1024, "n": 256, "ba": 8, "bw": 8})
        paged_shapes.append(
            {"b": 8, "kv": 4, "rep": 4, "hd": 64, "bs": 16, "mb": 32})
    space_bp = SPACES["bitplane_mac"]
    if smoke:  # interpreter trials are slow; keep the smoke space tiny
        space_bp = [g for g in space_bp if g["bm"] == g["bn"]]
    for shapes in bitplane_shapes:
        geom = tune("bitplane_mac", shapes, space_bp, registry=registry)
        rows.append(("bitplane_mac", shape_bucket(shapes), geom, backend))
    for shapes in SERVE_CELLS:
        geom = tune("bitplane_mac", shapes, SERVE_SPACE, registry=registry)
        rows.append(("bitplane_mac", shape_bucket(shapes), geom, backend))
    for shapes in paged_shapes:
        geom = tune("paged_attn", shapes, registry=registry)
        rows.append(("paged_attn", shape_bucket(shapes), geom, backend))
    return rows
