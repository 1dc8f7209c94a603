"""Autotuner tests: cache round-trip, trial-free warm runs, pins, and the
geometry token riding the Engine step-cache key."""
import json

import pytest

from repro.configs import get_config, reduce_config
from repro.kernels import autotune
from repro.kernels.autotune.tuner import DEFAULTS
from repro.launch.engine import Engine
from repro.telemetry import get_registry

TINY_SHAPES = {"m": 8, "k": 16, "n": 8, "ba": 2, "bw": 2}
TINY_SPACE = [{"bm": 8, "bn": 8, "bk": 8}, {"bm": 8, "bn": 8, "bk": 16}]


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("qwen2.5-3b"))


@pytest.fixture(autouse=True)
def _restore_default_cache():
    yield
    autotune.set_cache(None)  # re-resolve the committed cache afterwards


def _trials():
    return get_registry().counter("autotune.trials").value


# ------------------------------------------------------------ buckets/keys
def test_shape_bucket_rounds_up_to_pow2():
    assert autotune.shape_bucket({"m": 100, "k": 512, "n": 1}) == \
        "k512_m128_n1"
    # nearby shapes share a bucket; order of dict keys is irrelevant
    assert autotune.shape_bucket({"k": 400, "m": 65}) == \
        autotune.shape_bucket({"m": 128, "k": 300})


def test_backend_key_marks_interpret():
    assert autotune.backend_key(True).endswith("+interpret")
    assert not autotune.backend_key(False).endswith("+interpret")


# ------------------------------------------------------- cold/warm tuning
def test_cold_tune_then_warm_is_trial_free(tmp_path):
    cache = autotune.AutotuneCache(path=str(tmp_path / "tuned.json"))
    before = _trials()
    geom = autotune.tune("bitplane_mac", TINY_SHAPES, TINY_SPACE,
                         repeats=1, warmup=0, cache=cache)
    cold_trials = _trials() - before
    assert cold_trials == len(TINY_SPACE)
    assert geom in [{**DEFAULTS["bitplane_mac"], **c} for c in TINY_SPACE]
    # the winner landed on disk with its timing
    rec = json.loads((tmp_path / "tuned.json").read_text())
    (entry,) = rec["entries"].values()
    assert entry["geometry"] == geom and entry["us"] > 0
    # warm: same cell resolves from the cache with ZERO further trials
    before = _trials()
    assert autotune.tune("bitplane_mac", TINY_SHAPES, TINY_SPACE,
                         cache=cache) == geom
    assert _trials() == before
    # and a fresh cache object round-trips the same file
    reloaded = autotune.AutotuneCache(path=str(tmp_path / "tuned.json"))
    before = _trials()
    assert autotune.tune("bitplane_mac", TINY_SHAPES, TINY_SPACE,
                         cache=reloaded) == geom
    assert _trials() == before


def test_committed_cache_covers_standard_cells_trial_free():
    """The CI guarantee: the repo's tuned.json answers every cell
    ``tune_standard`` would tune, so CI never runs a trial."""
    before = _trials()
    rows = autotune.tune_standard(smoke=True)
    assert _trials() == before
    assert {r[0] for r in rows} == {"bitplane_mac", "paged_attn"}


# ------------------------------------------------------- lookup precedence
def test_lookup_defaults_cache_pin_precedence(tmp_path, monkeypatch):
    cache = autotune.AutotuneCache(path=str(tmp_path / "t.json"))
    shapes = {"m": 8, "k": 16, "n": 8}
    # nothing known: hardcoded defaults
    assert autotune.lookup("bitplane_mac", shapes, cache=cache) == \
        DEFAULTS["bitplane_mac"]
    # cached winner overrides defaults
    cache.store("bitplane_mac", autotune.shape_bucket(shapes), "int8",
                autotune.backend_key(False), {"bm": 8, "bn": 8, "bk": 16},
                1.0, 2)
    assert autotune.lookup("bitplane_mac", shapes, cache=cache,
                           interpret=False) == \
        {"bm": 8, "bn": 8, "bk": 16}
    # env pin overrides everything (partial pins merge)
    monkeypatch.setenv("REPRO_TUNE_BITPLANE_MAC", "bm=32")
    got = autotune.lookup("bitplane_mac", shapes, cache=cache,
                          interpret=False)
    assert got == {"bm": 32, "bn": 8, "bk": 16}


def test_malformed_pin_raises(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_BITPLANE_MAC", "bm=big")
    with pytest.raises(ValueError, match="REPRO_TUNE_BITPLANE_MAC"):
        autotune.env_pins()


# --------------------------------------------------------- geometry token
def test_geometry_token_tracks_stores_and_pins(tmp_path, monkeypatch):
    t0 = autotune.geometry_token()
    assert autotune.geometry_token() == t0  # stable while nothing changes
    cache = autotune.AutotuneCache(path=str(tmp_path / "t.json"))
    cache.store("bitplane_mac", "m8", "int8", "cpu", {"bm": 8}, 1.0, 1)
    t1 = autotune.geometry_token()
    assert t1 != t0
    monkeypatch.setenv("REPRO_TUNE_PAGED_ATTN", "bps=4")
    t2 = autotune.geometry_token()
    assert t2 != t1 and ("paged_attn", (("bps", 4),)) in t2[1]


def test_geometry_token_busts_engine_step_cache(tmp_path):
    eng = Engine()
    cfg = reduce_config(get_config("qwen2.5-3b"))
    d1 = eng.decode_step(cfg)
    # steady state: repeated requests reuse the executable (zero retraces)
    assert eng.decode_step(cfg) is d1
    assert eng.stats.compiles == 1 and eng.stats.hits == 1
    # a re-tune anywhere moves the token -> the step must rebuild
    cache = autotune.AutotuneCache(path=str(tmp_path / "t.json"))
    cache.store("paged_attn", "b4", "int8", "cpu+interpret", {"bps": 2},
                1.0, 1)
    d2 = eng.decode_step(cfg)
    assert d2 is not d1 and eng.stats.compiles == 2
    # and is stable again afterwards
    assert eng.decode_step(cfg) is d2


# ------------------------------------------------------------ kernel wiring
def test_paged_attention_honors_blocks_per_step_pin(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attn.ops import paged_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 1, 2, 64)).astype(np.float32))
    pools = rng.normal(size=(2, 8, 16, 2, 64)).astype(np.float32)
    kp, vp = jnp.asarray(pools[0]), jnp.asarray(pools[1])
    tbl = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    pos = jnp.asarray([63, 63], jnp.int32)
    ref = paged_attention(q, kp, vp, tbl, pos, impl="jnp")
    monkeypatch.setenv("REPRO_TUNE_PAGED_ATTN", "bps=3")
    out = paged_attention(q, kp, vp, tbl, pos, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_cached_bitplane_geometries_tile_their_padded_operands():
    """Every committed ``bitplane_mac`` geometry, with ``bm`` read as rows
    of the plane-folded (activation plane, row) axis, tiles the operands
    the wrapper pads for its bucket's shape, and the kernel runs on them."""
    import re

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.bitplane_mac.ops import _padded_planes, bitplane_mac

    committed = autotune.AutotuneCache(path=autotune.default_cache_path())
    keys = [k for k in committed.entries if k.startswith("bitplane_mac|")]
    assert keys
    for key in keys:
        bucket = key.split("|")[1]
        dims = {d: int(v) for d, v in re.findall(r"([a-z]+)(\d+)", bucket)}
        g = committed.entries[key]["geometry"]
        m, k, n, ba, bw = (dims[x] for x in ("m", "k", "n", "ba", "bw"))
        ua = jnp.zeros((m, k), jnp.int32)
        uw = jnp.zeros((k, n), jnp.int32)
        rows, w_planes, m_pad, bm = _padded_planes(ua, uw, ba, bw, g["bm"],
                                                   g["bn"], g["bk"])
        assert m_pad % 8 == 0 and bm == min(g["bm"], ba * m_pad)
        assert rows.shape[0] % bm == 0 and rows.shape[0] >= ba * m_pad
        assert rows.shape[1] % g["bk"] == 0 == w_planes.shape[1] % g["bk"]
        assert w_planes.shape[2] % g["bn"] == 0 and g["bk"] % 8 == 0
    # the smallest cached cell end to end, through the committed geometry
    rng = np.random.default_rng(3)
    ua = jnp.asarray(rng.integers(0, 16, size=(4, 64)), jnp.int32)
    uw = jnp.asarray(rng.integers(0, 16, size=(64, 32)), jnp.int32)
    out = bitplane_mac(ua, uw, bits_a=4, bits_w=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))
