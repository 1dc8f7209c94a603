"""Paged flash-decode kernel tests: parity vs the jnp gather oracle + wiring.

The kernel (``kernels/paged_attn``) must reproduce the dense-gather oracle
(``ref.paged_decode_ref`` — the exact pre-kernel serving computation) across
bf16/int8 pools, GQA, sliding windows, and ragged block tables with ``-1``
sentinel rows and partially-filled last blocks.  Expected agreement:

  * f32 pools: ~1e-6 (same f32 contraction, different-but-benign reduction
    grouping across blocks).
  * bf16 pools: within ~2 output ulp.  Exact bit-equality is unattainable in
    principle: online softmax rescales past contributions by exp(m_old-m_new)
    while one-shot softmax exponentiates once, so the two round differently.
    The CPU's engine (the jnp gather) remains the bit-exact path.
  * int8 pools: atol 1e-2 (quantization noise dominates; the kernel
    dequantizes in-register, the oracle pre-dequantizes — same scales).

Everything runs in Pallas interpret mode on CPU (``interpret=None`` resolves
via ``kernels.compat``), so CI exercises the kernel body on every PR.

Wiring tests pin the end-to-end story: ``attn_decode`` parity between the
two engines at the layer level with a shared (bit-identical) cache scatter,
the platform's choice of engine (``resolve_impl``, reported by
``Server.attn_impl``), zero steady-state retraces through the Server with the
kernel pinned, and flash prefill (``use_flash``) producing the same decode
cache bit-for-bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.kernels.paged_attn import ops
from repro.kernels.paged_attn.ops import paged_attention
from repro.launch.engine import Engine
from repro.launch.server import Request, Server
from repro.models.attention import (AttnCache, PagedAttnCache, _kv_quant,
                                    attn_decode, attn_prefill, init_attention)
from repro.models.model import init_params

ATOL = {"f32": 5e-6, "bf16": 1.6e-2, "int8": 1e-2}


def _pools(rng, nb, bs, kv, hd, dtype=jnp.float32):
    k = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), dtype)
    return k, v


def _ragged(rng, pos, mb, nb, bs):
    """Dense-prefix tables covering each row's pos; partially-filled last
    blocks whenever pos+1 is not a block multiple; -1 sentinels after."""
    b = len(pos)
    tbl = np.full((b, mb), -1, np.int32)
    perm = iter(rng.permutation(nb))
    for i, p in enumerate(pos):
        for j in range(p // bs + 1):
            tbl[i, j] = next(perm)
    return jnp.asarray(tbl)


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


# ------------------------------------------------------------- op-level parity
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("window", [0, 9])
@pytest.mark.parametrize("kv_heads", [4, 2, 1])  # H=4: MHA, GQA, MQA
def test_kernel_matches_oracle(dtype, window, kv_heads):
    rng = np.random.default_rng(0)
    B, H, hd, bs, nb, mb = 3, 4, 16, 8, 14, 4
    jdt = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jdt)
    kp, vp = _pools(rng, nb, bs, kv_heads, hd, jdt)
    pos = np.array([5, 17, 26])  # straddles block edges, partial last blocks
    tbl = _ragged(rng, pos, mb, nb, bs)
    kw = dict(window=window)
    ref = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="jnp", **kw)
    out = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="pallas", **kw)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert _err(ref, out) <= ATOL[dtype], (dtype, window, kv_heads)


@pytest.mark.parametrize("window", [0, 9])
def test_kernel_matches_oracle_int8(window):
    rng = np.random.default_rng(1)
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 16, 8, 14, 4
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.bfloat16)
    kf, vf = _pools(rng, nb, bs, KV, hd)
    kq, ks = _kv_quant(kf)
    vq, vs = _kv_quant(vf)
    pos = np.array([5, 17, 26])
    tbl = _ragged(rng, pos, mb, nb, bs)
    kw = dict(k_scale=ks, v_scale=vs, window=window)
    ref = paged_attention(q, kq, vq, tbl, jnp.asarray(pos), impl="jnp", **kw)
    out = paged_attention(q, kq, vq, tbl, jnp.asarray(pos), impl="pallas", **kw)
    assert _err(ref, out) <= ATOL["int8"]


def test_kernel_inactive_slot_is_finite():
    """A slot with an all-sentinel table (nothing admitted) must not poison
    the batch: the kernel flushes exact zeros, the oracle garbage — both
    unused, but NaN/inf would taint downstream reductions."""
    rng = np.random.default_rng(2)
    B, H, hd, bs, nb, mb = 2, 4, 16, 8, 10, 3
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, 2, hd)
    pos = np.array([13, 0])
    tbl = _ragged(rng, pos, mb, nb, bs).at[1].set(-1)
    out = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="pallas")
    ref = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="jnp")
    assert bool(jnp.all(jnp.isfinite(out)))
    assert bool(jnp.all(out[1] == 0))
    assert _err(ref[0], out[0]) <= ATOL["f32"]  # active row still matches


def test_kernel_single_and_full_tables():
    """Degenerate geometries: one block per slot, and a completely full
    table (pos on the last row of the last block)."""
    rng = np.random.default_rng(3)
    B, H, hd, bs, nb = 2, 2, 8, 4, 6
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, 2, hd)
    for mb, pos in ((1, [0, 3]), (3, [11, 7])):
        tbl = _ragged(rng, np.asarray(pos), mb, nb, bs)
        ref = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="jnp")
        out = paged_attention(q, kp, vp, tbl, jnp.asarray(pos), impl="pallas")
        assert _err(ref, out) <= ATOL["f32"]


@pytest.mark.parametrize("bps", [2, 3, 4, 8])
def test_kernel_bit_identical_across_blocks_per_step(bps):
    """The multi-block-per-grid-step variant packs bps pool-panel DMAs into
    one step but walks blocks in the same order, so it must be BIT-identical
    to bps=1 — on f32 pools, int8 pools, windows, and ragged tables (incl.
    the mb % bps tail)."""
    rng = np.random.default_rng(5)
    B, H, KV, hd, bs, nb, mb = 3, 4, 2, 16, 8, 22, 7
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)
    pos = np.array([5, 30, 51])
    tbl = _ragged(rng, pos, mb, nb, bs)
    for kw in (dict(), dict(window=9)):
        base = paged_attention(q, kp, vp, tbl, jnp.asarray(pos),
                               impl="pallas", blocks_per_step=1, **kw)
        out = paged_attention(q, kp, vp, tbl, jnp.asarray(pos),
                              impl="pallas", blocks_per_step=bps, **kw)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(out))
    kq, ks = _kv_quant(kp)
    vq, vs = _kv_quant(vp)
    kw = dict(k_scale=ks, v_scale=vs)
    base = paged_attention(q, kq, vq, tbl, jnp.asarray(pos), impl="pallas",
                           blocks_per_step=1, **kw)
    out = paged_attention(q, kq, vq, tbl, jnp.asarray(pos), impl="pallas",
                          blocks_per_step=bps, **kw)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


def test_op_rejects_unknown_impl():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((1, 1, 2, 8)), jnp.float32)
    kp, vp = _pools(rng, 2, 4, 2, 8)
    tbl = jnp.zeros((1, 1), jnp.int32)
    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, kp, vp, tbl, jnp.asarray([0]), impl="tpu")


def test_kernel_parity_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    B, H, KV, hd, bs, mb = 3, 4, 2, 8, 4, 4
    nb = B * mb + 2
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
    kp, vp = _pools(rng, nb, bs, KV, hd)

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(st.lists(st.integers(0, mb * bs - 1), min_size=B, max_size=B),
               st.integers(0, 2 ** 31 - 1),
               st.sampled_from([0, 3, 10]))
    def run(pos, seed, window):
        tbl = _ragged(np.random.default_rng(seed), np.asarray(pos), mb, nb, bs)
        p = jnp.asarray(pos)
        ref = paged_attention(q, kp, vp, tbl, p, window=window, impl="jnp")
        out = paged_attention(q, kp, vp, tbl, p, window=window, impl="pallas")
        assert _err(ref, out) <= ATOL["f32"]

    run()


# ---------------------------------------------------------- layer-level wiring
def _pin_impl(monkeypatch, impl):
    """Make the platform's engine selector answer ``impl``."""
    monkeypatch.setattr(ops, "resolve_impl", lambda: impl)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_attn_decode_paged_impl_parity(kv_dtype, monkeypatch):
    """Through ``attn_decode``: both impls share one scatter (caches must be
    bit-identical) and agree on the mixed output within kernel tolerance."""
    rng = np.random.default_rng(6)
    d, H, KV, hd, bs, nb, mb, B = 32, 4, 2, 8, 4, 10, 3, 3
    params = init_attention(jax.random.key(0), d, H, KV, hd,
                            dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, 1, d)), jnp.float32)
    pos = jnp.asarray([5, 9, 2])
    tbl = _ragged(rng, np.asarray(pos), mb, nb, bs)
    if kv_dtype == "int8":
        kq, ks = _kv_quant(jnp.asarray(
            rng.standard_normal((nb, bs, KV, hd)), jnp.float32))
        vq, vs = _kv_quant(jnp.asarray(
            rng.standard_normal((nb, bs, KV, hd)), jnp.float32))
        cache = PagedAttnCache(kq, vq, ks, vs)
    else:
        kp, vp = _pools(rng, nb, bs, KV, hd)
        cache = PagedAttnCache(kp, vp)
    kw = dict(n_heads=H, n_kv_heads=KV, head_dim=hd, rope_theta=1e4,
              block_table=tbl)
    _pin_impl(monkeypatch, "jnp")
    y_j, c_j = attn_decode(params, x, cache, pos, **kw)
    _pin_impl(monkeypatch, "pallas")
    y_p, c_p = attn_decode(params, x, cache, pos, **kw)
    for a, b in zip(jax.tree.leaves(c_j), jax.tree.leaves(c_p)):
        assert jnp.array_equal(a, b), "impl switch changed the cache scatter"
    tol = {"bf16": 5e-5, "int8": 1e-2}[kv_dtype]  # f32 activations
    assert _err(y_j, y_p) <= tol


# --------------------------------------------------------------- server-level
LENGTHS = (7, 16, 33)
MAX_NEW = 4


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(get_config("qwen2.5-3b"))


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.key(0), cfg)


def _serve_wave(server, cfg, rng):
    hs = [server.submit(Request(
        rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
        max_new_tokens=MAX_NEW)) for n in LENGTHS]
    server.drain()
    assert all(h.done and len(h.tokens) == MAX_NEW for h in hs)
    return hs


@pytest.mark.parametrize("backend,impl", [("cpu", "jnp"), ("tpu", "pallas")])
def test_server_attn_impl_selection(cfg, params, monkeypatch, backend, impl):
    """The platform picks the engine: the kernel on TPU, the gather (never
    the interpreted kernel) elsewhere; the Server reports that choice."""
    eng = Engine()
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops.resolve_impl() == impl
    with eng.activate():
        srv = Server(cfg, params, engine=eng, slots=2, block_size=8,
                     buckets=(16,), max_seq_len=24)
    assert srv.attn_impl == impl


def test_server_pallas_zero_steady_state_retraces(cfg, params, monkeypatch):
    """Two identical ragged waves through the pinned kernel (+ flash
    prefill): wave 2 must reuse every compiled step — the kernel rides inside
    the jitted decode step without adding trace keys."""
    _pin_impl(monkeypatch, "pallas")
    c = dataclasses.replace(cfg, use_flash_kernel=True)
    eng = Engine()
    rng = np.random.default_rng(0)
    with eng.activate():
        srv = Server(c, params, engine=eng, slots=2, block_size=8,
                     buckets=(16, 48), max_seq_len=48 + MAX_NEW)
        _serve_wave(srv, c, rng)
        warm = eng.stats.traces
        _serve_wave(srv, c, rng)
        assert eng.stats.traces == warm, \
            f"steady-state retrace: {warm} -> {eng.stats.traces}"
    from repro.telemetry import serving_slos

    slos = serving_slos(eng.registry, attn_impl=srv.attn_impl)
    assert slos["attn_impl"] == "pallas"
    assert slos["ttft_ms"] is not None and slos["tpot_ms"] is not None


# ------------------------------------------------------------- flash prefill
@pytest.mark.parametrize("window", [0, 16])
def test_flash_prefill_matches_chunked(window):
    """``use_flash`` prefill: same decode cache bit-for-bit (the cache is
    built from the projections, not the mixed output) and the mixed output
    within flash tolerance — including a right-padded ragged prompt."""
    rng = np.random.default_rng(7)
    d, H, KV, hd, S = 32, 4, 2, 8, 24
    params = init_attention(jax.random.key(1), d, H, KV, hd,
                            dtype=jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, S, d)), jnp.float32)
    kw = dict(n_heads=H, n_kv_heads=KV, head_dim=hd, rope_theta=1e4,
              window=window, cache_len=S, true_len=jnp.asarray(17))
    y0, c0 = attn_prefill(params, x, use_flash=False, **kw)
    y1, c1 = attn_prefill(params, x, use_flash=True, **kw)
    assert isinstance(c1, AttnCache)
    for a, b in zip(jax.tree.leaves(c0), jax.tree.leaves(c1)):
        assert jnp.array_equal(a, b), "flash prefill changed the cache"
    # valid (non-padded) rows agree; padded-tail rows are never consumed
    assert _err(y0[:, :17], y1[:, :17]) <= 2e-4
