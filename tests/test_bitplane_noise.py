"""Noisy bitplane_mac kernel: statistical parity, determinism, independence.

The fused noisy kernel draws from a different PRNG stream than the keyed jnp
engine (Mosaic hardware PRNG / counter-hash vs threefry), so cross-engine
agreement is pinned STATISTICALLY — moments and quantiles of the decode
deviation over >= 1k iid trials, and detuned-threshold error-rate bands
against an independent numpy Monte-Carlo of the exact in-kernel semantics —
never bitwise.  Determinism (same fabric key -> identical outputs) and
stream independence across grid positions ARE exact properties and are
asserted exactly.

Trials technique: replicating one operand row M times makes every output row
an iid draw of the same decode distribution (noise is elementwise), so a
single kernel launch yields M x N samples.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitserial import bitserial_matmul_unsigned
from repro.core.decoder import thresholds as core_thresholds
from repro.core.rbl import rbl_voltage
from repro.kernels.bitplane_mac.ops import bitplane_mac_noisy

SIGMAS = dict(mismatch_sigma=0.3, comparator_offset_sigma=0.03)


def _trials(bits=4, m=256, k=64, n=8, seed=0):
    """Replicated-row operands: every output row is an iid noise trial."""
    rng = np.random.default_rng(seed)
    row = rng.integers(0, 1 << bits, size=(1, k)).astype(np.int32)
    ua = jnp.asarray(np.repeat(row, m, axis=0))
    uw = jnp.asarray(rng.integers(0, 1 << bits, size=(k, n)).astype(np.int32))
    return ua, uw, np.asarray(ua) @ np.asarray(uw)


# ---------------------------------------------------------- determinism
def test_same_key_identical_different_keys_differ():
    ua, uw, _ = _trials()
    y1 = bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=4, bits_w=4,
                            **SIGMAS)
    y2 = bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=4, bits_w=4,
                            **SIGMAS)
    y3 = bitplane_mac_noisy(ua, uw, jax.random.key(1), bits_a=4, bits_w=4,
                            **SIGMAS)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))


def test_raw_uint32_key_matches_typed_key():
    ua, uw, _ = _trials(m=32)
    yt = bitplane_mac_noisy(ua, uw, jax.random.key(5), bits_a=4, bits_w=4,
                            **SIGMAS)
    yr = bitplane_mac_noisy(ua, uw, jax.random.PRNGKey(5), bits_a=4,
                            bits_w=4, **SIGMAS)
    np.testing.assert_array_equal(np.asarray(yt), np.asarray(yr))


def test_zero_noise_spec_is_exact():
    ua, uw, exact = _trials(m=16)
    out = bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=4, bits_w=4)
    np.testing.assert_array_equal(np.asarray(out), exact)


# -------------------------------------------- moment/quantile parity
def test_moment_and_quantile_parity_vs_jnp_oracle():
    """Kernel and keyed jnp engine draw from the SAME deviation distribution.

    256 trial rows x 8 columns = 2048 samples per engine; the oracle runs
    ``rbl_mode="physics"`` (the kernel's in-register voltage model).
    """
    ua, uw, exact = _trials(bits=4, m=256, k=64, n=8)
    ok = bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=4, bits_w=4,
                            **SIGMAS)
    oj = bitserial_matmul_unsigned(
        ua, uw, bits_a=4, bits_w=4, mode="sim", key=jax.random.key(1),
        rbl_mode="physics", **SIGMAS)
    dk = (np.asarray(ok) - exact).ravel()
    dj = (np.asarray(oj) - exact).ravel()
    s = dj.std()
    assert s > 0  # the noise must actually flip decodes at these sigmas
    assert abs(dk.mean() - dj.mean()) < 0.15 * s
    assert 0.85 < dk.std() / s < 1.15
    for q in (10, 25, 50, 75, 90):
        assert abs(np.percentile(dk, q) - np.percentile(dj, q)) < 0.15 * s


def test_detuned_threshold_error_rate_band():
    """Single plane pair + single group: the output IS the decoded count, so
    the error rate under detuned references must land in the band of an
    independent numpy Monte-Carlo of the in-kernel noise semantics."""
    rows, m, n, k_true = 8, 256, 128, 4
    a = np.zeros((m, rows), np.int32)
    a[:, :k_true] = 1
    ua, uw = jnp.asarray(a), jnp.asarray(np.ones((rows, n), np.int32))
    good = np.asarray(core_thresholds(rows, mode="physics"))
    ms, cs = 0.2, 0.02
    rng = np.random.default_rng(12345)
    samples = 200_000
    k_eff = k_true + ms * np.sqrt(k_true) * rng.standard_normal(samples)
    v = np.asarray(rbl_voltage(jnp.asarray(k_eff, jnp.float32), rows=rows,
                               mode="physics"))
    for detune in (0.0, 0.4 * 0.216845):  # centered / 0.4-level corner shift
        thr = good + detune
        out = bitplane_mac_noisy(
            ua, uw, jax.random.key(3), jnp.asarray(thr), bits_a=1, bits_w=1,
            mismatch_sigma=ms, comparator_offset_sigma=cs)
        err_kernel = float((np.asarray(out) != k_true).mean())
        dec = (v[:, None] <= (thr[None, :] + cs * rng.standard_normal(
            (samples, rows)))).sum(1)
        err_mc = float((dec != k_true).mean())
        assert err_mc > 0.05  # the regime is genuinely noisy
        assert abs(err_kernel - err_mc) < 0.03, (detune, err_kernel, err_mc)


def test_k_padding_groups_draw_no_noise():
    """K pads up to the bk tile; padded zero-count groups must be masked —
    otherwise comparator offset flips them and the sum drifts from the
    oracle's (which never has those groups)."""
    rows, m, n = 8, 64, 16
    a = np.zeros((m, rows), np.int32)
    a[:, :4] = 1
    ua, uw = jnp.asarray(a), jnp.asarray(np.ones((rows, n), np.int32))
    # bk=256 -> 31 padded groups beside the single real one; big offset noise
    out = bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=1, bits_w=1,
                             comparator_offset_sigma=0.05, bk=256)
    oj = bitserial_matmul_unsigned(
        ua, uw, bits_a=1, bits_w=1, mode="sim", key=jax.random.key(1),
        rbl_mode="physics", comparator_offset_sigma=0.05)
    dk = np.asarray(out) - 4
    dj = np.asarray(oj) - 4
    # with unmasked padding the kernel mean would sit tens of counts high
    assert abs(dk.mean() - dj.mean()) < 0.5


# ----------------------------------------------------- independence
def test_noise_independent_across_trial_slots():
    ua, uw, _ = _trials(bits=4, m=64, k=64, n=8)
    out = np.asarray(bitplane_mac_noisy(ua, uw, jax.random.key(0), bits_a=4,
                                        bits_w=4, **SIGMAS))
    # identical input rows, so any variation between rows is noise — and
    # with per-element streams the 64 trials cannot all coincide
    assert np.unique(out, axis=0).shape[0] > 1


def test_noise_independent_across_m_tiles():
    """Two M-tiles with identical contents: the grid-step fold must give
    them different streams, else every tile decodes identically."""
    rows = 8
    a = np.zeros((16, rows), np.int32)
    a[:, :4] = 1
    ua = jnp.asarray(a)
    uw = jnp.asarray(np.ones((rows, 128), np.int32))
    out = np.asarray(bitplane_mac_noisy(
        ua, uw, jax.random.key(2), bits_a=1, bits_w=1, bm=8, bn=128, bk=64,
        mismatch_sigma=0.4, comparator_offset_sigma=0.05))
    assert not np.array_equal(out[:8], out[8:])  # tile i=0 vs i=1


def test_noise_independent_across_k_group_steps():
    """Two identical K-blocks in separate grid steps (bk splits them): if the
    kk step fold were broken both halves would draw the SAME deviations and
    every total deviation would be even."""
    rows, m, n = 8, 64, 64
    half = np.zeros((m, rows), np.int32)
    half[:, :4] = 1
    ua = jnp.asarray(np.concatenate([half, half], axis=1))  # K = 16
    uw = jnp.asarray(np.ones((2 * rows, n), np.int32))
    out = np.asarray(bitplane_mac_noisy(
        ua, uw, jax.random.key(4), bits_a=1, bits_w=1, bm=64, bn=64, bk=8,
        mismatch_sigma=0.4, comparator_offset_sigma=0.05))
    dev = out - 8
    assert np.any(dev % 2 != 0)


def test_noise_independent_across_plane_pairs():
    """Activation value 3 = bits 11: both planes see identical counts.  If
    plane pairs shared a stream, deviation = d*1 + d*2 would always divide
    by 3."""
    rows, m, n = 8, 64, 64
    a = np.zeros((m, rows), np.int32)
    a[:, :4] = 3
    ua = jnp.asarray(a)
    uw = jnp.asarray(np.ones((rows, n), np.int32))
    exact = np.asarray(ua) @ np.asarray(uw)
    out = np.asarray(bitplane_mac_noisy(
        ua, uw, jax.random.key(6), bits_a=2, bits_w=1,
        mismatch_sigma=0.4, comparator_offset_sigma=0.05))
    dev = out - exact
    assert np.any(dev % 3 != 0)


# -------------------------------------------------- fabric dispatch
def test_fabric_noisy_pallas_dispatches_to_fused_kernel():
    from repro.core.fabric import (Fabric, FabricSpec, NoiseSpec,
                                   resolve_engine)

    spec = FabricSpec(mode="sim", backend="pallas",
                      noise=NoiseSpec(mismatch_sigma=0.05))
    assert resolve_engine(spec).__name__ == "_sim_pallas_noisy"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    fab = Fabric(spec)
    y1 = fab.matmul(x, w, key=jax.random.key(0))
    y2 = fab.matmul(x, w, key=jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert np.isfinite(np.asarray(y1)).all()
    # jnp oracle at the same spec stays available and statistically close
    yj = Fabric(spec.replace(backend="jnp")).matmul(x, w,
                                                    key=jax.random.key(0))
    ref = np.linalg.norm(np.asarray(yj))
    assert np.linalg.norm(np.asarray(y1) - np.asarray(yj)) < 0.2 * ref + 1e-6


def test_fabric_noisy_moment_parity_across_engines():
    """End-to-end fabric path (quantize -> noisy GEMM -> dequant): pallas
    and jnp engines agree on the deviation moments over replicated rows."""
    from repro.core.fabric import Fabric, FabricSpec, NoiseSpec

    rng = np.random.default_rng(7)
    row = rng.normal(size=(1, 64)).astype(np.float32)
    x = jnp.asarray(np.repeat(row, 128, axis=0))
    w = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    noise = NoiseSpec(mismatch_sigma=0.3, comparator_offset_sigma=0.03)
    yk = Fabric(FabricSpec(mode="sim", backend="pallas", noise=noise)).matmul(
        x, w, key=jax.random.key(0))
    yj = Fabric(FabricSpec(mode="sim", backend="jnp", noise=noise)).matmul(
        x, w, key=jax.random.key(1))
    ye = Fabric(FabricSpec(mode="exact")).matmul(x, w)
    dk = (np.asarray(yk) - np.asarray(ye)).ravel()
    dj = (np.asarray(yj) - np.asarray(ye)).ravel()
    s = dj.std()
    assert s > 0
    assert abs(dk.mean() - dj.mean()) < 0.25 * s
    assert 0.75 < dk.std() / s < 1.33

