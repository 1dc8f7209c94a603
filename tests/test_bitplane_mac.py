"""Plane-batched bit-serial engine + fused bitplane_mac kernel tests.

The contract, in increasing order of fusion:

  seed per-plane loop  ==  plane-batched engine  ==  fused Pallas kernel

bit-exact (noise-free), with the first two ALSO drawing identical PRNG noise
per plane pair (fold_in(key, p * bits_w + q) inside the batch via vmap).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitserial import (batched_group_counts, bitserial_matmul_looped,
                                  bitserial_matmul_unsigned, group_counts,
                                  plane_pair_weights)
from repro.core.imc_matmul import imc_matmul, int_matmul
from repro.core.quant import quantize, to_bitplanes, to_offset_binary
from repro.kernels.bitplane_mac.ops import bitplane_mac
from repro.kernels.bitplane_mac.ref import (bitplane_mac_batched_ref,
                                            bitplane_mac_ref)


def _mk_unsigned(bits, m, k, n, seed=0):
    rng = np.random.default_rng(seed)
    hi = 1 << bits
    ua = jnp.asarray(rng.integers(0, hi, size=(m, k)).astype(np.int32))
    uw = jnp.asarray(rng.integers(0, hi, size=(k, n)).astype(np.int32))
    return ua, uw


# ------------------------------------------------- plane-batched jnp engine
def test_batched_group_counts_match_per_pair():
    rng = np.random.default_rng(1)
    ua, uw = _mk_unsigned(4, 3, 21, 6, seed=1)
    a_planes = to_bitplanes(ua, 4)
    w_planes = to_bitplanes(uw, 4)
    batched = np.asarray(batched_group_counts(a_planes, w_planes))
    for p in range(4):
        for q in range(4):
            ref = np.asarray(group_counts(a_planes[p], w_planes[q]))
            np.testing.assert_array_equal(batched[p * 4 + q], ref)


def test_plane_pair_weights_shift_table():
    w = np.asarray(plane_pair_weights(3, 2))
    assert w.tolist() == [1 << (p + q) for p in range(3) for q in range(2)]


@pytest.mark.parametrize("bits", [4, 6, 8])
def test_batched_engine_bitexact_vs_seed_loop_sim(bits):
    ua, uw = _mk_unsigned(bits, 5, 37, 9, seed=bits)
    a = bitserial_matmul_unsigned(ua, uw, bits_a=bits, bits_w=bits, mode="sim")
    b = bitserial_matmul_looped(ua, uw, bits_a=bits, bits_w=bits, mode="sim")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bits", [4, 8])
def test_batched_engine_exact_mode_telescopes_to_int_matmul(bits):
    ua, uw = _mk_unsigned(bits, 4, 29, 7, seed=10 + bits)
    out = bitserial_matmul_unsigned(ua, uw, bits_a=bits, bits_w=bits,
                                    mode="exact")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))


def test_mixed_precision_planes():
    ua, uw = _mk_unsigned(6, 3, 17, 5, seed=3)
    uw = uw % (1 << 4)
    out = bitserial_matmul_unsigned(ua, uw, bits_a=6, bits_w=4, mode="sim")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))


@pytest.mark.parametrize("bits", [4, 8])
def test_batched_engine_mismatch_noise_matches_loop_keys(bits):
    """Per-plane-pair fold_in inside the batch == the loop's key schedule."""
    ua, uw = _mk_unsigned(bits, 4, 33, 6, seed=20 + bits)
    key = jax.random.key(7)
    a = bitserial_matmul_unsigned(ua, uw, bits_a=bits, bits_w=bits,
                                  mode="sim", key=key, mismatch=True)
    b = bitserial_matmul_looped(ua, uw, bits_a=bits, bits_w=bits,
                                mode="sim", key=key, mismatch=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # different keys must draw different noise somewhere on a big-k problem
    ua2, uw2 = _mk_unsigned(bits, 16, 256, 16, seed=30)
    y1 = bitserial_matmul_unsigned(ua2, uw2, bits_a=bits, bits_w=bits,
                                   mode="sim", key=jax.random.key(0),
                                   mismatch=True)
    y2 = bitserial_matmul_unsigned(ua2, uw2, bits_a=bits, bits_w=bits,
                                   mode="sim", key=jax.random.key(1),
                                   mismatch=True)
    assert not np.array_equal(np.asarray(y1), np.asarray(y2))


def test_batched_engine_comparator_offset_matches_loop_keys():
    ua, uw = _mk_unsigned(4, 4, 24, 5, seed=40)
    key = jax.random.key(11)
    a = bitserial_matmul_unsigned(ua, uw, bits_a=4, bits_w=4, mode="sim",
                                  key=key, comparator_offset_sigma=0.02)
    b = bitserial_matmul_looped(ua, uw, bits_a=4, bits_w=4, mode="sim",
                                key=key, comparator_offset_sigma=0.02)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_batched_engine_noise_requires_key():
    ua, uw = _mk_unsigned(4, 2, 16, 3, seed=50)
    with pytest.raises(ValueError):
        bitserial_matmul_unsigned(ua, uw, bits_a=4, bits_w=4, mode="sim",
                                  mismatch=True)


# ------------------------------------------------------ fused Pallas kernel
@pytest.mark.parametrize("bits,m,k,n", [(4, 8, 16, 8), (8, 16, 24, 8),
                                        (6, 5, 40, 12)])
def test_bitplane_kernel_bitexact_vs_both_refs(bits, m, k, n):
    ua, uw = _mk_unsigned(bits, m, k, n, seed=hash((bits, m)) % 2**32)
    out = bitplane_mac(ua, uw, bits_a=bits, bits_w=bits, interpret=True)
    ref_loop = bitplane_mac_ref(ua, uw, bits_a=bits, bits_w=bits)
    ref_batched = bitplane_mac_batched_ref(ua, uw, bits_a=bits, bits_w=bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_loop))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_batched))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))


@pytest.mark.slow
def test_bitplane_kernel_multiblock_ragged():
    # spans multiple (bm, bn, bk) blocks with ragged remainders everywhere
    ua, uw = _mk_unsigned(4, 140, 300, 135, seed=60)
    out = bitplane_mac(ua, uw, bits_a=4, bits_w=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))


def test_bitplane_kernel_batch_dims():
    rng = np.random.default_rng(70)
    ua = jnp.asarray(rng.integers(0, 16, size=(2, 3, 40)).astype(np.int32))
    uw = jnp.asarray(rng.integers(0, 16, size=(40, 6)).astype(np.int32))
    out = bitplane_mac(ua, uw, bits_a=4, bits_w=4, interpret=True)
    assert out.shape == (2, 3, 6)
    ref = np.asarray(ua).reshape(6, 40) @ np.asarray(uw)
    np.testing.assert_array_equal(np.asarray(out).reshape(6, 6), ref)


def test_bitplane_kernel_custom_thresholds_detune():
    # Shifting every comparator reference up one level (paper §IV-C corner
    # detuning) must corrupt the decode — proves thresholds are live data.
    # The activation's high plane is all zeros, so its groups count 0, which
    # the detuned bank (thr[0] above V(0)) reads as 1; the all-ones plane
    # counts 8, which it still reads as 8.
    from repro.core.decoder import thresholds as core_thresholds

    ua = jnp.full((8, 16), 1, jnp.int32)
    uw = jnp.full((16, 8), 3, jnp.int32)
    good = core_thresholds(8, mode="physics")
    out_good = bitplane_mac(ua, uw, good, bits_a=2, bits_w=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_good),
                                  np.asarray(ua) @ np.asarray(uw))
    detuned = jnp.concatenate([jnp.array([1.9]), good[:-1]])
    out_bad = bitplane_mac(ua, uw, detuned, bits_a=2, bits_w=2,
                           interpret=True)
    assert not np.array_equal(np.asarray(out_bad), np.asarray(out_good))
    np.testing.assert_array_equal(
        np.asarray(out_bad), np.asarray(_detuned_oracle(ua, uw, detuned, 2, 2)))


# The plane-folded row axis: every M (one row, ragged, a full decode tile,
# prefill tiles, a ragged last tile), every plane count, ragged K and N,
# leading batch dims and re-tuned thresholds.  Detuned references keep
# thr[0] below V(0), so a zero count still decodes to 0 and the references'
# K padding (to whole groups) and the kernel's (to whole blocks) agree.
FOLD_CASES = [
    ((1,), 8, 8, 40, 12, False),
    ((5,), 8, 8, 40, 12, False),
    ((8,), 8, 8, 40, 12, False),
    ((16,), 8, 8, 24, 20, False),
    ((24,), 8, 8, 40, 12, False),
    ((128,), 8, 8, 16, 9, False),
    ((130,), 8, 8, 16, 9, False),
    ((5,), 4, 8, 37, 11, False),
    ((130,), 4, 8, 19, 7, False),
    ((1,), 2, 2, 33, 5, False),
    ((24,), 2, 2, 21, 6, False),
    ((2, 3), 4, 8, 40, 6, False),
    ((8,), 8, 8, 40, 12, True),
    ((2, 3), 2, 2, 27, 10, True),
]


def _levels(rows):
    """V(k) for k in 0..rows, the physics voltage of every count."""
    from repro.kernels.common import counts_to_voltage

    return counts_to_voltage(jnp.arange(rows + 1, dtype=jnp.float32), rows)


def _detuned_oracle(ua, uw, thr, bits_a, bits_w, rows=8):
    """The pyramid with explicit references: batched group counts, the
    physics voltage of each count, the comparator bank, the 2^(p+q)
    weights."""
    k = ua.shape[-1]
    a = to_bitplanes(ua.reshape(-1, k), bits_a)
    counts = batched_group_counts(a, to_bitplanes(uw, bits_w), rows)
    v = _levels(rows)[counts]
    dec = sum((v <= thr[i]).astype(jnp.int32) for i in range(rows))
    out = jnp.einsum("i,imgn->mn", plane_pair_weights(bits_a, bits_w), dec)
    return out.reshape(*ua.shape[:-1], uw.shape[-1])


@pytest.mark.parametrize("batch,bits_a,bits_w,k,n,detune", FOLD_CASES)
def test_folded_kernel_bitexact_vs_batched_engine_and_seed_loop(
        batch, bits_a, bits_w, k, n, detune):
    from repro.core.decoder import thresholds as core_thresholds
    from repro.kernels.bitplane_mac.ops import bitplane_mac_noisy

    rng = np.random.default_rng([len(batch), batch[-1], bits_a, k, n])
    ua = jnp.asarray(rng.integers(0, 1 << bits_a, size=(*batch, k)),
                     jnp.int32)
    uw = jnp.asarray(rng.integers(0, 1 << bits_w, size=(k, n)), jnp.int32)
    thr = core_thresholds(8, mode="physics")
    if detune:
        thr = thr.at[2:].add(0.12)
    out = np.asarray(bitplane_mac(ua, uw, thr, bits_a=bits_a, bits_w=bits_w,
                                  interpret=True))
    assert out.shape == (*batch, n) and out.dtype == np.int32
    if detune:
        ref = np.asarray(_detuned_oracle(ua, uw, thr, bits_a, bits_w))
        np.testing.assert_array_equal(out, ref)
        assert not np.array_equal(out, np.asarray(ua) @ np.asarray(uw))
        # the unfolded grid (the noisy kernel, no noise) decodes the same
        unfolded = bitplane_mac_noisy(ua, uw, jax.random.key(0), thr,
                                      bits_a=bits_a, bits_w=bits_w,
                                      interpret=True)
        np.testing.assert_array_equal(out, np.asarray(unfolded))
        return
    flat_a = ua.reshape(-1, k)
    for ref in (bitplane_mac_batched_ref(flat_a, uw, bits_a=bits_a,
                                         bits_w=bits_w),
                bitplane_mac_ref(flat_a, uw, bits_a=bits_a, bits_w=bits_w)):
        np.testing.assert_array_equal(out.reshape(-1, n), np.asarray(ref))
    np.testing.assert_array_equal(out, np.asarray(ua) @ np.asarray(uw))


def test_noisy_kernel_output_is_unchanged():
    """The noisy kernel keeps its unfolded grid, which keys its noise
    stream: a fixed key and shape give the output recorded before the
    noise-free kernel's rows were folded."""
    from repro.kernels.bitplane_mac.ops import bitplane_mac_noisy

    rng = np.random.default_rng(90)
    ua = jnp.asarray(rng.integers(0, 16, size=(3, 40)).astype(np.int32))
    uw = jnp.asarray(rng.integers(0, 16, size=(40, 5)).astype(np.int32))
    out = bitplane_mac_noisy(ua, uw, jax.random.key(5), bits_a=4, bits_w=4,
                             mismatch_sigma=0.3, comparator_offset_sigma=0.05,
                             bm=8, bn=128, bk=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), [
        [2151, 2034, 1823, 1879, 2329],
        [2302, 2440, 2560, 2264, 2401],
        [2237, 1973, 2242, 1744, 2254]])


@pytest.mark.parametrize("m,rows", [(8, 64), (128, 1024)])
def test_row_fill_counters(m, rows):
    """``fabric.mac.real_rows``/``.tile_rows`` count the folded rows once
    per traced shape: an 8-row 8x8 call fills one 64-row tile, a 128-row
    call eight 128-row tiles; a second call with the same shapes does not
    retrace and adds nothing."""
    from repro.kernels.bitplane_mac.ops import _bitplane_mac_jit
    from repro.telemetry import get_registry

    reg = get_registry()
    real, tile = (reg.counter("fabric.mac.real_rows"),
                  reg.counter("fabric.mac.tile_rows"))
    ua, uw = _mk_unsigned(8, m, 16, 8, seed=m)
    _bitplane_mac_jit.clear_cache()  # this shape is traced here first
    r0, t0 = real.value, tile.value
    out = bitplane_mac(ua, uw, bits_a=8, bits_w=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))
    assert (real.value - r0, tile.value - t0) == (rows, rows)
    bitplane_mac(ua + 0, uw, bits_a=8, bits_w=8, interpret=True)
    assert (real.value - r0, tile.value - t0) == (rows, rows)


# The count-domain comparator bank: references c_i tabulated once per call
# from the thresholds, four group counts packed per dot.  Threshold kinds:
# the physics defaults; random descending references reaching above V(0)
# and below V(rows); references set exactly on level voltages.  The oracle
# and those references take the rows + 1 voltages under jit, as the
# kernel's wrapper does: XLA folds them at compile time, and its runtime
# exp (or an op-by-op dispatch) can round a level one ulp apart, which
# would move a reference set exactly on that level.
THRESHOLD_KINDS = ["physics", "random", "on_levels"]
_jit_levels = jax.jit(_levels, static_argnums=0)


def _thresholds_of(kind, rows, seed):
    from repro.core.decoder import thresholds as core_thresholds

    if kind == "physics":
        return core_thresholds(rows, mode="physics")
    rng = np.random.default_rng(seed)
    levels = np.asarray(_jit_levels(rows))
    if kind == "random":
        lo, hi = levels[-1] - 0.2, levels[0] + 0.2
        thr = np.sort(rng.uniform(lo, hi, rows))[::-1].copy()
        thr[0] = rng.uniform(levels[0], hi)
        thr[-1] = rng.uniform(lo, levels[-1])
    else:  # thr[i] is V(i) or V(i + 1): still descending
        thr = levels[np.arange(rows) + rng.integers(0, 2, rows)]
    return jnp.asarray(thr, jnp.float32)


@pytest.mark.parametrize("rows", [4, 8, 16, 32])
@pytest.mark.parametrize("kind", THRESHOLD_KINDS)
def test_count_references_reproduce_voltage_decode(rows, kind):
    """k >= c_i on every level k is the voltage comparator V(k) <= thr[i];
    with the physics references it is ``decoder.decode_voltage``."""
    from repro.core.decoder import decode_voltage
    from repro.core.rbl import level_voltages
    from repro.kernels.common import count_references

    thr = _thresholds_of(kind, rows, seed=rows)
    refs = np.asarray(jax.jit(count_references, static_argnums=1)(thr, rows))
    k = np.arange(rows + 1)
    by_count = (k[:, None] >= refs[None, :]).sum(axis=1)
    v = np.asarray(_jit_levels(rows))
    by_volts = (v[:, None] <= np.asarray(thr)[None, :]).sum(axis=1)
    np.testing.assert_array_equal(by_count, by_volts)
    if kind == "physics":
        np.testing.assert_array_equal(by_count, np.asarray(decode_voltage(
            level_voltages(rows, mode="physics"), rows=rows,
            mode="physics")))
        np.testing.assert_array_equal(by_count, k)


PACK_CASES = [(rows, bits, kind) for rows in (4, 8, 16)
              for bits in ((2, 2), (4, 8), (8, 8)) for kind in THRESHOLD_KINDS]


def _pack_case_check(rows, bits_a, bits_w, kind, bk, m=5, n=12):
    """bitplane_mac on K = two whole blocks plus five whole groups and a
    partial one, so the last block holds a pack that is part padding."""
    from repro.kernels.bitplane_mac.ops import bitplane_mac

    k = 2 * bk + 5 * rows + 3
    rng = np.random.default_rng([rows, bits_a, bits_w, bk])
    ua = jnp.asarray(rng.integers(0, 1 << bits_a, size=(m, k)), jnp.int32)
    uw = jnp.asarray(rng.integers(0, 1 << bits_w, size=(k, n)), jnp.int32)
    thr = _thresholds_of(kind, rows, seed=bits_a * 16 + bits_w)
    out = np.asarray(bitplane_mac(ua, uw, thr, bits_a=bits_a, bits_w=bits_w,
                                  rows=rows, bk=bk, interpret=True))
    oracle = jax.jit(_detuned_oracle, static_argnums=(3, 4, 5))
    np.testing.assert_array_equal(
        out, np.asarray(oracle(ua, uw, thr, bits_a, bits_w, rows)))
    if kind == "physics":
        np.testing.assert_array_equal(out, np.asarray(
            bitplane_mac_batched_ref(ua, uw, bits_a=bits_a, bits_w=bits_w,
                                     rows=rows)))
        np.testing.assert_array_equal(out, np.asarray(ua) @ np.asarray(uw))


@pytest.mark.parametrize("rows,bits,kind", PACK_CASES)
def test_packed_count_bank_bitexact_vs_oracles(rows, bits, kind):
    """Four groups per dot (``rows <= 31``, whole packs per block) decode
    exactly as the voltage-domain oracles, thresholds detuned or not."""
    from repro.kernels.bitplane_mac.bitplane_mac import group_pack

    assert group_pack(rows, 8 * rows)[0] == 4
    _pack_case_check(rows, *bits, kind, bk=8 * rows)


@pytest.mark.parametrize("rows,bk,kind", [
    (32, 128, "physics"), (32, 128, "random"), (32, 128, "on_levels"),
    (8, 40, "random")])
def test_one_group_per_dot_bitexact_vs_oracles(rows, bk, kind):
    """Counts past 31 (``rows`` 32), or a block of five groups, take one
    group per dot with the same count-domain compare, and stay exact."""
    from repro.kernels.bitplane_mac.bitplane_mac import group_pack

    assert group_pack(rows, bk)[0] == 1
    _pack_case_check(rows, 4, 8, kind, bk=bk)


@pytest.mark.parametrize("m,rows,per_dot", [(8, 8, 4), (128, 8, 4),
                                            (8, 32, 1), (128, 32, 1)])
def test_group_pack_counters(m, rows, per_dot):
    """``fabric.mac.groups``/``.group_dots`` count the row-groups decoded
    and the MXU dots issued for them, once per traced shape: four groups
    per dot at ``rows`` 8, one at ``rows`` 32, in a decode shape (one
    64-row tile) and a prefill shape (eight 128-row tiles)."""
    from repro.kernels.bitplane_mac.ops import _bitplane_mac_jit
    from repro.telemetry import get_registry

    reg = get_registry()
    groups, dots = (reg.counter("fabric.mac.groups"),
                    reg.counter("fabric.mac.group_dots"))
    ua, uw = _mk_unsigned(8, m, 64, 8, seed=m + rows)
    _bitplane_mac_jit.clear_cache()  # this shape is traced here first
    g0, d0 = groups.value, dots.value
    out = bitplane_mac(ua, uw, bits_a=8, bits_w=8, rows=rows, bm=128,
                       bn=128, bk=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ua) @ np.asarray(uw))
    steps = (8 * m // min(128, 8 * m)) * 8  # row tiles x weight planes
    assert groups.value - g0 == steps * 128 // rows
    assert (groups.value - g0) == per_dot * (dots.value - d0)
    bitplane_mac(ua + 0, uw, bits_a=8, bits_w=8, rows=rows, bm=128, bn=128,
                 bk=128, interpret=True)
    assert groups.value - g0 == steps * 128 // rows


# ------------------------------------------------------- fabric wiring
def test_fabric_matmul_sim_fused_kernel_matches_jnp_sim():
    from repro.core.fabric import FabricSpec

    rng = np.random.default_rng(80)
    x = jnp.asarray(rng.normal(size=(4, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(24, 8)).astype(np.float32))
    ys = imc_matmul(x, w, FabricSpec(bits_a=4, bits_w=4, mode="sim",
                                     backend="jnp"))
    yk = imc_matmul(x, w, FabricSpec(bits_a=4, bits_w=4, mode="sim",
                                     backend="pallas"))
    np.testing.assert_array_equal(np.asarray(ys), np.asarray(yk))
    ye = imc_matmul(x, w, FabricSpec(bits_a=4, bits_w=4, mode="exact"))
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yk), rtol=1e-6)


def test_exact_mode_telescopes_to_int_matmul_quantized():
    # The full quantize -> offset-binary -> pyramid pipeline in exact mode
    # equals the plain int8 matmul on the quantized operands.
    rng = np.random.default_rng(82)
    x = jnp.asarray(rng.normal(size=(6, 40)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(40, 10)).astype(np.float32))
    bits = 8
    qx, qw = quantize(x, bits), quantize(w, bits, axis=0)
    ua, uw = to_offset_binary(qx.q, bits), to_offset_binary(qw.q, bits)
    from repro.core.quant import signed_product_correction

    uu = bitserial_matmul_unsigned(ua, uw, bits_a=bits, bits_w=bits,
                                   mode="exact")
    acc = uu - signed_product_correction(ua, uw, bits)
    np.testing.assert_array_equal(np.asarray(acc),
                                  np.asarray(int_matmul(qx.q, qw.q)))
