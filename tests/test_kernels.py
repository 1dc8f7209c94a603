"""Kernel validation: Pallas (interpret=True on CPU) vs pure-jnp oracles.

Shape/dtype sweeps per the kernel contract; allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decoder import thresholds as core_thresholds
from repro.kernels.imc_mac.ops import imc_mac, imc_mac_dequant
from repro.kernels.imc_mac.ref import imc_mac_dequant_ref, imc_mac_ref
from repro.kernels.rbl_decode.ops import rbl_decode_mac
from repro.kernels.rbl_decode.ref import rbl_decode_mac_ref

SHAPES = [
    (8, 16, 8),        # tiny, fully padded
    (128, 128, 128),   # exactly one block
    (256, 384, 128),   # multi-block M/K
    (100, 130, 50),    # ragged everything
    (1, 8, 1),         # degenerate
]


@pytest.mark.parametrize("m,k,n", SHAPES)
def test_imc_mac_matches_ref(m, k, n):
    rng = np.random.default_rng(hash((m, k, n)) % 2**32)
    qa = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    out = imc_mac(qa, qw, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(imc_mac_ref(qa, qw)))


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (256, 128, 256),
                                      (128, 256, 512)])
def test_imc_mac_block_shape_sweep(bm, bn, bk):
    rng = np.random.default_rng(0)
    qa = jnp.asarray(rng.integers(-127, 128, size=(200, 300)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 128, size=(300, 170)), jnp.int8)
    out = imc_mac(qa, qw, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(imc_mac_ref(qa, qw)))


def test_imc_mac_batch_dims():
    rng = np.random.default_rng(1)
    qa = jnp.asarray(rng.integers(-127, 128, size=(4, 6, 96)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 128, size=(96, 32)), jnp.int8)
    out = imc_mac(qa, qw, interpret=True)
    assert out.shape == (4, 6, 32)
    ref = imc_mac_ref(qa.reshape(24, 96), qw).reshape(4, 6, 32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_imc_mac_int32_accumulation_no_overflow():
    # Worst case magnitudes over a deep K: |acc| = 127*127*2048 ~ 3.3e7 < 2^31.
    qa = jnp.full((8, 2048), 127, jnp.int8)
    qw = jnp.full((2048, 8), -127, jnp.int8)
    out = imc_mac(qa, qw, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((8, 8), -127 * 127 * 2048))


@pytest.mark.parametrize("m,k,n", [(64, 96, 32), (130, 140, 150)])
def test_imc_mac_dequant_matches_ref(m, k, n):
    rng = np.random.default_rng(2)
    qa = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int8)
    qw = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    sa = jnp.float32(0.0123)
    sw = jnp.asarray(rng.uniform(0.001, 0.1, size=(n,)), jnp.float32)
    out = imc_mac_dequant(qa, qw, sa, sw, interpret=True)
    ref = imc_mac_dequant_ref(qa, qw, sa, sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("m,k,n", [(16, 64, 8), (128, 256, 128), (50, 70, 30)])
def test_rbl_decode_matches_ref(m, k, n):
    rng = np.random.default_rng(hash((m, k, n, 1)) % 2**32)
    a = jnp.asarray(rng.integers(0, 2, size=(m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 2, size=(k, n)), jnp.int8)
    out = rbl_decode_mac(a, w, interpret=True)
    ref = rbl_decode_mac_ref(a, w, mode="physics")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_rbl_decode_equals_plain_popcount_matmul():
    # Noise-free decode is exact -> grouped path == plain binary matmul.
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.integers(0, 2, size=(32, 120)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 2, size=(120, 16)), jnp.int8)
    out = rbl_decode_mac(a, w, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(a, np.int32) @ np.asarray(w, np.int32))


def test_rbl_decode_custom_thresholds_detune():
    # Detuned comparator references (paper §IV-C corner re-tuning): shifting
    # all thresholds up by a full level makes every group read one count high
    # (where headroom exists) — decode errors must materialize.
    rng = np.random.default_rng(6)
    a = jnp.ones((16, 64), jnp.int8)
    w = jnp.ones((64, 8), jnp.int8)
    good = core_thresholds(8, mode="physics")
    out_good = rbl_decode_mac(a, w, good, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_good), np.full((16, 8), 64))
    detuned = jnp.concatenate([jnp.array([1.9]), good[:-1]])  # shift one level
    out_bad = rbl_decode_mac(a, w, detuned, interpret=True)
    assert np.all(np.asarray(out_bad) != np.asarray(out_good))


def test_rbl_decode_rows_16_physics():
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.integers(0, 2, size=(24, 160)), jnp.int8)
    w = jnp.asarray(rng.integers(0, 2, size=(160, 8)), jnp.int8)
    out = rbl_decode_mac(a, w, rows=16, bk=256, interpret=True)
    ref = rbl_decode_mac_ref(a, w, rows=16, mode="physics")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_interpret_resolves_from_the_platform(monkeypatch):
    """Off the TPU kernels interpret by default; on a TPU they compile, and
    an explicit request to interpret there is refused, not obeyed."""
    from repro.kernels.compat import resolve_interpret

    assert resolve_interpret(None) is True  # the test platform is the CPU
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="compile on a TPU"):
        resolve_interpret(True)
