"""FabricSpec / Fabric facade: the typed entry point to the IMC stack.

Covers spec validation + hashability, backend-registry dispatch (with early
raises on unsupported combos), the four facade verbs (matmul/linear/logic/
cost), NoiseSpec end-to-end through a model forward, PRNG key threading down
to the bit-serial engine, asymmetric precision parity, the removed pre-spec
kwargs (legacy spellings now raise ``TypeError``), and jit-cache stability of
equal specs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.constants as C
from repro.core.bitserial import bitserial_matmul_unsigned
from repro.core.fabric import (Fabric, FabricSpec, NoiseSpec, fabric_matmul,
                               resolve_engine)
from repro.core.imc_linear import apply_imc_linear, imc_linear_apply, init_imc_linear
from repro.core.imc_matmul import imc_matmul
from repro.core.quant import quantize, signed_product_correction, to_offset_binary


def _xw(m=8, k=64, n=16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(k, n)).astype(np.float32)))


# ------------------------------------------------------------------- spec
def test_spec_validation_raises():
    with pytest.raises(ValueError, match="mode"):
        FabricSpec(mode="approximate")
    with pytest.raises(ValueError, match="backend"):
        FabricSpec(backend="cuda")
    with pytest.raises(ValueError, match="bits_a"):
        FabricSpec(bits_a=9)
    with pytest.raises(ValueError, match="bits_w"):
        FabricSpec(bits_w=1)
    # noise is a sim-path concept
    with pytest.raises(ValueError, match="sim"):
        FabricSpec(mode="exact", noise=NoiseSpec.calibrated())
    # noisy + pallas is a supported engine since the in-kernel PRNG landed
    assert FabricSpec(mode="sim", backend="pallas",
                      noise=NoiseSpec.calibrated()).label == "sim/pallas+noise"
    with pytest.raises(ValueError, match=">= 0"):
        NoiseSpec(mismatch_sigma=-0.1)


def test_spec_hashable_and_noise_canonicalized():
    a = FabricSpec(mode="sim", backend="jnp")
    b = FabricSpec(mode="sim", backend="jnp", noise=NoiseSpec())
    assert a == b and hash(a) == hash(b)  # all-off NoiseSpec -> None
    assert b.noise is None and not b.noisy
    n = FabricSpec(mode="sim", noise=NoiseSpec(mismatch_sigma=0.05))
    assert n.noisy and n != a
    assert len({a, b, n}) == 2  # usable as a dict/jit-cache key


def test_spec_labels_and_bits_accessor():
    assert FabricSpec(backend="jnp").label == "exact/jnp"
    assert FabricSpec(mode="sim", backend="pallas").label == "sim/pallas"
    assert FabricSpec(mode="sim", backend="jnp",
                      noise=NoiseSpec.calibrated()).label == "sim/jnp+noise"
    assert FabricSpec().bits == 8
    with pytest.raises(ValueError, match="asymmetric"):
        FabricSpec(bits_a=4, bits_w=8).bits


def test_resolve_engine_covers_all_valid_combos():
    for spec in (FabricSpec(backend="jnp"), FabricSpec(backend="pallas"),
                 FabricSpec(mode="sim", backend="jnp"),
                 FabricSpec(mode="sim", backend="pallas"),
                 FabricSpec(mode="sim", backend="jnp",
                            noise=NoiseSpec.calibrated()),
                 FabricSpec(mode="sim", backend="pallas",
                            noise=NoiseSpec.calibrated())):
        assert callable(resolve_engine(spec))
        assert callable(Fabric(spec)._engine)


# ----------------------------------------------------------------- matmul
def test_fabric_matmul_exact_and_sim_agree():
    x, w = _xw()
    ye = fabric_matmul(x, w, FabricSpec(backend="jnp"))
    ys = fabric_matmul(x, w, FabricSpec(mode="sim", backend="jnp"))
    np.testing.assert_allclose(np.asarray(ye), np.asarray(ys), rtol=1e-6)
    ref = np.asarray(x @ w)
    rel = np.linalg.norm(np.asarray(ye) - ref) / np.linalg.norm(ref)
    assert rel < 0.02


def test_fabric_matmul_noisy_requires_key():
    x, w = _xw()
    spec = FabricSpec(mode="sim", noise=NoiseSpec.calibrated())
    with pytest.raises(ValueError, match="key"):
        Fabric(spec).matmul(x, w)


def test_fabric_matmul_noisy_differs_but_bounded():
    x, w = _xw(seed=3)
    fab = Fabric(FabricSpec(mode="sim", backend="jnp",
                            noise=NoiseSpec(mismatch_sigma=0.1,
                                            comparator_offset_sigma=0.005)))
    y0 = fabric_matmul(x, w, FabricSpec(mode="sim", backend="jnp"))
    yn = fab.matmul(x, w, key=jax.random.key(0))
    ref = np.asarray(x @ w)
    assert not np.array_equal(np.asarray(yn), np.asarray(y0))
    rel = np.linalg.norm(np.asarray(yn) - ref) / np.linalg.norm(ref)
    assert rel < 0.25  # noisy, but decode margins keep it in the ballpark


def test_key_threads_down_to_bitserial_engine():
    # The facade must hand the caller's key to bitserial_matmul_unsigned
    # unchanged: reproduce its output by hand with the same key.
    x, w = _xw(seed=4)
    sigma = 0.4
    spec = FabricSpec(mode="sim", backend="jnp",
                      noise=NoiseSpec(mismatch_sigma=sigma))
    key = jax.random.key(11)
    y = fabric_matmul(x, w, spec, key=key)

    qx = quantize(x, 8, axis=None)
    qw = quantize(w, 8, axis=0)
    ua, uw = to_offset_binary(qx.q, 8), to_offset_binary(qw.q, 8)
    uu = bitserial_matmul_unsigned(ua, uw, bits_a=8, bits_w=8, mode="sim",
                                   key=key, mismatch_sigma=sigma)
    acc = uu - signed_product_correction(ua, uw, 8)
    ref = acc.astype(jnp.float32) * qx.scale * qw.scale.reshape(1, -1)
    # identical noise draws; only jit-vs-eager dequant fusion rounding differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


# --------------------------------------------------- asymmetric precision
def test_asymmetric_correction_identity():
    rng = np.random.default_rng(5)
    qa = rng.integers(-7, 8, size=(6, 24)).astype(np.int32)  # 4-bit
    qw = rng.integers(-127, 128, size=(24, 10)).astype(np.int32)  # 8-bit
    ua = to_offset_binary(jnp.asarray(qa), 4)
    uw = to_offset_binary(jnp.asarray(qw), 8)
    corr = signed_product_correction(ua, uw, 4, 8)
    np.testing.assert_array_equal(np.asarray(ua @ uw - corr), qa @ qw)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_asymmetric_4x8_sim_parity_vs_float(backend):
    x, w = _xw(m=4, k=48, n=8, seed=6)
    spec = FabricSpec(bits_a=4, bits_w=8, mode="sim", backend=backend)
    y = fabric_matmul(x, w, spec)
    # bit-exact vs the exact digital-equivalent at the same precisions
    ye = fabric_matmul(x, w, FabricSpec(bits_a=4, bits_w=8, backend="jnp"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-6)
    # and within the 4-bit activation quantization budget of the float ref
    ref = np.asarray(x @ w)
    rel = np.linalg.norm(np.asarray(y) - ref) / np.linalg.norm(ref)
    assert rel < 0.2


# ----------------------------------------------------------------- linear
def test_fabric_linear_forward_and_ste_grads():
    fab = Fabric(FabricSpec(mode="sim", backend="jnp"))
    p = init_imc_linear(jax.random.key(0), 32, 16, use_bias=True)
    x = jax.random.normal(jax.random.key(1), (8, 32))

    def loss(params, x):
        y = fab.linear(params, x)
        return jnp.sum(y * y)

    val, grads = jax.value_and_grad(loss)(p, x)
    assert np.isfinite(float(val))
    assert grads["w"].shape == (32, 16) and grads["b"].shape == (16,)
    y = fab.linear(p, x)
    np.testing.assert_allclose(np.asarray(grads["b"]),
                               np.asarray(2 * y.sum(0)), rtol=1e-4)


def test_fabric_linear_noisy_keyed_deterministic():
    fab = Fabric(FabricSpec(mode="sim", backend="jnp",
                            noise=NoiseSpec(mismatch_sigma=0.3)))
    p = init_imc_linear(jax.random.key(0), 24, 8)
    x = jax.random.normal(jax.random.key(1), (4, 24))
    y1 = fab.linear(p, x, key=jax.random.key(2))
    y2 = fab.linear(p, x, key=jax.random.key(2))
    y3 = fab.linear(p, x, key=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))


# ------------------------------------------------------------------ logic
def test_fabric_logic_matches_boolean_ops():
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.integers(0, 2, size=64).astype(np.uint8))
    b = jnp.asarray(rng.integers(0, 2, size=64).astype(np.uint8))
    an, bn = np.asarray(a), np.asarray(b)
    truth = {"AND": an & bn, "OR": an | bn, "XOR": an ^ bn,
             "NAND": 1 - (an & bn), "NOR": 1 - (an | bn),
             "XNOR": 1 - (an ^ bn), "SUM": an ^ bn, "CARRY": an & bn}
    for spec in (FabricSpec(), FabricSpec(mode="sim")):
        fab = Fabric(spec)
        for op, want in truth.items():
            np.testing.assert_array_equal(np.asarray(fab.logic(a, b, op)),
                                          want, err_msg=f"{spec.label}:{op}")
    with pytest.raises(ValueError, match="op"):
        Fabric(FabricSpec()).logic(a, b, "MAJ")


def test_fabric_logic_noisy_keyed():
    a = jnp.ones((4096,), jnp.uint8)
    b = jnp.ones((4096,), jnp.uint8)
    fab = Fabric(FabricSpec(mode="sim", backend="jnp",
                            noise=NoiseSpec(mismatch_sigma=0.5)))
    with pytest.raises(ValueError, match="key"):
        fab.logic(a, b, "AND")
    out = fab.logic(a, b, "AND", key=jax.random.key(0))
    flips = int(np.sum(np.asarray(out) != 1))
    assert 0 < flips < 4096  # noise visibly flips some decodes, not all


# ------------------------------------------------------------------- cost
def test_fabric_cost_tracks_spec_precision():
    rep88 = Fabric(FabricSpec()).cost((128, 256), (256, 64))
    rep48 = Fabric(FabricSpec(bits_a=4, bits_w=8)).cost((128, 256), (256, 64))
    assert rep88.evaluations == 128 * 32 * 64 * 8
    assert rep48.evaluations == rep88.evaluations // 2  # half the a-planes
    assert rep48.energy_j < rep88.energy_j


# ------------------------------------------- NoiseSpec through a model
def test_noisy_spec_end_to_end_through_model_forward():
    from repro.configs import get_config, reduce_config
    from repro.models.common import fabric_noise_key
    from repro.models.model import forward_logits, init_params

    cfg = reduce_config(get_config("qwen2.5-3b"))
    cfg_exact = dataclasses.replace(cfg, fabric=FabricSpec(backend="jnp"))
    cfg_noisy = dataclasses.replace(cfg, fabric=FabricSpec(
        mode="sim", backend="jnp", noise=NoiseSpec.calibrated()))
    params = init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 16), 0,
                                          cfg.vocab_size)}
    exact = forward_logits(params, batch, cfg_exact)
    with pytest.raises(ValueError, match="key"):
        forward_logits(params, batch, cfg_noisy)  # noisy needs a key source
    with fabric_noise_key(jax.random.key(7)):
        noisy = forward_logits(params, batch, cfg_noisy)
    assert not np.array_equal(np.asarray(noisy), np.asarray(exact))
    rel = (np.linalg.norm(np.asarray(noisy - exact))
           / np.linalg.norm(np.asarray(exact)))
    assert rel < 0.2  # calibrated mismatch: rare decode flips, model intact


def test_config_parses_legacy_imc_fields_into_fabric():
    from repro.configs import get_config, reduce_config

    cfg = reduce_config(get_config("qwen2.5-3b"))
    assert cfg.imc_fabric is None  # imc off
    legacy = dataclasses.replace(cfg, imc_mode="sim", imc_bits=4)
    assert legacy.imc_fabric == FabricSpec(bits_a=4, bits_w=4, mode="sim")
    # the typed channel wins when set; the legacy fields are left untouched
    spec = FabricSpec(bits_a=4, bits_w=8, mode="sim")
    typed = dataclasses.replace(cfg, fabric=spec)
    assert typed.imc_fabric == spec and typed.imc_mode == "off"
    assert hash(typed) != hash(cfg)  # configs stay hashable with a spec


def test_config_fabric_channels_behave_under_replace():
    from repro.configs import get_config, reduce_config

    base = reduce_config(get_config("qwen2.5-3b"))
    spec = FabricSpec(bits_a=4, bits_w=8, mode="sim", backend="jnp")
    cfg = dataclasses.replace(base, fabric=spec)
    # a conflicting legacy write on a fabric-carrying config raises loudly
    # instead of being silently ignored or silently rebuilding a lesser spec
    with pytest.raises(ValueError, match="authoritative"):
        dataclasses.replace(cfg, imc_mode="exact")
    # fabric=None alone turns IMC off — no resurrection from stale fields
    off = dataclasses.replace(cfg, fabric=None)
    assert off.imc_fabric is None and off.fabric is None
    # legacy-built configs keep pre-spec replace() semantics end to end
    leg = dataclasses.replace(base, imc_mode="sim", imc_bits=4)
    assert dataclasses.replace(leg, imc_mode="off").imc_fabric is None
    assert dataclasses.replace(leg, imc_bits=8).imc_fabric == FabricSpec(
        mode="sim")
    # mixing channels in one replace works when the legacy side is cleared
    assert dataclasses.replace(leg, fabric=spec,
                               imc_mode="off").imc_fabric == spec


# ------------------------------------------------- legacy kwargs removed
def test_legacy_kwargs_are_gone():
    """The pre-spec loose kwargs finished deprecation: they now raise
    TypeError like any unknown keyword, and the spec path is the only one."""
    from repro.models.common import dense, init_dense

    x, w = _xw(seed=8)
    with pytest.raises(TypeError):
        imc_matmul(x, w, bits=8, mode="sim", mismatch=True)
    with pytest.raises(TypeError):
        imc_matmul(x, w, use_kernel=True)
    p = init_dense(jax.random.key(0), 16, 8)
    xa = jax.random.normal(jax.random.key(1), (4, 16))
    with pytest.raises(TypeError):
        dense(p, xa, imc_mode="exact", imc_bits=8)
    lp = init_imc_linear(jax.random.key(0), 16, 8, use_bias=True)
    with pytest.raises(TypeError):  # old positional tail (bits, mode, kernel)
        imc_linear_apply(xa, lp["w"], lp["b"], 8, "sim", False)
    with pytest.raises(TypeError):
        apply_imc_linear(lp, xa, bits=4, mode="sim")
    with pytest.raises(ImportError):
        from repro.core.legacy import legacy_fabric_spec  # noqa: F401


def test_spec_path_serves_former_legacy_shapes():
    """Every mapping the shims used to provide is a one-line FabricSpec."""
    x, w = _xw(seed=9)
    key = jax.random.key(0)
    noisy = FabricSpec(mode="sim", backend="jnp",
                       noise=NoiseSpec(mismatch_sigma=C.MC_SIGMA_VK))
    y = fabric_matmul(x, w, noisy, key=key)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(imc_matmul(x, w, noisy, key=key)))
    # the old use_kernel=True + noise combination silently fell back to jnp;
    # the typed spec makes the engine explicit (and pallas + noise is now a
    # real engine of its own, not a fallback)
    assert noisy.resolve_backend() == "jnp" and noisy.noisy
    assert FabricSpec(mode="sim", backend="pallas").resolve_backend() == \
        "pallas"


# -------------------------------------------------------------- jit cache
def test_equal_specs_share_one_jit_entry():
    x, w = _xw(m=2, k=16, n=4, seed=10)
    spec_a = FabricSpec(bits_a=4, bits_w=4, mode="sim", backend="jnp")
    fabric_matmul(x, w, spec_a)
    n_before = fabric_matmul._cache_size()
    # a NEW but equal spec instance (incl. a canonicalized no-op NoiseSpec)
    spec_b = FabricSpec(bits_a=4, bits_w=4, mode="sim", backend="jnp",
                        noise=NoiseSpec())
    y = fabric_matmul(x, w, spec_b)
    assert fabric_matmul._cache_size() == n_before  # no recompile
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(fabric_matmul(x, w, spec_a)))
    # a genuinely different spec DOES add an entry
    fabric_matmul(x, w, FabricSpec(bits_a=4, bits_w=5, mode="sim",
                                   backend="jnp"))
    assert fabric_matmul._cache_size() == n_before + 1


# ----------------------------------------------------------- named scopes
@pytest.mark.parametrize("backend,scopes", [
    ("jnp", ("fabric.quantize",)),
    ("pallas", ("fabric.quantize", "fabric.planes", "fabric.mac")),
])
def test_sim_stages_are_scoped_and_stay_exact(backend, scopes):
    """The fabric's stages carry named scopes into the lowered module (a
    profiler trace names device ops by them); the scopes change neither the
    noise-free sim's numbers, which equal the exact digital product bit for
    bit, nor the jit cache."""
    import re

    x, w = _xw(m=4, k=32, n=8, seed=11)
    spec = FabricSpec(mode="sim", backend=backend)
    y = fabric_matmul(x, w, spec)
    exact = fabric_matmul(x, w, FabricSpec(mode="exact"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(exact))
    n = fabric_matmul._cache_size()
    fabric_matmul(x, w, spec)
    assert fabric_matmul._cache_size() == n
    text = jax.jit(lambda a, b: fabric_matmul(a, b, spec)).lower(
        x, w).as_text(debug_info=True)
    found = set(re.findall(r"fabric\.(?:quantize|planes|mac)", text))
    assert found == set(scopes)
