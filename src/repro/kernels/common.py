"""Shared in-kernel analog-path helpers for the Pallas IMC kernels.

``rbl_decode`` (one bit-plane pair) and the noisy ``bitplane_mac`` evaluate
the identical decode stage in-register, and the noise-free ``bitplane_mac``
takes its comparator references in counts (:func:`count_references`) from
the same voltage map and comparison; keeping them here means a threshold
tie-break fix or physics recalibration lands in every kernel at once.  Pure
jnp on values (not refs), so it is safe inside kernel bodies and in
interpret mode alike.

This module also owns the **in-kernel PRNG** the noisy kernels draw from
(:func:`make_normal_sampler`): on the compiled TPU path it seeds the Mosaic
per-core hardware PRNG (``pltpu.prng_seed`` / ``prng_random_bits``); in
interpret mode — where those primitives have no CPU lowering — it substitutes
a stateless murmur-mixed counter PRNG over (seed, draw index, element index).
Both feed Box-Muller, so either path yields f32 N(0,1) variates.  The two
streams are necessarily DIFFERENT bit patterns, which is why noisy-kernel
parity against the keyed jnp oracle is pinned on moments/quantiles, never on
bit identity.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C

_PHI32 = 0x9E3779B9  # golden-ratio odd constant (Weyl increment / mixing)
_INV_2_24 = float(2.0 ** -24)


def counts_to_voltage(k_float, rows: int):
    """MAC count (possibly fractional: mismatch) -> V_RBL, two-regime physics.

    The in-register mirror of :func:`repro.core.rbl.rbl_voltage_physics` at
    the calibrated 0.7 ns window, with the §III-F capacitance scaling for
    non-8-row geometries.
    """
    u = C.U_LIN * (C.ROWS / rows)
    x = k_float * u
    lin = C.V0_LEAK - x
    x_tri = jnp.maximum(x - (C.V0_LEAK - C.VD_SAT), 0.0)
    tri = C.VD_SAT * jnp.exp(-x_tri / C.VD_SAT)
    return jnp.where(lin >= C.VD_SAT, lin, tri)


def decode_counts(k_float, thr, rows: int):
    """Counts -> V_RBL (two-regime physics) -> comparator decode -> counts.

    ``thr`` is a (1, rows) block of descending comparator references;
    count = number of thresholds >= V, matching ``decoder.decode_voltage``.
    """
    v = counts_to_voltage(k_float, rows)
    # comparator bank: count = number of thresholds >= V (thr descending)
    dec = jnp.zeros_like(k_float)
    for i in range(rows):  # static unroll: rows is small (8)
        dec = dec + (v <= thr[0, i]).astype(jnp.float32)
    return dec


def count_references(thresholds, rows: int):
    """The comparator bank moved from volts to counts: int32[rows] with
    c_i = #{k in 0..rows : V(k) > thr[i]}.

    V falls with the count, so comparator i (``V <= thr[i]``, as in
    :func:`decode_counts`) fires on a noise-free count k exactly when
    k >= c_i.  Evaluated on the ``rows + 1`` levels, once per call; the
    thresholds stay data, so detuned or re-tuned references move c_i.
    """
    levels = counts_to_voltage(jnp.arange(rows + 1, dtype=jnp.float32), rows)
    thr = jnp.asarray(thresholds, jnp.float32).reshape(rows, 1)
    return jnp.sum(levels[None, :] > thr, axis=1, dtype=jnp.int32)


def decode_counts_noisy(k_float, thr, rows: int, normal, *,
                        mismatch_sigma=None, comparator_offset_sigma=None):
    """The noisy sibling of :func:`decode_counts` — the NoiseSpec path.

    Device mismatch perturbs the effective count before the voltage map
    (stddev ``mismatch_sigma * sqrt(count)``, matching
    ``montecarlo.mc_count_noise``); comparator offset perturbs each
    reference independently per element per comparator (matching
    ``decoder.thermometer_code``).  ``normal(shape)`` is a sampler from
    :func:`make_normal_sampler` — every call site draws a fresh stream.
    """
    if mismatch_sigma:
        k_float = k_float + mismatch_sigma * jnp.sqrt(
            jnp.maximum(k_float, 0.0)) * normal(k_float.shape)
    v = counts_to_voltage(k_float, rows)
    dec = jnp.zeros_like(v)
    for i in range(rows):  # static unroll: rows is small (8)
        t = thr[0, i]
        if comparator_offset_sigma:
            t = t + comparator_offset_sigma * normal(v.shape)
        dec = dec + (v <= t).astype(jnp.float32)
    return dec


def _mix32(x):
    """murmur3 fmix32: bijective avalanche mix on uint32 lanes."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _mix32_i32(x):
    """:func:`_mix32` on int32 scalars (the same bits; Mosaic keeps scalar
    arithmetic in int32)."""
    def c(v):
        return jnp.int32(v - (1 << 32) if v >= 1 << 31 else v)

    x = x ^ jax.lax.shift_right_logical(x, c(16))
    x = x * c(0x85EBCA6B)
    x = x ^ jax.lax.shift_right_logical(x, c(13))
    x = x * c(0xC2B2AE35)
    x = x ^ jax.lax.shift_right_logical(x, c(16))
    return x


def _bits_to_uniform(bits):
    """uint32/int32 random bits -> f32 uniform in [0, 1) (24-bit mantissa).

    Converts through int32 (the shifted value is below 2^24): Mosaic has no
    uint32 -> float32 cast.
    """
    if bits.dtype != jnp.int32:
        bits = jax.lax.bitcast_convert_type(bits, jnp.int32)
    top = jax.lax.shift_right_logical(bits, jnp.full(bits.shape, 8, jnp.int32))
    return top.astype(jnp.float32) * _INV_2_24


def make_normal_sampler(seeds, *, hw_prng: bool):
    """Build a ``normal(shape) -> f32 N(0,1)`` sampler for a kernel body.

    ``seeds`` — tuple of int32 scalars identifying the stream (base key words
    + a flattened grid-step index), so every (M-tile, N-tile, plane-pair,
    K-group) grid position draws an independent stream regardless of
    execution order.

    ``hw_prng=True``  — compiled TPU path: seed the Mosaic per-core PRNG once
    (re-seeded at every grid step from the step-folded seeds, so megacore
    partitioning of the parallel axes cannot correlate streams), then draw
    sequentially with ``prng_random_bits``.

    ``hw_prng=False`` — interpret-mode fallback: a stateless counter PRNG.
    Each call mixes (seed, per-call salt, element linear index) through two
    murmur rounds; no sequential state, so it is order-independent and runs
    on any backend.

    Both paths map bits -> [0,1) uniforms -> Box-Muller normals.  The draw
    counter is advanced at Python level during tracing (the kernel body
    traces once), giving each call site a distinct static salt.
    """
    counter = [0]
    if hw_prng:
        # The hardware PRNG takes at most two seed words: fold the rest in.
        w0, w1 = (jnp.asarray(s, jnp.int32) for s in seeds[:2])
        for s in seeds[2:]:
            w1 = _mix32_i32(w1 ^ _mix32_i32(jnp.asarray(s, jnp.int32)))
        pltpu.prng_seed(w0, w1)

        def uniforms(shape, salt):
            del salt  # the hardware stream is sequential
            return _bits_to_uniform(pltpu.prng_random_bits(shape))
    else:
        mixed = jnp.uint32(0)
        for s in seeds:
            word = jax.lax.bitcast_convert_type(
                jnp.asarray(s, jnp.int32), jnp.uint32)
            mixed = _mix32(mixed ^ word)

        def uniforms(shape, salt):
            lin = jnp.zeros(shape, jnp.uint32)
            stride = 1
            for d in reversed(range(len(shape))):
                lin = lin + jax.lax.broadcasted_iota(
                    jnp.uint32, shape, d) * jnp.uint32(stride)
                stride *= shape[d]
            x = mixed + jnp.uint32(salt) * jnp.uint32(_PHI32)
            return _bits_to_uniform(_mix32(_mix32(
                lin * jnp.uint32(_PHI32) + x)))

    def normal(shape):
        salt = counter[0]
        counter[0] += 2
        u1 = uniforms(shape, salt)
        u2 = uniforms(shape, salt + 1)
        # Box-Muller; 1-u1 in (2^-24, 1], so the log is always finite.
        r = jnp.sqrt(-2.0 * jnp.log(1.0 - u1))
        return r * jnp.cos((2.0 * math.pi) * u2)

    return normal
