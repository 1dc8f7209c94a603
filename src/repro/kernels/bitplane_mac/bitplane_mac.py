"""Pallas TPU kernel: the FULL bit-plane pyramid MAC in one pallas_call.

Generalizes ``repro.kernels.rbl_decode`` from one bit-plane pair to all
``bits_a x bits_w`` pairs: per output tile the kernel sweeps plane pairs and
K-blocks, and for every (pair, K-block) it runs the paper's whole evaluation
pipeline — per-8-row-group binary MAC counts, charge-sharing RBL voltage,
comparator thermometer decode, and the ``2^(p+q)``-weighted digital
shift-accumulate — without leaving VMEM:

  out[m, n] = sum_{p,q} 2^{p+q} sum_g decode( V( sum_r a[p, m, g*rows+r]
                                                   * w[q, g*rows+r, n] ) )

The decode is algebraically the identity for noise-free counts, so the result
is bit-identical to the plane-batched jnp engine AND the seed per-plane loop
(``core/bitserial.py``); the point is that the 64-round einsum+decode pyramid
becomes ONE kernel launch with a single int32 accumulator per tile.

Implementation notes (TPU adaptation):
  * grid (M/bm, N/bn, PP, K/bk) with the plane-pair axis PP = bits_a * bits_w
    third and K innermost; both are "arbitrary" (they carry the accumulator),
    M/N tiles are parallel.
  * the index maps recover (p, q) from the flat pair index by div/mod, so the
    activation planes tensor [PA, M, K] and the weight planes tensor
    [PW, K, N] are streamed block-by-block — VMEM never holds more than one
    (bm, bk) + (bk, bn) plane slice.
  * group MACs are one (bm, rows) x (rows, bn) bf16 dot per row-group,
    unrolled over the ``bk // rows`` groups of a K-block and stacked into
    (groups, bm, bn) counts (Mosaic lowers no in-kernel reshape that splits
    the lane axis into groups); V(k) is the fitted two-regime physics on
    the VPU; the comparator bank is ``rows`` broadcast compares.
  * the plane weight 2^(p+q) is computed from ``pl.program_id`` on the fly
    (shift of an int32 one), and accumulation is int32 — float32 would lose
    bit-exactness beyond 2^24 for deep-K 8-bit operands.
  * thresholds arrive as a (1, rows) block so corner-re-tuned references
    (paper §IV-C) stay a data, not code, change.

The NOISY sibling (:func:`bitplane_mac_noisy_raw`) keeps the identical grid
and accumulator but runs the :class:`~repro.core.fabric.NoiseSpec`
Monte-Carlo INSIDE the kernel: per grid step it builds a PRNG stream seeded
from (fabric key words, flattened grid-step index) — the Mosaic hardware PRNG
when compiled, the counter-hash fallback in interpret mode
(``kernels.common.make_normal_sampler``) — then applies Gaussian device
mismatch to the effective counts ahead of the RBL voltage map and comparator
offset to the decode references, so all 64 plane pairs x K-groups x decode x
accumulate stay ONE ``pallas_call`` for noisy specs too.  The key words ride
in via scalar prefetch (``pltpu.PrefetchScalarGridSpec``).  Noise draws are
necessarily a different bit stream than the keyed jnp engine's threefry, so
parity with that oracle is statistical (moments/quantiles), never bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C
from repro.kernels.common import (decode_counts, decode_counts_noisy,
                                  make_normal_sampler)


def _group_counts(a_ref, b_ref, rows: int):
    """counts[g, m, n] = sum_r a[m, g*rows + r] * b[g*rows + r, n]: the
    binary MAC of every row-group of the block, exact in f32 (at most
    ``rows``).  One (bm, rows) x (rows, bn) bf16 dot per group (bits are
    {0,1}, so bf16 is exact); the weight block goes through f32 so that its
    row-group slices fall on whole (8, 128) tiles."""
    a = a_ref[0].astype(jnp.float32).astype(jnp.bfloat16)
    b = b_ref[0].astype(jnp.float32)
    return jnp.stack([
        jax.lax.dot_general(
            a[:, lo:lo + rows], b[lo:lo + rows, :].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for lo in range(0, a.shape[1], rows)])


def _make_kernel(rows: int, bits_w: int):
    def kernel(a_ref, b_ref, thr_ref, o_ref, acc_ref):
        pp = pl.program_id(2)
        kk = pl.program_id(3)

        @pl.when((pp == 0) & (kk == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dec = decode_counts(_group_counts(a_ref, b_ref, rows), thr_ref[...],
                            rows)
        # digital shift-accumulate: weight = 2^(p+q), pair index pp = p*PW + q
        shift = pp // bits_w + pp % bits_w
        weight = jax.lax.shift_left(jnp.int32(1), shift)
        acc_ref[...] += weight * jnp.sum(dec, axis=0).astype(jnp.int32)

        @pl.when((pp == pl.num_programs(2) - 1)
                 & (kk == pl.num_programs(3) - 1))
        def _flush():
            o_ref[...] = acc_ref[...]

    return kernel


@functools.partial(jax.jit, static_argnames=("rows", "bm", "bn", "bk",
                                             "interpret"))
def bitplane_mac_raw(a_planes, w_planes, thresholds, *, rows: int = C.ROWS,
                     bm: int = 128, bn: int = 128, bk: int = 256,
                     interpret: bool = False):
    """Fused full-pyramid decode MAC.

    a_planes: int8[PA, M, K] in {0,1} (activation bit-planes, LSB first);
    w_planes: int8[PW, K, N] in {0,1}; thresholds: float32[rows] descending.
    M, N, K must be divisible by (bm, bn, bk) and bk by rows (ops.py pads).
    Returns int32[M, N] = sum_{p,q} 2^(p+q) * sum_g decoded_count[p, q, g].
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % rows == 0
    grid = (m // bm, n // bn, pa * pw, k // bk)
    return pl.pallas_call(
        _make_kernel(rows, pw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, pp, kk: (pp // pw, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda i, j, pp, kk: (pp % pw, kk, j)),
            pl.BlockSpec((1, rows), lambda i, j, pp, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, pp, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(a_planes.astype(jnp.int8), w_planes.astype(jnp.int8),
      jnp.asarray(thresholds, jnp.float32).reshape(1, rows))


def _make_noisy_kernel(rows: int, bk: int, bits_w: int, mismatch_sigma,
                       comparator_sigma, hw_prng: bool, valid_groups: int):
    groups = bk // rows

    def kernel(seed_ref, a_ref, b_ref, thr_ref, o_ref, acc_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)
        pp = pl.program_id(2)
        kk = pl.program_id(3)

        @pl.when((pp == 0) & (kk == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # One independent stream per (M-tile, N-tile, plane-pair, K-group):
        # the flattened grid-step index folds into the fabric key words, so
        # no two grid positions (and no two keys) share noise.
        step = ((i * pl.num_programs(1) + j) * pl.num_programs(2) + pp) \
            * pl.num_programs(3) + kk
        normal = make_normal_sampler(
            (seed_ref[0], seed_ref[1], step), hw_prng=hw_prng)

        dec = decode_counts_noisy(
            _group_counts(a_ref, b_ref, rows), thr_ref[...], rows, normal,
            mismatch_sigma=mismatch_sigma,
            comparator_offset_sigma=comparator_sigma)
        # Padded K-groups (beyond the operand's real K) must not decode:
        # unlike the noise-free kernel — where decode(0) == 0 makes padding
        # free — comparator offset can flip a zero-count group's decode, and
        # the jnp oracle has no such groups at all.  Mask them out.
        g0 = kk * groups
        gidx = g0 + jax.lax.broadcasted_iota(jnp.int32, (groups, 1, 1), 0)
        dec = jnp.where(gidx < valid_groups, dec, 0.0)
        shift = pp // bits_w + pp % bits_w
        weight = jax.lax.shift_left(jnp.int32(1), shift)
        acc_ref[...] += weight * jnp.sum(dec, axis=0).astype(jnp.int32)

        @pl.when((pp == pl.num_programs(2) - 1)
                 & (kk == pl.num_programs(3) - 1))
        def _flush():
            o_ref[...] = acc_ref[...]

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "rows", "bm", "bn", "bk", "mismatch_sigma", "comparator_offset_sigma",
    "valid_groups", "interpret"))
def bitplane_mac_noisy_raw(a_planes, w_planes, thresholds, seed, *,
                           rows: int = C.ROWS, bm: int = 128, bn: int = 128,
                           bk: int = 256, mismatch_sigma=None,
                           comparator_offset_sigma=None,
                           valid_groups: int | None = None,
                           interpret: bool = False):
    """Fused full-pyramid decode MAC with in-kernel NoiseSpec Monte-Carlo.

    Same operand contract as :func:`bitplane_mac_raw`, plus ``seed`` —
    int32[2] PRNG key words (scalar-prefetched) — and the static noise
    sigmas.  ``valid_groups`` is the number of REAL row-groups (pre-padding,
    ``ceil(K_orig / rows)``; defaults to all): groups past it are K-padding
    and their decodes are masked, because comparator offset can flip a
    zero-count group's decode — mismatch alone is padding-safe (stddev
    ``sigma * sqrt(0) = 0``) but the offset term is not, and the jnp oracle
    has no padded groups to draw such flips from.  Returns int32[M, N].
    """
    pa, m, k = a_planes.shape
    pw, k2, n = w_planes.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % rows == 0
    if valid_groups is None:
        valid_groups = k // rows
    grid = (m // bm, n // bn, pa * pw, k // bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda i, j, pp, kk, s: (pp // pw, i, kk)),
            pl.BlockSpec((1, bk, bn),
                         lambda i, j, pp, kk, s: (pp % pw, kk, j)),
            pl.BlockSpec((1, rows), lambda i, j, pp, kk, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, pp, kk, s: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        _make_noisy_kernel(rows, bk, pw, mismatch_sigma,
                           comparator_offset_sigma, hw_prng=not interpret,
                           valid_groups=valid_groups),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(seed, a_planes.astype(jnp.int8), w_planes.astype(jnp.int8),
      jnp.asarray(thresholds, jnp.float32).reshape(1, rows))
