"""Pallas TPU kernel: the FULL bit-plane pyramid MAC in one pallas_call.

Generalizes ``repro.kernels.rbl_decode`` from one bit-plane pair to all
``bits_a x bits_w`` pairs: per output tile the kernel sweeps plane pairs and
K-blocks (the activation planes ride the row axis, see below), and for every
(pair, K-block) it runs the paper's whole evaluation pipeline — per-8-row-
group binary MAC counts, charge-sharing RBL voltage (tabulated on the count
levels, see below), comparator thermometer decode, and the
``2^(p+q)``-weighted digital shift-accumulate — without leaving VMEM:

  out[m, n] = sum_{p,q} 2^{p+q} sum_g decode( V( sum_r a[p, m, g*rows+r]
                                                   * w[q, g*rows+r, n] ) )

The decode is algebraically the identity for noise-free counts, so the result
is bit-identical to the plane-batched jnp engine AND the seed per-plane loop
(``core/bitserial.py``); the point is that the 64-round einsum+decode pyramid
becomes ONE kernel launch with a single int32 accumulator per tile.

Implementation notes (TPU adaptation):
  * the row axis carries (activation plane, row) pairs: the wrapper folds
    the activation planes [PA, M, K] into rows [PA*M', K] (M' = M padded to
    a multiple of 8), so a decode tick's 8 rows fill a 64-row tile with
    real bits instead of padding one 128-row tile per activation plane.
  * grid (R/bm, N/bn, PW, K/bk) over R folded rows, with the weight-plane
    axis third and K innermost; both are "arbitrary" (they carry the
    accumulator), row/N tiles are parallel.  Each (bk, bn) weight-plane
    block is read once per row tile, for every activation plane at once;
    VMEM never holds more than one (bm, bk) + (bk, bn) plane slice.
  * the comparator bank runs in the count domain.  A noise-free count k
    takes only the values 0..rows and V(k) falls with k, so comparator i
    (V <= thr[i]) fires exactly when k >= c_i, c_i = #{j : V(j) > thr[i]}.
    The wrapper evaluates the voltage map on the rows + 1 levels once per
    call and hands the kernel c_i as int32 scalars (scalar prefetch); the
    thresholds stay data, so corner-re-tuned references (paper §IV-C) are a
    data, not code, change.  Every comparator still runs on every count.
  * four row-groups share one dot (:func:`group_pack`): group g's
    activation bits are scaled by 64^(g mod 4) (exact in bf16), so one
    (bm, 4*rows) x (4*rows, bn) dot returns k0 + 64 k1 + 4096 k2 +
    262144 k3, exact in f32 (below 2^24).  Comparator i is then one add of
    (32 - c_i) to every 6-bit field and one mask of the fields' top bits;
    the masked bits of several packs add up before one shift and unpack,
    as long as no field can pass 63.  Counts past 31 (``rows`` > 31), or a
    K-block of whole groups not divisible by four, take one group per dot
    with the same compare on one wider field.
  * the plane weight 2^(p+q) is split: each step adds its 2^q-weighted
    decode (q from ``pl.program_id``), and the tile's flush shifts each row
    by its p, found from the row's global index, so the inner loop costs
    what the unfolded kernel's did.  Accumulation is int32 (float32 would
    lose bit-exactness beyond 2^24 for deep-K 8-bit operands); the kernel
    writes each row's partial sum, at most 2^(PA-1) * (2^PW - 1) * K, and
    the wrapper adds the PA row blocks.
  * whole zero groups of K padding (past ``ceil(K / rows)``) exist only in
    the block grid, not in the macro: in the last K-block their fields get
    no lift, so no comparator fires on them even when a detuned reference
    lies above V(0).

The NOISY sibling (:func:`bitplane_mac_noisy_raw`) keeps the unfolded
layout — activation planes [PA, M, K] on a (M/bm, N/bn, PA*PW, K/bk) grid,
the pair axis recovering (p, q) by div/mod — and runs the
:class:`~repro.core.fabric.NoiseSpec` Monte-Carlo INSIDE the kernel: per
grid step it builds a PRNG stream seeded from (fabric key words, flattened
grid-step index) — the Mosaic hardware PRNG when compiled, the counter-hash
fallback in interpret mode (``kernels.common.make_normal_sampler``) — then
applies Gaussian device mismatch to the effective counts ahead of the RBL
voltage map and comparator offset to the decode references, so all 64 plane
pairs x K-groups x decode x accumulate stay ONE ``pallas_call`` for noisy
specs too.  The key words ride in via scalar prefetch
(``pltpu.PrefetchScalarGridSpec``).  Noise draws are necessarily a different
bit stream than the keyed jnp engine's threefry, so
parity with that oracle is statistical (moments/quantiles), never bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C
from repro.kernels.common import decode_counts_noisy, make_normal_sampler


def _group_counts(a, b, rows: int):
    """counts[g, m, n] = sum_r a[m, g*rows + r] * b[g*rows + r, n]: the
    binary MAC of every row-group of the block, exact in f32 (at most
    ``rows``).  One (bm, rows) x (rows, bn) bf16 dot per group (bits are
    {0,1}, so bf16 is exact); the weight block goes through f32 so that its
    row-group slices fall on whole (8, 128) tiles."""
    a = a.astype(jnp.float32).astype(jnp.bfloat16)
    b = b.astype(jnp.float32)
    return jnp.stack([
        jax.lax.dot_general(
            a[:, lo:lo + rows], b[lo:lo + rows, :].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for lo in range(0, a.shape[1], rows)])


def group_pack(rows: int, bk: int) -> tuple[int, int]:
    """(groups per dot, field bits) of the count-domain comparator bank for
    ``rows``-row groups in a ``bk``-deep K-block.

    Four groups share one dot while a count fits a 6-bit field with its top
    bit spare (``rows <= 31``) and the block splits into whole packs: the
    MXU then returns k0 + 64 k1 + 4096 k2 + 262144 k3, an integer below
    2^24 and so exact in f32.  Otherwise each group has a dot of its own
    and one field whose top bit lies just above ``rows``."""
    if rows <= 31 and (bk // rows) % 4 == 0:
        return 4, 6
    return 1, rows.bit_length() + 1


def _make_kernel(rows: int, bk: int, m_pad: int, bits_a: int,
                 last_valid: int | None):
    pack, fbits = group_pack(rows, bk)
    span, packs = pack * rows, bk // (pack * rows)
    half = 1 << (fbits - 1)
    ones = sum(1 << (fbits * f) for f in range(pack))  # a 1 in every field
    tops = ones << (fbits - 1)  # every field's top bit
    # decoded packs summed before a field could pass 2^fbits - 1
    per_sum = ((1 << fbits) - 1) // rows if pack > 1 else packs

    def field_sum(x):
        """The sum over fields of x, each field holding a count at its top
        bit."""
        x = x >> (fbits - 1)
        out = x >> (fbits * (pack - 1))
        for f in range(pack - 1):
            out = out + ((x >> (fbits * f)) & ((1 << fbits) - 1))
        return out

    def kernel(ref_ref, a_ref, b_ref, scale_ref, o_ref, acc_ref):
        i = pl.program_id(0)
        q = pl.program_id(2)
        kk = pl.program_id(3)
        last = kk == pl.num_programs(3) - 1

        @pl.when((q == 0) & (kk == 0))
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # Group g's activation bits carry 64^(g mod 4) (powers of two, exact
        # in bf16), so one dot over a pack's 4*rows columns gives its four
        # counts in four fields.
        a = (a_ref[...].astype(jnp.float32) * scale_ref[...]).astype(
            jnp.bfloat16)
        b = b_ref[0].astype(jnp.bfloat16)
        # Comparator r fires on count k >= c_r, which sets the field's top
        # bit of k + half - c_r: one add and one mask per comparator for
        # every field of the pack.  The masked bits are summed as they are
        # and shifted down once per sum.
        lifts = [half - ref_ref[r] for r in range(rows)]
        full = [x * ones for x in lifts]
        tot, run = None, None
        for j in range(packs):
            lo = j * span
            p = jax.lax.dot_general(
                a[:, lo:lo + span], b[lo:lo + span, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32)
            adds = full
            if last_valid is not None and (j + 1) * pack > last_valid:
                # K padding past the operand's last group: the macro has no
                # such groups, so in the last block their fields get no lift
                # and no comparator fires on their zero counts.
                live = sum(1 << (fbits * f) for f in range(pack)
                           if j * pack + f < last_valid)
                live = jnp.where(last, jnp.int32(live), jnp.int32(ones))
                adds = [x * live for x in lifts]
            for x in adds:
                t = (p + x) & tops
                run = t if run is None else run + t
            if (j + 1) % per_sum == 0 or j == packs - 1:
                part = field_sum(run)
                tot = part if tot is None else tot + part
                run = None
        # digital shift-accumulate, weight-plane part 2^q ...
        weight = jax.lax.shift_left(jnp.int32(1), q)
        acc_ref[...] += weight * tot

        @pl.when((q == pl.num_programs(2) - 1) & last)
        def _flush():
            # ... and activation-plane part 2^p once per tile: folded row r
            # holds plane p = r // m_pad, found by compares (Mosaic has no
            # vector integer division).
            r = i * acc_ref.shape[0] + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            p = sum((r >= t * m_pad).astype(jnp.int32)
                    for t in range(1, bits_a))
            o_ref[...] = jax.lax.shift_left(acc_ref[...], p)

    return kernel


@functools.partial(jax.jit, static_argnames=("rows", "m_pad", "bits_a",
                                             "bm", "bn", "bk", "valid_groups",
                                             "interpret"))
def bitplane_mac_raw(a_rows, w_planes, refs, *, m_pad: int,
                     bits_a: int, rows: int = C.ROWS, bm: int = 128,
                     bn: int = 128, bk: int = 256,
                     valid_groups: int | None = None,
                     interpret: bool = False):
    """Fused full-pyramid decode MAC over plane-folded activation rows.

    a_rows: int8[R, K] in {0,1}: the activation bit-planes [PA, m_pad, K]
    (LSB first) flattened to rows, plane p in rows [p*m_pad, (p+1)*m_pad),
    then zero rows up to R (``bits_a`` = PA); w_planes: int8[PW, K, N] in
    {0,1}; refs: int32[rows], the comparator references in counts
    (``kernels.common.count_references`` of the thresholds).  R, N, K must
    be divisible by (bm, bn, bk) and bk by rows (ops.py pads);
    ``valid_groups`` is the number of real row-groups, ``ceil(K_orig /
    rows)`` (defaults to all): the zero groups past it do not decode.
    Returns int32[R, N]: row p*m_pad + m holds sum_q 2^(p+q) * sum_g
    decoded_count[p, q, g] of row m; summing the PA row blocks gives the
    product.
    """
    r, k = a_rows.shape
    pw, k2, n = w_planes.shape
    assert k == k2 and r % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % rows == 0 and r >= bits_a * m_pad
    pack, fbits = group_pack(rows, bk)
    last_valid = None
    if valid_groups is not None and valid_groups < k // rows:
        last_valid = valid_groups - (k // bk - 1) * (bk // rows)
        assert last_valid > 0, "K padding must lie in the last K-block"
    field = (np.arange(bk) // rows) % pack
    scale = np.left_shift(1, fbits * field).astype(np.float32).reshape(1, bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r // bm, n // bn, pw, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, q, kk, c: (i, kk)),
            pl.BlockSpec((1, bk, bn), lambda i, j, q, kk, c: (q, kk, j)),
            pl.BlockSpec((1, bk), lambda i, j, q, kk, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, q, kk, c: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )
    return pl.pallas_call(
        _make_kernel(rows, bk, m_pad, bits_a, last_valid),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(refs, jnp.int32), a_rows.astype(jnp.int8),
      w_planes.astype(jnp.int8), jnp.asarray(scale))


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
            _group_counts(a_ref[0], b_ref[0], rows), thr_ref[...], rows,
            normal, mismatch_sigma=mismatch_sigma,
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

    a_planes: int8[PA, M, K] in {0,1} (activation bit-planes, LSB first,
    not folded: the noise stream is keyed by the flattened index of the
    (M/bm, N/bn, PA*PW, K/bk) grid); w_planes, thresholds as in
    :func:`bitplane_mac_raw`; M, N, K divisible by (bm, bn, bk).  ``seed``
    is int32[2] PRNG key words (scalar-prefetched); the noise sigmas are
    static.  ``valid_groups`` is the number of REAL row-groups (pre-padding,
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
