"""Public wrappers for the bitplane_mac kernels (planes, padding, geometry).

Both entry points take *unsigned multi-bit* operands (offset-binary ints, the
same contract as ``core.bitserial.bitserial_matmul_unsigned``), explode them
into bit-planes, pad every axis to the kernel's block grid, and unpad the
result.  Zero padding is safe end-to-end: a zero bit contributes count 0 and
the decode maps 0 -> 0 (see the noisy-raw docstring for the noise argument),
so padded groups add nothing to the accumulator.

The noise-free entry folds the activation planes into the kernel's row
axis: M is padded to a multiple of 8, the planes [PA, M', K] become rows
[PA*M', K], and ``bm`` counts rows of that folded axis (the tile is
``min(bm, PA*M')``, so an 8-row 8-bit decode tick is one 64-row tile and a
128-row prefill eight 128-row tiles).  The kernel returns one 2^p-weighted
partial sum per folded row; the wrapper adds the PA row blocks under the
``fabric.mac`` scope.  The wrapper also moves the comparator references from
volts to counts (``kernels.common.count_references``: the voltage map on the
``rows + 1`` levels, once per call), and the kernel decodes four row-groups
per dot in packed 6-bit fields (see the kernel module).  Trace-time counters
``fabric.mac.real_rows`` (PA*M) and ``fabric.mac.tile_rows`` (folded rows
the grid launches) give the row fill of each traced shape;
``fabric.mac.groups`` (row-groups the grid decodes) over
``fabric.mac.group_dots`` (MXU dots issued for them) is the packing factor
that engaged.  The noisy entry keeps the unfolded [PA, M, K] layout, whose
grid keys its noise stream, and its voltage-domain decode of fractional
counts.

The wrappers are deliberately PLAIN functions in front of inner jits: tile
geometry defaults to the autotuner's cached winner for the call's shape
bucket (``repro.kernels.autotune``), and that resolution must happen at call
time, outside any jit cache — otherwise a re-tune (or a ``REPRO_TUNE_*`` pin
change) could silently keep executing stale tiles.  The resolved geometry is
then a static argument of the inner jit, so each geometry compiles once.
Explicit ``bm``/``bn``/``bk`` arguments always win over the tuner.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import constants as C
from repro.core.decoder import thresholds as core_thresholds
from repro.core.quant import to_bitplanes
from repro.kernels import autotune
from repro.kernels.bitplane_mac.bitplane_mac import (bitplane_mac_noisy_raw,
                                                     bitplane_mac_raw,
                                                     group_pack)
from repro.kernels.common import count_references
from repro.kernels.compat import resolve_interpret
from repro.telemetry import get_registry


def _resolve_geometry(m: int, k: int, n: int, bits_a: int, bits_w: int,
                      bm, bn, bk, interpret: bool) -> dict:
    geom = autotune.lookup(
        "bitplane_mac",
        {"m": m, "k": k, "n": n, "ba": bits_a, "bw": bits_w},
        interpret=interpret)
    if bm is not None:
        geom["bm"] = bm
    if bn is not None:
        geom["bn"] = bn
    if bk is not None:
        geom["bk"] = bk
    return geom


def _weight_planes(u_w, bits_w, bn, bk):
    """Weight bit-planes [PW, K, N] zero-padded to the block grid."""
    w_planes = to_bitplanes(u_w, bits_w)
    k, n = w_planes.shape[1:]
    pk, pn = (-k) % bk, (-n) % bn
    if pk or pn:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pk), (0, pn)))
    return w_planes


def _padded_planes(u_a, u_w, bits_a, bits_w, bm, bn, bk):
    """Both operands exploded into bit-planes and zero-padded to the block
    grid, under the ``fabric.planes`` scope: the activation planes folded
    into rows [PA * m_pad, K] (batch dims flattened into M, M padded to
    ``m_pad``, a multiple of 8; plane p in rows [p*m_pad, (p+1)*m_pad)),
    then zero rows up to a multiple of the row tile, ``min(bm, PA *
    m_pad)``; and [PW, K, N].  Returns (rows, weight planes, m_pad, tile)."""
    with jax.named_scope("fabric.planes"):
        k = u_a.shape[-1]
        a_planes = to_bitplanes(u_a.reshape(-1, k), bits_a)
        m = a_planes.shape[1]
        m_pad = m + (-m) % 8
        a_planes = jnp.pad(a_planes, ((0, 0), (0, m_pad - m),
                                      (0, (-k) % bk)))
        a_rows = a_planes.reshape(bits_a * m_pad, a_planes.shape[2])
        bm = min(bm, bits_a * m_pad)
        pr = (-a_rows.shape[0]) % bm
        if pr:
            a_rows = jnp.pad(a_rows, ((0, pr), (0, 0)))
        w_planes = _weight_planes(u_w, bits_w, bn, bk)
    return a_rows, w_planes, m_pad, bm


def _stacked_planes(u_a, u_w, bits_a, bits_w, bm, bn, bk):
    """The noisy kernel's operands, under the ``fabric.planes`` scope:
    activation planes [PA, M, K] (batch dims flattened into M) and
    [PW, K, N], each zero-padded to the block grid."""
    with jax.named_scope("fabric.planes"):
        k = u_a.shape[-1]
        a_planes = to_bitplanes(u_a.reshape(-1, k), bits_a)
        pm, pk = (-a_planes.shape[1]) % bm, (-k) % bk
        if pm or pk:
            a_planes = jnp.pad(a_planes, ((0, 0), (0, pm), (0, pk)))
        w_planes = _weight_planes(u_w, bits_w, bn, bk)
    return a_planes, w_planes


def _unpad(out, u_a, u_w):
    """The kernel's padded int32[M', N'] result cut to ``u_a @ u_w``'s
    shape."""
    batch = u_a.shape[:-1]
    n = u_w.shape[-1]
    return out[:math.prod(batch), :n].reshape(*batch, n)


@functools.partial(jax.jit, static_argnames=("bits_a", "bits_w", "rows",
                                             "bm", "bn", "bk", "interpret"))
def _bitplane_mac_jit(u_a, u_w, thr, *, bits_a, bits_w, rows, bm, bn, bk,
                      interpret):
    a_rows, w_planes, m_pad, bm = _padded_planes(u_a, u_w, bits_a, bits_w,
                                                 bm, bn, bk)
    batch, n = u_a.shape[:-1], u_w.shape[-1]
    m = math.prod(batch)
    # Trace-time row fill of the folded tile: real (plane, row) pairs over
    # the rows the grid launches.  Recorded once per traced shape.
    reg = get_registry()
    reg.counter("fabric.mac.real_rows").inc(bits_a * m)
    reg.counter("fabric.mac.tile_rows").inc(a_rows.shape[0])
    # ... and the row-groups the grid decodes over the MXU dots issued for
    # them: their ratio is the packing factor that engaged.
    steps = (a_rows.shape[0] // bm) * (w_planes.shape[2] // bn) * bits_w \
        * (w_planes.shape[1] // bk)
    groups = steps * (bk // rows)
    reg.counter("fabric.mac.groups").inc(groups)
    reg.counter("fabric.mac.group_dots").inc(groups // group_pack(rows, bk)[0])
    with jax.named_scope("fabric.mac"):
        out = bitplane_mac_raw(a_rows, w_planes, count_references(thr, rows),
                               m_pad=m_pad, bits_a=bits_a, rows=rows, bm=bm,
                               bn=bn, bk=bk,
                               valid_groups=-(-u_a.shape[-1] // rows),
                               interpret=interpret)
        # each activation plane's row block holds its 2^p-weighted partial
        # sums; their sum is the product
        parts = out[:bits_a * m_pad, :n].reshape(bits_a, m_pad, n)[:, :m]
        return jnp.sum(parts, axis=0, dtype=jnp.int32).reshape(*batch, n)


def bitplane_mac(u_a, u_w, thr=None, *, bits_a: int = 8, bits_w: int = 8,
                 rows: int = C.ROWS, bm: int | None = None,
                 bn: int | None = None, bk: int | None = None,
                 interpret: bool | None = None):
    """Fused full-pyramid bit-serial matmul for arbitrary shapes.

    u_a: int[..., K] in [0, 2^bits_a); u_w: int[K, N) likewise.  Leading batch
    dims of ``u_a`` flatten into M.  ``thr`` defaults to the physics-model
    comparator references for ``rows`` (re-tunable, paper §IV-C).  Tile
    geometry (bm, bn, bk) defaults to the autotuner's cached winner for this
    shape bucket; pass explicit values to override.
    Returns int32[..., N] == u_a @ u_w (noise-free decode is exact).
    """
    interpret = resolve_interpret(interpret)
    geom = _resolve_geometry(math.prod(u_a.shape[:-1]), u_a.shape[-1],
                             u_w.shape[-1], bits_a, bits_w, bm, bn, bk,
                             interpret)
    if thr is None:
        thr = core_thresholds(rows, mode="physics")
    return _bitplane_mac_jit(u_a, u_w, thr, bits_a=bits_a, bits_w=bits_w,
                             rows=rows, bm=geom["bm"], bn=geom["bn"],
                             bk=geom["bk"], interpret=interpret)


def _key_words(key):
    """PRNG key -> int32[2] seed words for scalar prefetch.

    Accepts a typed jax PRNG key or a raw uint32 key-data array; folds
    whatever width the impl uses down to two words (threefry2x32 is exactly
    two, rbg is four).
    """
    if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = jnp.asarray(key)
    data = data.reshape(-1).astype(jnp.uint32)
    if data.shape[0] == 1:
        data = jnp.concatenate([data, data ^ jnp.uint32(0x9E3779B9)])
    elif data.shape[0] > 2:
        folded = data[:2]
        for i in range(2, data.shape[0]):
            folded = folded.at[i % 2].set(folded[i % 2] ^ data[i])
        data = folded
    return jax.lax.bitcast_convert_type(data, jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "bits_a", "bits_w", "rows", "mismatch_sigma", "comparator_offset_sigma",
    "bm", "bn", "bk", "interpret"))
def _bitplane_mac_noisy_jit(u_a, u_w, thr, key, *, bits_a, bits_w, rows,
                            mismatch_sigma, comparator_offset_sigma, bm, bn,
                            bk, interpret):
    a_planes, w_planes = _stacked_planes(u_a, u_w, bits_a, bits_w, bm, bn,
                                         bk)
    with jax.named_scope("fabric.mac"):
        out = bitplane_mac_noisy_raw(
            a_planes, w_planes, thr, _key_words(key), rows=rows, bm=bm,
            bn=bn, bk=bk, mismatch_sigma=mismatch_sigma,
            comparator_offset_sigma=comparator_offset_sigma,
            valid_groups=-(-u_a.shape[-1] // rows), interpret=interpret)
        return _unpad(out, u_a, u_w)


def bitplane_mac_noisy(u_a, u_w, key, thr=None, *, bits_a: int = 8,
                       bits_w: int = 8, rows: int = C.ROWS,
                       mismatch_sigma: float | None = None,
                       comparator_offset_sigma: float | None = None,
                       bm: int | None = None, bn: int | None = None,
                       bk: int | None = None,
                       interpret: bool | None = None):
    """Fused full-pyramid bit-serial matmul with in-kernel NoiseSpec noise.

    Same operand contract as :func:`bitplane_mac` plus ``key`` (a jax PRNG
    key — the ambient ``fabric_noise_key``) and the NoiseSpec sigmas.  The
    whole noisy pyramid runs as ONE ``pallas_call``; same key -> identical
    outputs.  The draw stream differs from the keyed jnp engine's threefry by
    construction, so cross-engine agreement is statistical (moments /
    quantiles), never bitwise — tests pin it that way.
    """
    interpret = resolve_interpret(interpret)
    if thr is None:
        thr = core_thresholds(rows, mode="physics")
    geom = _resolve_geometry(math.prod(u_a.shape[:-1]), u_a.shape[-1],
                             u_w.shape[-1], bits_a, bits_w, bm, bn, bk,
                             interpret)
    return _bitplane_mac_noisy_jit(
        u_a, u_w, thr, key, bits_a=bits_a, bits_w=bits_w, rows=rows,
        mismatch_sigma=mismatch_sigma,
        comparator_offset_sigma=comparator_offset_sigma, bm=geom["bm"],
        bn=geom["bn"], bk=geom["bk"], interpret=interpret)
