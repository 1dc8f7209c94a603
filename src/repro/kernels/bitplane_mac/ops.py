"""Public wrappers for the bitplane_mac kernels (planes, padding, geometry).

Both entry points take *unsigned multi-bit* operands (offset-binary ints, the
same contract as ``core.bitserial.bitserial_matmul_unsigned``), explode them
into bit-planes, pad every axis to the kernel's block grid, and unpad the
result.  Zero padding is safe end-to-end: a zero bit contributes count 0 and
the decode maps 0 -> 0 (see the noisy-raw docstring for the noise argument),
so padded groups add nothing to the accumulator.

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

import jax
import jax.numpy as jnp

from repro.core import constants as C
from repro.core.decoder import thresholds as core_thresholds
from repro.core.quant import to_bitplanes
from repro.kernels import autotune
from repro.kernels.bitplane_mac.bitplane_mac import (bitplane_mac_noisy_raw,
                                                     bitplane_mac_raw)
from repro.kernels.compat import resolve_interpret


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


@functools.partial(jax.jit, static_argnames=("bits_a", "bits_w", "rows",
                                             "bm", "bn", "bk", "interpret"))
def _bitplane_mac_jit(u_a, u_w, thr, *, bits_a, bits_w, rows, bm, bn, bk,
                      interpret):
    batch = u_a.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    k = u_a.shape[-1]
    n = u_w.shape[-1]
    a_planes = to_bitplanes(u_a.reshape(m, k), bits_a)  # [PA, M, K]
    w_planes = to_bitplanes(u_w, bits_w)                # [PW, K, N]
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pk), (0, pn)))
    out = bitplane_mac_raw(a_planes, w_planes, thr, rows=rows, bm=bm, bn=bn,
                           bk=bk, interpret=interpret)
    return out[:m, :n].reshape(*batch, n)


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
    batch = u_a.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    geom = _resolve_geometry(m, u_a.shape[-1], u_w.shape[-1], bits_a, bits_w,
                             bm, bn, bk, interpret)
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
    batch = u_a.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    k = u_a.shape[-1]
    n = u_w.shape[-1]
    a_planes = to_bitplanes(u_a.reshape(m, k), bits_a)
    w_planes = to_bitplanes(u_w, bits_w)
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a_planes = jnp.pad(a_planes, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, pk), (0, pn)))
    out = bitplane_mac_noisy_raw(
        a_planes, w_planes, thr, _key_words(key), rows=rows, bm=bm, bn=bn,
        bk=bk, mismatch_sigma=mismatch_sigma,
        comparator_offset_sigma=comparator_offset_sigma,
        valid_groups=-(-k // rows), interpret=interpret)
    return out[:m, :n].reshape(*batch, n)


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
    batch = u_a.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    geom = _resolve_geometry(m, u_a.shape[-1], u_w.shape[-1], bits_a, bits_w,
                             bm, bn, bk, interpret)
    return _bitplane_mac_noisy_jit(
        u_a, u_w, thr, key, bits_a=bits_a, bits_w=bits_w, rows=rows,
        mismatch_sigma=mismatch_sigma,
        comparator_offset_sigma=comparator_offset_sigma, bm=geom["bm"],
        bn=geom["bn"], bk=geom["bk"], interpret=interpret)
