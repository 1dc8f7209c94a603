"""Pallas TPU kernel: grouped binary MAC with in-loop analog RBL decode.

Hardware-faithful emulation of the paper's evaluation pipeline for one
bit-plane pair: the K dimension is tiled into groups of ``rows`` (8 — one SRAM
column-load each); each group's binary MAC count is pushed through the
charge-sharing voltage model and the comparator thermometer decode *before*
the digital shift-accumulate, exactly as the macro would.

  out[m, n] = sum_g decode( V( sum_{r<rows} a[m, g*rows+r] * w[g*rows+r, n] ) )

The decode is algebraically the identity for noise-free counts, but this
kernel keeps the analog stage in-loop so threshold re-tuning / reduced-margin
studies (paper §III-F scaling) run at kernel speed instead of pure-jnp speed.

Implementation notes (TPU adaptation):
  * group MACs are a G-batched (bm, rows) x (rows, bn) dot_general — small-K
    matmuls; the MXU eats them as a batched contraction.  This path trades
    MXU efficiency for per-group visibility; the *exact* path (imc_mac) is
    the production-speed collapse of the same math.
  * V(k) uses the fitted two-regime physics (exp/linear) on the VPU;
    comparator bank = 8 broadcast compares + sum, i.e. pure vector ops.
  * thresholds arrive as a (1, rows) block so corner-re-tuned references
    (paper §IV-C) are a data, not code, change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import constants as C
from repro.kernels.common import decode_counts


def _make_kernel(rows: int, bk: int):
    groups = bk // rows

    def kernel(a_ref, b_ref, thr_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        bm = a_ref.shape[0]
        bn = b_ref.shape[1]
        a = a_ref[...].astype(jnp.float32).reshape(bm, groups, rows)
        b = b_ref[...].astype(jnp.float32).reshape(groups, rows, bn)
        # counts[g, m, n] = sum_r a[m, g, r] * b[g, r, n]
        counts = jax.lax.dot_general(
            a, b, (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32)
        dec = decode_counts(counts, thr_ref[...], rows)
        acc_ref[...] += jnp.sum(dec, axis=0)

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _flush():
            o_ref[...] = acc_ref[...].astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("rows", "bm", "bn", "bk",
                                             "interpret"))
def rbl_decode_mac_raw(a_bits, w_bits, thresholds, *, rows: int = C.ROWS,
                       bm: int = 128, bn: int = 128, bk: int = 256,
                       interpret: bool = False):
    """Grouped-decode binary MAC.

    a_bits: int8[M, K] in {0,1}; w_bits: int8[K, N] in {0,1};
    thresholds: float32[rows] descending comparator references.
    M, N, K must be divisible by (bm, bn, bk) and bk by rows (ops.py pads).
    Returns int32[M, N] = sum of per-group decoded counts.
    """
    m, k = a_bits.shape
    k2, n = w_bits.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    assert bk % rows == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _make_kernel(rows, bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, rows), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_bits.astype(jnp.int8), w_bits.astype(jnp.int8),
      jnp.asarray(thresholds, jnp.float32).reshape(1, rows))
