"""Pallas TPU kernel: IMC-equivalent int8 MAC (quantized GEMM).

This is the *exact digital equivalent* of the paper's bit-serial SRAM MAC:
because the thermometer decode is exact on [0, rows], the per-8-row group
counts telescope and the whole bit-plane pyramid collapses to an int8 x int8
integer matmul (see core/bitserial.py for the proof-by-construction).  On TPU
that is MXU-native work; this kernel supplies the blocked VMEM implementation
with int32 accumulation and optional fused per-channel dequantization.

Tiling: grid (M/bm, N/bn, K/bk), K innermost ("arbitrary"), VMEM int32
accumulator scratch per (bm, bn) tile.  MXU-aligned defaults bm=bn=bk=128
(int8 MXU likes 128x128; K-blocks stream through VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mac_kernel(a_ref, b_ref, o_ref, acc_ref):
    """One (bm, bn) output tile; accumulates over the K grid dimension."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def _mac_dequant_kernel(a_ref, b_ref, sa_ref, sw_ref, o_ref, acc_ref):
    """As _mac_kernel but flushes float32 acc * scale_a * scale_w[n]."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sa_ref[0, 0]
                      * sw_ref[...].astype(jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def imc_mac_raw(qa, qw, *, bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False):
    """int8[M,K] x int8[K,N] -> int32[M,N].  Shapes must be block-divisible
    (the ops.py wrapper pads)."""
    m, k = qa.shape
    k2, n = qw.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _mac_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qa.astype(jnp.int8), qw.astype(jnp.int8))


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def imc_mac_dequant_raw(qa, qw, scale_a, scale_w, *, bm: int = 128,
                        bn: int = 128, bk: int = 128,
                        interpret: bool = False):
    """Fused dequant: float32[M,N] = (qa @ qw) * scale_a * scale_w[None, :].

    scale_a: float32 scalar (per-tensor activation scale), passed via a (1,1)
    SMEM-style block; scale_w: float32[N] per-output-channel scales.
    """
    m, k = qa.shape
    k2, n = qw.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _mac_dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qa.astype(jnp.int8), qw.astype(jnp.int8),
      jnp.asarray(scale_a, jnp.float32).reshape(1, 1),
      jnp.asarray(scale_w, jnp.float32).reshape(1, n))
