"""jit'd public wrappers for the imc_mac kernel (padding + backend dispatch).

``interpret`` defaults to True off-TPU so the kernel body executes (and is
tested) on CPU; on TPU it compiles to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.imc_mac.imc_mac import imc_mac_dequant_raw, imc_mac_raw
from repro.kernels.compat import resolve_interpret


def _pad2(x, mult0, mult1):
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def imc_mac(qa, qw, *, bm: int = 128, bn: int = 128, bk: int = 128,
            interpret: bool | None = None):
    """int8 GEMM with int32 accumulation; arbitrary (even ragged) shapes.

    Leading batch dims of ``qa`` are flattened into M.
    """
    interpret = resolve_interpret(interpret)
    batch = qa.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    k = qa.shape[-1]
    n = qw.shape[-1]
    qa2 = _pad2(qa.reshape(m, k), bm, bk)
    qw2 = _pad2(qw, bk, bn)
    out = imc_mac_raw(qa2, qw2, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n].reshape(*batch, n)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def imc_mac_dequant(qa, qw, scale_a, scale_w, *, bm: int = 128, bn: int = 128,
                    bk: int = 128, interpret: bool | None = None):
    """Fused int8 GEMM + per-channel dequant -> float32."""
    interpret = resolve_interpret(interpret)
    batch = qa.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    k = qa.shape[-1]
    n = qw.shape[-1]
    qa2 = _pad2(qa.reshape(m, k), bm, bk)
    qw2 = _pad2(qw, bk, bn)
    sw = jnp.pad(jnp.asarray(scale_w, jnp.float32).reshape(-1),
                 (0, qw2.shape[1] - n))
    out = imc_mac_dequant_raw(qa2, qw2, scale_a, sw, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)
    return out[:m, :n].reshape(*batch, n)
