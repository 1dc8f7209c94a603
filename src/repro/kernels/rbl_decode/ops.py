"""jit'd public wrapper for the rbl_decode kernel (padding, thresholds)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import constants as C
from repro.core.decoder import thresholds as core_thresholds
from repro.kernels.rbl_decode.rbl_decode import rbl_decode_mac_raw
from repro.kernels.compat import resolve_interpret


@functools.partial(jax.jit, static_argnames=("rows", "bm", "bn", "bk",
                                             "interpret"))
def rbl_decode_mac(a_bits, w_bits, thr=None, *, rows: int = C.ROWS,
                   bm: int = 128, bn: int = 128, bk: int = 256,
                   interpret: bool | None = None):
    """Grouped analog-decode binary MAC for arbitrary shapes.

    Leading batch dims of ``a_bits`` flatten into M.  ``thr`` defaults to the
    physics-model comparator references for ``rows`` (re-tunable, §IV-C).
    """
    interpret = resolve_interpret(interpret)
    if thr is None:
        thr = core_thresholds(rows, mode="physics")
    batch = a_bits.shape[:-1]
    m = 1
    for b in batch:
        m *= b
    k = a_bits.shape[-1]
    n = w_bits.shape[-1]
    a2 = a_bits.reshape(m, k)
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a2 = jnp.pad(a2, ((0, pm), (0, pk)))
    if pk or pn:
        w_bits = jnp.pad(w_bits, ((0, pk), (0, pn)))
    out = rbl_decode_mac_raw(a2, w_bits, thr, rows=rows, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)
    return out[:m, :n].reshape(*batch, n)
