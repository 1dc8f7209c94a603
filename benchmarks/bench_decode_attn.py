"""Decode-attention microbench: paged gather (jnp) vs fused kernel (pallas).

Context-length sweep over the op the serving hot loop spends its decode time
in — :func:`repro.kernels.paged_attn.ops.paged_attention` against shared
paged pools through ragged block tables.  One row per (attn_impl, T_ctx);
each row's ``derived`` column carries decode tokens/s for the batch plus the
impl tag, so the perf trajectory never conflates the two engines.  On CPU the
pallas rows run through the Pallas interpreter (flagged ``interpret=True`` in
the row, exempt from the jnp-vs-kernel throughput comparison — Mosaic only
compiles on TPU).

Geometry mirrors serving: per-slot positions are staggered (3/4, full, 1/4,
1/2 of T_ctx) so tables are ragged with ``-1`` sentinel tails and partially
filled last blocks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.kernels.compat import resolve_interpret
from repro.kernels.paged_attn.ops import paged_attention


def _case(rng, ctx: int, *, b=4, h=8, kv=2, hd=64, bs=16):
    mb = ctx // bs
    pos = np.array([ctx * 3 // 4, ctx - 1, ctx // 4, ctx // 2][:b]) \
        .astype(np.int32)
    nb = int(sum(p // bs + 1 for p in pos)) + 1
    q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((nb, bs, kv, hd)), jnp.bfloat16)
    tbl = np.full((b, mb), -1, np.int32)
    perm = iter(rng.permutation(nb))
    for i, p in enumerate(pos):
        for j in range(p // bs + 1):
            tbl[i, j] = next(perm)
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(pos)


def paged_decode_attention(smoke: bool = False):
    rows = []
    ctxs = (256, 1024) if smoke else (512, 2048, 8192)
    iters = 5 if smoke else 20
    interp = resolve_interpret(None)
    rng = np.random.default_rng(0)
    for ctx in ctxs:
        q, kp, vp, tbl, pos = _case(rng, ctx)
        b = q.shape[0]
        for impl in ("jnp", "pallas"):
            fn = jax.jit(lambda q, kp, vp, tbl, pos, impl=impl:
                         paged_attention(q, kp, vp, tbl, pos, impl=impl))
            n_it = iters if (impl == "jnp" or not interp) else min(iters, 3)
            us, _ = time_fn(fn, q, kp, vp, tbl, pos, iters=n_it)
            tag = (" interpret=True (oracle-mode; not perf)"
                   if impl == "pallas" and interp else "")
            rows.append(row(
                f"paged_decode_attn/{impl}/ctx{ctx}", us,
                f"attn_impl={impl} {b / (us * 1e-6):.0f}tok/s{tag}"))
    return rows


ALL = [paged_decode_attention]
