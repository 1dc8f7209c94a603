"""Pallas TPU kernel: causal flash attention (online-softmax, VMEM-resident).

Addresses the §Perf finding that the jnp chunked-attention path materializes
per-chunk score tensors in HBM (f32, score-shaped — the dominant memory term
of train cells): here scores/probabilities live entirely in VMEM scratch;
HBM sees only Q/K/V reads and the output write.

Grid: (B*H, S/bq, S/bk), KV innermost ("arbitrary").  Per (bh, i) the scratch
carries the online-softmax state (m, l, acc) across j blocks:

    s      = q_i k_j^T * scale        (bq x bk, MXU)
    m'     = max(m, rowmax(s))
    alpha  = exp(m - m')
    p      = exp(s - m')              (masked causally / beyond valid length)
    l      = alpha*l + rowsum(p)
    acc    = alpha*acc + p v_j
    out_i  = acc / l                  (flushed at the last j block)

Causal self-attention (S == T), optional sliding window.  GQA handled by the
ops.py wrapper (head expansion).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _make_kernel(bq: int, bk: int, scale: float, s_valid: int,
                 window: int):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        qp = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kp <= qp) & (kp < s_valid) & (qp < s_valid)
        if window:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # fully-masked rows -> exp(NEG_INF-NEG_INF)=1
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(j == pl.num_programs(2) - 1)
        def _flush():
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "window", "s_valid",
                                             "bq", "bk", "interpret"))
def flash_attention_raw(q, k, v, *, scale: float, s_valid: int,
                        window: int = 0, bq: int = 128, bk: int = 128,
                        interpret: bool = False):
    """q/k/v: (BH, S, hd) with S % bq == 0 == S % bk. Causal self-attention."""
    bh, s, hd = q.shape
    assert s % bq == 0 and s % bk == 0
    grid = (bh, s // bq, s // bk)
    return pl.pallas_call(
        _make_kernel(bq, bk, scale, s_valid, window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
