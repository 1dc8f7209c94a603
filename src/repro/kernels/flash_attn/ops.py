"""Public wrapper: GQA-aware causal flash attention over (B, S, H, hd)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import resolve_interpret
from repro.kernels.flash_attn.flash_attn import flash_attention_raw


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, window: int = 0, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """Causal self-attention. q: (B, S, H, hd); k/v: (B, S, KV, hd).

    GQA: KV heads are expanded to H (wrapper-level repeat; the kernel sees
    flat (B*H, S, hd) panels).  S is padded to the block size; padded keys
    are masked inside the kernel via the valid-length closure.
    """
    interpret = resolve_interpret(interpret)
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    bq_eff = min(bq, max(s, 8))
    bk_eff = min(bk, max(s, 8))
    pad = (-s) % max(bq_eff, bk_eff)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
    out = flash_attention_raw(qf, kf, vf, scale=hd ** -0.5, s_valid=s,
                              window=window, bq=bq_eff, bk=bk_eff,
                              interpret=interpret)
    out = out[:, :s].reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out
