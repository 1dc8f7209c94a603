"""Public paged-decode attention op: impl dispatch + GQA grouping.

``paged_attention`` is what the model layer calls.  ``impl="jnp"`` runs the
dense gather oracle (:mod:`.ref` — bit-identical to the pre-kernel serving
path); ``impl="pallas"`` runs the fused flash-decode kernel
(:mod:`.paged_attn`), which reads the pools directly through the block table.
Both take the serving layout — q ``(B, 1, H, hd)``, pools
``(NB, bs, KV, hd)`` — and return ``(B, 1, H, hd)``; the kernel path regroups
heads to the `_sdpa` convention ``(B, KV, rep, hd)`` (head ``h`` =
``kvh * rep + r``) so GQA never materializes a K/V repeat.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.compat import resolve_interpret
from repro.kernels.paged_attn.paged_attn import paged_flash_decode_raw
from repro.kernels.paged_attn.ref import paged_decode_ref

ATTN_IMPLS = ("jnp", "pallas")


def paged_attention(q, k_pool, v_pool, block_table, pos, *, k_scale=None,
                    v_scale=None, window: int = 0, impl: str = "jnp",
                    blocks_per_step: int | None = None,
                    interpret: bool | None = None):
    """Paged decode attention against shared pools (post-scatter).

    q: (B, 1, H, hd); k_pool/v_pool: (NB, bs, KV, hd) bf16/f32 or int8 with
    (NB, bs, KV) scale pools; block_table: (B, MB) int32 dense prefixes with
    ``-1`` sentinels; pos: (B,) int32 current positions.  ``interpret=None``
    defers to :func:`repro.kernels.compat.resolve_interpret` (Pallas
    interpreter off-TPU).  ``blocks_per_step=None`` takes the autotuner's
    cached winner for this shape bucket (pool panels DMA'd per grid step;
    bit-identical across values).  Returns (B, 1, H, hd) in q.dtype.
    """
    if impl not in ATTN_IMPLS:
        raise ValueError(f"impl must be one of {ATTN_IMPLS}, got {impl!r}")
    if impl == "jnp":
        return paged_decode_ref(q, k_pool, v_pool, block_table, pos,
                                k_scale=k_scale, v_scale=v_scale,
                                window=window)
    b, sq, h, hd = q.shape
    assert sq == 1, "paged flash decode is single-token"
    kv = k_pool.shape[2]
    interpret = resolve_interpret(interpret)
    if blocks_per_step is None:
        blocks_per_step = autotune.lookup(
            "paged_attn",
            {"b": b, "kv": kv, "rep": h // kv, "hd": hd,
             "bs": k_pool.shape[1], "mb": block_table.shape[1]},
            dtype="int8" if k_scale is not None else str(k_pool.dtype),
            interpret=interpret)["bps"]
    qg = q.reshape(b, kv, h // kv, hd)  # grouped heads, sq axis folded away
    out = paged_flash_decode_raw(
        qg, k_pool, v_pool, k_scale, v_scale,
        block_table.astype(jnp.int32), jnp.asarray(pos, jnp.int32),
        scale=hd ** -0.5, window=window, blocks_per_step=blocks_per_step,
        interpret=interpret)
    return out.reshape(b, 1, h, hd)
