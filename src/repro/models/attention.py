"""GQA attention: full-causal and sliding-window, train/prefill/decode.

Memory-bounded by construction (framework targets 500k-token caches):
  * train/prefill run a scan over query chunks; global layers score each chunk
    against the full K/V (peak = one chunk of scores), local layers slice only
    a window+chunk K/V span (O(S*W) total work).
  * decode uses a single-token query against the cache; local layers keep a
    ring buffer of ``window`` entries, so a 500k-context local layer costs
    O(window), not O(S).

Cache entry per attention layer: {"k","v": (B, T_alloc, KV, hd) roped keys,
"key_pos": (B, T_alloc) int32 absolute positions (-1 = empty)} — explicit
positions make ring-buffer semantics exact and testable.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense, init_dense, shard_hint

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool = False,
                   dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": init_dense(kk, d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": init_dense(kv, d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": init_dense(ko, n_heads * head_dim, d_model, dtype=dtype),
    }


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim, positions,
                 rope_theta, **imc):
    b, s, _ = x.shape
    q = dense(params["wq"], x, **imc).reshape(b, s, n_heads, head_dim)
    k = dense(params["wk"], x, **imc).reshape(b, s, n_kv_heads, head_dim)
    v = dense(params["wv"], x, **imc).reshape(b, s, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = shard_hint(q, "heads")
    # K/V replicated across TP once per layer -> the q-chunk loop contracts
    # locally instead of resharding score-sized tensors every chunk (§Perf)
    k = shard_hint(k, "kv_rep")
    v = shard_hint(v, "kv_rep")
    return q, k, v


def _sdpa(q, k, v, mask, *, native_dtype_dots: bool = True):
    """q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd); mask: (B,1,1,Sq,Sk) or broadcastable.

    Grouped formulation keeps the KV axis explicit (no materialized repeat).
    ``native_dtype_dots``: contract in the input dtype with f32 ACCUMULATION
    (flash-attention numerics).  The alternative (cast operands to f32 first)
    doubles the bytes of every sharded-operand collective inside the chunk
    loop (§Perf iteration 2); softmax always runs in f32 either way.
    Returns (B, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    qg = q.reshape(b, sq, kv, rep, hd)
    if not native_dtype_dots:
        qg, k, v = (t.astype(jnp.float32) for t in (qg, k, v))
    scores = jnp.einsum("bqkrd,btkd->bkrqt", qg, k,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrqt,btkd->bqkrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


def _chunked_causal(q, k, v, *, window: int = 0, q_chunk: int = 512,
                    chunk_remat: bool = True, native_dtype_dots: bool = True):
    """Causal (optionally windowed) attention via a scan over query chunks.

    ``chunk_remat`` rematerializes each chunk's scores in the backward pass —
    without it the scan backward saves stacked per-chunk score tensors, i.e.
    the full S x T score matrix the chunking exists to avoid (measured ~6 TB
    of HBM traffic on qwen2.5 train_4k; see EXPERIMENTS §Perf iteration 1).
    """
    b, s, h, hd = q.shape
    chunk = q_chunk if s % q_chunk == 0 else s
    nc = s // chunk
    if nc == 1:
        qp = jnp.arange(s)[:, None]
        kp = jnp.arange(s)[None, :]
        mask = kp <= qp
        if window:
            mask &= kp > qp - window
        return _sdpa(q, k, v, mask[None, None, None],
                     native_dtype_dots=native_dtype_dots)

    qs = q.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    if window and window + chunk < s:
        span = window + chunk  # static slice size covering the window

        def body(_, args):
            ci, qc = args
            q_start = ci * chunk
            k_start = jnp.clip(q_start + chunk - span, 0, s - span)
            kc = jax.lax.dynamic_slice_in_dim(k, k_start, span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, k_start, span, axis=1)
            qp = (q_start + jnp.arange(chunk))[:, None]
            kp = (k_start + jnp.arange(span))[None, :]
            mask = (kp <= qp) & (kp > qp - window)
            return None, _sdpa(qc, kc, vc, mask[None, None, None],
                               native_dtype_dots=native_dtype_dots)
    else:
        def body(_, args):
            ci, qc = args
            q_start = ci * chunk
            qp = (q_start + jnp.arange(chunk))[:, None]
            kp = jnp.arange(s)[None, :]
            mask = kp <= qp
            if window:
                mask &= kp > qp - window
            return None, _sdpa(qc, k, v, mask[None, None, None],
                               native_dtype_dots=native_dtype_dots)

    if chunk_remat:
        body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (jnp.arange(nc), qs))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


class AttnCache(NamedTuple):
    k: jnp.ndarray  # (B, T_alloc, KV, hd) roped keys (bf16 or int8)
    v: jnp.ndarray
    key_pos: jnp.ndarray  # (B, T_alloc) int32; -1 = empty slot
    k_scale: jnp.ndarray | None = None  # (B, T_alloc, KV) f16 when int8 cache
    v_scale: jnp.ndarray | None = None


class PagedAttnCache(NamedTuple):
    """One attention layer's paged KV pool, shared by all batch slots.

    ``k``/``v``: (num_blocks, block_size, KV, hd) — bf16, or int8 with
    per-(block, offset, KV) f16 scale pools.  Logical position ``p`` of batch
    row ``b`` lives at physical row ``table[b, p // bs] * bs + p % bs``; the
    per-slot block table (built by
    :class:`~repro.models.kv_cache.BlockAllocator`) rides into
    :func:`attn_decode` as a traced argument, so growing/retiring requests
    never retraces.  There is no ``key_pos`` leaf: validity is derived from
    ``pos`` and the table (block ``j`` of a slot always covers positions
    ``[j*bs, (j+1)*bs)``).
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None


def _kv_quant(x):
    """Per-(B,T,KV) int8 quantization of roped K/V (amax over head_dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def attn_forward(params, x, *, n_heads, n_kv_heads, head_dim, rope_theta,
                 window: int = 0, positions=None, q_chunk: int = 512,
                 chunk_remat: bool = True, native_dtype_dots: bool = True,
                 use_flash: bool = False, **imc):
    """Training / no-cache forward. x: (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim,
                           positions, rope_theta, **imc)
    if use_flash:
        from repro.kernels.flash_attn.ops import flash_attention

        out = flash_attention(q, k, v, window=window)
    else:
        out = _chunked_causal(q, k, v, window=window, q_chunk=q_chunk,
                              chunk_remat=chunk_remat,
                              native_dtype_dots=native_dtype_dots)
    return dense(params["wo"], out.reshape(b, s, -1), **imc)


def attn_prefill(params, x, *, n_heads, n_kv_heads, head_dim, rope_theta,
                 window: int = 0, cache_len: int | None = None,
                 q_chunk: int = 512, kv_dtype: str = "bf16",
                 true_len=None, use_flash: bool = False, **imc):
    """Prefill: forward over the prompt AND build the decode cache.

    cache_len defaults to S for global layers, window for local layers.
    ``kv_dtype="int8"`` stores quantized K/V + per-(B,T,KV) scales (halves
    decode HBM traffic; see EXPERIMENTS §Perf).

    ``true_len`` (traced scalar) marks a right-padded prompt: positions
    ``>= true_len`` get ``key_pos = -1`` so downstream consumers (ring decode
    masking, the paged-cache scatter) treat the padded tail as empty.  The
    forward itself needs no extra masking — causal attention already keeps
    padded keys out of every valid query row — so one bucketed executable
    serves all prompt lengths up to S bit-identically.  That same causal
    argument makes ``use_flash`` (the Pallas flash kernel) safe under
    right-padding: per-bucket ``s_valid`` is the padded length, and padded
    *query* rows produce garbage that the cache scatter (key_pos = -1) and
    the caller's logit slicing at ``true_len - 1`` never consume.
    """
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim,
                           positions, rope_theta, **imc)
    if use_flash:
        from repro.kernels.flash_attn.ops import flash_attention

        out = flash_attention(q, k, v, window=window)
    else:
        out = _chunked_causal(q, k, v, window=window, q_chunk=q_chunk)
    t_alloc = cache_len if cache_len is not None else (window if window else s)
    if t_alloc <= s:  # keep the last t_alloc entries, ring-aligned so that
        # entry for position p sits at slot p % t_alloc (decode invariant)
        shift = s % t_alloc
        ck = jnp.roll(k[:, s - t_alloc:], shift, axis=1)
        cv = jnp.roll(v[:, s - t_alloc:], shift, axis=1)
        cp = jnp.roll(jnp.broadcast_to(
            jnp.arange(s - t_alloc, s)[None], (b, t_alloc)), shift, axis=1)
    else:  # roomier cache than the prompt: left-fill
        pad = t_alloc - s
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cp = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(s)[None], (b, s)),
             jnp.full((b, pad), -1, jnp.int32)], axis=1)
    if true_len is not None:
        cp = jnp.where(cp < jnp.asarray(true_len, jnp.int32), cp, -1)
    if kv_dtype == "int8":
        ck, ks = _kv_quant(ck)
        cv, vs = _kv_quant(cv)
        cache = AttnCache(ck, cv, cp.astype(jnp.int32), ks, vs)
    else:
        cache = AttnCache(ck, cv, cp.astype(jnp.int32))
    y = dense(params["wo"], out.reshape(b, s, -1), **imc)
    return y, cache


def _attn_decode_paged(params, x, cache: PagedAttnCache, pos, block_table, *,
                       n_heads, n_kv_heads, head_dim, rope_theta,
                       window: int = 0, **imc):
    """One-token decode against the shared paged pools.

    x: (B, 1, D); pos: (B,) int32; block_table: (B, MB) int32, -1 = empty.
    Each row writes its new K/V at flat pool row
    ``table[pos // bs] * bs + pos % bs`` (rows of inactive slots map out of
    bounds and are dropped), then attends over the fixed logical span
    ``MB * bs`` through its table via
    :func:`repro.kernels.paged_attn.ops.paged_attention`.  Gather row ``i``
    IS position ``i`` (tables are dense prefixes), so the validity mask is
    just ``i <= pos`` limited to allocated blocks.

    The platform picks the engine
    (:func:`repro.kernels.paged_attn.ops.resolve_impl`).  Off TPU the dense
    gather runs — bit-identical to the ring cache because the extra masked
    rows contribute exact zeros.  On TPU the fused flash-decode kernel
    reads the post-scatter pools block-by-block through the table (the
    gathered span never touches HBM), within one output ulp of the gather
    (online softmax rounds its rescaling differently from one-shot
    softmax).
    """
    b = x.shape[0]
    nb, bs = cache.k.shape[0], cache.k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = (pos if pos.ndim else jnp.full((b,), pos))[:, None]  # (B,1)
    q, k_new, v_new = _project_qkv(params, x, n_heads, n_kv_heads, head_dim,
                                   positions, rope_theta, **imc)
    tbl = jnp.where(block_table < 0, nb, block_table)  # (B, MB) OOB sentinel
    p = positions[:, 0]
    widx = tbl[jnp.arange(b), jnp.clip(p, 0, None) // bs] * bs + p % bs  # (B,)

    def put(pool, new):  # pool (NB, bs, *tail); new (B, *tail)
        flat = pool.reshape((nb * bs,) + pool.shape[2:])
        return flat.at[widx].set(new.astype(pool.dtype),
                                 mode="drop").reshape(pool.shape)

    int8_cache = cache.k_scale is not None
    if int8_cache:
        kq_new, ks_new = _kv_quant(k_new)
        vq_new, vs_new = _kv_quant(v_new)
        new_cache = PagedAttnCache(put(cache.k, kq_new[:, 0]),
                                   put(cache.v, vq_new[:, 0]),
                                   put(cache.k_scale, ks_new[:, 0]),
                                   put(cache.v_scale, vs_new[:, 0]))
    else:
        new_cache = PagedAttnCache(put(cache.k, k_new[:, 0]),
                                   put(cache.v, v_new[:, 0]))
    from repro.kernels.paged_attn.ops import paged_attention

    out = paged_attention(q, new_cache.k, new_cache.v, block_table, p,
                          k_scale=new_cache.k_scale,
                          v_scale=new_cache.v_scale, window=window)
    y = dense(params["wo"], out.reshape(b, 1, -1), **imc)
    return y, new_cache


def attn_decode(params, x, cache, pos, *, n_heads, n_kv_heads,
                head_dim, rope_theta, window: int = 0, block_table=None,
                **imc):
    """One-token decode. x: (B, 1, D); pos: scalar int32 OR (B,) int32 —
    per-row positions support continuous batching, where slots admitted at
    different ticks sit at different sequence positions.

    Ring path (``cache`` an :class:`AttnCache`): writes each row's new K/V
    into slot ``pos % T_alloc`` (ring semantics for local layers; for global
    layers T_alloc == context so the slot is just ``pos``).  Paged path
    (``cache`` a :class:`PagedAttnCache`): routes through the per-slot
    ``block_table`` instead — the ring stays the tested oracle.
    """
    if isinstance(cache, PagedAttnCache):
        assert block_table is not None, "paged decode needs a block table"
        return _attn_decode_paged(params, x, cache, pos, block_table,
                                  n_heads=n_heads, n_kv_heads=n_kv_heads,
                                  head_dim=head_dim, rope_theta=rope_theta,
                                  window=window, **imc)
    b = x.shape[0]
    t_alloc = cache.k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    positions = (pos if pos.ndim else jnp.full((b,), pos))[:, None]  # (B,1)
    q, k_new, v_new = _project_qkv(params, x, n_heads, n_kv_heads, head_dim,
                                   positions, rope_theta, **imc)
    rows = jnp.arange(b)
    slot = jnp.mod(positions[:, 0], t_alloc)  # (B,) per-row ring index
    int8_cache = cache.k_scale is not None
    if int8_cache:
        kq_new, ks_new = _kv_quant(k_new)
        vq_new, vs_new = _kv_quant(v_new)
        kq = cache.k.at[rows, slot].set(kq_new[:, 0])
        vq = cache.v.at[rows, slot].set(vq_new[:, 0])
        ks = cache.k_scale.at[rows, slot].set(ks_new[:, 0])
        vs = cache.v_scale.at[rows, slot].set(vs_new[:, 0])
        k = _kv_dequant(kq, ks, q.dtype)
        v = _kv_dequant(vq, vs, q.dtype)
    else:
        k = cache.k.at[rows, slot].set(k_new[:, 0].astype(cache.k.dtype))
        v = cache.v.at[rows, slot].set(v_new[:, 0].astype(cache.v.dtype))
    key_pos = cache.key_pos.at[rows, slot].set(positions[:, 0])
    valid = (key_pos >= 0) & (key_pos <= positions)  # (B,T)
    if window:
        valid &= key_pos > positions - window
    mask = valid[:, None, None, None, :]  # (B,1,1,1,T)
    out = _sdpa(q, k, v, mask)
    y = dense(params["wo"], out.reshape(b, 1, -1), **imc)
    if int8_cache:
        return y, AttnCache(kq, vq, key_pos, ks, vs)
    return y, AttnCache(k, v, key_pos)
