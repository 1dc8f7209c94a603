"""Unified decoder stack: pattern-grouped scan over heterogeneous blocks.

Layers follow ``cfg.pattern`` repeated ``n_groups`` times (+ optional ``tail``)
— e.g. gemma3 = ("local",)*5 + ("global",) x 8 groups; recurrentgemma =
("rglru","rglru","local") x 12 + ("rglru","rglru") tail.  Parameters of each
pattern position are stacked across groups and executed with ``jax.lax.scan``
(fast compiles at 80 layers, natural remat boundary, FSDP-friendly: XLA
all-gathers one group's weights per iteration).

Block kinds:
  attn   — global attention + MLP
  local  — sliding-window attention + MLP
  moe    — global attention + MoE FFN
  rglru  — RG-LRU recurrent block + MLP
  ssd    — Mamba2 SSD block (no MLP)
"""
from __future__ import annotations

import contextlib
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import attn_decode, attn_forward, attn_prefill
from repro.models.common import (fabric_noise_key, fold_fabric_key,
                                 init_rmsnorm, rmsnorm, shard_hint)
from repro.models.mlp import apply_mlp, init_mlp
from repro.models.moe import apply_moe, init_moe
from repro.models.rglru import init_rglru, rglru_decode, rglru_forward
from repro.models.ssd import init_ssd, ssd_decode, ssd_forward


class StackCache(NamedTuple):
    groups: Any  # tuple (per pattern position) of stacked (G, ...) caches
    tail: Any  # tuple (per tail position) of caches
    pos: jnp.ndarray  # scalar int32: next position to decode


# ------------------------------------------------------------------ init
def init_block(key, cfg: ModelConfig, kind: str):
    from repro.models.attention import init_attention

    keys = jax.random.split(key, 4)
    d = cfg.d_model
    p = {"norm1": init_rmsnorm(d)}
    if kind in ("attn", "local", "moe"):
        p["attn"] = init_attention(keys[0], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.hd, qkv_bias=cfg.qkv_bias)
    elif kind == "rglru":
        p["rglru"] = init_rglru(keys[0], d, cfg.lru_w, cfg.conv_width)
    elif kind == "ssd":
        p["ssd"] = init_ssd(keys[0], d, expand=cfg.ssm_expand,
                            headdim=cfg.ssm_headdim, state=cfg.ssm_state,
                            conv_width=cfg.conv_width)
        if cfg.post_norm:
            p["post_norm1"] = init_rmsnorm(d)
        return p
    else:
        raise ValueError(kind)
    if cfg.post_norm:
        p["post_norm1"] = init_rmsnorm(d)
        p["post_norm2"] = init_rmsnorm(d)
    p["norm2"] = init_rmsnorm(d)
    if kind == "moe":
        p["moe"] = init_moe(keys[1], d, cfg.d_ff, cfg.n_experts,
                            cfg.mlp if cfg.mlp != "none" else "swiglu")
    elif cfg.mlp != "none":
        p["mlp"] = init_mlp(keys[1], d, cfg.d_ff, cfg.mlp)
    return p


def init_stack(key, cfg: ModelConfig):
    """Stacked params: {"groups": tuple per position (leading dim G),
    "tail": tuple per tail position}."""
    g = cfg.n_groups_layers
    kg, kt = jax.random.split(key)
    groups = []
    for p_idx, kind in enumerate(cfg.pattern):
        pk = jax.random.fold_in(kg, p_idx)
        keys = jax.random.split(pk, g)
        groups.append(jax.vmap(lambda k, kd=kind: init_block(k, cfg, kd))(keys))
    tail = []
    for p_idx, kind in enumerate(cfg.tail):
        tail.append(init_block(jax.random.fold_in(kt, p_idx), cfg, kind))
    return {"groups": tuple(groups), "tail": tuple(tail)}


# ------------------------------------------------------------------ blocks
def _imc_kw(cfg: ModelConfig):
    """Fabric routing for every projection in the stack: ONE typed spec."""
    spec = cfg.imc_fabric
    if spec is None:
        return {}
    return {"spec": spec}


def _mix(cfg, params, x, kind, mode, cache, pos, prefill_extra=0,
         true_len=None, block_table=None):
    """The token-mixing half of a block. Returns (y, new_cache)."""
    imc = _imc_kw(cfg)
    window = cfg.window if kind == "local" else 0
    if kind in ("attn", "local", "moe"):
        kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                  head_dim=cfg.hd, rope_theta=cfg.rope_theta, window=window,
                  **imc)
        if mode == "train":
            return attn_forward(params["attn"], x, q_chunk=cfg.q_chunk,
                                chunk_remat=cfg.chunk_remat,
                                native_dtype_dots=cfg.native_dtype_dots,
                                use_flash=cfg.use_flash_kernel,
                                **kw), None
        if mode == "prefill":
            if true_len is not None:
                # Ragged (right-padded) admission prefill: keep EVERY row,
                # even for windowed layers — a window-sized ring over the
                # padded sequence could evict valid positions.  The cache is
                # ephemeral here (scattered into the paged pools), so the
                # full-length allocation lives only for one admit.
                cache_len = x.shape[1]
            else:
                cache_len = window if window else x.shape[1] + prefill_extra
            return attn_prefill(params["attn"], x, q_chunk=cfg.q_chunk,
                                cache_len=cache_len, kv_dtype=cfg.kv_dtype,
                                true_len=true_len,
                                use_flash=cfg.use_flash_kernel, **kw)
        return attn_decode(params["attn"], x, cache, pos,
                           block_table=block_table, **kw)
    if kind == "rglru":
        if mode in ("train", "prefill"):
            y, (h, cs) = rglru_forward(params["rglru"], x, **imc)
            return y, ((h, cs) if mode == "prefill" else None)
        h, cs = cache
        y, (h, cs) = rglru_decode(params["rglru"], x, h, cs, **imc)
        return y, (h, cs)
    if kind == "ssd":
        kw = dict(expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
                  state=cfg.ssm_state, **imc)
        if mode in ("train", "prefill"):
            y, c = ssd_forward(params["ssd"], x, chunk=cfg.ssd_chunk, **kw)
            return y, (c if mode == "prefill" else None)
        return ssd_decode(params["ssd"], x, cache, **kw)
    raise ValueError(kind)


def apply_block(params, x, kind: str, cfg: ModelConfig, mode: str,
                cache=None, pos=None, prefill_extra=0, true_len=None,
                block_table=None):
    """Pre-norm residual block. Returns (x, new_cache, aux)."""
    aux = {}
    h = rmsnorm(params["norm1"], x)
    y, new_cache = _mix(cfg, params, h, kind, mode, cache, pos,
                        prefill_extra=prefill_extra, true_len=true_len,
                        block_table=block_table)
    if cfg.post_norm:
        y = rmsnorm(params["post_norm1"], y)
    x = x + y
    x = shard_hint(x, "residual")
    if kind == "ssd":
        return x, new_cache, aux
    h = rmsnorm(params["norm2"], x)
    if kind == "moe":
        y, aux = apply_moe(params["moe"], h, n_experts=cfg.n_experts,
                           top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor,
                           kind=cfg.mlp if cfg.mlp != "none" else "swiglu",
                           combine_dtype=(jnp.float32
                                          if cfg.moe_combine_dtype == "f32"
                                          else jnp.bfloat16),
                           **_imc_kw(cfg))
    else:
        y = apply_mlp(params["mlp"], h, cfg.mlp, **_imc_kw(cfg))
    if cfg.post_norm:
        y = rmsnorm(params["post_norm2"], y)
    x = x + y
    x = shard_hint(x, "residual")
    return x, new_cache, aux


# ------------------------------------------------------------------ stack
def _zero_aux():
    return {"load_balance_loss": jnp.float32(0.0),
            "router_z_loss": jnp.float32(0.0)}


def _acc_aux(acc, aux):
    if not aux:
        return acc
    return {k: acc[k] + aux[k] for k in acc}


def stack_forward(params, x, cfg: ModelConfig, mode: str,
                  cache: Optional[StackCache] = None, pos=None,
                  prefill_extra: int = 0, true_len=None, block_table=None):
    """Run the full stack. Returns (x, new_cache | None, aux).

    ``true_len`` (prefill, traced scalar): the prompt occupies positions
    ``[0, true_len)`` of a right-padded ``x`` — caches mark the padded tail
    empty and ``pos`` lands on ``true_len``.  ``block_table`` (decode,
    (B, max_blocks) int32): routes attention through paged KV pools when the
    cache holds :class:`~repro.models.attention.PagedAttnCache` leaves.
    """
    assert mode in ("train", "prefill", "decode")
    build_cache = mode in ("prefill", "decode")

    # Noisy fabric: one ambient fold per forward, split per layer group and
    # carried through the scan xs — groups share ONE traced body, so without
    # this every group would replay the same trace-time noise stream.
    spec = cfg.imc_fabric
    gkeys = None
    if spec is not None and spec.noisy:
        base = fold_fabric_key()
        if base is not None:
            gkeys = jax.random.split(base, cfg.n_groups_layers)

    def group_body(carry, xs):
        x, aux_acc = carry
        gparams = xs[0]
        gcaches = xs[1] if mode == "decode" else (None,) * len(cfg.pattern)
        ctx = (fabric_noise_key(xs[-1]) if gkeys is not None
               else contextlib.nullcontext())
        new_caches = []
        with ctx:
            for p_idx, kind in enumerate(cfg.pattern):
                x, nc, aux = apply_block(gparams[p_idx], x, kind, cfg, mode,
                                         cache=gcaches[p_idx], pos=pos,
                                         prefill_extra=prefill_extra,
                                         true_len=true_len,
                                         block_table=block_table)
                new_caches.append(nc)
        ys = tuple(new_caches) if build_cache else None
        return (x, _acc_aux(aux_acc, aux)), ys

    body = jax.checkpoint(group_body) if (cfg.remat and mode == "train") \
        else group_body
    xs = (params["groups"],)
    if mode == "decode":
        xs = (params["groups"], cache.groups)
    if gkeys is not None:
        xs = xs + (gkeys,)
    (x, aux_acc), group_caches = jax.lax.scan(body, (x, _zero_aux()), xs)

    tail_caches = []
    for p_idx, kind in enumerate(cfg.tail):
        tc = cache.tail[p_idx] if mode == "decode" else None
        x, nc, aux = apply_block(params["tail"][p_idx], x, kind, cfg, mode,
                                 cache=tc, pos=pos,
                                 prefill_extra=prefill_extra,
                                 true_len=true_len, block_table=block_table)
        aux_acc = _acc_aux(aux_acc, aux)
        tail_caches.append(nc)

    new_cache = None
    if build_cache:
        new_pos = (pos + 1) if mode == "decode" else None
        if mode == "prefill":
            new_pos = jnp.asarray(
                x.shape[1] if true_len is None else true_len, jnp.int32)
        new_cache = StackCache(group_caches, tuple(tail_caches), new_pos)
    return x, new_cache, aux_acc
