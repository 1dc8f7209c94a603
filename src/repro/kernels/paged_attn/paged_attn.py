"""Pallas TPU kernel: paged flash-decode attention over per-slot block tables.

The serving hot loop's §Perf finding this addresses: the jnp paged-decode path
gathers every slot's **full logical span** (``MB * block_size`` rows) out of
the shared pools into a dense ``(B, T_ctx, KV, hd)`` HBM tensor — upcast to
f32 again for int8 pools — before a dense SDPA, so per-token HBM traffic
scales with the *allocated* span regardless of how many blocks are live.
Here attention reads the pools **directly** through the block table: the
gathered K/V never exists in HBM, int8 blocks dequantize in-register, and
sentinel (unallocated) table entries are skipped outright.

Grid: ``(B, ceil(MB / bps))`` — slot x table-block-group, the block axis
innermost ("arbitrary", carries the online-softmax state).  The block table
and per-slot positions ride in via **scalar prefetch**
(:class:`pltpu.PrefetchScalarGridSpec`), so each step's BlockSpec index maps
resolve ``table[b, j*bps+t]`` *before* the body runs and DMA ``bps``
``(block_size, KV, hd)`` K and V panels from the pool into VMEM —
``bps = blocks_per_step`` (autotuned, default 1) panel fetches in flight per
step, statically unrolled in the body.  A panel holds every kv-head: Mosaic
tiles a block's last two dims, which must be whole array dims (or multiples
of (8, 128)), so the body walks the kv-heads of a panel in a static loop.

Per ``(b, kv-head)`` the scratch carries flash-decode state across ``j``
blocks (the m/l/acc pattern of ``kernels/flash_attn``):

    s      = q_g k_j^T * scale        (rep x bs, MXU)
    m'     = max(m, rowmax(s))        (masked: ctx <= pos, sliding window)
    alpha  = exp(m - m')
    p      = where(valid, exp(s - m'), 0)
    l      = alpha*l + rowsum(p)
    acc    = alpha*acc + p v_j
    out    = acc / l                  (flushed at the last block)

GQA runs **grouped**: q arrives as ``(B, KV, rep, hd)`` (head ``h`` =
``kvh * rep + r``, the `_sdpa` layout), so K/V are never repeated — each
kv-head's ``rep`` query rows share one pool panel.  Blocks whose table entry
is ``-1`` (never allocated) or entirely outside the ``ctx <= pos`` /
sliding-window span are skipped with :func:`pl.when`; their DMA index clamps
to block 0 and the loaded panel is ignored.

Fully-masked slots (inactive: empty table, ``pos == 0``) flush ``acc/l = 0``
— their logits are never consumed by the server.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _make_kernel(bs: int, kv: int, rep: int, scale: float, window: int,
                 int8: bool, bps: int, mb: int):
    def kernel(tbl_ref, pos_ref, q_ref, *rest):
        k_refs = rest[0:bps]
        v_refs = rest[bps:2 * bps]
        idx = 2 * bps
        if int8:
            ks_refs = rest[idx:idx + bps]
            vs_refs = rest[idx + bps:idx + 2 * bps]
            idx += 2 * bps
        o_ref, m_ref, l_ref, acc_ref = rest[idx:idx + 4]
        b = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        pos = pos_ref[b]
        # Static unroll over the bps table blocks this grid step owns: their
        # panel DMAs were all issued by the pipeline (that is the point —
        # multiple pool fetches in flight per step), the online-softmax
        # update runs sequentially over the live ones.
        for t in range(bps):
            jj = j * bps + t
            entry = tbl_ref[b, jnp.minimum(jj, mb - 1)]
            base = jj * bs
            # A block contributes iff it exists (tail guard for mb % bps),
            # is allocated (no -1 sentinel), and its span [base, base+bs)
            # intersects the valid context (<= pos, and inside the sliding
            # window when one is set).
            live = (jj < mb) & (entry >= 0) & (base <= pos)
            if window:
                live &= base + bs > pos - window

            @pl.when(live)
            def _block(t=t, base=base):
                ctx = base + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
                valid = ctx <= pos
                if window:
                    valid &= ctx > pos - window
                if int8:
                    ks = ks_refs[t][0].astype(jnp.float32)  # (bs, KV)
                    vs = vs_refs[t][0].astype(jnp.float32)
                for h in range(kv):  # the panel holds every kv-head
                    q = q_ref[0, h].astype(jnp.float32)        # (rep, hd)
                    k = k_refs[t][0, :, h].astype(jnp.float32)  # (bs, hd)
                    v = v_refs[t][0, :, h].astype(jnp.float32)
                    if int8:  # in-register dequant against the scale pools
                        k = k * ks[:, h:h + 1]
                        v = v * vs[:, h:h + 1]
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    s = jnp.where(valid, s, NEG_INF)
                    m_prev = m_ref[h]  # (rep, 1)
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                    l_ref[h] = alpha * l_ref[h] + jnp.sum(
                        p, axis=1, keepdims=True)
                    acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    m_ref[h] = m_new

        @pl.when(j == pl.num_programs(1) - 1)
        def _flush():
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("scale", "window",
                                             "blocks_per_step", "interpret"))
def paged_flash_decode_raw(q, k_pool, v_pool, k_scale, v_scale, block_table,
                           pos, *, scale: float, window: int = 0,
                           blocks_per_step: int = 1,
                           interpret: bool = False):
    """One-token flash decode against shared paged pools.

    q: (B, KV, rep, hd); k_pool/v_pool: (NB, bs, KV, hd) bf16/f32 or int8
    (with k_scale/v_scale (NB, bs, KV) pools, else pass ``None``);
    block_table: (B, MB) int32, ``-1`` = unallocated; pos: (B,) int32 —
    position of the token being decoded (its K/V already written to the
    pool).  Returns (B, KV, rep, hd) in q.dtype.

    ``blocks_per_step`` (autotuned; see :mod:`repro.kernels.autotune`) packs
    that many consecutive table blocks into one grid step: each gets its own
    input panel with its own index map, so the Pallas pipeline keeps
    ``blocks_per_step`` pool-panel DMAs in flight (double-buffered at 2) per
    step instead of strictly one.  Results are bit-identical across
    ``blocks_per_step`` values — the online-softmax update order over blocks
    is unchanged.
    """
    b, kv, rep, hd = q.shape
    bs = k_pool.shape[1]
    mb = block_table.shape[1]
    int8 = k_scale is not None
    bps = max(1, min(blocks_per_step, mb))
    grid = (b, pl.cdiv(mb, bps))

    def blk(tbl_ref, bi, ji):
        # Unallocated entries clamp to block 0: the DMA still lands (the
        # pipeline always fetches) but pl.when skips the compute.  The ji
        # clamp guards the tail step when mb % bps != 0.
        return jnp.maximum(tbl_ref[bi, jnp.minimum(ji, mb - 1)], 0)

    # Each panel spans the whole (KV, hd) tail of a pool block (see above).
    def kv_map(t):
        return lambda b_, j, tbl, p: (blk(tbl, b_, j * bps + t), 0, 0, 0)

    def sc_map(t):
        return lambda b_, j, tbl, p: (blk(tbl, b_, j * bps + t), 0, 0)

    q_spec = pl.BlockSpec((1, kv, rep, hd), lambda b_, j, t, p: (b_, 0, 0, 0))
    kv_specs = [pl.BlockSpec((1, bs, kv, hd), kv_map(t)) for t in range(bps)]
    in_specs = [q_spec] + kv_specs + kv_specs
    inputs = [q] + [k_pool] * bps + [v_pool] * bps
    if int8:
        sc_specs = [pl.BlockSpec((1, bs, kv), sc_map(t)) for t in range(bps)]
        in_specs += sc_specs + sc_specs
        # Mosaic loads no f16 vectors; the scale pools are 1/hd of the pools.
        ks, vs = k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)
        inputs += [ks] * bps + [vs] * bps
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kv, rep, 1), jnp.float32),
            pltpu.VMEM((kv, rep, 1), jnp.float32),
            pltpu.VMEM((kv, rep, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _make_kernel(bs, kv, rep, scale, window, int8, bps, mb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, pos, *inputs)
