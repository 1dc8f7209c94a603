"""Typed serving API: ``Server.submit(Request) -> Handle``, ``poll``, ``drain``.

This replaces the ``BatchedServer.run(requests)`` batch call with an admission
queue over a **paged KV cache** (see :mod:`repro.models.kv_cache`):

  * :class:`Request` carries per-request ``prompt``, ``max_new_tokens``,
    ``eos_id``, ``seed`` and ``temperature`` — no server-wide prompt length
    or decode budget.
  * :meth:`Server.submit` does **block budgeting**: a request is admitted
    only when the allocator can hand it ``ceil(len / block_size)`` blocks now
    and *reserve* the worst-case remainder (``len + max_new_tokens`` rows),
    so an admitted request can never run dry mid-decode.  Requests that can
    never fit are rejected at submit; the rest queue until blocks free up.
  * ragged admission: each prompt is right-padded to the smallest configured
    **bucket** and prefilled with a traced ``length`` scalar — one compiled
    prefill executable per bucket, zero recompiles at steady state
    (asserted via ``Engine.stats.traces``).
  * decode runs all active slots in lockstep through ONE compiled step; the
    per-slot block table rides along as a traced argument, so growing,
    finishing, and re-admitting requests is data-only.
  * finished slots release their blocks immediately (``eos_id`` or
    ``max_new_tokens``), fault injection re-queues in-flight requests
    (greedy decode makes recovered streams bit-identical), and every decode
    step's wall time feeds the Engine's straggler monitor.

The paged pools are the only KV geometry.  Decode attention over them runs
on the engine the platform picks (:func:`repro.kernels.paged_attn.ops
.resolve_impl`): the ``paged_attn`` kernel on TPU, the jnp gather, its
reference, elsewhere.

Serving SLOs are first-class telemetry (all host-side; nothing recorded here
ever blocks on the device beyond the block the decode loop already does for
sampling):

  * ``server.ttft_s``   — time-to-first-token histogram (submit -> the
    prefill-produced token).
  * ``server.queue_wait_s`` — submit -> admission, per request: the part of
    TTFT spent queued rather than prefilling.
  * ``server.tpot_s``   — time-per-output-token histogram (decode tokens
    only, per finished request).
  * ``server.admitted`` / ``server.rejected`` counters,
    ``server.queue_depth`` gauge.
  * ``server.block_occupancy`` gauge (+ high-water mark) fed from the
    :class:`~repro.models.kv_cache.BlockAllocator` free list.
  * ``server.decode_tokens`` counter + ``server.decode_step_s`` histogram
    (with :attr:`Server.decode_s`, their ratio is decode tokens/s).

Every host-side phase of a poll runs under a span (:func:`repro.telemetry
.span`), written into the JAX profiler's trace beside the device's ops
while a profiler session is active, so a gap in the device's work names
the host work that caused it::

    server.poll              (tick)         one per poll
    ├─ server.admit          (rid, bucket, len)  one per admission
    │  ├─ server.admit.launch   pad, uploads, prefill + merge dispatch
    │  ├─ server.admit.wait     block on the prefill's logits
    │  └─ server.admit.sample   (vocab,) logits to host, first token
    ├─ server.decode         (tick, rows)   one per decode tick
    │  ├─ server.decode.inputs    table growth, key, uploads if needed
    │  ├─ server.decode.dispatch  the jitted step, then its argmax
    │  ├─ server.decode.wait      block on the step's ids
    │  └─ server.decode.fetch     the rest of their copy to the host
    └─ server.sample         (tick, rows)   per-row tokens, retire, gauges

A decode tick keeps greedy rows on the device: :func:`greedy_ids` takes
the argmax of the step's logits there, the host reads back one int32 per
slot, and those ids are the next tick's token input as they stand.  The
host builds and uploads a token array only on a tick after some row's last
token was chosen on the host (an admission's first token, or a row sampled
with ``temperature > 0``, whose logits alone go to the host), and the block
table only when it changed.  Counters: ``server.decode.token_uploads``,
``server.decode.host_rows``, ``server.decode.table_uploads``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.kernels.paged_attn import ops as paged_attn_ops
from repro.launch.engine import Engine
from repro.models.kv_cache import BlockAllocator, init_paged_cache
from repro.runtime.fault_tolerance import InjectedFailure
from repro.telemetry import clock, span


def greedy_ids(logits):
    """``(slots, vocab)`` logits -> ``(slots, 1)`` int32 argmax ids (first
    index on ties, as ``np.argmax``): the decode step's token input."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


@dataclass(frozen=True)
class Request:
    """One generation request (immutable; results live on the Handle)."""

    prompt: np.ndarray  # (S,) int32 token ids
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    seed: int = 0
    temperature: float = 0.0  # 0 -> greedy argmax


@dataclass
class Handle:
    """Mutable view of one submitted request's progress."""

    rid: int
    request: Request
    status: str = "queued"  # queued | active | done | rejected
    tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    host: int = 0  # which fleet host serves this request (0 single-host)
    reason: str = ""  # set when rejected
    _next_pos: int = 0  # next KV position this slot writes (host-side)
    _rng: Optional[np.random.Generator] = None
    _t_submit: float = 0.0  # telemetry clock at submit (TTFT start)
    _t_first: float = 0.0  # telemetry clock at first token (TPOT start)

    @property
    def done(self) -> bool:
        return self.status == "done"


class Server:
    """Continuous-batching server over a paged KV cache.

    Parameters
    ----------
    slots: max concurrent requests (the lockstep decode batch).
    kv: the KV geometry; ``"paged"`` (block tables, ragged admission) is
        the only one.
    block_size / num_blocks: paged pool geometry.  ``num_blocks`` defaults
        to ``slots * ceil(max_seq_len / block_size)`` (never blocks on
        admission); pass less to exercise queueing.
    buckets: padded prompt lengths to compile prefill for (ascending).
    max_seq_len: hard per-request cap on ``len(prompt) + max_new_tokens``;
        fixes the decode step's logical attention span.
    host: this server's fleet host index.  Decode-step wall times feed the
        Engine's straggler monitor under this index, so a fleet of Servers
        sharing one monitor produces REAL per-host entries instead of
        everything landing on host 0 (the pre-fleet behavior).
    fail_at: decode tick indices at which to inject a crash (chaos drill).
    """

    def __init__(self, cfg, params, *, engine: Optional[Engine] = None,
                 slots: int = 4, kv: str = "paged", block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 buckets: Sequence[int] = (16, 32, 64),
                 max_seq_len: Optional[int] = None,
                 host: int = 0,
                 fail_at: Optional[Sequence[int]] = None):
        if kv != "paged":
            raise ValueError(f"kv must be 'paged' (the ring geometry was "
                             f"removed), got {kv!r}")
        self.cfg, self.params = cfg, params
        self.engine = engine or Engine()
        self.slots = slots
        self.host = host
        self.buckets = tuple(sorted(buckets))
        self.max_seq_len = max_seq_len or (max(self.buckets) + 64)
        self.block_size = block_size
        self.max_blocks = -(-self.max_seq_len // block_size)
        self.num_blocks = num_blocks or slots * self.max_blocks
        self.alloc = BlockAllocator(self.num_blocks, block_size, slots,
                                    max_blocks_per_slot=self.max_blocks)
        self.cache = None
        self.active: List[Optional[Handle]] = [None] * slots
        self.queued: List[Handle] = []
        self.handles: List[Handle] = []
        self.recoveries = 0
        self.decode_ticks = 0
        self.decode_s = 0.0  # accumulated lockstep-decode wall time
        self._fail_at = set(fail_at or ())
        self._tick = 0  # one noise key per jitted invocation
        self._decode = self.engine.decode_step(cfg)
        self._admit_fn = self.engine.admit_step(cfg)
        self._prefills: Dict[int, object] = {}
        # Token inputs, whether the last tick's ids or a host upload, sit
        # replicated on the Engine's mesh: one placement, one trace.
        self._tok_at = NamedSharding(self.engine.mesh, PartitionSpec())
        self._ids = jax.jit(greedy_ids, out_shardings=self._tok_at)
        self._tok = None  # the last tick's ids, on the device
        self._tok_host = True  # some row's last token came from the host
        self._table = None  # the last block table uploaded: (host, device)
        reg = self.engine.registry
        self._m_admitted = reg.counter("server.admitted")
        self._m_rejected = reg.counter("server.rejected")
        self._m_recoveries = reg.counter("server.recoveries")
        self._m_decode_tokens = reg.counter("server.decode_tokens")
        self._m_queue = reg.gauge("server.queue_depth")
        self._m_occupancy = reg.gauge("server.block_occupancy")
        self._m_ttft = reg.histogram("server.ttft_s")
        self._m_tpot = reg.histogram("server.tpot_s")
        self._m_queue_wait = reg.histogram("server.queue_wait_s")
        self._m_step = reg.histogram("server.decode_step_s")
        self._m_tok_uploads = reg.counter("server.decode.token_uploads")
        self._m_host_rows = reg.counter("server.decode.host_rows")
        self._m_table_uploads = reg.counter("server.decode.table_uploads")

    @property
    def attn_impl(self) -> str:
        """The decode-attention engine the platform picked."""
        return paged_attn_ops.resolve_impl()

    def _feed_gauges(self):
        """Occupancy from the allocator's free list + queue depth (host ints)."""
        self._m_queue.set(len(self.queued))
        used = self.num_blocks - self.alloc.num_free
        self._m_occupancy.set(used / self.num_blocks)

    # ----------------------------------------------------------- public API
    def submit(self, request: Request) -> Handle:
        """Queue a request; returns its Handle (possibly already rejected)."""
        h = Handle(len(self.handles), request, host=self.host)
        h._t_submit = clock()
        self.handles.append(h)
        plen = int(len(request.prompt))
        worst = plen + request.max_new_tokens
        if plen > max(self.buckets):
            h.status, h.reason = "rejected", (
                f"prompt length {plen} exceeds the largest prefill "
                f"bucket {max(self.buckets)}")
            self._m_rejected.inc()
            return h
        if worst > self.max_seq_len or \
                self.alloc.blocks_for(worst) > self.num_blocks:
            h.status, h.reason = "rejected", (
                f"worst case {worst} tokens can never fit "
                f"(max_seq_len={self.max_seq_len}, "
                f"pool={self.num_blocks}x{self.block_size})")
            self._m_rejected.inc()
            return h
        self.queued.append(h)
        self._m_admitted.inc()
        self._m_queue.set(len(self.queued))
        return h

    def poll(self) -> List[Handle]:
        """Advance one tick (admit + one lockstep decode); returns handles
        that finished on this tick."""
        with span("server.poll", tick=self.decode_ticks):
            self._pump()
            self._feed_gauges()
            if not any(self.active):
                return []
            try:
                if self.decode_ticks in self._fail_at:
                    self._fail_at.discard(self.decode_ticks)
                    self.decode_ticks += 1
                    raise InjectedFailure(
                        "injected failure at decode tick "
                        f"{self.decode_ticks - 1}")
                return self._step()
            except InjectedFailure:
                self._recover()
                return []

    def drain(self) -> List[Handle]:
        """Serve every queued/active request to completion; returns all
        handles in submit order."""
        while self.queued or any(self.active):
            self.poll()
        return list(self.handles)

    # ------------------------------------------------------------ admission
    def _prefill_step(self, bucket: int):
        if bucket not in self._prefills:
            self._prefills[bucket] = self.engine.prefill_step(
                self.cfg, max_new_tokens=0, bucket=bucket)
        return self._prefills[bucket]

    def _bucket_for(self, plen: int) -> int:
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError(f"no bucket holds a length-{plen} prompt")

    def _next_key(self, slot: int = 0):
        k = self.engine.noise_key(self._tick, slot)
        self._tick += 1
        return k

    def _pump(self):
        """Admit queued requests into free slots while blocks allow."""
        for slot in range(self.slots):
            if not self.queued:
                return
            if self.active[slot] is not None:
                continue
            h = self.queued[0]
            plen = len(h.request.prompt)
            need = self.alloc.blocks_for(plen)
            reserve = self.alloc.blocks_for(
                plen + h.request.max_new_tokens) - need
            if not self.alloc.can_admit(need + reserve):
                return  # FIFO: wait for blocks instead of starving h
            self.alloc.alloc(slot, need, reserve=reserve)
            self.queued.pop(0)
            self._admit(h, slot)

    def _admit(self, h: Handle, slot: int):
        req = h.request
        plen = len(req.prompt)
        bucket = self._bucket_for(plen)
        self._m_queue_wait.observe(clock() - h._t_submit)
        with span("server.admit", rid=h.rid, bucket=bucket, len=plen):
            with span("server.admit.launch"):
                row = self._launch_prefill(req, slot, bucket)[0]
            with span("server.admit.wait"):
                self._await(row)
            with span("server.admit.sample"):
                h._rng = np.random.default_rng(req.seed)
                h.tokens = [self._sample(h, np.asarray(row))]
        h._t_first = clock()
        self._m_ttft.observe(h._t_first - h._t_submit)
        h._next_pos = plen
        h.status, h.slot = "active", slot
        self.active[slot] = h
        self._tok_host = True
        if self._finished(h):
            self._retire(h)

    def _launch_prefill(self, req: Request, slot: int, bucket: int):
        """Upload the padded prompt, dispatch its prefill and the merge of
        its cache into ``slot``; returns the prefill's logits, not waited
        on."""
        prompt = np.asarray(req.prompt, np.int32)
        padded = np.zeros((bucket,), np.int32)
        padded[:len(prompt)] = prompt
        batch = {"tokens": jnp.asarray(padded[None]),
                 "length": jnp.asarray(len(prompt), jnp.int32)}
        table_row = jnp.asarray(self.alloc.table_row(slot))
        logits, cache1 = self._prefill_step(bucket)(
            self.params, batch, self._next_key(slot))
        if self.cache is None:
            self.cache = init_paged_cache(cache1, self.slots,
                                          self.num_blocks, self.block_size)
        self.cache = self._admit_fn(self.cache, cache1, table_row,
                                    jnp.asarray(slot, jnp.int32))
        return logits

    @staticmethod
    def _await(x):
        """Block until ``x`` is computed.  Its copy to the host is requested
        first, so the copy starts as the step ends: waiting here adds no
        round trip to the ``np.asarray`` that follows."""
        x.copy_to_host_async()
        x.block_until_ready()

    # --------------------------------------------------------------- decode
    def _sample(self, h: Handle, logits_row: np.ndarray) -> int:
        if h.request.temperature <= 0:
            return int(np.argmax(logits_row))
        z = logits_row.astype(np.float64) / h.request.temperature
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(h._rng.choice(len(p), p=p))

    def _finished(self, h: Handle) -> bool:
        if len(h.tokens) >= h.request.max_new_tokens:
            return True
        return h.request.eos_id is not None and \
            h.tokens[-1] == h.request.eos_id

    def _retire(self, h: Handle):
        h.status = "done"
        if len(h.tokens) > 1:  # TPOT covers decode tokens only
            self._m_tpot.observe(
                (clock() - h._t_first) / (len(h.tokens) - 1))
        self.alloc.release(h.slot)
        self.active[h.slot] = None

    def _tokens(self):
        """The tick's ``(slots, 1)`` token input: the last tick's ids as
        they stand on the device, unless some row's last token was chosen
        on the host.  A free slot's row carries a stale id; its KV writes go
        where the table sends them, as a live row's would."""
        if not self._tok_host:
            return self._tok
        toks = np.zeros((self.slots, 1), np.int32)
        for i, h in enumerate(self.active):
            if h is not None:
                toks[i, 0] = h.tokens[-1]
        self._tok_host = False
        self._m_tok_uploads.inc()
        return jax.device_put(toks, self._tok_at)

    def _block_table(self):
        """The paged block table on the device, uploaded when it changed
        (a block appended, a request admitted or retired)."""
        table = self.alloc.table()
        if self._table is None or not np.array_equal(table, self._table[0]):
            self._table = (table, jnp.asarray(table))
            self._m_table_uploads.inc()
        return self._table[1]

    def _step(self) -> List[Handle]:
        rows = sum(h is not None for h in self.active)
        tick = self.decode_ticks
        # rows sampled with a temperature read their logits on the host
        hot = [i for i, h in enumerate(self.active)
               if h is not None and h.request.temperature > 0]
        with span("server.decode", tick=tick, rows=rows):
            with span("server.decode.inputs"):
                for i, h in enumerate(self.active):  # grow across a block
                    while h is not None and \
                            self.alloc.blocks_for(h._next_pos + 1) > \
                            len(self.alloc.slot_blocks(i)):
                        self.alloc.append(i)
                t0 = clock()  # the step's time: key and uploads to fetch
                args = (self.params, self.cache, self._tokens(),
                        self._next_key(), self._block_table())
            with span("server.decode.dispatch"):
                logits, self.cache = self._decode(*args)
                self._tok = self._ids(logits)
            with span("server.decode.wait"):
                if hot:
                    logits.copy_to_host_async()
                self._await(self._tok)
            with span("server.decode.fetch"):
                ids = np.asarray(self._tok)
                if hot:
                    logits = np.asarray(logits)
            dt = clock() - t0
        with span("server.sample", tick=tick, rows=rows):
            self.decode_s += dt
            self._m_step.observe(dt)
            self.engine.observe_step_time(dt, host=self.host)
            self.decode_ticks += 1
            finished = []
            for i, h in enumerate(self.active):
                if h is None:
                    continue
                h.tokens.append(self._sample(h, logits[i]) if i in hot
                                else int(ids[i, 0]))
                h._next_pos += 1
                if self._finished(h):
                    self._retire(h)
                    finished.append(h)
            if hot:
                self._tok_host = True
                self._m_host_rows.inc(len(hot))
            self._m_decode_tokens.inc(rows)
            self._feed_gauges()
        return finished

    # -------------------------------------------------------------- faults
    def _recover(self):
        """Re-queue in-flight requests from scratch (streams are replayed
        deterministically: per-request rngs reset with the request seed)."""
        requeued = []
        for i, h in enumerate(self.active):
            if h is not None:
                h.tokens = []
                h.status, h.slot, h._rng = "queued", None, None
                requeued.append(h)
            self.active[i] = None
            self.alloc.release(i)
        self.cache = None
        self.queued = requeued + self.queued
        self.recoveries += 1
        self._m_recoveries.inc()
        self._feed_gauges()
        self.alloc.check()
