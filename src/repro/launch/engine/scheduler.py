"""PagedBackend: continuous batching over the block-paged KV cache.

Successor of the PR-1 ``Scheduler`` with the three ROADMAP serving items
landed:

* **Optimistic admission** — a request is admitted when the pool covers
  its *current* footprint (plus an optional free-block watermark), not
  its worst case. More concurrency on skewed traces; the pool can now
  genuinely run out mid-flight, which is handled by —
* **LIFO preemption** — when a sequence needs a growth block and the
  pool is dry, the most recently admitted active sequence is evicted:
  its blocks are freed, its state collapses to a host-side *recompute
  record* (prompt + emitted tokens + RNG-stream position), and it
  re-prefills over its full history on re-admission (front of queue).
  The oldest admission is never evicted, so it always runs to
  completion and the engine cannot livelock. Sampled outputs survive
  preemption bit-exactly because each request's RNG stream is a pure
  function of (seed, stream position).
* **Bucketed prefill** — prompts are right-padded to the next
  power-of-two bucket and prefilled through one jit per *bucket*
  (O(log max_len) compiles instead of one per distinct length). Causal
  attention keeps padded keys invisible; per-row true lengths thread
  through ``model.prefill`` so ring/recurrent caches capture state at
  the real boundary; pad-tail cache blocks are routed to the reserved
  null block. Models whose state cannot be re-extracted at a traced
  length (mlstm/slstm) fall back to exact-length prefill automatically.
* **Batched prefill admission** — each admission drains the maximal
  FCFS *prefix* of the queue that shares the head's prefill bucket
  (up to ``max_prefill_batch`` and the free-slot/pool budget) and
  prefills it as ONE right-padded batch call, scattering each row's
  true-length cache into its slot. Batch widths are power-of-two
  bucketed too, so the jit cache stays at one trace per
  (prompt-bucket, batch-bucket) pair. Strictly a prefix — never
  skip-ahead — so FCFS fairness survives batching.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import sharding as shlib
from repro.launch.engine import tracing
from repro.launch.engine.api import (EngineConfig, RequestHandle,
                                     RequestOutput, prefill_bucket,
                                     register_sample)
from repro.launch.engine.sampling import SlotSampler, fused_sample
from repro.models import paged_kv
from repro.models.model import Model
from repro.models.transformer import RunCtx


@dataclasses.dataclass
class _Slot:
    req: Optional[RequestHandle] = None
    blocks: list[int] = dataclasses.field(default_factory=list)
    last_token: int = 0
    ticket: int = -1             # admission order; LIFO preemption key
    shared: int = 0              # leading blocks held by shared reference


@dataclasses.dataclass
class _Pending:
    """One dispatched decode whose sampled tokens are still on device
    (overlap mode). ``rows`` records (slot, ticket) pairs so a harvest
    can discard draws whose slot retired or was re-admitted in between
    (tickets are globally monotonic — equality proves same request);
    ``toks`` is the (num_slots,) int32 device array."""
    rows: list
    toks: object


class PagedBackend:
    """Host-side scheduler state + jit'd device steps (paged pools)."""

    # Role specialization (launch/engine/disagg.py): a prefill-only
    # backend runs admission + prefill and returns before the decode
    # phase — its slots never grow, preempt or COW; they are exported
    # as MigrationPackets by the disaggregated front-end instead.
    prefill_only = False

    def __init__(self, model: Model, params, cfg: EngineConfig,
                 ctx: RunCtx):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.layout = paged_kv.PagedLayout(
            num_slots=cfg.num_slots, num_blocks=cfg.num_blocks,
            block_size=cfg.block_size, max_len=cfg.max_len)
        self.caps = model.serving_caps()
        # Quantized paged KV: a jit-static PoolSpec threaded through the
        # RunCtx (write frontiers + fused-dequant kernels) and the pool
        # constructors; None keeps the bf16 path bit-identical.
        self.kv_spec = None
        if getattr(cfg, "kv_dtype", "bf16") != "bf16":
            self.kv_spec = paged_kv.make_pool_spec(
                model.cfg, self.layout, kv_dtype=cfg.kv_dtype)
            ctx = dataclasses.replace(ctx, kv_spec=self.kv_spec)
        self.ctx = ctx
        # COW prefix caching: only when EVERY layer's decode state lives
        # in the shared pool blocks (rings/SSM carries are per-slot and
        # a matched block chain cannot reconstruct them)
        self.prefix = paged_kv.PrefixIndex(cfg.block_size) \
            if cfg.prefix_cache and self.caps.prefix_cache else None
        self.alloc = paged_kv.BlockAllocator(
            self.layout, watermark=cfg.watermark_blocks,
            on_evict=self._on_evict if self.prefix is not None else None)
        # Cross-KV arena (encoder-decoder): one row per resident
        # request, refcount-shared across identical feature arrays,
        # freed with the slot at retirement AND preemption (resume
        # re-encodes — the recompute philosophy of the block pool).
        self.arena = paged_kv.CrossArena(cfg.num_slots) \
            if self.caps.cross_attn else None
        self.arena_ids = np.zeros((cfg.num_slots,), np.int32)
        self.enc_lengths = np.zeros((cfg.num_slots,), np.int32)
        self.arena_hits = 0          # admissions sharing a resident row
        self.pools = model.init_paged_cache(self.layout,
                                            spec=self.kv_spec)
        # Mesh-sharded serving: commit params and pools to their
        # NamedShardings once; shlib.jit_step pins every step's outputs
        # to the same shardings (stable placement, exact pool donation).
        self.shard = ctx.shard
        self._pool_sh = None
        if self.shard is not None:
            self.params = shlib.place_params(params, self.shard)
            self._pool_sh = shlib.named(
                self.shard.mesh,
                model.paged_cache_specs(self.layout, self.shard,
                                        spec=self.kv_spec))
            self.pools = jax.device_put(self.pools, self._pool_sh)
        self.table = np.full(
            (cfg.num_slots, self.layout.max_blocks_per_seq),
            paged_kv.NULL_BLOCK, np.int32)
        self.lengths = np.zeros((cfg.num_slots,), np.int32)
        self.slots = [_Slot() for _ in range(cfg.num_slots)]
        self.sampler = SlotSampler(cfg.num_slots)
        self.waiting: collections.deque[RequestHandle] = collections.deque()
        self.finished: list[RequestHandle] = []
        self.ragged_prefill = (cfg.bucketed_prefill
                               and self.caps.ragged_prefill)
        # Expert-sharded MoE decode runs the shard_map whose batch spec
        # requires B to divide |dp| — true for the decode/verify widths
        # (num_slots, checked by the Engine) but NOT for pow-2 prefill
        # batch buckets (e.g. a single admission), so prefill keeps the
        # unsharded expert path and lets GSPMD partition it.
        self.prefill_ctx = dataclasses.replace(ctx, moe_sharded=False) \
            if ctx.moe_sharded else ctx
        self.made_progress = False
        self._ticket = 0
        # Async host/device overlap (cfg.overlap): the one in-flight
        # decode awaiting its token fetch, plus outputs harvested
        # outside step() (migration flushes) owed to the next step.
        self._pending: Optional[_Pending] = None
        self._flushed: list[RequestOutput] = []
        # telemetry
        self.steps = 0
        self.slot_steps = 0          # active slots summed over steps
        self.block_token_steps = 0   # allocated token capacity x steps
        self.live_token_steps = 0    # live tokens x steps
        self.preemptions = 0
        self.prefill_calls = 0       # batched prefill launches
        self.prefill_reqs = 0        # requests prefilled (>= calls)
        self.prefill_tokens = 0      # real tokens computed at admission
        self.prefill_computed_tokens = 0  # the same, with batch/bucket pad
        self.prefix_lookups = 0      # admissions that consulted the index
        self.prefix_hits = 0         # admissions with a non-empty match
        self.prefix_hit_tokens = 0   # prompt tokens served from cache
        self.cow_copies = 0          # shared blocks copied before a write
        self.prefix_evictions = 0    # indexed blocks reclaimed by alloc

        if self.arena is not None:
            def decode_fn(params, pools, table, lengths, tokens,
                          arena_ids, enc_lengths):
                return model.decode_step_paged(
                    params, pools, table, lengths, tokens, self.ctx,
                    arena_ids=arena_ids, enc_lengths=enc_lengths)
        else:
            def decode_fn(params, pools, table, lengths, tokens):
                return model.decode_step_paged(params, pools, table,
                                               lengths, tokens, self.ctx)

        self._decode = shlib.jit_step(decode_fn, self.shard,
                                      self._pool_sh, donate=(1,))

        # Fused overlap step (cfg.overlap): token-feed select + decode +
        # on-device sampling in ONE jit call, so the overlapped path
        # pays a single dispatch per step and the logits never leave
        # the device — only the (num_slots,) sampled tokens are fetched,
        # one step later. ``use_prev`` rows take their fed token from
        # the previous step's device-resident draws (the double buffer).
        if self.arena is not None:
            def overlap_fn(params, pools, table, lengths, host_tokens,
                           prev_toks, use_prev, steps, samp,
                           arena_ids, enc_lengths):
                tokens = jnp.where(use_prev[:, None], prev_toks[:, None],
                                   host_tokens)
                logits, pools = model.decode_step_paged(
                    params, pools, table, lengths, tokens, self.ctx,
                    arena_ids=arena_ids, enc_lengths=enc_lengths)
                return fused_sample(logits, steps, samp), pools
        else:
            def overlap_fn(params, pools, table, lengths, host_tokens,
                           prev_toks, use_prev, steps, samp):
                tokens = jnp.where(use_prev[:, None], prev_toks[:, None],
                                   host_tokens)
                logits, pools = model.decode_step_paged(
                    params, pools, table, lengths, tokens, self.ctx)
                return fused_sample(logits, steps, samp), pools

        self._overlap_step = shlib.jit_step(overlap_fn, self.shard,
                                            self._pool_sh, donate=(1,))
        self._zero_toks = None       # lazy (num_slots,) int32 zero feed
        self._no_prev = np.zeros((cfg.num_slots,), bool)
        self._prefill_cache = {}
        self._suffix_cache = {}
        self._called: set = set()    # step-program keys called so far

        def cow_fn(pools, src, dst):
            # duplicate physical block src into dst across every pool
            # leaf (leading layer-count axis, then the block axis) —
            # only reachable when supports_prefix_cache gated the tree
            # to pure pool leaves
            return jax.tree.map(
                lambda p: p.at[:, dst].set(p[:, src]), pools)

        if self.shard is None:
            self._cow = jax.jit(cow_fn, donate_argnums=(0,))
        else:
            self._cow = jax.jit(cow_fn, donate_argnums=(0,),
                                out_shardings=self._pool_sh)

    # -- public backend API ---------------------------------------------

    def check_request(self, prompt_len: int, sampling):
        """Reject requests whose WORST-CASE footprint exceeds the pool
        (they could never run to completion even alone)."""
        worst = paged_kv.blocks_for(
            prompt_len + sampling.max_tokens, self.cfg.block_size)
        if worst > self.layout.usable_blocks:
            raise ValueError(
                f"request worst case ({worst} blocks) exceeds pool "
                f"capacity ({self.layout.usable_blocks} usable blocks) — "
                "it could never run to completion even alone")

    def enqueue(self, req: RequestHandle):
        """Append to the FCFS queue. Callers validate first
        (Engine.add_request / the ReplicaSet shared queue both run
        check_request) — no double check here."""
        self.waiting.append(req)

    @property
    def num_active(self) -> int:
        """Occupied decode slots."""
        return sum(s.req is not None for s in self.slots)

    @property
    def has_work(self) -> bool:
        """True while any request is waiting or active (or a migration
        flush harvested outputs the next step still owes the stream)."""
        return bool(self.waiting) or self.num_active > 0 \
            or bool(self._flushed)

    def step(self) -> list[RequestOutput]:
        """Admissions, growth (with preemption), one decode, sampling.

        With ``cfg.overlap`` the call routes through ``_step_overlap``:
        the decode for THIS step is dispatched before the previous
        step's sampled tokens are fetched, so host scheduling work
        hides under device compute. Token values are identical either
        way (the RNG-stream contract)."""
        with tracing.span("scheduler.step") as sp:
            outs = self._step()
            if sp:
                sp.set(rows=self.num_active)
        return outs

    def _step(self) -> list[RequestOutput]:
        outs: list[RequestOutput] = []
        self.made_progress = False
        if self._flushed:              # harvested during a migration
            outs.extend(self._flushed)
            self._flushed = []
            self.made_progress = True
        if self.cfg.overlap and not self.prefill_only:
            return self._step_overlap(outs)
        if self._pending is not None:  # overlap residue (role flip)
            outs.extend(self._harvest(self._pending))
            self._pending = None
        self._admit(outs)
        if self.prefill_only:
            return outs               # role-specialized: no decode here
        active = self._make_room()
        if not active:
            return outs
        tokens = np.zeros((self.cfg.num_slots, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].last_token
        args = (self.params, self.pools, jnp.asarray(self.table),
                jnp.asarray(self.lengths), jnp.asarray(tokens))
        if self.arena is not None:
            args += (jnp.asarray(self.arena_ids),
                     jnp.asarray(self.enc_lengths))
        first = self._first_call(("decode",))
        with tracing.span("scheduler.dispatch") as sp:
            if sp:
                sp.set(rows=len(active), followup=False, first=first)
            logits, self.pools = self._decode(*args)
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="tokens")
            toks = self.sampler.sample(logits)
        self.steps += 1
        self.slot_steps += len(active)
        self.block_token_steps += self.alloc.used_count * self.cfg.block_size
        self.made_progress = True
        for i in active:
            self.lengths[i] += 1          # the fed token got cached
            self.live_token_steps += int(self.lengths[i])
            outs.append(self._accept(i, int(toks[i])))
        return outs

    # -- async host/device overlap (cfg.overlap) -------------------------

    def _step_overlap(self, outs: list[RequestOutput]):
        """One overlapped step: (1) if a decode is in flight, try to
        dispatch THIS step's decode first, feeding the in-flight
        sampled tokens device-to-device (``_try_followup``); (2) block
        on the in-flight fetch and register its tokens; (3) admit — the
        admission prefill consumes the pools produced by whichever
        decode was dispatched last, so its writes are ordered after
        them by the data dependency; (4) when no follow-up could be
        dispatched, fall back to the sequential shape (growth with
        preemption, COW, dispatch) and leave the new decode pending.

        Outputs are bit-identical to the sequential path: every fed
        token and RNG-stream position matches, and the speculative
        writes of a follow-up that covered a row retired at harvest
        land only at positions nothing live ever reads (the row's own
        frontier, or blocks whose later reuse is write-ordered after
        this decode by the functional pool threading)."""
        pend, self._pending = self._pending, None
        followed = False
        if pend is not None:
            followed = self._try_followup(pend)
            outs.extend(self._harvest(pend))
        self._admit(outs)
        if followed:
            return outs
        active = self._make_room()
        if not active:
            return outs
        tokens = np.zeros((self.cfg.num_slots, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slots[i].last_token
        self._dispatch_decode(active, tokens, self._no_prev, None,
                              self.sampler.steps)
        return outs

    def _try_followup(self, pend: _Pending) -> bool:
        """Dispatch the next decode BEFORE harvesting ``pend`` when it
        is safe without host knowledge of the in-flight tokens:

        * rows whose in-flight token deterministically retires them
          (max_tokens reached) are excluded — their slot frees at
          harvest and must not decode again;
        * growth blocks and COW copies for every dispatched row must be
          allocatable WITHOUT preemption (preempting a row whose last
          token is still on device would need that token for the
          recompute record) — any shortfall bails to the sequential
          path, which may preempt after the harvest. Partial
          allocations are safe to keep: the sequential growth/COW
          passes skip rows already extended/privatized.

        An in-flight token that turns out to be a stop token retires
        its row at harvest anyway; the follow-up's draw for that row is
        discarded by the ticket check one step later, and its cache
        write landed one past the row's final frontier — never read.
        Returns True when the follow-up decode was dispatched."""
        bs = self.cfg.block_size
        inflight = set()
        for i, ticket in pend.rows:
            s = self.slots[i]
            if s.req is not None and s.ticket == ticket:
                inflight.add(i)
        dispatch = []
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            if i in inflight and \
                    len(s.req.token_ids) + 1 >= s.req.sampling.max_tokens:
                continue              # harvest retires this row for sure
            dispatch.append(i)
        if not dispatch:
            return False
        for i in dispatch:
            slot = self.slots[i]
            L = int(self.lengths[i])
            if L % bs == 0 and L // bs >= len(slot.blocks):
                if not self.alloc.can_alloc(1):
                    return False      # pool dry: sequential path preempts
                (nb,) = self.alloc.alloc(1)
                slot.blocks.append(nb)
                self.table[i, len(slot.blocks) - 1] = nb
            if self.prefix is not None:
                idx = L // bs
                if idx < slot.shared:
                    assert idx == slot.shared - 1, \
                        "write frontier deeper than the shared tail block"
                    if not self.alloc.can_alloc(1):
                        return False
                    self._cow_block(i, idx)
        host = np.zeros((self.cfg.num_slots, 1), np.int32)
        use_prev = np.zeros((self.cfg.num_slots,), bool)
        for i in dispatch:
            if i in inflight:
                use_prev[i] = True    # token still on device
            else:
                host[i, 0] = self.slots[i].last_token
        steps = self.sampler.steps.copy()
        steps[use_prev] += 1          # one draw ahead of the host mirror
        self._dispatch_decode(dispatch, host, use_prev, pend.toks, steps)
        return True

    def _dispatch_decode(self, active, host_tokens, use_prev, prev_toks,
                         steps):
        """Launch the fused feed-select + decode + on-device sample
        WITHOUT fetching the tokens; the result parks in
        ``self._pending``. Lengths advance at dispatch (the fed token's
        cache write is in flight), so the harvest only registers the
        sampled values."""
        followup = prev_toks is not None
        if prev_toks is None:         # no double buffer yet: dead feed
            if self._zero_toks is None:
                self._zero_toks = jnp.zeros((self.cfg.num_slots,),
                                            jnp.int32)
            prev_toks = self._zero_toks
        steps, samp = self.sampler.fused_args(steps)
        args = (self.params, self.pools, self.table, self.lengths,
                host_tokens, prev_toks, use_prev, steps, samp)
        if self.arena is not None:
            args += (self.arena_ids, self.enc_lengths)
        first = self._first_call(("overlap", samp is None))
        with tracing.span("scheduler.dispatch") as sp:
            if sp:
                sp.set(rows=len(active), followup=followup, first=first)
            toks, self.pools = self._overlap_step(*args)
        self.steps += 1
        self.slot_steps += len(active)
        self.block_token_steps += self.alloc.used_count * self.cfg.block_size
        self.made_progress = True
        rows = []
        for i in active:
            self.lengths[i] += 1
            self.live_token_steps += int(self.lengths[i])
            rows.append((i, self.slots[i].ticket))
        self._pending = _Pending(rows, toks)

    def _harvest(self, pend: _Pending) -> list[RequestOutput]:
        """Block on an in-flight decode's token fetch and register the
        draws. Rows whose slot retired or was re-admitted since the
        dispatch (ticket mismatch) are discarded — their speculative
        cache writes landed at never-read positions."""
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="tokens")
            toks = np.asarray(pend.toks)    # the one blocking fetch
        outs = []
        for i, ticket in pend.rows:
            slot = self.slots[i]
            if slot.req is None or slot.ticket != ticket:
                continue
            outs.append(self._accept(i, int(toks[i])))
        if outs:
            self.made_progress = True
        return outs

    def flush_overlap(self):
        """Harvest any in-flight decode NOW (no new dispatch) and buffer
        its outputs for the next ``step()``. Migration paths call this
        before reading host slot state (``lengths`` already counts the
        in-flight fed token, but ``slot.last_token`` is only current
        after the harvest) — and a flush may retire slots, so callers
        re-check occupancy afterwards."""
        if self._pending is None:
            return
        pend, self._pending = self._pending, None
        self._flushed.extend(self._harvest(pend))

    def live_handles(self) -> list[RequestHandle]:
        """Resident + queued request handles (latency aggregation —
        see ``api.latency_stats``)."""
        return [s.req for s in self.slots if s.req is not None] \
            + list(self.waiting)

    # -- internals ------------------------------------------------------

    def _accept(self, i: int, tok: int) -> RequestOutput:
        """Register one sampled token for slot i; emit/stop/retire."""
        slot = self.slots[i]
        out = register_sample(slot.req, tok, self.cfg.eos_id,
                              lambda: self._retire(i))
        if not out.finished:
            self.sampler.steps[i] = slot.req._n_sampled
            slot.last_token = tok
        return out

    def _first_call(self, key) -> bool:
        """True on the first call of the step program ``key``: the call
        that compiles it (or loads it from the persistent cache)."""
        new = key not in self._called
        self._called.add(key)
        return new

    def _make_room(self) -> list[int]:
        """Growth blocks, then private copies of shared tail blocks, for
        this step's decode writes; either may LIFO-preempt when the pool
        runs dry. Returns the slots still active."""
        with tracing.span("scheduler.grow") as sp:
            p0 = self.preemptions
            self._grow_blocks()
            active = [i for i, s in enumerate(self.slots)
                      if s.req is not None]
            self._ensure_cow(active)
            if sp:                    # _preempt puts victims at the front
                sp.set(preempted=[r.uid for r in itertools.islice(
                    self.waiting, self.preemptions - p0)])
        return [i for i in active if self.slots[i].req is not None]

    def _grow_blocks(self):
        """Allocate growth blocks oldest-admission-first; when the pool
        is dry, preempt LIFO until the allocation fits (a sequence may
        preempt itself if it is the newest — it then waits in queue)."""
        order = sorted(
            (i for i, s in enumerate(self.slots) if s.req is not None),
            key=lambda i: self.slots[i].ticket)
        for i in order:
            slot = self.slots[i]
            if slot.req is None:          # preempted earlier in this pass
                continue
            L = int(self.lengths[i])
            if L % self.cfg.block_size != 0 or \
                    L // self.cfg.block_size < len(slot.blocks):
                continue
            while not self.alloc.can_alloc(1):
                cands = [(j, self.slots[j].ticket)
                         for j, s in enumerate(self.slots)
                         if s.req is not None]
                victim = self.alloc.select_victim(cands)
                self._preempt(victim)
                if victim == i:
                    break
            if slot.req is None:
                continue
            (nb,) = self.alloc.alloc(1)
            slot.blocks.append(nb)
            self.table[i, len(slot.blocks) - 1] = nb

    def _on_evict(self, b: int):
        """Allocator reclaimed an unreferenced cached block: unlink it
        from the prefix index so it can never be matched again."""
        self.prefix.evict_block(b)
        self.prefix_evictions += 1

    def _ensure_cow(self, active):
        """Copy-on-write pass before a decode/verify device call: any
        slot whose next write position lands inside its SHARED prefix
        gets that block copied into a private one first, so the write
        cannot corrupt other slots sharing the block (or the pristine
        indexed copy future admissions will match). Only the LAST
        shared block is ever a write target — writes happen at the
        length frontier, which a full-prefix hit places one token
        inside the shared tail (lengths = S - 1)."""
        if self.prefix is None:
            return
        bs = self.cfg.block_size
        for i in active:
            slot = self.slots[i]
            idx = int(self.lengths[i]) // bs
            if idx >= slot.shared:
                continue
            assert idx == slot.shared - 1, \
                "write frontier deeper than the shared tail block"
            assert self.alloc.must_cow(slot.blocks[idx])
            while not self.alloc.can_alloc(1):   # LIFO, like _grow_blocks
                cands = [(j, self.slots[j].ticket)
                         for j, s in enumerate(self.slots)
                         if s.req is not None]
                victim = self.alloc.select_victim(cands)
                self._preempt(victim)
                if victim == i:
                    break
            if slot.req is None:           # preempted itself: waits in
                continue                   # queue, re-admits later
            self._cow_block(i, idx)

    def _cow_block(self, i: int, idx: int):
        """Copy shared block ``slot.blocks[idx]`` into a freshly owned
        one and swap the table entry; the old block keeps its other
        references (and its place in the prefix index) untouched."""
        slot = self.slots[i]
        old = slot.blocks[idx]
        (new,) = self.alloc.alloc(1)
        self.pools = self._cow(self.pools, old, new)
        slot.blocks[idx] = new
        self.table[i, idx] = new
        self.alloc.free([old])             # drop only THIS slot's ref
        slot.shared = idx                  # blocks before idx still shared
        self.cow_copies += 1

    def _imminent_growth(self) -> int:
        """Growth blocks active sequences will claim THIS step. Counted
        into admission so a new request cannot grab the last free blocks
        only to be LIFO-preempted by an older sequence's growth in the
        same step — a full prefill wasted per step until something
        retires."""
        bs = self.cfg.block_size
        return sum(1 for i, s in enumerate(self.slots)
                   if s.req is not None
                   and int(self.lengths[i]) % bs == 0
                   and int(self.lengths[i]) // bs >= len(s.blocks))

    def _cached_tokens(self, req: RequestHandle) -> list[int]:
        """Tokens a (re-)admitted request must have in cache before its
        next decode: the prompt, plus all-but-the-last emitted token on
        a preemption resume (the last one is fed to decode)."""
        if req._n_sampled > 0:            # preempted: re-prefill history
            return list(req.prompt) + req.token_ids[:-1]
        return list(req.prompt)

    def _bucket_key(self, S: int):
        """The prefill-trace identity of a cached length: the padded
        token width for ragged models, the exact length otherwise.
        Requests batch together iff their keys match."""
        bs = self.cfg.block_size
        if self.ragged_prefill:
            cap = paged_kv.blocks_for(self.cfg.max_len, bs) * bs
            return paged_kv.blocks_for(prefill_bucket(S, bs, cap), bs) * bs
        return ("exact", S)

    def _suffix_bucket(self, n: int) -> int:
        """Power-of-two bucket for a non-shared admission suffix: same
        policy as prompt buckets (floor = block size, capped), so
        suffix-prefill traces stay O(log max_len) like everything else."""
        bs = self.cfg.block_size
        cap = paged_kv.blocks_for(self.cfg.max_len, bs) * bs
        return prefill_bucket(n, bs, cap)

    def _enc_bucket(self, F: int) -> int:
        """Power-of-two bucket for an encoder frame count — its OWN
        axis (floor 8, capped at encoder_len), so prefill traces stay
        O(log max_len x log encoder_len) and the compile-cap gate keeps
        both axes observable."""
        return prefill_bucket(F, 8, self.model.cfg.encoder_len)

    def _admit_key(self, S: int, matched: int, req=None):
        """The admission-trace identity: full-hit installs (no device
        call), suffix prefills batched by suffix bucket, full prefills
        by the standard prompt bucket — times the frame bucket for
        encoder-decoder requests. Requests batch together iff their
        keys match."""
        if matched == S:
            key = ("hit",)
        elif matched > 0:
            key = ("sfx", self._suffix_bucket(S - matched))
        else:
            key = self._bucket_key(S)
        if self.arena is not None:
            key = (key, "enc",
                   self._enc_bucket(req.encoder_features.shape[0]))
        return key

    def _drain_bucket_run(self):
        """Pop the maximal FCFS PREFIX of the queue that (a) fits the
        free slots and the pool (cumulative current footprint + this
        step's imminent growth, watermark headroom while anything else
        runs), (b) shares the queue head's admission key (prefill
        bucket / suffix bucket / full hit), and (c) stays within
        ``max_prefill_batch``. Strictly a prefix: a request that does
        not fit ends the run — no skipping ahead — so batching cannot
        starve the head of the queue.

        Each accepted request's longest block-aligned cached prefix is
        matched here and its blocks are SHARED immediately (refcount
        pinned), so a later entry's fresh allocation cannot reclaim
        them out of the LRU mid-run; a request that then fails the pool
        check is un-pinned before the run closes. Returns
        ``(req, matched_blocks, cached_tokens, S)`` entries."""
        free = sum(1 for s in self.slots if s.req is None)
        if not free:
            return []
        bs = self.cfg.block_size
        cap = free if self.cfg.max_prefill_batch <= 0 else \
            min(free, self.cfg.max_prefill_batch)
        run = []
        need = self._imminent_growth()
        key0 = None
        arena_need = 0
        seen_feats: set[int] = set()
        for req in self.waiting:
            if len(run) >= cap:
                break
            cached = self._cached_tokens(req)
            S = len(cached)
            m = self.prefix.match(cached) if self.prefix is not None \
                else []
            key = self._admit_key(S, len(m) * bs, req)
            if run and key != key0:
                break
            if self.arena is not None:
                # a fresh feature array claims an arena row; identity-
                # shared features (resident or earlier in this run) ride
                # an existing row's refcount
                fk = id(req.encoder_features)
                fresh = (fk not in seen_feats and self.arena.lookup(fk)
                         == paged_kv.NULL_ARENA)
                if fresh and not self.arena.can_admit(arena_need + 1):
                    break
                if fresh:
                    arena_need += 1
                    seen_feats.add(fk)
            for b in m:                   # pin against mid-run reclaim
                self.alloc.share(b)
            # + 1: the admitted slot decodes THIS step, caching the fed
            # token at position ``cached`` — without that block counted
            # a boundary-length request admits then self-preempts,
            # wasting a full prefill every step. Matched blocks are
            # already resident; for a fresh full hit the +1 covers the
            # copy-on-write block the first decode claims instead.
            want = paged_kv.blocks_for(S + 1, bs) - len(m)
            # watermark headroom only matters while others are running;
            # a sole request must always pass (progress guarantee)
            strict = self.num_active > 0 or bool(run)
            if not self.alloc.can_admit(need + want, strict=strict):
                if m:
                    self.alloc.free(m)    # un-pin: hits return to LRU
                break
            need += want
            run.append((req, m, cached, S))
            key0 = key
        for _ in run:
            self.waiting.popleft()
        return run

    def _admit(self, outs: list[RequestOutput]):
        if not self.waiting:
            return
        with tracing.span("scheduler.admit") as sp:
            if sp:
                sp.set(uids=[])
            while self.waiting:
                run = self._drain_bucket_run()
                if not run:
                    return                # FCFS: no skipping ahead
                if sp:
                    sp.attrs["uids"] += [r[0].uid for r in run]
                self._place_batch(run, outs)

    def _place_batch(self, run, outs: list[RequestOutput]):
        """Admit one drained run (entries all share one admission key):
        install matched prefix blocks, allocate the rest, and compute
        ONLY the non-shared tokens — a full-prefix hit costs no device
        call at all, a partial hit prefills just the suffix through the
        verify path, and a miss takes the batched full prefill. Rows
        are FCFS-ordered, so emission order matches one-at-a-time
        admission exactly (a fresh full hit emits its first token from
        this step's decode instead of at admission; the token VALUE is
        bit-identical because it is drawn at the same RNG stream
        position from the same logits row)."""
        bs = self.cfg.block_size
        free_slots = [i for i, s in enumerate(self.slots) if s.req is None]
        rows = []                          # (slot, req, cached, S, ids)
        now = time.monotonic()
        for req, m, cached, S in run:
            if req.t_admitted is None:     # a resume keeps its first stamp
                req.t_admitted = now
            nbp = paged_kv.blocks_for(S, bs)
            # matched blocks were share()'d at drain time; only the
            # non-shared tail is allocated (may reclaim from the LRU,
            # which cannot touch the pinned matches)
            block_ids = list(m) + self.alloc.alloc(nbp - len(m))
            i = free_slots.pop(0)
            slot = self.slots[i]
            slot.req = req
            slot.blocks = block_ids
            slot.shared = len(m)
            slot.ticket = self._ticket
            self._ticket += 1
            self.table[i, :] = paged_kv.NULL_BLOCK
            self.table[i, :len(block_ids)] = block_ids
            if self.arena is not None:
                self._install_arena(i, req)
            rows.append((i, req, cached, S, block_ids))
            if self.prefix is not None:
                self.prefix_lookups += 1
                if m:
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += len(m) * bs
        _, m0, _, S0 = run[0]
        if m0 and len(m0) * bs == S0:
            row_logits = self._install_hits(rows)
        elif m0:
            row_logits = self._suffix_batch(rows)
        elif self.arena is not None:
            row_logits = self._encdec_batch(rows)
        else:
            row_logits = self._full_batch(rows)
        self.made_progress = True          # tokens cached in all flavors
        # index each row's full PROMPT-chunk blocks before sampling: a
        # max_tokens=1 row retires inside _accept, and its freed chain
        # must already be registered to land in the LRU (first-wins —
        # chunks cached earlier, including by this very batch, keep
        # their original block)
        if self.prefix is not None:
            for i, req, cached, S, block_ids in rows:
                for b in self.prefix.insert(cached, block_ids):
                    self.alloc.register(b)
        for i, req, cached, S, block_ids in rows:
            self.sampler.install(i, req.sampling, req._n_sampled)
            if req._n_sampled > 0:         # resume: nothing new to sample
                self.slots[i].last_token = req.token_ids[-1]
            elif row_logits is not None:   # miss/suffix: sample token 0
                outs.append(self._accept(
                    i, self.sampler.sample_one(i, row_logits[i:i + 1])))
            # fresh full hit: no logits yet — this step's decode replays
            # the prompt's last token and samples at stream position 0
        self._post_admit(rows)

    def _install_hits(self, rows):
        """Full-prefix hit: every block is already resident — no device
        call. A RESUME row's cache is complete (lengths = S, feed the
        last emitted token); a FRESH row still owes the sample after
        its prompt, so its length rewinds one token (lengths = S - 1)
        and this step's decode replays ``cached[-1]`` — the rewrite
        lands inside the shared tail block, which ``_ensure_cow``
        privatizes first."""
        for i, req, cached, S, block_ids in rows:
            if req._n_sampled > 0:
                self.lengths[i] = S
            else:
                self.lengths[i] = S - 1
                self.slots[i].last_token = cached[-1]
        return None

    def _suffix_batch(self, rows):
        """Partial hit: prefill ONLY each row's non-shared suffix, in
        one verify-path call (fed token j caches at ``lengths + j``,
        which is exactly suffix prefill when lengths = matched tokens).
        Non-participating slots ride along masked: local table rows at
        the null block and local lengths 0, so their writes land in the
        reserved block and their logits rows are ignored. Returns
        slot-indexed next-token logits."""
        bs = self.cfg.block_size
        i0, _, _, S0, _ = rows[0]
        W = self._suffix_bucket(S0 - self.slots[i0].shared * bs)
        fn = self._suffix_prefill(W)
        first = self._first_call(("suffix", W))
        N = self.cfg.num_slots
        toks = np.zeros((N, W), np.int32)
        slens = np.zeros((N,), np.int32)
        stable = np.full((N, self.layout.max_blocks_per_seq),
                         paged_kv.NULL_BLOCK, np.int32)
        last = np.zeros((N,), np.int32)
        for i, req, cached, S, block_ids in rows:
            mt = self.slots[i].shared * bs
            sfx = S - mt
            toks[i, :sfx] = cached[mt:]
            slens[i] = mt
            stable[i, :len(block_ids)] = block_ids
            last[i] = sfx - 1
            self.lengths[i] = S
            self.prefill_tokens += sfx
        with tracing.span("scheduler.prefill") as sp:
            if sp:
                sp.set(key=("suffix", W), computed=N * W, first=first,
                       tokens=sum(r[3] - self.slots[r[0]].shared * bs
                                  for r in rows))
            row_logits, self.pools = fn(
                self.params, self.pools, jnp.asarray(stable),
                jnp.asarray(slens), jnp.asarray(toks), jnp.asarray(last))
        self.prefill_calls += 1
        self.prefill_reqs += len(rows)
        self.prefill_computed_tokens += N * W
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="suffix_logits")
            return np.asarray(row_logits)  # (num_slots, V)

    def _full_batch(self, rows):
        """Prefix miss: the PR-4 batched full prefill — one right-padded
        batch call, each row's true-length cache scattered into its
        slot. Returns slot-indexed next-token logits."""
        fn, tok_w, cache_w, Nb = self._prefill(rows[0][3], len(rows))
        first = self._first_call(("prefill", tok_w, Nb))
        nbc = cache_w // self.cfg.block_size
        toks = np.zeros((Nb, tok_w), np.int32)
        lens = np.ones((Nb,), np.int32)    # batch fillers: harmless len 1
        ids = np.full((Nb, nbc), paged_kv.NULL_BLOCK, np.int32)
        row_of_slot = np.zeros((self.cfg.num_slots,), np.int32)
        valid = np.zeros((self.cfg.num_slots,), bool)
        for r, (i, req, cached, S, block_ids) in enumerate(rows):
            toks[r, :S] = cached           # exact path: tok_w == S, no pad
            lens[r] = S
            ids[r, :len(block_ids)] = block_ids  # pad tail -> null block
            row_of_slot[i] = r
            valid[i] = True
            self.lengths[i] = S
            self.prefill_tokens += S
        args = (self.params, self.pools, jnp.asarray(toks),
                jnp.asarray(ids), jnp.asarray(row_of_slot),
                jnp.asarray(valid), jnp.asarray(lens))
        with tracing.span("scheduler.prefill") as sp:
            if sp:
                sp.set(key=(tok_w, Nb), tokens=sum(r[3] for r in rows),
                       computed=Nb * tok_w, first=first)
            row_logits, self.pools = fn(*args)
        self.prefill_calls += 1
        self.prefill_reqs += len(rows)
        self.prefill_computed_tokens += Nb * tok_w
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="prefill_logits")
            row_logits = np.asarray(row_logits)  # (Nb, V): row pos S-1
        out = np.zeros((self.cfg.num_slots,) + row_logits.shape[1:],
                       row_logits.dtype)
        for r, (i, *_rest) in enumerate(rows):
            out[i] = row_logits[r]
        return out

    def _install_arena(self, i: int, req: RequestHandle) -> int:
        """Bind slot ``i`` to a cross-arena row: share the resident row
        when the SAME feature array (by identity) is already encoded,
        else claim a fresh one. The row is written by this admission's
        prefill call (idempotently for shared rows — the encoder is
        deterministic, so rewrites are bit-identical) and freed with the
        slot in ``_clear_slot``."""
        feats = req.encoder_features
        a = self.arena.lookup(id(feats))
        if a != paged_kv.NULL_ARENA:
            self.arena.share(a)
            self.arena_hits += 1
        else:
            a = self.arena.alloc(key=id(feats))
        self.arena_ids[i] = a
        self.enc_lengths[i] = feats.shape[0]
        return a

    def _encdec_batch(self, rows):
        """Encoder-decoder admission: one right-padded batch call runs
        the masked encoder forward, scatters each row's cross-KV into
        its arena row and packs the ragged decoder prefill into the
        block pool. Traces are cached per (prompt-bucket, frame-bucket,
        batch-bucket) triple. Returns slot-indexed next-token logits."""
        bs = self.cfg.block_size
        _, req0, _, S0, ids0 = rows[0]
        tok_w = self._bucket_key(S0) if self.ragged_prefill else S0
        Fb = self._enc_bucket(req0.encoder_features.shape[0])
        Nb = min(1 << max(len(rows) - 1, 0).bit_length(),
                 self.cfg.num_slots)
        fn = self._encdec_prefill(tok_w, Fb, Nb)
        first = self._first_call(("encdec", tok_w, Fb, Nb))
        nbc = paged_kv.blocks_for(tok_w, bs)
        d = self.model.cfg.d_model
        toks = np.zeros((Nb, tok_w), np.int32)
        lens = np.ones((Nb,), np.int32)    # batch fillers: harmless len 1
        frames = np.zeros((Nb, Fb, d), np.float32)
        enc_lens = np.zeros((Nb,), np.int32)   # fillers: fully masked
        ids = np.full((Nb, nbc), paged_kv.NULL_BLOCK, np.int32)
        aids = np.zeros((Nb,), np.int32)       # fillers: null arena row
        for r, (i, req, cached, S, block_ids) in enumerate(rows):
            toks[r, :S] = cached
            lens[r] = S
            F = req.encoder_features.shape[0]
            frames[r, :F] = np.asarray(req.encoder_features,
                                       np.float32)
            enc_lens[r] = F
            ids[r, :len(block_ids)] = block_ids
            aids[r] = self.arena_ids[i]
            self.lengths[i] = S
            self.prefill_tokens += S
        with tracing.span("scheduler.prefill") as sp:
            if sp:
                sp.set(key=("encdec", tok_w, Fb, Nb),
                       tokens=sum(r[3] for r in rows),
                       computed=Nb * tok_w, first=first)
            row_logits, self.pools = fn(
                self.params, self.pools, jnp.asarray(toks),
                jnp.asarray(frames), jnp.asarray(enc_lens),
                jnp.asarray(lens), jnp.asarray(ids), jnp.asarray(aids))
        self.prefill_calls += 1
        self.prefill_reqs += len(rows)
        self.prefill_computed_tokens += Nb * tok_w
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="prefill_logits")
            row_logits = np.asarray(row_logits)    # (Nb, V)
        out = np.zeros((self.cfg.num_slots,) + row_logits.shape[1:],
                       row_logits.dtype)
        for r, (i, *_rest) in enumerate(rows):
            out[i] = row_logits[r]
        return out

    def _encdec_prefill(self, tok_w: int, Fb: int, Nb: int):
        """Encoder-decoder prefill+pack, jit-cached per (prompt-bucket,
        frame-bucket, batch-bucket) — shares ``_prefill_cache`` so the
        compile-cap telemetry covers both axes."""
        key = ("encdec", tok_w, Fb, Nb)
        fn = self._prefill_cache.get(key)
        if fn is None:
            model, ctx = self.model, self.prefill_ctx

            def prefill_fn(params, pools, tokens, frames, enc_lens,
                           lengths, block_ids, arena_ids):
                return model.prefill_paged_encdec(
                    params, pools, tokens, frames, enc_lens, lengths,
                    block_ids, arena_ids, ctx)

            fn = shlib.jit_step(prefill_fn, self.shard, self._pool_sh,
                                donate=(1,))
            self._prefill_cache[key] = fn
        return fn

    def _prefill(self, S: int, n: int):
        """Prefill+pack, jit-cached per (prompt-bucket, batch-bucket):
        prompts pad to the power-of-two BUCKET (ragged models) or stay
        at the exact length (fallback — tokens keep width S, so
        recurrent chunk scans never see a pad token); batch widths pad
        to the next power of two (capped at num_slots). Returns
        (fn, token_width, cache_width, batch_width); cache_width is
        always a block multiple (pow-2 buckets are rounded up for
        non-pow-2 blocks)."""
        bs = self.cfg.block_size
        if self.ragged_prefill:
            Sb = self._bucket_key(S)
            tok_w = Sb
        else:
            Sb = paged_kv.blocks_for(S, bs) * bs
            tok_w = S
        Nb = min(1 << max(n - 1, 0).bit_length(), self.cfg.num_slots)
        key = (Sb, Nb) if self.ragged_prefill else ("exact", S, Nb)
        fn = self._prefill_cache.get(key)
        if fn is None:
            model, layout = self.model, self.layout
            ctx = self.prefill_ctx
            ragged = self.ragged_prefill
            kv_spec = self.kv_spec

            def prefill_fn(params, pools, tokens, block_ids, row_of_slot,
                           valid, length):
                logits, dense = model.prefill(
                    params, {"tokens": tokens}, ctx, max_len=Sb,
                    length=length if ragged else None)
                pools = model.pack_prefill_into_paged(
                    layout, pools, dense, row_of_slot, valid, block_ids,
                    spec=kv_spec)
                # only each row's next-token logits leave the device:
                # (Nb, V) instead of the full (Nb, tok_w, V) slab
                rows = jnp.take_along_axis(
                    logits, (length - 1)[:, None, None], axis=1)[:, 0]
                return rows, pools

            fn = shlib.jit_step(prefill_fn, self.shard, self._pool_sh,
                                donate=(1,))
            self._prefill_cache[key] = fn
        return fn, tok_w, Sb, Nb

    def _suffix_prefill(self, W: int):
        """Suffix-only prefill, jit-cached per suffix bucket ``W``
        (separate cache from full prefill so the O(log max_len) compile
        caps on each stay independently observable). Reuses the verify
        pass: fed token j caches at ``lengths + j`` reading the shared
        prefix through the block table, and ``commit_fn`` exports only
        each row's next-token logits row. Pad positions past a row's
        real blocks route to the null block (table rows are NULL beyond
        the chain; logical indices past the table width null-route in
        the kernel)."""
        fn = self._suffix_cache.get(W)
        if fn is None:
            model, ctx = self.model, self.ctx

            def suffix_fn(params, pools, table, lengths, tokens, last):
                def commit_fn(logits):    # (B, W, V) -> per-row last real
                    rows = jnp.take_along_axis(
                        logits, last[:, None, None], axis=1)[:, 0]
                    return rows, jnp.full(lengths.shape, W, jnp.int32)

                rows, _, pools = model.decode_verify(
                    params, pools, table, lengths, tokens, commit_fn, ctx)
                return rows, pools

            fn = shlib.jit_step(suffix_fn, self.shard, self._pool_sh,
                                donate=(1,))
            self._suffix_cache[W] = fn
        return fn

    def _preempt(self, i: int):
        """Evict slot i to a host-side recompute record (LIFO victim).
        NOT progress: a step that only evicts and re-queues emits no
        token and caches none, so reporting progress here would let
        Engine.drive spin through preempt/re-prefill churn forever —
        only admissions and decodes flip ``made_progress``."""
        slot = self.slots[i]
        req = slot.req
        req.num_preemptions += 1
        self.preemptions += 1
        self.alloc.free(slot.blocks)
        self._clear_slot(i)
        self.waiting.appendleft(req)      # preempted work goes first

    def _retire(self, i: int):
        """Backend cleanup after register_sample flagged the handle."""
        slot = self.slots[i]
        self.finished.append(slot.req)
        self.alloc.free(slot.blocks)
        self._clear_slot(i)

    def _clear_slot(self, i: int):
        slot = self.slots[i]
        slot.req = None
        slot.blocks = []
        slot.last_token = 0
        slot.ticket = -1
        slot.shared = 0
        self.table[i, :] = paged_kv.NULL_BLOCK
        self.lengths[i] = 0
        if self.arena is not None and self.arena_ids[i]:
            # retirement, preemption and migration detach all land here:
            # the arena row's refcount drops with the slot (resume
            # re-encodes), so rows can never outlive their requests
            self.arena.free(int(self.arena_ids[i]))
            self.arena_ids[i] = paged_kv.NULL_ARENA
            self.enc_lengths[i] = 0
        self.sampler.clear(i)
        self._post_clear(i)

    # -- migration (prefill/decode disaggregation) ----------------------

    def export_slot(self, i: int):
        """Host-side migration snapshot of occupied slot ``i``: the
        handle, its physical block chain, the cached length and the
        next token to feed. Device content is gathered separately by
        launch/engine/transport.py — JAX arrays are functional, so the
        gather may happen before or after ``detach_slot`` frees the
        chain without ever observing the reuse.

        An overlapped in-flight decode is harvested first: ``lengths``
        already counts the fed token (its pool write is ordered before
        any gather by the functional threading), but ``last_token`` is
        only current once the sampled value lands — exporting around an
        un-harvested token would migrate a stale feed. Callers must
        gate on occupancy AFTER any flush (the harvest can retire
        slots)."""
        self.flush_overlap()
        slot = self.slots[i]
        assert slot.req is not None, "exporting an empty slot"
        return slot.req, list(slot.blocks), int(self.lengths[i]), \
            slot.last_token

    def detach_slot(self, i: int):
        """Drop slot ``i`` WITHOUT retiring or re-queueing: its request
        now lives in a MigrationPacket. The block chain is freed here
        (shared references just decrement) because the packet carries
        gathered *content*, not block ids into this pool — a packet
        dropped mid-migration therefore leaks nothing on either side."""
        self.flush_overlap()           # no-op after export_slot's flush
        slot = self.slots[i]
        self.alloc.free(slot.blocks)
        self._clear_slot(i)

    def import_slot(self, req: RequestHandle, block_ids: list[int],
                    length: int, last_token: int) -> int:
        """Install a migrated request into a free slot over freshly
        alloc()'d ``block_ids`` (the transport scatters the packet's
        content into them; this installs the host-side view). The path
        is position-agnostic: ``length`` may sit anywhere from the
        full-hit rewind (S - 1, nothing sampled yet) to deep mid-decode
        re-export — ``cached`` reconstructs the block contents from the
        handle exactly like ``_cached_tokens`` does on a preemption
        resume. Full prompt+output chunks are registered in the prefix
        index so later admissions on THIS replica can share them."""
        free = [i for i, s in enumerate(self.slots) if s.req is None]
        assert free, "import into a full backend (caller gates on this)"
        i = free[0]
        slot = self.slots[i]
        slot.req = req
        slot.blocks = list(block_ids)
        slot.shared = 0                  # fresh private copies, COW-free
        slot.last_token = last_token
        slot.ticket = self._ticket
        self._ticket += 1
        self.table[i, :] = paged_kv.NULL_BLOCK
        self.table[i, :len(block_ids)] = block_ids
        if self.arena is not None:
            # the transport scatters the packet's cross row into this
            # arena row right after installing the host view
            self._install_arena(i, req)
        self.lengths[i] = length
        self.sampler.install(i, req.sampling, req._n_sampled)
        cached = (list(req.prompt) + req.token_ids)[:length]
        if self.prefix is not None:
            for b in self.prefix.insert(cached, slot.blocks):
                self.alloc.register(b)
        self._post_admit([(i, req, cached, length, list(block_ids))])
        self.made_progress = True
        return i

    def _post_admit(self, rows):
        """Subclass hook: ``(slot, req, cached, S, block_ids)`` rows just
        admitted (the speculative backend installs drafter state here)."""

    def _post_clear(self, i: int):
        """Subclass hook: slot ``i`` was just retired or preempted."""

    # -- reporting ------------------------------------------------------

    def reset_telemetry(self):
        """Zero the counters behind ``stats()`` (e.g. after bench
        warmup); does not touch scheduling state or jit caches."""
        self.finished.clear()
        self.steps = self.slot_steps = 0
        self.block_token_steps = self.live_token_steps = 0
        self.preemptions = 0
        self.prefill_calls = self.prefill_reqs = self.prefill_tokens = 0
        self.prefill_computed_tokens = 0
        self.prefix_lookups = self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.cow_copies = self.prefix_evictions = 0
        self.arena_hits = 0

    def stats(self) -> dict:
        """Cache/occupancy/scheduling telemetry for the run so far."""
        cap = self.block_token_steps or 1
        return {
            "steps": self.steps,
            "mean_active_slots": self.slot_steps / max(self.steps, 1),
            "cache_utilization": self.live_token_steps / cap,
            "overlap": bool(self.cfg.overlap),
            "blocks_free": self.alloc.free_count,
            "blocks_used": self.alloc.used_count,
            "preemptions": self.preemptions,
            "prefill_compiles": len(self._prefill_cache),
            "prefill_calls": self.prefill_calls,
            "prefill_reqs": self.prefill_reqs,
            "prefill_tokens": self.prefill_tokens,
            "prefill_computed_tokens": self.prefill_computed_tokens,
            "bucketed_prefill": self.ragged_prefill,
            "prefix_cache": {
                "enabled": self.prefix is not None,
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                "hit_rate": self.prefix_hits / max(self.prefix_lookups, 1),
                "hit_tokens": self.prefix_hit_tokens,
                "cow_copies": self.cow_copies,
                "evictions": self.prefix_evictions,
                "lru_blocks": self.alloc.lru_count,
                "suffix_compiles": len(self._suffix_cache),
            },
            "cross_arena": {
                "enabled": self.arena is not None,
                "rows_used": self.arena.used_count if self.arena else 0,
                "rows_free": self.arena.free_count if self.arena else 0,
                "shared_hits": self.arena_hits,
            },
        }
