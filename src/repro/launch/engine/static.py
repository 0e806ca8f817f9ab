"""StaticBackend: the lockstep batcher behind the unified Engine API.

A batch of waiting requests is admitted at once, prefilled as one
RIGHT-padded batch (real tokens at positions 0..len-1, so causal
attention never sees a pad key and rope positions match the unbatched
reference — fixing the PR-1 ``Server`` left-pad leak), then decoded in
lockstep with PER-ROW positions until every member finishes; only then
is the next batch admitted. Finished rows ride along shape-stably with
their outputs discarded. Dense (B, max_len) cache — no paging, no
preemption; the baseline the paged backend is benchmarked against.

Per-row prefill true lengths thread through ``model.prefill`` so ring
and recurrent caches capture state at each row's real boundary; prompt
lengths are padded to power-of-two buckets so the prefill jit cache
stays O(log max_len). Models whose prefill state cannot be extracted at
a traced length (mlstm/slstm) batch FCFS runs of equal prompt length
instead (exact prefill, no pad tokens ever enter the recurrence).
"""

from __future__ import annotations

import collections
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import sharding as shlib
from repro.launch.engine.api import (EngineConfig, RequestHandle,
                                     RequestOutput, prefill_bucket,
                                     register_sample)
from repro.launch.engine.sampling import SlotSampler


class StaticBackend:
    """Lockstep batcher over a dense (B, max_len) cache (the baseline).

    One batch in, right-padded batched prefill, per-row-position decode
    until every member finishes, then the next batch — no paging, no
    preemption. See the module docstring for the padding/bucketing
    contract; the serve bench prices it against the paged backend at
    equal cache memory."""

    def __init__(self, model, params, cfg: EngineConfig, ctx):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.ctx = ctx
        self.ragged = model.serving_caps().ragged_prefill
        B = cfg.num_slots
        self.waiting: collections.deque[RequestHandle] = collections.deque()
        self.finished: list[RequestHandle] = []
        self.batch: list[Optional[RequestHandle]] = [None] * B
        self.live = np.zeros((B,), bool)
        self.lengths = np.ones((B,), np.int32)
        self.last = np.zeros((B,), np.int32)
        self.cache = None
        self.sampler = SlotSampler(B)
        self.made_progress = False
        # telemetry
        self.steps = 0
        self.batches = 0
        self.slot_steps = 0
        self.live_token_steps = 0

        # Mesh-sharded serving: commit params once; shlib.jit_step pins
        # the cache's NamedShardings on every jit output so prefill
        # hands decode a stably-placed cache (batch over data axes,
        # kv-heads/state width over TP — launch/sharding.py cache rules).
        self.shard = ctx.shard
        self._cache_sh = None
        if self.shard is not None:
            self.params = shlib.place_params(params, self.shard)
            shapes = jax.eval_shape(
                lambda: model.init_cache(B, cfg.max_len))
            self._cache_sh = shlib.named(
                self.shard.mesh, shlib.batch_specs(shapes, self.shard))

        def decode_fn(params, cache, tokens, lengths):
            return model.decode_step(params, cache, tokens, lengths,
                                     self.ctx)

        self._decode = shlib.jit_step(decode_fn, self.shard,
                                      self._cache_sh, donate=(1,))
        self._prefill_cache = {}

    # -- public backend API ---------------------------------------------

    def enqueue(self, req: RequestHandle):
        """Append to the FCFS queue (validated by the caller)."""
        self.waiting.append(req)

    @property
    def num_active(self) -> int:
        """Live rows in the current lockstep batch."""
        return int(self.live.sum())

    @property
    def has_work(self) -> bool:
        """True while any request is waiting or live."""
        return bool(self.waiting) or bool(self.live.any())

    def live_handles(self):
        """Resident + queued request handles (latency aggregation —
        see ``api.latency_stats``)."""
        return [h for h in self.batch if h is not None
                and not h.finished] + list(self.waiting)

    def step(self) -> list[RequestOutput]:
        """Admit a fresh batch when idle, else one lockstep decode."""
        outs: list[RequestOutput] = []
        self.made_progress = False
        if not self.live.any():
            if not self.waiting:
                return outs
            self._admit_batch(outs)
            return outs
        rows = np.flatnonzero(self.live)
        tokens = jnp.asarray(self.last[:, None])
        logits, self.cache = self._decode(
            self.params, self.cache, tokens, jnp.asarray(self.lengths))
        toks = self.sampler.sample(logits)
        self.steps += 1
        self.slot_steps += len(rows)
        self.made_progress = True
        for i in rows:
            self.lengths[i] += 1          # the fed token got cached
            self.live_token_steps += int(self.lengths[i])
            outs.append(self._accept(int(i), int(toks[i])))
        if not self.live.any():
            self._clear_batch()
        return outs

    # -- internals ------------------------------------------------------

    def _admit_batch(self, outs: list[RequestOutput]):
        """Lockstep admission IS batched prefill admission here: the
        whole batch prefills as one right-padded call (one jit trace
        per pow-2 bucket of the max member). Admission is NOT
        fragmented by bucket — a lockstep lane idled by a bucket split
        stays idle for the entire generation cycle, which costs far
        more than the padding it saves. ``max_prefill_batch`` (> 0)
        bounds the admitted width and hence the prefill call width."""
        B = self.cfg.num_slots
        cap = B if self.cfg.max_prefill_batch <= 0 else \
            min(B, self.cfg.max_prefill_batch)
        reqs = []
        while self.waiting and len(reqs) < cap:
            # models without length-exact padded prefill (mlstm/slstm)
            # batch FCFS runs of EQUAL prompt length — correctness over
            # packing; the paged backend has no such restriction
            if not self.ragged and reqs and \
                    len(self.waiting[0].prompt) != len(reqs[0].prompt):
                break
            reqs.append(self.waiting.popleft())
        plens = [len(r.prompt) for r in reqs]
        Lb = self._bucket(max(plens))
        toks = np.zeros((B, Lb), np.int32)
        lens = np.ones((B,), np.int32)    # dummy rows: harmless length 1
        for i, r in enumerate(reqs):
            toks[i, :plens[i]] = r.prompt
            lens[i] = plens[i]
        logits, self.cache = self._prefill(Lb)(
            self.params, jnp.asarray(toks), jnp.asarray(lens))
        # each row's next-token logits live at its true last position
        row_logits = jnp.take_along_axis(
            logits, jnp.asarray(lens - 1)[:, None, None], axis=1)[:, 0]
        self.batches += 1
        self.lengths[:] = lens
        self.last[:] = 0
        now = time.monotonic()
        for i, r in enumerate(reqs):
            r.t_admitted = now
            self.batch[i] = r
            self.live[i] = True
            self.sampler.install(i, r.sampling, 0)
        first = self.sampler.sample(row_logits)
        for i in range(len(reqs)):
            outs.append(self._accept(i, int(first[i])))
        self.made_progress = True
        if not self.live.any():           # whole batch stopped at prefill
            self._clear_batch()

    def _bucket(self, maxp: int) -> int:
        if not self.ragged:
            return maxp                   # uniform lengths: exact
        # same floor/cap policy as the paged backend (one shared helper)
        # so both engines compile identical bucket sets on one trace
        return prefill_bucket(maxp, self.cfg.block_size, self.cfg.max_len)

    def _prefill(self, Lb: int):
        fn = self._prefill_cache.get(Lb)
        if fn is None:
            model, cfg, ctx = self.model, self.cfg, self.ctx
            ragged = self.ragged

            def prefill_fn(params, tokens, lengths):
                return model.prefill(params, {"tokens": tokens}, ctx,
                                     max_len=cfg.max_len,
                                     length=lengths if ragged else None)

            fn = shlib.jit_step(prefill_fn, self.shard, self._cache_sh)
            self._prefill_cache[Lb] = fn
        return fn

    def _accept(self, i: int, tok: int) -> RequestOutput:
        out = register_sample(self.batch[i], tok, self.cfg.eos_id,
                              lambda: self._finish(i))
        if not out.finished:
            self.sampler.steps[i] = self.batch[i]._n_sampled
            self.last[i] = tok
        return out

    def _finish(self, i: int):
        """Backend cleanup after register_sample flagged the handle."""
        self.finished.append(self.batch[i])
        self.live[i] = False              # rides along until batch ends

    def _clear_batch(self):
        B = self.cfg.num_slots
        self.batch = [None] * B
        self.live[:] = False
        self.lengths[:] = 1
        self.last[:] = 0
        self.cache = None
        for i in range(B):
            self.sampler.clear(i)

    # -- reporting ------------------------------------------------------

    def reset_telemetry(self):
        """Zero the counters behind ``stats()`` (e.g. after bench
        warmup); does not touch scheduling state or jit caches."""
        self.finished.clear()
        self.steps = self.batches = 0
        self.slot_steps = self.live_token_steps = 0

    def stats(self) -> dict:
        """Occupancy/utilization telemetry (dense-cache denominator:
        every lane pays max_len whether live or not)."""
        cap = self.steps * self.cfg.num_slots * self.cfg.max_len or 1
        return {
            "steps": self.steps,
            "batches": self.batches,
            "mean_active_slots": self.slot_steps / max(self.steps, 1),
            "cache_utilization": self.live_token_steps / cap,
            "prefill_compiles": len(self._prefill_cache),
        }
