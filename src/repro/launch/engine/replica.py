"""ReplicaSet: data-parallel engine replicas behind ONE admission queue.

EPAC scales throughput by replicating compute tiles behind one coherent
hub — VEC/STX/VRP share a CHI NoC and the uncore arbitrates work across
them. This is the serving analogue: R full ``Engine`` replicas over the
``data`` axis of the mesh (each gets its OWN KV block pool and its
model-axis TP subgrid via ``mesh.submeshes``), fed from one shared
admission queue. Requests are dispatched strictly FCFS — always the
queue head, never skip-ahead — through a pluggable placement policy:

  * ``least_loaded`` (default) — the replica with the fewest committed
    cache blocks (used + queued footprint), ties to the lowest replica
    index; the tensor-level version of the uncore routing a transaction
    to the least-occupied L2 slice.
  * ``round_robin`` — rotate over accepting replicas.

Fairness invariant: because dispatch only ever pops the HEAD of the
shared queue, and every replica's own queue is FCFS with a guaranteed-
progress oldest admission, no request waits unboundedly — the head is
dispatched as soon as ANY replica frees capacity, and within a replica
it inherits the engine's no-livelock guarantee. Preemption stays local
to a replica: an evicted request re-enters its OWN replica's queue
(front), never the shared queue, so its blocks/RNG bookkeeping never
crosses replicas.

On real accelerators each replica's submesh executes in parallel and
wall-clock throughput scales with R; on a CPU host simulating devices
the replicas time-share the cores, so the set also meters each
replica's BUSY time (cumulative wall spent inside its step calls) and
per-replica token counts — ``stats()['busy_s']`` — from which the
bench reports aggregate *capacity* (sum of per-replica-clock rates),
the number parallel hardware would sustain. Busy clocks are stamped
with ``time.monotonic()`` at the step dispatch/return boundaries
(never ``time.time()``, which can jump under NTP slew and is not an
interval clock); what the host waits on inside a step shows in the
engine's ``scheduler.fetch`` spans (engine/tracing.py), and the
device's own busy time in a profiler trace. ``step_workers > 1`` opts
into thread-parallel stepping. An honest accounting of when that helps:
the step loop is host-Python-heavy (dispatch bookkeeping, numpy
mirrors, jit-call argument marshalling all hold the GIL) and only the
blocking device fetch releases it, so threads pay GIL ping-pong on
every step and win only when per-step device compute is large enough
to dominate — big models on real accelerators, not smoke shapes. With
``overlap=True`` the blocking fetch shrinks further (the device call
of step N+1 is dispatched before step N's tokens are fetched), so the
GIL-released window threads could exploit mostly disappears;
overlap-within-a-replica and threads-across-replicas are largely
substitutes on a CPU host, and overlap is the cheaper of the two. It
therefore stays off by default.

Token streams are bit-identical to a single engine serving the same
requests: outputs are a pure function of (params, prompt,
SamplingParams) by the engine's RNG-stream contract, independent of
which replica, slot, or co-batch a request lands in.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from repro.launch.engine import api
from repro.launch.engine.api import (Engine, EngineConfig, RequestHandle,
                                     RequestOutput, SamplingParams)
from repro.models import paged_kv
from repro.models.model import Model


def least_loaded(rset: "ReplicaSet", candidates: list[int]) -> int:
    """Fewest committed blocks (paged) / occupied lanes (static); ties
    break to the LOWEST replica index so placement is deterministic."""
    return min(candidates, key=lambda r: (rset.load(r), r))


def round_robin(rset: "ReplicaSet", candidates: list[int]) -> int:
    """Rotate over accepting replicas (fallback policy)."""
    pick = min(candidates,
               key=lambda r: (r - rset._rr) % len(rset.replicas))
    rset._rr = pick + 1
    return pick


_POLICIES = {"least_loaded": least_loaded, "round_robin": round_robin}


class ReplicaSet:
    """Engine-shaped front-end over R data-parallel engine replicas.

    Parameters
    ----------
    model, params
        The target model and its parameter tree (shared by replicas).
    cfg : EngineConfig, optional
        The PER-REPLICA configuration (slots, pool, spec_tokens, ...);
        must not carry a mesh — pass it as ``mesh=`` instead.
    dp : int, optional
        Replica count; inferred from ``mesh.shape["data"]`` when a mesh
        is given.
    mesh : jax.sharding.Mesh, optional
        A (data, model) mesh; each replica runs on its own
        ``(1, tp)`` submesh of the data axis.
    policy : str or callable
        FCFS dispatch placement: ``"least_loaded"`` (default,
        fewest committed blocks, ties to the lowest index),
        ``"round_robin"``, or a callable ``(rset, candidates) -> int``.
    overrides : sequence of dict or None, optional
        Per-replica ``EngineConfig`` field replacements (one entry per
        replica; None entries keep ``cfg``) — e.g. ``spec_tokens`` per
        role so prefill replicas skip speculative decoding. May not
        carry ``mesh`` (pass ``mesh=``) or ``eos_id`` (stop semantics
        must match for outputs to stay request-pure). With overrides
        present, requests validate against EVERY replica, since any of
        them may end up serving the request.
    ctx : RunCtx, optional
        Kernel/sharding context forwarded to every replica.
    step_workers : int, optional
        Opt-in thread pool width for stepping busy replicas
        concurrently. Only the blocking device fetch releases the GIL,
        so this pays off only when per-step device compute dominates
        the host-side bookkeeping; with ``EngineConfig(overlap=True)``
        the fetch window shrinks further and threads gain almost
        nothing (see the module docstring). Off by default.

    Attributes
    ----------
    replicas : list of Engine
        The R identical engines (own KV pool, own submesh).
    queue : deque of RequestHandle
        The ONE shared admission queue; dispatch only ever pops its
        head (strict FCFS — no skip-ahead).
    finished : list of RequestHandle
        Handles retired so far, across replicas, in completion order.

    Notes
    -----
    Token streams are bit-identical to a single engine serving the same
    requests: outputs are a pure function of (params, prompt,
    SamplingParams) by the engine's RNG-stream contract, independent of
    which replica, slot, or co-batch a request lands in. Preemption
    stays replica-local — an evicted request re-enters its OWN
    replica's queue, never the shared queue. No request waits
    unboundedly: the head is dispatched as soon as ANY replica frees
    capacity, and within a replica it inherits the engine's
    no-livelock guarantee.
    """

    def __init__(self, model: Model, params, cfg: EngineConfig = None,
                 *, dp: Optional[int] = None, mesh=None,
                 policy="least_loaded", ctx=None, step_workers=None,
                 overrides: Optional[Sequence[Optional[dict]]] = None):
        cfg = cfg or EngineConfig()
        if mesh is not None:
            from repro.launch.mesh import submeshes

            dp = int(mesh.shape["data"]) if dp is None else dp
            meshes = submeshes(mesh, dp, axis="data")
        else:
            # replica meshes come from EITHER the mesh argument or
            # cfg.mesh — per-replica submeshing of cfg.mesh would be
            # ambiguous with a set-level mesh, so reject the combination
            if cfg.mesh is not None:
                raise ValueError("pass the mesh to ReplicaSet(mesh=...), "
                                 "not through EngineConfig")
            meshes = [None] * (dp or 1)
        if not meshes:
            raise ValueError("dp must be >= 1")
        self.dp = len(meshes)
        if overrides is not None and len(overrides) != self.dp:
            raise ValueError(f"{len(overrides)} overrides for "
                             f"{self.dp} replicas")
        cfgs = [cfg] * self.dp
        if overrides is not None:
            bad = {"mesh", "eos_id"} & set().union(
                *(ov.keys() for ov in overrides if ov))
            if bad:
                raise ValueError(f"per-replica overrides cannot change "
                                 f"{sorted(bad)}")
            cfgs = [dataclasses.replace(cfg, **(ov or {}))
                    for ov in overrides]
        self.replicas = [
            Engine(model, params, dataclasses.replace(c, mesh=m),
                   ctx=ctx) for c, m in zip(cfgs, meshes)]
        self.cfg = cfg                   # baseline per-replica config
        # replicas usually vouch for each other; with overrides any of
        # them may serve a request, so each must accept it individually
        self._validators = self.replicas if overrides is not None \
            else self.replicas[:1]
        self.policy = _POLICIES.get(policy, policy)
        if not callable(self.policy):
            raise ValueError(f"unknown dispatch policy {policy!r}")
        self.queue: collections.deque[RequestHandle] = collections.deque()
        self.finished: list[RequestHandle] = []
        self.made_progress = False
        self._uid = 0
        self._rr = 0                     # round-robin cursor
        # in-flight handles only: entries are pruned at retirement so a
        # long-running set does not accumulate every request ever served
        self._by_uid: dict[int, RequestHandle] = {}
        self._enq: dict[int, tuple[int, float]] = {}  # uid -> (step, t)
        workers = 1 if step_workers is None else \
            min(step_workers, os.cpu_count() or 1)
        self._pool = ThreadPoolExecutor(workers) if workers > 1 else None
        # telemetry
        self.steps = 0
        self.dispatched = [0] * self.dp
        self.busy_s = [0.0] * self.dp     # wall inside each replica's step
        self.tokens_out = [0] * self.dp   # tokens emitted per replica
        self.wait_steps: list[int] = []   # shared-queue wait per request
        self.wait_wall: list[float] = []

    @property
    def total_slots(self) -> int:
        """Decode slots across the whole set (per-replica cfgs summed)."""
        return sum(e.cfg.num_slots for e in self.replicas)

    # -- request lifecycle ----------------------------------------------

    def add_request(self, prompt,
                    sampling: Optional[SamplingParams] = None,
                    encoder_features=None) -> RequestHandle:
        """Validate against a representative replica and append to the
        shared FCFS queue; returns the live handle. ``prompt`` is a
        token-id sequence or an ``api.Request``."""
        if isinstance(prompt, api.Request):
            if sampling is not None or encoder_features is not None:
                raise ValueError("pass sampling/encoder_features inside "
                                 "the Request, not alongside it")
            sampling = prompt.sampling
            encoder_features = prompt.encoder_features
            prompt = prompt.prompt
        sampling = sampling or SamplingParams()
        prompt = list(prompt)
        # identical replicas: replica 0 vouches for all of them;
        # per-replica overrides: every replica must accept
        for eng in self._validators:
            eng.check_request(prompt, sampling, encoder_features)
        handle = RequestHandle(self._uid, prompt, sampling,
                               encoder_features=encoder_features)
        self._uid += 1
        self._by_uid[handle.uid] = handle
        self._enq[handle.uid] = (self.steps, time.monotonic())
        self.queue.append(handle)
        return handle

    def step(self) -> list[RequestOutput]:
        """Dispatch from the shared queue, then step every busy replica
        (concurrently when a thread pool is available) and merge their
        streams in replica order."""
        self.steps += 1
        moved = self._dispatch()
        busy = [(r, eng) for r, eng in enumerate(self.replicas)
                if eng.has_work]
        outs = self._timed_steps(busy)
        self.made_progress = moved > 0 or any(
            eng.backend.made_progress for _, eng in busy)
        self._finish(outs)
        return outs

    def _timed_steps(self, busy) -> list[RequestOutput]:
        """Step the given ``(index, engine)`` pairs — through the thread
        pool when one is configured — metering per-replica busy clocks
        and token counts; streams merge in replica order."""
        def timed_step(pair):
            r, eng = pair
            # monotonic: wall-clock (time.time) can jump under NTP
            # slew, making a busy interval negative or double-length
            t0 = time.monotonic()
            part = eng.step()
            self.busy_s[r] += time.monotonic() - t0
            self.tokens_out[r] += sum(len(o.new_tokens) for o in part)
            return part

        if self._pool is not None and len(busy) > 1:
            outs_per = list(self._pool.map(timed_step, busy))
        else:
            outs_per = [timed_step(p) for p in busy]
        outs: list[RequestOutput] = []
        for part in outs_per:
            outs.extend(part)
        return outs

    def _finish(self, outs: list[RequestOutput]):
        """Move retired handles from the in-flight map to ``finished``."""
        for out in outs:
            if out.finished:
                self.finished.append(self._by_uid.pop(out.request_id))

    @property
    def has_work(self) -> bool:
        """True while anything is queued or active on any replica."""
        return bool(self.queue) or any(e.has_work for e in self.replicas)

    def stats(self) -> dict:
        """Set-level telemetry: per-replica stats, dispatch counts,
        busy clocks, queue-wait distribution, and the aggregate
        occupancy/leak views the bench and CI read."""
        per = [e.stats() for e in self.replicas]
        paged = [e.backend for e in self.replicas
                 if hasattr(e.backend, "alloc")]
        live = sum(b.live_token_steps for b in paged)
        cap = sum(b.block_token_steps for b in paged)
        return {
            "dp": self.dp,
            "steps": self.steps,
            "per_replica": per,
            "dispatched": list(self.dispatched),
            "busy_s": list(self.busy_s),
            "tokens_out": list(self.tokens_out),
            "queue_depth": len(self.queue),
            "queue_wait_steps_mean": (sum(self.wait_steps)
                                      / max(len(self.wait_steps), 1)),
            "queue_wait_steps_max": max(self.wait_steps, default=0),
            "queue_wait_s_mean": (sum(self.wait_wall)
                                  / max(len(self.wait_wall), 1)),
            "ttft": self._ttft_stats(),
            "latency": api.latency_stats(
                list(self.finished) + list(self._by_uid.values())),
            # aggregate views the bench / leak checks read
            "mean_active_slots": sum(p["mean_active_slots"] for p in per),
            "cache_utilization": live / max(cap, 1),
            "blocks_used": sum(p.get("blocks_used", 0) for p in per),
            "preemptions": sum(p.get("preemptions", 0) for p in per),
            "prefill_compiles": sum(p["prefill_compiles"] for p in per),
            "prefill_calls": sum(p.get("prefill_calls", 0) for p in per),
            "prefill_reqs": sum(p.get("prefill_reqs", 0) for p in per),
        }

    def _ttft_stats(self) -> dict:
        """Time-to-first-token distribution (seconds) over every request
        that has sampled its first token so far — retired handles plus
        the in-flight map; the metric disaggregation is meant to win."""
        lat = [h.t_first_token - h.t_submit
               for h in list(self.finished) + list(self._by_uid.values())
               if h.t_first_token is not None]
        if not lat:
            return {"count": 0, "mean_s": 0.0, "p50_s": 0.0,
                    "p95_s": 0.0, "p99_s": 0.0}
        arr = np.asarray(lat)
        return {"count": len(lat),
                "mean_s": float(arr.mean()),
                "p50_s": float(np.percentile(arr, 50)),
                "p95_s": float(np.percentile(arr, 95)),
                "p99_s": float(np.percentile(arr, 99))}

    def reset_telemetry(self):
        """Zero every replica's counters and the set-level telemetry
        (bench warmup boundary); scheduling state is untouched."""
        for eng in self.replicas:
            eng.backend.reset_telemetry()
        self.finished.clear()
        self.steps = 0
        self.dispatched = [0] * self.dp
        self.busy_s = [0.0] * self.dp
        self.tokens_out = [0] * self.dp
        self.wait_steps.clear()
        self.wait_wall.clear()

    # -- dispatch -------------------------------------------------------

    def load(self, r: int) -> int:
        """Committed-capacity estimate: cache blocks held + the block
        footprint already queued at the replica (paged), or occupied +
        queued lanes (static)."""
        be = self.replicas[r].backend
        if hasattr(be, "alloc"):
            # emitted tokens count too: a preempted request waiting to
            # resume re-prefills its whole history, not just the prompt
            queued = sum(paged_kv.blocks_for(
                len(h.prompt) + len(h.token_ids) + 1,
                self.replicas[r].cfg.block_size) for h in be.waiting)
            return be.alloc.used_count + queued
        return be.num_active + len(be.waiting)

    def can_accept(self, r: int) -> bool:
        """A replica accepts while it has decode lanes not yet spoken
        for; beyond that, requests are better off in the shared queue
        where the policy can still steer them."""
        be = self.replicas[r].backend
        return self.replicas[r].cfg.num_slots \
            - be.num_active - len(be.waiting) > 0

    def _dispatch_candidates(self) -> list[int]:
        """Replica indices dispatch may target (subclass hook: the
        disaggregated engine restricts fresh admissions to prefill
        replicas, with a packet-backpressure gate)."""
        return list(range(self.dp))

    def _dispatch(self) -> int:
        moved = 0
        while self.queue:
            cands = [r for r in self._dispatch_candidates()
                     if self.can_accept(r)]
            if not cands:
                break                     # head waits; never skip ahead
            handle = self.queue.popleft()
            r = self.policy(self, cands)
            self.replicas[r].backend.enqueue(handle)
            self.dispatched[r] += 1
            step0, t0 = self._enq.pop(handle.uid)
            self.wait_steps.append(self.steps - 1 - step0)
            self.wait_wall.append(time.monotonic() - t0)
            moved += 1
        return moved

    # -- convenience drivers (Engine-shaped) ----------------------------

    def drain(self, max_steps: int = 100_000) -> list[RequestOutput]:
        """Step until idle; returns the concatenated output stream."""
        return api.drive(
            self, max_steps,
            "replica set stalled: waiting requests cannot be admitted "
            "on any replica")

    def generate(self, prompts: Sequence[Sequence[int]],
                 sampling=None, max_steps: int = 100_000,
                 encoder_features=None) -> list[list[int]]:
        """Submit ``prompts`` and drive to completion; returns token ids
        per prompt in submission order (token-identical to a single
        Engine serving the same prompts)."""
        return api.run_generate(self, prompts, sampling, max_steps,
                                encoder_features=encoder_features)
