"""Serving benchmark: static vs continuous batching under Poisson arrivals.

Replays one request trace — Poisson inter-arrival times, ragged prompts,
skewed output lengths (many short responses, a few long stragglers) —
through both backends of the unified serving ``Engine``
(repro.launch.engine):

* static     — lockstep batcher: right-padded batched prefill, decode
  every batch until its last member finishes. Dense (B, max_len) cache
  slab regardless of actual lengths.
* continuous — the paged backend: per-slot retirement + optimistic
  admission mid-flight, LIFO preemption under pool pressure, bucketed
  prefill, block-granular cache occupancy.
* sharded    — the same paged backend over a (data, model) mesh of the
  local devices (``--tp`` picks the model-axis degree): params sharded
  by the 2-D FSDP x TP rules, the block pool head-sharded (each device
  owns its kv-head shard of every block). Emits mesh shape, whether the
  head-shard shard_map path was active, and per-device resident cache
  bytes + utilization. Run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise a
  real multi-device mesh on CPU (the CI multi-device job does).
* replicas   — ``--dp`` data-parallel paged replicas behind ONE shared
  admission queue (ReplicaSet, least-loaded-blocks dispatch), each
  replica on its own data-axis submesh with its own KV pool, against a
  single replica of the identical per-replica config on the same
  (heavier, dp-scaled) trace. Emits aggregate tok/s, the speedup over
  one replica, per-replica utilization/dispatch share, and shared-queue
  wait — the fixed-per-replica scale-out story (EPAC: more tiles behind
  the same hub).

A sixth section, ``shared_prefix``, replays a saturated trace of
prompts sharing one long system prefix through the paged backend with
the COW prefix cache off and on: hit rate, prefill tokens saved, COW
copies, and a bit-identity check between the two runs (outputs_match).

A seventh section, ``disagg``, replays a mixed-prompt-length trace
through a symmetric ``ReplicaSet`` and a ``DisaggregatedEngine`` of the
same ``--dp`` at identical per-replica config (equal total cache
memory): prefill/decode role specialization vs everyone-does-both.
Reports wall tok/s and TTFT p50/p95 for both (long co-resident prefills
are exactly the interference TTFT p95 measures), migration volume
(packets, bytes, estimated fabric seconds via ``core.noc.p2p_time``)
and a bit-identity check (outputs_match). Every section now carries a
``ttft`` sub-dict computed from per-request submit/first-token stamps.

An eighth section, ``workloads``, prices the two NON-dense request
classes the one Engine serves: dropless MoE (qwen3-moe smoke) and
encoder-decoder traffic (whisper smoke — ``Request.encoder_features``
through the cross-KV arena), each at the same ``--mem-tokens`` budget
as the dense sections, each replayed co-batched and again
one-request-at-a-time at identical cache config. Reports per-class
tok/s, the co-batching speedup, arena sharing/leak telemetry, and the
bit-identity check (outputs_match) between the two replays — the
workload-generalization contract tests/test_workload_serve.py pins.

A ninth section, ``quantized``, prices the int8 paged KV pool
(per-(token, kv-head) scales, dequant fused into the kernels —
``EngineConfig(kv_dtype="int8")``) against the bf16 pool at equal cache
BYTE budget on a head_dim=64 smoke variant: usable-block capacity
ratio, tok/s for both, and the greedy token match rate vs the bf16 run
(floor-gated by benchmarks/check_serve_regression.py).

A tenth section, ``open_loop``, replays a deterministic open-loop
arrival trace (benchmarks/traffic.py — seeded Poisson arrivals,
decode-heavy output lengths, per-request TTFT/TPOT budgets) through
the paged backend with ``EngineConfig(overlap=)`` OFF and ON at equal
config: the overlap run dispatches step N+1's fused device call before
fetching step N's sampled tokens. Reports TTFT/TPOT p50/p95/p99,
goodput-under-SLO (token throughput of budget-meeting requests, with
budgets calibrated from the measured baseline so CI machine speed
cannot zero it), the overlap speedup, the p99-TTFT ratio, and the
bit-identity check (outputs_match — raw-asserted at JSON write: the
RNG-stream contract says overlap may change WHEN tokens are fetched
but never WHICH tokens come out).

The comparison is at EQUAL CACHE MEMORY (--mem-tokens of KV capacity):
the static engine must preallocate max_len per lane, so its batch is
``mem // max_len``; the paged engine spends the same tokens of pool on
whatever mix of live sequences fits (vLLM's core claim, and the
tensor-level version of EPAC's interleaved L2 slices vs per-core
private allocation).

Reported per engine: useful tokens/s, cache memory utilization (live
tokens / allocated token capacity, averaged over decode steps), lane
efficiency (useful tokens per slot-step — the scheduling win, hardware
independent), plus the paged engine's preemption count and prefill
compile count. Results go to stdout as CSV (benchmarks/common.py
discipline) AND to a machine-readable ``BENCH_serve.json`` so the perf
trajectory is trackable across PRs.

Warmup matters: the first token of a request is sampled at prefill, so
warmup requests use max_tokens=2 — with 1, the decode step would first
compile inside the timed region and dominate the wall times.

Run: PYTHONPATH=src python benchmarks/bench_serve.py --smoke
CSV:  name,us_per_call,derived  (via benchmarks/common.py emit discipline)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.engine import Engine, EngineConfig, SamplingParams
from repro.launch.engine.api import latency_stats
from repro.models.model import Model


@dataclasses.dataclass
class TraceItem:
    arrival: float              # seconds since trace start
    prompt: list[int]
    max_new: int


def make_trace(cfg, *, n_requests: int, rate: float, seed: int,
               prompt_lens=(8, 12, 16), n_new_max: int = 64):
    """Poisson arrivals; skewed (mostly-short) output-length distribution.

    The skew is the point: a lockstep batch decodes every member to the
    batch max, so one straggler holds ~B-1 finished lanes hostage."""
    rng = np.random.default_rng(seed)
    t = 0.0
    trace = []
    for _ in range(n_requests):
        t += float(rng.exponential(1.0 / rate))
        plen = int(rng.choice(prompt_lens))
        prompt = list(rng.integers(0, cfg.vocab_size, plen))
        max_new = int(rng.choice([4, 6, 8, n_new_max],
                                 p=[0.45, 0.25, 0.2, 0.1]))
        trace.append(TraceItem(t, prompt, max_new))
    return trace


def make_repetitive_trace(cfg, *, n_requests: int, seed: int,
                          n_new: int = 64):
    """Decode-heavy OFFLINE trace of REPETITIVE prompts (short patterns
    tiled) for the speculative section: prompt-lookup drafting keys on
    exactly this structure (templated prose / code), long fixed outputs
    put the weight on the decode loop speculation accelerates, and
    arrival=0 for every request keeps the engine saturated — the
    decode-throughput regime the K-token window is a lever for (the
    Poisson traces above measure admission behavior instead)."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n_requests):
        pat = list(rng.integers(0, cfg.vocab_size, int(rng.integers(2, 5))))
        plen = int(rng.integers(9, 17))
        trace.append(TraceItem(0.0, (pat * plen)[:plen], n_new))
    return trace


def make_shared_prefix_trace(cfg, *, n_requests: int, seed: int,
                             shared: int = 48, unique: int = 8,
                             n_new: int = 8):
    """Offline trace of prompts sharing one long system prefix (~86%
    of prompt tokens shared at the default 48+8 split) — the regime
    COW prefix caching targets: thousands of requests re-prefilling the
    same system prompt. Arrival 0 for every request keeps the queue
    saturated so admission cost (the thing caching removes) dominates
    the prefill side of the measurement."""
    rng = np.random.default_rng(seed)
    common = list(rng.integers(0, cfg.vocab_size, shared))
    return [TraceItem(0.0,
                      common + list(rng.integers(0, cfg.vocab_size,
                                                 unique)),
                      n_new)
            for _ in range(n_requests)]


def _wait_until(t0: float, arrival: float):
    dt = t0 + arrival - time.time()
    if dt > 0:
        time.sleep(dt)


def _warm(engine, trace):
    """Warm the jit caches on the engine itself (a second engine would
    double the pool memory the benchmark claims to budget)."""
    # max_tokens=2, not 1: the first token is sampled at prefill, so a
    # 1-token request retires without ever compiling the decode step.
    # Beyond the trace's prompt lengths, also warm every power-of-two
    # bucket up to max_len: preemption-resume re-prefills land at
    # prompt+emitted-1 tokens, which can hit buckets no prompt started
    # in — those compiles must not fall inside the timed region.
    warm = {len(r.prompt) for r in trace}
    b = 2
    while b < engine.cfg.max_len * 2:     # include the TOP bucket
        warm.add(min(b, engine.cfg.max_len - 2))
        b *= 2
    # the paged backend traces one prefill jit per (bucket, batch-
    # bucket) pair — warm every power-of-two batch width per bucket
    # (splits under pool pressure just warm the smaller widths, which
    # the replay is equally limited to); the static backend keys on
    # bucket alone, so extra widths would warm nothing
    widths = [1]
    if hasattr(engine.backend, "alloc"):
        while widths[-1] * 2 <= engine.cfg.num_slots:
            widths.append(widths[-1] * 2)
    vocab = engine.backend.model.cfg.vocab_size
    c = 1
    for plen in sorted(warm):
        for nb in widths:
            # DISTINCT rows: identical probe rows would prefix-hit each
            # other on cache-on engines and the (bucket, width) FULL-
            # prefill trace this pass exists to compile would first
            # trace inside the timed region
            batch = []
            for _ in range(nb):
                pat = [c % vocab, (c // vocab) % vocab]
                batch.append((pat * plen)[:plen])
                c += 1
            try:
                engine.generate(batch, SamplingParams(max_tokens=2))
            except ValueError:
                # tiny pools reject the top-bucket probe's worst case at
                # admission — a length no real request can use either,
                # so there is nothing to warm there
                break
    if getattr(engine.backend, "prefix", None) is None:
        return
    # prefix-cache engines take two more admission paths the replay
    # must not compile mid-measurement: full-hit installs (the COW jit
    # on the first decode) and suffix-only prefills (one trace per
    # power-of-two suffix bucket). ONE shared probe prompt across all
    # lengths produces exactly those: the first call per length misses
    # (already-warm full prefill) and registers, repeats full-hit, and
    # each longer length suffix-prefills from the previous one.
    base = trace[0].prompt[:1] * (engine.cfg.max_len - 2)
    for plen in sorted(warm):
        for nb in widths:
            try:
                engine.generate([base[:plen]] * nb,
                                SamplingParams(max_tokens=2))
            except ValueError:
                break


def _replay(engine, trace, handles_out=None) -> dict:
    """Warm (on the engine itself), reset telemetry, then replay the
    trace against the arrival clock. ``engine`` is an Engine or a
    ReplicaSet — both speak add_request/step/stats. ``handles_out``
    (optional list) receives the finished request handles in trace
    order, for sections that compare emitted tokens across configs."""
    if hasattr(engine, "replicas"):       # warm each replica's jit caches
        pre = list(getattr(engine, "prefill_ids", ()))
        for r in pre:                     # let prefill-only replicas
            engine.replicas[r].backend.prefill_only = False   # finish _warm
        for rep in engine.replicas:
            _warm(rep, trace)
        for r in pre:
            engine.replicas[r].backend.prefill_only = True
        if pre:                           # trace the migration jits too
            engine.generate([t.prompt for t in trace[:2]],
                            SamplingParams(max_tokens=2))
        engine.reset_telemetry()
    else:
        _warm(engine, trace)
        engine.backend.reset_telemetry()
    t0 = time.time()
    pending = list(trace)
    handles = []
    while pending or engine.has_work:
        now = time.time() - t0
        while pending and pending[0].arrival <= now:
            r = pending.pop(0)
            handles.append(engine.add_request(
                r.prompt, SamplingParams(max_tokens=r.max_new)))
        if engine.has_work:
            engine.step()
        elif pending:
            _wait_until(t0, pending[0].arrival)
    dt = time.time() - t0
    if handles_out is not None:
        handles_out.extend(handles)
    return _result_row(engine, handles, dt)


def _result_row(engine, handles, dt) -> dict:
    """Per-section telemetry row from finished handles + engine stats
    (shared by ``_replay`` and the workload-class replays so every
    section reports the same columns)."""
    useful = sum(len(h.token_ids) for h in handles)
    st = engine.stats()
    slots = getattr(engine, "total_slots", engine.cfg.num_slots)
    lane_eff = useful / max(st["steps"] * slots, 1)
    lat = latency_stats(handles)
    return {"tok_s": useful / dt, "useful": useful, "wall_s": dt,
            "ttft": lat["ttft"], "tpot": lat["tpot"],
            "lane_eff": lane_eff,
            "cache_util": st["cache_utilization"],
            "mean_active": st["mean_active_slots"],
            "preemptions": st.get("preemptions", 0),
            "prefill_compiles": st["prefill_compiles"],
            "prefill_calls": st.get("prefill_calls", 0),
            "blocks_leaked": st.get("blocks_used", 0)}


def _per_device_cache_bytes(engine: Engine) -> dict:
    """Resident paged-cache bytes per device (the head-sharded pool puts
    1/|tp| of every block on each TP device; per-slot state follows the
    cache rules)."""
    import collections

    per = collections.defaultdict(int)
    for leaf in jax.tree.leaves(engine.backend.pools):
        for sh in leaf.addressable_shards:
            per[sh.device.id] += sh.data.nbytes
    return {str(k): int(v) for k, v in sorted(per.items())}


def _replay_sharded(model, params, trace, args) -> dict:
    """Replay the trace through the paged backend sharded over a
    (data = n/tp, model = tp) mesh of the local devices. With one local
    device this degenerates to a (1, 1) mesh — the sharded code path
    still runs, which is what the single-device CI smoke checks."""
    from repro.launch.mesh import make_local_mesh, mesh_summary

    # fail loudly on a bad --tp rather than silently benchmarking an
    # unsharded mesh under the "sharded" label (make_local_mesh raises)
    mesh = make_local_mesh(args.tp)
    eng = Engine(model, params, EngineConfig(
        backend="paged", num_slots=args.slots,
        block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark,
        mesh=mesh))
    res = _replay(eng, trace)
    res["mesh"] = mesh_summary(mesh)
    res["head_sharded"] = bool(eng.backend.ctx.decode_head_shard)
    per_dev = _per_device_cache_bytes(eng)
    # symmetric layout: every device sees the same live/allocated ratio,
    # so per-device utilization is the global one over its resident share
    res["per_device_cache"] = {
        dev: {"bytes": b, "util": round(res["cache_util"], 4)}
        for dev, b in per_dev.items()}
    return res


def _capacity(rset) -> float:
    """Aggregate tokens/s over per-replica CLOCKS: each replica's
    emitted tokens over the wall time spent inside ITS step calls. On
    parallel hardware replicas overlap and this equals wall-clock
    throughput; on a CPU host simulating devices they time-share the
    cores, and this is the rate the set would sustain if they did not —
    the quantity data-parallel replication actually adds. A replica the
    dispatch policy starves (or overloads into a long straggler tail)
    drags the sum down, so this number also scores placement quality."""
    st = rset.stats()
    return sum(t / b for t, b in zip(st["tokens_out"], st["busy_s"])
               if b > 0)


def _replay_replicas(model, params, trace, args) -> dict:
    """The ``"replicas"`` section: the same (dp-scaled) trace through a
    ReplicaSet of ``--dp`` data-parallel paged replicas behind one
    shared admission queue, against a SINGLE replica of the identical
    per-replica config (same slots, same pool, same submesh shape) —
    the fixed-per-replica scale-out claim. Reports wall-clock AND
    per-replica-clock (capacity) aggregate tok/s, per-replica
    utilization / dispatch share, and shared-queue wait."""
    from repro.launch.engine import ReplicaSet
    from repro.launch.mesh import make_mesh, mesh_summary

    cfg = EngineConfig(
        backend="paged", num_slots=args.slots,
        block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark)
    mesh = sub0 = None
    if len(jax.devices()) >= args.dp * args.tp and \
            args.dp * args.tp > 1:
        # exactly dp x tp devices: each replica owns a (1, tp) subgrid
        mesh = make_mesh((args.dp, args.tp), ("data", "model"))
        # the dp=1 baseline runs on ONE replica-shaped submesh so both
        # sides get identical per-replica resources
        sub0 = make_mesh((1, args.tp), ("data", "model"))
    single = ReplicaSet(model, params, cfg, dp=1, mesh=sub0)
    res_1 = _replay(single, trace)
    cap_1 = _capacity(single)
    # drop the baseline's pool before the replica replay so resident
    # cache memory stays at the dp x pool the section claims to budget
    del single
    rset = ReplicaSet(model, params, cfg, dp=args.dp, mesh=mesh)
    res = _replay(rset, trace)
    st = rset.stats()
    res["dp"] = args.dp
    res["mesh"] = mesh_summary(mesh) if mesh is not None else None
    res["single_tok_s"] = cap_1
    res["single_wall_tok_s"] = res_1["tok_s"]
    res["agg_tok_s"] = _capacity(rset)
    res["speedup_vs_single"] = res["agg_tok_s"] / max(cap_1, 1e-9)
    res["speedup_wall"] = res["tok_s"] / max(res_1["tok_s"], 1e-9)
    res["dispatched"] = st["dispatched"]
    res["per_replica"] = [
        {"util": round(p["cache_utilization"], 4),
         "mean_active": round(p["mean_active_slots"], 3),
         "steps": p["steps"],
         "busy_s": round(b, 4),
         "tok_s": round(t / b, 2) if b > 0 else 0.0,
         "preemptions": p.get("preemptions", 0),
         "share": round(d / max(sum(st["dispatched"]), 1), 4)}
        for p, d, b, t in zip(st["per_replica"], st["dispatched"],
                              st["busy_s"], st["tokens_out"])]
    res["queue_wait"] = {
        "steps_mean": round(st["queue_wait_steps_mean"], 3),
        "steps_max": st["queue_wait_steps_max"],
        "s_mean": round(st["queue_wait_s_mean"], 6)}
    return res


def _replay_speculative(model, params, args) -> dict:
    """The ``"speculative"`` section: a decode-heavy repetitive-prompt
    trace through the paged backend WITHOUT speculation and through the
    same config with ``spec_tokens`` n-gram self-drafting, at equal
    cache memory. Reports both tok/s, the speedup, and the acceptance
    telemetry from ``Engine.stats()['spec']`` (the same per-request
    counters the docs cite). Speculation changes WHAT the step computes
    but not WHAT tokens come out — equivalence is pinned by
    tests/test_spec_decode.py; this section only prices it."""
    trace = make_repetitive_trace(model.cfg, n_requests=2 * args.requests,
                                  seed=args.seed + 2)
    base_cfg = EngineConfig(
        backend="paged", num_slots=args.slots, block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark)
    eng = Engine(model, params, base_cfg)
    res_b = _replay(eng, trace)
    del eng
    spec = Engine(model, params, dataclasses.replace(
        base_cfg, spec_tokens=args.spec_tokens, drafter=args.drafter))
    res = _replay(spec, trace)
    st = spec.stats()["spec"]
    res["spec_tokens"] = args.spec_tokens
    res["drafter"] = args.drafter
    res["base_tok_s"] = res_b["tok_s"]
    res["speedup_vs_paged"] = res["tok_s"] / max(res_b["tok_s"], 1e-9)
    res["accept_rate"] = round(st["accept_rate"], 4)
    res["accepted_per_step"] = round(
        st["accepted"] / max(st["steps"], 1), 4)
    res["emitted_per_step"] = round(st["emitted_per_step"], 4)
    res["proposed"] = st["proposed"]
    res["accepted"] = st["accepted"]
    return res


def _replay_shared_prefix(model, params, args) -> dict:
    """The ``"shared_prefix"`` section: a saturated trace of prompts
    sharing one long system prefix, through the paged backend with the
    COW prefix cache OFF and ON at equal cache memory. Reports both
    tok/s, the hit rate, prefill tokens computed under each config (the
    saved volume is the caching win), COW copy and LRU eviction counts,
    and whether the two runs emitted bit-identical tokens (the
    correctness contract tests/test_prefix_cache.py pins; the bench
    re-checks it on every run because BENCH_serve.json is CI-gated)."""
    trace = make_shared_prefix_trace(model.cfg,
                                     n_requests=2 * args.requests,
                                     seed=args.seed + 3)
    base_cfg = EngineConfig(
        backend="paged", num_slots=args.slots, block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark,
        prefix_cache=False)
    off = Engine(model, params, base_cfg)
    h_off: list = []
    res_off = _replay(off, trace, h_off)
    st_off = off.stats()
    del off
    on = Engine(model, params,
                dataclasses.replace(base_cfg, prefix_cache=True))
    h_on: list = []
    res = _replay(on, trace, h_on)
    st = on.stats()
    pc = st["prefix_cache"]
    res["base_tok_s"] = res_off["tok_s"]
    res["speedup_vs_uncached"] = res["tok_s"] / max(res_off["tok_s"],
                                                    1e-9)
    res["hit_rate"] = round(pc["hits"] / max(pc["lookups"], 1), 4)
    res["hits"] = pc["hits"]
    res["lookups"] = pc["lookups"]
    res["hit_tokens"] = pc["hit_tokens"]
    res["prefill_tokens"] = st["prefill_tokens"]
    res["prefill_tokens_uncached"] = st_off["prefill_tokens"]
    res["prefill_tokens_saved"] = (st_off["prefill_tokens"]
                                   - st["prefill_tokens"])
    res["prefill_reduction"] = (st_off["prefill_tokens"]
                                / max(st["prefill_tokens"], 1))
    res["cow_copies"] = pc["cow_copies"]
    res["evictions"] = pc["evictions"]
    res["suffix_compiles"] = pc["suffix_compiles"]
    res["outputs_match"] = ([h.token_ids for h in h_on]
                            == [h.token_ids for h in h_off])
    return res


def _replay_disagg(model, params, args) -> dict:
    """The ``"disagg"`` section: prefill/decode disaggregation
    (DisaggregatedEngine, roles="auto") against a symmetric ReplicaSet
    of the same ``--dp`` at IDENTICAL per-replica config — equal total
    cache memory — on a mixed-prompt-length trace whose long prefills
    are the TTFT interference role specialization removes. Reports wall
    tok/s and TTFT p50/p95 for both, migration volume (packets / bytes /
    estimated fabric seconds from ``core.noc.p2p_time``), steal count,
    and the bit-identity check (outputs_match) the CI gate enforces."""
    from repro.launch.engine import DisaggregatedEngine, ReplicaSet
    from repro.launch.mesh import make_mesh, mesh_summary

    # 2x requests per replica (like the replicas section): percentile
    # TTFT stats need the sample count, and the win lives in the
    # saturated regime where symmetric slots are decode-occupied
    trace = make_trace(model.cfg, n_requests=2 * args.requests * args.dp,
                       rate=args.rate, seed=args.seed + 4,
                       prompt_lens=(6, 12, 24, 40))
    cfg = EngineConfig(
        backend="paged", num_slots=args.slots,
        block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark)
    mesh = None
    if len(jax.devices()) >= args.dp * args.tp and \
            args.dp * args.tp > 1:
        mesh = make_mesh((args.dp, args.tp), ("data", "model"))
    sym = ReplicaSet(model, params, cfg, dp=args.dp, mesh=mesh)
    h_s: list = []
    res_sym = _replay(sym, trace, h_s)
    # drop the symmetric set's pools before the disagg replay so
    # resident cache stays at the dp x pool the section budgets
    del sym
    dis = DisaggregatedEngine(model, params, cfg, dp=args.dp,
                              mesh=mesh, roles="auto")
    h_d: list = []
    res = _replay(dis, trace, h_d)
    st = dis.stats()["disagg"]
    res["dp"] = args.dp
    res["roles"] = list(dis.roles)
    res["mesh"] = mesh_summary(mesh) if mesh is not None else None
    res["sym_tok_s"] = res_sym["tok_s"]
    res["sym_ttft"] = res_sym["ttft"]
    res["speedup_wall"] = res["tok_s"] / max(res_sym["tok_s"], 1e-9)
    res["ttft_p95_ratio"] = (res["ttft"]["p95_s"]
                             / max(res_sym["ttft"]["p95_s"], 1e-9))
    res["packets"] = st["imported"]
    res["stolen"] = st["stolen"]
    res["bytes_moved"] = st["bytes_moved"]
    res["bytes_per_packet"] = round(st["bytes_per_packet"], 1)
    res["fabric_s"] = st["fabric_s"]
    res["outputs_match"] = ([h.token_ids for h in h_d]
                            == [h.token_ids for h in h_s])
    res["sym_blocks_leaked"] = res_sym["blocks_leaked"]
    return res


def _replay_encdec(engine, items, handles_out=None) -> dict:
    """Offline (arrival-0) replay of encoder-decoder requests — a list
    of ``(prompt, frames, max_new)`` triples — with ``_replay``'s warm /
    reset / time discipline. Needs its own warm pass because the
    generic ``_warm`` probes carry no encoder features, which
    ``check_request`` rejects on an enc-dec config; probe clips are all
    one encoder length, so the enc bucket axis contributes exactly one
    bucket of compiles."""
    cfg = engine.backend.model.cfg
    flen = max(f.shape[0] for _, f, _ in items)
    widths = [1]
    while widths[-1] * 2 <= engine.cfg.num_slots:
        widths.append(widths[-1] * 2)
    probe_rng = np.random.default_rng(0)
    c = 1
    for plen in sorted({len(p) for p, _, _ in items}):
        for nb in widths:
            prompts, feats = [], []
            for _ in range(nb):
                pat = [c % cfg.vocab_size, (c // cfg.vocab_size)
                       % cfg.vocab_size]
                prompts.append((pat * plen)[:plen])
                feats.append(probe_rng.standard_normal(
                    (flen, cfg.d_model)).astype(np.float32))
                c += 1
            engine.generate(prompts, SamplingParams(max_tokens=2),
                            encoder_features=feats)
    engine.backend.reset_telemetry()
    t0 = time.time()
    handles = [engine.add_request(p, SamplingParams(max_tokens=n),
                                  encoder_features=f)
               for p, f, n in items]
    while engine.has_work:
        engine.step()
    dt = time.time() - t0
    if handles_out is not None:
        handles_out.extend(handles)
    res = _result_row(engine, handles, dt)
    arena = engine.stats()["cross_arena"]
    res["arena_rows_leaked"] = arena["rows_used"]
    res["arena_shared_hits"] = arena["shared_hits"]
    return res


def _replay_workloads(args) -> dict:
    """The ``"workloads"`` section: the OTHER two request classes —
    dropless MoE (qwen3-moe smoke) and encoder-decoder (whisper smoke,
    cross-KV arena) — through the same paged ``Engine`` at the same
    ``--mem-tokens`` cache budget the dense sections spend. Each class
    replays its trace co-batched across ``--slots`` lanes and again
    one-request-at-a-time on an identically-budgeted single-slot
    engine: tokens must be bit-identical (the co-batching-invariance
    contract tests/test_workload_serve.py pins, re-checked every run
    because BENCH_serve.json is CI-gated), nothing may leak from the
    block pool or the cross-KV arena, and the repeated-clip enc-dec
    trace must actually share arena rows by feature identity."""
    out = {}
    base = dict(backend="paged", block_size=args.block_size,
                num_blocks=args.mem_tokens // args.block_size + 1,
                max_len=args.max_len, watermark_blocks=args.watermark)

    cfg = get_config("qwen3_moe_30b_a3b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = make_trace(cfg, n_requests=args.requests, rate=args.rate,
                       seed=args.seed + 5)
    eng = Engine(model, params,
                 EngineConfig(num_slots=args.slots, **base))
    h: list = []
    res = _replay(eng, trace, h)
    del eng
    seq = Engine(model, params, EngineConfig(num_slots=1, **base))
    h_seq: list = []
    res_seq = _replay(seq, trace, h_seq)
    del seq, model, params
    res["arch"] = cfg.name
    res["seq_tok_s"] = res_seq["tok_s"]
    res["cobatch_speedup"] = res["tok_s"] / max(res_seq["tok_s"], 1e-9)
    res["seq_blocks_leaked"] = res_seq["blocks_leaked"]
    res["outputs_match"] = ([x.token_ids for x in h]
                            == [x.token_ids for x in h_seq])
    out["moe"] = res

    cfg = get_config("whisper_base").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed + 6)
    flen = cfg.encoder_len
    shared_clip = rng.standard_normal(
        (flen, cfg.d_model)).astype(np.float32)
    items = []
    for i in range(args.requests):
        plen = int(rng.choice((6, 10)))
        prompt = list(rng.integers(0, cfg.vocab_size, plen))
        # every third request decodes the SAME clip object — the
        # several-transcripts-per-audio pattern identity sharing
        # detects, so co-resident repeats hold one arena row
        clip = shared_clip if i % 3 == 0 else rng.standard_normal(
            (flen, cfg.d_model)).astype(np.float32)
        items.append((prompt, clip, 12))
    eng = Engine(model, params,
                 EngineConfig(num_slots=args.slots, **base))
    h = []
    res = _replay_encdec(eng, items, h)
    del eng
    seq = Engine(model, params, EngineConfig(num_slots=1, **base))
    h_seq = []
    res_seq = _replay_encdec(seq, items, h_seq)
    del seq, model, params
    res["arch"] = cfg.name
    res["seq_tok_s"] = res_seq["tok_s"]
    res["cobatch_speedup"] = res["tok_s"] / max(res_seq["tok_s"], 1e-9)
    res["seq_blocks_leaked"] = res_seq["blocks_leaked"]
    res["outputs_match"] = ([x.token_ids for x in h]
                            == [x.token_ids for x in h_seq])
    out["encdec"] = res
    return out


def _pool_bytes_per_block(model, layout, spec) -> int:
    """Bytes one physical block occupies across every full-attention
    pool leaf (payload + scale leaves under a quantized ``spec``),
    computed from abstract shapes — no allocation. Per-slot state
    (rings, SSM carries) is excluded: it does not scale with the
    block budget this section trades."""
    shapes = jax.eval_shape(lambda: model.init_paged_cache(layout, spec))
    mask = model.paged_pool_mask(layout, spec)
    total = 0
    for leaf, kind in zip(jax.tree.leaves(shapes), jax.tree.leaves(mask)):
        if kind == "pool":
            total += (leaf.size // leaf.shape[1]
                      * np.dtype(leaf.dtype).itemsize)
    return int(total)


def _replay_quantized(args) -> dict:
    """The ``"quantized"`` section: int8 paged KV (per-(token, kv-head)
    scale leaves, dequant fused into the decode/verify kernels) against
    the bf16 pool at EQUAL cache byte budget, on a head_dim=64 smoke
    variant (the TPU lane-width-representative geometry; tiny smoke
    head dims understate the payload ratio because the fixed 4-byte
    scale amortizes over the head dim). The int8 engine converts the
    byte budget into several-fold the usable blocks (3.75x vs the
    f32-stored default pool) — the serving win is
    CAPACITY: more concurrent tokens resident per byte. Reports the
    usable-block capacity ratio, tok/s for both engines, the greedy
    token-level match rate vs the bf16 run (the quality gate
    benchmarks/check_serve_regression.py enforces a floor on), and
    both leak counters."""
    from repro.models import paged_kv

    cfg = dataclasses.replace(get_config(args.arch).smoke(), head_dim=64)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    trace = make_trace(cfg, n_requests=args.requests, rate=args.rate,
                       seed=args.seed + 7)
    bs = args.block_size
    nb_bf16 = args.mem_tokens // bs + 1
    layout = paged_kv.PagedLayout(
        num_slots=args.slots, num_blocks=nb_bf16, block_size=bs,
        max_len=args.max_len)
    spec = paged_kv.make_pool_spec(cfg, layout, kv_dtype="int8")
    b_bf16 = _pool_bytes_per_block(model, layout, None)
    b_int8 = _pool_bytes_per_block(model, layout, spec)
    budget = (nb_bf16 - 1) * b_bf16        # null block excluded
    nb_int8 = budget // b_int8 + 1
    base = EngineConfig(
        backend="paged", num_slots=args.slots, block_size=bs,
        num_blocks=nb_bf16, max_len=args.max_len,
        watermark_blocks=args.watermark)
    eng = Engine(model, params, base)
    h_fp: list = []
    res_fp = _replay(eng, trace, h_fp)
    del eng
    qeng = Engine(model, params, dataclasses.replace(
        base, num_blocks=int(nb_int8), kv_dtype="int8"))
    h_q: list = []
    res = _replay(qeng, trace, h_q)
    matched = total = 0
    for a, b in zip(h_fp, h_q):
        total += max(len(a.token_ids), len(b.token_ids))
        matched += sum(x == y for x, y in zip(a.token_ids, b.token_ids))
    res["kv_dtype"] = "int8"
    res["head_dim"] = cfg.head_dim
    res["bf16_tok_s"] = res_fp["tok_s"]
    res["bf16_blocks_leaked"] = res_fp["blocks_leaked"]
    res["bf16_preemptions"] = res_fp["preemptions"]
    res["bytes_per_block_bf16"] = b_bf16
    res["bytes_per_block_int8"] = b_int8
    res["cache_bytes_budget"] = int(budget)
    res["usable_blocks_bf16"] = nb_bf16 - 1
    res["usable_blocks_int8"] = int(nb_int8) - 1
    res["capacity_ratio"] = round((nb_int8 - 1) / (nb_bf16 - 1), 4)
    res["match_rate"] = round(matched / max(total, 1), 4)
    return res


def _replay_open_loop(model, params, args) -> dict:
    """The ``"open_loop"`` section: one deterministic Poisson arrival
    trace (benchmarks/traffic.py; decode-heavy output lengths, since
    TPOT and the overlap toggle both live in the decode loop) through
    the paged backend with ``overlap=`` OFF then ON at equal config.

    SLO budgets are calibrated from the MEASURED baseline replay
    (generous multiples of its median TTFT/TPOT, longer prompts earning
    proportionally more TTFT headroom) so goodput-under-SLO is a
    scheduling metric, not a CPU-speed lottery; the same budgets then
    score both runs, and ``ttft_p99_ratio`` (overlap p99 over baseline
    p99) is machine-normalized by construction. Outputs must be
    bit-identical across the toggle — the RNG-stream contract — and
    ``_write_json`` raw-asserts it."""
    try:                          # package import (python -m benchmarks.run)
        from benchmarks import traffic
    except ImportError:           # script import (python benchmarks/bench_serve.py)
        import traffic

    trace = traffic.make_open_loop_trace(
        model.cfg, kind="poisson", n_requests=2 * args.requests,
        rate=args.rate, seed=args.seed + 8)
    base_cfg = EngineConfig(
        backend="paged", num_slots=args.slots,
        block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark)
    off = Engine(model, params, base_cfg)
    h_off: list = []
    res_off = _replay(off, trace, h_off)
    del off
    # budgets from the measured baseline: 4x its median TTFT, 3x its
    # median TPOT (floored so an all-zero-latency degenerate run can't
    # produce zero budgets) — applied identically to both runs
    lat = latency_stats(h_off)
    budget = traffic.SLO(ttft_s=max(4.0 * lat["ttft"]["p50_s"], 1e-3),
                         tpot_s=max(3.0 * lat["tpot"]["p50_s"], 1e-4))
    traffic.annotate_slos(trace, ttft_s=budget.ttft_s,
                          tpot_s=budget.tpot_s)
    slo_off = traffic.slo_report(h_off, trace, res_off["wall_s"])
    on = Engine(model, params,
                dataclasses.replace(base_cfg, overlap=True))
    h_on: list = []
    res = _replay(on, trace, h_on)
    slo_on = traffic.slo_report(h_on, trace, res["wall_s"])
    res["kind"] = "poisson"
    res["rate"] = args.rate
    res["requests"] = len(trace)
    res["overlap"] = True
    res["slo_budget"] = dataclasses.asdict(budget)
    res["slo"] = slo_on
    res["base_slo"] = slo_off
    res["base_tok_s"] = res_off["tok_s"]
    res["base_blocks_leaked"] = res_off["blocks_leaked"]
    res["overlap_speedup"] = res["tok_s"] / max(res_off["tok_s"], 1e-9)
    res["ttft_p99_ratio"] = (slo_on["ttft"]["p99_s"]
                             / max(slo_off["ttft"]["p99_s"], 1e-9))
    res["goodput_tok_s"] = slo_on["goodput_tok_s"]
    res["goodput_frac"] = slo_on["goodput_frac"]
    res["outputs_match"] = ([h.token_ids for h in h_on]
                            == [h.token_ids for h in h_off])
    return res


def run_bench(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = make_trace(cfg, n_requests=args.requests, rate=args.rate,
                       seed=args.seed)

    static_batch = max(args.mem_tokens // args.max_len, 1)
    eng_s = Engine(model, params,
                   EngineConfig(backend="static", num_slots=static_batch,
                                max_len=args.max_len,
                                block_size=args.block_size))
    res_s = _replay(eng_s, trace)
    eng_c = Engine(model, params, EngineConfig(
        backend="paged", num_slots=args.slots,
        block_size=args.block_size,
        num_blocks=args.mem_tokens // args.block_size + 1,
        max_len=args.max_len, watermark_blocks=args.watermark))
    res_c = _replay(eng_c, trace)
    res_sh = _replay_sharded(model, params, trace, args)
    # the replica section uses its own heavier trace: 2x requests per
    # replica so the scale-out claim is measured in the saturated
    # regime, where straggler tails amortize over a long bulk phase
    rep_trace = make_trace(cfg, n_requests=2 * args.requests * args.dp,
                           rate=args.rate, seed=args.seed + 1)
    res_r = _replay_replicas(model, params, rep_trace, args)
    res_sp = _replay_speculative(model, params, args)
    res_px = _replay_shared_prefix(model, params, args)
    res_dg = _replay_disagg(model, params, args)
    res_w = _replay_workloads(args)
    res_q = _replay_quantized(args)
    res_ol = _replay_open_loop(model, params, args)
    return {
        "arch": cfg.name,
        "mem_tokens": args.mem_tokens,
        "static": res_s,
        "continuous": res_c,
        "sharded": res_sh,
        "replicas": res_r,
        "speculative": res_sp,
        "shared_prefix": res_px,
        "disagg": res_dg,
        "workloads": res_w,
        "quantized": res_q,
        "open_loop": res_ol,
        "speedup": res_c["tok_s"] / max(res_s["tok_s"], 1e-9),
    }


def _write_json(result: dict, json_path: str):
    """Persist machine-readable results; fail loudly on a block leak
    from EITHER entry point (CLI main or benchmarks/run.py)."""
    with open(json_path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    if result["continuous"]["blocks_leaked"] \
            or result["sharded"]["blocks_leaked"] \
            or result["replicas"]["blocks_leaked"] \
            or result["speculative"]["blocks_leaked"] \
            or result["shared_prefix"]["blocks_leaked"] \
            or result["disagg"]["blocks_leaked"] \
            or result["disagg"]["sym_blocks_leaked"]:
        raise SystemExit("block leak detected")
    if not result["shared_prefix"]["outputs_match"]:
        raise SystemExit("prefix cache changed emitted tokens")
    if not result["disagg"]["outputs_match"]:
        raise SystemExit("disaggregation changed emitted tokens")
    for cls in ("moe", "encdec"):
        w = result["workloads"][cls]
        if w["blocks_leaked"] or w["seq_blocks_leaked"]:
            raise SystemExit(f"{cls} workload leaked blocks")
        if not w["outputs_match"]:
            raise SystemExit(f"co-batching changed {cls} emitted tokens")
    if result["workloads"]["encdec"]["arena_rows_leaked"]:
        raise SystemExit("cross-KV arena leaked rows")
    q = result["quantized"]
    if q["blocks_leaked"] or q["bf16_blocks_leaked"]:
        raise SystemExit("quantized section leaked blocks")
    ol = result["open_loop"]
    if ol["blocks_leaked"] or ol["base_blocks_leaked"]:
        raise SystemExit("open_loop section leaked blocks")
    if not ol["outputs_match"]:
        raise SystemExit("overlap changed emitted tokens")


def _emit(result: dict, json_path: str):
    res_s, res_c = result["static"], result["continuous"]
    res_m = result["sharded"]
    print("name,tok_s,cache_util,lane_eff,useful_tokens,wall_s")
    print(f"serve_static,{res_s['tok_s']:.2f},{res_s['cache_util']:.3f},"
          f"{res_s['lane_eff']:.3f},{res_s['useful']},"
          f"{res_s['wall_s']:.2f}")
    print(f"serve_continuous,{res_c['tok_s']:.2f},"
          f"{res_c['cache_util']:.3f},{res_c['lane_eff']:.3f},"
          f"{res_c['useful']},{res_c['wall_s']:.2f}")
    print(f"serve_sharded,{res_m['tok_s']:.2f},"
          f"{res_m['cache_util']:.3f},{res_m['lane_eff']:.3f},"
          f"{res_m['useful']},{res_m['wall_s']:.2f}")
    res_r = result["replicas"]
    print(f"serve_replicas,{res_r['tok_s']:.2f},"
          f"{res_r['cache_util']:.3f},{res_r['lane_eff']:.3f},"
          f"{res_r['useful']},{res_r['wall_s']:.2f}")
    res_p = result["speculative"]
    print(f"serve_speculative,{res_p['tok_s']:.2f},"
          f"{res_p['cache_util']:.3f},{res_p['lane_eff']:.3f},"
          f"{res_p['useful']},{res_p['wall_s']:.2f}")
    res_x = result["shared_prefix"]
    print(f"serve_shared_prefix,{res_x['tok_s']:.2f},"
          f"{res_x['cache_util']:.3f},{res_x['lane_eff']:.3f},"
          f"{res_x['useful']},{res_x['wall_s']:.2f}")
    res_d = result["disagg"]
    print(f"serve_disagg,{res_d['tok_s']:.2f},"
          f"{res_d['cache_util']:.3f},{res_d['lane_eff']:.3f},"
          f"{res_d['useful']},{res_d['wall_s']:.2f}")
    res_w = result["workloads"]
    for nm, r in (("serve_moe", res_w["moe"]),
                  ("serve_encdec", res_w["encdec"])):
        print(f"{nm},{r['tok_s']:.2f},{r['cache_util']:.3f},"
              f"{r['lane_eff']:.3f},{r['useful']},{r['wall_s']:.2f}")
    res_q = result["quantized"]
    print(f"serve_quantized,{res_q['tok_s']:.2f},"
          f"{res_q['cache_util']:.3f},{res_q['lane_eff']:.3f},"
          f"{res_q['useful']},{res_q['wall_s']:.2f}")
    res_o = result["open_loop"]
    print(f"serve_open_loop,{res_o['tok_s']:.2f},"
          f"{res_o['cache_util']:.3f},{res_o['lane_eff']:.3f},"
          f"{res_o['useful']},{res_o['wall_s']:.2f}")
    print(f"# sharded mesh {res_m['mesh']['axes']}; "
          f"head_sharded={res_m['head_sharded']}; "
          f"per-device cache {res_m['per_device_cache']}")
    print(f"# replicas dp={res_r['dp']}: aggregate capacity "
          f"{res_r['agg_tok_s']:.1f} tok/s = "
          f"{res_r['speedup_vs_single']:.2f}x one replica "
          f"({res_r['single_tok_s']:.1f}); wall {res_r['tok_s']:.1f} "
          f"({res_r['speedup_wall']:.2f}x, replicas time-share CPU "
          f"cores); dispatched {res_r['dispatched']}; "
          f"queue wait {res_r['queue_wait']}")
    print(f"# speculative K={res_p['spec_tokens']} "
          f"({res_p['drafter']}): {res_p['tok_s']:.1f} tok/s = "
          f"{res_p['speedup_vs_paged']:.2f}x non-speculative paged "
          f"({res_p['base_tok_s']:.1f}) on the repetitive trace; "
          f"accept rate {res_p['accept_rate']:.2f}, "
          f"{res_p['accepted_per_step']:.2f} accepted drafts/step")
    print(f"# shared prefix: hit rate {res_x['hit_rate']:.2f} "
          f"({res_x['hits']}/{res_x['lookups']}), prefill tokens "
          f"{res_x['prefill_tokens']} vs "
          f"{res_x['prefill_tokens_uncached']} uncached "
          f"({res_x['prefill_reduction']:.2f}x fewer, "
          f"{res_x['prefill_tokens_saved']} saved); "
          f"{res_x['tok_s']:.1f} tok/s = "
          f"{res_x['speedup_vs_uncached']:.2f}x uncached "
          f"({res_x['base_tok_s']:.1f}); cow copies "
          f"{res_x['cow_copies']}; outputs_match "
          f"{res_x['outputs_match']}")
    print(f"# disagg dp={res_d['dp']} roles={res_d['roles']}: "
          f"{res_d['tok_s']:.1f} tok/s vs symmetric "
          f"{res_d['sym_tok_s']:.1f} ({res_d['speedup_wall']:.2f}x); "
          f"ttft p50/p95 {res_d['ttft']['p50_s'] * 1e3:.1f}/"
          f"{res_d['ttft']['p95_s'] * 1e3:.1f} ms vs "
          f"{res_d['sym_ttft']['p50_s'] * 1e3:.1f}/"
          f"{res_d['sym_ttft']['p95_s'] * 1e3:.1f} ms "
          f"(p95 ratio {res_d['ttft_p95_ratio']:.2f}); "
          f"{res_d['packets']} packets ({res_d['stolen']} stolen), "
          f"{res_d['bytes_moved']} bytes, "
          f"fabric {res_d['fabric_s']:.2e} s; "
          f"outputs_match {res_d['outputs_match']}")
    print(f"# workloads at the same {result['mem_tokens']}-token "
          f"budget: moe ({res_w['moe']['arch']}) "
          f"{res_w['moe']['tok_s']:.1f} tok/s = "
          f"{res_w['moe']['cobatch_speedup']:.2f}x one-at-a-time "
          f"({res_w['moe']['seq_tok_s']:.1f}), outputs_match "
          f"{res_w['moe']['outputs_match']}; encdec "
          f"({res_w['encdec']['arch']}) "
          f"{res_w['encdec']['tok_s']:.1f} tok/s = "
          f"{res_w['encdec']['cobatch_speedup']:.2f}x one-at-a-time "
          f"({res_w['encdec']['seq_tok_s']:.1f}), arena shared hits "
          f"{res_w['encdec']['arena_shared_hits']}, rows leaked "
          f"{res_w['encdec']['arena_rows_leaked']}, outputs_match "
          f"{res_w['encdec']['outputs_match']}")
    print(f"# quantized kv ({res_q['kv_dtype']}, head_dim "
          f"{res_q['head_dim']}): {res_q['usable_blocks_int8']} usable "
          f"blocks vs {res_q['usable_blocks_bf16']} bf16 at the same "
          f"{res_q['cache_bytes_budget']} cache bytes "
          f"({res_q['capacity_ratio']:.2f}x capacity); "
          f"{res_q['tok_s']:.1f} tok/s vs bf16 "
          f"{res_q['bf16_tok_s']:.1f}; greedy match rate "
          f"{res_q['match_rate']:.4f}")
    print(f"# open loop ({res_o['kind']}, {res_o['rate']:.0f} req/s, "
          f"{res_o['requests']} reqs): overlap {res_o['tok_s']:.1f} "
          f"tok/s = {res_o['overlap_speedup']:.2f}x no-overlap "
          f"({res_o['base_tok_s']:.1f}); ttft p50/p99 "
          f"{res_o['slo']['ttft']['p50_s'] * 1e3:.1f}/"
          f"{res_o['slo']['ttft']['p99_s'] * 1e3:.1f} ms "
          f"(p99 ratio {res_o['ttft_p99_ratio']:.2f}); tpot p50/p99 "
          f"{res_o['slo']['tpot']['p50_s'] * 1e3:.2f}/"
          f"{res_o['slo']['tpot']['p99_s'] * 1e3:.2f} ms; goodput "
          f"{res_o['goodput_tok_s']:.1f} tok/s "
          f"({res_o['goodput_frac']:.2f} of emitted, "
          f"{res_o['slo']['slo_frac']:.2f} of requests in SLO); "
          f"outputs_match {res_o['outputs_match']}")
    print(f"# equal cache budget {result['mem_tokens']} tokens; "
          f"continuous/static tokens/s: {result['speedup']:.2f}x; "
          f"mean active slots {res_c['mean_active']:.2f}; "
          f"preemptions {res_c['preemptions']}; "
          f"prefill compiles {res_c['prefill_compiles']}; "
          f"blocks leaked {res_c['blocks_leaked']}")
    print(f"# wrote {json_path}")
    _write_json(result, json_path)


def _parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--mem-tokens", type=int, default=512,
                    help="KV cache capacity in tokens, shared budget")
    ap.add_argument("--slots", type=int, default=8,
                    help="decode slots for the continuous engine")
    ap.add_argument("--watermark", type=int, default=2,
                    help="free-block admission watermark (paged)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the sharded section "
                         "(mesh over local devices; run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to fake devices on CPU)")
    ap.add_argument("--dp", type=int, default=2,
                    help="data-parallel replicas for the replicas "
                         "section (ReplicaSet over the mesh's data "
                         "axis; dp*tp must divide the device count)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens per step for the speculative "
                         "section (K; the verify step scores K+1 "
                         "positions in one pass)")
    ap.add_argument("--drafter", default="ngram",
                    choices=["ngram", "draft_model"],
                    help="draft source for the speculative section "
                         "(the bench builds no draft model, so "
                         "'ngram' is the meaningful choice here)")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable results path")
    return ap


def run():
    """benchmarks/run.py entry: smoke trace, common-CSV rows + JSON."""
    from benchmarks.common import emit

    args = _parser().parse_args(["--smoke"])
    result = run_bench(args)
    for name, r in (("serve_static", result["static"]),
                    ("serve_continuous", result["continuous"]),
                    ("serve_sharded", result["sharded"]),
                    ("serve_replicas", result["replicas"]),
                    ("serve_speculative", result["speculative"]),
                    ("serve_shared_prefix", result["shared_prefix"]),
                    ("serve_disagg", result["disagg"]),
                    ("serve_moe", result["workloads"]["moe"]),
                    ("serve_encdec", result["workloads"]["encdec"]),
                    ("serve_quantized", result["quantized"]),
                    ("serve_open_loop", result["open_loop"])):
        emit(name, 1e6 / max(r["tok_s"], 1e-9),
             f"tok_s={r['tok_s']:.2f} util={r['cache_util']:.3f} "
             f"preemptions={r['preemptions']} "
             f"prefill_compiles={r['prefill_compiles']}")
    _write_json(result, args.json)


def main():
    args = _parser().parse_args()
    compile_cache.enable()
    _emit(run_bench(args), args.json)


if __name__ == "__main__":
    main()
