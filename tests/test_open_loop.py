"""Open-loop traffic, chaos/fault-injection, and property tests for the
serving invariants (ISSUE-10 test layer).

What PRs 1–9 pinned with friendly traces, this suite attacks with
adversarial ones:

  1. traffic generators (benchmarks/traffic.py): determinism (a trace
     is a pure function of its seed — no wall clock), arrival-order and
     rate sanity per kind, SLO scoring arithmetic;
  2. chaos traces, injected by STEP INDEX (not wall time, so a failure
     reproduces from nothing but its seed): admission bursts at
     pool-exhaustion boundaries, all-max-length storms, and
     cancel-mid-prefill floods (max_tokens=1 / instant-stop-token
     requests — the register-before-retire path) — each replayed with
     ``overlap=`` off AND on, asserting bit-identical outputs, zero
     block leaks, and exact completion;
  3. strict FCFS under preemption pressure: fresh admissions leave the
     queue in uid order — the head is never overtaken (resumes are
     replica-local and exempt by design);
  4. overlap bit-identity across attention/recurrent/hybrid archs with
     mixed greedy + seeded stochastic sampling (the RNG-stream
     contract is WHY dispatch-ahead is legal);
  5. BlockAllocator property tests (tests/_hypothesis_compat.py):
     random op interleavings always satisfy ``check_invariant`` and
     owned ⊎ LRU ⊎ free partitions every non-null block;
  6. telemetry clocks: ReplicaSet busy/wait clocks survive wall-clock
     jumps (monotonic stamps), the union of the paged backend's
     ``scheduler.fetch`` spans stays inside the run's wall time under
     overlap;
  7. a multi-device subprocess run of the overlap identity (mesh-
     sharded pools change WHERE tensors live, never WHAT comes out).
"""

import collections
import itertools
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.launch.engine import (Engine, EngineConfig, ReplicaSet,
                                 SamplingParams)
from repro.launch.engine import replica as replica_mod
from repro.models import paged_kv
from repro.models.model import Model

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:                  # `benchmarks` lives at the
    sys.path.insert(0, _ROOT)              # repo root, not under src/
from benchmarks import traffic  # noqa: E402


def _smoke(arch="olmo_1b"):
    cfg = get_config(arch).smoke()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


# -- 1. traffic generators ------------------------------------------------


@pytest.mark.parametrize("kind", ["poisson", "bursty", "ramp"])
def test_trace_deterministic_and_ordered(kind):
    """A trace is a pure function of its seed: two builds are equal
    field-for-field, a different seed diverges, and arrivals are
    nondecreasing (the replay loop pops the head only)."""
    cfg, _, _ = _smoke()
    mk = lambda s: traffic.make_open_loop_trace(  # noqa: E731
        cfg, kind=kind, n_requests=40, rate=100.0, seed=s)
    a, b, c = mk(7), mk(7), mk(8)
    assert [(i.arrival, i.prompt, i.max_new) for i in a] \
        == [(i.arrival, i.prompt, i.max_new) for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in c]
    arr = [i.arrival for i in a]
    assert arr == sorted(arr) and arr[0] >= 0.0
    assert all(0 <= t < cfg.vocab_size for i in a for t in i.prompt)


def test_trace_kinds_shape():
    """Kind-specific structure: bursty arrivals cluster (many gaps are
    the intra-burst spread), ramp inter-arrival gaps shrink over the
    trace, and an unknown kind raises."""
    cfg, _, _ = _smoke()
    rng = np.random.default_rng(3)
    burst = traffic.bursty_arrivals(64, 200.0, rng, burst=8)
    gaps = np.diff(burst)
    assert (gaps <= 2e-4).sum() >= 48     # 7 of each 8-burst are spread
    ramp = traffic.ramp_arrivals(400, 200.0, np.random.default_rng(3))
    g = np.diff(ramp)
    assert g[:100].mean() > g[-100:].mean()   # rate ramps UP
    with pytest.raises(ValueError):
        traffic.make_open_loop_trace(cfg, kind="lumpy", n_requests=4,
                                     rate=1.0, seed=0)


class _FakeHandle:
    def __init__(self, t_first, gaps, n_tokens):
        self.t_submit = 0.0
        self.t_first_token = t_first
        self.t_tokens = ([t_first + sum(gaps[:i]) for i in
                          range(len(gaps) + 1)] if t_first is not None
                         else [])
        self.token_ids = list(range(n_tokens))


def test_slo_report_scoring():
    """Goodput counts tokens ONLY from requests meeting both budgets;
    TTFT-only requests (a single token — TPOT undefined) pass on TTFT
    alone; an unfinished request (no first token) never meets."""
    cfg, _, _ = _smoke()
    trace = traffic.make_open_loop_trace(cfg, kind="poisson",
                                         n_requests=4, rate=1.0, seed=0)
    traffic.annotate_slos(trace, ttft_s=0.1, tpot_s=0.01)
    handles = [
        _FakeHandle(0.05, [0.005] * 9, 10),    # meets both
        _FakeHandle(0.05, [0.5] * 9, 10),      # blows TPOT
        _FakeHandle(10.0, [0.005] * 9, 10),    # blows TTFT (scale <= 2)
        _FakeHandle(None, [], 0),              # never started
    ]
    rep = traffic.slo_report(handles, trace, wall_s=2.0)
    assert rep["slo_met"] == 1 and rep["count"] == 4
    assert rep["goodput_tok_s"] == pytest.approx(10 / 2.0)
    assert rep["goodput_frac"] == pytest.approx(10 / 30)
    assert rep["ttft"]["count"] == 3 and rep["tpot"]["count"] == 3


# -- 2. chaos traces (step-indexed injection) -----------------------------


def _drive_steps(eng, work, max_steps=20_000):
    """Open-loop replay on the STEP clock: ``work`` is a list of
    (arrival_step, prompt, SamplingParams); request i is submitted the
    moment the step counter reaches its arrival step, whether or not
    the engine has capacity — arrivals never wait for completions.
    Deterministic: no wall clock anywhere."""
    pending = collections.deque(work)
    handles = []
    step = 0
    while pending or eng.has_work:
        while pending and pending[0][0] <= step:
            _, prompt, sp = pending.popleft()
            handles.append(eng.add_request(prompt, sp))
        if eng.has_work:
            eng.step()
        step += 1
        assert step < max_steps, "chaos trace stalled"
    return handles


def _assert_clean(eng, handles, work):
    st = eng.stats()
    assert st["blocks_used"] == 0, st
    be = eng.backend
    assert be.alloc.free_count == be.layout.usable_blocks
    assert np.all(be.lengths == 0)
    for h, (_, _, sp) in zip(handles, work):
        assert h.finished
        assert len(h.token_ids) <= sp.max_tokens


def _chaos_outputs(model, params, work, *, overlap, **cfg_kw):
    base = dict(backend="paged", num_slots=3, block_size=4,
                num_blocks=17, max_len=32, overlap=overlap)
    base.update(cfg_kw)
    eng = Engine(model, params, EngineConfig(**base))
    handles = _drive_steps(eng, work)
    _assert_clean(eng, handles, work)
    return [h.token_ids for h in handles], eng.stats()


def _both_overlaps(model, params, work, **cfg_kw):
    """Replay one chaos trace with overlap off and on: outputs must be
    bit-identical (RNG-stream contract) and both runs leak-free."""
    toks_off, _ = _chaos_outputs(model, params, work, overlap=False,
                                 **cfg_kw)
    toks_on, st = _chaos_outputs(model, params, work, overlap=True,
                                 **cfg_kw)
    assert toks_on == toks_off
    return toks_on, st


def test_chaos_pool_exhaustion_bursts(rng):
    """Bursts wider than the free pool at admission boundaries: 6
    requests land on one step into a 16-usable-block pool that can hold
    ~2 of them, repeatedly — optimistic admission + LIFO preemption
    churn. Zero leaks, exact completion, overlap-identical."""
    cfg, model, params = _smoke()
    work = []
    for b in range(4):                     # 4 bursts of 6
        for _ in range(6):
            plen = int(rng.integers(6, 14))
            prompt = list(map(int, rng.integers(0, cfg.vocab_size, plen)))
            work.append((b * 40, prompt,
                         SamplingParams(max_tokens=int(
                             rng.integers(4, 14)))))
    _, st = _both_overlaps(model, params, work, num_blocks=13)
    assert st["preemptions"] > 0           # the burst actually bit


def test_chaos_all_max_len_storm(rng):
    """Every request wants the whole lane: prompt + output pinned at
    the max_len boundary (the growth path crosses a block boundary on
    the final token). Nothing leaks, nobody is starved."""
    cfg, model, params = _smoke()
    work = []
    for i in range(8):
        plen = 16
        prompt = list(map(int, rng.integers(0, cfg.vocab_size, plen)))
        work.append((0, prompt, SamplingParams(max_tokens=32 - plen - 1)))
    _both_overlaps(model, params, work, num_slots=2, num_blocks=17)


def test_chaos_cancel_mid_prefill_flood(rng):
    """Cancel-like floods: max_tokens=1 requests retire INSIDE the
    admission step (the register-before-retire path), and stop-token
    requests retire on their first sampled token — interleaved with
    long-running requests so retirement constantly races admission and,
    under overlap, the in-flight harvest."""
    cfg, model, params = _smoke()
    # a stop id that greedy decode actually emits: the oracle's first
    # token for a probe prompt (cheap: one engine call)
    probe = list(map(int, rng.integers(0, cfg.vocab_size, 6)))
    eng = Engine(model, params, EngineConfig(
        backend="paged", num_slots=1, block_size=4, num_blocks=17,
        max_len=32))
    stop_id = eng.generate([probe], SamplingParams(max_tokens=1))[0][0]
    del eng
    work = []
    for i in range(18):
        if i % 3 == 2:                     # a long request to race with
            plen = int(rng.integers(8, 12))
            sp = SamplingParams(max_tokens=12)
        elif i % 3 == 1:                   # instant stop-token retire
            plen = 6
            sp = SamplingParams(max_tokens=12,
                                stop_token_ids=(stop_id,))
        else:                              # retire inside admission
            plen = int(rng.integers(4, 9))
            sp = SamplingParams(max_tokens=1)
        prompt = probe if plen == 6 else list(
            map(int, rng.integers(0, cfg.vocab_size, plen)))
        work.append((i // 3, prompt, sp))
    toks, _ = _both_overlaps(model, params, work)
    assert any(t == [] for t in toks)      # stop floods emitted nothing


def test_chaos_bursty_trace_through_generator(rng):
    """End to end with the real generator: a seeded bursty trace's
    arrivals quantized onto the step clock (one step per ms of trace
    time) through a tiny pool — the bench's trace shape under the
    chaos harness, with stochastic sampling in the mix."""
    cfg, model, params = _smoke()
    items = traffic.make_open_loop_trace(
        cfg, kind="bursty", n_requests=16, rate=400.0, seed=11,
        prompt_lens=(4, 6, 10), max_new_choices=(2, 5, 9),
        max_new_p=(0.3, 0.4, 0.3), burst=5)
    work = []
    for k, it in enumerate(items):
        sp = SamplingParams(max_tokens=it.max_new) if k % 2 == 0 else \
            SamplingParams(max_tokens=it.max_new, temperature=0.8,
                           top_k=7, top_p=0.9, seed=k)
        work.append((int(it.arrival * 1000), it.prompt, sp))
    _both_overlaps(model, params, work)


# -- 3. strict FCFS: the head is never overtaken --------------------------


def test_fcfs_head_never_overtaken(rng):
    """Fresh admissions must leave the queue in uid order even under
    preemption churn: spy on ``_place_batch`` and assert the fresh
    (never-preempted, zero-sampled) admission sequence is sorted.
    Resumed victims re-enter at the FRONT of the queue by design —
    they are not fresh admissions and are exempt."""
    cfg, model, params = _smoke()
    eng = Engine(model, params, EngineConfig(
        backend="paged", num_slots=3, block_size=4, num_blocks=13,
        max_len=32, overlap=True))
    be = eng.backend
    fresh_order = []
    orig = be._place_batch

    def spy(run, outs):
        for req, m, cached, S in run:
            if req.num_preemptions == 0 and req._n_sampled == 0:
                fresh_order.append(req.uid)
        return orig(run, outs)

    be._place_batch = spy
    work = []
    for i in range(20):
        plen = int(rng.integers(4, 14))
        prompt = list(map(int, rng.integers(0, cfg.vocab_size, plen)))
        work.append((i // 4, prompt,
                     SamplingParams(max_tokens=int(rng.integers(4, 14)))))
    handles = _drive_steps(eng, work)
    _assert_clean(eng, handles, work)
    assert eng.stats()["preemptions"] > 0
    assert fresh_order == sorted(fresh_order)
    assert len(fresh_order) == len(work)


# -- 4. overlap bit-identity across architectures -------------------------


@pytest.mark.parametrize("arch", ["olmo_1b", "recurrentgemma_2b",
                                  "xlstm_1_3b"])
def test_overlap_identity_across_archs(rng, arch):
    """The acceptance identity: ``overlap=True`` changes WHEN tokens
    are fetched, never WHICH tokens come out — per arch family
    (attention / recurrent-hybrid / xLSTM), ragged prompts, mixed
    greedy + seeded stochastic sampling, pool small enough to preempt."""
    cfg, model, params = _smoke(arch)
    work = []
    for i, plen in enumerate((5, 9, 3, 12, 7, 6)):
        prompt = list(map(int, rng.integers(0, cfg.vocab_size, plen)))
        sp = SamplingParams(max_tokens=6 + i % 4) if i % 2 == 0 else \
            SamplingParams(max_tokens=6 + i % 4, temperature=0.7,
                           top_k=9, top_p=0.95, seed=100 + i)
        work.append((i // 2, prompt, sp))
    _both_overlaps(model, params, work, num_slots=2, num_blocks=17)


def test_overlap_config_validation():
    """The toggle is paged-only and incompatible with speculation (the
    verify window already amortizes fetches; overlapping it would
    double-buffer the wrong boundary)."""
    _, model, params = _smoke()
    with pytest.raises(ValueError, match="paged"):
        Engine(model, params, EngineConfig(backend="static",
                                           overlap=True))
    with pytest.raises(ValueError, match="speculative"):
        Engine(model, params, EngineConfig(
            backend="paged", num_slots=2, block_size=4, num_blocks=17,
            max_len=32, overlap=True, spec_tokens=2))


# -- 5. BlockAllocator property tests --------------------------------------


def _decode_ops(codes, alloc, num_blocks):
    """Interpret an integer stream as allocator ops against a live
    multiset mirror; every mutation is followed by check_invariant()
    inside the allocator itself. Returns the mirror."""
    live = []                              # our references, multiset
    for code in codes:
        op, arg = code % 6, code // 6
        if op == 0:
            n = arg % 3 + 1
            if alloc.can_alloc(n):
                live += alloc.alloc(n)
        elif op == 1 and live:
            alloc.free([live.pop(arg % len(live))])
        elif op == 2 and live:             # extra ref on a live block
            b = live[arg % len(live)]
            alloc.share(b)
            live.append(b)
        elif op == 3 and live:             # index it (parks in LRU later)
            alloc.register(live[arg % len(live)])
        elif op == 4 and alloc.lru_count:  # prefix-cache re-hit: revive
            b = list(alloc._lru)[arg % alloc.lru_count]
            alloc.share(b)
            live.append(b)
        elif op == 5:                      # read-only probe
            assert isinstance(
                alloc.must_cow(1 + arg % (num_blocks - 1)), bool)
    return live


@given(st.lists(st.integers(0, 10_000), min_size=0, max_size=80),
       st.integers(4, 14))
@settings(max_examples=25, deadline=None)
def test_allocator_random_interleavings(codes, num_blocks):
    """Any interleaving of alloc/free/share/register/revive keeps the
    invariant (owned ⊎ LRU ⊎ free partitions blocks 1..N-1, cached ⊆
    resident, refcounts >= 1), the allocator's refcounts agree with an
    independent multiset mirror, and releasing every mirror reference
    returns the pool to fully-free."""
    layout = paged_kv.PagedLayout(num_slots=2, num_blocks=num_blocks,
                                  block_size=4, max_len=64)
    evicted = []
    alloc = paged_kv.BlockAllocator(layout, watermark=1,
                                    on_evict=evicted.append)
    live = _decode_ops(codes, alloc, num_blocks)
    owned = set(alloc._refs)
    lru = set(alloc._lru)
    free = set(alloc._free)
    assert not (owned & lru) and not (owned & free) and not (lru & free)
    assert owned | lru | free == set(range(1, num_blocks))
    assert alloc._refs == dict(collections.Counter(live))
    assert len(set(evicted) & owned) == len(
        set(evicted) & owned & set(live))  # evictions only recycle
    for b in list(live):
        alloc.free([b])
    assert alloc.used_count == 0
    assert alloc.free_count == num_blocks - 1


@given(st.lists(st.integers(0, 10_000), min_size=0, max_size=40),
       st.integers(4, 10))
@settings(max_examples=15, deadline=None)
def test_allocator_misuse_always_raises(codes, num_blocks):
    """After ANY legal op prefix: double-free, freeing the null block,
    sharing a free block, and registering a non-live block all raise —
    and the failed call leaves the invariant intact."""
    layout = paged_kv.PagedLayout(num_slots=2, num_blocks=num_blocks,
                                  block_size=4, max_len=64)
    alloc = paged_kv.BlockAllocator(layout)
    live = _decode_ops(codes, alloc, num_blocks)
    with pytest.raises(ValueError):
        alloc.free([paged_kv.NULL_BLOCK])
    if alloc._free:
        b = alloc._free[0]
        with pytest.raises(ValueError):
            alloc.share(b)
        with pytest.raises(ValueError):
            alloc.register(b)
        with pytest.raises(ValueError):
            alloc.free([b])
    alloc.check_invariant()
    for b in list(live):
        alloc.free([b])
    assert alloc.used_count == 0


# -- 6. telemetry clocks ---------------------------------------------------


def test_replica_busy_clock_survives_wall_jump(rng, monkeypatch):
    """Regression for the busy-clock skew: stamps must come from the
    monotonic clock, so a wall clock jumping BACKWARD mid-run (NTP
    slew) cannot produce negative busy/wait intervals. time.time is
    patched to run backwards; telemetry must not notice."""
    cfg, model, params = _smoke()
    # steps backward forever: every caller of the process-wide
    # time.time (jax's own included) can read it without running it dry
    jumpy = itertools.count(1e9, -7.3)
    monkeypatch.setattr(replica_mod.time, "time",
                        lambda: float(next(jumpy)))
    rset = ReplicaSet(model, params, EngineConfig(
        backend="paged", num_slots=2, block_size=4, num_blocks=17,
        max_len=32), dp=2)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 8, 6, 7)]
    rset.generate(prompts, SamplingParams(max_tokens=4))
    st = rset.stats()
    assert all(b >= 0.0 for b in st["busy_s"])
    assert sum(st["busy_s"]) > 0.0
    assert st["queue_wait_s_mean"] >= 0.0
    assert all(w >= 0.0 for w in rset.wait_wall)
    assert st["latency"]["ttft"]["count"] == len(prompts)
    assert st["latency"]["ttft"]["p50_s"] >= 0.0


def test_device_clock_interval_union_under_overlap(rng):
    """The host's blocking waits on the device are the
    ``scheduler.fetch`` spans: with overlap ON their union is positive
    and lies within the run's wall time, each span inside it."""
    import time as _time

    from repro.launch.engine import tracing

    cfg, model, params = _smoke()
    eng = Engine(model, params, EngineConfig(
        backend="paged", num_slots=2, block_size=4, num_blocks=33,
        max_len=32, overlap=True))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 8, 6)]
    eng.generate(prompts, SamplingParams(max_tokens=8))  # warm compiles
    eng.backend.reset_telemetry()
    with tracing.record() as spans:
        t0 = _time.monotonic()
        eng.generate(prompts, SamplingParams(max_tokens=8))
        t1 = _time.monotonic()
    fetch = sorted((s.start, s.end) for s in spans
                   if s.name == "scheduler.fetch")
    union, end = 0.0, t0
    for s, e in fetch:
        assert t0 <= s <= e <= t1
        union += max(0.0, e - max(s, end))
        end = max(end, e)
    st = eng.stats()
    assert st["overlap"] is True
    assert 0.0 < union <= t1 - t0
    assert st["latency"]["tpot"]["count"] == len(prompts)


# -- 7. multi-device overlap identity (subprocess) -------------------------

_PRELUDE = """
import jax, numpy as np
from repro.configs import get_config
from repro.launch.engine import Engine, EngineConfig, SamplingParams
from repro.launch.mesh import make_mesh
from repro.models.model import Model

assert len(jax.devices()) == 8
MESH = make_mesh((4, 2), ("data", "model"))
"""


def _run(body: str):
    # dedent the body BEFORE prepending the unindented prelude (see
    # test_sharded_serve.py); "body ran" guards against a silently
    # unexecuted body.
    code = _PRELUDE + textwrap.dedent(body)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    assert "body ran" in proc.stdout, f"test body never executed:\n{code}"
    return proc.stdout


def test_overlap_identity_sharded_subprocess():
    """Overlap identity on a (4 data x 2 model) mesh: the fused overlap
    step runs against the head-sharded pool, and its outputs must match
    the no-overlap mesh engine token for token (greedy and seeded
    stochastic), with zero leaks on both."""
    _run("""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 9, 3, 12, 7)]
    sps = [SamplingParams(max_tokens=7) if i % 2 == 0 else
           SamplingParams(max_tokens=7, temperature=0.8, top_k=5,
                          top_p=0.9, seed=40 + i)
           for i in range(len(prompts))]
    outs = {}
    for overlap in (False, True):
        eng = Engine(model, params, EngineConfig(
            backend="paged", num_slots=2, block_size=4, num_blocks=17,
            max_len=32, mesh=MESH, overlap=overlap))
        handles = [eng.add_request(p, sp) for p, sp in zip(prompts, sps)]
        while eng.has_work:
            eng.step()
        assert eng.stats()["blocks_used"] == 0
        outs[overlap] = [h.token_ids for h in handles]
        del eng
    assert outs[True] == outs[False]
    print("body ran")
    """)
