"""The engine's host spans (launch/engine/tracing.py): off by default
and free, nested per thread while recording, and placed at the paged
scheduler's boundaries with the stamps and counters that go with them."""

import threading

import jax
import pytest

from repro.configs import get_config
from repro.launch.engine import Engine, EngineConfig, SamplingParams, tracing
from repro.models.model import Model


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _engine(smoke, **kw):
    _, model, params = smoke
    ec = dict(backend="paged", num_slots=2, block_size=4, num_blocks=33,
              max_len=32, overlap=True)
    ec.update(kw)
    return Engine(model, params, EngineConfig(**ec))


def _prompts(smoke, rng, lengths=(5, 8, 6, 11)):
    cfg = smoke[0]
    return [list(map(int, rng.integers(0, cfg.vocab_size, L)))
            for L in lengths]


def test_off_by_default_returns_the_shared_noop():
    a, b = tracing.span("x"), tracing.span("y")
    assert a is b
    with a as sp:
        assert sp is None
    with tracing.record() as spans:
        pass
    assert spans == []
    assert tracing.span("z") is a          # off again after the record


def test_nesting_parents_and_threads():
    seen = {}

    def worker():
        with tracing.span("w.outer") as o:
            with tracing.span("w.inner") as i:
                seen["w"] = (o, i)

    with tracing.record() as spans:
        with tracing.span("outer") as outer:
            outer.set(k=1)
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            with tracing.span("inner") as inner:
                pass
    assert not t.is_alive()
    assert {s.name for s in spans} == {"outer", "inner", "w.outer",
                                       "w.inner"}
    assert outer.parent is None and inner.parent is outer
    wo, wi = seen["w"]
    # the worker's stack is its own: its outer span has no parent even
    # though it ran inside the main thread's "outer"
    assert wo.parent is None and wi.parent is wo
    assert wo.thread != outer.thread == inner.thread
    assert outer.attrs == {"k": 1}
    for s in spans:
        assert s.start <= s.end
    assert outer.start <= inner.start <= inner.end <= outer.end
    with pytest.raises(RuntimeError):
        with tracing.record():
            with tracing.record():
                pass


def _ancestor(span, name):
    p = span.parent
    while p is not None and p.name != name:
        p = p.parent
    return p


def test_overlap_generate_gives_each_step_its_fetch(smoke, rng):
    eng = _engine(smoke)
    prompts = _prompts(smoke, rng)
    with tracing.record() as spans:
        eng.generate(prompts, SamplingParams(max_tokens=6))
    steps = [s for s in spans if s.name == "scheduler.step"]
    fetches = [s for s in spans if s.name == "scheduler.fetch"]
    assert steps and fetches
    assert {s.attrs["what"] for s in fetches} == {"tokens",
                                                  "prefill_logits"}
    harvest = {id(_ancestor(f, "scheduler.step")) for f in fetches
               if f.attrs["what"] == "tokens"}
    # every step after the first harvests the decode dispatched before it
    for s in steps[1:]:
        assert id(s) in harvest, s
    for f in fetches:
        assert _ancestor(f, "scheduler.step") is not None
    disp = [s for s in spans if s.name == "scheduler.dispatch"]
    assert sum(d.attrs["first"] for d in disp) == 1      # one greedy key
    assert any(d.attrs["followup"] for d in disp)
    pre = [s for s in spans if s.name == "scheduler.prefill"]
    assert pre and all(p.parent.name == "scheduler.admit" for p in pre)
    assert all(p.attrs["computed"] >= p.attrs["tokens"] for p in pre)


def test_admission_spans_carry_the_admitted_uids(smoke, rng):
    eng = _engine(smoke)
    prompts = _prompts(smoke, rng)
    with tracing.record() as spans:
        handles = [eng.add_request(p, SamplingParams(max_tokens=4))
                   for p in prompts]
        eng.drain()
    admitted = [u for s in spans if s.name == "scheduler.admit"
                for u in s.attrs["uids"]]
    assert sorted(admitted) == sorted(h.uid for h in handles)
    assert len(admitted) == len(set(admitted))       # nothing preempted


@pytest.mark.parametrize("overlap", [True, False])
def test_request_stamps_are_ordered(smoke, rng, overlap):
    # a pool too small for both slots' growth forces preemptions, and a
    # sampled half takes the first-token round trip
    eng = _engine(smoke, num_blocks=9, overlap=overlap)
    prompts = _prompts(smoke, rng, (9, 10, 7, 12, 6, 8))
    with tracing.record() as spans:
        handles = [eng.add_request(p, SamplingParams(
            max_tokens=12, temperature=0.8 * (k % 2), seed=k))
            for k, p in enumerate(prompts)]
        eng.drain()
    assert eng.stats()["preemptions"] > 0
    assert any(h.num_preemptions for h in handles)
    for h in handles:
        assert h.t_submit <= h.t_admitted <= h.t_first_token
    grows = [s for s in spans if s.name == "scheduler.grow"]
    preempted = [u for s in grows for u in s.attrs["preempted"]]
    assert sorted(set(preempted)) == sorted(
        h.uid for h in handles if h.num_preemptions)
    assert len(preempted) == eng.stats()["preemptions"]
    assert any(s.attrs.get("what") == "first_token" for s in spans
               if s.name == "scheduler.fetch")


def test_prefill_computed_tokens_count_the_padding(smoke, rng):
    eng = _engine(smoke, num_slots=4, max_prefill_batch=4)
    prompts = _prompts(smoke, rng, (5, 6, 7))
    eng.generate(prompts, SamplingParams(max_tokens=2))
    st = eng.stats()
    assert st["prefill_tokens"] == 18
    # one batch of 3 rows padded to 4, each to the 8-token bucket
    assert st["prefill_computed_tokens"] == 4 * 8
    assert st["prefill_computed_tokens"] >= st["prefill_tokens"]
    eng.backend.reset_telemetry()
    assert eng.stats()["prefill_computed_tokens"] == 0
