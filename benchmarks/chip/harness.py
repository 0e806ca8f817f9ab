"""One run of one cell: build, warm up, measure a window, check.

A cell is a configuration under a traffic mix. Everything that belongs
to one of them is found by name:

* ``configs/<config>.json``: the published configuration as it is run,
  the program's name for it, and the reference module beside it;
* ``traffic/<mix>.json``: the loop, the lengths, the pool and slots;
* ``limits/<workload>.json``: the limit of each number compared;
* ``metrics/<metric>.py``: one reader per metric, ``read(run)``.

The program is used only through ``repro.configs``, ``repro.models``
and ``repro.launch.engine``. The run takes the engine's handles (token
stamps), its scheduler counters and, with ``trace``, the profiler's
trace of the window; the arithmetic on them lives here.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import resource
import time
from pathlib import Path

import numpy as np

from chip import check, devtrace, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
COUNTERS = ("steps", "slot_steps", "live_token_steps", "block_token_steps",
            "preemptions", "prefill_calls", "prefill_reqs", "prefill_tokens",
            "prefix_hits")


class Unrunnable(RuntimeError):
    """The run cannot produce a result (no chip, bad cell)."""


def load_json(*parts) -> dict:
    with open(HERE.joinpath(*parts)) as f:
        return json.load(f)


def load_cell(workload: str, bench: dict | None = None):
    """(cell entry, configuration file, mix, limits) for a workload."""
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise Unrunnable(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = load_json("configs", f"{cell['config']}.json")
    return (cell, cfg, traffic.load_mix(cell["traffic"]),
            load_json("limits", f"{workload}.json"))


def wanted_metrics(bench: dict, workload: str, per_layer: bool):
    """[(name, unit)] of the metrics a cell reports in this kind of run."""
    out = []
    for m in bench["per_layer" if per_layer else "end_to_end"]:
        if workload in m.get("workloads", [workload]):
            out.append((m["name"], m["unit"]))
    return out


def reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chip_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(cfg: dict):
    return importlib.import_module(f"chip.configs.{cfg['reference']}")


def enable_compile_cache():
    """JAX's persistent compilation cache in one fixed directory inside
    the checkout (the path is part of a program's cache key, so a moved
    directory would never hit), every program kept."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def seed_words(seed: int, stream: int) -> int:
    """A 32-bit seed for ``stream`` derived from the run's seed, which may
    be any whole number."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------


def build_model(cfg: dict, dm):
    """The program's model for ``cfg``; refuses one whose sizes are not
    the configuration's."""
    from repro.configs import get_config
    from repro.models.model import Model

    prog = cfg["program"]
    mc = dataclasses.replace(get_config(prog["config"]),
                             **prog.get("overrides", {}))
    have = dict(d_model=mc.d_model, n_layers=mc.n_layers, n_heads=mc.n_heads,
                n_kv_heads=mc.n_kv_heads, head_dim=mc.head_dim, d_ff=mc.d_ff,
                vocab_size=mc.vocab_size, rope_theta=mc.rope_theta,
                tied=mc.tie_embeddings, dtype=mc.dtype,
                norm={"rmsnorm": "rmsnorm",
                      "nonparametric": "layernorm_noaffine"}.get(mc.norm))
    want = dict(d_model=dm.d_model, n_layers=dm.n_layers, n_heads=dm.n_heads,
                n_kv_heads=dm.n_kv_heads, head_dim=dm.head_dim, d_ff=dm.d_ff,
                vocab_size=dm.vocab_size, rope_theta=dm.rope_theta,
                tied=dm.tied, dtype=cfg["dtype"], norm=dm.norm)
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad or mc.block_pattern != ("attn",) or not mc.gated_mlp \
            or mc.activation != "silu" or mc.rope_style != "rope":
        raise Unrunnable(f"program config {prog['config']} departs from "
                         f"{cfg['name']}: {bad}")
    return Model(mc)


def program_params(w: dict, dm, model):
    """The published-form weights ``w`` in the program's parameter layout
    (the big leaves are the same arrays). Refuses a layout that is not
    the one this mapping knows."""
    import jax

    norm = (lambda s: {"scale": s}) if dm.norm == "rmsnorm" \
        else (lambda s: {})
    layer = {"ln1": norm(w.get("attn_norm")), "ln2": norm(w.get("mlp_norm")),
             "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "mlp": {k: w[k] for k in ("w_up", "w_gate", "w_down")}}
    p = {"embed": w["embed"], "groups": {"g0": {"p0": layer}},
         "final_norm": norm(w.get("final_norm"))}
    if not dm.tied:
        p["lm_head"] = w["head"]
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), p)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise Unrunnable("the program's parameter layout changed; "
                         "program_params no longer maps onto it")
    return p


def make_engine(model, params, mix: dict):
    from repro.launch.engine import Engine, EngineConfig

    ec = EngineConfig(backend="paged", num_slots=mix["slots"],
                      block_size=mix["block_size"],
                      num_blocks=mix["num_blocks"], max_len=mix["max_len"],
                      max_prefill_batch=mix["max_prefill_batch"],
                      kv_dtype=mix["kv_dtype"], overlap=True,
                      prefix_cache=mix.get("prefix_cache", True))
    return Engine(model, params, ec)


def sampling(mix: dict, r: traffic.Req):
    from repro.launch.engine import SamplingParams

    if r.greedy:
        return SamplingParams(max_tokens=r.max_tokens, seed=r.seed)
    return SamplingParams(max_tokens=r.max_tokens,
                          temperature=mix["temperature"],
                          top_p=mix["top_p"], seed=r.seed)


def counters(engine) -> dict:
    """The scheduler's counters, and the requests queued for a slot."""
    b = engine.backend
    out = {k: int(getattr(b, k)) for k in COUNTERS}
    out["queued"] = len(b.waiting)
    return out


def warm_up(engine, mix: dict, lengths, rng):
    """Run every prompt length in ``lengths`` at every prefill batch width
    the engine can form (powers of two up to ``max_prefill_batch``), with
    greedy-only and mixed decode steps, so that every program the window
    can call is compiled (or loaded from the cache) before it opens."""
    widths = sorted({min(1 << i, mix["max_prefill_batch"])
                     for i in range(mix["max_prefill_batch"].bit_length())})
    first = True
    for S in lengths:
        for nb in widths:
            for j in range(nb):
                r = traffic.Req(
                    prompt=rng.integers(0, 100, size=S).tolist(),
                    max_tokens=max(1, min(3, mix["max_len"] - S)),
                    greedy=first or j > 0, seed=int(rng.integers(1 << 30)))
                engine.add_request(r.prompt, sampling(mix, r))
            first = False
            engine.drain()


def warm_lengths(lo: int, hi: int) -> list[int]:
    """``lo``, ``hi`` and every power of two between them: one prompt in
    each power-of-two prefill bucket the traffic can reach."""
    out, p = {lo, hi}, 1
    while p < hi:
        if p > lo:
            out.add(p)
        p *= 2
    return sorted(out)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    loop: str                    # "closed" | "open"
    mix: dict
    dm: object                   # configs.<reference>.Dims
    peak: dict | None
    setup_s: float
    t0: float                    # window, on the monotonic clock
    t1: float
    handles: list                # every RequestHandle live in the window
    arrivals: list               # open loop: (due, submitted, handle)
    counters: dict               # scheduler counters over the window
    prefilled: list              # prompt lengths prefilled in the window
    trace: devtrace.Trace | None = None
    # per window step, a ``Step``
    steps: list = dataclasses.field(default_factory=list)
    gc_pauses: list = dataclasses.field(default_factory=list)  # seconds


class Step(tuple):
    """One engine step of the window, as the host saw it: its start and
    end on the monotonic clock, the prompt tokens prefilled so far, and
    at its end the process's CPU seconds (all threads), involuntary
    context switches and major page faults so far. A step far slower
    than the others shows by these whether the host computed, was put
    off the CPU, waited on the disk, or waited on the device."""

    @classmethod
    def now(cls, start: float, prefilled: int) -> "Step":
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return cls((start, time.monotonic(), prefilled,
                    ru.ru_utime + ru.ru_stime, ru.ru_nivcsw, ru.ru_majflt))


def _exhaustible(reqs):
    yield from reqs
    raise Unrunnable("the closed loop used up its mix's 'requests'; "
                     "the mix needs more for this window")


def _phase_open(engine, reqs, start, span, submit, note, steps=None):
    """Serve ``reqs`` (due at ``start + r.due``) until ``start + span``.
    Appends each step's ``Step`` to ``steps``. Returns the time the phase
    closed."""
    end, i = start + span, 0
    steps = [] if steps is None else steps
    while True:
        now = time.monotonic()
        while i < len(reqs) and start + reqs[i].due <= now:
            submit(reqs[i], start + reqs[i].due)
            i += 1
        if now >= end:
            return now
        if engine.has_work:
            with note("engine.step"):
                engine.step()
            steps.append(Step.now(now, engine.backend.prefill_tokens))
        else:
            nxt = start + reqs[i].due if i < len(reqs) else end
            with note("wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - now))


def _phase_closed(engine, seq, span, submit, note, steps):
    """Step the full engine for ``span`` seconds, replacing every request
    that finishes with the next of ``seq``. Appends each step's ``Step``
    to ``steps``. Returns the close time."""
    now = time.monotonic()
    end = now + span
    while True:
        with note("engine.step"):
            outs = engine.step()
        steps.append(Step.now(now, engine.backend.prefill_tokens))
        for o in outs:
            if o.finished:
                submit(next(seq), None)
        now = time.monotonic()
        if now >= end:
            return now


def run(cell: dict, cfg: dict, mix: dict, limits: dict, *, seed: int,
        seconds: float, trace: bool, t_start: float, peak: dict | None,
        control: bool = False):
    """One run; returns the result dict that ``run.py`` prints. With
    ``control`` the int8 reference is also judged in the program's
    place (``control_correct``, ``control_checks``; see ``check``)."""
    import jax

    ref = reference(cfg)
    dm = ref.dims(cfg)
    model = build_model(cfg, dm)
    stamps = {"start": t_start, "init": time.monotonic()}
    key = jax.random.key(seed_words(seed, 1), impl="rbg")
    w = jax.jit(lambda k: ref.make_weights(k, dm))(key)
    jax.block_until_ready(w)
    stamps["weights"] = time.monotonic()
    params = program_params(w, dm, model)
    engine = make_engine(model, params, mix)
    stamps["engine"] = time.monotonic()
    rng = np.random.default_rng(seed_words(seed, 2))

    loop = mix["loop"]
    if loop == "closed":
        initial, seq = traffic.closed_loop(mix, seed, dm.token_vocab)
        seq = _exhaustible(seq)
        lens = [len(r.prompt) for r in initial] + [mix["prompt"]["min"],
                                                   mix["prompt"]["max"]]
    else:
        lead, window = traffic.open_loop(mix, seed, dm.token_vocab, seconds)
        lens = [mix["prompt"]["min"], mix["prompt"]["max"]]
    warm_up(engine, mix, warm_lengths(min(lens), max(lens)), rng)
    stamps["warm_up"] = time.monotonic()

    log_reqs = []                # (Req, handle, due, submitted)

    def submit(r, due):
        h = engine.add_request(r.prompt, sampling(mix, r))
        log_reqs.append((r, h, due, time.monotonic()))

    nothing = contextlib.nullcontext
    note = jax.profiler.TraceAnnotation if trace else (lambda _n: nothing())
    if loop == "closed":
        for r in initial:
            submit(r, None)
        while any(h.t_first_token is None for _, h, _, _ in log_reqs):
            for o in engine.step():
                if o.finished:
                    submit(next(seq), None)
    else:
        _phase_open(engine, lead, time.monotonic(), mix["lead_in_s"],
                    submit, nothing)
    n_setup = len(log_reqs)
    stamps["fill" if loop == "closed" else "lead_in"] = time.monotonic()

    traces, steps, gc_pauses = [], [], []

    def on_gc(phase, _info):
        if phase == "start":
            gc_pauses.append(time.monotonic())
        else:
            gc_pauses[-1] = time.monotonic() - gc_pauses[-1]

    cm = devtrace.capture(traces) if trace else nothing()
    gc.callbacks.append(on_gc)
    try:
        with cm, note(devtrace.WINDOW_SPAN):
            c0 = counters(engine)
            t0 = time.monotonic()
            if loop == "closed":
                t1 = _phase_closed(engine, seq, seconds, submit, note, steps)
            else:
                t1 = _phase_open(engine, window, t0, seconds, submit, note,
                                 steps)
            c1 = counters(engine)
    finally:
        gc.callbacks.remove(on_gc)
    setup_s = t0 - t_start
    devices = sorted({d for leaf in jax.tree.leaves(engine.backend.pools)
                      for d in leaf.devices()}, key=lambda d: d.id)
    mem = [d.memory_stats() or {} for d in devices]

    handles = [h for _, h, _, _ in log_reqs
               if not (h.finished and h.t_tokens and h.t_tokens[-1] <= t0)]
    arrivals = [(due, sub, h) for _, h, due, sub in log_reqs[n_setup:]]
    prefilled = [len(h.prompt) for h in handles
                 if h.t_first_token is not None and t0 < h.t_first_token <= t1]
    rec = Run(loop=loop, mix=mix, dm=dm, peak=peak, setup_s=setup_s, t0=t0,
              t1=t1, handles=handles, arrivals=arrivals,
              counters={k: c1[k] - c0[k] for k in c1}, prefilled=prefilled,
              trace=traces[0] if traces else None, steps=steps,
              gc_pauses=gc_pauses)

    # the served outputs, then the program's state goes before the
    # reference runs
    short = sum(1 for _, h, _, _ in log_reqs
                if h.finished and len(h.token_ids) != h.sampling.max_tokens)
    done = [(h.prompt, list(h.token_ids)) for r, h, _, _ in log_reqs
            if h.finished and r.greedy and h.t_tokens[-1] <= t1]
    del engine, params, handles, log_reqs
    gc.collect()
    picked = check.sample(done, np.random.default_rng(seed_words(seed, 3)),
                          limits["min_tokens"], limits["max_requests"])
    stamps["window"] = t1
    g_prog, g_ctl, n_cmp = check.gaps(ref, dm, w, picked, control=control)
    stamps["reference"] = time.monotonic()
    correct, checks = check.verdict(g_prog, short, n_cmp, limits)
    out = dict(rec=rec, correct=correct, checks=checks, failed=short,
               mem=mem, stamps=stamps)
    if control:
        # the int8 reference in the program's place, at the same
        # positions, judged by the same comparison
        out["control_correct"], out["control_checks"] = check.verdict(
            g_ctl, 0, n_cmp, limits)
    return out
