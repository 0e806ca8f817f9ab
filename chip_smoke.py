"""Bring-up smoke of the serving path on a TPU: olmo-1b at its published
widths, random bf16 weights from ``--seed``, served through ``Engine``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # replicas and tensor parallelism

It runs in one process and starts none. It refuses to run without a TPU.
Every phase raises on failure, so the script exits non-zero unless all of
them pass; the last line of standard output is then
``{"ok": true, "device": {...}}``. Seconds printed on the way are smoke
timings with compilation included, not device metrics.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import sharding as shlib  # noqa: E402
from repro.launch.engine import (Engine, EngineConfig,  # noqa: E402
                                 ReplicaSet, SamplingParams)
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import paged_kv  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.models.transformer import RunCtx  # noqa: E402

# Pallas against the jnp references on the same bf16 weights and inputs.
# Both keep softmax statistics in f32 but sum in different orders, so an
# attention output may round to a neighbouring bf16 value, and the
# logits are a matmul of the bf16 hidden state, resolved to 2**-8 of
# its magnitude. The bound is two bf16 ulps of the largest logit:
# max |pallas - ref| over every logit compared <= 2**-7 * max |ref|.
# (At olmo-1b's widths with 2 and 4 layers the CPU interpreter differs
# from the references by 1/1500 of max |ref|; dropping attention output
# altogether moves the logits by 1/40 of it.)
LOGIT_RTOL = 2.0 ** -7

BLOCK_SIZE = 16
# The served run: a pool of 32768 tokens (128 KiB of bf16 K/V per token
# at olmo-1b's widths, about 4.3 GB), eight decode slots, prompts of
# roughly 100, 400 and 1800 tokens.
SERVE_CFG = EngineConfig(backend="paged", num_slots=8, block_size=BLOCK_SIZE,
                         num_blocks=32768 // BLOCK_SIZE + 1, max_len=2048,
                         overlap=True)
SERVE_LENGTHS = (100, 400, 1800)
SHORT_CFG = dataclasses.replace(SERVE_CFG, num_blocks=4096 // BLOCK_SIZE + 1,
                                max_len=256)


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def hbm() -> dict:
    """Device 0's memory counters, where the backend reports them."""
    st = jax.devices()[0].memory_stats() or {}
    return {k: st[k] for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in st}


def build(cfg, seed: int):
    """The model and random weights, initialised on the default device."""
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return model, params


# -- kernels against references --------------------------------------------


def trace_logits(model, params, ctx, prompts, feed, block_size=BLOCK_SIZE):
    """Prefill ``prompts`` (one right-padded batch) into a fresh paged
    pool, then decode the teacher-forced ``feed`` ((steps, B) token ids)
    one token per step. Returns the next-token logits of every step,
    (steps + 1, B, V) f32. Under ``ctx.shard`` params and pool are
    placed on its mesh as the serving backend places them."""
    B = len(prompts)
    steps = len(feed)
    width = paged_kv.blocks_for(max(map(len, prompts)), block_size) \
        * block_size
    max_len = width + paged_kv.blocks_for(steps + 1, block_size) * block_size
    nbm = paged_kv.blocks_for(max_len, block_size)
    layout = paged_kv.PagedLayout(num_slots=B, num_blocks=1 + B * nbm,
                                  block_size=block_size, max_len=max_len)
    table = (1 + np.arange(B * nbm, dtype=np.int32)).reshape(B, nbm)
    pools = model.init_paged_cache(layout)
    pool_sh = None
    if ctx.shard is not None:
        pool_sh = shlib.named(ctx.shard.mesh,
                              model.paged_cache_specs(layout, ctx.shard))
        pools = jax.device_put(pools, pool_sh)
        params = shlib.place_params(params, ctx.shard)
    toks = np.zeros((B, width), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    for r, p in enumerate(prompts):
        toks[r, :len(p)] = p
    ids = jnp.asarray(table[:, :width // block_size])
    rows = jnp.arange(B, dtype=jnp.int32)
    valid = jnp.ones((B,), bool)

    def prefill_fn(params, pools, tokens, lengths):
        logits, dense = model.prefill(params, {"tokens": tokens}, ctx,
                                      max_len=width, length=lengths)
        pools = model.pack_prefill_into_paged(layout, pools, dense, rows,
                                              valid, ids)
        last = jnp.take_along_axis(logits, (lengths - 1)[:, None, None],
                                   axis=1)[:, 0]
        return last, pools

    def decode_fn(params, pools, table, lengths, tokens):
        return model.decode_step_paged(params, pools, table, lengths,
                                       tokens, ctx)

    prefill = shlib.jit_step(prefill_fn, ctx.shard, pool_sh, donate=(1,))
    decode = shlib.jit_step(decode_fn, ctx.shard, pool_sh, donate=(1,))
    logits, pools = prefill(params, pools, jnp.asarray(toks),
                            jnp.asarray(lens))
    out = [np.asarray(logits)]
    lengths = lens.copy()
    for t in range(steps):
        logits, pools = decode(params, pools, jnp.asarray(table),
                               jnp.asarray(lengths),
                               jnp.asarray(feed[t][:, None]))
        out.append(np.asarray(logits))
        lengths += 1
    return np.stack(out)


def kernel_inputs(vocab: int, prompt_lens, steps: int, seed: int):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).tolist() for n in prompt_lens]
    feed = rng.integers(0, vocab, (steps, len(prompt_lens))).astype(np.int32)
    return prompts, feed


def logit_gap(a, b) -> dict:
    """max |a - b| next to the tolerance it is held to (LOGIT_RTOL of
    max |b|)."""
    scale = float(np.max(np.abs(b)))
    return {"max_abs_diff": float(np.max(np.abs(a - b))),
            "ref_max_abs": scale, "tol": LOGIT_RTOL * scale}


def check_kernels(model, params, kernel_mode: str, prompt_lens=(512, 237),
                  steps: int = 4, seed: int = 0) -> dict:
    """One prefill and ``steps`` paged decode steps through the Pallas
    kernels (``kernel_mode``) and through the jnp references, same
    inputs; raises when the logits differ by more than the tolerance."""
    prompts, feed = kernel_inputs(model.cfg.vocab_size, prompt_lens, steps,
                                  seed)
    fast = trace_logits(model, params, RunCtx(kernel_mode=kernel_mode),
                        prompts, feed)
    ref = trace_logits(model, params, RunCtx(kernel_mode="ref"), prompts,
                       feed)
    gap = logit_gap(fast, ref)
    check(np.all(np.isfinite(fast)), "non-finite Pallas logits")
    check(gap["max_abs_diff"] <= gap["tol"],
          f"Pallas logits off the references: {gap}")
    return gap


# -- serving through Engine -------------------------------------------------


def request_mix(vocab: int, seed: int, lengths, n: int, new_tokens=(16, 64),
                repeat: int = 0):
    """``n`` seeded requests cycling over prompt ``lengths`` (each jittered
    by up to 1/16), even indices greedy and odd ones sampled. With
    ``repeat`` a prompt repeats a phrase of that many tokens, which gives
    the ngram drafter something to propose."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        base = lengths[i % len(lengths)]
        L = int(base + rng.integers(-(base // 16), base // 16 + 1))
        if repeat:
            phrase = rng.integers(0, vocab, repeat)
            prompt = np.resize(phrase, L).tolist()
        else:
            prompt = rng.integers(0, vocab, L).tolist()
        sp = SamplingParams(
            max_tokens=int(rng.integers(new_tokens[0], new_tokens[1] + 1)),
            temperature=0.0 if i % 2 == 0 else 0.8, top_p=0.95,
            seed=seed * 1000 + i)
        reqs.append((prompt, sp))
    return reqs


def check_drained(name: str, engines, handles):
    """Every request ran to its token count; every pool is back to zero
    used blocks."""
    for h in handles:
        check(h.finished and len(h.token_ids) == h.sampling.max_tokens,
              f"{name}: request {h.uid} ended with {len(h.token_ids)} of "
              f"{h.sampling.max_tokens} tokens")
    for e in engines:
        used = e.stats()["blocks_used"]
        check(used == 0, f"{name}: {used} blocks leaked")


def serve(model, params, ecfg, reqs, ctx=None, name="serve"):
    """Serve ``reqs`` through one Engine; returns (engine, handles)."""
    t0 = time.perf_counter()
    engine = Engine(model, params, ecfg, ctx=ctx)
    handles = [engine.add_request(p, s) for p, s in reqs]
    engine.drain()
    check_drained(name, [engine], handles)
    st = engine.stats()
    say(name, wall_s=time.perf_counter() - t0, requests=len(handles),
        tokens=sum(len(h.token_ids) for h in handles), blocks_used=0,
        prefill_compiles=st["prefill_compiles"],
        preemptions=st["preemptions"])
    return engine, handles


def pool_bytes(engine) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(engine.backend.pools))


def serve_overlap_pair(model, params, ecfg, reqs, ctx=None) -> dict:
    """The mix with overlap on, then off: every output must match,
    sampled ones included (the RNG-stream contract)."""
    eng, on = serve(model, params, dataclasses.replace(ecfg, overlap=True),
                    reqs, ctx, "serve overlap=on")
    nbytes = pool_bytes(eng)
    del eng
    gc.collect()   # an engine's reference cycles hold its device pool
    eng, off = serve(model, params, dataclasses.replace(ecfg, overlap=False),
                     reqs, ctx, "serve overlap=off")
    differ = [i for i in range(len(reqs))
              if on[i].token_ids != off[i].token_ids]
    check(not differ,
          f"overlap on and off differ on requests {differ}")
    return {"engine": eng, "pool_bytes": nbytes,
            "greedy": sum(s.greedy for _, s in reqs),
            "identical": len(reqs) - len(differ), "requests": len(reqs)}


def decode_step_text(engine) -> str:
    """Compiled text of the engine's decode step at its own shapes."""
    be = engine.backend
    tokens = jnp.zeros((be.cfg.num_slots, 1), jnp.int32)
    return be._decode.lower(be.params, be.pools, jnp.asarray(be.table),
                            jnp.asarray(be.lengths),
                            tokens).compile().as_text()


def short_passes(model, params, ecfg, reqs, ctx=None) -> dict:
    """Four requests each through the speculative verify kernel (ngram
    drafts, K=4) and through the int8 pool's fused-dequant kernels."""
    spec_cfg = dataclasses.replace(ecfg, spec_tokens=4, drafter="ngram",
                                   overlap=False)
    eng, _ = serve(model, params, spec_cfg, reqs, ctx, "spec K=4")
    spec = eng.stats()["spec"]
    del eng
    gc.collect()
    eng, _ = serve(model, params, dataclasses.replace(ecfg, kv_dtype="int8"),
                   reqs, ctx, "kv int8")
    del eng
    gc.collect()
    return {"proposed": spec["proposed"], "accepted": spec["accepted"]}


# -- four chips --------------------------------------------------------------


def placed_on(tree) -> set:
    return set().union(*(x.devices() for x in jax.tree.leaves(tree)))


def multi_chip(model, params, ecfg, reqs, ctx=None) -> dict:
    """ReplicaSet(dp=4) over a (data=4, model=1) mesh and one Engine over
    a (data=1, model=4) mesh, each against a one-chip engine on chip 0
    in this process. Everything is measured before any check raises, so
    a failing run still prints its comparison."""
    n = len(jax.devices())
    check(n >= 4, f"--chips 4 needs four devices, found {n}")
    eng, base = serve(model, params, ecfg, reqs, ctx, "one chip")
    base_tokens = [h.token_ids for h in base]
    del eng
    gc.collect()
    prompts, feed = kernel_inputs(model.cfg.vocab_size, (512, 237), 4, 0)
    single_ctx = RunCtx() if ctx is None else ctx
    base_logits = trace_logits(model, params, single_ctx, prompts, feed)

    dp_mesh = make_mesh((4, 1), ("data", "model"))
    t0 = time.perf_counter()
    rs = ReplicaSet(model, params, ecfg, mesh=dp_mesh, ctx=ctx)
    placement = []
    for r, e in enumerate(rs.replicas):
        want = {dp_mesh.devices[r, 0]}
        placement.append(placed_on(e.backend.params) == want
                         and placed_on(e.backend.pools) == want)
    dp_handles = [rs.add_request(p, s) for p, s in reqs]
    rs.drain()
    check_drained("replicas dp=4", rs.replicas, dp_handles)
    dp_same = sum(h.token_ids == t for h, t in zip(dp_handles, base_tokens))
    dp_gap = max(
        logit_gap(trace_logits(model, e.backend.params, e.backend.ctx,
                               prompts, feed), base_logits)["max_abs_diff"]
        for e in rs.replicas)
    say("replicas dp=4", wall_s=time.perf_counter() - t0,
        chips=[str(dp_mesh.devices[r, 0]) for r in range(4)],
        placed_on_own_chip=placement, dispatched=rs.dispatched,
        tokens_identical=f"{dp_same}/{len(reqs)}", max_logit_diff=dp_gap)
    del rs
    gc.collect()

    tp_mesh = make_mesh((1, 4), ("data", "model"))
    t0 = time.perf_counter()
    eng = Engine(model, params, dataclasses.replace(ecfg, mesh=tp_mesh),
                 ctx=ctx)
    head_shard = eng.backend.ctx.decode_head_shard
    tp_handles = [eng.add_request(p, s) for p, s in reqs]
    eng.drain()
    check_drained("engine tp=4", [eng], tp_handles)
    tp_same = sum(h.token_ids == t for h, t in zip(tp_handles, base_tokens))
    tp_diff = [i for i, (h, t) in enumerate(zip(tp_handles, base_tokens))
               if h.token_ids != t]
    tp_gap = logit_gap(trace_logits(model, params, eng.backend.ctx, prompts,
                                    feed), base_logits)
    say("engine tp=4", wall_s=time.perf_counter() - t0,
        head_sharded_decode=head_shard,
        tokens_identical=f"{tp_same}/{len(reqs)}", differing=tp_diff,
        **{f"logit_{k}": v for k, v in tp_gap.items()})
    del eng
    gc.collect()

    check(all(placement), f"replica placement {placement}")
    check(dp_same == len(reqs), "replica outputs differ from one chip")
    check(head_shard, "tp=4 engine did not take the head-sharded decode")
    check(tp_same == len(reqs),
          f"tp=4 outputs differ from one chip on requests {tp_diff}")
    return {"dp_identical": dp_same, "tp_identical": tp_same}


# -- main --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica and tensor-parallel "
                         "paths against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    cache_dir = compile_cache.enable()
    compiles = {"n": 0, "s": 0.0}

    def on_compile(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t_start = time.perf_counter()
    cfg = get_config("olmo_1b")
    t0 = time.perf_counter()
    model, params = build(cfg, args.seed)
    jax.block_until_ready(params)
    say("model", name=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
        heads=f"{cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}",
        vocab=cfg.vocab_size, dtype=cfg.dtype,
        params=model.param_count(params), wall_s=time.perf_counter() - t0,
        compile_cache=cache_dir)

    if args.chips == 4:
        reqs = request_mix(cfg.vocab_size, args.seed, (100, 400), 8,
                           new_tokens=(16, 32))
        multi_chip(model, params, SERVE_CFG, reqs)
    else:
        t0 = time.perf_counter()
        gap = check_kernels(model, params, "pallas", seed=args.seed)
        say("kernels pallas vs ref", wall_s=time.perf_counter() - t0,
            within_tol=True, **gap)

        reqs = request_mix(cfg.vocab_size, args.seed, SERVE_LENGTHS, 12)
        res = serve_overlap_pair(model, params, SERVE_CFG, reqs)
        text = decode_step_text(res["engine"])
        check("tpu_custom_call" in text,
              "the compiled decode step holds no Pallas kernel")
        del res["engine"]
        gc.collect()
        say("serve", pool_tokens=SERVE_CFG.block_size
            * (SERVE_CFG.num_blocks - 1), **res,
            decode_step_has_tpu_custom_call=True, **hbm())

        short = request_mix(cfg.vocab_size, args.seed + 1, (100,), 4,
                            new_tokens=(16, 32), repeat=20)
        say("short passes", **short_passes(model, params, SHORT_CFG, short))

    say("smoke timings", wall_s=time.perf_counter() - t_start,
        compiles=compiles["n"], compile_s=compiles["s"], **hbm())
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
