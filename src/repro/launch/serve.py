"""Serving CLI. The engine lives in ``repro.launch.engine`` — one
``Engine`` front-end (``add_request``/``step``/``generate``) over the
``paged`` (continuous batching, optimistic admission + preemption,
batched bucketed prefill) and ``static`` (lockstep) backends, and a
``ReplicaSet`` that runs R data-parallel engine replicas behind one
shared admission queue. Import from there:

    from repro.launch.engine import Engine, EngineConfig, SamplingParams
    from repro.launch.engine import ReplicaSet

The PR-1 ``Server``/``Scheduler`` adapters finished their deprecation
cycle in PR 4 and are gone; this module is now only the CLI.

Run: PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b --smoke
     PYTHONPATH=src python -m repro.launch.serve --dp 2 --tp 2  # mesh
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.engine import (DisaggregatedEngine, Engine, EngineConfig,
                                 ReplicaSet, SamplingParams)
from repro.launch.mesh import replica_cli_mesh
from repro.models.model import Model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", choices=("static", "paged"),
                    default="paged")
    ap.add_argument("--n-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard each engine over "
                         "a (data, model) mesh of the local devices")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas behind one shared "
                         "admission queue (ReplicaSet); each replica "
                         "gets its own KV pool and TP subgrid")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding: ngram-drafted tokens "
                         "per step (paged backend; bit-identical "
                         "outputs)")
    ap.add_argument("--roles", default=None,
                    help="prefill/decode disaggregation over the dp "
                         "replicas: comma-separated roles (e.g. "
                         "'prefill,decode') or 'auto'; requires dp >= 2 "
                         "and the paged backend (bit-identical outputs, "
                         "KV blocks migrate between pools)")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8", "fp8"),
                    default="bf16",
                    help="paged KV pool storage precision: int8/fp8 "
                         "store quantized blocks + per-(token, head) "
                         "scales with dequant fused into the kernels "
                         "(paged backend only)")
    args = ap.parse_args()
    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    mesh = replica_cli_mesh(args.dp, args.tp)
    ecfg = EngineConfig(backend=args.backend, num_slots=args.slots,
                        max_len=128, spec_tokens=args.spec_tokens,
                        kv_dtype=args.kv_dtype)
    if args.roles is not None:
        roles = args.roles if args.roles == "auto" \
            else tuple(args.roles.split(","))
        engine = DisaggregatedEngine(model, params, ecfg, dp=args.dp,
                                     mesh=mesh, roles=roles)
    elif args.dp > 1:
        engine = ReplicaSet(model, params, ecfg, dp=args.dp, mesh=mesh)
    else:
        engine = Engine(model, params,
                        dataclasses.replace(ecfg, mesh=mesh))
    prompts = [list(rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(4, 16))))
               for _ in range(args.requests)]
    sp = [SamplingParams(max_tokens=int(rng.integers(4, args.n_new + 1)),
                         temperature=args.temperature, seed=i)
          for i in range(args.requests)]
    t0 = time.time()
    outs = engine.generate(prompts, sp)
    dt = time.time() - t0
    total = sum(len(o) for o in outs)
    print(f"[{args.backend} dp={args.dp}] {total} tokens over "
          f"{len(outs)} reqs in {dt:.2f}s ({total / dt:.1f} tok/s)  "
          f"stats={engine.stats()}")
    for i, o in enumerate(outs[:2]):
        print(f"req{i}: {o[:12]}...")


if __name__ == "__main__":
    main()
