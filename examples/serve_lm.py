"""Serving example: the unified Engine API, streaming outputs.

Loads (or random-inits) a smoke model, submits a stream of ragged
requests with per-request SamplingParams (temperature / top-k / top-p /
seed / stop tokens), and drives ``Engine.step()`` by hand to show the
streaming interface: each step yields per-request token increments as
they are sampled. The paged backend admits optimistically, preempts LIFO
under cache pressure (watch the preemption counter with a tiny
--mem-tokens), and prefills through power-of-two buckets; the static
backend is the lockstep baseline behind the same API.

Run: PYTHONPATH=src python examples/serve_lm.py [--arch olmo_1b]
     PYTHONPATH=src python examples/serve_lm.py --backend static
     PYTHONPATH=src python examples/serve_lm.py --smoke   # CI-sized
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch import compile_cache
from repro.launch.engine import (DisaggregatedEngine, Engine, EngineConfig,
                                 ReplicaSet, SamplingParams)
from repro.models.model import Model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--backend", choices=("paged", "static"),
                    default="paged")
    ap.add_argument("--n-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--mem-tokens", type=int, default=256,
                    help="paged KV pool capacity in tokens")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the backend over "
                         "a (data = n/tp, model = tp) mesh of the local "
                         "devices (fake N CPU devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel engine replicas behind one "
                         "shared admission queue (ReplicaSet); splits "
                         "the mesh's data axis, each replica keeping "
                         "its own KV pool and TP subgrid")
    ap.add_argument("--spec-tokens", type=int, default=0,
                    help="speculative decoding: draft tokens per step "
                         "(paged backend; ngram self-drafting — outputs "
                         "are bit-identical, only faster on repetitive "
                         "text)")
    ap.add_argument("--roles", default=None,
                    help="prefill/decode disaggregation over the dp "
                         "replicas: comma-separated roles (e.g. "
                         "'prefill,decode') or 'auto'. Prefill replicas "
                         "export first-token slots as migration packets; "
                         "decode replicas import them — outputs stay "
                         "bit-identical, stats() grows a 'disagg' "
                         "section")
    ap.add_argument("--kv-dtype", choices=("bf16", "int8", "fp8"),
                    default="bf16",
                    help="paged KV pool storage precision: int8/fp8 "
                         "store quantized blocks with per-(token, head) "
                         "scales, dequant fused into the kernels "
                         "(several-fold cache capacity per byte; paged backend "
                         "only)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI")
    args = ap.parse_args()
    compile_cache.enable()
    if args.smoke:
        args.requests, args.n_new = 6, 8

    cfg = get_config(args.arch).smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    from repro.launch.mesh import make_local_mesh, replica_cli_mesh

    if args.dp > 1:
        # dp x tp devices, each replica a (1, tp) TP subgrid
        mesh = replica_cli_mesh(args.dp, args.tp)
    elif args.tp > 1 or len(jax.devices()) > 1:
        mesh = make_local_mesh(args.tp)
    else:
        mesh = None
    if mesh is not None:
        print(f"mesh: {dict(zip(('data', 'model'), mesh.devices.shape))}")

    ecfg = EngineConfig(
        backend=args.backend, num_slots=args.slots, block_size=16,
        num_blocks=args.mem_tokens // 16 + 1, max_len=128,
        spec_tokens=args.spec_tokens, kv_dtype=args.kv_dtype)
    if args.roles is not None:
        roles = args.roles if args.roles == "auto" \
            else tuple(args.roles.split(","))
        engine = DisaggregatedEngine(model, params, ecfg, dp=args.dp,
                                     mesh=mesh, roles=roles)
        print(f"disaggregated: roles={list(engine.roles)}, "
              f"{engine.total_slots} total slots")
    elif args.dp > 1:
        engine = ReplicaSet(model, params, ecfg, dp=args.dp, mesh=mesh)
        print(f"replica set: dp={args.dp}, "
              f"{engine.total_slots} total slots")
    else:
        import dataclasses

        engine = Engine(model, params,
                        dataclasses.replace(ecfg, mesh=mesh))

    handles = []
    for i in range(args.requests):
        prompt = list(rng.integers(0, cfg.vocab_size,
                                   int(rng.integers(4, 16))))
        # skewed output lengths: mostly short, a few long stragglers
        max_new = int(rng.choice([4, 6, 8, args.n_new],
                                 p=[0.4, 0.25, 0.2, 0.15]))
        handles.append(engine.add_request(prompt, SamplingParams(
            max_tokens=max_new, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=i)))
    print(f"arch={cfg.name}  backend={args.backend}  "
          f"{args.requests} requests into {args.slots} slots")

    t0 = time.time()
    total = 0
    while engine.has_work:
        for out in engine.step():                 # streaming increments
            total += len(out.new_tokens)
            if out.request_id < 2 and out.new_tokens:
                print(f"  stream req{out.request_id} += "
                      f"{list(out.new_tokens)}"
                      + (f"  [done: {out.finish_reason}]"
                         if out.finished else ""))
    dt = time.time() - t0

    st = engine.stats()
    print(f"decoded {total} tokens over {len(handles)} reqs in {dt:.2f}s "
          f"({total / dt:.1f} tok/s)")
    print(f"  stats: {st}")
    for h in handles[:3]:
        print(f"  req{h.uid}: {h.token_ids[:10]}... ({h.finish_reason})")
    assert all(h.finished for h in handles)
    if args.backend == "paged":
        assert st["blocks_used"] == 0, "block leak"


if __name__ == "__main__":
    main()
