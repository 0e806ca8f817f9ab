import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

THE two lines above must run before any jax import (jax locks the device
count at first init) — hence the unusual module layout.

For each cell this proves the distribution config is coherent end-to-end:
  * the production mesh builds ((16,16) single-pod / (2,16,16) multi-pod),
  * param/opt/batch/cache shardings fit the mesh (divisibility-checked),
  * jit(step).lower(**ShapeDtypeStructs).compile() succeeds under SPMD,
  * memory_analysis / cost_analysis / the collective schedule are recorded
    to JSON for EXPERIMENTS.md §Dry-run and roofline/analysis.py.

Step lowered per cell kind:  train -> train_step (fwd+bwd+optimizer),
prefill -> prefill_step (logits + cache), decode/long -> serve_step
(1 token against a seq_len cache).

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo_1b \
      --cell train_4k --mesh both --out experiments/dryrun
"""

import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, LM_SHAPES, get_cell, get_config
from repro.launch import sharding as shlib
from repro.launch.mesh import make_production_mesh, mesh_summary
from repro.launch.params import active_param_count, total_param_count
from repro.launch.train import init_state, make_train_step, state_specs
from repro.models.model import Model, input_specs
from repro.models.transformer import RunCtx
from repro.optim import OptConfig
from repro.optim.schedule import constant
from repro.roofline import analysis as ra
from repro.roofline.hw import V5E


def default_opt_for(cfg) -> OptConfig:
    """Baseline optimizer per arch. kimi-k2 (1T params) trains with
    factored bf16 state + Kahan bf16 params — full f32 Adam at 512 v5e
    chips is arithmetically impossible (12 TB state vs 8 TB HBM) and
    would be dishonest as a 'fitting' baseline."""
    if cfg.name.startswith("kimi"):
        # kahan=False: the bf16 compensation buffer would double the 8 GB
        # per-device param footprint; at 1T params the fit wins.
        return OptConfig(kind="adafactor", state_dtype="bfloat16",
                         kahan=False, norm_tile="vec")
    return OptConfig(kind="adamw", state_dtype="float32")


def build_lowerable(cfg, cell, mesh, remat="full", kernel_mode="ref",
                    unroll=False, knobs=None):
    """-> (jitted fn, tuple of ShapeDtypeStruct args) for one cell.

    ``knobs`` (optional dict) selects §Perf variants: layout ('2d'|'fsdp'),
    ce_chunk (int), moe_mode ('gather'|'partial'), decode_seq_shard (bool),
    grad_accum (int).
    """
    knobs = knobs or {}
    model = Model(cfg)
    shard = shlib.make_shard_ctx(
        mesh, layout=knobs.get("layout", "2d"),
        cache_seq_shard=knobs.get("decode_seq_shard", False))
    ctx = RunCtx(kernel_mode=kernel_mode,
                 remat=remat if cell.kind == "train" else "none",
                 shard=shard, moe_sharded=cfg.is_moe,
                 scan_unroll=unroll,
                 ce_chunk=knobs.get("ce_chunk", 0),
                 moe_mode=knobs.get("moe_mode", "gather"),
                 decode_seq_shard=knobs.get("decode_seq_shard", False),
                 residual_spec=knobs.get("residual_spec", "none"))
    specs = input_specs(cfg, cell)
    params_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = shlib.param_specs(params_shapes, shard)

    if cell.kind == "train":
        opt_cfg = default_opt_for(cfg)
        if knobs.get("grad_accum"):
            opt_cfg = dataclasses.replace(
                opt_cfg, grad_accum=knobs["grad_accum"],
                accum_dtype=knobs.get("accum_dtype", "float32"))
        step = make_train_step(model, opt_cfg, ctx,
                               functools.partial(constant, peak_lr=1e-4))
        state_shapes = jax.eval_shape(
            lambda: init_state(model, opt_cfg))
        sspecs = state_specs(state_shapes, shard)
        bspecs = shlib.batch_specs(specs, shard)
        metric_shapes = jax.eval_shape(step, state_shapes, specs)[1]
        mspecs = jax.tree.map(lambda _: jax.sharding.PartitionSpec(),
                              metric_shapes)
        # out_shardings pin the new state to the same FSDP x TP layout —
        # without this GSPMD replicates grads/params on the way out
        # (observed: 33 GB all-reduce instead of reduce-scatter).
        fn = jax.jit(step,
                     in_shardings=(shlib.named(mesh, sspecs),
                                   shlib.named(mesh, bspecs)),
                     out_shardings=(shlib.named(mesh, sspecs),
                                    shlib.named(mesh, mspecs)),
                     donate_argnums=(0,))
        return fn, (state_shapes, specs)

    if cell.kind == "prefill":
        def prefill_step(params, batch):
            # Serving prefill: sampler needs only the last position's
            # logits (XLA DCEs the full (B, S, V) logits einsum).
            logits, cache = model.prefill(params, batch, ctx,
                                          max_len=cell.seq_len)
            return logits[:, -1], cache
        bspecs = shlib.batch_specs(specs, shard)
        out_shapes = jax.eval_shape(prefill_step, params_shapes, specs)
        logits_spec = shlib.batch_specs({"tokens": out_shapes[0]}, shard)[
            "tokens"]
        cache_spec = shlib.batch_specs(out_shapes[1], shard)
        fn = jax.jit(prefill_step,
                     in_shardings=(shlib.named(mesh, pspecs),
                                   shlib.named(mesh, bspecs)),
                     out_shardings=(shlib.named(mesh, logits_spec),
                                    shlib.named(mesh, cache_spec)))
        return fn, (params_shapes, specs)

    # decode / long: one token against a seq_len-deep cache
    cache_shapes = specs.pop("cache")
    tokens = specs.pop("tokens")
    pos = specs.pop("pos")
    mrope = specs.pop("mrope_positions", None)

    def serve_step(params, cache, tokens, pos, mrope_positions=None):
        return model.decode_step(params, cache, tokens, pos, ctx,
                                 mrope_positions=mrope_positions)

    cspecs = shlib.batch_specs(cache_shapes, shard)
    tspecs = shlib.batch_specs({"tokens": tokens}, shard)["tokens"]
    args = [params_shapes, cache_shapes, tokens, pos]
    inshard = [shlib.named(mesh, pspecs), shlib.named(mesh, cspecs),
               shlib.named(mesh, tspecs),
               shlib.named(mesh, shlib.batch_specs({"pos": pos}, shard)["pos"])]
    if mrope is not None:
        args.append(mrope)
        inshard.append(shlib.named(
            mesh, shlib.batch_specs({"mrope_positions": mrope}, shard)[
                "mrope_positions"]))
    out_shapes = jax.eval_shape(serve_step, *args)
    logits_spec = shlib.batch_specs({"tokens": out_shapes[0]}, shard)[
        "tokens"]
    fn = jax.jit(serve_step, in_shardings=tuple(inshard),
                 out_shardings=(shlib.named(mesh, logits_spec),
                                shlib.named(mesh, cspecs)),
                 donate_argnums=(1,))
    return fn, tuple(args)


def _cell_costs(cfg, cell, mesh, n_dev, pod_size, remat,
                build=None):
    """Compile one depth variant UNROLLED; return (flops, bytes, colls).

    XLA cost_analysis ignores while-loop trip counts, so the shallow cost
    variants unroll every layer/chunk scan — their bodies then appear as
    inline HLO and are counted exactly. (The sLSTM time-step loop stays a
    loop; its in-loop R-matmul is <3% of an xLSTM layer — noted in
    EXPERIMENTS.md §Roofline.)
    """
    build = build or build_lowerable
    with mesh:
        fn, args = build(cfg, cell, mesh, remat=remat, unroll=True)
        compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll = ra.parse_collectives(hlo, pod_size=pod_size, n_devices=n_dev)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def depth_corrected_costs(cfg, cell, mesh, n_dev, pod_size, remat,
                          build=None):
    """XLA cost_analysis counts a scan body ONCE regardless of trip count
    (verified empirically), so layer-scanned models are undercounted. Fit
    f(depth) = a + b*depth from two shallow variants (1 and 2 pattern
    periods) and extrapolate to the true depth. Linear in depth holds for
    flops, bytes and wire-bytes alike (stacked params scale with L too).
    Remainder layers (depth % period) are credited pro-rata.
    """
    p = len(cfg.block_pattern)
    units = cfg.n_layers // p
    rem = cfg.n_layers % p
    enc_per_unit = (cfg.n_encoder_layers // max(units, 1)
                    if cfg.enc_dec else 0)

    def variant(s):
        return dataclasses.replace(
            cfg, n_layers=s * p,
            n_encoder_layers=s * enc_per_unit if cfg.enc_dec else 0)

    f1, b1, c1 = _cell_costs(variant(1), cell, mesh, n_dev, pod_size, remat,
                             build)
    f2, b2, c2 = _cell_costs(variant(2), cell, mesh, n_dev, pod_size, remat,
                             build)
    scale = units + rem / p

    def fit(v1, v2):
        # a + b*s with slope clamped non-negative: XLA occasionally fuses
        # the depth-2 variant harder than depth-1, producing a slightly
        # negative slope that would extrapolate to nonsense at s=61.
        b = max(v2 - v1, 0.0)
        a = max(v1 - b, 0.0)
        return a + b * scale
    flops = fit(f1, f2)
    nbytes = fit(b1, b2)
    wire = {k: fit(c1.wire_bytes.get(k, 0.0), c2.wire_bytes.get(k, 0.0))
            for k in set(c1.wire_bytes) | set(c2.wire_bytes)}
    pod_wire = fit(c1.pod_wire_bytes, c2.pod_wire_bytes)
    coll = ra.CollectiveStats(
        ops=c2.ops,
        operand_bytes={k: fit(c1.operand_bytes.get(k, 0.0),
                              c2.operand_bytes.get(k, 0.0))
                       for k in set(c1.operand_bytes) | set(c2.operand_bytes)},
        wire_bytes=wire, pod_wire_bytes=max(pod_wire, 0.0),
        total_operand_bytes=float(sum(
            max(v, 0.0) for v in (fit(c1.operand_bytes.get(k, 0.0),
                                      c2.operand_bytes.get(k, 0.0))
                                  for k in set(c1.operand_bytes)
                                  | set(c2.operand_bytes)))),
        total_wire_bytes=float(sum(max(v, 0.0) for v in wire.values())))
    return max(flops, 0.0), max(nbytes, 0.0), coll


def run_cell(arch: str, cell_name: str, multi_pod: bool, remat="full",
             build=build_lowerable, cost_scale: float = 1.0):
    """``cost_scale`` multiplies fitted flops/bytes/wire — required for
    grad-accum variants whose microbatch scan body XLA counts once."""
    cfg = get_config(arch)
    cell = get_cell(cell_name)
    if cell_name == "long_500k" and not cfg.sub_quadratic:
        return {"arch": arch, "cell": cell_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": "pure full-attention arch; long_500k requires "
                          "sub-quadratic mixing (DESIGN.md §4)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = 512 if multi_pod else 256
    pod_size = 256 if multi_pod else None
    t0 = time.time()
    # Full-depth compile: THE dry-run gate (memory fit + compilability).
    with mesh:
        fn, args = build(cfg, cell, mesh, remat=remat)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)                      # proves it fits (spec step 3)
        print({k: v for k, v in compiled.cost_analysis().items()
               if k in ("flops", "bytes accessed")})
    # Depth-corrected roofline inputs (scan trip-count fix).
    flops_dev, bytes_dev, coll = depth_corrected_costs(
        cfg, cell, mesh, n_dev, pod_size, remat, build)
    if cost_scale != 1.0:
        flops_dev *= cost_scale
        bytes_dev *= cost_scale
        coll = ra.CollectiveStats(
            ops=coll.ops,
            operand_bytes={k: v * cost_scale
                           for k, v in coll.operand_bytes.items()},
            wire_bytes={k: v * cost_scale
                        for k, v in coll.wire_bytes.items()},
            pod_wire_bytes=coll.pod_wire_bytes * cost_scale,
            total_operand_bytes=coll.total_operand_bytes * cost_scale,
            total_wire_bytes=coll.total_wire_bytes * cost_scale)
    terms = ra.roofline_terms(flops_dev, bytes_dev, coll)
    mf = ra.model_flops(cfg, cell)
    hlo_flops_global = flops_dev * n_dev
    result = {
        "arch": arch, "cell": cell_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_shape": mesh_summary(mesh),
        "status": "ok",
        "seconds": {"lower": round(t_lower, 1), "compile": round(t_compile, 1)},
        "params_total": total_param_count(cfg),
        "params_active": active_param_count(cfg),
        "memory_per_device": None if mem is None else {
            "arguments_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
            "total_bytes": int(mem.argument_size_in_bytes
                               + mem.temp_size_in_bytes
                               + mem.output_size_in_bytes),
            "fits_16GB": bool(mem.argument_size_in_bytes
                              + mem.temp_size_in_bytes
                              + mem.output_size_in_bytes < V5E.hbm_bytes),
        },
        "cost_per_device": {"flops": flops_dev, "bytes_accessed": bytes_dev},
        "collectives": {
            "ops": coll.ops,
            "operand_bytes": {k: int(v) for k, v in coll.operand_bytes.items()},
            "wire_bytes": {k: int(v) for k, v in coll.wire_bytes.items()},
            "pod_wire_bytes": int(coll.pod_wire_bytes),
            "total_wire_bytes": int(coll.total_wire_bytes),
        },
        "roofline": terms,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": (mf / hlo_flops_global
                               if hlo_flops_global else None),
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(ARCH_IDS))
    ap.add_argument("--cell", nargs="*",
                    default=[c.name for c in LM_SHAPES])
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default="full")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in args.arch:
        for cell in args.cell:
            for multi in meshes:
                tag = f"{arch}_{cell}_{'multi' if multi else 'single'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip-cached] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                t0 = time.time()
                try:
                    res = run_cell(arch, cell, multi, remat=args.remat)
                except Exception as e:
                    failures += 1
                    res = {"arch": arch, "cell": cell,
                           "mesh": "multi" if multi else "single",
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    r = res["roofline"]
                    extra = (f" dominant={r['dominant']} "
                             f"frac={r['roofline_fraction']:.3f}"
                             f" ({time.time()-t0:.0f}s)")
                print(f"[{status}] {tag}{extra}", flush=True)
    print(f"done; {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
