"""Training driver: step builder + fault-tolerant loop.

Features exercised end-to-end by examples/train_lm.py and the integration
tests:
  * pjit train_step with 2-D FSDP x TP shardings (launch/sharding.py),
  * microbatch gradient accumulation (scan, f32 accumulators),
  * global-norm clipping (optionally via the VRP compensated reduction),
  * Kahan-compensated bf16 params (OptConfig.kahan),
  * checkpoint/restart (atomic + async, resume == uninterrupted run —
    tests/test_train_loop.py asserts bitwise-close resumption),
  * straggler detection hooks (step-time outlier monitor),
  * deterministic data skipping (data/pipeline.py batch_at(step)).

Run:  PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --smoke
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_source
from repro.launch import sharding as shlib
from repro.launch.mesh import make_local_mesh
from repro.models.model import Model
from repro.models.transformer import RunCtx
from repro.optim import OptConfig, apply_updates, init_opt_state
from repro.optim.schedule import warmup_cosine


def make_train_step(model: Model, opt_cfg: OptConfig, ctx: RunCtx,
                    lr_fn: Callable):
    """Pure (state, batch) -> (state, metrics); jit/pjit-ready."""

    def loss_fn(params, batch):
        return model.loss_fn(params, batch, ctx)

    def grads_of(params, batch):
        if opt_cfg.grad_accum <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads
        A = opt_cfg.grad_accum

        adt = jnp.dtype(opt_cfg.accum_dtype)

        def micro(carry, mb):
            acc, loss_acc = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            acc = jax.tree.map(
                lambda a, gg: (a.astype(jnp.float32)
                               + gg.astype(jnp.float32) / A).astype(adt),
                acc, g)
            return (acc, loss_acc + loss / A), None

        split = jax.tree.map(
            lambda x: x.reshape((A, x.shape[0] // A) + x.shape[1:]), batch)
        zero = jax.tree.map(
            lambda p: jnp.zeros(p.shape, adt), params)
        (grads, loss), _ = jax.lax.scan(micro, (zero, 0.0), split)
        return loss, {"loss": loss}, grads

    def train_step(state, batch):
        loss, metrics, grads = grads_of(state["params"], batch)
        lr = lr_fn(state["opt"]["step"])
        new_params, new_opt, om = apply_updates(
            state["params"], grads, state["opt"], opt_cfg, lr)
        metrics = {**metrics, **om, "lr": lr}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_state(model: Model, opt_cfg: OptConfig, seed: int = 0):
    params = model.init(jax.random.PRNGKey(seed))
    return {"params": params, "opt": init_opt_state(params, opt_cfg)}


def state_specs(state_shapes, shard: shlib.ShardCtx):
    pspecs = shlib.param_specs(state_shapes["params"], shard)
    ospecs = shlib.opt_state_specs(pspecs, state_shapes["opt"], shard)
    return {"params": pspecs, "opt": ospecs}


class StragglerMonitor:
    """Step-time outlier detector (straggler mitigation hook).

    At 1000-node scale the mitigation action is re-sharding around the
    slow host (launch/elastic.py); single-process here, so the monitor
    records and exposes decisions for the driver.
    """

    def __init__(self, window: int = 32, threshold: float = 3.0):
        self.times: list[float] = []
        self.window = window
        self.threshold = threshold
        self.flags = 0

    def observe(self, dt: float) -> bool:
        self.times.append(dt)
        hist = self.times[-self.window:]
        if len(hist) < 8:
            return False
        med = float(np.median(hist[:-1]))
        is_straggler = dt > self.threshold * med
        self.flags += int(is_straggler)
        return is_straggler


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    log_every: int = 10
    metrics_path: Optional[str] = None


def train_loop(model: Model, opt_cfg: OptConfig, ctx: RunCtx,
               data_cfg: DataConfig, loop_cfg: TrainLoopConfig,
               mesh=None, lr_fn=None, state=None, fail_at: Optional[int] = None):
    """Fault-tolerant training loop. Returns (state, metrics history).

    ``fail_at`` raises mid-run (tests use it to validate restart).
    Restores from the latest checkpoint in ckpt_dir if one exists.
    """
    lr_fn = lr_fn or functools.partial(
        warmup_cosine, peak_lr=3e-4, warmup_steps=20,
        total_steps=loop_cfg.steps)
    step_fn = make_train_step(model, opt_cfg, ctx, lr_fn)
    source = make_source(data_cfg)
    ckpt = CheckpointManager(loop_cfg.ckpt_dir, keep=loop_cfg.keep)
    monitor = StragglerMonitor()

    if mesh is not None:
        shard = shlib.make_shard_ctx(mesh)
        state_shapes = jax.eval_shape(
            lambda: init_state(model, opt_cfg))
        sspec = shlib.named(mesh, state_specs(state_shapes, shard))
        bspec = shlib.named(mesh, shlib.batch_specs(
            source.batch_at(0), shard))
        step_fn = jax.jit(step_fn, in_shardings=(sspec, bspec),
                          donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    start_step = 0
    latest = ckpt.latest_step()
    if latest is not None:
        template = jax.eval_shape(lambda: init_state(model, opt_cfg))
        shardings = None
        if mesh is not None:
            shardings = sspec
        state, meta = ckpt.restore(latest, template=template,
                                   shardings=shardings)
        start_step = int(meta.get("step", latest))
    elif state is None:
        state = init_state(model, opt_cfg)
        if mesh is not None:
            state = jax.device_put(state, sspec)

    history = []
    for step in range(start_step, loop_cfg.steps):
        if fail_at is not None and step == fail_at:
            ckpt.wait()
            raise RuntimeError(f"injected failure at step {step}")
        batch = source.batch_at(step)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        metrics = jax.tree.map(float, jax.device_get(metrics))
        dt = time.time() - t0
        straggler = monitor.observe(dt)
        metrics.update(step=step, dt=dt, straggler=straggler)
        history.append(metrics)
        if loop_cfg.metrics_path:
            with open(loop_cfg.metrics_path, "a") as f:
                f.write(json.dumps(metrics) + "\n")
        if step % loop_cfg.log_every == 0:
            print(f"step {step:5d} loss {metrics['loss']:.4f} "
                  f"gnorm {metrics.get('grad_norm', 0):.2f} {dt*1e3:.0f} ms",
                  flush=True)
        if (step + 1) % loop_cfg.ckpt_every == 0 or step + 1 == loop_cfg.steps:
            ckpt.save(step + 1, state, metadata={"step": step + 1})
    ckpt.wait()
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--kahan", action="store_true")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = Model(cfg)
    opt_cfg = OptConfig(kahan=args.kahan, grad_accum=args.grad_accum)
    ctx = RunCtx(kernel_mode="auto")
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch)
    mesh = make_local_mesh(tp=args.tp) if len(jax.devices()) > 1 else None
    loop_cfg = TrainLoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir)
    _, hist = train_loop(model, opt_cfg, ctx, data_cfg, loop_cfg, mesh=mesh)
    print(f"final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
