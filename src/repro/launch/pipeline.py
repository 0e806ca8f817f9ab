"""Pipeline parallelism (GPipe-style) over a named ``pipe`` mesh axis.

Each pipeline device holds ONE stage's parameters (stage = contiguous run
of layers, stacked on a leading axis and sharded over ``pipe``). The
schedule runs M + S - 1 ticks; at every tick each device applies its stage
to the microbatch in flight and collective-permutes activations to the
next stage — the EPAC analogy is the NoC's credit-based point-to-point
channels (collective-permute IS the point-to-point primitive).

This axis composes with the DP/TP meshes: a production layout would be
(pipe, data, model). The dry-run matrix keeps the assigned 2-D/3-D meshes,
so PP ships as a tested feature (tests/test_pipeline.py) rather than a
dry-run default — recorded in DESIGN.md.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh,
                   axis: str = "pipe"):
    """Run microbatches through S pipeline stages.

    stage_fn:     (params_slice, activation) -> activation (one stage).
    stage_params: pytree with leading dim S (sharded over ``axis``).
    x_micro:      (M, B_micro, ...) microbatches, replicated over ``axis``.
    Returns (M, B_micro, ...) outputs of the LAST stage.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]

    def local(params_l, xs):
        # params_l: (1, ...) my stage's params; xs: (M, B, ...) replicated
        me = jax.lax.axis_index(axis)
        p_mine = jax.tree.map(lambda t: t[0], params_l)
        n_ticks = M + S - 1

        def tick(carry, t):
            inflight, outputs = carry
            # which microbatch is at my stage this tick (GPipe diagonal)
            mb = t - me
            active = jnp.logical_and(mb >= 0, mb < M)
            # stage 0 injects from xs; others consume the permuted input
            inj = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(mb, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(me == 0, inj, inflight)
            y = stage_fn(p_mine, x_in)
            y = jnp.where(active, y, inflight)
            # last stage records finished microbatches
            outputs = jnp.where(
                jnp.logical_and(me == S - 1, active),
                jax.lax.dynamic_update_index_in_dim(
                    outputs, y, jnp.clip(mb, 0, M - 1), axis=0),
                outputs)
            # hand activations to the next stage (ring permute; the wrap
            # edge S-1 -> 0 carries garbage that stage 0 ignores)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outputs), None

        # the carry becomes device-varying after the first tick, and
        # shard_map checks manual-axis variance of scan carries
        zero = jax.lax.pcast(jnp.zeros_like(xs[0]), axis, to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), axis, to="varying")
        (_, outputs), _ = jax.lax.scan(
            tick, (zero, outs0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast them
        outputs = jnp.where(me == S - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )(stage_params, x_micro)


def stack_stages(layer_params_list, n_stages: int):
    """Group a list of per-layer param pytrees into S stacked stages."""
    L = len(layer_params_list)
    assert L % n_stages == 0, (L, n_stages)
    per = L // n_stages
    stages = []
    for s in range(n_stages):
        chunk = layer_params_list[s * per:(s + 1) * per]
        stages.append(jax.tree.map(lambda *xs: jnp.stack(xs), *chunk))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stages)


def make_stage_fn(layer_fn: Callable):
    """Lift a single-layer fn into a stage fn over stacked layer params."""

    def stage_fn(stage_params, x):
        def body(xc, lp):
            return layer_fn(lp, xc), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    return stage_fn
