"""Chunked diagonal linear-recurrence kernel (RG-LRU / SSM state update).

h_t = a_t * h_{t-1} + x_t  — the core of RecurrentGemma's RG-LRU and the
normalizer updates of xLSTM. The access pattern is exactly an STX stencil
in time: static, local, streaming — so the same VMEM discipline applies.
Time is blocked; the carry h lives in a VMEM scratch across sequential
time blocks (grid dim 2, "arbitrary"), batch and feature dims are
parallel. Within a block the recurrence is a lax.fori_loop over VMEM-
resident data (FREP: repeated FP op sequence, no refetch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, x_ref, h0_ref, o_ref, h_ref, *, block_t: int):
    tj = pl.program_id(2)

    @pl.when(tj == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    def step(t, h):
        at = a_ref[:, t, :].astype(jnp.float32)
        xt = x_ref[:, t, :].astype(jnp.float32)
        h = at * h + xt
        o_ref[:, pl.ds(t, 1), :] = h[:, None, :].astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t, step, h_ref[...])


def rglru_scan_pallas(a, x, h0=None, *, block_b=8, block_t=128, block_d=128,
                      interpret=False):
    """a, x: (B, T, D) -> h: (B, T, D); h_t = a_t h_{t-1} + x_t.

    B % block_b == 0, T % block_t == 0, D % block_d == 0 (ops.py pads).
    """
    B, T, D = x.shape
    assert B % block_b == 0 and T % block_t == 0 and D % block_d == 0
    if h0 is None:
        h0 = jnp.zeros((B, D), x.dtype)
    grid = (B // block_b, D // block_d, T // block_t)
    return pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_t, block_d),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_b, block_t, block_d),
                         lambda i, j, t: (i, t, j)),
            pl.BlockSpec((block_b, block_d), lambda i, j, t: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_t, block_d),
                               lambda i, j, t: (i, t, j)),
        out_shape=jax.ShapeDtypeStruct((B, T, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x, h0)
