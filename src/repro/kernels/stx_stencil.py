"""STX/SPU stencil kernel — halo-blocked structured-grid update.

The SPU co-processors accelerate "stencil workloads with static access
patterns and local data dependencies" (7-point / 27-point stencils,
diffusion/wave time-stepping). TPU adaptation: each grid cell computes an
output tile from an input tile *plus halo*, streamed HBM->VMEM via
element-indexed BlockSpecs (`pl.Element`) over a once-padded input — the
static access pattern is entirely in the index maps, exactly the SPU's
hardware address generation.

General 3x3 (2-D) and 3x3x3 (3-D) weighted stencils cover the paper's
5/9-point and 7/27-point cases (zero weights prune FLOPs at trace time).
Weights arrive via SMEM — the SPU's configuration registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _halo_spec(block_shape, index_map):
    """Element-indexed BlockSpec: every dimension indexes elements, not
    blocks, so the map can address a tile plus its halo."""
    return pl.BlockSpec(tuple(pl.Element(b) for b in block_shape),
                        index_map)


def _st2d_kernel(w_ref, x_ref, o_ref):
    xb = x_ref[...]  # (bm + 2, bn + 2) with halo
    acc = jnp.zeros_like(o_ref)
    bm, bn = o_ref.shape
    for di in range(3):
        for dj in range(3):
            acc = acc + w_ref[di, dj] * jax.lax.dynamic_slice(xb, (di, dj), (bm, bn))
    o_ref[...] = acc


def stencil2d_pallas(x, weights, *, block_m=128, block_n=128, interpret=False):
    """3x3 stencil on (M, N), zero boundary. M, N multiples of block."""
    m, n = x.shape
    assert m % block_m == 0 and n % block_n == 0
    xp = jnp.pad(x, 1)
    grid = (m // block_m, n // block_n)
    return pl.pallas_call(
        _st2d_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # weights (3, 3)
            _halo_spec((block_m + 2, block_n + 2),
                       lambda i, j: (i * block_m, j * block_n)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(weights, xp)


def _st3d_kernel(w_ref, x_ref, o_ref):
    xb = x_ref[...]  # (bd + 2, bm + 2, bn + 2)
    acc = jnp.zeros_like(o_ref)
    bd, bm, bn = o_ref.shape
    for dd in range(3):
        for di in range(3):
            for dj in range(3):
                acc = acc + w_ref[dd, di, dj] * jax.lax.dynamic_slice(
                    xb, (dd, di, dj), (bd, bm, bn))
    o_ref[...] = acc


def stencil3d_pallas(x, weights, *, block_d=8, block_m=128, block_n=128,
                     interpret=False):
    """3x3x3 stencil on (D, M, N), zero boundary."""
    d, m, n = x.shape
    assert d % block_d == 0 and m % block_m == 0 and n % block_n == 0
    xp = jnp.pad(x, 1)
    grid = (d // block_d, m // block_m, n // block_n)
    return pl.pallas_call(
        _st3d_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _halo_spec((block_d + 2, block_m + 2, block_n + 2),
                       lambda i, j, k: (i * block_d, j * block_m,
                                        k * block_n)),
        ],
        out_specs=pl.BlockSpec((block_d, block_m, block_n),
                               lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct((d, m, n), x.dtype),
        interpret=interpret,
    )(weights, xp)
