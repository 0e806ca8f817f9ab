"""STX tensor op — MXU-tiled matmul with explicit VMEM accumulation.

The STX tile computes tensor ops (matmul/conv) on Snitch clusters whose
defining features map 1:1 onto this kernel:

  SSR (stream semantic registers)  -> BlockSpec index_maps stream HBM
                                      blocks into VMEM without "core" code
  FREP (HW loop, no refetch)       -> the (i, j, k) Pallas grid
  TCDM scratchpad                  -> the f32 VMEM accumulator scratch
  DMA-core double buffering        -> Pallas's automatic block pipelining
                                      (Gazillion-style outstanding copies)

Block shapes default to (128, 128, 128): MXU-aligned, and a working set of
3 * 128*128*4 B = 192 kB — inside the paper's 64-256 kB TCDM budget per
cluster, deliberately (see DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def stx_matmul_pallas(x, w, *, block_m=128, block_n=128, block_k=128,
                      out_dtype=None, interpret=False):
    """(M, K) @ (K, N) -> (M, N). M, N, K must be multiples of the blocks
    (ops.py pads — VLA masked-tail discipline, see core/vec.py)."""
    m, kdim = x.shape
    _, n = w.shape
    assert m % block_m == 0 and n % block_n == 0 and kdim % block_k == 0
    grid = (m // block_m, n // block_n, kdim // block_k)
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
