"""VRP compensated-reduction kernel — double-word dot/sum on the fly.

The VRP tile's HPDcache streams operands to the VPFPU at 16 B/cycle and the
tile is "typically limited by memory bandwidth rather than compute" — i.e.
extended precision is nearly free when fused into the streaming reduction.
This kernel is the TPU version of that claim: a single pass over HBM
accumulating a **two-term (double-word) expansion per vector lane** using
error-free transforms, so the extra precision costs only VPU flops (the
memory roofline term is unchanged vs a naive dot).

TPU has no f64, so the base dtype is f32 (2 x 24-bit significands ~ 48
bits, the TPU-native extended format; see DESIGN.md §2 item 4). Lane
partials (8, 128, 2) are finalized by ops.py with a compensated tree —
the same split the silicon makes between the streaming pipelines and the
full-width normalization stage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vrp import two_prod, two_sum

_F32_SPLITTER = float(2**12 + 1)


def _accum(s_ref, c_ref, val):
    """Neumaier accumulation of ``val`` into (s, c) per lane."""
    s, err = two_sum(s_ref[...], val)
    s_ref[...] = s
    c_ref[...] = c_ref[...] + err


def _dot_kernel(x_ref, y_ref, o_ref, s_ref, c_ref, *, nb):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    p, e = two_prod(x_ref[0], y_ref[0], splitter=_F32_SPLITTER)
    _accum(s_ref, c_ref, p)
    c_ref[...] = c_ref[...] + e  # product error is already second-order

    @pl.when(j == nb - 1)
    def _store():
        o_ref[0, :, :, 0] = s_ref[...]
        o_ref[0, :, :, 1] = c_ref[...]


def vrp_dot_pallas(x, y, *, interpret=False):
    """Compensated dot of flat f32 vectors; n % 1024 == 0 (ops.py pads).

    Returns per-lane expansions (8, 128, 2); finalize with ops.finalize.
    """
    n = x.shape[0]
    assert n % 1024 == 0, "pad to lane multiple (VLA discipline) in ops.py"
    nb = n // 1024
    xr = x.reshape(nb, 8, 128)
    yr = y.reshape(nb, 8, 128)
    return pl.pallas_call(
        functools.partial(_dot_kernel, nb=nb),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 8, 128), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, 8, 128), lambda j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, 128, 2), lambda j: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 8, 128, 2), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(xr, yr)[0]


def _sum_kernel(x_ref, o_ref, s_ref, c_ref, *, nb):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        c_ref[...] = jnp.zeros_like(c_ref)

    _accum(s_ref, c_ref, x_ref[0])

    @pl.when(j == nb - 1)
    def _store():
        o_ref[0, :, :, 0] = s_ref[...]
        o_ref[0, :, :, 1] = c_ref[...]


def vrp_sum_pallas(x, *, interpret=False):
    """Compensated sum of a flat f32 vector; n % 1024 == 0."""
    n = x.shape[0]
    assert n % 1024 == 0
    nb = n // 1024
    return pl.pallas_call(
        functools.partial(_sum_kernel, nb=nb),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, 8, 128), lambda j: (j, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, 128, 2), lambda j: (0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 8, 128, 2), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((8, 128), jnp.float32),
            pltpu.VMEM((8, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x.reshape(nb, 8, 128))[0]
