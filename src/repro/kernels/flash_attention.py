"""Blocked (flash) attention — the LM hot path on the STX execution tile.

Online-softmax attention with VMEM-resident running (max, sum, acc) state,
GQA head mapping, causal and sliding-window (SWA) masking with block-level
FLOP skipping. Grid = (batch*heads, q_blocks, kv_blocks), kv innermost
sequential; the (m, l, acc) scratch plays the TCDM role and the kv-block
skip predicate plays the SPU's static-access-pattern pruning.

Working set at defaults (bq=bk=128, D<=256, f32 acc):
  q 128x256x4 + k/v 2x128x256x4 + acc 128x256x4 = ~0.5 MB << 16 MB VMEM,
leaving headroom for Pallas's double buffering (Gazillion-style outstanding
block fetches).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Kernel constants are typed f32/i32: with jax_enable_x64 on, a Python
# float passed to jnp.where becomes an f64 operand and a Python int in a
# floor division or an index map an i64 one, which Mosaic cannot lower.
MASK_VALUE = np.float32(-0.7 * np.finfo(np.float32).max)
ZERO = np.float32(0.0)
ONE = np.float32(1.0)
I0 = np.int32(0)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, causal, window, kv_len, block_q, block_k, nk):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level skip: never spend MXU cycles on fully-masked kv blocks.
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = kj * block_k
    needed = k_lo < kv_len
    if causal:
        needed = jnp.logical_and(needed, k_lo <= q_hi)
    if window is not None:
        k_hi = k_lo + block_k - 1
        needed = jnp.logical_and(needed, k_hi > q_lo - window)

    @pl.when(needed)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                      # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # (bq, bk)
        p = jnp.where(mask, p, ZERO)
        corr = jnp.exp(m_prev - m_new)            # (bq, 1)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _store():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == ZERO, ONE, l)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           kv_len=None, block_q=128, block_k=128,
                           interpret=False):
    """q: (B, Hq, Sq, D), k/v: (B, Hkv, Skv, D); Sq % bq == Skv % bk == 0.

    ``kv_len`` masks out tail padding of the kv sequence (ops.py pads).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    kv_len = Skv if kv_len is None else kv_len
    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Skv, D)
    vr = v.reshape(B * Hkv, Skv, D)
    grid = (B * Hq, Sq // block_q, Skv // block_k)

    hq, hkv, grp = np.int32(Hq), np.int32(Hkv), np.int32(group)

    def kv_map(bh, qi, kj):
        b, h = bh // hq, bh % hq
        return b * hkv + h // grp, kj, I0

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, kv_len=kv_len, block_q=block_q,
                          block_k=block_k, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, I0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, kj: (bh, qi, I0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)
