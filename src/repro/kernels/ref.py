"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel in this package must match its oracle here (assert_allclose in
tests/test_kernels_*.py across shape/dtype sweeps). The oracles are also
the CPU lowering path for the dry-run: identical math, so HLO FLOP/byte
counts stay representative of the kernelized TPU build.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# STX matmul
# ---------------------------------------------------------------------------


def matmul(x, w, out_dtype=None):
    """(..., K) @ (K, N), f32 accumulation."""
    out = jnp.einsum("...k,kn->...n", x, w, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


# ---------------------------------------------------------------------------
# STX stencil (the SPU workload: structured-grid, fixed pattern)
# ---------------------------------------------------------------------------


def stencil2d(x, weights):
    """3x3 weighted stencil on (..., M, N); zero boundary (halo = 0)."""
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)])
    out = jnp.zeros_like(x)
    for di in range(3):
        for dj in range(3):
            out = out + weights[di, dj] * jax.lax.slice_in_dim(
                jax.lax.slice_in_dim(xp, di, di + x.shape[-2], axis=-2),
                dj, dj + x.shape[-1], axis=-1)
    return out


def stencil3d(x, weights):
    """3x3x3 weighted stencil on (..., D, M, N); zero boundary."""
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(1, 1)] * 3)
    out = jnp.zeros_like(x)
    for dd in range(3):
        for di in range(3):
            for dj in range(3):
                sl = xp[..., dd:dd + x.shape[-3], di:di + x.shape[-2],
                        dj:dj + x.shape[-1]]
                out = out + weights[dd, di, dj] * sl
    return out


def seven_point_weights(dtype=jnp.float32):
    """Classic 7-point Laplacian weights as a 3x3x3 mask."""
    w = np.zeros((3, 3, 3), dtype=np.float64)
    w[1, 1, 1] = -6.0
    for d in ((0, 1, 1), (2, 1, 1), (1, 0, 1), (1, 2, 1), (1, 1, 0), (1, 1, 2)):
        w[d] = 1.0
    return jnp.asarray(w, dtype)


def five_point_weights(dtype=jnp.float32):
    w = np.zeros((3, 3), dtype=np.float64)
    w[1, 1] = -4.0
    w[0, 1] = w[2, 1] = w[1, 0] = w[1, 2] = 1.0
    return jnp.asarray(w, dtype)


# ---------------------------------------------------------------------------
# Flash attention (GQA / causal / sliding-window)
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    q_offset=0):
    """Oracle attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D). GQA maps query head h to
    kv head h // (Hq // Hkv). ``window`` (if set) restricts attention to
    the last ``window`` positions (SWA). ``q_offset`` positions queries at
    absolute position q_offset + i (decode: Sq=1, q_offset=pos).
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((Sq, k.shape[2]), bool)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows (can happen with tiny windows) -> zeros, not NaN.
    probs = jnp.where(jnp.any(mask, -1)[None, None, :, None], probs, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vx.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged decode attention (block-pool KV cache)
# ---------------------------------------------------------------------------


def _gather_dequant(pool, scale_pool, block_table, B, S, Hkv, D,
                    layer=None):
    """Gather pool blocks into (B, S, Hkv, D) f32 sequences, applying the
    per-(token, head) dequant scales when the pool is quantized. With
    ``layer`` the pools are layer stacks, gathered at that layer."""
    idx = block_table if layer is None else (layer, block_table)
    x = pool[idx].reshape(B, S, Hkv, D).astype(jnp.float32)
    if scale_pool is not None:
        x = x * scale_pool[idx].reshape(B, S, Hkv)[..., None]
    return x


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           layer=None, window=None, scale=None,
                           k_scale=None, v_scale=None):
    """Oracle single-token decode attention over a block-paged KV cache.

    q: (B, Hq, D) — the query for the token at position ``lengths[b] - 1``.
    k_pool, v_pool: (NB, BS, Hkv, D) — shared pool of BS-token blocks;
    or the stacked (L, NB, BS, Hkv, D) pools of every layer, read at
    ``layer`` (an int32 scalar; ignored for a one-layer pool).
    block_table: (B, NBMAX) int32 — per-sequence logical->physical block map
    (entries past a sequence's last block may hold any in-range id).
    lengths: (B,) int32 — valid tokens per sequence (including the current
    token, whose K/V must already be written to the pool).
    ``window`` restricts attention to the last ``window`` positions (SWA).
    ``k_scale``/``v_scale``: the pools' f32 dequant scales, (NB, BS, Hkv)
    or stacked (L, NB, BS, Hkv), when the pool stores int8/fp8 payloads
    (None = fp pool, historical math). Returns (B, Hq, D) in q.dtype.
    """
    B, Hq, D = q.shape
    BS, Hkv = k_pool.shape[-3:-1]
    if k_pool.ndim == 4:
        layer = None
    group = Hq // Hkv
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    S = block_table.shape[1] * BS
    k = _gather_dequant(k_pool, k_scale, block_table, B, S, Hkv, D, layer)
    v = _gather_dequant(v_pool, v_scale, block_table, B, S, Hkv, D, layer)
    kx = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1)  # (B, Hq, S, D)
    vx = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    kpos = jnp.arange(S)[None, :]
    valid = kpos < lengths[:, None]
    if window is not None:
        valid = valid & (kpos >= lengths[:, None] - window)
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.any(valid, -1)[:, None, None], probs, 0.0)
    return jnp.einsum("bhs,bhsd->bhd", probs,
                      vx.astype(jnp.float32)).astype(q.dtype)


def paged_verify_attention(q, k_pool, v_pool, block_table, lengths, *,
                           window=None, scale=None, k_scale=None,
                           v_scale=None):
    """Oracle multi-query decode attention over a block-paged KV cache.

    The speculative-decode verify step: each sequence contributes a
    q-block of K+1 query rows for the positions ``lengths[b] + j``
    (j = 0..K), whose K/V must already be written to the pool. Row j
    attends positions < ``lengths[b] + j + 1`` — causal within the
    window, so the block-row j result is bit-equal to the single-query
    ``paged_decode_attention`` at length ``lengths[b] + j + 1``.

    q: (B, K1, Hq, D); pools: (NB, BS, Hkv, D); block_table: (B, NBMAX);
    lengths: (B,) int32 tokens cached BEFORE the verify window. ``window``
    restricts each row to its last ``window`` positions.
    ``k_scale``/``v_scale``: (NB, BS, Hkv) f32 dequant scales when the
    pool stores int8/fp8 payloads (None = fp pool). -> (B, K1, Hq, D).
    """
    B, K1, Hq, D = q.shape
    _, BS, Hkv, _ = k_pool.shape
    group = Hq // Hkv
    scale = float(scale) if scale is not None else float(1.0 / np.sqrt(D))
    S = block_table.shape[1] * BS
    k = _gather_dequant(k_pool, k_scale, block_table, B, S, Hkv, D)
    v = _gather_dequant(v_pool, v_scale, block_table, B, S, Hkv, D)
    kx = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1)  # (B, Hq, S, D)
    vx = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
    logits = jnp.einsum("bjhd,bhsd->bjhs", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    kpos = jnp.arange(S)[None, None, :]                      # (1, 1, S)
    limit = lengths[:, None, None] + 1 + jnp.arange(K1)[None, :, None]
    valid = kpos < limit                                     # (B, K1, S)
    if window is not None:
        valid = valid & (kpos >= limit - window)
    logits = jnp.where(valid[:, :, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.any(valid, -1)[:, :, None, None], probs, 0.0)
    return jnp.einsum("bjhs,bhsd->bjhd", probs,
                      vx.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# VRP compensated reductions (double-word = 2-term expansion)
# ---------------------------------------------------------------------------


def vrp_dot(x, y):
    """Double-word dot oracle via core.vrp at K=2 in the input dtype."""
    from repro.core import vrp
    from repro.core.precision import PrecisionEnv

    env = PrecisionEnv(compute_terms=2, base_dtype=str(x.dtype))
    e = vrp.dot(x, y, env)
    return e  # (2,) expansion [hi, lo]


def vrp_sum(x):
    from repro.core import vrp
    from repro.core.precision import PrecisionEnv

    env = PrecisionEnv(compute_terms=2, base_dtype=str(x.dtype))
    return vrp.sum_floats(x.reshape(-1), env)


# ---------------------------------------------------------------------------
# RG-LRU / diagonal linear recurrence scan
# ---------------------------------------------------------------------------


def linear_scan(a, x, h0=None):
    """h_t = a_t * h_{t-1} + x_t along axis 1. a, x: (B, T, D).

    Implemented as an associative scan (log-depth; the XLA-native form a
    TPU would run when not using the Pallas kernel; also makes its FLOPs
    visible to cost_analysis, unlike a while-loop scan).
    """
    if h0 is not None:
        x = x.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        aL, bL = left
        aR, bR = right
        return aL * aR, bL * aR + bR

    _, h = jax.lax.associative_scan(combine, (a, x), axis=1)
    return h
