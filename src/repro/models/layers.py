"""Shared model layers: norms, MLPs, rotary embeddings, initializers.

Covers the variation across the 10 assigned architectures:
  norms       — rmsnorm (llama family), layernorm (whisper), nonparametric
                (OLMo's non-parametric LN: no scale/bias)
  MLPs        — gated (SwiGLU: yi/qwen/olmo-style; GeGLU: gemma) and plain
                (whisper)
  positions   — RoPE (default), M-RoPE (qwen2-vl 3-D multimodal rotary),
                sinusoidal (whisper encoder), none (xLSTM)
All functions are pure; params are plain dict pytrees.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def truncated_normal_init(key, shape, dtype, stddev=None):
    stddev = stddev if stddev is not None else 1.0 / math.sqrt(shape[0])
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(kind: str, dim: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((dim,), dtype)}  # (1 + scale) convention
    if kind == "layernorm":
        return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}
    if kind == "nonparametric":
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (xf * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)
    if kind in ("layernorm", "nonparametric"):
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            xf = xf * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return xf.astype(dt)
    raise ValueError(kind)


def group_norm(x, scale, groups: int, eps: float = 1e-6):
    """Per-head group norm (xLSTM cell output normalization)."""
    dt = x.dtype
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, groups, d // groups)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    xf = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (xf * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACT = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


def init_mlp(key, d_model: int, d_ff: int, dtype, gated: bool = True,
             out_dim: int | None = None):
    out_dim = out_dim or d_model
    ks = jax.random.split(key, 3)
    p = {"w_up": truncated_normal_init(ks[0], (d_model, d_ff), dtype),
         "w_down": truncated_normal_init(ks[1], (d_ff, out_dim), dtype)}
    if gated:
        p["w_gate"] = truncated_normal_init(ks[2], (d_model, d_ff), dtype)
    return p


def apply_mlp(params, x, activation: str = "silu"):
    act = _ACT[activation]
    up = x @ params["w_up"]
    if "w_gate" in params:
        up = act(x @ params["w_gate"]) * up
    else:
        up = act(up)
    return up @ params["w_down"]


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------


def _rope_angles(positions, head_dim: int, theta: float):
    """positions (..., S) -> cos/sin (..., S, head_dim/2), f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    B, S, H, D = x.shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (B, S))
    cos, sin = _rope_angles(positions, D, theta)      # (B, S, D/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta: float = 10000.0):
    """Qwen2-VL multimodal RoPE. positions3: (3, B, S) [t, h, w] ids.

    ``sections`` partitions the head_dim/2 frequency slots among the three
    position streams (e.g. (16, 24, 24) for D=128). Text tokens carry
    identical t/h/w ids, reducing to standard RoPE.
    """
    B, S, H, D = x.shape
    half = D // 2
    assert sum(sections) == half, (sections, half)
    cos_parts, sin_parts = [], []
    start = 0
    for stream, sec in enumerate(sections):
        freqs = 1.0 / (theta ** (jnp.arange(start, start + sec, dtype=jnp.float32) / half))
        ang = positions3[stream][..., None].astype(jnp.float32) * freqs  # (B,S,sec)
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        start += sec
    cos = jnp.concatenate(cos_parts, -1)[:, :, None, :]
    sin = jnp.concatenate(sin_parts, -1)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embed(positions, d: int, dtype=jnp.float32):
    """Whisper-style sinusoidal embeddings at (possibly traced) positions.

    positions: (...,) int -> (..., d).

    Built as one ``where``-selected table rather than
    ``concatenate([sin, cos])``: when a downstream matmul operand is
    sharded on d, the SPMD partitioner miscompiles the
    concat-on-the-sharded-axis pattern (device halves glued back in the
    wrong order — sharded encoder outputs were off by |sin - cos|).
    The two forms are bitwise identical; only the where form survives
    partitioning.
    """
    half = d // 2
    dim = jnp.arange(half, dtype=jnp.float32)
    inv = jnp.exp(-math.log(10000.0) * dim / max(half - 1, 1))
    idx = jnp.arange(d)
    ang = positions[..., None].astype(jnp.float32) * inv[idx % half]
    return jnp.where(idx < half, jnp.sin(ang), jnp.cos(ang)).astype(dtype)


# ---------------------------------------------------------------------------
# Vocab-parallel embedding lookup (Megatron-style)
# ---------------------------------------------------------------------------


def vocab_parallel_lookup(table, tokens, shard):
    """Gather rows of a vocab-sharded table without GSPMD's one-hot
    rewrite (observed: a (tokens, V) one-hot matmul costing ~70 GB temp
    and 1.4e13 bogus FLOPs/device on the 256-chip mesh).

    Each model-shard gathers ids that fall in its vocab range, zeros the
    rest, and a psum over the TP axis assembles the embeddings — the
    uncore analogy: the L2 slice owning the address responds, the NoC
    merges. Falls back to a plain take when V doesn't divide |tp|
    (whisper's 51865) or there is no mesh.
    """
    if shard is None or getattr(shard, "layout", "2d") != "2d":
        # fsdp layout shards the table on d: the row gather is local.
        return jnp.take(table, tokens, axis=0)
    V = table.shape[0]
    tp = shard.tp_axis
    tp_size = shard.tp_size
    if V % tp_size != 0:
        return jnp.take(table, tokens, axis=0)
    from jax.sharding import PartitionSpec as P

    v_loc = V // tp_size

    def local(tbl, ids):
        lo = jax.lax.axis_index(tp) * v_loc
        loc = ids - lo
        valid = jnp.logical_and(loc >= 0, loc < v_loc)
        g = jnp.take(tbl, jnp.clip(loc, 0, v_loc - 1), axis=0)
        g = jnp.where(valid[..., None], g, jnp.zeros((), g.dtype))
        return jax.lax.psum(g, tp)

    dp = shard.dp_axes
    batch_axes = dp if tokens.shape[0] % shard.dp_size == 0 else None
    return jax.shard_map(
        local, mesh=shard.mesh,
        in_specs=(P(tp, None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None))(table, tokens)


# ---------------------------------------------------------------------------
# Causal depthwise temporal conv (Griffin / xLSTM front conv)
# ---------------------------------------------------------------------------


def init_conv1d(key, dim: int, width: int, dtype):
    return {"w": truncated_normal_init(key, (width, dim), dtype, stddev=0.1),
            "b": jnp.zeros((dim,), dtype)}


def conv_state_at(x, width, length):
    """Causal-conv carry state at a traced per-row offset.

    x: (B, S, D) conv INPUTS whose first ``length[b]`` positions are real
    (right-padded prefill); length: (B,) int32. Returns the
    (B, width-1, D) tail ``apply_conv1d`` would carry had row b stopped
    at ``length[b]`` — the last width-1 real inputs, zero-prefixed for
    rows shorter than the kernel.
    """
    B = x.shape[0]
    xc = jnp.concatenate(
        [jnp.zeros((B, width - 1) + x.shape[2:], x.dtype), x], axis=1)
    idx = length[:, None] + jnp.arange(width - 1)[None, :]
    return xc[jnp.arange(B)[:, None], idx]


def apply_conv1d(params, x, state=None):
    """Causal depthwise conv. x: (B, S, D); state: (B, width-1, D) or None.

    Returns (y, new_state) where new_state holds the last width-1 inputs.
    """
    w = params["w"]
    width = w.shape[0]
    if state is None:
        state = jnp.zeros(x[:, :1].shape[:1] + (width - 1,) + x.shape[2:], x.dtype)
    xc = jnp.concatenate([state, x], axis=1)
    y = sum(xc[:, i:i + x.shape[1]] * w[i] for i in range(width))
    y = y + params["b"]
    return y.astype(x.dtype), xc[:, -(width - 1):]
