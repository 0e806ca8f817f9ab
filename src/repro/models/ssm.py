"""Recurrent blocks: xLSTM's mLSTM/sLSTM and RecurrentGemma's RG-LRU.

mLSTM uses a **chunkwise-parallel** form for training/prefill (linear in
sequence length — the reason xlstm/recurrentgemma run the long_500k cell)
and an O(1)-state recurrent step for decode. The two forms are
algebraically identical (tests/test_ssm.py checks chunkwise == step-by-
step). All gate math is log-space stabilized (the m-state of the xLSTM
paper).

sLSTM has a true recurrent matrix R and "cannot be parallelized" (xLSTM
paper) — it is a lax.scan over time, block-diagonal per head.

RG-LRU is a gated diagonal linear recurrence; training/prefill lower
through kernels/ops.rglru_scan (STX chunked-scan kernel on TPU).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers


# ---------------------------------------------------------------------------
# mLSTM (matrix-memory LSTM)
# ---------------------------------------------------------------------------


def init_mlstm_block(key, cfg, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    return {
        "w_up": layers.truncated_normal_init(ks[0], (d, 2 * d), dtype),
        "conv": layers.init_conv1d(ks[1], d, 4, dtype),
        "wq": layers.truncated_normal_init(ks[2], (d, d), dtype),
        "wk": layers.truncated_normal_init(ks[3], (d, d), dtype),
        "wv": layers.truncated_normal_init(ks[4], (d, d), dtype),
        "w_if": layers.truncated_normal_init(ks[5], (d, 2 * cfg.n_heads), dtype),
        # Positive forget bias => long memory at init (standard xLSTM init).
        "b_if": jnp.concatenate([jnp.zeros((cfg.n_heads,), dtype),
                                 jnp.linspace(3.0, 6.0, cfg.n_heads).astype(dtype)]),
        "gn_scale": jnp.ones((d,), dtype),
        "w_down": layers.truncated_normal_init(ks[6], (d, d), dtype),
    }


def mlstm_chunkwise(q, k, v, ig, fg, chunk: int = 256, state=None,
                    unroll: bool = False):
    """Chunkwise-parallel stabilized mLSTM.

    q,k,v: (B, H, S, hd); ig/fg: (B, H, S) raw gate pre-activations.
    Returns (h (B,H,S,hd), final_state (C, n, m)).
    """
    B, H, S, hd = q.shape
    S0 = S
    pad = (-S) % chunk
    if pad:
        # VLA tail padding: pad gates so pads are no-ops on the carried
        # state (input gate -> 0 weight, forget gate -> keep).
        zp = [(0, 0), (0, 0), (0, pad), (0, 0)]
        gp = [(0, 0), (0, 0), (0, pad)]
        q, k, v = (jnp.pad(t, zp) for t in (q, k, v))
        ig = jnp.pad(ig, gp, constant_values=-1e30)
        fg = jnp.pad(fg, gp, constant_values=30.0)
        S = S + pad
    L, N = chunk, S // chunk
    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(fg.astype(jnp.float32))     # log forget
    li = ig.astype(jnp.float32)                          # log input

    rs = lambda x: x.reshape(B, H, N, L, *x.shape[3:]).swapaxes(0, 2).swapaxes(1, 2)
    # -> (N, B, H, L, ...)
    qc, kc, vc = rs(qf), rs(kf), rs(vf)
    lfc, lic = rs(lf), rs(li)

    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    tri = jnp.tril(jnp.ones((L, L), bool))

    def chunk_step(carry, inp):
        C, n, m = carry
        qj, kj, vj, lfj, lij = inp                      # (B, H, L, ...)
        a = jnp.cumsum(lfj, axis=-1)                    # inclusive decay sums
        A = a[..., -1:]                                 # (B, H, 1)
        # Intra-chunk log weights D_ij = a_i - a_j + li_j (j <= i).
        D = a[..., :, None] - a[..., None, :] + lij[..., None, :]
        D = jnp.where(tri, D, -jnp.inf)
        m_intra = jnp.max(D, axis=-1)                   # (B, H, L)
        m_inter = m[..., None] + a                      # (B, H, L)
        m_i = jnp.maximum(m_inter, m_intra)
        m_i = jnp.maximum(m_i, -1e30)                   # keep finite
        Sij = jnp.einsum("bhid,bhjd->bhij", qj, kj) * jnp.exp(D - m_i[..., None])
        inter_w = jnp.exp(m_inter - m_i)                # (B, H, L)
        num = (inter_w[..., None] * jnp.einsum("bhid,bhde->bhie", qj, C)
               + jnp.einsum("bhij,bhje->bhie", Sij, vj))
        den = (inter_w * jnp.einsum("bhid,bhd->bhi", qj, n)
               + jnp.sum(Sij, axis=-1))
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_i))[..., None]
        # Carry update.
        m_k = A - a + lij                               # gate weight per key
        m_new = jnp.maximum(m[..., None] + A, jnp.max(m_k, -1, keepdims=True))[..., 0]
        carry_w = jnp.exp(m[..., None] + A - m_new[..., None])[..., 0]
        kw = jnp.exp(m_k - m_new[..., None])            # (B, H, L)
        C = carry_w[..., None, None] * C + jnp.einsum("bhj,bhjd,bhje->bhde", kw, kj, vj)
        n = carry_w[..., None] * n + jnp.einsum("bhj,bhjd->bhd", kw, kj)
        return (C, n, m_new), h

    (C, n, m), hs = jax.lax.scan(chunk_step, (C0, n0, m0),
                                 (qc, kc, vc, lfc, lic),
                                 unroll=True if unroll else 1)
    h = hs.swapaxes(0, 2).swapaxes(0, 1).reshape(B, H, S, hd)
    return h[:, :, :S0].astype(q.dtype), (C, n, m)


def mlstm_step(q, k, v, ig, fg, state):
    """Single-token recurrent mLSTM. q,k,v: (B, H, hd); gates (B, H)."""
    C, n, m = state
    hd = q.shape[-1]
    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(fg.astype(jnp.float32))
    li = ig.astype(jnp.float32)
    m_new = jnp.maximum(lf + m, li)
    fw = jnp.exp(lf + m - m_new)
    iw = jnp.exp(li - m_new)
    C = fw[..., None, None] * C + iw[..., None, None] * (kf[..., :, None] * vf[..., None, :])
    n = fw[..., None] * n + iw[..., None] * kf
    num = jnp.einsum("bhd,bhde->bhe", qf, C)
    den = jnp.einsum("bhd,bhd->bh", qf, n)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h.astype(q.dtype), (C, n, m_new)


def _mlstm_qkv_gates(params, cfg, xn, conv_state=None, length=None):
    B, S, d = xn.shape
    H = cfg.n_heads
    hd = d // H
    up = xn @ params["w_up"]
    c, z = jnp.split(up, 2, axis=-1)
    cc, conv_state = layers.apply_conv1d(params["conv"], c, conv_state)
    if length is not None:
        # Right-padded prefill: the emitted carry must hold the last
        # width-1 REAL conv inputs, not the padded tail.
        conv_state = layers.conv_state_at(
            c, params["conv"]["w"].shape[0], length)
    cc = jax.nn.silu(cc)
    split_heads = lambda t: t.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    q = split_heads(cc @ params["wq"])
    k = split_heads(cc @ params["wk"])
    v = split_heads(c @ params["wv"])
    gates = c @ params["w_if"] + params["b_if"]
    ig, fg = jnp.split(gates, 2, axis=-1)               # (B, S, H)
    return q, k, v, ig.transpose(0, 2, 1), fg.transpose(0, 2, 1), z, conv_state


def freeze_gates_past(ig, fg, length):
    """Mask mLSTM gate pre-activations past each row's true length so the
    chunkwise scan carries state FROZEN at ``length`` — the trick
    ``mlstm_chunkwise`` plays on its own chunk-tail padding, made exact:
    input gate -> -1e30 (zero key weight) and forget gate -> 1e30
    (log_sigmoid(1e30) == -0.0, so pad steps decay nothing). The carried
    (C, n, m) then equals the state at ``length``; pad-position outputs
    are garbage and must not be read. ig/fg: (B, H, S); length: (B,)."""
    pad = jnp.arange(ig.shape[-1])[None, None, :] >= length[:, None, None]
    return (jnp.where(pad, -1e30, ig).astype(ig.dtype),
            jnp.where(pad, 1e30, fg).astype(fg.dtype))


def apply_mlstm_block(params, cfg, xn, chunk: int = 256, unroll: bool = False):
    """Full-sequence mLSTM mixing (pre-normed input xn). Returns delta."""
    B, S, d = xn.shape
    q, k, v, ig, fg, z, _ = _mlstm_qkv_gates(params, cfg, xn)
    h, _ = mlstm_chunkwise(q, k, v, ig, fg, chunk=min(chunk, S), unroll=unroll)
    h = h.transpose(0, 2, 1, 3).reshape(B, S, d)
    h = layers.group_norm(h, params["gn_scale"], cfg.n_heads)
    return (h * jax.nn.silu(z)) @ params["w_down"]


def init_mlstm_cache(cfg, batch, dtype):
    H, d = cfg.n_heads, cfg.d_model
    hd = d // H
    return {
        "C": jnp.zeros((batch, H, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, H, hd), jnp.float32),
        "m": jnp.full((batch, H), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, 3, d), dtype),
    }


def apply_mlstm_decode(params, cfg, xn, cache):
    B, _, d = xn.shape
    q, k, v, ig, fg, z, conv_state = _mlstm_qkv_gates(
        params, cfg, xn, cache["conv"])
    h, (C, n, m) = mlstm_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                              ig[:, :, 0], fg[:, :, 0],
                              (cache["C"], cache["n"], cache["m"]))
    h = h.reshape(B, 1, d)
    h = layers.group_norm(h, params["gn_scale"], cfg.n_heads)
    out = (h * jax.nn.silu(z)) @ params["w_down"]
    return out, {"C": C, "n": n, "m": m, "conv": conv_state}


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory LSTM with recurrent matrix; sequential by design)
# ---------------------------------------------------------------------------


def init_slstm_block(key, cfg, dtype):
    d, H = cfg.d_model, cfg.n_heads
    hd = d // H
    ks = jax.random.split(key, 5)
    ffp = int(round(d * 4 / 3 / 64)) * 64 or 64          # xLSTM pf=4/3 FFN
    return {
        "w_zifo": layers.truncated_normal_init(ks[0], (d, 4 * d), dtype),
        "r_zifo": layers.truncated_normal_init(
            ks[1], (4, H, hd, hd), dtype, stddev=1.0 / math.sqrt(hd)),
        "b_zifo": jnp.concatenate([
            jnp.zeros((2 * d,), dtype),
            jnp.full((d,), 4.0, dtype),                  # forget bias
            jnp.zeros((d,), dtype)]),
        "gn_scale": jnp.ones((d,), dtype),
        "ff": layers.init_mlp(ks[2], d, ffp, dtype, gated=True),
    }


def _slstm_cell(params, cfg, x_part, state):
    """One sLSTM step. x_part: (B, 4d) precomputed input projection."""
    h, c, n, m = state                                   # h: (B, H, hd)
    B = x_part.shape[0]
    H = cfg.n_heads
    d = cfg.d_model
    hd = d // H
    rec = jnp.einsum("bhd,ghde->bghe", h, params["r_zifo"].astype(jnp.float32))
    rec = rec.reshape(B, 4 * d)
    pre = x_part.astype(jnp.float32) + rec + params["b_zifo"].astype(jnp.float32)
    zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
    zt = jnp.tanh(zt).reshape(B, H, hd)
    ot = jax.nn.sigmoid(ot).reshape(B, H, hd)
    li = it.reshape(B, H, hd)
    lf = jax.nn.log_sigmoid(ft).reshape(B, H, hd)
    m_new = jnp.maximum(lf + m, li)
    fw = jnp.exp(lf + m - m_new)
    iw = jnp.exp(li - m_new)
    c = fw * c + iw * zt
    n = fw * n + iw
    hidden = ot * c / jnp.maximum(n, jnp.exp(-m_new))
    return hidden, (hidden, c, n, m_new)


def apply_slstm_block(params, cfg, xn):
    """Sequential sLSTM over (B, S, d) pre-normed input. Returns delta."""
    B, S, d = xn.shape
    H = cfg.n_heads
    hd = d // H
    x_parts = xn @ params["w_zifo"]                      # (B, S, 4d)
    state = (jnp.zeros((B, H, hd), jnp.float32),) * 3 + (
        jnp.full((B, H, hd), -1e30, jnp.float32),)

    def step(st, xp):
        hidden, st = _slstm_cell(params, cfg, xp, st)
        return st, hidden

    _, hs = jax.lax.scan(step, state, jnp.moveaxis(x_parts, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d).astype(xn.dtype)
    h = layers.group_norm(h, params["gn_scale"], H)
    return layers.apply_mlp(params["ff"], h, "gelu")


def init_slstm_cache(cfg, batch, dtype):
    H, d = cfg.n_heads, cfg.d_model
    hd = d // H
    z = jnp.zeros((batch, H, hd), jnp.float32)
    return {"h": z, "c": z, "n": z,
            "m": jnp.full((batch, H, hd), -1e30, jnp.float32)}


def apply_slstm_decode(params, cfg, xn, cache):
    B, _, d = xn.shape
    xp = (xn @ params["w_zifo"])[:, 0]
    hidden, (h, c, n, m) = _slstm_cell(
        params, cfg, xp, (cache["h"], cache["c"], cache["n"], cache["m"]))
    out = hidden.reshape(B, 1, d).astype(xn.dtype)
    out = layers.group_norm(out, params["gn_scale"], cfg.n_heads)
    out = layers.apply_mlp(params["ff"], out, "gelu")
    return out, {"h": h, "c": c, "n": n, "m": m}


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma / Griffin recurrent block)
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def init_rglru_block(key, cfg, dtype):
    d = cfg.d_model
    dr = cfg.rnn_width or d
    ks = jax.random.split(key, 7)
    # Lambda init so that a = exp(-c*softplus(L)) spans ~(0.9, 0.999).
    u = jax.random.uniform(ks[0], (dr,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / _RGLRU_C))
    return {
        "w_x": layers.truncated_normal_init(ks[1], (d, dr), dtype),
        "w_gate": layers.truncated_normal_init(ks[2], (d, dr), dtype),
        "conv": layers.init_conv1d(ks[3], dr, 4, dtype),
        "lam": lam.astype(jnp.float32),
        "w_a": layers.truncated_normal_init(ks[4], (dr, dr), dtype),
        "b_a": jnp.zeros((dr,), dtype),
        "w_i": layers.truncated_normal_init(ks[5], (dr, dr), dtype),
        "b_i": jnp.zeros((dr,), dtype),
        "w_out": layers.truncated_normal_init(ks[6], (dr, d), dtype),
    }


def _rglru_coeffs(params, y):
    """Gated decay a_t and driven input b_t from conv output y (f32)."""
    yf = y.astype(jnp.float32)
    r = jax.nn.sigmoid(yf @ params["w_a"].astype(jnp.float32)
                       + params["b_a"].astype(jnp.float32))
    i = jax.nn.sigmoid(yf @ params["w_i"].astype(jnp.float32)
                       + params["b_i"].astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(params["lam"]) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    return a, beta * (i * yf)


def apply_rglru_block(params, cfg, xn, kernel_mode="auto", shard=None):
    """Full-sequence Griffin recurrent mixing. Returns delta."""
    gate = jax.nn.gelu(xn @ params["w_gate"], approximate=True)
    xb = xn @ params["w_x"]
    y, _ = layers.apply_conv1d(params["conv"], xb)
    a, b = _rglru_coeffs(params, y)
    h = kops.rglru_scan(a, b, mode=kernel_mode,
                        sharding=shard).astype(xn.dtype)
    return (gate * h) @ params["w_out"]


def init_rglru_cache(cfg, batch, dtype):
    dr = cfg.rnn_width or cfg.d_model
    return {"h": jnp.zeros((batch, dr), jnp.float32),
            "conv": jnp.zeros((batch, 3, dr), dtype)}


def apply_rglru_decode(params, cfg, xn, cache):
    gate = jax.nn.gelu(xn @ params["w_gate"], approximate=True)
    xb = xn @ params["w_x"]
    y, conv_state = layers.apply_conv1d(params["conv"], xb, cache["conv"])
    a, b = _rglru_coeffs(params, y)
    h = a[:, 0] * cache["h"] + b[:, 0]
    out = (gate * h[:, None].astype(xn.dtype)) @ params["w_out"]
    return out, {"h": h, "conv": conv_state}
