"""Attention blocks: GQA/MQA, sliding-window, cross-attention, KV cache.

Training/prefill lower through the STX flash kernel path (kernels/ops.py);
decode attends a preallocated KV cache with positional masking. All
projections are bias-optional (qwen2-vl uses QKV bias), with optional
per-head QK-norm (qwen3).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.models import layers


def init_attention(key, cfg, dtype, *, cross: bool = False):
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": layers.truncated_normal_init(ks[0], (d, hq * hd), dtype),
        "wk": layers.truncated_normal_init(ks[1], (d, hkv * hd), dtype),
        "wv": layers.truncated_normal_init(ks[2], (d, hkv * hd), dtype),
        "wo": layers.truncated_normal_init(ks[3], (hq * hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = layers.init_norm("rmsnorm", hd, dtype)
        p["k_norm"] = layers.init_norm("rmsnorm", hd, dtype)
    return p


def _project_qkv(params, cfg, xq, xkv):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = xq @ params["wq"]
    k = xkv @ params["wk"]
    v = xkv @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, Sq, hq, hd)
    k = k.reshape(B, Skv, hkv, hd)
    v = v.reshape(B, Skv, hkv, hd)
    if "q_norm" in params:
        q = layers.apply_norm("rmsnorm", params["q_norm"], q)
        k = layers.apply_norm("rmsnorm", params["k_norm"], k)
    return q, k, v


def attend(params, cfg, x, positions, *, window=None, causal=True,
           mrope_positions=None, kernel_mode="auto", shard=None):
    """Full-sequence (train / prefill) self-attention. x: (B, S, d).
    ``shard``: the ``ShardCtx`` the kernel is split over, or None."""
    q, k, v = _project_qkv(params, cfg, x, x)
    if cfg.rope_style == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    out = kops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        mode=kernel_mode, sharding=shard)
    B, S, _ = x.shape
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
    return out @ params["wo"]


def attend_cross(params, cfg, x, kv_cache):
    """Cross-attention against precomputed encoder K/V (whisper decoder)."""
    B, Sq, _ = x.shape
    hq, hd = cfg.n_heads, cfg.head_dim
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, Sq, hq, hd).transpose(0, 2, 1, 3)
    k, v = kv_cache["k"], kv_cache["v"]  # (B, Hkv, Senc, hd)
    out = kops.flash_attention(q, k, v, causal=False, mode="ref")
    out = out.transpose(0, 2, 1, 3).reshape(B, Sq, hq * hd)
    return out @ params["wo"]


def attend_masked(params, cfg, x, lengths):
    """Bidirectional self-attention over a right-padded batch (the
    encoder stack under bucketed serving admission).

    x: (B, S, d) where only the first ``lengths[b]`` rows are real
    (the rest is frame-bucket padding). Pad KEYS are masked to -inf so
    they carry exact zero softmax mass; pad QUERY rows produce garbage
    nobody reads (the caller consumes encoder output only at real
    positions). Pure-jnp oracle math (kernels/ref.flash_attention with
    a key-validity mask) — the serving paths run mode='ref' and the
    engine/oracle identity tests rely on matching numerics.
    """
    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(params, cfg, x, x)
    group = hq // hkv
    scale = float(1.0 / np.sqrt(hd))
    kx = jnp.repeat(k.transpose(0, 2, 1, 3), group, axis=1)
    vx = jnp.repeat(v.transpose(0, 2, 1, 3), group, axis=1)
    logits = jnp.einsum("bqhd,bhkd->bhqk",
                        q.astype(jnp.float32).reshape(B, S, hq, hd),
                        kx.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None, :] < lengths[:, None]     # (B, S) keys
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # Fully-masked rows (batch-filler lanes, lengths == 0) -> zeros.
    probs = jnp.where(jnp.any(valid, -1)[:, None, None, None], probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vx.astype(jnp.float32))
    out = out.transpose(0, 2, 1, 3).reshape(B, S, hq * hd).astype(x.dtype)
    return out @ params["wo"]


def attend_cross_masked(params, cfg, x, kv_cache, enc_lengths):
    """Cross-attention with per-row encoder-length masking.

    x: (B, Sq, d); kv_cache: {"k","v"} of (B, Hkv, Senc, hd) where only
    the first ``enc_lengths[b]`` encoder positions are real (the rest is
    frame-bucket padding or cross-arena capacity). The -inf key masking
    gives pads exact zero softmax mass, so real-row outputs match the
    unpadded ``attend_cross`` at token level; fully-masked rows (empty
    decode slots reading the null arena row) collapse to zeros instead
    of NaN. Same oracle math as ``attend_masked``.
    """
    B, Sq, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    q = q.reshape(B, Sq, hq, hd)
    k, v = kv_cache["k"], kv_cache["v"]              # (B, Hkv, Senc, hd)
    Senc = k.shape[2]
    group = hq // hkv
    scale = float(1.0 / np.sqrt(hd))
    kx = jnp.repeat(k, group, axis=1)
    vx = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bqhd,bhkd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    valid = jnp.arange(Senc)[None, :] < enc_lengths[:, None]    # (B, Senc)
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.any(valid, -1)[:, None, None, None], probs, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vx.astype(jnp.float32))
    out = out.transpose(0, 2, 1, 3).reshape(B, Sq, hq * hd).astype(x.dtype)
    return out @ params["wo"]


def encode_cross_kv(params, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output."""
    B, Senc, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = enc_out @ params["wk"]
    v = enc_out @ params["wv"]
    if "bk" in params:
        k, v = k + params["bk"], v + params["bv"]
    k = k.reshape(B, Senc, hkv, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, Senc, hkv, hd).transpose(0, 2, 1, 3)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch: int, max_len: int, dtype, *, window=None):
    """Ring-buffer cache for windowed layers, linear cache otherwise."""
    size = min(window, max_len) if window else max_len
    shape = (batch, size, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attend(params, cfg, x, cache, pos, *, window=None,
                  mrope_positions=None):
    """Single-token decode. x: (B, 1, d); pos: scalar int32 (contiguous
    decode) or (B,) int32 (ragged decode — each row at its own depth).
    The broadcast front-end of ``decode_attend_batched``."""
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    return decode_attend_batched(params, cfg, x, cache, posv, window=window,
                                 mrope_positions=mrope_positions)


def decode_attend_batched(params, cfg, x, cache, pos, *, window=None,
                          mrope_positions=None):
    """Single-token decode with PER-SLOT positions (continuous batching).

    x: (B, 1, d); pos: (B,) int32 — each slot's current position (the new
    token's absolute position; equals that slot's cached length). Same
    ring/linear cache layout as ``decode_attend``, but writes and validity
    masks are per-row, so slots at different depths decode in one step.
    """
    B = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _project_qkv(params, cfg, x, x)
    posb = pos[:, None].astype(jnp.int32)
    if cfg.rope_style == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, posb, cfg.rope_theta)
        k = layers.apply_rope(k, posb, cfg.rope_theta)

    size = cache["k"].shape[1]
    slot = jnp.mod(pos, size) if window else jnp.clip(pos, 0, size - 1)
    bidx = jnp.arange(B)
    ck = cache["k"].at[bidx, slot].set(k[:, 0])
    cv = cache["v"].at[bidx, slot].set(v[:, 0])

    # Per-row validity over cache slots. For ring buffers (windowed
    # layers) every slot is a held, in-window position once the buffer
    # has wrapped; before wrapping only slots 0..pos are written. Linear
    # caches mask future slots. RoPE is applied at write time, so held
    # keys carry their absolute positions and ring order does not matter.
    idx = jnp.arange(size)[None, :]
    valid = idx <= pos[:, None]
    if window:
        valid = jnp.logical_or(valid, (pos[:, None] + 1) >= size)

    qf = q.astype(jnp.float32).reshape(B, hq, hd)
    kf = ck.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = cv.astype(jnp.float32).transpose(0, 2, 1, 3)
    group = hq // hkv
    qg = qf.reshape(B, hkv, group, hd)
    logits = jnp.einsum("bhgd,bhsd->bhgs", qg, kf) / jnp.sqrt(hd).astype(jnp.float32)
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", probs, vf)
    out = out.reshape(B, 1, hq * hd).astype(x.dtype)
    return out @ params["wo"], {"k": ck, "v": cv}


def ring_from_prefill(kv, size, length):
    """Length-aware ring-cache extraction for right-padded prefill.

    kv: (B, S, Hkv, D) full-sequence keys or values whose first
    ``length[b]`` positions are real (the rest is bucket padding);
    size: ring capacity; length: (B,) int32 true lengths (traced).
    Returns the (B, size, Hkv, D) ring holding positions
    [max(0, length-size), length) at slot ``pos % size`` — exactly the
    layout ``decode_attend_batched`` continues from — with never-written
    slots zeroed (masked by the decode validity predicate).
    """
    B = kv.shape[0]
    s = jnp.arange(size)[None, :]
    last = length[:, None] - 1                        # (B, 1)
    # largest position p < length with p % size == s (negative -> unset)
    p = last - jnp.mod(last - s, size)
    valid = p >= 0
    pc = jnp.clip(p, 0, kv.shape[1] - 1)
    ring = kv[jnp.arange(B)[:, None], pc]             # (B, size, Hkv, D)
    return jnp.where(valid[..., None, None], ring, 0).astype(kv.dtype)


def decode_attend_paged(params, cfg, x, pool, block_table, lengths, *,
                        window=None, mrope_positions=None,
                        kernel_mode="auto", shard=None, kv_spec=None,
                        layer=None):
    """Single-token decode against a block-paged KV pool.

    x: (B, 1, d); pool: {"k","v"} of (NB, BS, Hkv, D) (plus
    ``k_scale``/``v_scale`` leaves when ``kv_spec`` is a quantized
    ``paged_kv.PoolSpec`` — the new row is quantized at this write
    frontier and dequant fuses into the kernel). With ``layer`` (an
    int32 scalar) every pool leaf is the stack of all layers,
    (L, NB, BS, Hkv, D): the row is written at ``[layer, phys, off]``
    and the kernel reads that layer in place, so no layer's pool is
    sliced out of the stack or written back into it. block_table:
    (B, NBMAX) int32; lengths: (B,) tokens already cached per slot — the
    new token lands at position ``lengths[b]``, whose destination block
    ``block_table[b, lengths[b] // BS]`` the scheduler must have allocated
    (retired slots point at the reserved null block 0, making their writes
    harmless). With ``shard`` (a ShardCtx; requires
    ``paged_kv.head_shard_ok``) attention runs through the
    collective-free head-sharded shard_map over the TP-sharded pool.
    Returns (out, new_pool).
    """
    from repro.models.paged_kv import write_kv_rows

    B = x.shape[0]
    hq, hd = cfg.n_heads, cfg.head_dim
    bs = pool["k"].shape[-3]
    q, k, v = _project_qkv(params, cfg, x, x)
    posb = lengths[:, None].astype(jnp.int32)
    if cfg.rope_style == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, posb, cfg.rope_theta)
        k = layers.apply_rope(k, posb, cfg.rope_theta)

    bidx = jnp.arange(B)
    logical = jnp.clip(lengths // bs, 0, block_table.shape[1] - 1)
    phys = block_table[bidx, logical]
    off = lengths % bs
    pool = write_kv_rows(pool, phys, off, k[:, 0], v[:, 0], kv_spec,
                         layer=layer)

    out = kops.paged_attention(
        q.reshape(B, hq, hd), pool, block_table, lengths + 1,
        mode="decode", layer=layer, window=window, kernel_mode=kernel_mode,
        sharding=shard, kv_format=kv_spec)
    out = out.reshape(B, 1, hq * hd).astype(x.dtype)
    return out @ params["wo"], pool


def verify_attend_paged(params, cfg, x, pool, block_table, lengths, *,
                        kernel_mode="auto", shard=None, kv_spec=None):
    """Multi-token decode (speculative verify) against a paged KV pool.

    x: (B, K1, d) — the last accepted token plus K draft tokens per
    slot; lengths: (B,) tokens already cached, so fed token j lands at
    position ``lengths[b] + j`` (its destination block must be in the
    table — unallocated tail positions route to the reserved null
    block, where garbage writes are harmless because reads are masked
    by length). All K+1 K/V rows are written first, then every row
    attends causally within the window through the multi-query kernel —
    one pool sweep for the whole window instead of one per token.
    With ``shard`` (a ShardCtx; requires ``paged_kv.head_shard_ok``)
    the attention runs through the collective-free head-sharded
    shard_map over the TP-sharded pool, exactly like the single-token
    ``decode_attend_paged_headshard``. When ``kv_spec`` is a quantized
    ``paged_kv.PoolSpec`` all K+1 rows quantize at the write frontier
    and dequant fuses into the verify kernel. Returns (out (B, K1, d'),
    new_pool).
    """
    from repro.models.paged_kv import write_kv_rows

    B, K1, _ = x.shape
    hq, hd = cfg.n_heads, cfg.head_dim
    bs = pool["k"].shape[1]
    q, k, v = _project_qkv(params, cfg, x, x)
    pos = lengths[:, None] + jnp.arange(K1)[None, :]    # (B, K1)
    if cfg.rope_style == "rope":
        q = layers.apply_rope(q, pos, cfg.rope_theta)
        k = layers.apply_rope(k, pos, cfg.rope_theta)

    logical = pos // bs
    nbmax = block_table.shape[1]
    # pad rows can sit past the table's last row (a slot near max_len
    # with fewer than K usable drafts): route their writes to the
    # reserved null block 0 instead of clipping into the slot's own
    # last REAL block, which would corrupt live cached K/V
    phys = jnp.where(
        logical < nbmax,
        jnp.take_along_axis(block_table, jnp.clip(logical, 0, nbmax - 1),
                            axis=1),
        0)
    off = pos % bs
    pool = write_kv_rows(pool, phys, off, k, v, kv_spec)

    out = kops.paged_attention(
        q, pool, block_table, lengths, mode="verify",
        kernel_mode=kernel_mode, sharding=shard, kv_format=kv_spec)
    out = out.reshape(B, K1, hq * hd).astype(x.dtype)
    return out @ params["wo"], pool


def decode_attend_paged_headshard(params, cfg, x, pool, block_table,
                                  lengths, shard, *, kernel_mode="auto",
                                  kv_spec=None):
    """Tensor-parallel ``decode_attend_paged`` over a HEAD-sharded pool.

    Projections stay under GSPMD (wq/wk/wv are column-parallel, wo is
    row-parallel per launch/sharding.py), the new token's K/V write is a
    head-aligned scatter into the sharded pool, and the block gather +
    online softmax run under shard_map with every device holding its
    kv-head shard of every block — so the pool, by far the largest
    serving tensor, never crosses the interconnect and GSPMD can never
    fall back to all-gathering it. Quantized pools shard their
    per-(token, head) scale leaves on the same head axis, so dequant
    stays shard-local too. Thin wrapper over ``decode_attend_paged``
    with ``shard`` set; requires ``paged_kv.head_shard_ok`` (head
    counts divide |tp|).
    """
    return decode_attend_paged(params, cfg, x, pool, block_table, lengths,
                               kernel_mode=kernel_mode, shard=shard,
                               kv_spec=kv_spec)


def decode_attend_seqshard(params, cfg, x, cache, pos, shard,
                           mrope_positions=None):
    """Flash-decoding: KV cache sharded over the TP axis on the SEQUENCE
    dim; each shard computes a partial softmax over its positions and an
    LSE combine (pmax/psum) assembles the exact result.

    §Perf motivation: GQA kv-head counts (4-8) rarely divide |tp|=16, so
    the baseline keeps the cache head-replicated and GSPMD all-gathers it
    every step (~37 GB wire for yi_6b decode_32k). Sequence sharding cuts
    that to KBs: only (num, den, max) partials move. Linear caches only
    (windowed layers keep their small ring buffers).
    """
    from jax.sharding import PartitionSpec as P

    mesh = shard.mesh
    dp = shard.batch_axes
    tp = shard.tp_axis
    tp_size = shard.tp_size
    B = x.shape[0]
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = hq // hkv
    q, k, v = _project_qkv(params, cfg, x, x)
    posb = jnp.full((B, 1), pos, jnp.int32)
    if cfg.rope_style == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, posb, cfg.rope_theta)
        k = layers.apply_rope(k, posb, cfg.rope_theta)
    S = cache["k"].shape[1]
    s_loc = S // tp_size

    def local(qv, kv, vv, ck, cv):
        B_l = qv.shape[0]                  # batch is dp-sharded in here
        me = jax.lax.axis_index(tp)
        lo = me * s_loc
        slot = jnp.asarray(pos, jnp.int32) - lo
        in_range = jnp.logical_and(slot >= 0, slot < s_loc)
        cslot = jnp.clip(slot, 0, s_loc - 1)
        z = jnp.zeros((), jnp.int32)
        ck_new = jax.lax.dynamic_update_slice(ck, kv, (z, cslot, z, z))
        cv_new = jax.lax.dynamic_update_slice(cv, vv, (z, cslot, z, z))
        ck = jnp.where(in_range, ck_new, ck)
        cv = jnp.where(in_range, cv_new, cv)
        # partial attention over my positions (bf16 dot, f32 accumulate —
        # no f32 materialization of the cache)
        qg = qv.reshape(B_l, 1, hkv, group, hd)[:, 0]  # (B_l, hkv, g, hd)
        logits = jnp.einsum("bhgd,bshd->bhgs", qg.astype(cfg.dtype), ck,
                            preferred_element_type=jnp.float32)
        logits = logits * (1.0 / float(np.sqrt(hd)))
        kpos = lo + jnp.arange(s_loc)
        valid = kpos <= pos
        logits = jnp.where(valid[None, None, None, :], logits, -1e30)
        m_loc = jnp.max(logits, -1)                        # (B, hkv, g)
        p = jnp.exp(logits - m_loc[..., None])
        p = jnp.where(valid[None, None, None, :], p, 0.0)
        l_loc = jnp.sum(p, -1)
        acc = jnp.einsum("bhgs,bshd->bhgd", p.astype(cfg.dtype), cv,
                         preferred_element_type=jnp.float32)
        m_g = jax.lax.pmax(m_loc, tp)
        scale = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * scale, tp)
        acc_g = jax.lax.psum(acc * scale[..., None], tp)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        out = out.reshape(B_l, 1, hq * hd).astype(x.dtype)
        return out, ck, cv

    out, ck, cv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, None, None, None),
                  P(dp, None, None, None),
                  P(dp, tp, None, None), P(dp, tp, None, None)),
        out_specs=(P(dp, None, None), P(dp, tp, None, None),
                   P(dp, tp, None, None)),
    )(q, k, v, cache["k"], cache["v"])
    return out @ params["wo"], {"k": ck, "v": cv}
