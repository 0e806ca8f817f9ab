"""Encoder-decoder assembly (whisper-base backbone).

The conv/mel frontend is a STUB per the assignment: ``input_specs`` feeds
precomputed frame embeddings (B, F, d) directly to the encoder. Decoder
blocks add a cross-attention sublayer; decode caches both the self-KV ring
and the (static per request) cross K/V.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import layers
from repro.models.transformer import RunCtx, _logits
from repro.configs.base import ModelConfig


def init_encdec(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 2 * (cfg.n_encoder_layers + cfg.n_layers) + 4)
    ki = 0

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {
            "ln1": layers.init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn_lib.init_attention(k1, cfg, dtype),
            "ln2": layers.init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": layers.init_mlp(k2, cfg.d_model, cfg.d_ff, dtype,
                                   gated=cfg.gated_mlp),
        }

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "ln1": layers.init_norm(cfg.norm, cfg.d_model, dtype),
            "attn": attn_lib.init_attention(k1, cfg, dtype),
            "lnx": layers.init_norm(cfg.norm, cfg.d_model, dtype),
            "xattn": attn_lib.init_attention(k2, cfg, dtype, cross=True),
            "ln2": layers.init_norm(cfg.norm, cfg.d_model, dtype),
            "mlp": layers.init_mlp(k3, cfg.d_model, cfg.d_ff, dtype,
                                   gated=cfg.gated_mlp),
        }

    enc = [enc_block(ks[ki + i]) for i in range(cfg.n_encoder_layers)]
    ki += cfg.n_encoder_layers
    dec = [dec_block(ks[ki + i]) for i in range(cfg.n_layers)]
    ki += cfg.n_layers
    return {
        "embed": layers.truncated_normal_init(
            ks[ki], (cfg.vocab_size, cfg.d_model), dtype, stddev=1.0),
        "enc": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "enc_norm": layers.init_norm(cfg.norm, cfg.d_model, dtype),
        "dec": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "final_norm": layers.init_norm(cfg.norm, cfg.d_model, dtype),
    }


def encode(params, cfg: ModelConfig, frames, ctx: RunCtx,
           enc_lengths=None):
    """frames: (B, F, d) stub embeddings -> encoder output (B, F, d).

    ``enc_lengths`` ((B,) int32, optional) masks right-padding: the
    serving engine buckets frame counts to powers of two, so pad keys
    must carry zero attention mass for real positions to match the
    unpadded oracle. ``None`` (training / exact-length prefill) keeps
    the unmasked flash path.
    """
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + layers.sinusoidal_embed(jnp.arange(x.shape[1]), cfg.d_model,
                                    x.dtype)

    def body(xc, p):
        xn = layers.apply_norm(cfg.norm, p["ln1"], xc)
        if enc_lengths is None:
            out = attn_lib.attend(p["attn"], cfg, xn,
                                  jnp.arange(xn.shape[1]), causal=False,
                                  kernel_mode=ctx.kernel_mode,
                                  shard=ctx.shard)
        else:
            out = attn_lib.attend_masked(p["attn"], cfg, xn, enc_lengths)
        xc = xc + out
        xn = layers.apply_norm(cfg.norm, p["ln2"], xc)
        xc = xc + layers.apply_mlp(p["mlp"], xn, cfg.activation)
        return xc, None

    x, _ = jax.lax.scan(body, x, params["enc"],
                        unroll=True if ctx.scan_unroll else 1)
    return layers.apply_norm(cfg.norm, params["enc_norm"], x)


def _dec_block(p, cfg, x, positions, cross_kv, ctx, self_cache=None,
               pos=None):
    xn = layers.apply_norm(cfg.norm, p["ln1"], x)
    if self_cache is None:
        x = x + attn_lib.attend(p["attn"], cfg, xn, positions, causal=True,
                                kernel_mode=ctx.kernel_mode, shard=ctx.shard)
        new_cache = None
    else:
        out, new_cache = attn_lib.decode_attend(p["attn"], cfg, xn,
                                                self_cache, pos)
        x = x + out
    xn = layers.apply_norm(cfg.norm, p["lnx"], x)
    x = x + attn_lib.attend_cross(p["xattn"], cfg, xn, cross_kv)
    xn = layers.apply_norm(cfg.norm, p["ln2"], x)
    x = x + layers.apply_mlp(p["mlp"], xn, cfg.activation)
    return x, new_cache


def forward(params, cfg: ModelConfig, tokens, frames, ctx: RunCtx):
    """Training forward: (B, S) tokens + (B, F, d) frames -> logits."""
    enc_out = encode(params, cfg, frames, ctx)
    x = params["embed"][tokens]
    x = x + layers.sinusoidal_embed(jnp.arange(x.shape[1]), cfg.d_model,
                                    x.dtype)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def body(xc, p):
        cross_kv = attn_lib.encode_cross_kv(p["xattn"], cfg, enc_out)
        xc, _ = _dec_block(p, cfg, xc, positions, cross_kv, ctx)
        return xc, None

    x, _ = jax.lax.scan(body, x, params["dec"],
                        unroll=True if ctx.scan_unroll else 1)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x), jnp.zeros((), jnp.float32)


def loss_fn(params, cfg: ModelConfig, batch, ctx: RunCtx):
    logits, aux = forward(params, cfg, batch["tokens"], batch["frames"], ctx)
    tgt = batch["targets"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = jnp.mean(logz - gold)
    return ce, {"ce": ce, "aux": aux, "loss": ce}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Self-KV ring per decoder layer + slot for cross K/V."""
    dtype = jnp.dtype(cfg.dtype)
    one = attn_lib.init_kv_cache(cfg, batch, max_len, dtype)
    L = cfg.n_layers
    stack = lambda t: jnp.broadcast_to(t[None], (L,) + t.shape)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    cross = {"k": jnp.zeros((L, batch, hkv, cfg.encoder_len, hd), dtype),
             "v": jnp.zeros((L, batch, hkv, cfg.encoder_len, hd), dtype)}
    return {"self": jax.tree.map(stack, one), "cross": cross}


def prefill(params, cfg: ModelConfig, tokens, frames, ctx: RunCtx,
            max_len=None):
    """Encode + decoder prefill. Returns (logits, cache)."""
    S = tokens.shape[1]
    max_len = max_len or S
    enc_out = encode(params, cfg, frames, ctx)
    x = params["embed"][tokens]
    x = x + layers.sinusoidal_embed(jnp.arange(S), cfg.d_model, x.dtype)
    positions = jnp.arange(S, dtype=jnp.int32)

    def body(xc, p):
        cross_kv = attn_lib.encode_cross_kv(p["xattn"], cfg, enc_out)
        xn = layers.apply_norm(cfg.norm, p["ln1"], xc)
        q, k, v = attn_lib._project_qkv(p["attn"], cfg, xn, xn)
        from repro.kernels import ops as kops
        out = kops.flash_attention(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), causal=True,
                                   mode=ctx.kernel_mode,
                                   sharding=ctx.shard)
        B = xc.shape[0]
        out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
        xc = xc + out @ p["attn"]["wo"]
        xn = layers.apply_norm(cfg.norm, p["lnx"], xc)
        xc = xc + attn_lib.attend_cross(p["xattn"], cfg, xn, cross_kv)
        xn = layers.apply_norm(cfg.norm, p["ln2"], xc)
        xc = xc + layers.apply_mlp(p["mlp"], xn, cfg.activation)
        pad = max_len - S
        cache = {"self": {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                          "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))},
                 "cross": cross_kv}
        return xc, cache

    x, caches = jax.lax.scan(body, x, params["dec"],
                             unroll=True if ctx.scan_unroll else 1)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x), caches


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, ctx: RunCtx):
    """One decoder token against self cache + cross K/V."""
    x = params["embed"][tokens]
    x = x + layers.sinusoidal_embed(pos + jnp.arange(1), cfg.d_model, x.dtype)

    def body(xc, scanned):
        p, self_c, cross_c = scanned
        xc, new_c = _dec_block(p, cfg, xc, None, cross_c, ctx,
                               self_cache=self_c, pos=pos)
        return xc, new_c

    x, new_self = jax.lax.scan(body, x,
                               (params["dec"], cache["self"], cache["cross"]),
                               unroll=True if ctx.scan_unroll else 1)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    new_cache = {"self": new_self, "cross": cache["cross"]}
    return _logits(params, cfg, x)[:, 0], new_cache


# ---------------------------------------------------------------------------
# Paged serving (continuous batching): self-KV on the block pool,
# cross-KV in the per-request arena
# ---------------------------------------------------------------------------


def init_paged_cache(cfg: ModelConfig, layout):
    """Paged decode state for the encoder-decoder serving path.

    ``{"self": {"k","v"}, "cross": {"k","v"}}`` — decoder self-attention
    rides the standard block pool (``(L, NB, BS, Hkv, hd)``, flat
    layer-stacked to match ``params["dec"]``); cross-attention reads the
    per-request arena (``paged_kv.init_cross_arena``), written once at
    admission and static thereafter. Block table and lengths live with
    the scheduler, as in the decoder-only tree.
    """
    from repro.models import paged_kv

    dtype = jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, layout.num_blocks, layout.block_size,
             cfg.n_kv_heads, cfg.head_dim)
    return {"self": {"k": jnp.zeros(shape, dtype),
                     "v": jnp.zeros(shape, dtype)},
            "cross": paged_kv.init_cross_arena(cfg, layout, dtype)}


def paged_pool_mask(cfg: ModelConfig, layout):
    """Kind strings over ``init_paged_cache``: the decoder self-KV is
    ``"pool"`` (block axis at axis 1), the cross arena is ``"cross"``
    (arena-row axis at axis 1). Drives KV migration gather/scatter."""
    return {"self": {"k": "pool", "v": "pool"},
            "cross": {"k": "cross", "v": "cross"}}


def paged_cache_specs(cfg: ModelConfig, layout, shard):
    """PartitionSpecs for the encoder-decoder paged tree: self-KV pools
    head-sharded over TP like every full-attention pool; the cross arena
    head-sharded over TP too (arena rows stay replicated over the data
    axes — row count is ``num_slots + 1``, which the null row keeps off
    any pow-2 divisibility grid)."""
    from jax.sharding import PartitionSpec as P

    from repro.launch import sharding as shlib

    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, layout))
    pool = jax.tree.map(lambda t: shlib.paged_pool_spec(t, shard),
                        shapes["self"])
    hkv = cfg.n_kv_heads
    tp = shard.tp_axis if hkv % shard.tp_size == 0 else None
    cross_spec = P(None, None, tp, None, None)
    return {"self": pool,
            "cross": jax.tree.map(lambda t: cross_spec, shapes["cross"])}


def prefill_paged(params, cfg: ModelConfig, pools, tokens, frames,
                  enc_lengths, lengths, block_ids, arena_ids, ctx: RunCtx):
    """Batched admission for encoder-decoder requests: encoder forward
    (masked to each row's true frame count), cross-KV scattered into the
    arena, ragged causal decoder prefill packed into the block pool.

    tokens: (N, Sb) right-padded to the prompt bucket; frames:
    (N, Fb, d) right-padded to the frame bucket; enc_lengths, lengths:
    (N,) true frame/prompt counts; block_ids: (N, nbp) physical
    destinations (pad tails at the null block); arena_ids: (N,)
    destination arena rows (batch fillers at the null row). Right
    padding is exact for the decoder — causal attention hides pad keys,
    absolute sinusoidal positions don't shift, and pad-row K/V lands in
    the null block — while the encoder and cross-attention mask pads
    explicitly (bidirectional attention would otherwise see them).
    Returns ``(row_logits (N, V) at each row's last real position,
    new pools)``.
    """
    from repro.kernels import ops as kops
    from repro.models import paged_kv

    N, Sb = tokens.shape
    bs = pools["self"]["k"].shape[2]
    enc_out = encode(params, cfg, frames, ctx, enc_lengths=enc_lengths)
    x = params["embed"][tokens]
    x = x + layers.sinusoidal_embed(jnp.arange(Sb), cfg.d_model, x.dtype)

    def body(xc, p):
        cross_kv = attn_lib.encode_cross_kv(p["xattn"], cfg, enc_out)
        xn = layers.apply_norm(cfg.norm, p["ln1"], xc)
        q, k, v = attn_lib._project_qkv(p["attn"], cfg, xn, xn)
        out = kops.flash_attention(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3),
                                   v.transpose(0, 2, 1, 3), causal=True,
                                   mode=ctx.kernel_mode,
                                   sharding=ctx.shard)
        out = out.transpose(0, 2, 1, 3).reshape(
            N, Sb, cfg.n_heads * cfg.head_dim)
        xc = xc + out @ p["attn"]["wo"]
        xn = layers.apply_norm(cfg.norm, p["lnx"], xc)
        xc = xc + attn_lib.attend_cross_masked(p["xattn"], cfg, xn,
                                               cross_kv, enc_lengths)
        xn = layers.apply_norm(cfg.norm, p["ln2"], xc)
        xc = xc + layers.apply_mlp(p["mlp"], xn, cfg.activation)
        return xc, {"kv": {"k": k, "v": v}, "cross": cross_kv}

    x, caches = jax.lax.scan(body, x, params["dec"],
                             unroll=True if ctx.scan_unroll else 1)
    W = block_ids.shape[1] * bs            # cache width, block multiple
    dense = jax.tree.map(
        lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, W - Sb), (0, 0), (0, 0))),
        caches["kv"])                      # (L, N, W, Hkv, hd)
    new_self = paged_kv.pack_prefill_kv(pools["self"], dense, block_ids, bs)
    new_cross = paged_kv.pack_cross_arena(pools["cross"], caches["cross"],
                                          arena_ids)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits(params, cfg, x)       # (N, Sb, V)
    rows = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return rows, {"self": new_self, "cross": new_cross}


def decode_step_paged(params, cfg: ModelConfig, pools, block_table,
                      lengths, tokens, arena_ids, enc_lengths,
                      ctx: RunCtx):
    """Shape-stable continuous-batching decode step (encoder-decoder).

    tokens: (B, 1); lengths: (B,) tokens already cached per slot (the
    new token's position, which also selects its absolute sinusoidal
    embedding per row); arena_ids: (B,) each slot's cross-arena row
    (empty slots at the null row 0, whose fully-masked cross read
    collapses to zeros); enc_lengths: (B,) true encoder lengths.
    Returns (logits (B, V), new pools) — the cross arena passes through
    untouched (written only at admission).
    """
    x = params["embed"][tokens]
    x = x + layers.sinusoidal_embed(lengths[:, None], cfg.d_model, x.dtype)

    def body(xc, scanned):
        p, self_pool, xk, xv = scanned
        xn = layers.apply_norm(cfg.norm, p["ln1"], xc)
        out, new_pool = attn_lib.decode_attend_paged(
            p["attn"], cfg, xn, self_pool, block_table, lengths,
            kernel_mode=ctx.kernel_mode)
        xc = xc + out
        xn = layers.apply_norm(cfg.norm, p["lnx"], xc)
        kv = {"k": xk[arena_ids], "v": xv[arena_ids]}  # (B, Hkv, enc, hd)
        xc = xc + attn_lib.attend_cross_masked(p["xattn"], cfg, xn, kv,
                                               enc_lengths)
        xn = layers.apply_norm(cfg.norm, p["ln2"], xc)
        xc = xc + layers.apply_mlp(p["mlp"], xn, cfg.activation)
        return xc, new_pool

    x, new_self = jax.lax.scan(
        body, x, (params["dec"], pools["self"],
                  pools["cross"]["k"], pools["cross"]["v"]),
        unroll=True if ctx.scan_unroll else 1)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x)[:, 0], {"self": new_self,
                                           "cross": pools["cross"]}
