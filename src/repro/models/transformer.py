"""LM assembly: heterogeneous block stacks with scan-over-layers.

Layers are grouped into repeating *pattern periods* (e.g. recurrentgemma's
(rglru, rglru, local)) and each group is a lax.scan over stacked params —
HLO stays O(1) in depth, which keeps the 61-layer kimi-k2 dry-run
compilable. A trailing partial period becomes a count-1 group.

Block kinds: attn (optional SWA), local (windowed attn), mlstm, slstm,
rglru. Every kind supports three phases with one param set:
  forward  (train)            — full sequence, no cache
  prefill                     — full sequence, returns cache
  decode                      — one token + cache
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import layers, moe, ssm
from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class RunCtx:
    """Static per-call context (jit-static; hashable)."""

    kernel_mode: str = "auto"       # kernels/ops dispatch mode
    causal: bool = True
    remat: str = "none"             # none | full
    shard: Any = None               # launch.sharding.ShardCtx or None
    moe_sharded: bool = False
    # Unroll layer/chunk scans. Used by the dry-run's shallow cost
    # variants: XLA cost_analysis ignores while-loop trip counts, so
    # FLOPs are only countable from unrolled bodies.
    scan_unroll: bool = False
    # §Perf knobs (hillclimbed; see EXPERIMENTS.md):
    ce_chunk: int = 0          # >0: scan CE over seq chunks (no full logits)
    moe_mode: str = "gather"   # 'gather' (FSDP weight gather) | 'partial'
    decode_seq_shard: bool = False  # flash-decoding LSE combine over tp
    # Paged serving under a mesh: run decode attention through the
    # head-sharded pool shard_map (each device owns its kv-head shard of
    # every block). Set by the Engine when paged_kv.head_shard_ok holds.
    decode_head_shard: bool = False
    # Quantized paged KV: a paged_kv.PoolSpec (hashable, jit-static) when
    # the pool stores int8/fp8 payloads with per-(token, head) scale
    # leaves; None keeps the historical bf16 path bit-identical.
    kv_spec: Any = None
    # Residual-stream constraint after every block:
    #   'none'  — GSPMD chooses; observed: it DELAYS the row-parallel
    #             reduction into the next norm's f32 upcast, so the
    #             activation all-reduce moves f32 (2x traffic);
    #   'batch' — constrain to (batch-sharded, replicated): forces the
    #             reduce on the bf16 tensor;
    #   'seq'   — Megatron-SP: additionally shard the sequence over tp
    #             between blocks (reduce-scatter + all-gather schedule,
    #             residual memory / |tp|).
    residual_spec: str = "none"


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 4)
    p = {"ln1": layers.init_norm(cfg.norm, cfg.d_model, dtype)}
    if kind in ("attn", "local"):
        p["attn"] = attn_lib.init_attention(ks[0], cfg, dtype)
        p["ln2"] = layers.init_norm(cfg.norm, cfg.d_model, dtype)
        if cfg.is_moe:
            p["moe"] = moe.init_moe(ks[1], cfg, dtype)
        elif cfg.d_ff > 0:
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype,
                                       gated=cfg.gated_mlp)
    elif kind == "rglru":
        p["rec"] = ssm.init_rglru_block(ks[0], cfg, dtype)
        if cfg.d_ff > 0:
            p["ln2"] = layers.init_norm(cfg.norm, cfg.d_model, dtype)
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype,
                                       gated=cfg.gated_mlp)
    elif kind == "mlstm":
        p["mix"] = ssm.init_mlstm_block(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["mix"] = ssm.init_slstm_block(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def _window_for(cfg, kind):
    if kind == "local":
        return cfg.local_window
    return cfg.sliding_window


def _ffn_part(p, cfg, x, ctx, dropless: bool = False):
    """Post-mixing FFN/MoE with pre-norm + residual. Returns (x, aux).

    ``dropless`` is set by every SERVING path (prefill-with-cache,
    decode, verify): expert capacity is sized so no assignment ever
    drops, making the MoE per-token — outputs independent of right-
    padding, co-batched traffic and batch width, which the engine's
    identity contract requires. Training keeps capacity-factor drops.
    """
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        xn = layers.apply_norm(cfg.norm, p["ln2"], x)
        if ctx.moe_sharded and ctx.shard is not None:
            delta, aux = moe.apply_moe_sharded(p["moe"], cfg, xn, ctx.shard,
                                               mode=ctx.moe_mode,
                                               dropless=dropless)
        else:
            delta, aux = moe.apply_moe(p["moe"], cfg, xn,
                                       dropless=dropless)
        x = x + delta
    elif "mlp" in p:
        xn = layers.apply_norm(cfg.norm, p["ln2"], x)
        x = x + layers.apply_mlp(p["mlp"], xn, cfg.activation)
    return x, aux


def apply_block(p, cfg: ModelConfig, kind: str, x, positions, ctx: RunCtx,
                mrope_positions=None, with_cache: bool = False,
                cache_len: Optional[int] = None, prefill_length=None):
    """Full-sequence block. Returns (x, aux, cache-or-None).

    ``prefill_length`` ((B,) int32, traced) marks RIGHT-padded prefill:
    only the first ``prefill_length[b]`` tokens of row b are real. Causal
    masking already keeps padded keys out of every real query's window,
    so the forward math needs no change — but emitted decode caches must
    capture state *at the true length*, not at the padded end. Attention
    rings and the RG-LRU gather/recompute their state at the true
    boundary; mlstm freezes its chunk scan past it by gate masking and
    slstm by carry selection, so every decoder-only kind is exact under
    right padding (``prefill_supports_ragged``).
    """
    xn = layers.apply_norm(cfg.norm, p["ln1"], x)
    cache = None
    if kind in ("attn", "local"):
        window = _window_for(cfg, kind)
        if with_cache:
            out, cache = _attend_with_cache(p["attn"], cfg, xn, positions,
                                            window, ctx, mrope_positions,
                                            cache_len, prefill_length)
        else:
            out = attn_lib.attend(p["attn"], cfg, xn, positions,
                                  window=window, causal=ctx.causal,
                                  mrope_positions=mrope_positions,
                                  kernel_mode=ctx.kernel_mode,
                                  shard=ctx.shard)
        x = _constrain_residual(x + out, ctx)
        x, aux = _ffn_part(p, cfg, x, ctx, dropless=with_cache)
        return x, aux, cache
    if kind == "rglru":
        if with_cache:
            out, cache = _rglru_with_cache(p["rec"], cfg, xn, ctx,
                                           prefill_length)
        else:
            out = ssm.apply_rglru_block(p["rec"], cfg, xn,
                                        kernel_mode=ctx.kernel_mode,
                                        shard=ctx.shard)
        x = _constrain_residual(x + out, ctx)
        x, aux = _ffn_part(p, cfg, x, ctx, dropless=with_cache)
        return x, aux, cache
    if kind == "mlstm":
        # NOTE: the mLSTM chunk scan stays a loop even in unrolled cost
        # variants (fully unrolling 16 chunks x 7 layers x ~30 einsums
        # under autodiff blew XLA compile time past 30 min). Cost effect:
        # intra-chunk einsums are counted for 1 of N chunks, an ~11%
        # undercount of the mLSTM *mixing* flops (projections dominate
        # and are counted exactly) — recorded in EXPERIMENTS.md §Roofline.
        if with_cache:
            out, cache = _mlstm_with_cache(p["mix"], cfg, xn,
                                           length=prefill_length)
        else:
            out = ssm.apply_mlstm_block(p["mix"], cfg, xn,
                                        chunk=cfg.mlstm_chunk)
        return x + out, jnp.zeros((), jnp.float32), cache
    if kind == "slstm":
        if with_cache:
            out, cache = _slstm_with_cache(p["mix"], cfg, xn,
                                           length=prefill_length)
        else:
            out = ssm.apply_slstm_block(p["mix"], cfg, xn)
        return x + out, jnp.zeros((), jnp.float32), cache
    raise ValueError(kind)


# --- prefill variants that also emit a decode cache -------------------------


def _attend_with_cache(params, cfg, xn, positions, window, ctx,
                       mrope_positions, cache_len, length=None):
    B, S, _ = xn.shape
    q, k, v = attn_lib._project_qkv(params, cfg, xn, xn)
    if cfg.rope_style == "mrope":
        q = layers.apply_mrope(q, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
        k = layers.apply_mrope(k, mrope_positions, cfg.mrope_sections, cfg.rope_theta)
    elif cfg.rope_style == "rope":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    out = __import__("repro.kernels.ops", fromlist=["x"]).flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window,
        mode=ctx.kernel_mode, sharding=ctx.shard)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = out @ params["wo"]
    size = min(window, cache_len or S) if window else (cache_len or S)
    if window and length is not None:
        # Right-padded prefill: rebuild the ring from the true per-row
        # tail, not the padded one. Linear caches need nothing — pad-key
        # garbage past ``length`` is masked by the decode validity
        # predicate and overwritten as decode advances.
        ck = attn_lib.ring_from_prefill(k, size, length)
        cv = attn_lib.ring_from_prefill(v, size, length)
    elif window and S >= size:
        ck, cv = k[:, -size:], v[:, -size:]
        # ring-order the tail so slot (pos % size) stays consistent
        roll = (S % size)
        ck = jnp.roll(ck, roll, axis=1)
        cv = jnp.roll(cv, roll, axis=1)
    else:
        pad = size - S
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return out, {"k": ck, "v": cv}


def _rglru_with_cache(params, cfg, xn, ctx, length=None):
    gate = jax.nn.gelu(xn @ params["w_gate"], approximate=True)
    xb = xn @ params["w_x"]
    y, conv_state = layers.apply_conv1d(params["conv"], xb)
    a, b = ssm._rglru_coeffs(params, y)
    h = __import__("repro.kernels.ops", fromlist=["x"]).rglru_scan(
        a, b, mode=ctx.kernel_mode, sharding=ctx.shard)
    out = (gate * h.astype(xn.dtype)) @ params["w_out"]
    if length is None:
        return out, {"h": h[:, -1].astype(jnp.float32), "conv": conv_state}
    # Right-padded prefill: the recurrence is causal, so the state at the
    # true length is just an interior scan step — gather it, and rebuild
    # the conv tail from the last (width-1) REAL inputs (zero-prefixed,
    # matching apply_conv1d's initial state for short prompts).
    B = xn.shape[0]
    h_true = h[jnp.arange(B), jnp.maximum(length - 1, 0)]
    conv_true = layers.conv_state_at(xb, params["conv"]["w"].shape[0],
                                     length)
    return out, {"h": h_true.astype(jnp.float32), "conv": conv_true}


def _mlstm_with_cache(params, cfg, xn, unroll=False, length=None):
    B, S, d = xn.shape
    q, k, v, ig, fg, z, conv_state = ssm._mlstm_qkv_gates(
        params, cfg, xn, length=length)
    if length is not None:
        # Right-padded prefill: freeze the chunk scan past the true
        # length (input gate off, forget gate exactly 1), so the carried
        # (C, n, m) IS the state at ``length``; pad-row h is garbage and
        # never read (logits are taken at real positions only).
        ig, fg = ssm.freeze_gates_past(ig, fg, length)
    h, (C, n, m) = ssm.mlstm_chunkwise(q, k, v, ig, fg,
                                       chunk=min(cfg.mlstm_chunk, S),
                                       unroll=unroll)
    h = h.transpose(0, 2, 1, 3).reshape(B, S, d)
    h = layers.group_norm(h, params["gn_scale"], cfg.n_heads)
    out = (h * jax.nn.silu(z)) @ params["w_down"]
    return out, {"C": C, "n": n, "m": m, "conv": conv_state}


def _slstm_with_cache(params, cfg, xn, length=None):
    B, S, d = xn.shape
    H = cfg.n_heads
    hd = d // H
    x_parts = xn @ params["w_zifo"]
    state = (jnp.zeros((B, H, hd), jnp.float32),) * 3 + (
        jnp.full((B, H, hd), -1e30, jnp.float32),)
    if length is None:
        length = jnp.full((B,), S, jnp.int32)

    def step(st, inp):
        xp, t = inp
        hidden, st_new = ssm._slstm_cell(params, cfg, xp, st)
        # Right-padded prefill: keep the pre-step carry on pad rows so
        # the final state is frozen bit-exactly at each true length.
        # Exact-length prefill takes the same select: without it the step
        # compiles differently (fused multiply-adds), and the two prefills
        # would then disagree in the last bits.
        keep = (t < length)[:, None, None]
        st = tuple(jnp.where(keep, new, old)
                   for new, old in zip(st_new, st))
        return st, hidden

    (h_f, c_f, n_f, m_f), hs = jax.lax.scan(
        step, state, (jnp.moveaxis(x_parts, 1, 0),
                      jnp.arange(S, dtype=jnp.int32)))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d).astype(xn.dtype)
    h = layers.group_norm(h, params["gn_scale"], H)
    out = layers.apply_mlp(params["ff"], h, "gelu")
    return out, {"h": h_f, "c": c_f, "n": n_f, "m": m_f}


# --- decode ------------------------------------------------------------------


def apply_block_decode(p, cfg: ModelConfig, kind: str, x, cache, pos,
                       ctx: RunCtx, mrope_positions=None):
    """One-token block step. x: (B, 1, d). Returns (x, new_cache)."""
    xn = layers.apply_norm(cfg.norm, p["ln1"], x)
    if kind in ("attn", "local"):
        window = _window_for(cfg, kind)
        if (ctx.decode_seq_shard and ctx.shard is not None
                and window is None):
            out, cache = attn_lib.decode_attend_seqshard(
                p["attn"], cfg, xn, cache, pos, ctx.shard,
                mrope_positions=mrope_positions)
        else:
            out, cache = attn_lib.decode_attend(
                p["attn"], cfg, xn, cache, pos, window=window,
                mrope_positions=mrope_positions)
        x = x + out
        x, _ = _ffn_part(p, cfg, x, ctx, dropless=True)
        return x, cache
    if kind == "rglru":
        out, cache = ssm.apply_rglru_decode(p["rec"], cfg, xn, cache)
        x = x + out
        x, _ = _ffn_part(p, cfg, x, ctx, dropless=True)
        return x, cache
    if kind == "mlstm":
        out, cache = ssm.apply_mlstm_decode(p["mix"], cfg, xn, cache)
        return x + out, cache
    if kind == "slstm":
        out, cache = ssm.apply_slstm_decode(p["mix"], cfg, xn, cache)
        return x + out, cache
    raise ValueError(kind)


def apply_block_decode_paged(p, cfg: ModelConfig, kind: str, x, cache,
                             block_table, lengths, ctx: RunCtx,
                             mrope_positions=None, layer=None):
    """One-token block step with PER-SLOT positions over the paged cache.

    Full-attention layers attend a shared block pool via the per-sequence
    block table; windowed layers keep per-slot ring buffers (bounded state
    — paging buys nothing); SSM kinds carry per-slot recurrent state and
    are position-independent, so the stock decode applies unchanged.
    ``layer`` (an int32 scalar) marks a full-attention ``cache`` as the
    stack of every layer's pool, updated and read at that index.
    """
    xn = layers.apply_norm(cfg.norm, p["ln1"], x)
    if kind in ("attn", "local"):
        window = _window_for(cfg, kind)
        if window is None:
            out, cache = attn_lib.decode_attend_paged(
                p["attn"], cfg, xn, cache, block_table, lengths,
                mrope_positions=mrope_positions,
                kernel_mode=ctx.kernel_mode,
                shard=ctx.shard if ctx.decode_head_shard else None,
                kv_spec=ctx.kv_spec, layer=layer)
        else:
            out, cache = attn_lib.decode_attend_batched(
                p["attn"], cfg, xn, cache, lengths, window=window,
                mrope_positions=mrope_positions)
        x = x + out
        x, _ = _ffn_part(p, cfg, x, ctx, dropless=True)
        return x, cache
    if kind == "rglru":
        out, cache = ssm.apply_rglru_decode(p["rec"], cfg, xn, cache)
        x = x + out
        x, _ = _ffn_part(p, cfg, x, ctx, dropless=True)
        return x, cache
    if kind == "mlstm":
        out, cache = ssm.apply_mlstm_decode(p["mix"], cfg, xn, cache)
        return x + out, cache
    if kind == "slstm":
        out, cache = ssm.apply_slstm_decode(p["mix"], cfg, xn, cache)
        return x + out, cache
    raise ValueError(kind)


def _decode_window_scan(p, cfg: ModelConfig, kind: str, x, cache,
                        block_table, lengths, ctx: RunCtx):
    """Scan the stock single-token decode cell over a K+1-token verify
    window, stacking the PER-POSITION cache states as candidates.

    x: (B, K1, d). Returns (out (B, K1, d), candidates) where every
    cache leaf gains a K1 axis after its batch axis ((B, K1, ...)):
    candidate j is the state after consuming fed tokens 0..j. Because
    the cells are causal, candidate j is independent of any rejected
    token after j — selection at the accept boundary is exact, and the
    math per position is bit-identical to the non-speculative decode
    path (same cells, same order).
    """
    K1 = x.shape[1]

    def cell(c, inp):
        xt, j = inp
        xo, c2 = apply_block_decode_paged(p, cfg, kind, xt[:, None], c,
                                          block_table, lengths + j, ctx)
        return c2, (xo[:, 0], c2)

    _, (outs, stk) = jax.lax.scan(
        cell, cache,
        (jnp.moveaxis(x, 1, 0), jnp.arange(K1, dtype=jnp.int32)))
    out = jnp.moveaxis(outs, 0, 1)
    cands = jax.tree.map(lambda t: jnp.moveaxis(t, 0, 1), stk)
    return out, cands


def apply_block_verify_paged(p, cfg: ModelConfig, kind: str, x, cache,
                             block_table, lengths, ctx: RunCtx):
    """Multi-token block step for the speculative verify window.

    x: (B, K1, d) — hidden states for the K+1 fed tokens. Full-attention
    layers run ONE multi-query pass over the paged pool (state commits
    by construction: the rejected tail is rolled back by the host's
    length-pointer rewind, no block copies); windowed rings and SSM
    kinds scan the stock decode cell and stack per-position candidate
    states for the later commit selection (``select_verify_state``).
    """
    if kind in ("attn", "local") and _window_for(cfg, kind) is None:
        xn = layers.apply_norm(cfg.norm, p["ln1"], x)
        out, pool = attn_lib.verify_attend_paged(
            p["attn"], cfg, xn, cache, block_table, lengths,
            kernel_mode=ctx.kernel_mode,
            shard=ctx.shard if ctx.decode_head_shard else None,
            kv_spec=ctx.kv_spec)
        x = x + out
        x, _ = _ffn_part(p, cfg, x, ctx, dropless=True)
        return x, pool
    return _decode_window_scan(p, cfg, kind, x, cache, block_table,
                               lengths, ctx)


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     dtype):
    if kind in ("attn", "local"):
        return attn_lib.init_kv_cache(cfg, batch, max_len, dtype,
                                      window=_window_for(cfg, kind))
    if kind == "rglru":
        return ssm.init_rglru_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return ssm.init_mlstm_cache(cfg, batch, dtype)
    if kind == "slstm":
        return ssm.init_slstm_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Groups (pattern periods) and the full LM
# ---------------------------------------------------------------------------


def layer_groups(cfg: ModelConfig):
    """[(pattern tuple, repeat count), ...] covering all layers in order."""
    p = tuple(cfg.block_pattern)
    full, rem = divmod(cfg.n_layers, len(p))
    groups = []
    if full:
        groups.append((p, full))
    if rem:
        groups.append((p[:rem], 1))
    return groups


def layer_walk(cfg: ModelConfig):
    """Yield ``(group_key, pattern, count)`` per scan group, in order.

    THE shared walk over the stacked-group structure: every tree that
    mirrors ``params["groups"]`` (decode caches, paged pools, masks,
    sharding specs) is built or consumed through this generator (or
    ``map_layer_tree`` / ``scan_groups`` on top of it), so the group/
    pattern keying ``g{g}``/``p{pi}`` is defined in exactly one place.
    """
    for g, (pattern, count) in enumerate(layer_groups(cfg)):
        yield f"g{g}", pattern, count


def map_layer_tree(cfg: ModelConfig, fn):
    """Build ``{gk: {pk: fn(gk, pk, kind, count)}}`` over ``layer_walk``.

    The shared constructor for every same-structure side tree (caches,
    pools, pool masks, partition specs): ``fn`` sees the group/pattern
    keys plus the layer KIND and stack count and returns one subtree.
    """
    return {gk: {f"p{pi}": fn(gk, f"p{pi}", kind, count)
                 for pi, kind in enumerate(pattern)}
            for gk, pattern, count in layer_walk(cfg)}


def scan_groups(params, cfg: ModelConfig, x, trees, block_fn, ctx: RunCtx,
                carried=None):
    """Scan ``x`` through every stacked group, threading a side tree.

    The shared driver behind ``decode_step`` / ``decode_step_paged`` /
    ``decode_verify_paged``: ``trees`` mirrors ``params["groups"]`` (a
    decode cache or paged pool) and ``block_fn(kind, layer_params,
    layer_tree, x) -> (x, new_layer_tree)`` is the per-layer cell.
    Each layer's tree rides the scan as xs -> ys: sliced out of the
    stack, updated, stacked again. A layer kind for which ``carried``
    is true rides it whole, as carry, instead: ``block_fn`` then gets
    the group's stack and ``layer=`` its int32 index into it, and
    updates the stack in place.
    Returns ``(x, new_trees)`` with ``new_trees`` same-structure.
    """
    new_trees = {}
    for gk, pattern, count in layer_walk(cfg):
        keep = {f"p{pi}" for pi, kind in enumerate(pattern)
                if carried is not None and carried(kind)}
        stacks = {pk: t for pk, t in trees[gk].items() if pk in keep}
        sliced = {pk: t for pk, t in trees[gk].items() if pk not in keep}

        def body(carry, scanned, pattern=pattern, keep=keep):
            xc, stacks = carry
            layer_params, layer_tree, layer = scanned
            stacks, new_lt = dict(stacks), {}
            for pi, kind in enumerate(pattern):
                pk = f"p{pi}"
                if pk in keep:
                    xc, stacks[pk] = block_fn(kind, layer_params[pk],
                                              stacks[pk], xc, layer=layer)
                else:
                    xc, new_lt[pk] = block_fn(kind, layer_params[pk],
                                              layer_tree[pk], xc)
            return (xc, stacks), new_lt

        layer_ix = jnp.arange(count, dtype=jnp.int32)
        (x, stacks), new_gc = jax.lax.scan(
            body, (x, stacks), (params["groups"][gk], sliced, layer_ix),
            unroll=True if ctx.scan_unroll else 1)
        new_trees[gk] = {**stacks, **new_gc}
    return x, new_trees


def _is_pool_kind(cfg: ModelConfig, kind: str) -> bool:
    """True for layer kinds whose decode state lives in the shared block
    pool (full attention); windowed rings and SSM carries are per-slot."""
    return kind in ("attn", "local") and _window_for(cfg, kind) is None


def init_lm(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4 + cfg.n_layers)
    params = {"embed": layers.truncated_normal_init(
        ks[0], (cfg.vocab_size, cfg.d_model), dtype, stddev=1.0)}
    ki = 1
    groups = {}
    for gk, pattern, count in layer_walk(cfg):
        gp = {}
        for pi, kind in enumerate(pattern):
            stacked = [init_block(ks[ki + i], cfg, kind, dtype)
                       for i in range(count)]
            ki += count
            gp[f"p{pi}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
        groups[gk] = gp
    params["groups"] = groups
    params["final_norm"] = layers.init_norm(cfg.norm, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.truncated_normal_init(
            ks[ki], (cfg.d_model, cfg.vocab_size), dtype)
    return params


def _constrain_residual(x, ctx):
    if ctx.residual_spec == "none" or ctx.shard is None:
        return x
    from repro.launch import sharding as shlib
    sh = ctx.shard
    if ctx.residual_spec == "seq":
        return shlib.constrain(x, sh, sh.batch_axes, sh.tp_axis, None)
    return shlib.constrain(x, sh, sh.batch_axes, None, None)


def _pattern_runs(pattern):
    """[(kind, start_pos, run_len), ...] for consecutive equal kinds."""
    runs = []
    for pi, kind in enumerate(pattern):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, pi, 1])
    return [tuple(r) for r in runs]


def _apply_groups(params, cfg, x, positions, ctx, mrope_positions=None,
                  with_cache=False, cache_len=None, prefill_length=None):
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}
    for gk, pattern, count in layer_walk(cfg):
        gp = params["groups"][gk]
        runs = _pattern_runs(pattern)

        def body(carry, layer_params, runs=runs):
            xc, aux = carry
            layer_caches = {}
            for kind, start, n in runs:
                def one(xb, lp, kind=kind):
                    return apply_block(lp, cfg, kind, xb, positions, ctx,
                                       mrope_positions, with_cache,
                                       cache_len, prefill_length)
                if ctx.remat == "full":
                    one = jax.checkpoint(one)
                if n == 1:
                    xc, a, cache = one(xc, layer_params[f"p{start}"])
                    xc = _constrain_residual(xc, ctx)
                    aux = aux + a
                    if with_cache:
                        layer_caches[f"p{start}"] = cache
                else:
                    # Runs of identical kinds become an INNER scan: the
                    # period body stays O(1) in run length, keeping XLA
                    # compile time tractable (xLSTM's m^7 s period body
                    # compiled superlinearly when inlined 7x).
                    stacked = jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[layer_params[f"p{start + j}"] for j in range(n)])

                    def inner(c2, lp2, one=one):
                        x2, a2 = c2
                        x3, a3, cache = one(x2, lp2)
                        x3 = _constrain_residual(x3, ctx)
                        return (x3, a2 + a3), cache

                    (xc, aux), run_caches = jax.lax.scan(
                        inner, (xc, aux), stacked,
                        unroll=True if ctx.scan_unroll else 1)
                    if with_cache:
                        for j in range(n):
                            layer_caches[f"p{start + j}"] = jax.tree.map(
                                lambda t, j=j: t[j], run_caches)
            return (xc, aux), layer_caches if with_cache else None

        (x, aux_total), group_caches = jax.lax.scan(
            body, (x, aux_total), gp, unroll=True if ctx.scan_unroll else 1)
        if with_cache:
            caches[gk] = group_caches
    return x, aux_total, caches if with_cache else None


def _logits(params, cfg, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    # f32 straight from the matmul. A bf16 product converted to f32 let
    # the TPU compiler skip the bf16 rounding in some fusions and not
    # others, so the decode step fused with the sampler (overlap) drew
    # other tokens than the decode step and the sampler apart.
    return jnp.matmul(x, head, preferred_element_type=jnp.float32)


def _embed(params, cfg, tokens, visual_embeds=None, pos_offset=0,
           shard=None):
    x = layers.vocab_parallel_lookup(params["embed"], tokens, shard)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if cfg.visual_prefix and visual_embeds is not None:
        x = jnp.concatenate([visual_embeds.astype(x.dtype),
                             x[:, cfg.visual_prefix:]], axis=1)
    if cfg.pos_embed == "sinusoidal":
        positions = pos_offset + jnp.arange(x.shape[1])
        x = x + layers.sinusoidal_embed(positions, cfg.d_model, x.dtype)
    return x


def forward_hidden(params, cfg: ModelConfig, tokens, ctx: RunCtx,
                   visual_embeds=None, mrope_positions=None):
    """tokens: (B, S) -> final-norm hidden (B, S, d), aux scalar."""
    B, S = tokens.shape
    x = _embed(params, cfg, tokens, visual_embeds, shard=ctx.shard)
    positions = jnp.arange(S, dtype=jnp.int32)
    x, aux, _ = _apply_groups(params, cfg, x, positions, ctx,
                              mrope_positions)
    return layers.apply_norm(cfg.norm, params["final_norm"], x), aux


def forward(params, cfg: ModelConfig, tokens, ctx: RunCtx,
            visual_embeds=None, mrope_positions=None):
    """tokens: (B, S) -> logits (B, S, V) f32, aux scalar."""
    x, aux = forward_hidden(params, cfg, tokens, ctx, visual_embeds,
                            mrope_positions)
    return _logits(params, cfg, x), aux


def _ce_from_hidden(params, cfg, x, tgt, ctx: RunCtx):
    """Cross-entropy from hidden states; ctx.ce_chunk > 0 scans over
    sequence chunks so the full (B, S, V) logits never materialize
    (§Perf: at gemma's 256k vocab full train logits are 13 GB f32 per
    device even vocab-sharded)."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    C = ctx.ce_chunk
    B, S, _ = x.shape
    if not C or S % C != 0 or S == C:
        logits = (x @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    nc = S // C
    xs = jnp.moveaxis(x.reshape(B, nc, C, x.shape[-1]), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(B, nc, C), 1, 0)

    def body(acc, inp):
        xc, tc = inp
        logits = (xc @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    return total / (B * S)


def loss_fn(params, cfg: ModelConfig, batch, ctx: RunCtx):
    """batch: {tokens (B, S), targets (B, S)} -> (loss, metrics)."""
    x, aux = forward_hidden(params, cfg, batch["tokens"], ctx,
                            batch.get("visual_embeds"),
                            batch.get("mrope_positions"))
    ce = _ce_from_hidden(params, cfg, x, batch["targets"], ctx)
    loss = ce + cfg.moe_aux_coef * aux
    return loss, {"ce": ce, "aux": aux, "loss": loss}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked decode caches mirroring the group structure."""
    dtype = jnp.dtype(cfg.dtype)

    def one(gk, pk, kind, count):
        c = init_block_cache(cfg, kind, batch, max_len, dtype)
        return jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (count,) + t.shape), c)

    return map_layer_tree(cfg, one)


def init_paged_cache(cfg: ModelConfig, layout, spec=None):
    """Stacked per-layer caches for the paged serving engine.

    Full-attention layers share a block pool (paged_kv.init_layer_pool);
    windowed and SSM layers keep per-slot bounded state exactly as in
    ``init_cache``. ``spec`` (a quantized ``paged_kv.PoolSpec``) switches
    the full-attention pools to int8/fp8 payloads with per-(token, head)
    scale leaves; windowed rings and SSM state stay full-precision. The
    block table and lengths live with the scheduler, not in this tree —
    all layers of a sequence share one table.
    """
    from repro.models import paged_kv

    dtype = jnp.dtype(cfg.dtype)

    def one(gk, pk, kind, count):
        if kind in ("attn", "local"):
            c = paged_kv.init_layer_pool(
                cfg, layout, dtype, window=_window_for(cfg, kind),
                spec=spec)
        else:
            c = init_block_cache(cfg, kind, layout.num_slots,
                                 layout.max_len, dtype)
        return jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (count,) + t.shape), c)

    return map_layer_tree(cfg, one)


def paged_pool_mask(cfg: ModelConfig, layout, spec=None):
    """Same-structure tree of kind strings over ``init_paged_cache``:
    ``"pool"`` for full-attention BLOCK-POOL leaves (block axis at
    axis 1, after the stacked layer-count axis) and ``"slot"`` for
    PER-SLOT state (windowed rings, SSM carries, conv tails — slot axis
    also at axis 1). Encoder-decoder trees add ``"cross"`` for the
    cross-KV arena (arena-row axis at axis 1). The classification walks
    layer KINDS, exactly like ``paged_cache_specs`` — never shapes, so a
    ring buffer whose slot count happens to equal the pool's block count
    cannot be misclassified. Consumed by ``paged_kv.extract_blocks``/
    ``insert_blocks`` (KV migration between replicas)."""
    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, layout, spec))

    def one(gk, pk, kind, count):
        tag = "pool" if _is_pool_kind(cfg, kind) else "slot"
        return jax.tree.map(lambda t: tag, shapes[gk][pk])

    return map_layer_tree(cfg, one)


def paged_cache_specs(cfg: ModelConfig, layout, shard, spec=None):
    """PartitionSpecs for the ``init_paged_cache`` tree under a mesh:
    block pools head-sharded over TP (every device owns its kv-head
    shard of every block, replicated over data axes), ring buffers and
    SSM state on the standard per-slot cache rules. Pool leaves are
    identified by LAYER KIND (the same walk as ``init_paged_cache``),
    not by shape. Quantized pools (``spec``) add 4-D scale leaves, whose
    kv-head axis lands on the same TP axis via the truncating spec fit."""
    from repro.launch import sharding as shlib

    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, layout, spec))

    def one(gk, pk, kind, count):
        sub = shapes[gk][pk]
        if _is_pool_kind(cfg, kind):
            return jax.tree.map(
                lambda t: shlib.paged_pool_spec(t, shard), sub)
        return shlib.batch_specs(sub, shard)

    return map_layer_tree(cfg, one)


def pack_prefill_into_paged(cfg: ModelConfig, layout, pools, dense_caches,
                            row_of_slot, valid, block_ids, spec=None):
    """Install a BATCH of prefilled dense caches (from ``prefill`` with
    ``max_len == block_ids.shape[1] * block_size``) into the paged tree.

    ``block_ids`` is (N, nbp) — per prefill-batch row, the physical
    destinations of its cache blocks (pad tails at the null block);
    ``row_of_slot`` ((num_slots,) int32) and ``valid`` ((num_slots,)
    bool) give the inverse slot<-row map for per-slot state (rings, SSM
    carries, conv tails): slot s takes row ``row_of_slot[s]`` where
    ``valid[s]``. Pure function; jit per (prompt-bucket, batch-bucket).
    """
    from repro.models import paged_kv

    def one(gk, pk, kind, count):
        pool = pools[gk][pk]
        dense = dense_caches[gk][pk]
        if kind in ("attn", "local"):
            if _window_for(cfg, kind) is None:
                return paged_kv.pack_prefill_kv(
                    pool, dense, block_ids, layout.block_size, spec=spec)
            return {
                "k": paged_kv.pack_prefill_ring(
                    pool["k"], dense["k"], row_of_slot, valid),
                "v": paged_kv.pack_prefill_ring(
                    pool["v"], dense["v"], row_of_slot, valid)}
        return paged_kv.pack_prefill_state(pool, dense, row_of_slot, valid)

    return map_layer_tree(cfg, one)


def decode_step_paged(params, cfg: ModelConfig, pools, block_table, lengths,
                      tokens, ctx: RunCtx):
    """Shape-stable continuous-batching decode step.

    tokens: (B, 1) — one token per decode slot; lengths: (B,) int32 tokens
    already cached per slot (the new token's position); block_table:
    (B, NBMAX) int32. Retired slots ride along pointed at the null block,
    their outputs discarded by the scheduler. Returns
    (logits (B, V) f32, new pools).
    """
    if cfg.enc_dec or cfg.rope_style == "mrope" or cfg.pos_embed != "none":
        raise NotImplementedError(
            "paged decode supports decoder-only rope/none-pos models")
    x = _embed(params, cfg, tokens, shard=ctx.shard)

    def block_fn(kind, lp, lc, xc, layer=None):
        return apply_block_decode_paged(lp, cfg, kind, xc, lc,
                                        block_table, lengths, ctx,
                                        layer=layer)

    # Block-pool leaves ride the layer scan whole, as carry: each layer
    # appends its rows at [layer, phys, off] and the kernel reads the
    # stack at that index, so the step never copies a pool. As xs -> ys,
    # every layer's pool would be sliced out of the stack, written, and
    # stacked again. Per-slot state (windowed rings, SSM carries) is
    # small and keeps xs -> ys.
    x, new_pools = scan_groups(params, cfg, x, pools, block_fn, ctx,
                               carried=lambda kind: _is_pool_kind(cfg,
                                                                  kind))
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x)[:, 0], new_pools


def select_verify_state(cfg: ModelConfig, cands, commit):
    """Commit a verify window's per-slot state at the accept boundary.

    ``cands`` is the candidate tree from ``decode_verify_paged``'s layer
    walk: full-attention pool leaves are already final (length-pointer
    rollback), every other leaf is (count, B, K1, ...) — candidate j is
    the state after fed token j. ``commit``: (B,) int32 in [1, K1] —
    keep the state after fed token ``commit - 1``.
    """
    idx = jnp.maximum(commit - 1, 0).astype(jnp.int32)

    def sel(leaf):
        ix = idx.reshape((1, -1, 1) + (1,) * (leaf.ndim - 3))
        return jnp.take_along_axis(leaf, ix, axis=2)[:, :, 0]

    def one(gk, pk, kind, count):
        sub = cands[gk][pk]
        if _is_pool_kind(cfg, kind):
            return sub
        return jax.tree.map(sel, sub)

    return map_layer_tree(cfg, one)


def decode_verify_paged(params, cfg: ModelConfig, pools, block_table,
                        lengths, tokens, commit_fn, ctx: RunCtx):
    """Speculative-decode verify: score a K+1-token window in ONE pass.

    tokens: (B, K1) — per slot, the last accepted token followed by K
    draft tokens; fed token j is cached at position ``lengths[b] + j``
    and logits row j scores the NEXT position — so row j is exactly what
    ``decode_step_paged`` would have returned after feeding tokens
    0..j. ``commit_fn(logits (B, K1, V)) -> (out_tokens, commit)`` is
    the accept rule traced inline (engine/sampling.verify_accept);
    ``commit[b]`` in [1, K1] counts the fed tokens whose cache state to
    keep. Full-attention pools commit by construction (the host rewinds
    the length pointer over the rejected tail — no block copies);
    per-slot states are selected at the accept boundary. Returns
    (out_tokens (B, K1), commit (B,), new_pools).
    """
    if cfg.enc_dec or cfg.rope_style == "mrope" or cfg.pos_embed != "none":
        raise NotImplementedError(
            "paged verify supports decoder-only rope/none-pos models")
    x = _embed(params, cfg, tokens, shard=ctx.shard)

    def block_fn(kind, lp, lc, xc):
        return apply_block_verify_paged(lp, cfg, kind, xc, lc,
                                        block_table, lengths, ctx)

    x, cands = scan_groups(params, cfg, x, pools, block_fn, ctx)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits(params, cfg, x)                  # (B, K1, V) f32
    out_tokens, commit = commit_fn(logits)
    return out_tokens, commit, select_verify_state(cfg, cands, commit)


def prefill_supports_ragged(cfg: ModelConfig) -> bool:
    """True when right-padded (bucketed / ragged-batch) prefill is exact
    for this architecture: every block kind captures its decode state at
    the traced true length (attention rings and RG-LRU by gather/
    recompute, mlstm by gate freezing, slstm by carry selection), and
    positions are either relative (rope) or absent. The serving engines
    gate on this and fall back to exact-length prefill otherwise."""
    kinds = set(cfg.block_pattern)
    return (kinds <= {"attn", "local", "rglru", "mlstm", "slstm"}
            and not cfg.enc_dec and not cfg.visual_prefix
            and cfg.rope_style in ("rope", "none")
            and cfg.pos_embed == "none")


def prefill(params, cfg: ModelConfig, tokens, ctx: RunCtx, max_len=None,
            visual_embeds=None, mrope_positions=None, length=None):
    """Prefill: logits for the full prompt + a decode cache at max_len.

    ``length`` ((B,) int32, traced) marks RIGHT-padded prompts: row b's
    real tokens are ``tokens[b, :length[b]]``. Causal attention already
    ignores the padded tail for every real query, so logits at real
    positions are exact; the emitted caches capture per-row state at the
    true length (see ``apply_block``). Requires
    ``prefill_supports_ragged(cfg)``.
    """
    B, S = tokens.shape
    if length is not None and not prefill_supports_ragged(cfg):
        raise NotImplementedError(
            f"{cfg.name}: padded prefill needs a decoder-only stack "
            "with relative/absent positions")
    x = _embed(params, cfg, tokens, visual_embeds, shard=ctx.shard)
    positions = jnp.arange(S, dtype=jnp.int32)
    x, aux, caches = _apply_groups(params, cfg, x, positions, ctx,
                                   mrope_positions, with_cache=True,
                                   cache_len=max_len or S,
                                   prefill_length=length)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x), caches


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, ctx: RunCtx,
                mrope_positions=None):
    """tokens: (B, 1) at position ``pos`` -> (logits (B, V), new cache)."""
    x = _embed(params, cfg, tokens, pos_offset=pos, shard=ctx.shard)

    def block_fn(kind, lp, lc, xc):
        return apply_block_decode(lp, cfg, kind, xc, lc, pos, ctx,
                                  mrope_positions)

    x, new_caches = scan_groups(params, cfg, x, cache, block_fn, ctx)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x)[:, 0], new_caches
