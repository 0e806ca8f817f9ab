"""Mixture-of-Experts with expert parallelism (EP) over the model axis.

Dispatch strategy (the uncore analogy): tokens are the NoC "flits" and
experts the distributed L2 slices — the router computes the interleaving.
We use a sort-based capacity dispatch (no (T, E, C) one-hot tensor, which
is O(T*E*C) memory and infeasible at kimi-k2 scale):

  1. route: top-k expert ids + weights per token (router replicated),
  2. sort assignments by expert id; position-within-expert via cumsum,
  3. gather up to C tokens per *local* expert into (E_local, C, d),
  4. three grouped einsums (gated FFN),
  5. scatter-add back with routing weights; psum over the model axis.

Two code paths with identical math: ``apply_moe`` (single-device: all
experts local) and ``apply_moe_sharded`` (shard_map: experts sharded over
the TP axis, expert weights FSDP-gathered over the DP axes on use).
tests/test_moe.py checks local == sharded on a multi-device CPU mesh.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.models import layers


def init_moe(key, cfg, dtype):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    return {
        "router": layers.truncated_normal_init(ks[0], (d, E), jnp.float32),
        "w1": (std * jax.random.truncated_normal(ks[1], -2, 2, (E, d, ff))).astype(dtype),
        "w3": (std * jax.random.truncated_normal(ks[2], -2, 2, (E, d, ff))).astype(dtype),
        "w2": (1.0 / math.sqrt(ff) * jax.random.truncated_normal(
            ks[3], -2, 2, (E, ff, d))).astype(dtype),
    }


def route(x2d, router_w, top_k: int, *, normalize=True):
    """x2d: (T, d) -> (ids (T,k), weights (T,k) f32, load (E,), imp (E,)).

    The Switch aux loss E*sum(load*imp) is computed by the CALLER so that
    sharded paths can pmean load/imp across shards BEFORE the (nonlinear)
    product — per-shard aux values do not average to the global aux.
    """
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, top_k)
    if normalize:
        topw = topw / jnp.sum(topw, -1, keepdims=True)
    E = router_w.shape[-1]
    load = jnp.mean(jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32), axis=0)
    imp = jnp.mean(probs, axis=0)
    return topi, topw, load, imp


def aux_loss(load, imp):
    return load.shape[-1] * jnp.sum(load * imp)


def _dispatch_indices(topi, top_k: int, n_experts: int, capacity: int):
    """Sorted assignment bookkeeping shared by both paths.

    Returns (sorted expert id, sorted token id, sorted weight index,
    position-within-expert) — all (T*k,).
    """
    T = topi.shape[0]
    flat_e = topi.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_t[order]
    counts = jnp.bincount(flat_e, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    return se, st, order, pos


def _expert_ffn(xg, w1, w3, w2, activation="silu"):
    """xg: (E, C, d) through per-expert gated FFN."""
    act = {"silu": jax.nn.silu, "gelu": lambda v: jax.nn.gelu(v, approximate=True)}[activation]
    h = act(jnp.einsum("ecd,edf->ecf", xg, w1)) * jnp.einsum("ecd,edf->ecf", xg, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _capacity(cfg, T: int, dropless: bool) -> int:
    """Tokens each expert can take. ``dropless`` sizes the buffers so NO
    assignment ever overflows (an expert holds at most T tokens — top-k
    ids are distinct per token): per-token math then depends only on
    that token's own hidden state, which is what the serving engine's
    identity contract needs — outputs independent of right-padding,
    co-batched traffic and batch width. Training keeps the
    capacity-factor drop semantics (the Switch efficiency/auxiliary
    story needs over-capacity tokens to actually drop)."""
    if dropless:
        return T
    return max(1, int(math.ceil(T * cfg.moe_top_k / cfg.n_experts
                                * cfg.moe_capacity_factor)))


def _moe_math(x2d, params_router, w1, w3, w2, cfg, e_lo: int, e_local: int,
              dropless: bool = False):
    """Shared dispatch->compute->combine on one device's experts.

    x2d: (T, d). Experts [e_lo, e_lo + e_local) live here. Returns the
    *partial* output (T, d) (sum over local experts only) plus aux loss.
    """
    T, d = x2d.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    C = _capacity(cfg, T, dropless)
    topi, topw, load, imp = route(x2d, params_router, k)
    se, st, order, pos = _dispatch_indices(topi, k, E, C)
    sw = topw.reshape(-1)[order]

    local = jnp.logical_and(se >= e_lo, se < e_lo + e_local)
    valid = jnp.logical_and(local, pos < C)
    slot = jnp.where(valid, (se - e_lo) * C + pos, e_local * C)  # overflow row

    xg = jnp.zeros((e_local * C + 1, d), x2d.dtype).at[slot].set(x2d[st])
    yg = _expert_ffn(xg[:-1].reshape(e_local, C, d), w1, w3, w2,
                     cfg.activation)
    yg = yg.reshape(e_local * C, d)
    contrib = jnp.where(valid[:, None], yg[jnp.minimum(slot, e_local * C - 1)]
                        * sw[:, None].astype(yg.dtype), 0.0)
    out = jnp.zeros((T, d), yg.dtype).at[st].add(contrib)
    return out.astype(x2d.dtype), (load, imp)


def apply_moe(params, cfg, x, dropless: bool = False):
    """Single-device MoE. x: (B, S, d) -> (out, aux). ``dropless``
    disables capacity dropping (serving paths — see ``_capacity``)."""
    B, S, d = x.shape
    out, (load, imp) = _moe_math(x.reshape(-1, d), params["router"],
                                 params["w1"], params["w3"], params["w2"],
                                 cfg, 0, cfg.n_experts, dropless=dropless)
    return out.reshape(B, S, d), aux_loss(load, imp)


def _dp_index(dp):
    """Linear index over a (possibly composite) DP axis tuple."""
    idx = jax.lax.axis_index(dp[0])
    for a in dp[1:]:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def apply_moe_sharded(params, cfg, x, shard, mode: str = "gather",
                      dropless: bool = False):
    """EP MoE under shard_map. Two collective schedules:

    'gather'  (baseline, paper-faithful FSDP): expert weights are
        all-gathered over DP on use, full-d contraction, output psum over
        TP. Weight traffic per layer ~ 3 x |experts_local| x d x ff.
    'partial' (§Perf hillclimb): weights stay DP-sharded; the contraction
        runs on each device's d-slice and ACTIVATION partial sums move
        instead (h psums over DP, output all-gather over DP). For kimi-k2
        this trades ~6.3 GB/layer of weight gathers for ~0.8 GB/layer of
        activation traffic — the EPAC lesson that the NoC should move the
        smaller operand.
    """
    from jax.sharding import PartitionSpec as P

    mesh = shard.mesh
    dp, tp = shard.dp_axes, shard.tp_axis
    tp_size = mesh.shape[tp]
    assert cfg.n_experts % tp_size == 0, (cfg.n_experts, tp_size)
    e_local = cfg.n_experts // tp_size

    def local_gather(x_l, router, w1_l, w3_l, w2_l):
        B_l, S_l, d = x_l.shape
        w1 = jax.lax.all_gather(w1_l, dp, axis=1, tiled=True)
        w3 = jax.lax.all_gather(w3_l, dp, axis=1, tiled=True)
        w2 = jax.lax.all_gather(w2_l, dp, axis=2, tiled=True)
        e_lo = jax.lax.axis_index(tp) * e_local
        out, (load, imp) = _moe_math(x_l.reshape(-1, d), router, w1, w3, w2,
                                     cfg, e_lo, e_local, dropless=dropless)
        out = jax.lax.psum(out, tp)
        load = jax.lax.pmean(load, dp)   # identical over tp already
        imp = jax.lax.pmean(imp, dp)
        return out.reshape(B_l, S_l, d), aux_loss(load, imp)

    def local_partial(x_l, router, w1_l, w3_l, w2_l):
        B_l, S_l, d = x_l.shape
        T = B_l * S_l
        E, k = cfg.n_experts, cfg.moe_top_k
        C = _capacity(cfg, T, dropless)
        d_loc = w1_l.shape[1]
        x2 = x_l.reshape(T, d)
        topi, topw, load, imp = route(x2, router, k)
        se, st, order, pos = _dispatch_indices(topi, k, E, C)
        sw = topw.reshape(-1)[order]
        e_lo = jax.lax.axis_index(tp) * e_local
        local = jnp.logical_and(se >= e_lo, se < e_lo + e_local)
        valid = jnp.logical_and(local, pos < C)
        slot = jnp.where(valid, (se - e_lo) * C + pos, e_local * C)
        # Gather only my d-slice of the tokens into capacity buffers.
        d_lo = _dp_index(dp) * d_loc
        x_slice = jax.lax.dynamic_slice_in_dim(x2, d_lo, d_loc, axis=1)
        xg = jnp.zeros((e_local * C + 1, d_loc), x2.dtype).at[slot].set(
            x_slice[st])
        xg = xg[:-1].reshape(e_local, C, d_loc)
        # Partial contraction over d; psum assembles the full h.
        act = {"silu": jax.nn.silu,
               "gelu": lambda v: jax.nn.gelu(v, approximate=True)}[cfg.activation]
        h1 = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xg, w1_l), dp)
        h3 = jax.lax.psum(jnp.einsum("ecd,edf->ecf", xg, w3_l), dp)
        h = act(h1) * h3
        yg = jnp.einsum("ecf,efd->ecd", h, w2_l)      # (E_loc, C, d_loc)
        yg = yg.reshape(e_local * C, d_loc)
        contrib = jnp.where(
            valid[:, None],
            yg[jnp.minimum(slot, e_local * C - 1)] * sw[:, None].astype(yg.dtype),
            0.0)
        out_loc = jnp.zeros((T, d_loc), yg.dtype).at[st].add(contrib)
        out_loc = jax.lax.psum(out_loc, tp)           # sum expert groups
        out = jax.lax.all_gather(out_loc, dp, axis=1, tiled=True)
        load = jax.lax.pmean(load, dp)
        imp = jax.lax.pmean(imp, dp)
        return out.reshape(B_l, S_l, d).astype(x_l.dtype), aux_loss(load, imp)

    local_fn = local_gather if mode == "gather" else local_partial
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P(tp, dp, None), P(tp, dp, None), P(tp, None, dp)),
        out_specs=(P(dp, None, None), P()),
    )(x, params["router"], params["w1"], params["w3"], params["w2"])
    return out, aux
