"""Public kernel API — jit'd wrappers with backend dispatch + VLA padding.

Every op takes ``mode``:
  'auto'      — Pallas on TPU, jnp reference on CPU (dry-run lowering path;
                identical math so HLO FLOP/byte counts stay representative)
  'pallas'    — force pallas_call (real TPU execution)
  'interpret' — pallas_call(interpret=True): kernel body runs in Python on
                CPU — the per-kernel correctness gate used by tests/
  'ref'       — force the pure-jnp oracle

Inputs of arbitrary size are padded to block multiples and sliced back —
the VEC tile's vector-length-agnostic discipline (no scalar tails, no
shape-specialized kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .flash_attention import flash_attention_pallas
from .paged_attention import (paged_decode_attention_headshard as
                              _pa_headshard)
from .paged_attention import (paged_verify_attention_headshard as
                              _pv_headshard)
from .paged_attention import (paged_decode_attention_pallas,
                              paged_verify_attention_pallas)
from .rglru_scan import rglru_scan_pallas
from .stx_matmul import stx_matmul_pallas
from .stx_stencil import stencil2d_pallas, stencil3d_pallas
from .vrp_dot import vrp_dot_pallas, vrp_sum_pallas


def _use_pallas(mode: str) -> tuple[bool, bool]:
    """-> (use pallas, interpret flag)."""
    if mode == "auto":
        return (jax.default_backend() == "tpu", False)
    if mode == "pallas":
        return True, False
    if mode == "interpret":
        return True, True
    if mode == "ref":
        return False, False
    raise ValueError(f"unknown kernel mode {mode!r}")


def _ref_grad(fast, ref):
    """``fast``'s forward with the gradient of ``ref`` (same math):
    the Pallas kernels have no backward pass, so training differentiates
    the jnp reference instead."""
    f = jax.custom_vjp(fast)
    f.defvjp(lambda *args: (fast(*args), args),
             lambda args, g: jax.vjp(ref, *args)[1](g))
    return f


def _per_shard(fn, sharding, dims):
    """``fn`` run shard by shard on ``sharding``'s mesh (a ``ShardCtx``),
    or ``fn`` itself when ``sharding`` is None. GSPMD cannot partition a
    Mosaic kernel, so under a mesh a Pallas call needs a shard_map.

    ``dims`` gives each operand's dims by role: ``"b"`` splits over the
    batch axes and ``"h"`` (heads, channels) over the tp axis, each only
    where the mesh divides that dim in every operand; ``None`` stays
    whole. The output takes the first operand's spec.
    """
    if sharding is None:
        return fn
    from jax.sharding import PartitionSpec as P

    mesh = sharding.mesh
    split = {"b": tuple(sharding.batch_axes),
             "h": () if sharding.tp_axis in sharding.batch_axes
             else (sharding.tp_axis,)}
    width = {r: int(np.prod([mesh.shape[a] for a in ax]))
             for r, ax in split.items()}

    def run(*args):
        ok = {r: bool(ax) for r, ax in split.items()}
        for d, x in zip(dims, args):
            for role, n in zip(d, x.shape):
                if role is not None and n % width[role]:
                    ok[role] = False
        specs = tuple(P(*(split[r] if r is not None and ok[r] else None
                          for r in d)) for d in dims[:len(args)])
        # check_vma=False: pallas_call's out_shape carries no per-axis
        # variance, which the checked shard_map would demand
        return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=specs[0], check_vma=False)(*args)

    return run


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# ---------------------------------------------------------------------------


def stx_matmul(x, w, *, block_m=128, block_n=128, block_k=128, mode="auto",
               interpret=False, out_dtype=None):
    """(..., K) @ (K, N) through the STX tile."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.matmul(x, w, out_dtype=out_dtype)
    lead = x.shape[:-1]
    xm = x.reshape(-1, x.shape[-1])
    xm, m0 = _pad_to(xm, 0, block_m)
    xm, k0 = _pad_to(xm, 1, block_k)
    wp, _ = _pad_to(w, 0, block_k)
    wp, n0 = _pad_to(wp, 1, block_n)
    out = stx_matmul_pallas(xm, wp, block_m=block_m, block_n=block_n,
                            block_k=block_k, out_dtype=out_dtype,
                            interpret=interp)
    return out[:m0, :n0].reshape(*lead, n0)


def stencil2d(x, weights, *, block_m=128, block_n=128, mode="auto",
              interpret=False):
    """3x3 weighted stencil, zero boundary; x: (M, N)."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.stencil2d(x, weights)
    xp, m0 = _pad_to(x, 0, block_m)
    xp, n0 = _pad_to(xp, 1, block_n)
    out = stencil2d_pallas(xp, weights, block_m=block_m, block_n=block_n,
                           interpret=interp)
    return out[:m0, :n0]


def stencil3d(x, weights, *, block_d=8, block_m=32, block_n=128, mode="auto",
              interpret=False):
    """3x3x3 weighted stencil, zero boundary; x: (D, M, N)."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.stencil3d(x, weights)
    xp, d0 = _pad_to(x, 0, block_d)
    xp, m0 = _pad_to(xp, 1, block_m)
    xp, n0 = _pad_to(xp, 2, block_n)
    out = stencil3d_pallas(xp, weights, block_d=block_d, block_m=block_m,
                           block_n=block_n, interpret=interp)
    return out[:d0, :m0, :n0]


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, mode="auto", interpret=False,
                    sharding=None):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D). Under ``sharding`` (a
    ``ShardCtx``) the kernel runs per batch and head shard."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    ref = functools.partial(_ref.flash_attention, causal=causal,
                            window=window, scale=scale)
    if not use and not interp:
        return ref(q, k, v)

    def fast(q, k, v):
        qp, sq0 = _pad_to(q, 2, block_q)
        kp, skv0 = _pad_to(k, 2, block_k)
        vp, _ = _pad_to(v, 2, block_k)
        out = flash_attention_pallas(
            qp, kp, vp, causal=causal, window=window, scale=scale,
            kv_len=skv0, block_q=block_q, block_k=block_k,
            interpret=interp)
        return out[:, :, :sq0]

    bhsd = ("b", "h", None, None)
    fast = _per_shard(fast, sharding, (bhsd,) * 3)
    return _ref_grad(fast, ref)(q, k, v)


def paged_attention(q, pool, block_table, lengths, *, mode="decode",
                    layer=None, window=None, scale=None, kernel_mode="auto",
                    sharding=None, tp_axis="model", kv_format=None,
                    interpret=False):
    """ONE entry point for paged attention over a pool dict.

    Unifies what used to be four call sites (decode / verify, plain /
    head-sharded) behind a single dispatcher, so quantized pools and
    lane-padded layouts plug in without new entry points:

    * ``mode="decode"`` — q: (B, Hq, D), one query row per slot at
      position ``lengths[b] - 1``; ``mode="verify"`` — q: (B, K1, Hq, D)
      speculative K+1 query rows, ``lengths`` counting tokens cached
      BEFORE the window.
    * ``pool`` — the per-layer pool dict ``{"k", "v"}``; a quantized
      pool also carries ``k_scale``/``v_scale`` (NB, BS, Hkv) f32
      leaves, detected here and fused into whichever backend runs.
    * ``layer`` — decode only: an int32 scalar that selects one layer
      of a STACKED pool dict (every leaf with a leading layer axis,
      ``(L, NB, BS, Hkv, D)``), which the kernel reads in place; None
      for a one-layer pool.
    * ``sharding`` — None for single-device, or an object with ``mesh``
      / ``tp_axis`` attributes (e.g. ``ShardCtx``) for the head-sharded
      shard_map path (scales shard over Hkv with the payload).
    * ``kv_format`` — the pool's ``paged_kv.PoolSpec`` (or None). Used
      for the lane-padding contract: when blocks are physically wider
      than the model head dim (``padded_head_dim``), q is zero-padded to
      the block width and the output sliced back; the softmax scale
      ALWAYS derives from the logical head dim. The spec is advisory —
      quantization is detected from the pool leaves — so bf16 callers
      may pass None.
    * ``kernel_mode`` — the usual backend switch ('auto' / 'pallas' /
      'interpret' / 'ref'), oracle and Pallas paths taking identical
      arguments.
    """
    if mode not in ("decode", "verify"):
        raise ValueError(f"mode must be 'decode' or 'verify', got {mode!r}")
    decode = mode == "decode"
    if layer is not None and not decode:
        raise ValueError("a layer index reads a stacked pool in decode "
                         "mode only")
    stacked = {"layer": layer} if decode else {}
    k_pool, v_pool = pool["k"], pool["v"]
    k_scale, v_scale = pool.get("k_scale"), pool.get("v_scale")
    D = q.shape[-1]
    Dp = k_pool.shape[-1]
    if scale is None:
        scale = float(1.0 / np.sqrt(D))   # logical head dim, pre-padding
    if Dp != D:
        widths = [(0, 0)] * (q.ndim - 1) + [(0, Dp - D)]
        q = jnp.pad(q, widths)
    use, interp = _use_pallas(kernel_mode)
    interp = interp or interpret
    if sharding is not None:
        if not use and not interp:
            attend = _ref.paged_decode_attention if decode \
                else _ref.paged_verify_attention
        else:
            attend = functools.partial(
                paged_decode_attention_pallas if decode
                else paged_verify_attention_pallas, interpret=interp)
        fn = _pa_headshard if decode else _pv_headshard
        out = fn(q, k_pool, v_pool, block_table, lengths,
                 mesh=sharding.mesh,
                 tp_axis=getattr(sharding, "tp_axis", tp_axis),
                 window=window, scale=scale, attend=attend,
                 k_scale=k_scale, v_scale=v_scale, **stacked)
    elif not use and not interp:
        fn = _ref.paged_decode_attention if decode \
            else _ref.paged_verify_attention
        out = fn(q, k_pool, v_pool, block_table, lengths, window=window,
                 scale=scale, k_scale=k_scale, v_scale=v_scale, **stacked)
    else:
        fn = paged_decode_attention_pallas if decode \
            else paged_verify_attention_pallas
        out = fn(q, k_pool, v_pool, block_table, lengths, window=window,
                 scale=scale, k_scale=k_scale, v_scale=v_scale,
                 interpret=interp, **stacked)
    return out[..., :D] if Dp != D else out


# -- thin deprecated aliases (one-PR deprecation window) --------------------
# The four historical entry points forward to ``paged_attention``; new
# call sites should use the dispatcher directly.


def paged_decode_attention(q, k_pool, v_pool, block_table, lengths, *,
                           window=None, scale=None, mode="auto",
                           interpret=False):
    """Deprecated alias: ``paged_attention(..., mode="decode")``.

    q: (B, Hq, D); k_pool/v_pool: (NB, BS, Hkv, D); block_table:
    (B, NBMAX) int32; lengths: (B,) int32 valid tokens per sequence
    (including the current token). No padding pass is needed: the pool is
    block-shaped by construction and raggedness is masked in-kernel.
    """
    return paged_attention(q, {"k": k_pool, "v": v_pool}, block_table,
                           lengths, mode="decode", window=window,
                           scale=scale, kernel_mode=mode,
                           interpret=interpret)


def paged_verify_attention(q, k_pool, v_pool, block_table, lengths, *,
                           window=None, scale=None, mode="auto",
                           interpret=False):
    """Deprecated alias: ``paged_attention(..., mode="verify")``.

    q: (B, K1, Hq, D) — K+1 query rows per sequence at positions
    ``lengths[b] + j``, whose K/V are already written to the pool;
    lengths: (B,) int32 tokens cached BEFORE the verify window. Row j
    attends positions < ``lengths[b] + 1 + j`` (causal within the
    window), so each row is equivalent to ``paged_decode_attention`` at
    its own length while every pool block is fetched once for all rows.
    """
    return paged_attention(q, {"k": k_pool, "v": v_pool}, block_table,
                           lengths, mode="verify", window=window,
                           scale=scale, kernel_mode=mode,
                           interpret=interpret)


class _MeshSharding:
    """Minimal ``sharding`` adapter for the deprecated headshard aliases
    (the dispatcher wants an object with ``mesh``/``tp_axis``)."""

    def __init__(self, mesh, tp_axis):
        self.mesh = mesh
        self.tp_axis = tp_axis


def paged_decode_attention_headshard(q, k_pool, v_pool, block_table,
                                     lengths, *, mesh, tp_axis="model",
                                     window=None, scale=None, mode="auto",
                                     interpret=False):
    """Deprecated alias: ``paged_attention(..., mode="decode",
    sharding=...)`` — head-sharded multi-device paged decode attention
    (see kernels/paged_attention.py for the layout argument)."""
    return paged_attention(q, {"k": k_pool, "v": v_pool}, block_table,
                           lengths, mode="decode", window=window,
                           scale=scale, kernel_mode=mode,
                           sharding=_MeshSharding(mesh, tp_axis),
                           interpret=interpret)


def paged_verify_attention_headshard(q, k_pool, v_pool, block_table,
                                     lengths, *, mesh, tp_axis="model",
                                     window=None, scale=None, mode="auto",
                                     interpret=False):
    """Deprecated alias: ``paged_attention(..., mode="verify",
    sharding=...)`` — head-sharded multi-device multi-query verify
    attention over the speculative window."""
    return paged_attention(q, {"k": k_pool, "v": v_pool}, block_table,
                           lengths, mode="verify", window=window,
                           scale=scale, kernel_mode=mode,
                           sharding=_MeshSharding(mesh, tp_axis),
                           interpret=interpret)


def _finalize_expansion(lanes):
    """Compensated tree over per-lane (8, 128, 2) partials -> (2,)."""
    from repro.core import vrp
    from repro.core.precision import PrecisionEnv

    env = PrecisionEnv(compute_terms=2, base_dtype=str(lanes.dtype))
    return vrp.tree_sum(lanes.reshape(-1, 2), env)


def vrp_dot(x, y, *, mode="auto", interpret=False):
    """Double-word dot of flat vectors -> (2,) expansion [hi, lo]."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.vrp_dot(x, y)
    xp, _ = _pad_to(x.reshape(-1), 0, 1024)
    yp, _ = _pad_to(y.reshape(-1), 0, 1024)
    lanes = vrp_dot_pallas(xp, yp, interpret=interp)
    return _finalize_expansion(lanes)


def vrp_sum(x, *, mode="auto", interpret=False):
    """Double-word sum of a flat vector -> (2,) expansion [hi, lo]."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.vrp_sum(x)
    xp, _ = _pad_to(x.reshape(-1), 0, 1024)
    lanes = vrp_sum_pallas(xp, interpret=interp)
    return _finalize_expansion(lanes)


def rglru_scan(a, x, h0=None, *, block_b=8, block_t=128, block_d=128,
               mode="auto", interpret=False, sharding=None):
    """Diagonal linear recurrence h_t = a_t h_{t-1} + x_t; (B, T, D).
    Under ``sharding`` (a ``ShardCtx``) the kernel runs per batch and
    channel shard."""
    use, interp = _use_pallas(mode)
    interp = interp or interpret
    if not use and not interp:
        return _ref.linear_scan(a, x, h0)

    def fast(a, x, *h0):
        ap, b0 = _pad_to(a, 0, block_b)
        xp, _ = _pad_to(x, 0, block_b)
        ap, t0 = _pad_to(ap, 1, block_t)
        xp, _ = _pad_to(xp, 1, block_t)
        ap, d0 = _pad_to(ap, 2, block_d)
        xp, _ = _pad_to(xp, 2, block_d)
        h0p = None
        if h0:
            h0p, _ = _pad_to(h0[0], 0, block_b)
            h0p, _ = _pad_to(h0p, 1, block_d)
        out = rglru_scan_pallas(ap, xp, h0p, block_b=block_b,
                                block_t=block_t, block_d=block_d,
                                interpret=interp)
        return out[:b0, :t0, :d0]

    btd = ("b", None, "h")
    fast = _per_shard(fast, sharding, (btd, btd, ("b", "h")))
    h0s = () if h0 is None else (h0,)
    return _ref_grad(fast, _ref.linear_scan)(a, x, *h0s)
