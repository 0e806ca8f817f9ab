"""Paged (ragged) decode-attention kernel — block-pool KV gather on-chip.

The serving engine's KV cache is a shared pool of fixed-size token blocks
with a per-sequence block table (models/paged_kv.py) — the software
analogue of EPAC's distributed L2 slices under programmable address
interleaving: a sequence's logical positions are scattered over physical
slices, and the *index map* (here the prefetched block table) is the
hardware address-generation step.

One grid step = one (sequence, logical block) pair; the kv axis is
innermost-sequential and carries the online-softmax (m, l, acc) scratch,
exactly like kernels/flash_attention.py. The block table and per-sequence
lengths arrive via PrefetchScalarGridSpec so the BlockSpec index map can
route each grid step's DMA to the right physical block — fully-masked
blocks (past a sequence's length, or entirely outside its sliding window)
are predicated off before touching the MXU.

Ragged batches therefore cost O(sum(ceil(len_i / BS))) block fetches, not
O(B * max_len) — the whole point of continuous batching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import I0, MASK_VALUE, ONE, ZERO

# Megacore work split: the batch axis is embarrassingly parallel (each
# sequence owns its own online-softmax scratch); the kv-block axis is
# sequential by construction. Interpret mode ignores compiler params.
_MEGACORE = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


def _dequant(ref, scale_ref, lead=1):
    """Load one (BS, Hkv, D) block in f32, fusing the per-(token, head)
    dequant multiply when the pool carries int8/fp8 payload + scales —
    the fp copy of the block exists only in VMEM registers, never in
    HBM. ``lead`` leading unit block dims (the block, and the layer of a
    stacked pool) are indexed away."""
    x = ref[(0,) * lead].astype(jnp.float32)
    if scale_ref is not None:
        s = scale_ref[(0,) * lead]                      # (BS, Hkv)
        # a one-head block's (BS, 1) scales already match its (BS, D)
        x = x * (s if s.ndim == x.ndim else s[..., None])
    return x


def _pa_kernel(bt_ref, len_ref, layer_ref, *refs, scale, window,
               block_size, hkv, group, nb, quantized):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    k_lo = i * block_size
    needed = k_lo < length
    if window is not None:
        needed = jnp.logical_and(needed,
                                 k_lo + block_size > length - window)

    @pl.when(needed)
    def _block():
        hq = hkv * group
        q = q_ref[0].astype(jnp.float32)                # (Hq, D)
        d = q.shape[-1]
        # (BS, Hkv, D); a one-head pool's blocks come as (BS, D)
        k = _dequant(k_ref, ks_ref, 2).reshape(block_size, hkv, d)
        v = _dequant(v_ref, vs_ref, 2).reshape(block_size, hkv, d)
        qg = q.reshape(hkv, group, d)
        kt = k.transpose(1, 0, 2)                       # (Hkv, BS, D)
        vt = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = s.reshape(hq, block_size)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (hq, block_size), 1)
        mask = kpos < length
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= length - window)
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                             # (Hq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, ZERO)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pg = p.reshape(hkv, group, block_size)
        pv = jax.lax.dot_general(pg, vt, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv.reshape(hq, d)
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _store():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == ZERO, ONE, l)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, block_table, lengths, *,
                                  layer=None, window=None, scale=None,
                                  k_scale=None, v_scale=None,
                                  interpret=False):
    """q: (B, Hq, D); pools: the stacked (L, NB, BS, Hkv, D) pools of
    every layer, read at ``layer`` (a traced int32 scalar), or one
    layer's (NB, BS, Hkv, D) pool (``layer`` ignored); block_table:
    (B, NBMAX); lengths: (B,) valid tokens incl. the current one;
    ``k_scale`` / ``v_scale``: the matching (L, NB, BS, Hkv) or
    (NB, BS, Hkv) f32 dequant scales for int8/fp8 pools (None = fp
    pool), DMA'd per block through the same prefetched index map as the
    payload and applied in VMEM. -> (B, Hq, D).

    The layer index is a scalar-prefetch operand, so the kernel DMAs
    the blocks of one layer straight out of the stack: the decode step
    carries the whole pool through its layer scan and never slices a
    layer out of it."""
    if k_pool.ndim == 4:                # one layer: a stack of one
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    B, Hq, D = q.shape
    L, NB, BS, Hkv, _ = k_pool.shape
    group = Hq // Hkv
    nbmax = block_table.shape[1]
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    quantized = k_scale is not None
    # One kv head (a head-sharded pool with n_kv_heads == tp): on the
    # chip the payload's layout drops the unit head axis, and an operand
    # that kept it would be copied whole, each layer, into the kernel's
    # layout. Its blocks go in as (BS, D). The scales keep the axis:
    # a (BS,) block of an (L, NB, BS) array does not lower.
    heads = () if Hkv == 1 else (Hkv,)
    k_pool, v_pool = (p.reshape((L, NB, BS) + heads + (D,))
                      for p in (k_pool, v_pool))

    def kv_map(b, i, bt, lens, lyr):
        return (lyr[0], bt[b, i]) + (I0,) * (1 + len(heads) + 1)

    def scale_map(b, i, bt, lens, lyr):
        return (lyr[0], bt[b, i], I0, I0)

    def q_map(b, i, bt, lens, lyr):
        return (b, I0, I0)

    in_specs = [
        pl.BlockSpec((1, Hq, D), q_map),
        pl.BlockSpec((1, 1, BS) + heads + (D,), kv_map),
        pl.BlockSpec((1, 1, BS) + heads + (D,), kv_map),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, BS, Hkv), scale_map)] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nbmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),    # running max
            pltpu.VMEM((Hq, 1), jnp.float32),    # running sum
            pltpu.VMEM((Hq, D), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_pa_kernel, scale=scale, window=window,
                          block_size=BS, hkv=Hkv, group=group, nb=nbmax,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        compiler_params=_MEGACORE,
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *operands)


def _pv_kernel(bt_ref, len_ref, *refs, scale, window, block_size,
               hkv, group, nb, k1, quantized):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]                 # cached BEFORE the verify window
    k_lo = i * block_size
    # the block is needed if ANY of the K+1 rows can see it: the last
    # row has the highest upper bound (length + k1), the first row the
    # lowest window floor (length + 1 - window)
    needed = k_lo < length + k1
    if window is not None:
        needed = jnp.logical_and(needed,
                                 k_lo + block_size > length + 1 - window)

    @pl.when(needed)
    def _block():
        hq = hkv * group
        q = q_ref[0].astype(jnp.float32)                # (K1, Hq, D)
        k = _dequant(k_ref, ks_ref)                     # (BS, Hkv, D)
        v = _dequant(v_ref, vs_ref)
        d = q.shape[-1]
        # group the query rows under their kv heads: (Hkv, K1*group, D)
        qg = q.reshape(k1, hkv, group, d).transpose(1, 0, 2, 3) \
              .reshape(hkv, k1 * group, d)
        kt = k.transpose(1, 0, 2)                       # (Hkv, BS, D)
        vt = v.transpose(1, 0, 2)
        s = jax.lax.dot_general(qg, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        # -> per-query-row layout (K1*Hq, BS), row-major in (K1, Hq)
        s = s.reshape(hkv, k1, group, block_size).transpose(1, 0, 2, 3) \
             .reshape(k1 * hq, block_size)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                               (k1 * hq, block_size), 1)
        # row j of the q-block attends positions < length + 1 + j
        j = jax.lax.broadcasted_iota(jnp.int32,
                                     (k1 * hq, block_size), 0) // np.int32(hq)
        limit = length + 1 + j
        mask = kpos < limit
        if window is not None:
            mask = jnp.logical_and(mask, kpos >= limit - window)
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[...]                             # (K1*Hq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, ZERO)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pg = p.reshape(k1, hkv, group, block_size).transpose(1, 0, 2, 3) \
              .reshape(hkv, k1 * group, block_size)
        pv = jax.lax.dot_general(pg, vt, (((2,), (1,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        pv = pv.reshape(hkv, k1, group, d).transpose(1, 0, 2, 3) \
               .reshape(k1 * hq, d)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(i == nb - 1)
    def _store():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == ZERO, ONE, l)
        o_ref[0] = out.reshape(k1, hkv * group, -1).astype(o_ref.dtype)


def paged_verify_attention_pallas(q, k_pool, v_pool, block_table, lengths,
                                  *, window=None, scale=None, k_scale=None,
                                  v_scale=None, interpret=False):
    """Multi-query-per-slot paged decode attention (speculative verify).

    q: (B, K1, Hq, D) — K+1 query rows per sequence for positions
    ``lengths[b] + j``; pools: (NB, BS, Hkv, D); block_table: (B, NBMAX);
    lengths: (B,) tokens cached BEFORE the window (the window's own K/V
    must already be written to the pool). Row j attends positions
    < ``lengths[b] + 1 + j``. ``k_scale``/``v_scale``: (NB, BS, Hkv)
    f32 dequant scales for int8/fp8 pools, fused in VMEM like the
    decode kernel. -> (B, K1, Hq, D).

    Same grid walk as ``paged_decode_attention_pallas`` — one step per
    (sequence, logical block), kv innermost-sequential carrying the
    online-softmax scratch — but the q-block is K+1 rows, so a verify
    step fetches each block ONCE for all K+1 queries instead of K+1
    times across sequential decode steps (the whole point: the decode
    loop's memory traffic amortizes over the speculative window).
    """
    B, K1, Hq, D = q.shape
    _, BS, Hkv, _ = k_pool.shape
    group = Hq // Hkv
    nbmax = block_table.shape[1]
    scale = np.float32(scale if scale is not None else 1.0 / np.sqrt(D))
    quantized = k_scale is not None

    def kv_map(b, i, bt, lens):
        return (bt[b, i], I0, I0, I0)

    def scale_map(b, i, bt, lens):
        return (bt[b, i], I0, I0)

    in_specs = [
        pl.BlockSpec((1, K1, Hq, D),
                     lambda b, i, bt, lens: (b, I0, I0, I0)),
        pl.BlockSpec((1, BS, Hkv, D), kv_map),
        pl.BlockSpec((1, BS, Hkv, D), kv_map),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, BS, Hkv), scale_map)] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nbmax),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, K1, Hq, D),
                               lambda b, i, bt, lens: (b, I0, I0, I0)),
        scratch_shapes=[
            pltpu.VMEM((K1 * Hq, 1), jnp.float32),    # running max
            pltpu.VMEM((K1 * Hq, 1), jnp.float32),    # running sum
            pltpu.VMEM((K1 * Hq, D), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_pv_kernel, scale=scale, window=window,
                          block_size=BS, hkv=Hkv, group=group, nb=nbmax,
                          k1=K1, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K1, Hq, D), q.dtype),
        compiler_params=_MEGACORE,
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


def paged_verify_attention_headshard(q, k_pool, v_pool, block_table,
                                     lengths, *, mesh, tp_axis="model",
                                     window=None, scale=None, attend=None,
                                     k_scale=None, v_scale=None,
                                     interpret=False):
    """Multi-device multi-query verify attention over a HEAD-sharded
    pool: the ``paged_decode_attention_headshard`` layout with a K+1
    q-block per sequence. Each device of ``tp_axis`` runs the stock
    verify kernel over its kv-head shard of every block — kv-head
    groups attend independently, so the sharded output needs NO
    collective and no pool byte crosses the interconnect.

    q: (B, K1, Hq, D) sharded over Hq; pools: (NB, BS, Hkv, D) sharded
    over Hkv; ``k_scale``/``v_scale``: (NB, BS, Hkv) f32 dequant scales
    for quantized pools, sharded over Hkv alongside the payload;
    requires ``paged_kv.head_shard_ok`` (head counts divide |tp|).
    ``attend`` is the per-shard op; defaults to the Pallas kernel.
    """
    from jax.sharding import PartitionSpec as P

    if attend is None:
        attend = functools.partial(paged_verify_attention_pallas,
                                   interpret=interpret)
    tp = tp_axis
    in_specs = (P(None, None, tp, None), P(None, None, tp, None),
                P(None, None, tp, None), P(None, None), P(None))
    operands = (q, k_pool, v_pool, block_table.astype(jnp.int32),
                lengths.astype(jnp.int32))

    if k_scale is None:
        def local(qv, kp, vp, bt, ln):
            return attend(qv, kp, vp, bt, ln, window=window, scale=scale)
    else:
        in_specs += (P(None, None, tp), P(None, None, tp))
        operands += (k_scale, v_scale)

        def local(qv, kp, vp, bt, ln, ks, vs):
            return attend(qv, kp, vp, bt, ln, window=window, scale=scale,
                          k_scale=ks, v_scale=vs)

    # check_vma=False: pallas_call's out_shape carries no per-axis
    # variance, which the checked shard_map would demand
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=P(None, None, tp, None), check_vma=False,
    )(*operands)


def paged_decode_attention_headshard(q, k_pool, v_pool, block_table,
                                     lengths, *, mesh, tp_axis="model",
                                     layer=None, window=None, scale=None,
                                     attend=None, k_scale=None,
                                     v_scale=None, interpret=False):
    """Multi-device paged decode attention over a HEAD-sharded pool.

    The ``decode_seq_shard`` idea applied to the pool layout (the
    ROADMAP multi-device variant over the block pool): every device on
    the ``tp_axis`` owns its kv-head shard of EVERY physical block —
    the software analogue of slicing EPAC's distributed L2 by way
    rather than by address — while block tables and lengths stay
    replicated scalars. Because kv-head groups attend independently,
    each shard runs the stock single-device kernel over its local heads
    and the sharded output needs NO collective; no pool byte ever
    crosses the interconnect.

    q: (B, Hq, D) sharded over Hq; pools: (NB, BS, Hkv, D), or the
    stacked (L, NB, BS, Hkv, D) read at ``layer``, sharded over Hkv
    (the layer axis stays whole); ``k_scale``/``v_scale``: the matching
    f32 dequant scales for quantized pools, sharded over Hkv alongside
    the payload; requires Hq % |tp| == 0 and Hkv % |tp| == 0 (group
    alignment then holds automatically — see ``paged_kv.head_shard_ok``).
    ``attend`` is the per-shard op; defaults to the Pallas kernel.
    """
    from jax.sharding import PartitionSpec as P

    if attend is None:
        attend = functools.partial(paged_decode_attention_pallas,
                                   interpret=interpret)
    tp = tp_axis
    pool_spec = P(*(None,) * (k_pool.ndim - 2), tp, None)
    in_specs = (P(None, tp, None), pool_spec, pool_spec, P(None, None),
                P(None), P())
    operands = (q, k_pool, v_pool, block_table.astype(jnp.int32),
                lengths.astype(jnp.int32),
                jnp.asarray(0 if layer is None else layer, jnp.int32))
    if k_scale is not None:
        scale_spec = P(*(None,) * (k_scale.ndim - 1), tp)
        in_specs += (scale_spec, scale_spec)
        operands += (k_scale, v_scale)

    def local(qv, kp, vp, bt, ln, ly, *scales):
        ks, vs = scales if scales else (None, None)
        return attend(qv, kp, vp, bt, ln, layer=ly, window=window,
                      scale=scale, k_scale=ks, v_scale=vs)

    # check_vma=False: pallas_call's out_shape carries no per-axis
    # variance, which the checked shard_map would demand
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=P(None, tp, None), check_vma=False,
    )(*operands)
