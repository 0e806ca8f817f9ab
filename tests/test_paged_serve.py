"""Paged KV cache + the unified serving Engine: kernel/layer/engine
equivalence and scheduler invariants (tentpole coverage).

Contract chain, weakest to strongest:
  1. paged kernel (interpret) == jnp ref oracle, over GQA/MQA, sliding
     window, ragged lengths and block-boundary cases;
  2. paged layer decode == dense layer decode on identical histories;
  3. right-padded (bucketed) prefill == exact-length prefill, logits and
     downstream decode;
  4. Engine equivalence: paged backend == static backend == unbatched
     oracle, greedy, on ragged prompts (the PR-1 left-pad leak is the
     regression target), through real smoke models;
  5. scheduler invariants: no block leaked/double-freed under optimistic
     admission + LIFO preemption, retired slots reused, outputs
     independent of admission order and preemption history, bucketed
     prefill compile cap.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.launch.engine import Engine, EngineConfig, SamplingParams
from repro.models import attention as attn_lib
from repro.models import paged_kv
from repro.models.model import Model
from repro.models.transformer import RunCtx

CTX = RunCtx(kernel_mode="ref")


def _rand_pool_case(rng, B, hq, hkv, hd, bs, nbmax, lengths):
    nb = B * nbmax + 1
    q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, hkv, hd)), jnp.float32)
    # distinct physical blocks per sequence, deliberately scrambled
    perm = rng.permutation(nb - 1) + 1
    bt = jnp.asarray(perm[:B * nbmax].reshape(B, nbmax), jnp.int32)
    return q, kp, vp, bt, jnp.asarray(lengths, jnp.int32)


# -- 1. kernel vs oracle ------------------------------------------------


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("window", [None, 5])
def test_paged_kernel_matches_ref(rng, hq, hkv, window):
    bs, nbmax = 4, 4
    # ragged: mid-block, exact block boundary, single token, full
    lengths = [7, 8, 1, 16]
    q, kp, vp, bt, ln = _rand_pool_case(rng, 4, hq, hkv, 16, bs, nbmax,
                                        lengths)
    got = ops.paged_decode_attention(q, kp, vp, bt, ln, window=window,
                                     mode="interpret")
    want = ref.paged_decode_attention(q, kp, vp, bt, ln, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_bf16(rng):
    q, kp, vp, bt, ln = _rand_pool_case(rng, 2, 4, 2, 32, 8, 2, [5, 11])
    q, kp, vp = (t.astype(jnp.bfloat16) for t in (q, kp, vp))
    got = ops.paged_decode_attention(q, kp, vp, bt, ln, mode="interpret")
    want = ref.paged_decode_attention(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


@given(st.integers(1, 31), st.integers(1, 31))
@settings(max_examples=15, deadline=None)
def test_paged_kernel_any_ragged_pair(l0, l1):
    """Property: any pair of lengths within the table range agrees with
    the oracle (block-boundary cases arise from the sweep)."""
    rng = np.random.default_rng(l0 * 100 + l1)
    q, kp, vp, bt, ln = _rand_pool_case(rng, 2, 4, 2, 8, 4, 8, [l0, l1])
    got = ops.paged_decode_attention(q, kp, vp, bt, ln, mode="interpret")
    want = ref.paged_decode_attention(q, kp, vp, bt, ln)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# -- 2. paged oracle vs dense attention on one history ------------------


def test_paged_ref_matches_dense_gather(rng):
    """Gathering a sequence's blocks and running dense attention over its
    first L positions must equal the paged oracle."""
    B, hq, hkv, hd, bs, nbmax = 3, 4, 2, 16, 4, 4
    lengths = [6, 12, 16]
    q, kp, vp, bt, ln = _rand_pool_case(rng, B, hq, hkv, hd, bs, nbmax,
                                        lengths)
    S = nbmax * bs
    k_seq = kp[bt].reshape(B, S, hkv, hd).transpose(0, 2, 1, 3)
    v_seq = vp[bt].reshape(B, S, hkv, hd).transpose(0, 2, 1, 3)
    paged = ref.paged_decode_attention(q, kp, vp, bt, ln)
    for b, L in enumerate(lengths):
        dense = ref.flash_attention(q[b:b + 1, :, None],
                                    k_seq[b:b + 1, :, :L],
                                    v_seq[b:b + 1, :, :L], causal=False)
        np.testing.assert_allclose(np.asarray(paged[b]),
                                   np.asarray(dense[0, :, 0]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch,window", [("olmo_1b", None),
                                         ("h2o_danube_3_4b", 16)])
def test_layer_decode_paged_matches_dense(rng, arch, window):
    """Replay the same token history through the dense decode_attend and
    the paged/batched path; outputs must agree step by step."""
    cfg = get_config(arch).smoke()
    params = attn_lib.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, n_steps = 2, 9
    layout = paged_kv.PagedLayout(num_slots=B, num_blocks=9, block_size=4,
                                  max_len=16)
    dense = attn_lib.init_kv_cache(cfg, B, 16, jnp.float32, window=window)
    if window is None:
        paged = paged_kv.init_layer_pool(cfg, layout, jnp.float32)
        table = np.zeros((B, layout.max_blocks_per_seq), np.int32)
        alloc = paged_kv.BlockAllocator(layout)
        for b in range(B):
            table[b] = alloc.alloc(layout.max_blocks_per_seq)
        table = jnp.asarray(table)
    else:
        paged = attn_lib.init_kv_cache(cfg, B, 16, jnp.float32,
                                       window=window)
    for t in range(n_steps):
        x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
        out_d, dense = attn_lib.decode_attend(params, cfg, x, dense,
                                              jnp.int32(t), window=window)
        lengths = jnp.full((B,), t, jnp.int32)
        if window is None:
            out_p, paged = attn_lib.decode_attend_paged(
                params, cfg, x, paged, table, lengths, kernel_mode="ref")
        else:
            out_p, paged = attn_lib.decode_attend_batched(
                params, cfg, x, paged, lengths, window=window)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"step {t}")


def _decode_step_by_slices(params, cfg, pools, table, lengths, tokens,
                           ctx):
    """The paged decode step through ``scan_groups`` with nothing
    carried: every layer's pool rides the layer scan as xs -> ys (sliced
    out of the stack, written, stacked again)."""
    from repro.models import layers, transformer

    x = transformer._embed(params, cfg, tokens)

    def block_fn(kind, lp, lc, xc):
        return transformer.apply_block_decode_paged(lp, cfg, kind, xc, lc,
                                                    table, lengths, ctx)

    x, new_pools = transformer.scan_groups(params, cfg, x, pools, block_fn,
                                           ctx)
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return transformer._logits(params, cfg, x)[:, 0], new_pools


@pytest.mark.parametrize("arch,pattern,kv_dtype,mode", [
    ("olmo_1b", None, "bf16", "ref"),
    ("olmo_1b", None, "int8", "interpret"),
    # windowed ring and RG-LRU state (xs -> ys) beside a pool (carry)
    ("recurrentgemma_2b", ("rglru", "attn", "local"), "bf16", "ref")])
def test_decode_step_in_place_matches_sliced_walk(rng, arch, pattern,
                                                  kv_dtype, mode):
    """``decode_step_paged`` (pools carried whole through the layer scan,
    updated in place by layer index) gives logits and new pools bit-equal
    to the walk that slices each layer's pool out of the stack."""
    import dataclasses

    cfg = get_config(arch).smoke()
    if pattern is not None:
        cfg = dataclasses.replace(cfg, block_pattern=pattern, n_layers=6)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    layout = paged_kv.PagedLayout(num_slots=3, num_blocks=13, block_size=4,
                                  max_len=16)
    spec = None if kv_dtype == "bf16" else paged_kv.make_pool_spec(
        cfg, layout, kv_dtype=kv_dtype)
    ctx = RunCtx(kernel_mode=mode, kv_spec=spec)

    def noise(t):
        if t.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, t.shape), t.dtype)
        return jnp.asarray(rng.normal(size=t.shape)).astype(t.dtype)

    pools = jax.tree.map(noise, model.init_paged_cache(layout, spec))
    table = jnp.asarray(rng.permutation(12).reshape(3, 4) + 1, jnp.int32)
    lengths = jnp.asarray([3, 8, 13], jnp.int32)
    tokens = jnp.asarray([[5], [7], [9]], jnp.int32)
    got = jax.jit(lambda p, pl: model.decode_step_paged(
        p, pl, table, lengths, tokens, ctx))(params, pools)
    want = jax.jit(lambda p, pl: _decode_step_by_slices(
        p, cfg, pl, table, lengths, tokens, ctx))(params, pools)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- 3. right-padded (bucketed) prefill == exact-length prefill ---------


@pytest.mark.parametrize("arch", ["olmo_1b", "h2o_danube_3_4b",
                                  "recurrentgemma_2b", "xlstm_1_3b"])
def test_padded_prefill_matches_exact(rng, arch):
    """Masked (right-padded) prefill must reproduce exact-length prefill:
    logits at every real position AND the downstream decode logits (i.e.
    ring/recurrent/conv cache state was extracted at the true length)."""
    cfg = get_config(arch).smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, Sb, ML = 11, 16, 32
    prompt = rng.integers(0, cfg.vocab_size, S)
    exact_t = jnp.asarray([prompt], jnp.int32)
    pad_t = jnp.zeros((1, Sb), jnp.int32).at[0, :S].set(exact_t[0])
    lg_e, cache_e = model.prefill(params, {"tokens": exact_t}, CTX,
                                  max_len=ML)
    lg_p, cache_p = model.prefill(params, {"tokens": pad_t}, CTX,
                                  max_len=ML,
                                  length=jnp.asarray([S], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg_p[:, :S]), np.asarray(lg_e),
                               rtol=1e-5, atol=1e-5)
    tok = jnp.argmax(lg_e[:, S - 1:S], -1).astype(jnp.int32)
    for t in range(4):
        de, cache_e = model.decode_step(params, cache_e, tok,
                                        jnp.int32(S + t), CTX)
        dp, cache_p = model.decode_step(params, cache_p, tok,
                                        jnp.int32(S + t), CTX)
        np.testing.assert_allclose(np.asarray(dp), np.asarray(de),
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"decode step {t}")
        tok = jnp.argmax(dp, -1)[:, None].astype(jnp.int32)


# -- 4. engine-level: paged == static == unbatched oracle ---------------


def _oracle_greedy(model, params, prompt, n_new, max_len=64):
    """Unbatched reference: exact prefill + scalar decode loop."""
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, CTX,
        max_len=max_len)
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    while len(out) < n_new:
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.int32(len(prompt) + len(out) - 1), CTX)
        out.append(int(jnp.argmax(lg[0])))
    return out


def _engine(model, params, backend, **kw):
    base = dict(backend=backend, num_slots=2, block_size=4, num_blocks=17,
                max_len=32)
    base.update(kw)
    return Engine(model, params, EngineConfig(**base))


@pytest.mark.parametrize("arch", ["olmo_1b", "h2o_danube_3_4b",
                                  "recurrentgemma_2b"])
def test_engine_backends_match_oracle_ragged(rng, arch):
    """RAGGED prompts through one Engine API, both backends: greedy
    paged == static == unbatched oracle. Regression for the PR-1 static
    left-pad leak (prefill attended pad keys, shifting short-prompt
    outputs) — right-padded prefill with true-length cache extraction
    must match the per-request reference exactly."""
    cfg = get_config(arch).smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_new = 6
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (3, 7, 12)]
    want = [_oracle_greedy(model, params, p, n_new) for p in prompts]
    sp = SamplingParams(max_tokens=n_new)
    got_p = _engine(model, params, "paged").generate(prompts, sp)
    got_s = _engine(model, params, "static",
                    num_slots=3, max_len=64).generate(prompts, sp)
    assert got_p == want, f"paged != oracle: {got_p} vs {want}"
    assert got_s == want, f"static != oracle: {got_s} vs {want}"


def test_engine_single_long_prompt_spans_blocks(rng):
    """One prompt spanning several blocks decodes identically to the
    dense path (block-table indirection is invisible)."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = list(rng.integers(0, cfg.vocab_size, 19))  # 5 blocks of 4
    want = _oracle_greedy(model, params, prompt, 8)
    eng = _engine(model, params, "paged", num_slots=1, max_len=40)
    assert eng.generate([prompt], SamplingParams(max_tokens=8)) == [want]


def test_engine_xlstm_ragged_prefill(rng):
    """mlstm/slstm prefill is now exact under right padding (gate
    freezing / carry selection hold the recurrent state at the true
    length), so BOTH backends take the bucketed path for xLSTM and must
    still match the unbatched oracle on prompts that are NOT block
    multiples."""
    cfg = get_config("xlstm_1_3b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (3, 7, 7)]       # 7 % block_size(4) != 0
    want = [_oracle_greedy(model, params, p, 4, max_len=32)
            for p in prompts]
    sp = SamplingParams(max_tokens=4)
    eng = _engine(model, params, "paged")
    assert eng.generate(prompts, sp) == want
    assert eng.stats()["bucketed_prefill"]
    got_s = _engine(model, params, "static", num_slots=3).generate(
        prompts, sp)                     # ragged: one right-padded batch
    assert got_s == want


def test_xlstm_bucketed_prefill_compile_cap(rng):
    """Regression for the exact-length fallback that compiled one prefill
    jit per distinct prompt length: xLSTM must now ride the power-of-two
    buckets (mirror of the paged <= 5 compiles test), outputs unchanged."""
    cfg = get_config("xlstm_1_3b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [int(rng.integers(3, 21)) for _ in range(12)]
    assert len(set(lens)) >= 8, "trace not ragged enough"
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in lens]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=4, block_size=4,
                              num_blocks=129, max_len=64))
    got = eng.generate(prompts, SamplingParams(max_tokens=3))
    st = eng.stats()
    assert st["bucketed_prefill"]
    assert st["prefill_compiles"] <= 5, st
    for i in (0, 5, 11):
        assert got[i] == _oracle_greedy(model, params, prompts[i], 3)


def test_engine_non_pow2_block_size(rng):
    """Bucketed prefill must round pow-2 buckets up to a block multiple
    when block_size itself is not a power of two."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 13)]
    want = [_oracle_greedy(model, params, p, 4) for p in prompts]
    eng = _engine(model, params, "paged", block_size=6, num_blocks=23)
    assert eng.generate(prompts, SamplingParams(max_tokens=4)) == want
    assert eng.stats()["blocks_used"] == 0


# -- 5. scheduler invariants --------------------------------------------


def _run_trace(model, params, work, *, num_slots, num_blocks=33,
               watermark=0):
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=num_slots,
                              block_size=4, num_blocks=num_blocks,
                              max_len=32, watermark_blocks=watermark))
    handles = [eng.add_request(p, SamplingParams(max_tokens=n))
               for p, n in work]
    eng.drain()
    return eng, handles


def test_engine_no_block_leak_and_slot_reuse(rng):
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    work = [(list(rng.integers(0, cfg.vocab_size,
                               int(rng.integers(2, 12)))),
             int(rng.integers(1, 10))) for _ in range(9)]
    eng, handles = _run_trace(model, params, work, num_slots=3)
    be = eng.backend
    # more requests than slots -> retired slots were reused
    assert len(be.finished) == 9
    # every block returned to the free list; allocator saw no double-free
    # (it raises on double-free) and nothing leaked:
    assert be.alloc.used_count == 0
    assert be.alloc.free_count == be.layout.usable_blocks
    assert np.all(be.table == paged_kv.NULL_BLOCK)
    assert np.all(be.lengths == 0)
    for h, (p, n) in zip(handles, work):
        assert h.finished and len(h.token_ids) == n


def test_engine_outputs_independent_of_admission_order(rng):
    """Greedy outputs are a pure function of (params, prompt): shuffling
    submission order and changing slot count must not change any
    request's tokens (no cross-request contamination through the shared
    pool or the null block)."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    work = [(list(rng.integers(0, cfg.vocab_size,
                               int(rng.integers(2, 10)))),
             int(rng.integers(2, 8))) for _ in range(6)]
    _, hs_a = _run_trace(model, params, work, num_slots=2)
    order = [3, 0, 5, 1, 4, 2]
    _, hs_b = _run_trace(model, params, [work[i] for i in order],
                         num_slots=4)
    outs_a = {tuple(work[i][0]): hs_a[i].token_ids for i in range(6)}
    for j, i in enumerate(order):
        assert hs_b[j].token_ids == outs_a[tuple(work[i][0])]


def test_optimistic_admission_with_preemption(rng):
    """Acceptance: a trace whose WORST-CASE footprints can never be
    co-resident under PR-1 full reservation (sum exceeds the pool, so
    admission was serialized) runs fully concurrent under optimistic
    admission, survives pool exhaustion via LIFO preemption + recompute,
    finishes with bit-identical greedy outputs and leaks zero blocks
    (allocator returns to all-free)."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, 8)))
               for _ in range(3)]
    n_new, bs, num_blocks = 16, 4, 14        # 13 usable blocks
    worst = paged_kv.blocks_for(8 + n_new, bs)
    assert 3 * worst > num_blocks - 1        # full reservation: never 3-up
    # uncontended reference (big pool, no preemption possible)
    ref_eng = Engine(model, params,
                     EngineConfig(backend="paged", num_slots=3,
                                  block_size=bs, num_blocks=65,
                                  max_len=64))
    want = ref_eng.generate(prompts, SamplingParams(max_tokens=n_new))
    assert ref_eng.stats()["preemptions"] == 0

    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=3, block_size=bs,
                              num_blocks=num_blocks, max_len=64))
    handles = [eng.add_request(p, SamplingParams(max_tokens=n_new))
               for p in prompts]
    max_active = 0
    while eng.has_work:
        eng.step()
        max_active = max(max_active, eng.backend.num_active)
    st = eng.stats()
    assert max_active == 3, "optimistic admission never co-admitted all"
    assert st["preemptions"] >= 1, "pool pressure never triggered"
    assert [h.token_ids for h in handles] == want
    assert st["blocks_used"] == 0
    assert eng.backend.alloc.free_count == eng.backend.layout.usable_blocks
    assert np.all(eng.backend.table == paged_kv.NULL_BLOCK)


def test_bucketed_prefill_compile_cap(rng):
    """Acceptance: 32 requests over >= 12 distinct prompt lengths compile
    at most (length buckets) x (batch buckets) prefill entries — lengths
    3..20 under block 4 span 4 pow-2 buckets {4, 8, 16, 32}; batch widths
    with 4 slots span at most {1, 2, 4} — and every output still matches
    the unbatched oracle. Batched admission must also actually batch:
    fewer prefill calls than requests."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    lens = [int(rng.integers(3, 21)) for _ in range(32)]
    assert len(set(lens)) >= 12, "trace not ragged enough"
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in lens]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=4, block_size=4,
                              num_blocks=129, max_len=64))
    got = eng.generate(prompts, SamplingParams(max_tokens=3))
    st = eng.stats()
    assert st["bucketed_prefill"]
    assert st["prefill_compiles"] <= 4 * 3, st
    assert st["prefill_reqs"] == 32, st
    assert st["prefill_calls"] < 32, "admission never batched a prefill"
    # spot-check correctness across buckets (cheap subset)
    for i in (0, 7, 19, 31):
        assert got[i] == _oracle_greedy(model, params, prompts[i], 3)


def test_batched_prefill_admission_one_call(rng):
    """A same-bucket burst into an idle engine prefills as ONE batched
    call (FCFS prefix drain), and the scattered true-length caches are
    exact: outputs match the unbatched oracle per request."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # lengths 5..8 share the pow-2 bucket 8 under block_size 4
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 8, 6, 7)]
    want = [_oracle_greedy(model, params, p, 4) for p in prompts]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=4, block_size=4,
                              num_blocks=33, max_len=32))
    got = eng.generate(prompts, SamplingParams(max_tokens=4))
    st = eng.stats()
    assert got == want, (got, want)
    assert st["prefill_calls"] == 1, st
    assert st["prefill_reqs"] == 4, st
    assert st["blocks_used"] == 0, st


def test_batched_prefill_respects_max_prefill_batch(rng):
    """The drain cap: max_prefill_batch=2 splits a 4-request same-bucket
    burst into two batched calls; outputs unchanged."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in (5, 8, 6, 7)]
    want = [_oracle_greedy(model, params, p, 4) for p in prompts]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=4, block_size=4,
                              num_blocks=33, max_len=32,
                              max_prefill_batch=2))
    got = eng.generate(prompts, SamplingParams(max_tokens=4))
    st = eng.stats()
    assert got == want
    assert st["prefill_calls"] == 2, st
    assert st["prefill_reqs"] == 4, st


def test_batched_prefill_stops_at_bucket_boundary(rng):
    """FCFS prefix semantics: a queue [8-bucket, 8-bucket, 16-bucket,
    8-bucket] drains as {two 8s} then {16} then {8} — never skipping
    ahead to glue the fourth request onto the first batch."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plens = (6, 8, 12, 5)                 # buckets 8, 8, 16, 8 (block 4)
    prompts = [list(map(int, rng.integers(0, cfg.vocab_size, L)))
               for L in plens]
    want = [_oracle_greedy(model, params, p, 3) for p in prompts]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=4, block_size=4,
                              num_blocks=65, max_len=32))
    got = eng.generate(prompts, SamplingParams(max_tokens=3))
    st = eng.stats()
    assert got == want
    assert st["prefill_calls"] == 3, st
    assert st["prefill_reqs"] == 4, st


def test_engine_queues_when_pool_tight(rng):
    """Pool too small for all requests at once: the engine must finish
    everything via queueing/preemption without corruption or leaks."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    work = [(list(rng.integers(0, cfg.vocab_size, 8)), 8)
            for _ in range(5)]
    eng, handles = _run_trace(model, params, work, num_slots=4,
                              num_blocks=10)
    assert all(len(h.token_ids) == 8 for h in handles)
    assert eng.backend.alloc.used_count == 0


def test_engine_eos_retirement(rng):
    """EOS is stripped, never emitted — whether it arrives straight out
    of prefill (zero tokens) or mid-decode — and retirement frees the
    slot for queued work."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = list(rng.integers(0, cfg.vocab_size, 7))
    eos = _oracle_greedy(model, params, prompt, 1)[0]
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=1, block_size=4,
                              num_blocks=17, max_len=32, eos_id=eos))
    r1 = eng.add_request(list(prompt), SamplingParams(max_tokens=20))
    r2 = eng.add_request(list(rng.integers(0, cfg.vocab_size, 5)),
                         SamplingParams(max_tokens=3))
    eng.drain()
    assert r1.finished and r1.token_ids == []        # stripped, not emitted
    assert r1.finish_reason == "stop"
    assert r2.finished and len(r2.token_ids) <= 3 and eos not in r2.token_ids
    assert eng.backend.alloc.used_count == 0


def test_allocator_double_free_detected():
    layout = paged_kv.PagedLayout(num_slots=1, num_blocks=4, block_size=4,
                                  max_len=8)
    alloc = paged_kv.BlockAllocator(layout)
    ids = alloc.alloc(2)
    alloc.free(ids)
    with pytest.raises(ValueError):
        alloc.free([ids[0]])
    with pytest.raises(ValueError):
        alloc.free([paged_kv.NULL_BLOCK])
    with pytest.raises(MemoryError):
        alloc.alloc(4)


def test_admission_counts_first_step_growth(rng):
    """Regression: admission must reserve the candidate's OWN first-step
    growth block (the fed token is cached the same step). Without
    blocks_for(cached + 1) a boundary-length request admits, immediately
    self-preempts in _grow_blocks, and wastes a full prefill per step
    until the older sequence retires (observed: 5 thrash preemptions on
    this exact trace)."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=2, block_size=8,
                              num_blocks=7, max_len=48))    # 6 usable
    a = eng.add_request(list(rng.integers(0, cfg.vocab_size, 8)),
                        SamplingParams(max_tokens=40))
    for _ in range(27):                  # drive A deep into the pool
        eng.step()
    b_prompt = list(map(int, rng.integers(0, cfg.vocab_size, 8)))
    b = eng.add_request(b_prompt, SamplingParams(max_tokens=4))
    eng.drain()
    st = eng.stats()
    assert st["preemptions"] == 0, f"admission thrash: {st}"
    assert st["blocks_used"] == 0
    assert len(a.token_ids) == 40
    assert b.token_ids == _oracle_greedy(model, params, b_prompt, 4,
                                         max_len=48)


def test_engine_rejects_oversized_request(rng):
    """A request whose worst case could never fit the pool even alone is
    a ValueError at add_request, not a mid-flight failure/livelock."""
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params,
                 EngineConfig(backend="paged", num_slots=1, block_size=4,
                              num_blocks=5, max_len=256))   # 16 tokens
    with pytest.raises(ValueError):
        eng.add_request(list(rng.integers(0, cfg.vocab_size, 10)),
                        SamplingParams(max_tokens=20))


def test_allocator_watermark_and_victim_selection():
    layout = paged_kv.PagedLayout(num_slots=2, num_blocks=8, block_size=4,
                                  max_len=16)
    alloc = paged_kv.BlockAllocator(layout, watermark=2)   # 7 usable
    assert alloc.can_admit(5, strict=True)
    assert not alloc.can_admit(6, strict=True)      # watermark headroom
    assert alloc.can_admit(7, strict=False)         # sole request bypass
    # LIFO: the latest admission (highest ticket) is evicted first
    assert paged_kv.BlockAllocator.select_victim(
        [(0, 5), (2, 9), (1, 7)]) == 2
    with pytest.raises(ValueError):
        paged_kv.BlockAllocator.select_victim([])
