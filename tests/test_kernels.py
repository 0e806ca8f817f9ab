"""Per-kernel interpret-mode validation vs the pure-jnp oracles,
sweeping shapes and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref


def _randn(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


@pytest.mark.parametrize("m,k,n", [(32, 16, 32), (70, 50, 130), (128, 128, 128),
                                   (1, 7, 300)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stx_matmul(rng, m, k, n, dtype):
    x = _randn(rng, (m, k), dtype)
    w = _randn(rng, (k, n), dtype)
    out = ops.stx_matmul(x, w, block_m=32, block_n=64, block_k=16,
                         mode="interpret")
    want = ref.matmul(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


def test_stx_matmul_batched_lead(rng):
    x = _randn(rng, (3, 5, 40), jnp.float32)
    w = _randn(rng, (40, 24), jnp.float32)
    out = ops.stx_matmul(x, w, block_m=16, block_n=16, block_k=16,
                         mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul(x, w)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("shape", [(64, 64), (65, 70), (128, 33)])
@pytest.mark.parametrize("weights_fn", [ref.five_point_weights,
                                        lambda: jnp.ones((3, 3), jnp.float32)])
def test_stencil2d(rng, shape, weights_fn):
    x = _randn(rng, shape, jnp.float32)
    w = weights_fn()
    out = ops.stencil2d(x, w, block_m=32, block_n=32, mode="interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.stencil2d(x, w)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 16, 32), (9, 20, 33)])
def test_stencil3d_seven_point(rng, shape):
    x = _randn(rng, shape, jnp.float32)
    w = ref.seven_point_weights()
    out = ops.stencil3d(x, w, block_d=4, block_m=8, block_n=16,
                        mode="interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.stencil3d(x, w)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 16)])
def test_flash_attention(rng, hq, hkv, causal, window):
    B, S, D = 2, 80, 32
    q = _randn(rng, (B, hq, S, D), jnp.float32)
    k = _randn(rng, (B, hkv, S, D), jnp.float32)
    v = _randn(rng, (B, hkv, S, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32, mode="interpret")
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16(rng):
    B, H, S, D = 1, 2, 64, 64
    q = _randn(rng, (B, H, S, D), jnp.bfloat16)
    k = _randn(rng, (B, H, S, D), jnp.bfloat16)
    v = _randn(rng, (B, H, S, D), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32,
                              mode="interpret")
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_vrp_dot_beats_naive(rng):
    n = 3000
    x = jnp.asarray(rng.normal(size=n) * 1e4, jnp.float32)
    y = jnp.asarray(rng.normal(size=n), jnp.float32)
    exact = float(np.dot(np.asarray(x, np.float64), np.asarray(y, np.float64)))
    naive_err = abs(float(jnp.dot(x, y)) - exact)
    d = ops.vrp_dot(x, y, mode="interpret")
    got = float(d[0]) + float(d[1])
    assert abs(got - exact) < max(naive_err / 100, 1e-8)


def test_vrp_sum_matches_ref(rng):
    x = jnp.asarray(rng.normal(size=2048) * 1e6, jnp.float32)
    kern = ops.vrp_sum(x, mode="interpret")
    oracle = ops.vrp_sum(x, mode="ref")
    exact = float(np.sum(np.asarray(x, np.float64)))
    assert abs(float(kern[0]) + float(kern[1]) - exact) <= \
        abs(float(oracle[0]) + float(oracle[1]) - exact) * 10 + 1e-6


@pytest.mark.parametrize("B,T,D", [(3, 100, 40), (2, 64, 128), (1, 17, 5)])
def test_rglru_scan(rng, B, T, D):
    a = jnp.asarray(0.8 + 0.2 * rng.random((B, T, D)), jnp.float32)
    x = _randn(rng, (B, T, D), jnp.float32)
    out = ops.rglru_scan(a, x, block_b=2, block_t=16, block_d=16,
                         mode="interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.linear_scan(a, x)),
                               rtol=1e-5, atol=1e-5)


def test_rglru_scan_with_state(rng):
    B, T, D = 2, 32, 16
    a = jnp.asarray(0.9 * rng.random((B, T, D)), jnp.float32)
    x = _randn(rng, (B, T, D), jnp.float32)
    h0 = _randn(rng, (B, D), jnp.float32)
    out = ops.rglru_scan(a, x, h0, block_b=2, block_t=8, block_d=8,
                         mode="interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.linear_scan(a, x, h0)),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(1, 200), st.integers(1, 64))
@settings(max_examples=20, deadline=None)
def test_property_matmul_any_shape(m, n):
    """VLA property: any (m, k) x (k, n) works via masked padding."""
    rng = np.random.default_rng(m * 1000 + n)
    x = jnp.asarray(rng.normal(size=(m, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(24, n)), jnp.float32)
    out = ops.stx_matmul(x, w, block_m=32, block_n=32, block_k=8,
                         mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul(x, w)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)])   # MHA/GQA/MQA
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("mode", ["interpret", "ref"])
def test_paged_decode_reads_stacked_pool_by_layer(rng, hq, hkv, kv_dtype,
                                                  mode):
    """Paged decode over the stacked pools of every layer, read at layer
    l, is bit-equal to the same call on layer l's pool alone, for every
    l: the index selects the blocks and changes no arithmetic."""
    from repro.models import paged_kv

    L, B, bs, nbmax, D = 3, 3, 4, 4, 16
    nb = B * nbmax + 1
    q = _randn(rng, (B, hq, D), jnp.bfloat16)
    k = _randn(rng, (L, nb, bs, hkv, D), jnp.float32)
    v = _randn(rng, (L, nb, bs, hkv, D), jnp.float32)
    if kv_dtype == "bf16":
        pool = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
    else:
        spec = paged_kv.PoolSpec(kv_dtype=kv_dtype, block_size=bs,
                                 n_kv_heads=hkv, head_dim=D)
        (kq, ks), (vq, vs) = (paged_kv.quantize_kv(t, spec) for t in (k, v))
        pool = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    table = jnp.asarray(rng.permutation(nb - 1)[:B * nbmax]
                        .reshape(B, nbmax) + 1, jnp.int32)
    lengths = jnp.asarray([5, 16, 1], jnp.int32)
    for layer in range(L):
        got = ops.paged_attention(q, pool, table, lengths,
                                  layer=jnp.int32(layer), kernel_mode=mode)
        want = ops.paged_attention(q, {n: t[layer] for n, t in pool.items()},
                                   table, lengths, kernel_mode=mode)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32),
                                      err_msg=f"layer {layer}")
