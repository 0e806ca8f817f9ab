"""Multi-device distribution correctness (8 fake CPU devices, subprocess).

The suite's default process must keep 1 device (smoke-test contract), so
these tests re-exec python with XLA_FLAGS set. Inside, they verify:
  * MoE sharded (shard_map EP) == local math,
  * vocab-parallel embedding lookup == plain take,
  * a sharded train step == single-device train step,
  * dry-run cell build lowers+compiles on a (pod, data, model) mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    code = textwrap.dedent(body)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_moe_sharded_matches_local():
    _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import moe
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import make_shard_ctx
    cfg = dataclasses.replace(get_config("qwen3_moe_30b_a3b").smoke(),
                              moe_capacity_factor=8.0)
    mesh = make_mesh((2, 4), ("data", "model"))
    shard = make_shard_ctx(mesh)
    rng = np.random.default_rng(0)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)), jnp.float32)
    local, aux_l = moe.apply_moe(params, cfg, x)
    with mesh:
        sharded, aux_s = jax.jit(
            lambda p, xx: moe.apply_moe_sharded(p, cfg, xx, shard))(params, x)
    np.testing.assert_allclose(np.asarray(local), np.asarray(sharded),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(float(aux_l), float(aux_s), rtol=1e-4)
    print("moe ok")
    """)


def test_vocab_parallel_lookup_matches_take():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.layers import vocab_parallel_lookup
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import make_shard_ctx
    mesh = make_mesh((2, 4), ("data", "model"))
    shard = make_shard_ctx(mesh)
    rng = np.random.default_rng(1)
    V, d = 64, 16
    table = jnp.asarray(rng.normal(size=(V, d)), jnp.float32)
    toks = jnp.asarray(rng.integers(0, V, (4, 10)), jnp.int32)
    with mesh:
        out = jax.jit(lambda t, i: vocab_parallel_lookup(t, i, shard))(
            table, toks)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, toks, axis=0)),
                               rtol=1e-6)
    print("lookup ok")
    """)


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    _run("""
    import functools, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch import sharding as shlib
    from repro.launch.mesh import make_mesh
    from repro.launch.train import init_state, make_train_step, state_specs
    from repro.models.model import Model
    from repro.models.transformer import RunCtx
    from repro.optim import OptConfig
    from repro.optim.schedule import constant
    cfg = get_config("olmo_1b").smoke()
    model = Model(cfg)
    opt_cfg = OptConfig(weight_decay=0.0)
    lr = functools.partial(constant, peak_lr=1e-2)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
             "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}
    # single device
    step0 = make_train_step(model, opt_cfg, RunCtx(kernel_mode="ref"), lr)
    s0 = init_state(model, opt_cfg)
    n0, m0 = jax.jit(step0)(s0, batch)
    # 8-device (2 dp x 4 tp) mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    shard = shlib.make_shard_ctx(mesh)
    ctx = RunCtx(kernel_mode="ref", shard=shard)
    step1 = make_train_step(model, opt_cfg, ctx, lr)
    s1 = init_state(model, opt_cfg)
    shapes = jax.eval_shape(lambda: init_state(model, opt_cfg))
    sspec = shlib.named(mesh, state_specs(shapes, shard))
    bspec = shlib.named(mesh, shlib.batch_specs(batch, shard))
    with mesh:
        s1 = jax.device_put(s1, sspec)
        n1, m1 = jax.jit(step1, in_shardings=(sspec, bspec))(s1, batch)
    assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-4
    for a, b in zip(jax.tree.leaves(n0["params"]),
                    jax.tree.leaves(n1["params"])):
        # atol covers Adam's rsqrt amplification of cross-device psum
        # reduction-order noise on near-zero gradients (single elements).
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-4, atol=2e-3)
    print("train step ok")
    """)


def test_dryrun_cell_lowers_on_multipod_mesh():
    _run("""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.launch.dryrun import build_lowerable
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_config("recurrentgemma_2b").smoke()
    for cell in (ShapeCell("t", "train", 64, 8),
                 ShapeCell("d", "decode", 64, 8)):
        with mesh:
            fn, args = build_lowerable(cfg, cell, mesh)
            compiled = fn.lower(*args).compile()
            assert compiled.cost_analysis()["flops"] > 0
    print("dryrun lowering ok")
    """)


def test_compressed_psum_shard_map():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.optim.grad_compression import compressed_psum
    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)

    def local(gl, res):
        total, new_res, n = compressed_psum(gl[0], res[0], "data")
        return (total / n)[None], new_res[None]

    with mesh:
        mean, _ = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None))))(
                g, jnp.zeros_like(g))
    got = np.asarray(mean)[0]
    want = np.asarray(jnp.mean(g, 0))
    np.testing.assert_allclose(got, want, atol=0.05)
    print("compressed psum ok")
    """)


def test_flash_decoding_matches_baseline_decode():
    """The §Perf decode winner (seq-sharded cache + LSE combine) must be
    numerically exact vs the replicated-cache baseline."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import attention as attn_lib
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import make_shard_ctx
    cfg = get_config("yi_6b").smoke()      # GQA kv=2, heads=4
    mesh = make_mesh((2, 4), ("data", "model"))
    shard = make_shard_ctx(mesh, cache_seq_shard=True)
    rng = np.random.default_rng(0)
    params = attn_lib.init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, S = 4, 16
    cache = attn_lib.init_kv_cache(cfg, B, S, jnp.float32)
    # pre-populate a few positions
    for t in range(5):
        xt = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
        _, cache = attn_lib.decode_attend(params, cfg, xt, cache,
                                          jnp.int32(t))
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
    base_out, base_cache = attn_lib.decode_attend(params, cfg, x, cache,
                                                  jnp.int32(5))
    with mesh:
        fd_out, fd_cache = jax.jit(
            lambda p, xx, c: attn_lib.decode_attend_seqshard(
                p, cfg, xx, c, jnp.int32(5), shard))(params, x, cache)
    np.testing.assert_allclose(np.asarray(fd_out), np.asarray(base_out),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(fd_cache["k"]),
                               np.asarray(base_cache["k"]), rtol=1e-5)
    print("flash decoding ok")
    """)
