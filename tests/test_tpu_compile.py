"""The serving path's Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each case lowers and compiles a kernel at real widths for
one chip of a ``v5e:2x2`` topology and asserts the compiled program
holds the kernel (``tpu_custom_call``). The suite's conftest turns
``jax_enable_x64`` on, so these also guard the kernels' f32/i32
constants. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU compiler library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (paged_decode_attention_pallas,
                                           paged_verify_attention_pallas)

B, NB, BS, NBMAX, D = 8, 512, 16, 64, 128
# (query heads, kv heads): olmo-1b's MHA and qwen3-moe's GQA
LAYOUTS = {"mha16": (16, 16), "gqa32x4": (32, 4)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: not describable
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_compiles(shape, layout, kv_dtype):
    hq, hkv = LAYOUTS[layout]
    q = shape((B, hq, D), jnp.bfloat16)
    table, lengths = shape((B, NBMAX), jnp.int32), shape((B,), jnp.int32)
    if kv_dtype == "bf16":
        pool = shape((NB, BS, hkv, D), jnp.bfloat16)
        text = compiled_text(paged_decode_attention_pallas, q, pool, pool,
                             table, lengths)
    else:
        pool = shape((NB, BS, hkv, D), jnp.int8)
        scale = shape((NB, BS, hkv), jnp.float32)

        def decode(q, k, v, bt, ln, ks, vs):
            return paged_decode_attention_pallas(q, k, v, bt, ln,
                                                 k_scale=ks, v_scale=vs)

        text = compiled_text(decode, q, pool, pool, table, lengths, scale,
                             scale)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_paged_verify_compiles(shape, layout):
    hq, hkv = LAYOUTS[layout]
    pool = shape((NB, BS, hkv, D), jnp.bfloat16)
    text = compiled_text(paged_verify_attention_pallas,
                         shape((B, 5, hq, D), jnp.bfloat16), pool, pool,
                         shape((B, NBMAX), jnp.int32),
                         shape((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_flash_prefill_compiles(shape, layout):
    hq, hkv = LAYOUTS[layout]
    kv = shape((1, hkv, 1024, D), jnp.bfloat16)
    text = compiled_text(flash_attention_pallas,
                         shape((1, hq, 1024, D), jnp.bfloat16), kv, kv)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def tp4(topo):
    """A (data=1, model=4) mesh over the four described chips."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.launch.sharding import make_shard_ctx

    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    return make_shard_ctx(mesh)


# olmo's 16 kv heads split over tp=4; 2 kv heads do not and stay whole
@pytest.mark.parametrize("hq,hkv", [(16, 16), (16, 2)])
def test_flash_prefill_compiles_tensor_parallel(tp4, hq, hkv):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the prefill
    kernel runs per head shard through a shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(tp4.mesh, P())
    q = jax.ShapeDtypeStruct((1, hq, 512, D), jnp.bfloat16, sharding=rep)
    kv = jax.ShapeDtypeStruct((1, hkv, 512, D), jnp.bfloat16, sharding=rep)

    def prefill(q, k, v):
        return ops.flash_attention(q, k, v, mode="pallas", sharding=tp4)

    assert "tpu_custom_call" in compiled_text(prefill, q, kv, kv)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_engine_step_compiles_tensor_parallel(tp4, step):
    """A tp=4 olmo-1b engine step (two layers, published widths) with
    the Pallas kernels: head-sharded paged decode, per-shard prefill."""
    import dataclasses

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch import sharding as shlib
    from repro.models import paged_kv
    from repro.models.model import Model
    from repro.models.transformer import RunCtx

    cfg = dataclasses.replace(get_config("olmo_1b"), n_layers=2)
    model = Model(cfg)
    ctx = RunCtx(kernel_mode="pallas", shard=tp4, decode_head_shard=True)
    layout = paged_kv.PagedLayout(num_slots=8, num_blocks=257,
                                  block_size=16, max_len=512)

    def placed(shapes, specs):
        return jax.tree.map(
            lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
            shapes, shlib.named(tp4.mesh, specs))

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = placed(pshapes, shlib.param_specs(pshapes, tp4))
    pool_specs = model.paged_cache_specs(layout, tp4)
    pools = placed(jax.eval_shape(lambda: model.init_paged_cache(layout)),
                   pool_specs)
    rep = NamedSharding(tp4.mesh, P())

    def arg(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)

    if step == "decode":
        def fn(params, pools, table, lengths, tokens):
            return model.decode_step_paged(params, pools, table, lengths,
                                           tokens, ctx)

        args = (arg(8, 32), arg(8), arg(8, 1))
    else:
        def fn(params, pools, tokens, block_ids, rows, valid, length):
            logits, dense = model.prefill(params, {"tokens": tokens}, ctx,
                                          max_len=512, length=length)
            return logits[:, -1], model.pack_prefill_into_paged(
                layout, pools, dense, rows, valid, block_ids)

        args = (arg(1, 512), arg(1, 32), arg(8), arg(8, dtype=jnp.bool_),
                arg(1))
    step_fn = shlib.jit_step(fn, tp4, shlib.named(tp4.mesh, pool_specs),
                             donate=(1,))
    text = step_fn.lower(params, pools, *args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_gradient_compiles(shape):
    """Training differentiates through the forward kernel: the Pallas
    forward stays, the backward is the reference's."""
    q = shape((1, 16, 256, D), jnp.bfloat16)

    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, mode="pallas")
        return out.astype(jnp.float32).sum()

    text = compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         q, q, q)
    assert "tpu_custom_call" in text
