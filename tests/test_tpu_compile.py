"""The serving path's Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each case lowers and compiles a kernel at real widths for
one chip of a ``v5e:2x2`` topology and asserts the compiled program
holds the kernel (``tpu_custom_call``). The suite's conftest turns
``jax_enable_x64`` on, so these also guard the kernels' f32/i32
constants. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU compiler library.
"""

import dataclasses
import re

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


# ``%name = type opcode(`` of one instruction of a compiled HLO text
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>\S+) = "
                     r"(?P<type>.*?) (?P<op>[a-z][\w\-]*)\(")


def traffic_on(text: str, shapes, words=("copy", "slice")) -> list:
    """Names of the instructions in a compiled HLO ``text``, fused or
    not, that copy or slice a buffer of one of ``shapes`` (dims
    tuples): ``copy``, ``copy-start``/``-done``, ``slice``,
    ``dynamic-slice`` and ``dynamic-update-slice``, found by opcode, or
    by name for a fusion (``dynamic-slice_bitcast_fusion.4``)."""
    found = []
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        what = m["name"] if m["op"] == "fusion" else m["op"]
        if not any(w in what for w in words):
            continue
        dims = {tuple(int(d) for d in s.split(",") if d)
                for s in re.findall(r"\[([\d,]*)\]", m["type"])}
        if dims & set(shapes):
            found.append(m["name"])
    return found


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


def _tp_step_text(tp4, step, cfg, kv_dtype="bf16") -> str:
    """The compiled text of a tp=4 engine step of ``cfg`` with the
    Pallas kernels: head-sharded paged decode, per-shard prefill."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import sharding as shlib
    from repro.models import paged_kv
    from repro.models.model import Model
    from repro.models.transformer import RunCtx

    model = Model(cfg)
    layout = paged_kv.PagedLayout(num_slots=8, num_blocks=257,
                                  block_size=16, max_len=512)
    spec = None if kv_dtype == "bf16" else paged_kv.make_pool_spec(
        cfg, layout, kv_dtype=kv_dtype, head_sharded=True)
    ctx = RunCtx(kernel_mode="pallas", shard=tp4, decode_head_shard=True,
                 kv_spec=spec)

    def placed(shapes, specs):
        return jax.tree.map(
            lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
            shapes, shlib.named(tp4.mesh, specs))

    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = placed(pshapes, shlib.param_specs(pshapes, tp4))
    pool_specs = model.paged_cache_specs(layout, tp4, spec)
    pools = placed(jax.eval_shape(
        lambda: model.init_paged_cache(layout, spec)), pool_specs)
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
                layout, pools, dense, rows, valid, block_ids, spec)

        args = (arg(1, 512), arg(1, 32), arg(8), arg(8, dtype=jnp.bool_),
                arg(1))
    step_fn = shlib.jit_step(fn, tp4, shlib.named(tp4.mesh, pool_specs),
                             donate=(1,))
    return step_fn.lower(params, pools, *args).compile().as_text()


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_engine_step_compiles_tensor_parallel(tp4, step):
    """A tp=4 olmo-1b engine step (two layers, published widths) with
    the Pallas kernels: head-sharded paged decode, per-shard prefill."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config("olmo_1b"), n_layers=2)
    assert "tpu_custom_call" in _tp_step_text(tp4, step, cfg)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_decode_step_compiles_one_kv_head_per_shard(tp4, kv_dtype):
    """Four kv heads over tp=4 leave each shard's pool one kv head, as
    yi-6b's and qwen3-moe's pools have there: the decode kernel's blocks
    of such a pool, and of its int8 scales, lower for the chip."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config("olmo_1b"), n_layers=2,
                              n_kv_heads=4)
    assert "tpu_custom_call" in _tp_step_text(tp4, "decode", cfg, kv_dtype)


# published widths; recurrentgemma's period made (rglru, attn, local), so
# one scan carries a full-attention pool (of one kv head) beside per-slot
# state
IN_PLACE_CASES = {"olmo_1b-bf16": ("olmo_1b", None, 4, "bf16"),
                  "olmo_1b-int8": ("olmo_1b", None, 4, "int8"),
                  "recurrentgemma_2b-mixed": (
                      "recurrentgemma_2b", ("rglru", "attn", "local"), 6,
                      "bf16"),
                  "recurrentgemma_2b-mixed-int8": (
                      "recurrentgemma_2b", ("rglru", "attn", "local"), 6,
                      "int8")}


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_decode_step_updates_pool_in_place(shape, case):
    """The one-chip paged decode step, pools donated, appends each
    layer's rows into the stacked pool in place: no copy, slice or
    update-slice of a whole stacked pool or of one layer's pool is
    left in the compiled program, and the Pallas kernel is there.

    The pool holds 2049 blocks, as in service: a pool that fits the
    chip's fast memory is staged there by the compiler through slices
    and copies of its own. So are an int8 pool's f32 scale leaves,
    which besides take another layout on the chip than the kernel
    reads, so the step still copies them: for them, only the layer
    walk's dynamic slices and update slices are checked."""
    from repro.configs import get_config
    from repro.models import paged_kv
    from repro.models.model import Model
    from repro.models.transformer import RunCtx

    arch, pattern, n_layers, kv_dtype = IN_PLACE_CASES[case]
    cfg = dataclasses.replace(get_config(arch), n_layers=n_layers)
    if pattern is not None:
        cfg = dataclasses.replace(cfg, block_pattern=pattern)
    model = Model(cfg)
    layout = paged_kv.PagedLayout(num_slots=8, num_blocks=2049,
                                  block_size=16, max_len=512)
    spec = None if kv_dtype == "bf16" else paged_kv.make_pool_spec(
        cfg, layout, kv_dtype=kv_dtype)
    ctx = RunCtx(kernel_mode="pallas", kv_spec=spec)

    def placed(tree):
        return jax.tree.map(lambda t: shape(t.shape, t.dtype), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = placed(jax.eval_shape(
        lambda: model.init_paged_cache(layout, spec)))

    def step(params, pools, table, lengths, tokens):
        return model.decode_step_paged(params, pools, table, lengths,
                                       tokens, ctx)

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, pools, shape((8, 32), jnp.int32), shape((8,), jnp.int32),
        shape((8, 1), jnp.int32)).compile().as_text()
    kinds = jax.tree.leaves(model.paged_pool_mask(layout, spec))
    pooled = [(path[-1].key, t.shape) for (path, t), kind in zip(
        jax.tree_util.tree_leaves_with_path(pools), kinds)
        if kind == "pool"]
    # each stacked (L, ...) leaf and one layer's (...) slice of it
    payload = {s[i:] for k, s in pooled if k in ("k", "v") for i in (0, 1)}
    scales = {s[i:] for k, s in pooled if k not in ("k", "v")
              for i in (0, 1)}
    assert payload, "the model has no block-pool layer"
    assert not traffic_on(text, payload)
    assert not traffic_on(text, scales, words=("dynamic",))
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
