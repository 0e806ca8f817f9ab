"""Plain float32 reference of a dense decoder-only transformer, and the
random weights the benchmark serves.

It follows the published descriptions of the two families the benchmark
runs, and imports nothing of the program under test:

* Llama / Yi (arXiv:2403.04652, ``01-ai/Yi-6B`` config): RMSNorm with a
  weight, grouped-query attention with rotary embeddings (the
  rotate-half form), SwiGLU feed-forward, untied output head.
* OLMo (arXiv:2402.00838, ``allenai/OLMo-1B`` config): LayerNorm with
  no weight or bias, multi-head attention with rotary embeddings,
  SwiGLU feed-forward, output head tied to the embedding.

Weights are made on the device from a seed in one jitted call, in
bfloat16, the type they are served in. The reference reads them
upcast to float32 and computes everything in float32 at
``Precision.HIGHEST``, one layer at a time, with
attention in blocks of queries so that a long sequence fits beside the
weights.

``forward(..., quant=True)`` is the control: the same arithmetic with
the input and the weight of every linear layer rounded to int8 (a
symmetric scale per token and per output channel), the step a later
change to the program might take below the configuration's bfloat16.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

# Every product is asked for at full float32 precision by name: on a TPU
# a float32 matmul otherwise runs in a single bfloat16 pass, and a
# ``default_matmul_precision`` context around the tracing of a jitted
# function does not reach its lowering.
HIGHEST = jax.lax.Precision.HIGHEST

# The scales of the random weights. A random model is only a fair test of
# the served logits when its greedy choice is not decided by a large
# margin. These scales keep every layer's contribution to the residual
# stream near one (so the stream's norm grows with depth as in a trained
# model) and the logits at a spread of about 4.5, so that the top two
# logits are often close. With a tied head the embedding then makes up
# a small part of the final hidden state, so the model does not simply
# repeat its last input token.
EMBED_STD = 0.1          # embedding rows, and the untied head's columns
QK_GAIN = 1.5            # q, k per-dimension std: scores spread 2.25
O_GAIN = 1.0             # attention output projection
DOWN_GAIN = 1.5          # feed-forward output projection
NORM_STD = 0.1           # RMSNorm weight = 1 + N(0, NORM_STD)
ATTN_QUERY_BLOCK = 512   # queries per attention block in the reference


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the reference needs, read from a configuration file."""

    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int          # rows of the embedding (padded vocabulary)
    token_vocab: int         # ids the tokenizer can produce
    rope_theta: float
    norm_eps: float
    norm: str                # "rmsnorm" | "layernorm_noaffine"
    tied: bool
    max_positions: int


def dims(cfg: dict) -> Dims:
    """Read the sizes from a configuration file's published keys: the
    Hugging Face Llama keys (Yi) or OLMo's own keys."""
    if "hidden_size" in cfg:                      # Llama / Yi
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        if cfg["hidden_act"] != "silu":
            raise ValueError(f"unsupported activation {cfg['hidden_act']}")
        return Dims(d_model=d, n_layers=cfg["num_hidden_layers"], n_heads=h,
                    n_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
                    d_ff=cfg["intermediate_size"],
                    vocab_size=cfg["vocab_size"],
                    token_vocab=cfg["vocab_size"],
                    rope_theta=float(cfg["rope_theta"]),
                    norm_eps=float(cfg["rms_norm_eps"]), norm="rmsnorm",
                    tied=bool(cfg["tie_word_embeddings"]),
                    max_positions=cfg["max_position_embeddings"])
    d, h = cfg["d_model"], cfg["n_heads"]           # OLMo
    if cfg["activation_type"] != "swiglu" or cfg["layer_norm_with_affine"]:
        raise ValueError("only OLMo's swiglu, non-affine LayerNorm is "
                         "described here")
    # OLMo's ff_proj is (mlp_ratio * d_model) wide and SwiGLU halves it.
    return Dims(d_model=d, n_layers=cfg["n_layers"], n_heads=h,
                n_kv_heads=cfg.get("n_kv_heads") or h, head_dim=d // h,
                d_ff=cfg["mlp_ratio"] * d // 2,
                vocab_size=cfg["embedding_size"],
                token_vocab=cfg["vocab_size"],
                rope_theta=float(cfg["rope_theta"]),
                norm_eps=1e-5, norm="layernorm_noaffine",
                tied=bool(cfg["weight_tying"]),
                max_positions=cfg["max_sequence_length"])


def weight_shapes(dm: Dims) -> dict:
    """Leaf name -> shape of the published-form weights. Per-layer leaves
    are stacked on a leading layer axis."""
    L, d, f = dm.n_layers, dm.d_model, dm.d_ff
    q, kv = dm.n_heads * dm.head_dim, dm.n_kv_heads * dm.head_dim
    s = {"embed": (dm.vocab_size, d),
         "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv), "wo": (L, q, d),
         "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d)}
    if dm.norm == "rmsnorm":
        s.update(attn_norm=(L, d), mlp_norm=(L, d), final_norm=(d,))
    if not dm.tied:
        s["head"] = (d, dm.vocab_size)
    return s


def _std(name: str, shape, dm: Dims) -> float:
    fan_in = shape[-2] if len(shape) >= 2 else 1
    return {"embed": EMBED_STD, "head": EMBED_STD,
            "wq": QK_GAIN / math.sqrt(fan_in),
            "wk": QK_GAIN / math.sqrt(fan_in),
            "wv": 1.0 / math.sqrt(fan_in),
            "wo": O_GAIN / math.sqrt(fan_in),
            "w_gate": 1.0 / math.sqrt(fan_in),
            "w_up": 1.0 / math.sqrt(fan_in),
            "w_down": DOWN_GAIN / math.sqrt(fan_in)}.get(name, NORM_STD)


def make_weights(key, dm: Dims, dtype=jnp.bfloat16) -> dict:
    """Published-form random weights in ``dtype``. Call under ``jax.jit``
    so that they are made on the device in one program. Norm leaves hold
    ``gamma - 1``: the weight is one plus a bfloat16 offset, so that the
    program and the reference read the same number."""
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(dm).items())):
        k = jax.random.fold_in(key, i)
        out[name] = (_std(name, shape, dm)
                     * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _int8_round(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, quant: bool):
    """x (..., n) @ w (n, m) in float32; with ``quant`` both sides are
    first rounded to int8 (per token of x, per output channel of w)."""
    w = w.astype(jnp.float32)
    if quant:
        x = _int8_round(x, -1)
        w = _int8_round(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, gamma_m1, dm: Dims):
    if dm.norm == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + dm.norm_eps)
        return y * (1.0 + gamma_m1.astype(jnp.float32))
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + dm.norm_eps)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x: (S, H, D); pos: (S,)."""
    half = x.shape[-1] // 2
    dim = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention in query blocks.
    q: (S, Hq, D); k, v: (S, Hkv, D). Query head h reads kv head
    h // (Hq // Hkv), as Hugging Face's ``repeat_kv`` arranges them."""
    S, Hq, D = q.shape
    rep = Hq // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    qb = min(ATTN_QUERY_BLOCK, S)
    nb = S // qb
    kpos = jnp.arange(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) \
            / math.sqrt(D)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    return jax.lax.map(block, jnp.arange(nb)).reshape(S, Hq, D)


def forward(w: dict, dm: Dims, tokens, quant: bool = False):
    """Next-token logits (float32) at every position of one sequence.

    tokens: (S,) int32, with S a multiple of ``ATTN_QUERY_BLOCK`` or
    smaller than it (pad the tail: causal attention keeps padding out
    of every earlier position). Returns (S, V).
    """
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"][tokens].astype(jnp.float32)
    layer_keys = ["wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"]
    if dm.norm == "rmsnorm":
        layer_keys += ["attn_norm", "mlp_norm"]
    stacked = {k: w[k] for k in layer_keys}

    def layer(x, lw):
        h = _norm(x, lw.get("attn_norm"), dm)
        q = _linear(h, lw["wq"], quant).reshape(S, dm.n_heads, dm.head_dim)
        k = _linear(h, lw["wk"], quant).reshape(S, dm.n_kv_heads,
                                                dm.head_dim)
        v = _linear(h, lw["wv"], quant).reshape(S, dm.n_kv_heads,
                                                dm.head_dim)
        q = _rope(q, pos, dm.rope_theta)
        k = _rope(k, pos, dm.rope_theta)
        a = _attention(q, k, v).reshape(S, dm.n_heads * dm.head_dim)
        x = x + _linear(a, lw["wo"], quant)
        h = _norm(x, lw.get("mlp_norm"), dm)
        g = jax.nn.silu(_linear(h, lw["w_gate"], quant))
        u = _linear(h, lw["w_up"], quant)
        return x + _linear(g * u, lw["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, stacked)
    h = _norm(x, w.get("final_norm"), dm)
    head = w["embed"].T if dm.tied else w["head"]
    return _linear(h, head, quant)
