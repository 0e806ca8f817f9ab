"""Vectorized on-device sampling step for the serving engine.

One jit'd function samples every decode slot at once from per-slot
parameter arrays (temperature / top-k / top-p / seed / RNG-stream step)
— replacing the old host-side per-row argmax/softmax loop. Retired or
empty slots ride along with default parameters; their draws are
discarded by the scheduler, keeping the call shape-stable.

Determinism contract: token t of a request is drawn from
``fold_in(PRNGKey(seed), t)`` — a pure function of the request's own
(seed, t) and its own logits — so sampled outputs do not depend on
admission order, slot index, co-batched requests, or preemption/resume
history (the stream position survives a preemption in the request's
recompute record).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.engine import tracing


def fold_seed(seed: int) -> int:
    """Fold an arbitrary Python int seed into the non-negative int32
    range the device-side param arrays carry (numpy 2.x raises on
    out-of-range int32 assignment). Pure masking — a given seed always
    selects the same stream through every entry point."""
    return int(seed) & 0x7FFFFFFF


def _sample_row(logits, seed, step, temp, top_k, top_p):
    """One slot: logits (V,) f32 -> sampled token id (int32)."""
    V = logits.shape[0]
    greedy = jnp.argmax(logits).astype(jnp.int32)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    t = jnp.maximum(temp, 1e-6).astype(jnp.float32)
    scaled = logits.astype(jnp.float32) / t
    desc = jnp.flip(jnp.sort(scaled))               # descending
    # top-k: logits below the k-th highest are cut (k <= 0 disables;
    # ties at the threshold survive — the standard caveat)
    k_eff = jnp.where(top_k <= 0, V, jnp.clip(top_k, 1, V))
    thresh_k = desc[jnp.clip(k_eff - 1, 0, V - 1)]
    # top-p (nucleus): keep the smallest descending-probability prefix
    # whose mass reaches top_p — i.e. tokens whose PRECEDING cumulative
    # mass is < p. The argmax token is always kept.
    probs = jax.nn.softmax(desc)
    cum = jnp.cumsum(probs)
    kept = (cum - probs) < jnp.clip(top_p, 1e-6, 1.0)
    thresh_p = desc[jnp.maximum(jnp.sum(kept) - 1, 0)]
    allowed = (scaled >= thresh_k) & (scaled >= thresh_p)
    masked = jnp.where(allowed, scaled, -jnp.inf)
    sampled = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


@jax.jit
def sample_tokens(logits, seeds, steps, temps, top_ks, top_ps):
    """logits (B, V) f32 + per-slot param arrays (B,) -> (B,) int32."""
    return jax.vmap(_sample_row)(logits, seeds, steps, temps, top_ks,
                                 top_ps)


def fused_sample(logits, steps, samp):
    """Traced sampling tail of the scheduler's fused overlap step:
    greedy argmax when ``samp`` is None (same first-occurrence
    tie-break as the host fast path in ``SlotSampler.sample``), else
    the full per-slot sampler — ``samp`` is the (seeds, temps, top_ks,
    top_ps) arrays and ``steps`` the per-slot RNG-stream positions.
    Returns the (B,) int32 tokens still on device."""
    if samp is None:
        return jnp.argmax(logits, -1).astype(jnp.int32)
    seeds, temps, top_ks, top_ps = samp
    return sample_tokens(logits, seeds, steps, temps, top_ks, top_ps)


def verify_accept(logits, tokens, num_drafts, seeds, steps, temps,
                  top_ks, top_ps):
    """Vectorized accept/resample rule for a speculative verify window.

    Because every request's sampler is a *deterministic* function of its
    own RNG stream (token t draws from ``fold_in(PRNGKey(seed), t)``
    applied to the target logits at position t), the standard
    rejection-sampling acceptance collapses to exact-match coupling:
    compute the token the baseline sampler WOULD emit at each of the
    K+1 window positions (greedy argmax, or the seeded categorical draw
    at stream position ``steps + j``), accept the longest draft prefix
    that matches those draws, and emit the first mismatching target
    token as the correction (the row-K target is the bonus token when
    every draft matches). Outputs are therefore bit-identical to
    non-speculative decoding — distribution preservation is trivial
    because this IS the same sampler, evaluated ahead of time.

    Parameters
    ----------
    logits : (B, K1, V) f32
        Target-model logits for the K+1 fed tokens; row j scores the
        position after fed token j.
    tokens : (B, K1) int32
        The fed window: row 0 is the last accepted token, rows 1..K the
        draft proposals (garbage-padded past ``num_drafts``).
    num_drafts : (B,) int32
        Usable drafts per slot; padded rows can never be accepted.
    seeds, steps, temps, top_ks, top_ps : (B,) arrays
        The per-slot sampling parameters (SlotSampler layout); ``steps``
        is each request's RNG-stream position at the window start.

    Returns
    -------
    out_tokens : (B, K1) int32
        The emitted tokens, -1 past each row's emitted prefix.
    commit : (B,) int32 in [1, K1]
        Fed tokens whose cache state is valid (accepted drafts + 1).
    """
    tgt = jax.vmap(
        lambda lg, s, st, t, k, p: jax.vmap(
            lambda l, j: _sample_row(l, s, st + j, t, k, p))(
                lg, jnp.arange(lg.shape[0], dtype=jnp.int32))
    )(logits, seeds, steps, temps, top_ks, top_ps)           # (B, K1)
    return _accept_targets(tgt, tokens, num_drafts)


def verify_accept_greedy(logits, tokens, num_drafts):
    """All-greedy fast path of ``verify_accept`` (the serving default):
    targets are plain argmax rows — no sort/top-k/top-p/RNG machinery,
    which dominates the verify step's device time on small models. The
    backend selects it at call time when every slot decodes greedily;
    outputs equal ``verify_accept`` with ``temps <= 0``."""
    return _accept_targets(jnp.argmax(logits, -1).astype(jnp.int32),
                           tokens, num_drafts)


def _accept_targets(tgt, tokens, num_drafts):
    """Shared tail of the accept rule: longest draft prefix matching the
    per-position target draws, plus the correction/bonus target."""
    K1 = tgt.shape[1]
    jidx = jnp.arange(K1, dtype=jnp.int32)
    ok = (tokens[:, 1:] == tgt[:, :-1]) \
        & (jidx[None, :-1] < num_drafts[:, None])
    # accepted = length of the leading all-True prefix
    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    out = jnp.where(jidx[None, :] <= acc[:, None], tgt, -1)
    return out, acc + 1


class SlotSampler:
    """Host-side mirror of the per-slot sampling parameter arrays.

    The backend installs a request's SamplingParams at admission and
    resets the slot at retirement; ``sample`` forwards the arrays to the
    jit'd step. ``steps[i]`` is the owning request's RNG-stream position
    and must be advanced by the backend after every accepted draw.
    """

    def __init__(self, num_slots: int):
        self.temps = np.zeros((num_slots,), np.float32)
        self.top_ks = np.zeros((num_slots,), np.int32)
        self.top_ps = np.ones((num_slots,), np.float32)
        self.seeds = np.zeros((num_slots,), np.int32)
        self.steps = np.zeros((num_slots,), np.int32)

    def install(self, slot: int, sampling, n_sampled: int):
        """Install a request's SamplingParams at admission; ``n_sampled``
        is its RNG-stream position (nonzero on preemption resume)."""
        self.temps[slot] = sampling.temperature
        self.top_ks[slot] = sampling.top_k
        self.top_ps[slot] = sampling.top_p
        self.seeds[slot] = fold_seed(sampling.seed)
        self.steps[slot] = n_sampled

    def clear(self, slot: int):
        """Reset a retired/preempted slot to the default (greedy) row."""
        self.temps[slot] = 0.0
        self.top_ks[slot] = 0
        self.top_ps[slot] = 1.0
        self.seeds[slot] = 0
        self.steps[slot] = 0

    def sample(self, logits):
        """logits: (B, V) device array -> (B,) numpy int32 tokens."""
        if (self.temps <= 0.0).all():
            # all-greedy fast path (the default): skip the per-slot
            # sort/softmax/cumsum machinery the stochastic step needs
            return np.argmax(np.asarray(logits), -1).astype(np.int32)
        toks = sample_tokens(logits, jnp.asarray(self.seeds),
                             jnp.asarray(self.steps),
                             jnp.asarray(self.temps),
                             jnp.asarray(self.top_ks),
                             jnp.asarray(self.top_ps))
        return np.asarray(toks)

    def fused_args(self, steps):
        """The (steps, samp) pair the scheduler threads into its fused
        overlap step: ``samp`` is None on the all-greedy fast path
        (selecting ``fused_sample``'s argmax variant — a distinct jit
        trace, since the pytree structure differs), else the per-slot
        parameter arrays. ``steps`` overrides ``self.steps`` — under
        overlap a slot with an un-harvested in-flight token sits one
        stream position ahead of the host mirror."""
        if (self.temps <= 0.0).all():
            return steps, None
        return steps, (self.seeds, self.temps, self.top_ks, self.top_ps)

    def sample_one(self, slot: int, row_logits):
        """Sample for ONE slot (prefill admission) from the parameters
        just installed — same streams as the batch path, no duplicate
        parameter marshalling. row_logits: (1, V). A sampled row takes
        one device round trip (a ``scheduler.fetch`` span)."""
        if self.temps[slot] <= 0.0:
            return int(np.argmax(np.asarray(row_logits)[0]))
        with tracing.span("scheduler.fetch") as sp:
            if sp:
                sp.set(what="first_token")
            sl = slice(slot, slot + 1)
            toks = sample_tokens(row_logits, jnp.asarray(self.seeds[sl]),
                                 jnp.asarray(self.steps[sl]),
                                 jnp.asarray(self.temps[sl]),
                                 jnp.asarray(self.top_ks[sl]),
                                 jnp.asarray(self.top_ps[sl]))
            return int(np.asarray(toks)[0])
