"""The comparison that decides ``correct``: served greedy tokens against
the plain float32 reference.

Once the window has closed, a sample of the greedy requests that the
run finished, drawn from the seed and always holding the one with the
most served tokens, is run through the reference over its prompt and
its served tokens. At every served position the number compared is the
gap by which the served token's reference logit lies below the
reference's best logit there: 0 where the program chose the reference's
argmax, and small where a near tie was resolved the other way by
rounding. The run's reading is the widest gap over the sample.

The control puts the reference computed in int8 (``forward(...,
quant=True)``) in the program's place: at the same positions it reads
the gap of the token the int8 reference puts first, and ``verdict``
judges that reading with the same limits as the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sample(done, rng, min_tokens: int, max_requests: int):
    """From ``done`` ((prompt, served) pairs of finished greedy requests)
    take the one with the most served tokens, then others in an order
    drawn from ``rng``, until ``min_tokens`` served tokens or
    ``max_requests`` requests are in the sample."""
    if not done:
        return []
    order = sorted(range(len(done)), key=lambda i: -len(done[i][1]))
    first, rest = order[0], order[1:]
    picked = [first] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for i in picked:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(done[i])
        n += len(done[i][1])
    return out


def verdict(gap: float, short: int, n_cmp: int, limits: dict):
    """(correct, checks) of one run, the program's or the control's:
    each number compared beside its limit, and whether all hold."""
    checks = {"logit_gap": {"value": gap, "max": limits["logit_gap"]},
              "short_outputs": {"value": short, "max": 0},
              "tokens_compared": {"value": n_cmp,
                                  "min": limits["min_tokens"]}}
    correct = (gap <= limits["logit_gap"] and short == 0
               and n_cmp >= limits["min_tokens"])
    return correct, checks


def _bucket(n: int, block: int) -> int:
    """The padded sequence length: ``block`` times a power of two."""
    b = block
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("ref", "dm", "control"))
def _gaps(w, tokens, target, mask, *, ref, dm, control):
    logits = ref.forward(w, dm, tokens)
    best = logits.max(-1)

    def gap(tok):
        g = best - jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
        return jnp.where(mask, g, 0.0).max()

    g_prog = gap(target)
    if not control:
        return g_prog, jnp.float32(0)
    low = jnp.argmax(ref.forward(w, dm, tokens, quant=True), -1)
    return g_prog, gap(low.astype(jnp.int32))


def gaps(ref, dm, weights, samples, control: bool = False):
    """Widest gap of the served tokens over ``samples`` and, with
    ``control``, the widest gap of the int8 reference's choices at the
    same positions. Returns (program gap, control gap or None, tokens
    compared)."""
    block = ref.ATTN_QUERY_BLOCK
    g_prog, g_ctl, n = 0.0, 0.0, 0
    for prompt, served in samples:
        seq = list(prompt) + list(served[:-1])
        S = _bucket(len(seq), block) if len(seq) > block else len(seq)
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq)] = seq
        target = np.zeros(S, np.int32)
        mask = np.zeros(S, bool)
        lo = len(prompt) - 1
        target[lo:lo + len(served)] = served
        mask[lo:lo + len(served)] = True
        gp, gc = _gaps(weights, jnp.asarray(tokens), jnp.asarray(target),
                       jnp.asarray(mask), ref=ref, dm=dm, control=control)
        g_prog = max(g_prog, float(gp))
        g_ctl = max(g_ctl, float(gc))
        n += len(served)
    return g_prog, (g_ctl if control else None), n
