"""Seed-invariant traffic, from a mix's file of parameters.

A mix (``traffic/<name>.json``) states distributions and counts; this
one generator turns them into requests. Every seed gives the same
work: lengths are taken at the fixed quantiles ``(i + 0.5) / n`` of
each distribution, and an open loop offers a fixed number of arrivals.
The seed only orders and pairs the lengths, places the arrivals, and
draws the token ids and sampling seeds.

Two loop kinds:

* ``closed``: every slot holds a request, and a finished request is
  replaced at once from a sequence of fresh ones. The slots start in
  equilibrium: as a renewal process would find them after a long run,
  each slot's output length is drawn in proportion to length (a slot
  spends longer in a long request) and the slot is part-way through
  it, at an age uniform within it. Its prompt then holds the prompt
  and the tokens already made, and it still has the rest to make.
* ``open``: a fixed count of arrivals, ``rate`` times the span, after
  a lead-in of arrivals made the same way, so that the queue and the
  slots are in their steady state when the window opens. The gaps
  between arrivals are the quantiles of the exponential law (the gaps
  of a Poisson process), scaled to fill the span, in a stratified
  order: every seed offers the same set of gaps, and so the same
  bursts and lulls, in another order.

The ordering is stratified in rounds: each run of ``ROUND`` requests
takes one length from each of ``ROUND`` strata of the sorted grid, so
that any prefix of the sequence, such as the requests that fit in a
window, holds nearly the same lengths under every seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent
ROUND = 16


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of the traffic. ``due`` is seconds from the start of
    its phase (open loop), ``None`` in a closed loop."""

    prompt: list                 # token ids
    max_tokens: int
    greedy: bool
    seed: int
    due: float | None = None


def load_mix(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def grid(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of ``dist``, sorted.
    ``loguniform`` spans [min, max]. A ``lognormal`` (median, sigma)
    length beyond [min, max] is set to the nearer end, as a prompt longer
    than the context is cut to fit it and an answer stops at
    ``max_tokens``: the law's quantiles inside the range, and so its
    median, are kept."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist["dist"] == "lognormal":
        nd, mu, s = NormalDist(), math.log(dist["median"]), dist["sigma"]
        x = np.exp(mu + s * np.array([nd.inv_cdf(v) for v in u]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def length_biased(lengths: np.ndarray, n: int) -> np.ndarray:
    """``n`` values at the quantiles (i + 0.5) / n of the law that picks
    from ``lengths`` in proportion to length."""
    srt = np.sort(lengths)
    cdf = np.cumsum(srt) / srt.sum()
    u = (np.arange(n) + 0.5) / n
    return srt[np.minimum(np.searchsorted(cdf, u), len(srt) - 1)]


def stratified_order(n: int, rng) -> np.ndarray:
    """A permutation of range(n) (indices into a sorted grid) in rounds:
    each round takes one index from each of ``ROUND`` strata, in random
    order, and the strata are visited at random positions."""
    strata = np.array_split(np.arange(n), min(ROUND, n))
    key = np.empty(n)
    for idx in strata:
        key[idx] = rng.permutation(len(idx)) + rng.random(len(idx))
    return np.argsort(key, kind="stable")


def _greedy(k: int, share: float) -> bool:
    """Exactly ``share`` of every run of requests decodes greedily."""
    return math.floor((k + 1) * share) > math.floor(k * share)


def _request(k: int, prompt_len: int, max_tokens: int, mix: dict, rng,
             vocab: int) -> Req:
    return Req(prompt=rng.integers(0, vocab, size=prompt_len).tolist(),
               max_tokens=max_tokens, greedy=_greedy(k, mix["greedy_share"]),
               seed=int(rng.integers(0, 2 ** 31 - 1)))


def _requests(mix: dict, n: int, rng, vocab: int) -> list[Req]:
    """``n`` requests from the mix's prompt and output grids, each
    ordered by its own stratified permutation."""
    p = grid(mix["prompt"], n)[stratified_order(n, rng)]
    o = grid(mix["output"], n)[stratified_order(n, rng)]
    return [_request(k, int(p[k]), int(o[k]), mix, rng, vocab)
            for k in range(n)]


def _spread_index(n: int) -> np.ndarray:
    """A fixed permutation of range(n) that pairs small with large the
    same way every time: k -> k * step mod n, with the step coprime to n
    and near n / golden ratio."""
    step = max(1, round(n * 0.618))
    while math.gcd(step, n) != 1:
        step += 1
    return (np.arange(n) * step) % n


def equilibrium(outputs: np.ndarray, slots: int, rng):
    """(output length, age) of each slot of a closed loop in equilibrium:
    lengths at ``slots`` quantiles of the length-biased law over
    ``outputs``, ages at ``slots`` quantiles of a uniform share of each.
    The pairing is fixed, so every seed starts from the same set of
    (length, age) pairs; the seed only assigns them to slots.
    0 <= age < output length."""
    outs = length_biased(outputs, slots)
    u = (_spread_index(slots) + 0.5) / slots
    ages = np.minimum(np.floor(u * outs), outs - 1).astype(np.int64)
    order = rng.permutation(slots)
    return outs[order], ages[order]


def closed_loop(mix: dict, seed: int, vocab: int):
    """(initial requests, one per slot, in equilibrium; the sequence of
    replacements). See the module docstring. The initial prompts are
    paired with the slots' states in a fixed way too, so the start holds
    the same (prompt, age, output) triples under every seed."""
    rng = np.random.default_rng(seed)
    slots = mix["slots"]
    prompts = grid(mix["prompt"], slots)[_spread_index(slots)[::-1]]
    outs, ages = equilibrium(grid(mix["output"], mix["requests"]), slots,
                             np.random.default_rng(0))
    order = rng.permutation(slots)
    initial = [_request(k, int(prompts[i] + ages[i]), int(outs[i] - ages[i]),
                        mix, rng, vocab) for k, i in enumerate(order)]
    return initial, _requests(mix, mix["requests"], rng, vocab)


def arrivals(n: int, span: float, rng) -> np.ndarray:
    """``n`` arrival times in [0, span): gaps at the quantiles of the
    exponential law, in a stratified order, scaled so that the n gaps
    (the first from the phase's start) and the time after the last
    arrival fill ``span`` as they would at that rate."""
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = -np.log1p(-u)                       # Exp(1) quantiles, sorted
    gaps = gaps[stratified_order(n + 1, rng)]
    return np.cumsum(gaps)[:n] * span / gaps.sum()


def open_loop(mix: dict, seed: int, vocab: int, seconds: float):
    """(lead-in requests, window requests), each sorted by ``due``
    (seconds from the start of its own phase)."""
    rng = np.random.default_rng(seed)
    phases = []
    for span in (mix["lead_in_s"], seconds):
        n = max(1, round(mix["rate"] * span))
        due = arrivals(n, span, rng)
        reqs = _requests(mix, n, rng, vocab)
        phases.append([dataclasses.replace(r, due=float(t))
                       for r, t in zip(reqs, due)])
    return phases[0], phases[1]
