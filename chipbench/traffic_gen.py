"""The one generator of every traffic mix: requests for serving cells and
token batches for training cells, each a pure function of the seed and
of the mix's parameters (a ``traffic/<name>.json`` file).

The benchmark keeps its own copy, so that no change to the program can
change what is offered to it. The token stream is the one of
``repro.data.synthetic.lm_tokens`` (Zipf ranks with exponent 1.1, and a
copy of the previous token with probability 0.3), copied here.

Arrivals are a Poisson process at the mix's rate: independent
exponential gaps drawn from the seed, as ``repro.serve.trace``'s
``poisson`` draws them (copied here), conditioned on ``rate × seconds``
arrivals in the window (the gaps are scaled so that the count is the
same on every seed; the arrival times are then the order statistics of
that many uniform draws over the window, bursts and lulls included).
Prompt and output lengths are a fixed multiset whose shares follow the
mix's weights, put in an order of the seed's over the whole window. So
every seed offers the same work at the same mean rate, with arrivals
and sizes in another order.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

__all__ = ["Request", "lm_tokens", "requests", "train_tokens", "seed32", "jax_key",
           "window_quantile"]

# salts that keep the streams of one seed apart
_SALT_ARRIVALS, _SALT_PROMPTS, _SALT_OUTPUTS, _SALT_TRAIN = 0xA1, 0xB2, 0xC3, 0xD4


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of an open-loop serving mix. ``max_new`` counts every
    output token, the prefill's first token included."""

    rid: int
    arrival: float
    prompt_len: int
    max_new: int


def _rng(seed: int, *salts: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *salts]))


def lm_tokens(seed: int, start: int, batch: int, seq_len: int, vocab: int) -> np.ndarray:
    """(batch, seq_len + 1) int32 token ids; a copy of
    ``repro.data.synthetic.lm_tokens``, which the serving engine uses to
    make each request's prompt from (engine seed, request id)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 505, start, batch]))
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / ranks**1.1
    p /= p.sum()
    toks = rng.choice(vocab, size=(batch, seq_len + 1), p=p)
    mask = rng.uniform(size=(batch, seq_len)) < 0.3
    toks[:, 1:][mask] = toks[:, :-1][mask]
    return toks.astype(np.int32)


def _shares(values, weights, n: int) -> np.ndarray:
    """``n`` values whose counts follow ``weights`` (largest remainders)."""
    w = np.asarray(weights, np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return np.repeat(np.asarray(values), counts)


def _shuffled(values, seed: int, salt: int) -> np.ndarray:
    """``values`` in an order drawn from the seed."""
    return _rng(seed, salt).permutation(np.asarray(values))


def requests(mix: dict, seed: int, seconds: float) -> list[Request]:
    """The requests due in a window of ``seconds``: ``rate × seconds`` of
    them, Poisson arrivals inside the window."""
    n = max(int(round(mix["rate"] * seconds)), 1)
    gaps = _rng(seed, _SALT_ARRIVALS).exponential(1.0 / mix["rate"], size=n + 1)
    arrivals = np.cumsum(gaps)[:-1] * (seconds / gaps.sum())
    prompts = _shuffled(_shares(mix["prompt_lens"], mix["prompt_weights"], n), seed,
                        _SALT_PROMPTS)
    lo, hi = mix["max_new"]
    outs = _shuffled(np.rint(lo + (hi - lo) * (np.arange(n) + 0.5) / n).astype(int), seed,
                     _SALT_OUTPUTS)
    return [Request(rid=i, arrival=float(arrivals[i]), prompt_len=int(prompts[i]),
                    max_new=int(outs[i])) for i in range(n)]


def train_tokens(seed: int, round_idx: int, tau: int, batch: int, seq: int,
                 vocab: int) -> np.ndarray:
    """(tau, batch, seq) int32 tokens of one commit round: every row of
    every round is a stream of its own."""
    toks = lm_tokens(seed, _SALT_TRAIN * 1_000_003 + round_idx, tau * batch, seq, vocab)
    return toks[:, :-1].reshape(tau, batch, seq)


def seed32(seed: int) -> int:
    """A 32-bit seed for APIs that take no more (the engine's prompt
    seed), mixed from every bit of ``seed``."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def jax_key(seed: int):
    """A JAX PRNG key from every bit of ``seed``: ``PRNGKey`` alone keeps
    only the low 32 bits without 64-bit mode."""
    import jax

    return jax.random.PRNGKey(seed32(seed))


def window_quantile(values, q: float) -> float:
    """The ``q`` quantile (nearest rank, 0 < q < 1) of ``values``; inf
    ranks above every number, as an unanswered request does."""
    xs = sorted(values)
    if not xs:
        return math.inf
    return xs[min(len(xs) - 1, max(int(math.ceil(q * len(xs))) - 1, 0))]
