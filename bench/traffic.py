"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a closed loop out.

A traffic file (``bench/traffic/<name>.json``) holds

* ``prompt`` and ``output``: length distributions, each
  ``{"median", "sigma", "min", "max"}`` of a lognormal truncated to
  ``[min, max]`` (the part of the distribution in that range, not a pile
  at its ends);
* ``zipf_a``: the Zipf exponent of token content over the whole
  vocabulary;
* ``pool``: how many requests the loop draws from (it cycles);
* ``warmup_steps``: scheduler steps of the cell's own traffic run as
  set-up before the window;
* ``check_requests`` and ``check_tokens``: how many finished requests
  the correctness check compares, and how many served tokens at least;
* ``source`` and ``cuts``: where the length shape comes from, and why the
  range cuts it (read by people, not by the generator).

Every seed gets the same work in another order.  A pool of n requests
takes its lengths at the n quantiles ``(k + 0.5) / n`` of their
distributions, the same for every seed; request i takes the quantile whose
rank is that of ``frac(a + i / phi)`` (prompts) or ``frac(b + i * (sqrt 2
- 1))`` (outputs) among the pool's, where only the offsets a and b come
from the seed: these sequences spread evenly over (0, 1), so any run of
consecutive requests -- the ones a window serves -- holds nearly the same
mix of lengths whatever the seed.  The pool's prompt tokens are the
same for every seed too, in another order: Zipf ranks at the quantiles of
their count, mapped to ids by one fixed permutation (so the hot embedding
rows are spread over the vocabulary), and shuffled by the seed.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of the loop: its prompt and how many tokens to serve."""

    prompt: np.ndarray
    max_new: int


def load_traffic(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def lengths_at(dist: dict, u) -> np.ndarray:
    """Lengths at quantiles ``u`` of a lognormal truncated to [min, max]."""
    norm = NormalDist()
    mu, sigma = np.log(dist["median"]), dist["sigma"]
    lo, hi = (norm.cdf((np.log(dist[k]) - mu) / sigma) for k in ("min", "max"))
    u = lo + np.ravel(u) * (hi - lo)
    u = np.clip(u, 1e-12, 1.0 - 1e-12)
    z = np.array([norm.inv_cdf(float(x)) for x in u])
    x = np.exp(mu + sigma * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


# strides of the two low-discrepancy sequences: 1/phi and sqrt(2) - 1
_STRIDE_PROMPT = (np.sqrt(5.0) - 1.0) / 2.0
_STRIDE_OUTPUT = np.sqrt(2.0) - 1.0


def _strata(u: np.ndarray) -> np.ndarray:
    """The n quantiles ``(k + 0.5) / n``, in the order of ``u``'s ranks:
    every seed gets the same lengths, and consecutive requests keep the
    even spread of the sequence ``u``."""
    ranks = np.argsort(np.argsort(u, kind="stable"), kind="stable")
    return (ranks + 0.5) / len(u)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** a
    return np.cumsum(p / p.sum())


def zipf_tokens(vocab: int, a: float, n: int) -> np.ndarray:
    """The ``n`` prompt tokens of a pool: Zipf ranks at the n quantiles
    ``(k + 0.5) / n``, mapped to ids by one fixed permutation of the
    vocabulary.  The same tokens for every seed, so the hot embedding rows
    (and the pages that hold them) are the same too."""
    ranks = np.searchsorted(zipf_cdf(vocab, a), (np.arange(n) + 0.5) / n,
                            side="right")
    ids = _rng(0, 3).permutation(vocab).astype(np.int32)
    return ids[np.minimum(ranks, vocab - 1)]


def make_requests(traffic: dict, vocab: int, seed: int) -> list[Req]:
    """The loop's requests, in the order the clients take them."""
    n = int(traffic["pool"])
    a, b = _rng(seed, 2).random(2)
    i = np.arange(n)
    prompts = lengths_at(traffic["prompt"],
                         _strata((a + i * _STRIDE_PROMPT) % 1.0))
    outputs = lengths_at(traffic["output"],
                         _strata((b + i * _STRIDE_OUTPUT) % 1.0))
    toks = _rng(seed, 3).permutation(
        zipf_tokens(vocab, float(traffic["zipf_a"]), int(prompts.sum())))
    cuts = np.cumsum(prompts)[:-1]
    return [Req(p, int(m)) for p, m in zip(np.split(toks, cuts), outputs)]


class ClosedLoop:
    """One client per lane, no think time: a client sends its next request
    the moment its last one finishes.  Requests are handed out in order,
    cycling through the list."""

    def __init__(self, reqs: list[Req], clients: int):
        self.reqs = reqs
        self.clients = clients
        self.sent = 0

    def next(self) -> Req:
        req = self.reqs[self.sent % len(self.reqs)]
        self.sent += 1
        return req
