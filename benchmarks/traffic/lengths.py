"""Request lengths: a fixed grid of quantiles of two clipped log-normal
distributions (prompt, output), sent in rounds. Every seed sends the
same set of prompt lengths and the same set of output lengths, and every
round of `strata` requests takes one prompt and one output from each of
`strata` bands of neighbouring quantiles: any stretch of a run holds a
fair sample, and the seed chooses the order, the pairing and which
member of a band comes when. A plain draw of 100 requests would move
the mean output length by several percent, and that would be read as
noise in every bound."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n quantile points of a clipped log-normal, ascending."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


class Lengths:
    """(prompt, output) pairs, pass after pass over the grid. `strata`
    divides `grid`; 1 is a plain seeded permutation."""

    def __init__(self, params: dict, seed: int):
        n, s = params["grid"], params["strata"]
        if n % s:
            raise ValueError(f"strata {s} does not divide grid {n}")
        self.bands = [quantiles(params[k], n).reshape(s, n // s)
                      for k in ("prompt", "output")]
        self.seed, self.i = seed, 0
        self.pairs = self._pass(0)

    def _pass(self, p: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, p])
        cols = []
        for bands in self.bands:
            # column r of a band is the member round r takes; a round's
            # values are then put in an order of their own, which pairs
            # prompts with outputs at random
            rounds = rng.permuted(bands, axis=1).T
            cols.append(rng.permuted(rounds, axis=1).reshape(-1))
        return np.stack(cols, 1)

    def next(self) -> tuple[int, int, int]:
        """(index of the request, prompt length, output length)."""
        p, k = divmod(self.i, len(self.pairs))
        if k == 0 and p:
            self.pairs = self._pass(p)
        pl, ol = self.pairs[k]
        self.i += 1
        return self.i - 1, int(pl), int(ol)


def prompt_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, 1, index]).integers(
        1, vocab, n, dtype=np.int32)
