"""The one traffic generator: every mix is a JSON file of parameters.

Lengths are drawn from a clipped lognormal, and arrivals (for an open
loop) from a Poisson process.  Both are *stratified*: a block of ``n``
requests takes the ``n`` quantiles of the distribution at
``(i + 0.5) / n``.  So every seed offers the same work.  Token ids are
uniform over the vocabulary, from the seed.

Where each quantile goes is set in two steps:

* a *layout*, the same for every seed, cuts the ``n`` ranks into bands of
  neighbours and deals one rank of every band to each segment of about
  ``layout_every`` consecutive requests, so that every few seconds of
  traffic hold a small copy of the whole distribution (a short and a long
  prompt, a short and a long gap);
* the seed then permutes ranks among groups of ``swap_within`` neighbours
  in rank, so that each seed sends its own order while the shape of the
  load, and with it the queueing that sets a latency tail, stays the same.

Lengths and gaps are laid out apart, each from its own stream.

Open loop (``"arrivals": "poisson"``): block ``b`` covers the time
``[b * W, (b + 1) * W)`` and holds ``round(rate * W)`` requests, spaced as
the normalised exponential spacings of a Poisson process that is known to
put that many arrivals in the block.  Block 0 is the measured window;
later blocks keep the load on while the window's requests drain.

Backlog (``"arrivals": "backlog"``): no times; requests come in blocks of
``block`` and the driver keeps ``queue_factor * max_batch`` of them
waiting.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

_STD_NORMAL = NormalDist()
_LAYOUT = 1 << 20          # streams of the layout, apart from the seed's


@dataclass
class Item:
    """One request as the generator offers it."""
    idx: int
    due: float            # seconds from the window's start (0 for backlog)
    prompt: List[int]
    max_new_tokens: int


def clipped_lognormal_quantiles(n: int, median: float, sigma: float,
                                lo: int, hi: int) -> np.ndarray:
    """The ``n`` stratified draws of a lognormal clipped to [lo, hi],
    ascending."""
    qs = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * _STD_NORMAL.inv_cdf(q)) for q in qs]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_spacings(n: int) -> np.ndarray:
    """The ``n`` stratified draws of a unit exponential, ascending."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def layout(n: int, every: int, rng: np.random.Generator) -> np.ndarray:
    """The rank at each of ``n`` positions: bands of ``ceil(n / every)``
    neighbouring ranks, each dealt one rank to a segment of about
    ``every`` consecutive positions."""
    k = -(-n // every)
    segments: List[List[int]] = [[] for _ in range(k)]
    for lo in range(0, n, k):
        for s, r in zip(rng.permutation(k), range(lo, min(lo + k, n))):
            segments[s].append(r)
    return np.concatenate([rng.permutation(np.array(s, np.int64))
                           for s in segments if s])


def swap_neighbours(n: int, within: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``n`` ranks that moves each only inside its group
    of ``within`` neighbours."""
    out = np.arange(n)
    for lo in range(0, n, within):
        out[lo:lo + within] = rng.permutation(out[lo:lo + within])
    return out


class Traffic:
    """Requests of one mix for one seed, block by block."""

    def __init__(self, mix: dict, seed: int, vocab_size: int, *,
                 window_s: float = 0.0, rate: float = 0.0):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.open_loop = mix["arrivals"] == "poisson"
        if self.open_loop:
            if rate <= 0 or window_s <= 0:
                raise ValueError("an open-loop mix needs a rate and a window")
            self.block_n = max(1, round(rate * window_s))
        else:
            self.block_n = int(mix["block"])
        self.window_s = window_s
        self.rate = rate

    def _place(self, ascending: np.ndarray, stream: int, block: int) -> np.ndarray:
        """The ascending draws in this seed's order (see the module's
        docstring)."""
        n = len(ascending)
        ranks = layout(n, int(self.mix["layout_every"]),
                       _rng(0, _LAYOUT + stream, block))
        swap = swap_neighbours(n, int(self.mix["swap_within"]),
                               _rng(self.seed, stream, block))
        return ascending[swap[ranks]]

    def _lengths(self, key: str, block: int, n: int) -> np.ndarray:
        d = self.mix[key]
        vals = clipped_lognormal_quantiles(n, d["median"], d["sigma"],
                                           d["min"], d["max"])
        return self._place(vals, 1 if key == "prompt_tokens" else 2, block)

    def block(self, b: int) -> List[Item]:
        n = self.block_n
        p_len = self._lengths("prompt_tokens", b, n)
        o_len = self._lengths("output_tokens", b, n)
        if self.open_loop:
            # n arrivals in the block: n + 1 spacings span it exactly
            gaps = self._place(exponential_spacings(n + 1), 3, b)
            due = b * self.window_s + self.window_s * np.cumsum(gaps)[:n] / gaps.sum()
        else:
            due = np.zeros(n)
        tok_rng = _rng(self.seed, 4, b)
        return [Item(idx=b * n + i, due=float(due[i]),
                     prompt=tok_rng.integers(0, self.vocab, int(p_len[i])).tolist(),
                     max_new_tokens=int(o_len[i]))
                for i in range(n)]

    def __iter__(self) -> Iterator[Item]:
        b = 0
        while True:
            yield from self.block(b)
            b += 1
