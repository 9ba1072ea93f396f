"""One general traffic generator, driven by the data files in ``traffic/``.

A mix file gives the arrival process and two clamped-lognormal length
distributions (the ``LengthSpec`` arithmetic of ``repro.scale.arrivals``:
lognormal with a median and a shape ``sigma``, rounded and clamped to
``[low, high]``).

Every seed gets the same requests block by block, in another order
inside each block of ``block`` requests: lengths are the distribution's
stratified quantiles at ``(i + 0.5) / n``, laid out in one fixed order
(the same for every seed), and the seed permutes each block. So the work
of a run, and how it spreads over the window, does not change with the
seed; runs with different seeds differ by order inside a block, token ids
and weights. With ``block`` 1 every seed sends the same schedule: at an
open-loop rate near the knee the order of a few tens of requests decides
whether a queue forms, so a seeded order would change the work. Two
arrival processes:

- ``poisson``: open loop at a fixed rate (the cell's ``rate_per_s``).
  The ``n = round(rate * seconds)`` gaps are the exponential quantiles,
  laid out and permuted as the lengths are, and scaled so that the last
  request is due inside the window.
- ``backlog``: an offline batch. Requests are handed out in order while
  the load generator keeps the engine's queue full; ``due`` is the time
  of hand-out. Each block holds every one of its ``block`` quantiles.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: the seed of the fixed layout of lengths and gaps shared by every run
LAYOUT_SEED = 0


@dataclasses.dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    low: int
    high: int

    def quantiles(self, n: int) -> np.ndarray:
        """The ``n`` stratified quantiles of the clamped lognormal."""
        nd = NormalDist()
        mu = math.log(self.median)
        vals = [round(math.exp(mu + self.sigma * nd.inv_cdf((i + 0.5) / n)))
                for i in range(n)]
        return np.clip(np.asarray(vals, np.int64), self.low, self.high)


@dataclasses.dataclass
class Item:
    """One request of the mix: prompt ids, output length and due time
    (seconds from the start of the window; ``None`` for a backlog)."""
    rid: int
    prompt: np.ndarray
    max_new: int
    due: Optional[float]


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    arrivals: str
    prompt: Lengths
    output: Lengths
    block: int = 64

    @classmethod
    def from_dict(cls, d: dict) -> "Mix":
        if d["arrivals"] not in ("poisson", "backlog"):
            raise ValueError(f"unknown arrivals {d['arrivals']!r}")
        return cls(name=d["name"], arrivals=d["arrivals"],
                   prompt=Lengths(**d["prompt"]), output=Lengths(**d["output"]),
                   block=int(d.get("block", 64)))

    def max_positions(self) -> int:
        return self.prompt.high + self.output.high

    def prompt_lengths(self) -> List[int]:
        """Every prompt length this mix can send (for warm-up)."""
        return list(range(self.prompt.low, self.prompt.high + 1))


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole-number seed, 64-bit and negative ones too."""
    return np.random.default_rng(int(seed) % (1 << 64))


def _block_order(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """Indices ``0..n-1`` with each run of ``block`` in a seeded order."""
    return np.concatenate([lo + rng.permutation(min(block, n - lo))
                           for lo in range(0, n, block)])


def _fixed(values: np.ndarray, k: int) -> np.ndarray:
    """``values`` in the ``k``-th fixed layout shared by every seed."""
    layout = np.random.default_rng([LAYOUT_SEED, k])
    return values[layout.permutation(len(values))]


def generate(mix: Mix, *, seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None, backlog_size: int = 4096
             ) -> List[Item]:
    """The requests of one run. ``rate`` (requests/s, the mean over the
    window) is needed for a Poisson mix; a backlog hands out up to
    ``backlog_size`` requests."""
    rng = rng_for(seed)
    if mix.arrivals == "poisson":
        if not rate or rate <= 0:
            raise ValueError("a poisson mix needs the cell's rate_per_s > 0")
        n = max(1, int(round(rate * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = _fixed(-np.log1p(-u), 0)[_block_order(n, mix.block, rng)]
        # the last request falls half a mean gap before the close
        due = np.cumsum(gaps) * (seconds * (1.0 - 0.5 / n) / gaps.sum())
        plen, olen = _fixed(mix.prompt.quantiles(n), 1), _fixed(mix.output.quantiles(n), 2)
    else:
        n = backlog_size
        due = [None] * n
        reps = -(-n // mix.block)
        plen = np.tile(mix.prompt.quantiles(mix.block), reps)[:n]
        olen = np.tile(_fixed(mix.output.quantiles(mix.block), 2), reps)[:n]
    # a request keeps its prompt and output lengths; the seed orders blocks
    order = _block_order(n, mix.block, rng)
    plen, olen = plen[order], olen[order]
    ids = rng.integers(0, vocab, size=int(plen.sum()), dtype=np.int32)
    cuts = np.cumsum(plen)[:-1]
    return [Item(rid=i, prompt=p, max_new=int(o),
                 due=None if d is None else float(d))
            for i, (p, o, d) in enumerate(zip(np.split(ids, cuts), olen, due))]
