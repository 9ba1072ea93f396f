"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest, is run through the plain
reference: each prompt followed by its served tokens. The number
compared is the widest gap by which a served token's reference logit
lies below the reference's best logit at that position. Tokens are
greedy, so a program that computes what the reference computes serves
tokens whose gap is rounding; the control (the reference in float8)
puts other tokens first.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference
from arch import Arch
from traffic import rng_for

SAMPLE_TOKENS = 384      # served tokens the sample reaches, at least
SAMPLE_ROWS = 32         # and at most this many requests


def sample(done: Sequence[Tuple[np.ndarray, List[int]]], seed: int
           ) -> List[Tuple[np.ndarray, List[int]]]:
    """Finished ``(prompt, served)`` pairs to compare: the longest served
    answer first, then others in seeded order up to ``SAMPLE_TOKENS``."""
    if not done:
        return []
    longest = max(range(len(done)), key=lambda i: (len(done[i][1]), -i))
    rest = [i for i in rng_for(seed + 1).permutation(len(done)) if i != longest]
    picked, served = [], 0
    for i in [longest] + rest:
        if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_ROWS:
            break
        picked.append(done[i])
        served += len(done[i][1])
    return picked


def _row(prompt: np.ndarray, served: List[int], max_len: int):
    """The padded sequence fed to the reference and the positions at which
    each served token was chosen."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    if len(seq) > max_len:
        raise ValueError(f"sequence of {len(seq)} > {max_len} positions")
    padded = np.zeros((max_len,), np.int32)
    padded[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return padded, pos


def widest_gap(a: Arch, params: dict, rows, *, control: bool = False
               ) -> Tuple[float, int]:
    """(widest gap over every served token of ``rows``, tokens compared).
    With ``control`` the tokens compared are the float8 control's first
    choices at the same positions, not the served ones."""
    worst, n = 0.0, 0
    for prompt, served in rows:
        padded, pos = _row(prompt, served, a.max_len)
        toks = jnp.asarray(padded)
        if control:
            targets = reference.control_tokens(a, params, toks)
        else:
            t = np.zeros((a.max_len,), np.int32)
            t[pos] = np.asarray(served, np.int32)
            targets = jnp.asarray(t)
        g, _ = reference.gaps(a, params, toks, targets)
        g = np.asarray(jax.device_get(g))[pos]
        worst = max(worst, float(g.max()))
        n += len(pos)
    return worst, n
