"""Operations and bytes that the algorithm needs, computed from shapes
by each architecture's model module (``prefill_work``, ``decode_work``).

The same count holds whatever implements the step, so a share of the
peak can only rise by doing the same work faster:

- parameters are counted at the configuration's dtype (bfloat16, 2 B);
- real prompt tokens, never padding;
- only the top-k experts' operations; an expert's weights are read once
  if any token of the call routes to it (expected count under uniform
  routing);
- the cache only for valid positions, as the architecture stores them
  (keys and values, or whatever the module counts);
- the embedding only for the rows looked up; the prefill's logits only
  for its last position (the only one it samples).
"""
from __future__ import annotations

from typing import Iterable

from arch import Arch

BYTES = 2          # bfloat16


def prefill(a: Arch, n: int) -> tuple:
    """(FLOPs, bytes) of a prefill of ``n`` real prompt tokens."""
    return a.module.prefill_work(a.sizes, n)


def decode(a: Arch, lens: Iterable[int]) -> tuple:
    """(FLOPs, bytes) of one decode step over slots whose caches hold
    ``lens`` valid positions (this step's token included)."""
    lens = list(lens)
    if not lens:
        return 0, 0
    return a.module.decode_work(a.sizes, lens)
