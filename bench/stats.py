"""End-to-end numbers from the load generator's records."""
from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np

from loadgen import Record


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear interpolation), or None if empty."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def ttfts(records: List[Record]) -> List[float]:
    """First-token time minus the *scheduled* arrival, of every request
    that got a first token."""
    return [r.stamps[0] - r.due for r in records if r.stamps]


def token_gaps(records: List[Record]) -> List[float]:
    """All gaps between consecutive output tokens, pooled over requests."""
    out: List[float] = []
    for r in records:
        out.extend(np.diff(r.stamps).tolist())
    return out


def window_tokens(records: List[Record], window_s: float) -> int:
    """Prompt tokens whose prefill ended in the window (the prefill's
    token is stamped then) plus output tokens stamped in it."""
    n = 0
    for r in records:
        inside = sum(1 for t in r.stamps if t <= window_s)
        if inside:
            n += r.prompt_len + inside
    return n
