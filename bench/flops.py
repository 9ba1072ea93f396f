"""Operations and bytes that the algorithm needs, computed from shapes.

The same count holds whatever implements the step, so a share of the
peak can only rise by doing the same work faster:

- parameters are counted at the configuration's dtype (bfloat16, 2 B);
- real prompt tokens, never padding;
- only the top-k experts' operations; an expert's weights are read once
  if any token of the call routes to it (expected count under uniform
  routing);
- keys and values only for valid cached positions;
- the embedding only for the rows looked up; the prefill's logits only
  for its last position (the only one it samples).
"""
from __future__ import annotations

from typing import Iterable

from arch import Arch

BYTES = 2          # bfloat16


def attn_params(a: Arch) -> int:
    return a.d_model * a.head_dim * (2 * a.heads + 2 * a.kv_heads)


def expert_params(a: Arch) -> int:
    """One SwiGLU FFN (an expert, or the dense MLP)."""
    return 3 * a.d_model * a.d_ff


def param_count(a: Arch) -> int:
    """Every parameter, as the program initialises them."""
    ffn = (a.d_model * a.experts + a.experts * expert_params(a)
           if a.experts else expert_params(a))
    layer = attn_params(a) + ffn + 2 * a.d_model
    table = a.vocab * a.d_model
    return table * (1 if a.tied else 2) + a.layers * layer + a.d_model


def active_param_count(a: Arch) -> int:
    """Parameters a token touches (the router whole, top-k experts)."""
    ffn = (a.d_model * a.experts + a.top_k * expert_params(a)
           if a.experts else expert_params(a))
    layer = attn_params(a) + ffn + 2 * a.d_model
    table = a.vocab * a.d_model
    return table * (1 if a.tied else 2) + a.layers * layer + a.d_model


def _body_flops_per_token(a: Arch) -> int:
    """Matrix products of the layers for one token, attention scores aside."""
    ffn = (a.d_model * a.experts + a.top_k * expert_params(a)
           if a.experts else expert_params(a))
    return 2 * a.layers * (attn_params(a) + ffn)


def _attn_flops(a: Arch, positions: int) -> int:
    """Scores and weighted values of one query against ``positions`` keys."""
    return 4 * a.layers * a.heads * a.head_dim * positions


def _head_flops(a: Arch) -> int:
    return 2 * a.d_model * a.vocab


def _experts_touched(a: Arch, tokens: int) -> float:
    return a.experts * (1.0 - (1.0 - a.top_k / a.experts) ** tokens)


def _weight_bytes(a: Arch, tokens: int) -> float:
    """Layer weights and the head, read once per call of ``tokens`` tokens."""
    if a.experts:
        ffn = a.d_model * a.experts + _experts_touched(a, tokens) * expert_params(a)
    else:
        ffn = expert_params(a)
    layers = a.layers * (attn_params(a) + ffn + 2 * a.d_model)
    return BYTES * (layers + a.vocab * a.d_model + a.d_model)


def _kv_bytes(a: Arch, positions: int) -> int:
    return BYTES * 2 * a.layers * a.kv_heads * a.head_dim * positions


def prefill(a: Arch, n: int) -> tuple:
    """(FLOPs, bytes) of a prefill of ``n`` real prompt tokens."""
    flops = (n * _body_flops_per_token(a) + _attn_flops(a, n * (n + 1) // 2)
             + _head_flops(a))
    byts = (_weight_bytes(a, n) + BYTES * n * a.d_model     # embedding rows
            + _kv_bytes(a, n))                               # cache written
    return flops, byts


def decode(a: Arch, lens: Iterable[int]) -> tuple:
    """(FLOPs, bytes) of one decode step over slots whose caches hold
    ``lens`` valid positions (this step's token included)."""
    lens = list(lens)
    if not lens:
        return 0, 0
    t = len(lens)
    flops = (t * (_body_flops_per_token(a) + _head_flops(a))
             + _attn_flops(a, sum(lens)))
    byts = (_weight_bytes(a, t) + BYTES * t * a.d_model
            + _kv_bytes(a, sum(lens))                        # cache read
            + _kv_bytes(a, t))                               # new rows written
    return flops, byts
