"""A toy architecture for the harness's tests, added as a model module
and nothing else: dense SwiGLU layers and MoE layers alternate
(Qwen2-MoE's ``decoder_sparse_step`` 2; the program runs it as
``moe_period`` 2), so the engine's layer tree holds two slots a group
where ``gqa`` has one. Each layer's equations are ``gqa``'s."""
from __future__ import annotations

import dataclasses

from jax import lax

import arch
import weights

gqa = arch.load_model("gqa")
STEP = 2        # every second layer holds the experts


def sizes(d: dict):
    s = gqa.sizes(d)
    if int(d.get("decoder_sparse_step", 1)) != STEP or not s.experts \
            or s.layers % STEP:
        raise ValueError(f"{d['name']}: runs decoder_sparse_step {STEP} "
                         f"with experts, over an even number of layers")
    return s


def model_config(s):
    return dataclasses.replace(gqa.model_config(s), moe_period=STEP)


def _halves(s):
    """The dense layers and the MoE layers, each as a ``gqa`` model."""
    moe = dataclasses.replace(s, layers=s.layers // STEP)
    return dataclasses.replace(moe, experts=0, top_k=0), moe


def shapes(s) -> dict:
    out = {}
    for slot, half in enumerate(_halves(s)):
        for path, spec in gqa.shapes(half).items():
            out[path.replace("layers/", f"layers/{slot}/", 1)] = spec
    return out


def tree(flat: dict) -> dict:
    t = weights.nest(flat)
    t["layers"] = (t["layers"]["0"], t["layers"]["1"])
    return t


def forward(s, params: dict, tokens, quant: bool = False):
    dense, moe = _halves(s)

    def group(x, p):
        x = gqa.layer(dense, p[0], x, quant)
        return gqa.layer(moe, p[1], x, quant), None

    x, _ = lax.scan(group, gqa.embed(params, tokens, quant), params["layers"])
    return gqa.logits(s, params, x, quant)


def _apart(count, s, *args):
    """``count`` of the dense layers plus that of the MoE layers, less
    what both count outside the layers (embedding, head, final norm)."""
    dense, moe = _halves(s)
    parts = [count(x, *args) for x in
             (dense, moe, dataclasses.replace(dense, layers=0))]
    if isinstance(parts[0], tuple):
        return tuple(d + m - n for d, m, n in zip(*parts))
    return parts[0] + parts[1] - parts[2]


def param_count(s) -> int:
    return _apart(gqa.param_count, s)


def active_param_count(s) -> int:
    return _apart(gqa.active_param_count, s)


def prefill_work(s, n: int) -> tuple:
    return _apart(gqa.prefill_work, s, n)


def decode_work(s, lens: list) -> tuple:
    return _apart(gqa.decode_work, s, lens)
