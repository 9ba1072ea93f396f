"""AdamW with sharded (ZeRO) state and optional int8 moments.

State sharding is inherited from the parameter sharding (fsdp x model):
because master params, m and v carry the same logical axes as the
weights, jit out_shardings partition them identically — ZeRO-3 without
bespoke machinery.

``moments="int8"`` stores m/v blockwise-int8 (paper theme: compress what
crosses/occupies a scarce resource — here HBM capacity). This is what
lets jamba-398B's optimizer fit the 16 GiB/chip budget (DESIGN.md §4);
the quantizer is the kernels/quant hot spot.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import (Quantized, dequantize_int8_blockwise,
                                    quantize_int8_blockwise)

PyTree = Any
_QBLOCK = 256


class AdamWState(NamedTuple):
    step: jax.Array
    m: PyTree                 # f32 arrays or Quantized pairs
    v: PyTree


def _is_quant(x):
    return isinstance(x, Quantized)


def _maybe_quant(x: jax.Array, mode: str):
    if mode == "int8":
        return quantize_int8_blockwise(x, _QBLOCK)
    return x


def _maybe_dequant(x, shape):
    if _is_quant(x):
        return dequantize_int8_blockwise(x, shape)
    return x


def adamw_init(params: PyTree, *, moments: str = "f32") -> AdamWState:
    def zero_like(p):
        z = jnp.zeros(p.shape, jnp.float32)
        return _maybe_quant(z, moments)
    m = jax.tree.map(zero_like, params)
    v = jax.tree.map(zero_like, params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=m, v=v)


def opt_logical(params_logical: PyTree, int8: bool) -> AdamWState:
    """Logical axes of the ``adamw_init`` state: m/v follow the params'
    axes; int8 moments are flat blocks sharded over every mesh axis."""
    def leaf(lg):
        if int8:
            return Quantized(q=("flat_shard", None), scale=("flat_shard",))
        return lg
    is_lg = lambda x: isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)
    moments = jax.tree.map(leaf, params_logical, is_leaf=is_lg)
    return AdamWState(step=(), m=moments, v=moments)


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_update(grads: PyTree, state: AdamWState, params: PyTree, *,
                 lr, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
                 weight_decay: float = 0.1, grad_clip: float = 1.0,
                 moments: str = "f32") -> Tuple[PyTree, AdamWState, dict]:
    step = state.step + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-9)) if grad_clip > 0 else 1.0

    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    flat_g, treedef = jax.tree.flatten(grads)
    flat_p = treedef.flatten_up_to(params)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)

    new_p, new_m, new_v = [], [], []
    for g, p, m, v in zip(flat_g, flat_p, flat_m, flat_v):
        g = g.astype(jnp.float32) * scale
        mf = _maybe_dequant(m, g.shape)
        vf = _maybe_dequant(v, g.shape)
        mf = b1 * mf + (1 - b1) * g
        vf = b2 * vf + (1 - b2) * g * g
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
        pf = p.astype(jnp.float32)
        if p.ndim >= 2:                       # decay matrices only
            update = update + weight_decay * pf
        pf = pf - lr * update
        new_p.append(pf.astype(p.dtype))
        new_m.append(_maybe_quant(mf, "int8") if _is_quant(m) else mf)
        new_v.append(_maybe_quant(vf, "int8") if _is_quant(v) else vf)

    params2 = jax.tree.unflatten(treedef, new_p)
    state2 = AdamWState(step=step,
                        m=jax.tree.unflatten(treedef, new_m),
                        v=jax.tree.unflatten(treedef, new_v))
    return params2, state2, {"grad_norm": gnorm}
