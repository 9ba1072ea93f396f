"""Seeded random weights, made on the device in one jitted call.

The tree is the layout the engine takes (``params["layers"]`` is a tuple
with one slot per layer period; each leaf leads with the layer count)
and the plain reference reads. Matrices are normal with standard
deviation ``1 / sqrt(fan_in)``, the embedding (and head) 0.02, and the
norm scales ``1 + 0.1 * normal`` so that a norm whose scale is dropped
shows in the logits.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from arch import Arch


def key_for(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed: both 32-bit halves count."""
    s = int(seed) % (1 << 64)
    key = jax.random.key(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def shapes(a: Arch) -> dict:
    """``{path: (shape, fan_in)}`` of every leaf; ``fan_in`` 0 marks a norm
    scale, ``None`` an embedding table."""
    L, D, F = a.layers, a.d_model, a.d_ff
    hq, hkv, hd = a.heads, a.kv_heads, a.head_dim
    out = {
        "embed/table": ((a.vocab, D), None),
        "final_norm/scale": ((D,), 0),
        "layers/norm1/scale": ((L, D), 0),
        "layers/norm2/scale": ((L, D), 0),
        "layers/attn/wq": ((L, D, hq, hd), D),
        "layers/attn/wk": ((L, D, hkv, hd), D),
        "layers/attn/wv": ((L, D, hkv, hd), D),
        "layers/attn/wo": ((L, hq, hd, D), hq * hd),
    }
    if a.experts:
        E = a.experts
        out["layers/moe/router"] = ((L, D, E), D)
        out["layers/moe/w_in"] = ((L, E, D, 2, F), D)
        out["layers/moe/w_out"] = ((L, E, F, D), F)
    else:
        out["layers/mlp/w_in"] = ((L, D, 2, F), D)
        out["layers/mlp/w_out"] = ((L, F, D), F)
    if not a.tied:
        out["lm_head/w"] = ((a.vocab, D), None)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    tree["layers"] = (tree["layers"],)      # one slot: the layer period is 1
    return tree


@partial(jax.jit, static_argnums=(0, 2))
def _make(a: Arch, key: jax.Array, dtype) -> dict:
    flat = {}
    specs = shapes(a)
    for i, (path, (shape, fan_in)) in enumerate(sorted(specs.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in is None:
            w = 0.02 * z
        elif fan_in == 0:
            w = 1.0 + 0.1 * z
        else:
            w = z / math.sqrt(fan_in)
        flat[path] = w.astype(dtype)
    return _nest(flat)


def make(a: Arch, seed: int, dtype=jnp.float32) -> dict:
    return _make(a, key_for(seed), jnp.dtype(dtype))
