"""Seeded random weights, made on the device in one jitted call.

The leaves and the tree are the model module's: ``shapes`` gives each
leaf's path, shape and fan-in (0 marks a norm scale, ``None`` an
embedding table), ``tree`` nests them into the layout the engine takes
and the plain reference reads. The key is folded with each leaf's place in the
sorted order of the paths. Matrices are normal with standard
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


def nest(flat: dict) -> dict:
    """``{"a/b/c": leaf}`` as nested dicts ``{"a": {"b": {"c": leaf}}}``."""
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


@partial(jax.jit, static_argnums=(0, 2))
def _make(a: Arch, key: jax.Array, dtype) -> dict:
    flat = {}
    specs = a.module.shapes(a.sizes)
    for i, (path, (shape, fan_in)) in enumerate(sorted(specs.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if fan_in is None:
            w = 0.02 * z
        elif fan_in == 0:
            w = 1.0 + 0.1 * z
        else:
            w = z / math.sqrt(fan_in)
        flat[path] = w.astype(dtype)
    return a.module.tree(flat)


def make(a: Arch, seed: int, dtype=jnp.float32) -> dict:
    return _make(a, key_for(seed), jnp.dtype(dtype))
