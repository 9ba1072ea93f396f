"""The plain reference: a float32 forward pass over a whole sequence,
and the readings of it that decide ``correct``.

Each architecture's pass is its model module's ``forward`` (``models/``),
written from the published descriptions, independent of the program: it
imports nothing from ``repro``. No cache, no bucketing, every matrix
product at ``Precision.HIGHEST``. This module holds the primitives the
modules share.

``quant=True`` is the control: the same pass with both operands of every
matrix product rounded to float8 (e4m3, one scale per weight matrix and
per activation row), the step below the bfloat16 the configuration
states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from arch import Arch

HI = lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x: jax.Array, axes) -> jax.Array:
    """Round to e4m3 with one absmax scale over ``axes``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(spec: str, x: jax.Array, w: jax.Array, quant: bool) -> jax.Array:
    """``einsum(spec, x, w)``: ``x`` has tokens on its first axis."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = _fp8(x, tuple(range(1, x.ndim)))
        w = _fp8(w, tuple(range(w.ndim)))
    return jnp.einsum(spec, x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, H, hd); rotates the two halves of each head."""
    s, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnums=(0,))
def gaps(a: Arch, params: dict, tokens: jax.Array, targets: jax.Array):
    """For each position ``p`` of ``tokens``: how far the reference's logit
    of ``targets[p]`` lies below its best logit, and its best token."""
    logits = a.module.forward(a.sizes, params, tokens)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


@partial(jax.jit, static_argnums=(0,))
def control_tokens(a: Arch, params: dict, tokens: jax.Array) -> jax.Array:
    """The token the float8 control puts first at each position."""
    return jnp.argmax(a.module.forward(a.sizes, params, tokens, quant=True),
                      axis=-1)
