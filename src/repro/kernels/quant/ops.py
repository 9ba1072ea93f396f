"""Public jit'd wrappers for the int8 quant kernels. On the CPU backend
they run the kernel body in interpret mode; on TPU the same call
compiles to Mosaic."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret
from repro.kernels.quant import kernel as K


@functools.partial(jax.jit, static_argnames=("block",))
def quantize_int8(x: jax.Array, block: int = 256):
    """Any-shape x -> (q (nblk, block) int8, scale (nblk,1) f32, meta)."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    blocks = flat.reshape(-1, block)
    q, s = K.quantize_int8_pallas(blocks, interpret=interpret())
    return q, s


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def dequantize_int8(q: jax.Array, scale: jax.Array, shape, dtype=jnp.float32):
    out = K.dequantize_int8_pallas(q, scale, dtype=dtype, interpret=interpret())
    n = 1
    for d in shape:
        n *= d
    return out.reshape(-1)[:n].reshape(shape)
