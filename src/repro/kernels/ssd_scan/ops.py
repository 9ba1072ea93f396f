"""jit'd wrapper for the SSD scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret
from repro.kernels.ssd_scan import kernel as K


@functools.partial(jax.jit, static_argnames=("chunk", "head_tile"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, C: jax.Array, *, chunk: int = 128,
             head_tile: int = 8):
    """Mamba2 SSD: returns (y (B,S,H,P), final_state (B,H,P,N))."""
    return K.ssd_scan_pallas(x, dt, A, Bm, C, chunk=chunk,
                             head_tile=head_tile, interpret=interpret())
