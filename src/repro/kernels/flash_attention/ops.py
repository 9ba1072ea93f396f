"""jit'd public wrapper: (B,S,H,hd) layout like the model zoo."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import interpret
from repro.kernels.flash_attention import kernel as K


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "q_block", "kv_block"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    q_block: int = 256, kv_block: int = 256) -> jax.Array:
    """q (B,S,Hq,hd); k/v (B,S,Hkv,hd) -> (B,S,Hq,hd)."""
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = K.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                 softcap=softcap, q_block=q_block,
                                 kv_block=kv_block, interpret=interpret())
    return out.swapaxes(1, 2)
