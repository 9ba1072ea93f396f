"""jit'd wrapper with the model-zoo (B,1,Hq,hd) / (B,S,Hkv,hd) layout."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import interpret
from repro.kernels.decode_attention import kernel as K


@functools.partial(jax.jit, static_argnames=("window", "softcap", "kv_block"))
def decode_attention_kernel(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                            cache_len, *, window: Optional[int] = None,
                            softcap: Optional[float] = None,
                            kv_block: int = 256) -> jax.Array:
    """q (B,1,Hq,hd); caches (B,S,Hkv,hd); cache_len scalar.
    Returns (B,1,Hq,hd)."""
    b, _, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    qk = q[:, 0].reshape(b, hkv, g, d)                 # (B,Hkv,G,hd)
    kc = k_cache.swapaxes(1, 2)                        # (B,Hkv,S,hd)
    vc = v_cache.swapaxes(1, 2)
    out = K.decode_attention_bhgd(qk, kc, vc, cache_len, window=window,
                                  softcap=softcap, kv_block=kv_block,
                                  interpret=interpret())
    return out.reshape(b, hq, d)[:, None]
