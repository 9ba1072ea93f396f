"""The plain reference: a float32 forward pass over a whole sequence.

Written from the published descriptions (InternLM2, arXiv:2403.17297;
Granite-3.0 MoE), independent of the program: it imports nothing from
``repro``. Pre-norm decoder layers: RMSNorm, grouped-query attention
with rotary embeddings (the half-split ``rotate_half`` layout), causal
softmax, then a SwiGLU MLP, or a router that sends each token to its
top-k experts (softmax over all experts, top-k, weights renormalised)
computed densely for every expert with no capacity. No cache, no
bucketing, every matrix product at ``Precision.HIGHEST``.

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


def _attention(a: Arch, p: dict, h, quant: bool):
    s = h.shape[0]
    q = _rope(_mm("sd,dhk->shk", h, p["wq"], quant), a.rope_theta)
    k = _rope(_mm("sd,dhk->shk", h, p["wk"], quant), a.rope_theta)
    v = _mm("sd,dhk->shk", h, p["wv"], quant)
    rep = a.heads // a.kv_heads                   # query head j reads kv j // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(a.head_dim))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return _mm("shk,hkd->sd", o, p["wo"], quant)


def _swiglu(h, w_in, w_out, quant: bool, spec_in: str, spec_out: str):
    g = _mm(spec_in, h, w_in, quant)
    act = jax.nn.silu(g[..., 0, :]) * g[..., 1, :]
    return _mm(spec_out, act, w_out, quant)


def _moe(a: Arch, p: dict, h, quant: bool):
    probs = jax.nn.softmax(_mm("sd,de->se", h, p["router"], quant), axis=-1)
    top, idx = lax.top_k(probs, a.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    y = _swiglu(h, p["w_in"], p["w_out"], quant,
                "sd,edtf->setf", "sef,efd->sed")          # every expert
    return jnp.einsum("sed,se->sd", y, gate, precision=HI)


def forward(a: Arch, params: dict, tokens: jax.Array, quant: bool = False
            ) -> jax.Array:
    """Logits (S, V) in float32 of one sequence ``tokens`` (S,)."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    if quant:
        x = _fp8(x, (1,))

    def layer(x, p):
        h = _rmsnorm(x, p["norm1"]["scale"], a.norm_eps)
        x = x + _attention(a, p["attn"], h, quant)
        h = _rmsnorm(x, p["norm2"]["scale"], a.norm_eps)
        if a.experts:
            x = x + _moe(a, p["moe"], h, quant)
        else:
            x = x + _swiglu(h, p["mlp"]["w_in"], p["mlp"]["w_out"], quant,
                            "sd,dtf->stf", "sf,fd->sd")
        return x, None

    x, _ = lax.scan(layer, x, params["layers"][0])
    x = _rmsnorm(x, params["final_norm"]["scale"], a.norm_eps)
    head = params["embed"]["table"] if a.tied else params["lm_head"]["w"]
    return _mm("sd,vd->sv", x, head, quant)


@partial(jax.jit, static_argnums=(0,))
def gaps(a: Arch, params: dict, tokens: jax.Array, targets: jax.Array):
    """For each position ``p`` of ``tokens``: how far the reference's logit
    of ``targets[p]`` lies below its best logit, and its best token."""
    logits = forward(a, params, tokens)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(logits, axis=-1)


@partial(jax.jit, static_argnums=(0,))
def control_tokens(a: Arch, params: dict, tokens: jax.Array) -> jax.Array:
    """The token the float8 control puts first at each position."""
    return jnp.argmax(forward(a, params, tokens, quant=True), axis=-1)
