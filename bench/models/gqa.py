"""The pre-norm decoder with grouped-query attention: InternLM2
(arXiv:2403.17297) and Granite-3.0 MoE.

Every layer is alike: RMSNorm, grouped-query attention with rotary
embeddings (the half-split ``rotate_half`` layout) and a causal softmax,
RMSNorm, then a SwiGLU MLP or, where the configuration has experts, a
router that sends each token to its top-k experts (softmax over all
experts, top-k, weights renormalised) computed densely for every expert
with no capacity. The layers form one scanned group (a layer period of 1).

The model module of the benchmark for this architecture: its sizes, the
program's ``ModelConfig``, the weight layout, the plain reference and the
operations and bytes a call needs (the rules are in ``flops.py``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

import weights
from flops import BYTES
from reference import HI, _fp8, _mm, _rmsnorm, _rope


@dataclasses.dataclass(frozen=True)
class Sizes:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    experts: int
    top_k: int
    tied: bool
    rope_theta: float
    norm_eps: float


def sizes(d: dict) -> Sizes:
    """The published ``config.json`` keys of ``d``, as they are run."""
    if d.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{d['name']}: only SwiGLU (silu) models are supported")
    heads = int(d["num_attention_heads"])
    return Sizes(
        name=d["name"],
        layers=int(d["num_hidden_layers"]),
        d_model=int(d["hidden_size"]),
        heads=heads,
        kv_heads=int(d.get("num_key_value_heads", heads)),
        head_dim=int(d.get("head_dim", int(d["hidden_size"]) // heads)),
        d_ff=int(d["intermediate_size"]),
        vocab=int(d["vocab_size"]),
        experts=int(d.get("num_local_experts", 0)),
        top_k=int(d.get("num_experts_per_tok", 0)),
        tied=bool(d.get("tie_word_embeddings", False)),
        rope_theta=float(d["rope_theta"]),
        norm_eps=float(d["rms_norm_eps"]))


def model_config(s: Sizes):
    """The program's ``ModelConfig`` for these sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=s.name, family="moe" if s.experts else "dense",
        num_layers=s.layers, d_model=s.d_model,
        vocab_size=s.vocab, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        rope_theta=s.rope_theta, d_ff=s.d_ff, mlp_activation="silu",
        num_experts=s.experts, num_experts_per_tok=s.top_k,
        norm_eps=s.norm_eps, dtype="bfloat16",
        tie_embeddings=s.tied)


# ----------------------------------------------------------------------
# weights

def shapes(s: Sizes) -> dict:
    """``{path: (shape, fan_in)}`` of every leaf (see ``weights.py``)."""
    L, D, F = s.layers, s.d_model, s.d_ff
    hq, hkv, hd = s.heads, s.kv_heads, s.head_dim
    out = {
        "embed/table": ((s.vocab, D), None),
        "final_norm/scale": ((D,), 0),
        "layers/norm1/scale": ((L, D), 0),
        "layers/norm2/scale": ((L, D), 0),
        "layers/attn/wq": ((L, D, hq, hd), D),
        "layers/attn/wk": ((L, D, hkv, hd), D),
        "layers/attn/wv": ((L, D, hkv, hd), D),
        "layers/attn/wo": ((L, hq, hd, D), hq * hd),
    }
    if s.experts:
        E = s.experts
        out["layers/moe/router"] = ((L, D, E), D)
        out["layers/moe/w_in"] = ((L, E, D, 2, F), D)
        out["layers/moe/w_out"] = ((L, E, F, D), F)
    else:
        out["layers/mlp/w_in"] = ((L, D, 2, F), D)
        out["layers/mlp/w_out"] = ((L, F, D), F)
    if not s.tied:
        out["lm_head/w"] = ((s.vocab, D), None)
    return out


def tree(flat: dict) -> dict:
    """The engine's layout: ``params["layers"]`` holds one slot, the layer
    period being 1."""
    t = weights.nest(flat)
    t["layers"] = (t["layers"],)
    return t


# ----------------------------------------------------------------------
# the plain reference

def _attention(s: Sizes, p: dict, h, quant: bool):
    n = h.shape[0]
    q = _rope(_mm("sd,dhk->shk", h, p["wq"], quant), s.rope_theta)
    k = _rope(_mm("sd,dhk->shk", h, p["wk"], quant), s.rope_theta)
    v = _mm("sd,dhk->shk", h, p["wv"], quant)
    rep = s.heads // s.kv_heads                   # query head j reads kv j // rep
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(s.head_dim))
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return _mm("shk,hkd->sd", o, p["wo"], quant)


def _swiglu(h, w_in, w_out, quant: bool, spec_in: str, spec_out: str):
    g = _mm(spec_in, h, w_in, quant)
    act = jax.nn.silu(g[..., 0, :]) * g[..., 1, :]
    return _mm(spec_out, act, w_out, quant)


def _moe(s: Sizes, p: dict, h, quant: bool):
    probs = jax.nn.softmax(_mm("sd,de->se", h, p["router"], quant), axis=-1)
    top, idx = lax.top_k(probs, s.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    y = _swiglu(h, p["w_in"], p["w_out"], quant,
                "sd,edtf->setf", "sef,efd->sed")         # every expert
    return jnp.einsum("sed,se->sd", y, gate, precision=HI)


def layer(s: Sizes, p: dict, x, quant: bool):
    """One decoder layer: attention, then the MLP or the experts that
    ``p`` holds."""
    h = _rmsnorm(x, p["norm1"]["scale"], s.norm_eps)
    x = x + _attention(s, p["attn"], h, quant)
    h = _rmsnorm(x, p["norm2"]["scale"], s.norm_eps)
    if "moe" in p:
        return x + _moe(s, p["moe"], h, quant)
    return x + _swiglu(h, p["mlp"]["w_in"], p["mlp"]["w_out"], quant,
                       "sd,dtf->stf", "sf,fd->sd")


def embed(params: dict, tokens, quant: bool):
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    return _fp8(x, (1,)) if quant else x


def logits(s: Sizes, params: dict, x, quant: bool):
    x = _rmsnorm(x, params["final_norm"]["scale"], s.norm_eps)
    head = params["embed"]["table"] if s.tied else params["lm_head"]["w"]
    return _mm("sd,vd->sv", x, head, quant)


def forward(s: Sizes, params: dict, tokens: jax.Array, quant: bool = False
            ) -> jax.Array:
    """Logits (S, V) in float32 of one sequence ``tokens`` (S,)."""
    x = embed(params, tokens, quant)
    x, _ = lax.scan(lambda x, p: (layer(s, p, x, quant), None), x,
                    params["layers"][0])
    return logits(s, params, x, quant)


# ----------------------------------------------------------------------
# operations and bytes

def _attn_params(s: Sizes) -> int:
    return s.d_model * s.head_dim * (2 * s.heads + 2 * s.kv_heads)


def _expert_params(s: Sizes) -> int:
    """One SwiGLU FFN (an expert, or the dense MLP)."""
    return 3 * s.d_model * s.d_ff


def _ffn_params(s: Sizes, experts) -> int:
    """The FFN of a layer with ``experts`` of its experts counted (the
    router always whole)."""
    if not s.experts:
        return _expert_params(s)
    return s.d_model * s.experts + experts * _expert_params(s)


def _count(s: Sizes, experts) -> int:
    per_layer = _attn_params(s) + _ffn_params(s, experts) + 2 * s.d_model
    table = s.vocab * s.d_model
    return table * (1 if s.tied else 2) + s.layers * per_layer + s.d_model


def param_count(s: Sizes) -> int:
    """Every parameter, as the program initialises them."""
    return _count(s, s.experts)


def active_param_count(s: Sizes) -> int:
    """Parameters a token touches (the router whole, top-k experts)."""
    return _count(s, s.top_k)


def _body_flops_per_token(s: Sizes) -> int:
    """Matrix products of the layers for one token, attention scores aside."""
    return 2 * s.layers * (_attn_params(s) + _ffn_params(s, s.top_k))


def _attn_flops(s: Sizes, positions: int) -> int:
    """Scores and weighted values of one query against ``positions`` keys."""
    return 4 * s.layers * s.heads * s.head_dim * positions


def _head_flops(s: Sizes) -> int:
    return 2 * s.d_model * s.vocab


def _experts_touched(s: Sizes, tokens: int) -> float:
    return s.experts * (1.0 - (1.0 - s.top_k / s.experts) ** tokens)


def _weight_bytes(s: Sizes, tokens: int) -> float:
    """Layer weights and the head, read once per call of ``tokens`` tokens."""
    ffn = _ffn_params(s, _experts_touched(s, tokens) if s.experts else 0)
    layers = s.layers * (_attn_params(s) + ffn + 2 * s.d_model)
    return BYTES * (layers + s.vocab * s.d_model + s.d_model)


def _kv_bytes(s: Sizes, positions: int) -> int:
    return BYTES * 2 * s.layers * s.kv_heads * s.head_dim * positions


def prefill_work(s: Sizes, n: int) -> tuple:
    """(FLOPs, bytes) of a prefill of ``n`` real prompt tokens."""
    flops = (n * _body_flops_per_token(s) + _attn_flops(s, n * (n + 1) // 2)
             + _head_flops(s))
    byts = (_weight_bytes(s, n) + BYTES * n * s.d_model     # embedding rows
            + _kv_bytes(s, n))                               # cache written
    return flops, byts


def decode_work(s: Sizes, lens: list) -> tuple:
    """(FLOPs, bytes) of one decode step over slots whose caches hold
    ``lens`` valid positions (this step's token included)."""
    t = len(lens)
    flops = (t * (_body_flops_per_token(s) + _head_flops(s))
             + _attn_flops(s, sum(lens)))
    byts = (_weight_bytes(s, t) + BYTES * t * s.d_model
            + _kv_bytes(s, sum(lens))                        # cache read
            + _kv_bytes(s, t))                               # new rows written
    return flops, byts
