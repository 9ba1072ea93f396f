"""Unified LM: dense / MoE / SSM / hybrid / VLM / audio backbones.

One forward covers train & prefill; ``decode_step`` covers single-token
serving against a cache. Layers run under ``lax.scan`` over period-groups
(HLO stays O(1) in depth) with optional remat.

Named scopes (``jax.named_scope``, metadata only) mark each layer's parts
in the compiled ops' names: ``embed``, ``attn`` or ``ssm`` (the mixer with
its cache write), ``ffn`` (dense MLP or MoE) and ``lm_head``.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import activation_fn, mlp, rmsnorm, rope, row_parallel
from repro.models.moe import moe_ffn
from repro.models.params import layer_period, num_groups, slot_kind
from repro.models import precision
from repro.parallel.sharding import constrain

PyTree = Any


class ForwardResult(NamedTuple):
    hidden: jax.Array          # (B, S, D)
    aux_loss: jax.Array        # MoE load-balance loss (0 for non-MoE)


# ----------------------------------------------------------------------
# embeddings
# ----------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
                 frontend_embeds: Optional[jax.Array] = None) -> jax.Array:
    table = params["embed"]["table"]
    if cfg.num_codebooks > 1:
        # tokens (B, S, C): sum of per-codebook embeddings
        parts = [jnp.take(table[c], tokens[..., c], axis=0)
                 for c in range(cfg.num_codebooks)]
        x = sum(parts)
    else:
        x = jnp.take(table, tokens, axis=0)
    x = x.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    return constrain(x, "batch", "seq", "embed")


# ----------------------------------------------------------------------
# single layer
# ----------------------------------------------------------------------

def _attention_mixer(cfg: ModelConfig, kind: dict, p: dict, x: jax.Array, *,
                     positions, impl: str, cache=None, pos=None,
                     cp_axis=None, mesh=None):
    window = cfg.window_size if kind["local"] else None
    xc = x.astype(jnp.bfloat16)
    q = jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(jnp.bfloat16))
    k = jnp.einsum("bsd,dhk->bshk", xc, p["wk"].astype(jnp.bfloat16))
    v = jnp.einsum("bsd,dhk->bshk", xc, p["wv"].astype(jnp.bfloat16))
    q = constrain(q, "batch", "seq", "act_heads", None)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    new_cache = None
    if cache is None:
        out = attn_mod.attention(q, k, v, causal=True, window=window,
                                 softcap=cfg.attn_logit_softcap, impl=impl)
    else:
        if pos.ndim == 0:      # aligned batch: one shared position
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
        else:                  # continuous batching: per-row positions
            bidx = jnp.arange(k.shape[0])
            k_cache = cache["k"].at[bidx, pos].set(k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[bidx, pos].set(v[:, 0].astype(cache["v"].dtype))
        new_cache = {"k": k_cache, "v": v_cache}
        cache_len = pos + 1
        if cp_axis:
            out = attn_mod.decode_attention_context_parallel(
                q, k_cache, v_cache, cache_len, mesh=mesh, axis=cp_axis,
                window=window, softcap=cfg.attn_logit_softcap)
        else:
            out = attn_mod.decode_attention(
                q, k_cache, v_cache, cache_len,
                window=window, softcap=cfg.attn_logit_softcap)
    out = constrain(out, "batch", "seq", "act_heads", None)
    y = row_parallel("bshk,hkd->bsd", out.astype(jnp.bfloat16),
                     p["wo"].astype(jnp.bfloat16), x_shard_dim=2, w_shard_dim=0)
    return y.astype(x.dtype), new_cache


def _ssm_mixer(cfg: ModelConfig, p: dict, x: jax.Array, *,
               cache=None, impl: str = "auto"):
    """Mamba2 block. cache: {"h": (B,H,P,N), "conv_x/b/c": states} for decode."""
    b, s, d = x.shape
    din, n, h_heads, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    xc = x.astype(jnp.bfloat16)
    xz = jnp.einsum("bsd,dti->bsti", xc, p["w_xz"].astype(jnp.bfloat16))
    x_in, z = xz[..., 0, :], xz[..., 1, :]                  # (B,S,din)
    x_in = constrain(x_in, "batch", "seq", "act_mlp")
    bc = jnp.einsum("bsd,dtn->bstn", xc, p["w_bc"].astype(jnp.bfloat16))
    b_in, c_in = bc[..., 0, :], bc[..., 1, :]               # (B,S,N)
    dt_raw = jnp.einsum("bsd,dh->bsh", xc, p["w_dt"].astype(jnp.bfloat16))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    new_cache = None
    if cache is None:
        x_conv, _ = ssm_mod.causal_conv(x_in, p["conv_x"].astype(x_in.dtype))
        b_conv, _ = ssm_mod.causal_conv(b_in, p["conv_b"].astype(b_in.dtype))
        c_conv, _ = ssm_mod.causal_conv(c_in, p["conv_c"].astype(c_in.dtype))
        x_conv, b_conv, c_conv = map(jax.nn.silu, (x_conv, b_conv, c_conv))
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        xh = x_conv.reshape(b, s, h_heads, hd)
        if impl == "pallas" and s % cfg.ssm_chunk == 0:
            from repro.kernels.ssd_scan import ops as ssd_ops
            y, _ = ssd_ops.ssd_scan(xh, dt, A, b_conv, c_conv,
                                    chunk=cfg.ssm_chunk)
        else:
            y, _ = ssm_mod.ssd_chunked(xh, dt, A, b_conv, c_conv, chunk=cfg.ssm_chunk)
        y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
        y = y.reshape(b, s, din)
    else:
        x_c, cs_x = ssm_mod.causal_conv_step(x_in[:, 0], p["conv_x"].astype(x_in.dtype), cache["conv_x"])
        b_c, cs_b = ssm_mod.causal_conv_step(b_in[:, 0], p["conv_b"].astype(b_in.dtype), cache["conv_b"])
        c_c, cs_c = ssm_mod.causal_conv_step(c_in[:, 0], p["conv_c"].astype(c_in.dtype), cache["conv_c"])
        x_c, b_c, c_c = map(jax.nn.silu, (x_c, b_c, c_c))
        dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        xh = x_c.reshape(b, h_heads, hd)
        yt, hnew = ssm_mod.ssd_decode_step(xh, dt, A, b_c, c_c, cache["h"])
        yt = yt + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, :, None]
        y = yt.reshape(b, 1, din)
        new_cache = {"h": hnew, "conv_x": cs_x, "conv_b": cs_b, "conv_c": cs_c}

    # gated RMSNorm (mamba2)
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    out = row_parallel("bsi,id->bsd", y.astype(jnp.bfloat16),
                       p["out"].astype(jnp.bfloat16), x_shard_dim=2, w_shard_dim=0)
    return out.astype(x.dtype), new_cache


def apply_layer(cfg: ModelConfig, slot: int, p: dict, x: jax.Array, *,
                positions, impl: str = "auto", cache=None, pos=None,
                cp_axis=None, mesh=None,
                capacity_factor=1.25):
    kind = slot_kind(cfg, slot)
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, p["norm1"]["scale"], cfg.norm_eps)
    if kind["kind"] == "attn":
        with jax.named_scope("attn"):
            mix, new_cache = _attention_mixer(
                cfg, kind, p["attn"], h, positions=positions, impl=impl,
                cache=cache, pos=pos, cp_axis=cp_axis, mesh=mesh)
    else:
        with jax.named_scope("ssm"):
            mix, new_cache = _ssm_mixer(cfg, p["ssm"], h, cache=cache, impl=impl)
    x = x + mix
    if kind["has_ffn"]:
        h = rmsnorm(x, p["norm2"]["scale"], cfg.norm_eps)
        with jax.named_scope("ffn"):
            if kind["moe"]:
                y, metrics = moe_ffn(h, p["moe"], num_experts=cfg.num_experts,
                                     top_k=cfg.num_experts_per_tok,
                                     activation=activation_fn(cfg.mlp_activation),
                                     capacity_factor=capacity_factor)
                aux = aux + metrics.aux_loss
            else:
                y = mlp(h, p["mlp"], activation_fn(cfg.mlp_activation))
        x = x + y
    x = constrain(x, "batch", "seq", "embed")
    return x, new_cache, aux


# ----------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------

def forward(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None, *,
            impl: str = "auto", remat: str = "minimal",
            capacity_factor: float = 1.25, unroll: int = 1) -> ForwardResult:
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    s_total = x.shape[1]
    positions = jnp.arange(s_total)
    period = layer_period(cfg)

    def group_body(carry, group_params):
        x, aux = carry
        for slot in range(period):
            x, _, a = apply_layer(cfg, slot, group_params[slot], x,
                                  positions=positions, impl=impl,
                                  capacity_factor=capacity_factor)
            aux = aux + a
        return (x, aux), None

    if remat == "full":
        group_body = jax.checkpoint(group_body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "minimal":
        group_body = jax.checkpoint(
            group_body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    (x, aux), _ = jax.lax.scan(group_body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"], unroll=unroll)
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    return ForwardResult(hidden=x, aux_loss=aux)


# ----------------------------------------------------------------------
# logits + loss (chunked, vocab-parallel)
# ----------------------------------------------------------------------

def _head_table(cfg: ModelConfig, params: PyTree) -> jax.Array:
    return (params["embed"]["table"] if cfg.tie_embeddings
            else params["lm_head"]["w"])


def logits_for(cfg: ModelConfig, params: PyTree, hidden: jax.Array) -> jax.Array:
    """Full logits — small vocab / decode only."""
    table = _head_table(cfg, params).astype(jnp.bfloat16)
    h = hidden.astype(jnp.bfloat16)
    if cfg.num_codebooks > 1:
        logits = jnp.einsum("bsd,cvd->bscv", h, table)
    else:
        logits = jnp.einsum("bsd,vd->bsv", h, table)
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
    return constrain(logits, "batch", "seq", "act_vocab") if cfg.num_codebooks == 1 \
        else constrain(logits, "batch", "seq", None, "act_vocab")


def cross_entropy(cfg: ModelConfig, params: PyTree, hidden: jax.Array,
                  labels: jax.Array, loss_mask: jax.Array, *,
                  chunk: int = 512, z_loss: float = 1e-4):
    """Chunked vocab-parallel CE: never materializes (B, S, V) at once.

    labels (B,S) int32 [(B,S,C) for codebooks]; loss_mask (B,S) f32.
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk
    table = _head_table(cfg, params).astype(jnp.bfloat16)

    hs = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)      # (nc,B,C,D)
    if cfg.num_codebooks > 1:
        ls = labels.reshape(b, nc, chunk, cfg.num_codebooks).swapaxes(0, 1)
    else:
        ls = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    ms = loss_mask.reshape(b, nc, chunk).swapaxes(0, 1)

    def chunk_body(carry, inp):
        tot, cnt, zacc = carry
        h, lab, msk = inp
        h = h.astype(jnp.bfloat16)
        if cfg.num_codebooks > 1:
            logits = jnp.einsum("bsd,cvd->bscv", h, table).astype(jnp.float32)
        else:
            logits = jnp.einsum("bsd,vd->bsv", h, table).astype(jnp.float32)
        if cfg.final_logit_softcap:
            logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)              # (B,C) or (B,C,cb)
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        ce = lse - ll
        if cfg.num_codebooks > 1:
            ce = ce.mean(-1)
            lse_for_z = lse.mean(-1)
        else:
            lse_for_z = lse
        tot = tot + (ce * msk).sum()
        zacc = zacc + ((lse_for_z ** 2) * msk).sum()
        cnt = cnt + msk.sum()
        return (tot, cnt, zacc), None

    zero = jnp.zeros((), jnp.float32)
    (tot, cnt, zacc), _ = jax.lax.scan(chunk_body, (zero, zero, zero), (hs, ls, ms))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt + z_loss * zacc / cnt


# ----------------------------------------------------------------------
# KV / state cache + decode
# ----------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Tuple[PyTree, PyTree]:
    """Returns (cache, logical_axes). Leaves lead with G (scan dim)."""
    g = num_groups(cfg)
    period = layer_period(cfg)
    slots, slots_l = [], []
    for slot in range(period):
        kind = slot_kind(cfg, slot)
        if kind["kind"] == "attn":
            shp = (g, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            slots.append({"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)})
            lg = ("layer_group", "decode_batch", "kv_seq", "kv_heads", None)
            slots_l.append({"k": lg, "v": lg})
        else:
            din, n, h, hd, k = (cfg.d_inner, cfg.ssm_state, cfg.ssm_heads,
                                cfg.ssm_head_dim, cfg.ssm_conv)
            slots.append({
                "h": jnp.zeros((g, batch, h, hd, n), jnp.float32),
                "conv_x": jnp.zeros((g, batch, k - 1, din), dtype),
                "conv_b": jnp.zeros((g, batch, k - 1, n), dtype),
                "conv_c": jnp.zeros((g, batch, k - 1, n), dtype),
            })
            slots_l.append({
                "h": ("layer_group", "decode_batch", "ssm_inner", None, None),
                "conv_x": ("layer_group", "decode_batch", None, "ssm_inner"),
                "conv_b": ("layer_group", "decode_batch", None, None),
                "conv_c": ("layer_group", "decode_batch", None, None),
            })
    return tuple(slots), tuple(slots_l)


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """(ShapeDtypeStruct cache, logical axes) — no allocation (dry-run)."""
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype)[0])
    return cache, init_cache_logical(cfg)


def init_cache_logical(cfg: ModelConfig):
    period = layer_period(cfg)
    slots_l = []
    for slot in range(period):
        kind = slot_kind(cfg, slot)
        if kind["kind"] == "attn":
            lg = ("layer_group", "decode_batch", "kv_seq", "kv_heads", None)
            slots_l.append({"k": lg, "v": lg})
        else:
            slots_l.append({
                "h": ("layer_group", "decode_batch", "ssm_inner", None, None),
                "conv_x": ("layer_group", "decode_batch", None, "ssm_inner"),
                "conv_b": ("layer_group", "decode_batch", None, None),
                "conv_c": ("layer_group", "decode_batch", None, None),
            })
    return tuple(slots_l)


def decode_step(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
                cache: PyTree, pos: jax.Array, *,
                frontend_embeds: Optional[jax.Array] = None,
                cp_axis=None, mesh=None,
                impl: str = "auto", unroll: int = 1):
    """One decode step. tokens (B,1) [(B,1,C) codebooks]; pos scalar int32
    (aligned batch) or (B,) int32 (continuous batching).
    Returns (logits (B,1,V) [(B,1,C,V)], new_cache)."""
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    period = layer_period(cfg)

    def group_body(x, inp):
        group_params, cache_slices = inp
        new_slices = []
        for slot in range(period):
            x, nc, _ = apply_layer(cfg, slot, group_params[slot], x,
                                   positions=positions, impl=impl,
                                   cache=cache_slices[slot], pos=pos,
                                   cp_axis=cp_axis, mesh=mesh,
                                   capacity_factor=None)
            new_slices.append(nc)
        return x, tuple(new_slices)

    x, new_cache = jax.lax.scan(group_body, x, (params["layers"], cache),
                                unroll=unroll)
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        logits = logits_for(cfg, params, x)
    return logits, new_cache


def prefill(cfg: ModelConfig, params: PyTree, tokens: jax.Array,
            max_len: int, *, frontend_embeds=None, impl: str = "auto",
            cache_dtype=jnp.bfloat16, unroll: int = 1,
            length: Optional[jax.Array] = None):
    """Run the full prompt, building a cache for subsequent decode.
    Returns (last_hidden (B,1,D) logits, cache, next_pos).

    ``length`` (a traced scalar) supports right-padded prompts (the
    serving engine's power-of-two length buckets): logits come from the
    token at ``length - 1`` and ``next_pos`` is ``length``. Causal
    attention makes the pad tail inert for the real tokens, and decode
    masks cache rows ``>= pos``, so the pad K/V are never read. (SSM
    configs must pass exact-length prompts — recurrent state runs
    through every position.)"""
    x = embed_tokens(cfg, params, tokens, frontend_embeds)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    period = layer_period(cfg)
    g = num_groups(cfg)

    def group_body(x, group_params):
        new_slices = []
        for slot in range(period):
            kind = slot_kind(cfg, slot)
            h = rmsnorm(x, group_params[slot]["norm1"]["scale"], cfg.norm_eps)
            if kind["kind"] == "attn":
                with jax.named_scope("attn"):
                    p = group_params[slot]["attn"]
                    xc = h.astype(jnp.bfloat16)
                    q = jnp.einsum("bsd,dhk->bshk", xc, p["wq"].astype(jnp.bfloat16))
                    k = jnp.einsum("bsd,dhk->bshk", xc, p["wk"].astype(jnp.bfloat16))
                    v = jnp.einsum("bsd,dhk->bshk", xc, p["wv"].astype(jnp.bfloat16))
                    q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
                    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
                    window = cfg.window_size if kind["local"] else None
                    out = attn_mod.attention(q, k, v, causal=True, window=window,
                                             softcap=cfg.attn_logit_softcap, impl=impl)
                    y = jnp.einsum("bshk,hkd->bsd", out.astype(jnp.bfloat16),
                                   p["wo"].astype(jnp.bfloat16))
                    x = x + y.astype(x.dtype)
                    kc = jnp.zeros((b, max_len, cfg.num_kv_heads, cfg.head_dim), cache_dtype)
                    kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(cache_dtype), 0, axis=1)
                    vc = jnp.zeros((b, max_len, cfg.num_kv_heads, cfg.head_dim), cache_dtype)
                    vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(cache_dtype), 0, axis=1)
                    new_slices.append({"k": kc, "v": vc})
            else:
                with jax.named_scope("ssm"):
                    p = group_params[slot]["ssm"]
                    # full-sequence mix, but also keep final ssm/conv states
                    din, n, hh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
                    xc = h.astype(jnp.bfloat16)
                    xz = jnp.einsum("bsd,dti->bsti", xc, p["w_xz"].astype(jnp.bfloat16))
                    x_in, z = xz[..., 0, :], xz[..., 1, :]
                    bc = jnp.einsum("bsd,dtn->bstn", xc, p["w_bc"].astype(jnp.bfloat16))
                    b_in, c_in = bc[..., 0, :], bc[..., 1, :]
                    dt_raw = jnp.einsum("bsd,dh->bsh", xc, p["w_dt"].astype(jnp.bfloat16))
                    A = -jnp.exp(p["A_log"].astype(jnp.float32))
                    x_conv, st_x = ssm_mod.causal_conv(x_in, p["conv_x"].astype(x_in.dtype))
                    b_conv, st_b = ssm_mod.causal_conv(b_in, p["conv_b"].astype(b_in.dtype))
                    c_conv, st_c = ssm_mod.causal_conv(c_in, p["conv_c"].astype(c_in.dtype))
                    x_conv, b_conv, c_conv = map(jax.nn.silu, (x_conv, b_conv, c_conv))
                    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
                    xhh = x_conv.reshape(b, s, hh, hd)
                    y, hfin = ssm_mod.ssd_chunked(xhh, dt, A, b_conv, c_conv, chunk=cfg.ssm_chunk)
                    y = y + xhh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
                    y = y.reshape(b, s, din)
                    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
                    y = rmsnorm(y, p["norm"], cfg.norm_eps)
                    out = jnp.einsum("bsi,id->bsd", y.astype(jnp.bfloat16),
                                     p["out"].astype(jnp.bfloat16))
                    x = x + out.astype(x.dtype)
                    new_slices.append({"h": hfin, "conv_x": st_x.astype(cache_dtype),
                                       "conv_b": st_b.astype(cache_dtype),
                                       "conv_c": st_c.astype(cache_dtype)})
            if kind["has_ffn"]:
                h2 = rmsnorm(x, group_params[slot]["norm2"]["scale"], cfg.norm_eps)
                with jax.named_scope("ffn"):
                    if kind["moe"]:
                        y2, _ = moe_ffn(h2, group_params[slot]["moe"],
                                        num_experts=cfg.num_experts,
                                        top_k=cfg.num_experts_per_tok,
                                        activation=activation_fn(cfg.mlp_activation),
                                        capacity_factor=None)
                    else:
                        y2 = mlp(h2, group_params[slot]["mlp"], activation_fn(cfg.mlp_activation))
                x = x + y2
        return x, tuple(new_slices)

    x, cache = jax.lax.scan(group_body, x, params["layers"], unroll=unroll)
    x = rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    if length is None:
        x_last = x[:, -1:]
        npos = jnp.asarray(s, jnp.int32)
    else:
        npos = jnp.asarray(length, jnp.int32)
        x_last = jax.lax.dynamic_slice_in_dim(x, npos - 1, 1, axis=1)
    with jax.named_scope("lm_head"):
        logits = logits_for(cfg, params, x_last)
    return logits, cache, npos
