"""A configuration file of ``configs/`` read into the sizes the benchmark
uses, and into the program's ``ModelConfig``.

The file holds the published ``config.json`` keys as they are run (each
key changed from the source is listed under ``reduced``) and the
engine's settings under ``engine``.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Arch:
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
    slots: int
    max_len: int
    cache_dtype: str
    weights_dtype: str

    @classmethod
    def from_file(cls, path: Path) -> "Arch":
        d = json.loads(Path(path).read_text())
        if d.get("hidden_act", "silu") != "silu":
            raise ValueError(f"{path}: only SwiGLU (silu) models are supported")
        eng = d["engine"]
        heads = int(d["num_attention_heads"])
        return cls(
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
            norm_eps=float(d["rms_norm_eps"]),
            slots=int(eng["slots"]),
            max_len=int(eng["max_len"]),
            cache_dtype=eng["cache_dtype"],
            weights_dtype=eng["weights_dtype"])

    def model_config(self):
        """The program's ``ModelConfig`` for these sizes."""
        from repro.configs.base import ModelConfig
        return ModelConfig(
            name=self.name, family="moe" if self.experts else "dense",
            num_layers=self.layers, d_model=self.d_model,
            vocab_size=self.vocab, num_heads=self.heads,
            num_kv_heads=self.kv_heads, head_dim=self.head_dim,
            rope_theta=self.rope_theta, d_ff=self.d_ff, mlp_activation="silu",
            num_experts=self.experts, num_experts_per_tok=self.top_k,
            norm_eps=self.norm_eps, dtype="bfloat16",
            tie_embeddings=self.tied)

    @classmethod
    def from_model_config(cls, cfg, *, slots: int, max_len: int,
                          cache_dtype: str = "float32",
                          weights_dtype: str = "float32") -> "Arch":
        """The sizes of a program ``ModelConfig`` (tests at small sizes)."""
        return cls(name=cfg.name, layers=cfg.num_layers, d_model=cfg.d_model,
                   heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.head_dim, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                   experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
                   tied=cfg.tie_embeddings, rope_theta=cfg.rope_theta,
                   norm_eps=cfg.norm_eps, slots=slots, max_len=max_len,
                   cache_dtype=cache_dtype, weights_dtype=weights_dtype)
