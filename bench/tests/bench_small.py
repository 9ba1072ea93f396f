"""The harness's ``Arch`` for a program ``ModelConfig`` of the
grouped-query decoder (``models/gqa.py``), for tests at small sizes: the
config's sizes written as the published ``config.json`` keys, which the
model module reads as it reads a configuration file."""
from arch import Arch


def arch_of(cfg, *, slots: int, max_len: int) -> Arch:
    return Arch.from_dict({
        "name": cfg.name, "model": "gqa", "hidden_act": cfg.mlp_activation,
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
        "num_local_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "engine": {"slots": slots, "max_len": max_len,
                   "cache_dtype": "float32", "weights_dtype": "float32"}})
