"""Pooled percentiles, token counting in the window, and the counts of
operations and bytes against the program's own parameter counts."""
import os

import numpy as np
import pytest

import flops
import stats
from arch import Arch
from loadgen import Record
from repro.configs import get_config

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
NAMES = ["internlm2-1.8b", "granite-moe-1b-a400m"]


def rec(rid, due, stamps, prompt_len=10):
    return Record(rid=rid, prompt_len=prompt_len, max_new=len(stamps), due=due,
                  submit=due, stamps=list(stamps), done=True)


def test_gaps_are_pooled_over_requests():
    a = rec(0, 0.0, [1.0, 1.1, 1.3])            # gaps 0.1, 0.2
    b = rec(1, 0.5, [2.0, 2.0, 2.4, 2.5])       # 0.0 (two tokens in one step), 0.4, 0.1
    gaps = stats.token_gaps([a, b])
    assert sorted(np.round(gaps, 9)) == [0.0, 0.1, 0.1, 0.2, 0.4]
    assert stats.percentile(gaps, 50) == pytest.approx(0.1)
    assert stats.percentile(gaps, 100) == pytest.approx(0.4)
    assert stats.percentile([], 50) is None


def test_ttft_counts_from_the_scheduled_arrival():
    recs = [rec(0, 0.0, [1.0]), rec(1, 0.5, [2.0, 2.5]), rec(2, 3.0, [])]
    assert stats.ttfts(recs) == [1.0, 1.5]
    assert stats.percentile(stats.ttfts(recs), 90) == pytest.approx(1.45)


def test_window_tokens_count_prefills_and_tokens_inside():
    recs = [rec(0, 0.0, [1.0, 2.0, 9.0], prompt_len=100),   # in: 100 + 3
            rec(1, 0.0, [11.0, 12.0], prompt_len=50),        # prefill after
            rec(2, 0.0, [10.0], prompt_len=7)]               # at the close
    assert stats.window_tokens(recs, 10.0) == 103 + 8


def sizes(name):
    a = Arch.from_file(os.path.join(CONFIGS, f"{name}.json"))
    return a, a.sizes


@pytest.mark.parametrize("name", NAMES)
def test_config_file_matches_the_program_config(name):
    a, s = sizes(name)
    cfg = get_config(name)
    assert (s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff,
            s.vocab, s.experts, s.top_k, s.tied) == \
        (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
         cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.num_experts,
         cfg.num_experts_per_tok, cfg.tie_embeddings)
    assert a.model_config().param_count() == cfg.param_count()


@pytest.mark.parametrize("name", NAMES)
def test_counts_match_the_program_parameter_counts(name):
    a, s = sizes(name)
    cfg = a.model_config()
    assert a.module.param_count(s) == cfg.param_count()
    assert a.module.active_param_count(s) == pytest.approx(
        cfg.active_param_count(), rel=1e-6)
    table = s.vocab * s.d_model
    embed = table * (1 if s.tied else 2)
    body = a.module.active_param_count(s) - embed - (2 * s.layers + 1) * s.d_model
    # a decode token: 2 operations per active body weight, the head, and
    # attention over its cache
    f, _ = flops.decode(a, [100])
    assert f == 2 * body + 2 * table + 4 * s.layers * s.heads * s.head_dim * 100
    # a prefill of n tokens: n tokens of body, the causal half of the
    # scores, and logits for the last position only
    n = 512
    f, _ = flops.prefill(a, n)
    assert f == n * 2 * body + 4 * s.layers * s.heads * s.head_dim * n * (n + 1) // 2 \
        + 2 * table


def test_bytes_count_bf16_weights_and_valid_cache_only():
    a, s = sizes("internlm2-1.8b")
    _, b1 = flops.decode(a, [10])
    _, b2 = flops.decode(a, [1010])
    kv_row = 2 * s.layers * s.kv_heads * s.head_dim * 2
    assert b2 - b1 == 1000 * kv_row
    weights = a.module.param_count(s) - s.vocab * s.d_model    # one table is looked up
    assert b1 == pytest.approx(2 * weights + 2 * s.d_model + 11 * kv_row)


def test_moe_reads_only_the_experts_a_call_can_touch():
    a, s = sizes("granite-moe-1b-a400m")
    _, one = flops.decode(a, [10])
    _, many = flops.decode(a, [10] * 64)
    expert = 3 * s.d_model * s.d_ff * 2 * s.layers
    # one token reads its top-8 experts; 64 tokens nearly all 32
    assert many - one == pytest.approx(
        (32 * (1 - (1 - 8 / 32) ** 64) - 8) * expert
        + 63 * (2 * s.d_model + 2 * 2 * s.layers * s.kv_heads * s.head_dim * 11),
        rel=1e-9)
