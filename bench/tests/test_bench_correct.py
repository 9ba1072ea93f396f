"""The comparison that decides ``correct``, at the configurations' small
sizes on the CPU: the engine against the plain reference, the float8
control against the same limit (also in the program's place, through the
run's own comparison), and a run with its timed path broken in each way
a serving cell can break, which must come out not correct."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
import weights
from bench_small import arch_of
from repro.configs import get_config
from repro.serve.engine import Request, ServeEngine

NAMES = ["internlm2-1.8b", "granite-moe-1b-a400m"]
# bf16 compute against the float32 reference at these sizes differs by at
# most ~0.013 in a logit (logits span about +-0.5); the float8 control by
# 0.06 or more (measured over seeds 0-3). 0.03 lies between.
LOGIT_ATOL = 0.03
# the same for the widest gap of served tokens at the small sizes
SMALL_GAP_LIMIT = 0.05


def small_arch(name, slots=2, max_len=64):
    cfg = get_config(name).reduced()
    return cfg, arch_of(cfg, slots=slots, max_len=max_len)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("name", NAMES)
def test_engine_logits_match_the_reference(name, seed):
    """Bucketed prefill, the splice into the slot cache, decode through it
    with per-row positions, and the LM head (and the MoE FFN) against one
    float32 pass over the whole sequence; the control is farther off."""
    cfg, a = small_arch(name)
    params = weights.make(a, seed)
    eng = ServeEngine(cfg, params, slots=a.slots, max_len=a.max_len)
    got = []

    def keep(fn):
        def inner(*args):
            out = fn(*args)
            got.append(np.asarray(out[0])[0, 0])
            return out
        return inner

    eng._prefill, eng._decode = keep(eng._prefill), keep(eng._decode)
    prompt = np.random.default_rng(seed).integers(0, a.vocab, 13).astype(np.int32)
    req = Request(0, prompt, max_new_tokens=8)
    eng.submit(req)
    eng.run()
    seq = jnp.asarray(np.concatenate([prompt, np.int32(req.out_tokens[:-1])]))
    rows = slice(len(prompt) - 1, len(prompt) - 1 + len(got))
    ref = np.asarray(a.module.forward(a.sizes, params, seq))[rows]
    ctl = np.asarray(a.module.forward(a.sizes, params, seq, quant=True))[rows]
    assert len(got) == 8
    assert np.abs(np.stack(got) - ref).max() < LOGIT_ATOL
    assert np.abs(ctl - ref).max() > LOGIT_ATOL


def _cell(seconds_limit=SMALL_GAP_LIMIT):
    spec = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cfg = get_config("internlm2-1.8b").reduced(vocab_size=512)
    cell = run.Cell(spec, "internlm2-chat",
                    arch=arch_of(cfg, slots=4, max_len=1024))
    cell.params = dict(cell.params, logit_gap_limit=seconds_limit)
    return cell


PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}


def _cache_unchanged(engine):
    dec = engine._decode
    engine._decode = lambda p, t, c, pos: (dec(p, t, c, pos)[0], c)


def _half_batch(engine):
    """Every other slot's token left out of the decode step (slot 0, the
    first to fill, among them)."""
    dec = engine._decode
    engine._decode = lambda p, t, c, pos: dec(p, t.at[::2].set(0), c, pos)


def _token_altered(engine):
    fin = engine._finish_decode

    def inner(act, logits):
        retired = fin(act, logits)
        for s in act[:1]:
            req = engine.active[s] or retired[0]
            req.out_tokens[-1] = (req.out_tokens[-1] + 1) % engine.cfg.vocab_size
        return retired
    engine._finish_decode = inner


@pytest.mark.parametrize("fault", [None, _cache_unchanged, _half_batch,
                                   _token_altered, "control"],
                         ids=["sound", "cache_unchanged", "half_batch",
                              "token_altered", "float8_control"])
def test_a_broken_timed_path_is_not_correct(fault):
    """``control``: the float8 control's tokens in the program's place,
    through the run's own comparison (``run.py --control 1``)."""
    cell = _cell()
    control = fault == "control"
    res = run.run_cell(cell, seed=2**33 + 1, seconds=1.5, trace=False,
                       peaks=PEAKS, devices=jax.devices(),
                       engine_hook=None if control else fault, control=control)
    gap = res["checks"]["max_logit_gap"]["value"]
    assert res["attempted"] > 0 and list(res)[-1] == "checks"
    if fault is None:
        assert res["correct"], gap
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    else:
        assert not res["correct"] and gap > SMALL_GAP_LIMIT


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_float8_control_fails_the_limit(seed):
    cell = _cell()
    counter = run.CompileCounter()
    params, engine, _ = run.set_up(cell, seed, trace=False)
    drive, _, _ = run.measure(cell, engine, seed=seed, seconds=1.0, trace=False,
                              counter=counter)
    rows = check.sample(run.served(drive), seed)
    program, _ = check.widest_gap(cell.arch, params, rows)
    control, _ = check.widest_gap(cell.arch, params, rows, control=True)
    assert program <= SMALL_GAP_LIMIT < control

