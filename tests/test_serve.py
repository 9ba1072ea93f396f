"""Serving engine + disaggregated KV store."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.models.params import init_params
from repro.serve.disagg import DisaggKV, KVStoreParams
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_engine_serves_all_requests(small_lm):
    cfg, params = small_lm
    eng = ServeEngine(cfg, params, slots=3, max_len=64, impl="ref")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=5) for i in range(7)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and len(r.out_tokens) == 5 for r in reqs)


def test_engine_greedy_matches_offline(small_lm):
    cfg, params = small_lm
    eng = ServeEngine(cfg, params, slots=2, max_len=64, impl="ref")
    rng = np.random.default_rng(1)
    r = Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4)
    eng.submit(r)
    eng.run()
    full = jnp.asarray(np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)]))[None]
    res = M.forward(cfg, params, full, impl="ref", remat="none")
    nxt = int(jnp.argmax(M.logits_for(cfg, params, res.hidden[:, -1:])[0, 0]))
    assert nxt == r.out_tokens[-1]


def test_engine_mixed_lengths(small_lm):
    cfg, params = small_lm
    eng = ServeEngine(cfg, params, slots=4, max_len=64, impl="ref")
    rng = np.random.default_rng(2)
    reqs = []
    for i, (plen, new) in enumerate([(4, 3), (12, 6), (8, 2), (16, 4), (6, 5)]):
        r = Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=new)
        reqs.append(r)
        eng.submit(r)
    eng.run()
    for r, (_, new) in zip(reqs, [(4, 3), (12, 6), (8, 2), (16, 4), (6, 5)]):
        assert r.done and len(r.out_tokens) == new


def test_engine_run_returns_completed_requests(small_lm):
    """Regression: run() used to always return [] — it must hand back
    every request retired during the call, in retirement order."""
    cfg, params = small_lm
    eng = ServeEngine(cfg, params, slots=2, max_len=64, impl="ref")
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=3) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1, 2, 3, 4]
    assert all(r.done for r in done)
    # a second run with nothing queued completes nothing new
    assert eng.run() == []
    # late submissions are returned by the call that retires them
    late = Request(rid=99, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                   max_new_tokens=2)
    eng.submit(late)
    assert [r.rid for r in eng.run()] == [99]


def test_engine_fabric_placement(small_lm):
    """§5.2 wired into serving: the engine consults the fabric router
    for the decode cache placement."""
    cfg, params = small_lm
    kv = DisaggKV(KVStoreParams(n_keys=10_000, soc_cache_keys=1_000))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, impl="ref",
                      fabric=kv.fabric(), cache_hit_mass=kv.cache_hit_mass())
    assert eng.placement is not None
    assert eng.placement.location == "soc_cache"
    assert eng.placement.rate > eng.placement.baseline_rate
    # without a fabric there is no placement plan
    eng2 = ServeEngine(cfg, params, slots=2, max_len=64, impl="ref")
    assert eng2.placement is None


def test_engine_retires_a_request_the_cache_cuts(small_lm):
    """A request longer than the cache retires once its host-side position
    reaches ``max_len - 1``: ``max_len - prompt`` tokens, the first
    ``max_len - prompt`` of its uncut answer. A request in the other slot
    serves on, as it does alone."""
    cfg, params = small_lm
    max_len = 32
    rng = np.random.default_rng(4)
    long_prompt = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    short_prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)

    def serve(max_len, *reqs):
        eng = ServeEngine(cfg, params, slots=2, max_len=max_len, impl="ref")
        for r in reqs:
            eng.submit(r)
        positions = []
        while eng.queue or any(eng.active):
            eng.step()
            positions.append(eng.pos.copy())
        return eng, positions

    cut = Request(rid=0, prompt=long_prompt, max_new_tokens=100)
    other = Request(rid=1, prompt=short_prompt, max_new_tokens=20)
    eng, positions = serve(max_len, cut, other)
    assert cut.done and len(cut.out_tokens) == max_len - len(long_prompt)
    # one position a decode step; the step that retired it is the first
    # to leave slot 0 at the cache's last position
    steps = len(cut.out_tokens) - 1
    assert [int(p[0]) for p in positions[:steps]] == \
        list(range(len(long_prompt) + 1, max_len))
    assert eng.active[0] is None and other.done
    alone = Request(rid=1, prompt=short_prompt, max_new_tokens=20)
    serve(max_len, alone)
    assert other.out_tokens == alone.out_tokens and len(alone.out_tokens) == 20
    uncut = Request(rid=0, prompt=long_prompt, max_new_tokens=100)
    serve(128, uncut)
    assert cut.out_tokens == uncut.out_tokens[:len(cut.out_tokens)]


def test_disagg_data_plane_correct():
    kv = DisaggKV(KVStoreParams(n_keys=5000, soc_cache_keys=500))
    rng = np.random.default_rng(0)
    for alt in ["A1", "A2", "A3", "A4", "A5"]:
        for k in rng.integers(0, 5000, 50):
            v, lat = kv.get(int(k), alt)
            assert (v == kv.values[int(k)]).all()
            assert 0 < lat < 1e-4


def test_disagg_latency_ordering():
    kv = DisaggKV(KVStoreParams(n_keys=5000, soc_cache_keys=5000))  # all cached
    _, l5 = kv.get(1, "A5")
    _, l4 = kv.get(1, "A4")
    _, l1 = kv.get(1, "A1")
    _, l2 = kv.get(1, "A2")
    assert l5 < l4 < l1 < l2   # Fig 17(a)


def test_disagg_combined_beats_components():
    kv = DisaggKV(KVStoreParams(n_keys=100_000, soc_cache_keys=10_000))
    paths, alts = kv.fabric(), kv.alternatives()
    total, allocs = kv.combined_a4_a5()
    assert total > alts["A4"].solo_rate(paths)
    assert sum(a.rate for a in allocs) == pytest.approx(total)
