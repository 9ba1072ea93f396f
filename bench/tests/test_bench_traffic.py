"""The traffic generator: deterministic per seed, the same sizes for
every seed, and lengths and rate as the mix files state."""
import json
import math
import os
from collections import Counter

import numpy as np
import pytest

import traffic

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return traffic.Mix.from_dict(json.load(f))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3, -5])
def test_same_seed_same_requests(seed):
    m = mix("chat-poisson")
    a = traffic.generate(m, seed=seed, seconds=20, vocab=1000, rate=2.0)
    b = traffic.generate(m, seed=seed, seconds=20, vocab=1000, rate=2.0)
    assert [(x.due, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new, x.prompt.tolist()) for x in b]


def blocked(name, block):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return traffic.Mix.from_dict(dict(json.load(f), block=block))


def test_seeds_share_sizes_and_gaps_in_another_order():
    m = blocked("chat-poisson", 8)
    a = traffic.generate(m, seed=1, seconds=50, vocab=1000, rate=2.2)
    b = traffic.generate(m, seed=2, seconds=50, vocab=1000, rate=2.2)
    for key in (lambda x: len(x.prompt), lambda x: x.max_new):
        assert Counter(map(key, a)) == Counter(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.due for r in rs]), 9))
    assert gaps(a) == gaps(b)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]


@pytest.mark.parametrize("block", [8, 5])
def test_each_block_holds_the_same_requests_for_every_seed(block):
    m = blocked("chat-poisson", block)
    runs = [traffic.generate(m, seed=s, seconds=50, vocab=1000, rate=0.8)
            for s in (11, 2**31 + 5)]
    a, b = runs
    assert len(a) == len(b) == 40
    for lo in range(0, 40, m.block):
        blk = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs[lo:lo + m.block])
        assert blk(a) == blk(b)
        # a block starts and ends at the same time for every seed
        hi = min(lo + m.block, 40) - 1
        assert a[hi].due == pytest.approx(b[hi].due)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_blocks_of_one_send_the_same_schedule_for_every_seed():
    """Blocks of one: only the token ids (and the weights) follow the seed."""
    m = blocked("chat-poisson", 1)
    a, b = (traffic.generate(m, seed=s, seconds=50, vocab=92544, rate=0.8)
            for s in (3, 2**31 + 7))
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]


def test_poisson_rate_and_lengths_match_the_file():
    m = mix("chat-poisson")
    seconds, rate = 50, 2.2
    reqs = traffic.generate(m, seed=3, seconds=seconds, vocab=92544, rate=rate)
    assert len(reqs) == round(rate * seconds)
    due = np.array([r.due for r in reqs])
    assert (np.diff(due) > 0).all() and 0 < due[0] and due[-1] < seconds
    # the gaps are exponential: their coefficient of variation is near 1
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    for spec, got in ((m.prompt, [len(r.prompt) for r in reqs]),
                      (m.output, [r.max_new for r in reqs])):
        assert min(got) >= spec.low and max(got) <= spec.high
        assert abs(np.median(got) - spec.median) <= 0.02 * spec.median + 1
        logs = np.log([g for g in got if spec.low < g < spec.high])
        # inside the clamps the log-lengths spread by about sigma
        assert 0.6 * spec.sigma < logs.std() < 1.2 * spec.sigma
    assert all(0 <= t < 92544 for r in reqs for t in r.prompt)
    assert max(len(r.prompt) + r.max_new for r in reqs) <= m.max_positions() <= 1023


def test_backlog_blocks_hold_every_quantile():
    m = mix("longprompt-backlog")
    reqs = traffic.generate(m, seed=4, seconds=50, vocab=1000, backlog_size=256)
    assert all(r.due is None for r in reqs)
    q = sorted(m.prompt.quantiles(m.block).tolist())
    for i in range(0, 256, m.block):
        assert sorted(len(r.prompt) for r in reqs[i:i + m.block]) == q
    assert abs(np.median([len(r.prompt) for r in reqs]) - 640) <= 13


def test_lognormal_quantiles_follow_the_length_spec():
    spec = traffic.Lengths(median=256, sigma=0.8, low=16, high=640)
    q = spec.quantiles(1001)
    assert q[500] == 256
    # the share clamped at the top is the lognormal's tail beyond `high`
    tail = 1 - 0.5 * (1 + math.erf(math.log(640 / 256) / (0.8 * math.sqrt(2))))
    assert abs((q == 640).mean() - tail) < 0.01
