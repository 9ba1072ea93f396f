"""The serving engine's own observability: ``serve.*`` spans in the JAX
profiler's trace, the named programs, the ``stats`` counters and the
requests' host-clock stamps, on three greedy requests at a tiny size."""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models.params import init_params
from repro.serve.engine import Request, ServeEngine

#: (prompt length, max new tokens) of the three requests
SHAPES = [(5, 4), (12, 6), (9, 3)]
#: the span each ``serve.*`` span sits in (None: the outermost)
PARENT = {"serve.step": None, "serve.admit": "serve.step",
          "serve.prefill_call": "serve.admit", "serve.splice": "serve.admit",
          "serve.decode_call": "serve.step", "serve.sample": "serve.step"}
FETCH_PARENT = {"token": "serve.admit", "argmax": "serve.sample"}


def _serve(cfg, params):
    eng = ServeEngine(cfg, params, slots=2, max_len=64, impl="ref")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=100 + i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate(SHAPES)]
    for r in reqs:
        eng.submit(r)
    active = []
    while eng.queue or any(eng.active):
        active.append(eng.step())
    return eng, reqs, active


def _events(trace_dir):
    """``[(line, name, start, end, stats)]`` of the host planes."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    out.append((line.name, ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = get_config("internlm2-1.8b").reduced()
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    _, plain, _ = _serve(cfg, params)            # compiles; profiler off
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        eng, reqs, active = _serve(cfg, params)
    finally:
        jax.profiler.stop_trace()
    return eng, reqs, active, plain, _events(trace_dir)


def _spans(events):
    return [e for e in events if e[1].startswith("serve.")]


def _parent(span, spans):
    """The innermost ``serve.*`` span of the same thread holding ``span``."""
    line, _, s, e, _ = span
    holders = [x for x in spans if x is not span and x[0] == line
               and x[2] <= s and e <= x[3]]
    return min(holders, key=lambda x: x[3] - x[2])[1] if holders else None


def test_the_spans_nest_as_the_engine_calls(served):
    eng, reqs, active, _, events = served
    spans = _spans(events)
    names = {sp[1] for sp in spans}
    assert names == set(PARENT) | {"serve.fetch"}
    for sp in spans:
        want = (FETCH_PARENT[sp[4]["what"]] if sp[1] == "serve.fetch"
                else PARENT[sp[1]])
        assert _parent(sp, spans) == want, sp
    steps = [sp for sp in spans if sp[1] == "serve.step"]
    assert [sp[4]["active"] for sp in sorted(steps, key=lambda x: x[2])] == active
    decodes = [sp[4]["active"] for sp in spans if sp[1] == "serve.decode_call"]
    assert sorted(decodes) == sorted(a for a in active if a)


def test_admission_spans_carry_each_request(served):
    eng, reqs, _, _, events = served
    spans = _spans(events)
    for name in ("serve.admit", "serve.prefill_call"):
        got = sorted(sp[4]["rid"] for sp in spans if sp[1] == name)
        assert got == [r.rid for r in reqs]
    for sp in spans:
        if sp[1] == "serve.admit":
            req = next(r for r in reqs if r.rid == sp[4]["rid"])
            assert sp[4]["prompt_len"] == len(req.prompt)
            assert sp[4]["bucket"] == eng._bucket_len(len(req.prompt))
    fetches = [sp[4]["what"] for sp in spans if sp[1] == "serve.fetch"]
    assert len(fetches) == eng.stats["host_fetches"]


def test_the_programs_are_named(served):
    events = served[-1]
    modules = {e[4]["hlo_module"] for e in events if "hlo_module" in e[4]}
    assert {"jit_decode_step", "jit_prefill"} <= modules
    assert not any("lambda" in m for m in modules)


def test_the_counters(served):
    eng, reqs, active, _, _ = served
    st = eng.stats
    steps = sum(1 for a in active if a)
    assert st["decode_steps"] == steps
    assert st["decode_slot_steps"] == sum(active)
    assert st["admitted"] == len(reqs)
    # greedy: one read per decode step (every slot's pick at once) and
    # one per admission (its first token); positions are host integers
    assert st["host_fetches"] == st["decode_steps"] + st["admitted"]


def _programs(events):
    """Names of the executed programs in launch order: each host launch
    joined on its ``run_id`` to the module of the operations it ran."""
    module = {e[4]["run_id"]: e[4]["hlo_module"] for e in events
              if "hlo_module" in e[4] and "run_id" in e[4]}
    launches = sorted((e[2], e[4]["run_id"]) for e in events
                      if e[1] == "PjRtCpuExecutable::ExecuteHelper")
    return [module[r] for _, r in launches if r in module]


def test_one_greedy_pick_between_decode_steps(served):
    """Between two decode executions with no admission (prefill) between
    them, the only program is the greedy pick: positions and tokens go up
    as host arrays, so no eager device operation runs in the gap."""
    _, reqs, active, _, events = served
    progs = _programs(events)
    at = [i for i, p in enumerate(progs) if p == "jit_decode_step"]
    assert len(at) == sum(1 for a in active if a)
    gaps = [progs[i + 1:j] for i, j in zip(at, at[1:])]
    plain = [g for g in gaps if "jit_prefill" not in g]
    assert plain and all(g == ["jit_greedy_pick"] for g in plain), gaps
    assert progs[at[-1] + 1:] == ["jit_greedy_pick"]
    # one pick per decode step and one per greedy prefill
    assert progs.count("jit_greedy_pick") == len(at) + len(reqs)


def test_the_host_clock_stamps(served):
    _, reqs, _, _, _ = served
    for r in reqs:
        assert r.submitted_at is not None and r.admitted_at >= r.submitted_at
    # the third request waits for a slot
    assert reqs[2].admitted_at > max(reqs[0].admitted_at, reqs[1].admitted_at)


def test_tokens_are_the_same_with_the_profiler_off(served):
    _, reqs, _, plain, _ = served
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in plain]
    assert [len(r.out_tokens) for r in reqs] == [m for _, m in SHAPES]
