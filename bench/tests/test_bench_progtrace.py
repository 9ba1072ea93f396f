"""The engine's own trace (``progtrace``): leaf operations, the scope
filter, decode gaps and the clock on intervals made up here; the five
readers on a trace of the tiny engine recorded in the test, and on none."""
import json
import os
from types import SimpleNamespace

import pytest

import progtrace
import run
from bench_small import arch_of
from progtrace import DECODE, PREFILL, Device, Exec, Op, Span, Trace
from repro.configs import get_config

READERS = ["decode_gap_ms_p50", "host_fetches_per_step", "admit_wait_ms_p90",
           "decode_attn_ms", "decode_ffn_ms"]
ATTN = "jit(decode_step)/while/body/closed_call/attn/dot_general"
FFN = "jit(decode_step)/while/body/closed_call/ffn/dot_general"


def test_a_loop_around_its_body_is_not_a_leaf():
    ops = [Op(10, 90, "while.2", ""), Op(12, 30, "fusion.1", ATTN),
           Op(30, 60, "fusion.2", FFN), Op(61, 89, "fusion.3", ATTN),
           Op(95, 99, "argmax", "")]
    leaves = progtrace.leaves(ops)
    assert [o.name for o in leaves] == ["fusion.1", "fusion.2", "fusion.3", "argmax"]
    dev = Device([Exec(10, 92, DECODE)], leaves, 0)
    # the loop's 80 ns would count its body a second time
    assert progtrace.scope_ns(dev, dev.execs, None) == 18 + 30 + 28
    # an operation outside every execution given does not count
    assert progtrace.scope_ns(dev, [Exec(0, 94, DECODE)], None) == 76


def test_the_scope_filter_matches_whole_components():
    ops = [Op(0, 10, "a", ATTN), Op(10, 30, "b", FFN),
           Op(30, 34, "c", "jit(decode_step)/lm_head/dot_general"),
           Op(34, 35, "d", "jit(decode_step)/attn_out/x"), Op(35, 36, "e", "")]
    dev = Device([Exec(0, 40, DECODE)], ops, 0)
    got = {s: progtrace.scope_ns(dev, dev.execs, s)
           for s in ("attn", "ffn", "lm_head", "embed")}
    assert got == {"attn": 10, "ffn": 20, "lm_head": 4, "embed": 0}


def test_a_gap_with_a_prefill_between_is_skipped():
    execs = [Exec(100, 140, DECODE), Exec(150, 190, DECODE),
             Exec(200, 230, PREFILL), Exec(240, 280, DECODE),
             Exec(283, 320, DECODE), Exec(330, 360, "jit__argmax"),
             Exec(370, 400, DECODE), Exec(900, 940, DECODE)]
    dev = Device(execs, [], 0)
    # a small program between two decodes stays inside the gap; the last
    # decode starts after the window closed
    assert progtrace.decode_gaps(dev, (0, 800)) == [10, 3, 50]
    # the window is on the host clock: a device clock 200 ns ahead
    assert progtrace.decode_gaps(dev._replace(offset=200), (0, 800)) == [
        3, 50, 500]
    t = Trace([], (0, 800), [dev], "name")
    assert progtrace.decode_gap_ms_p50(t) == pytest.approx(10e-6)


@pytest.mark.parametrize("shift", [0, -1_000_000, 2_000_000])
def test_the_clock_from_name_matched_launches(shift):
    # each call span launches its own program 1 ms after it opens; eager
    # operations run between; the device clock sits ``shift`` ns from the
    # host's. Times in units of 0.1 ms: a program's executions lie further
    # apart than the clocks can (``devtrace.MAX_OFFSET``)
    u = 100_000
    spans = [Span(100 * u, 105 * u, "serve.prefill_call", {}),
             Span(300 * u, 305 * u, "serve.decode_call", {}),
             Span(500 * u, 505 * u, "serve.decode_call", {}),
             Span(700 * u, 705 * u, "serve.prefill_call", {})]
    execs = [Exec(110, 250, PREFILL, 1), Exec(255, 256, "jit__argmax", 2),
             Exec(310, 390, DECODE, 3), Exec(392, 395, "jit_add", 4),
             Exec(396, 397, "jit__argmax", 7), Exec(398, 399, "jit_add", 8),
             Exec(510, 590, DECODE, 5), Exec(710, 740, PREFILL, 6)]
    execs = [x._replace(start=x.start * u + shift, end=x.end * u + shift)
             for x in execs]
    assert progtrace.fit_offset(execs, spans) == shift + 10 * u
    # host launch events and device executions matched on run_id; the
    # eager operations launched during a step wait behind it and do not
    # count
    launches = {1: 102 * u, 2: 200 * u, 3: 302 * u, 4: 320 * u, 5: 502 * u,
                6: 702 * u, 7: 330 * u, 8: 340 * u}
    assert progtrace.fit_offset(execs, spans, launches) == shift + 8 * u
    assert progtrace.fit_offset(execs, []) is None


def test_program_names_lose_their_suffix():
    assert progtrace.program("jit_decode_step(17075880666624042904)") == DECODE
    assert progtrace.program("jit_prefill(5)") == PREFILL
    assert progtrace.program("jit_prefill") == PREFILL


def _tiny_cell(name):
    spec = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    cfg = get_config("internlm2-1.8b").reduced(vocab_size=512)
    return run.Cell(spec, name,
                    arch=arch_of(cfg, slots=4, max_len=1024))


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced window of the tiny engine, read as ``run_cell`` does."""
    out = tmp_path_factory.mktemp("bench")
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "OUT_DIR", out)
    mp.setattr(progtrace, "OUT_DIR", out)
    try:
        cell = _tiny_cell("internlm2-chat")
        _, engine, _ = run.set_up(cell, 11, trace=True)
        drive, summary, setup_s = run.measure(
            cell, engine, seed=2**33 + 5, seconds=1.5, trace=True,
            counter=run.CompileCounter())
        yield run.Run(cell=cell, arch=cell.arch, peaks={}, drive=drive,
                      stats=dict(engine.stats), trace=summary,
                      setup_s=setup_s, seconds=1.5)
    finally:
        mp.undo()


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_a_recorded_trace(traced_run, name):
    v = run.Metrics().read(name, traced_run)
    assert v is not None and v >= 0, name
    if name == "host_fetches_per_step":
        # one greedy-pick read a step, and one more per admission (its
        # first token)
        assert 1.0 <= v < 2


def test_the_trace_joins_programs_scopes_and_spans(traced_run):
    t = progtrace.of(traced_run)
    assert t.joined_by in ("run_id", "name") and t.window is not None
    names = {sp.name for sp in t.spans}
    assert {"serve.step", "serve.admit", "serve.prefill_call", "serve.decode_call",
            "serve.sample", "serve.fetch", "serve.splice"} <= names
    admits = [sp.args["rid"] for sp in t.spans if sp.name == "serve.admit"]
    assert sorted(admits) == sorted(r.rid for r in traced_run.drive.records
                                    if r.admit is not None)
    dev = t.devices[0]
    assert {DECODE, PREFILL} <= {x.program for x in dev.execs}
    per_exec = progtrace.decode_scope_ms(t, None)
    attn, ffn = (progtrace.decode_scope_ms(t, s) for s in ("attn", "ffn"))
    assert 0 < attn and 0 < ffn and attn + ffn < per_exec
    # loaded once for all readers
    assert progtrace.of(traced_run) is t


def test_the_readers_read_nothing_without_a_trace(tmp_path, monkeypatch):
    """An empty trace directory, an untraced run, and requests without
    the engine's stamps (an engine that lacks them) read None."""
    monkeypatch.setattr(progtrace, "OUT_DIR", tmp_path)
    (tmp_path / "trace-internlm2-chat").mkdir()
    cell = _tiny_cell("internlm2-chat")
    unstamped = SimpleNamespace(records=[SimpleNamespace(
        request=SimpleNamespace(rid=0))])
    reader = run.Metrics()
    for trace in (SimpleNamespace(), None):
        r = run.Run(cell=cell, arch=cell.arch, peaks={}, drive=unstamped,
                    stats={}, trace=trace, setup_s=0.0, seconds=1.0)
        for name in READERS:
            assert reader.read(name, r) is None, name
