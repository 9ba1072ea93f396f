"""The reduction from a profiler trace to busy time, idle gaps and the
device time of each phase's program: on intervals made up here, and on a
small trace recorded in the test."""
import time

import jax
import jax.numpy as jnp
import pytest

import devtrace
from devtrace import Device, Exec, Op, Span


def test_union_merges_and_clips():
    got = devtrace.union([(5, 8), (0, 3), (2, 4), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 12), (20, 25)]


def test_timeline_labels_the_innermost_open_span():
    spans = [Span(0, 100, "bench.step"), Span(10, 30, "bench.prefill"),
             Span(12, 20, "bench.prefill_call"), Span(50, 90, "bench.sample")]
    segs = devtrace.timeline(spans)
    at = lambda t: devtrace.label_at(segs, t)
    assert [at(t) for t in (5, 11, 15, 25, 40, 60, 95, 150)] == [
        "bench.step", "bench.prefill", "bench.prefill_call", "bench.prefill",
        "bench.step", "bench.sample", "bench.step", "bench.loadgen"]


@pytest.mark.parametrize("shift", [0, -1_000_000, 2_000_000])
def test_reduce_on_made_up_intervals(shift):
    # p1 and p4 (two prefill buckets) launched from prefill calls, p2
    # from decode calls, p3 (an eager op) right after each decode; the
    # decode after the window closed still counts to its phase
    spans = [Span(0, 1000, "bench.window"),
             Span(100, 105, "bench.prefill_call"),
             Span(300, 305, "bench.decode_call"),
             Span(400, 420, "bench.sample"),
             Span(500, 505, "bench.decode_call"),
             Span(600, 605, "bench.prefill_call"),
             Span(1100, 1105, "bench.decode_call")]
    ops = [Op(110, 200, "fusion"), Op(200, 250, "dot"),
           Op(310, 390, "fusion"), Op(392, 395, "argmax"),
           Op(510, 590, "fusion"), Op(592, 595, "argmax"),
           Op(610, 640, "dot"), Op(1110, 1190, "fusion")]
    execs = [Exec(110, 250, "p1"), Exec(310, 390, "p2"), Exec(392, 395, "p3"),
             Exec(510, 590, "p2"), Exec(592, 595, "p3"), Exec(610, 640, "p4"),
             Exec(1110, 1190, "p2")]
    # the device's clock sits ``shift`` ns from the host's
    ops = [o._replace(start=o.start + shift, end=o.end + shift) for o in ops]
    execs = [x._replace(start=x.start + shift, end=x.end + shift) for x in execs]
    s = devtrace.reduce([Device(execs, ops)], spans)
    assert devtrace.clock_offset(execs, spans) == shift + 10
    assert devtrace.attribute(execs, spans) == {
        "p1": "prefill", "p4": "prefill", "p2": "decode"}
    assert s.window_s == pytest.approx(1000e-9)
    busy = 140 + 80 + 3 + 80 + 3 + 30
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.idle_share == pytest.approx(1 - busy / 1000)
    assert s.phase_s == {"prefill": pytest.approx(170e-9),
                         "decode": pytest.approx(240e-9)}
    assert s.phase_runs == {"prefill": 2, "decode": 3}
    gaps = dict(s.idle_gaps)
    assert gaps["bench.sample"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx((1000 - busy) * 1e-9)
    assert dict(s.device_ops)["decode:fusion"] == pytest.approx(160e-9)


def test_reduce_a_recorded_trace(tmp_path):
    prefill = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    decode = jax.jit(lambda x: (x * 2.0 + 1.0).max())
    x = jnp.ones((256, 256))
    prefill(x).block_until_ready()
    decode(x).block_until_ready()
    TA = jax.profiler.TraceAnnotation
    devtrace.start(str(tmp_path))
    try:
        with TA(devtrace.WINDOW):
            for _ in range(3):
                with TA("bench.prefill_call"):
                    prefill(x).block_until_ready()
                for _ in range(2):
                    with TA("bench.decode_call"):
                        decode(x).block_until_ready()
                with TA("bench.sample"):
                    time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    devices, spans = devtrace.load(devtrace.find(str(tmp_path)))
    assert {sp.name for sp in spans} >= {devtrace.WINDOW, "bench.prefill_call",
                                         "bench.decode_call", "bench.sample"}
    s = devtrace.reduce(devices, spans)
    assert s.phase_runs == {"prefill": 3, "decode": 6}
    assert sorted(devtrace.attribute(devices[0].execs, spans).values()) == [
        "decode", "prefill"]
    assert 0 < s.busy_s < s.window_s
    # an execution spans its operations and the short gaps between them
    assert 0.5 * s.busy_s < s.phase_s["prefill"] + s.phase_s["decode"] < s.window_s
    # the host slept 60 ms in `bench.sample` with nothing on the device
    assert dict(s.idle_gaps)["bench.sample"] >= 0.055
