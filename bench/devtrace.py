"""Reduction of a JAX profiler trace to device busy time, idle gaps and
the device time of the engine's two programs.

What a device ran: on a TPU, the ``/device:TPU:<n>`` planes hold one
event per program execution (line ``XLA Modules``, named by the module
and its fingerprint) and one per operation (line ``XLA Ops``). On the
CPU backend (the tests) the host plane holds the operations, each with
its ``program_id`` and ``run_id``; an execution is the extent of one
run's operations. Host spans are the benchmark's own ``bench.*``
annotations.

- busy time: the union of operation intervals inside the ``bench.window``
  span; the idle share is what is left of the span;
- each stretch of an idle gap is labelled by the innermost ``bench.*``
  span open then: what the host was doing while the device waited;
- a program is attributed to a phase by its launching annotation: each
  ``bench.<phase>_call`` span votes for the execution it launched, and a
  program takes the phase that launched more than half of its executions
  (each prefill bucket is a program of its own; an eager operation that
  runs around the calls takes none). The device's clock is put on the
  host's with an offset of its own in each trace, so the launched
  execution is the first to start after the span opened *plus one offset
  for the whole trace*: the offset under which the executions matched to
  the spans run longest (the engine's programs run far longer than the
  eager operations around them). A phase's device time is the sum of its
  programs' executions over the whole trace, the window and the drain
  after it.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import Counter, defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
PHASES = ("prefill", "decode")
#: how far (ns) the device's clock may sit from the host's in a trace
MAX_OFFSET = 5_000_000
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


class Exec(NamedTuple):
    """One execution of a program on a device (ns)."""
    start: int
    end: int
    program: str


class Op(NamedTuple):
    start: int
    end: int
    name: str


class Span(NamedTuple):
    start: int
    end: int
    name: str


class Device(NamedTuple):
    execs: List[Exec]
    ops: List[Op]


class Summary(NamedTuple):
    window_s: float
    busy_s: float
    phase_s: Dict[str, float]           # device seconds of each phase's programs
    phase_runs: Dict[str, int]          # executions of them in the trace
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    clock_offset_s: float = 0.0         # device clock minus host clock

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def start(trace_dir: str) -> None:
    """Start the profiler: device activity and the host's annotations,
    without the Python function tracer (it would stall every call)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def _op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..), ...`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _tpu_device(plane) -> Device:
    execs, ops = [], []
    for line in plane.lines:
        if line.name == "XLA Modules":
            for ev in line.events:
                s = int(ev.start_ns)
                execs.append(Exec(s, s + int(ev.duration_ns), ev.name))
        elif line.name == "XLA Ops":
            for ev in line.events:
                s = int(ev.start_ns)
                ops.append(Op(s, s + int(ev.duration_ns), _op_name(ev.name)))
    return Device(execs, ops)


def load(path: str) -> Tuple[List[Device], List[Span]]:
    """(what each device ran, host spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    tpus = [p for p in planes if _TPU_PLANE.match(p.name)]
    devices = [_tpu_device(p) for p in sorted(tpus, key=lambda p: p.name)]
    spans: List[Span] = []
    host_ops: List[Op] = []
    extent: Dict[int, List] = {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if name.startswith("bench."):
                    spans.append(Span(s, e, name))
                    continue
                if tpus:
                    continue
                prog = _stat(ev, "program_id")
                if prog is None:
                    continue
                host_ops.append(Op(s, e, name))
                ex = extent.setdefault(int(_stat(ev, "run_id") or 0),
                                       [s, e, str(prog)])
                ex[0], ex[1] = min(ex[0], s), max(ex[1], e)
    if not tpus and host_ops:
        devices = [Device([Exec(*x) for x in extent.values()], host_ops)]
    return devices, spans


def find(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    return paths[-1] if paths else None


def union(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def timeline(spans: Sequence[Span]) -> List[Tuple[int, str]]:
    """``[(time, label)]``: from each time on, the innermost span open
    (spans of one thread nest)."""
    segs: List[Tuple[int, str]] = []
    stack: List[Tuple[int, str]] = []
    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1][0] <= sp.start:
            end, _ = stack.pop()
            segs.append((end, stack[-1][1] if stack else "bench.loadgen"))
        segs.append((sp.start, sp.name))
        stack.append((sp.end, sp.name))
    while stack:
        end, _ = stack.pop()
        segs.append((end, stack[-1][1] if stack else "bench.loadgen"))
    return segs


def label_at(segs: List[Tuple[int, str]], t: int) -> str:
    i = bisect.bisect_right(segs, (t, "\uffff")) - 1
    return segs[i][1] if i >= 0 else "bench.loadgen"


def split(segs: List[Tuple[int, str]], lo: int, hi: int, into: Counter) -> None:
    """Add to ``into`` the seconds of [lo, hi) under each label."""
    i = bisect.bisect_right(segs, (lo, "\uffff")) - 1
    t = lo
    while t < hi:
        label = segs[i][1] if i >= 0 else "bench.loadgen"
        end = segs[i + 1][0] if i + 1 < len(segs) else hi
        end = min(max(end, t), hi)
        into[label] += (end - t) / 1e9
        t, i = end, i + 1


def _calls(spans: Sequence[Span]) -> List[Tuple[int, str]]:
    """``[(start, phase)]`` of the ``bench.<phase>_call`` spans."""
    out = []
    for sp in spans:
        if sp.name.startswith("bench.") and sp.name.endswith("_call"):
            phase = sp.name[len("bench."):-len("_call")]
            if phase in PHASES:
                out.append((sp.start, phase))
    return out


def clock_offset(execs: Sequence[Exec], spans: Sequence[Span]) -> int:
    """The offset (ns, device minus host) under which the executions
    matched to the call spans run longest (see module doc); the smallest
    such offset."""
    order = sorted(execs)
    starts = np.array([x.start for x in order], np.int64)
    dur = np.array([x.end - x.start for x in order] + [0], np.int64)
    calls = np.array(sorted(s for s, _ in _calls(spans)), np.int64)
    if not len(calls) or not len(starts):
        return 0
    # the matched set changes only where a span's start plus the offset
    # crosses an execution's start: try each such offset
    lo = np.searchsorted(starts, calls - MAX_OFFSET)
    hi = np.searchsorted(starts, calls + MAX_OFFSET)
    cands = np.unique(np.concatenate(
        [starts[a:b] - c for a, b, c in zip(lo, hi, calls)] or [np.zeros(1, np.int64)]))
    # an execution is launched once: count each matched one once
    score = np.array([dur[np.unique(np.searchsorted(starts, calls + d))].sum()
                      for d in cands])
    best = cands[score == score.max()]
    return int(best[np.argmin(np.abs(best))])       # the smallest that does


def attribute(execs: Sequence[Exec], spans: Sequence[Span],
              offset: Optional[int] = None) -> Dict[str, str]:
    """``{program: phase}`` by launching annotation (see module doc)."""
    if offset is None:
        offset = clock_offset(execs, spans)
    order = sorted(execs)
    starts = [x.start for x in order]
    runs = Counter(x.program for x in order)
    votes: Dict[str, Counter] = defaultdict(Counter)
    for start, phase in _calls(spans):
        i = bisect.bisect_left(starts, start + offset)
        if i < len(order):
            votes[order[i].program][phase] += 1
    out = {}
    for prog, c in votes.items():
        phase, n = c.most_common(1)[0]
        if 2 * n > runs[prog]:
            out[prog] = phase
    return out


def reduce(devices: List[Device], spans: List[Span], *, top: int = 10
           ) -> Optional[Summary]:
    """Summary over the ``bench.window`` span, averaged over devices."""
    win = [sp for sp in spans if sp.name == WINDOW]
    if not win or not any(d.ops for d in devices):
        return None
    lo, hi = win[0].start, win[0].end
    inner = [sp for sp in spans if sp.name != WINDOW]
    segs = timeline(inner)
    busy, phase_s, phase_runs = 0.0, Counter(), Counter()
    offsets: List[int] = []
    by_op: Counter = Counter()
    gaps: Counter = Counter()
    for dev in devices:
        off = clock_offset(dev.execs, inner)
        offsets.append(off)
        # device times less the offset are host times
        merged = [(s - off, e - off) for s, e in
                  union([(o.start, o.end) for o in dev.ops], lo + off, hi + off)]
        busy += sum(e - s for s, e in merged) / 1e9
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                split(segs, prev, s, gaps)
            prev = max(prev, e)
        label = attribute(dev.execs, inner, off)
        for x in dev.execs:
            if x.program in label:
                phase_s[label[x.program]] += (x.end - x.start) / 1e9
                phase_runs[label[x.program]] += 1
        execs = sorted(dev.execs)
        starts = [x.start for x in execs]
        for o in dev.ops:
            if o.end <= lo + off or o.start >= hi + off:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            prog = execs[i].program if i >= 0 and o.start < execs[i].end else None
            by_op[f"{label.get(prog, 'other')}:{o.name}"] += \
                (min(o.end, hi + off) - max(o.start, lo + off)) / 1e9
    n = len(devices)
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / n,
        phase_s={k: v / n for k, v in phase_s.items()},
        phase_runs={k: v // n for k, v in phase_runs.items()},
        device_ops=[(k, v / n) for k, v in by_op.most_common(top) if v > 0],
        idle_gaps=[(k, v / n) for k, v in gaps.most_common(top)],
        clock_offset_s=float(np.median(offsets)) / 1e9)
