"""The serving engine's own trace: its ``serve.*`` host spans, each
device's executions by program name, and each operation's named-scope
path, on one clock.

  python3 bench/progtrace.py runs/bench/trace-<workload>

prints what a trace holds, to read it by hand: planes and lines, span
counts, programs, the clock and one operation's stats.

- Host spans: the engine's ``serve.*`` spans (``repro.serve.engine``)
  with their keywords, and the window, ``bench.window``.
- Programs: on a TPU, the ``XLA Modules`` events of each
  ``/device:TPU:<n>`` plane, named ``<module>(<fingerprint>)``; on the
  CPU backend, the extent of each run's operations (their ``hlo_module``
  and ``run_id`` stats). The suffix is stripped: the engine's programs
  are ``jit_decode_step`` and ``jit_prefill`` (one per bucket).
- Scope path of an operation: the ``op_name`` of the instruction of
  the same name in its program's HLO, which the trace keeps on the
  ``/host:metadata`` plane (no operation event carries it, on the v5e
  or the CPU). The model's scopes (``embed``, ``attn``, ``ssm``, ``ffn``,
  ``lm_head``) are components of that path.
- One clock: the device-minus-host offset is the median, over the
  ``serve.decode_call`` and ``serve.prefill_call`` spans, of the start
  of the first execution of the span's own program after it opened,
  minus the span's start (so it includes the launch). Where the device's
  executions and the host's launch events both carry a ``run_id``, the
  two programs' executions are matched on it instead.
- Leaf operations only: an operation that contains another of its line
  (the layer loop's ``while`` around its body) does not count, so no
  device time counts twice.
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import devtrace

ROOT = Path(__file__).resolve().parent.parent
#: where ``run.measure`` writes a traced run's trace, ``trace-<workload>``
OUT_DIR = ROOT / "runs" / "bench"
DECODE, PREFILL = "jit_decode_step", "jit_prefill"
CALLS = {"serve.decode_call": DECODE, "serve.prefill_call": PREFILL}
_SUFFIX = re.compile(r"\([^()]*\)$")


class Span(NamedTuple):
    start: int
    end: int
    name: str
    args: dict


class Exec(NamedTuple):
    """One execution of a program on a device (ns, device clock)."""
    start: int
    end: int
    program: str                        # module name, suffix stripped
    run_id: Optional[int] = None


class Op(NamedTuple):
    start: int
    end: int
    name: str
    scope: str                          # named-scope path, "" if unknown


class Device(NamedTuple):
    execs: List[Exec]                   # sorted by start
    ops: List[Op]                       # leaf operations, sorted by start
    offset: Optional[int]               # device clock minus host clock, ns


class Trace(NamedTuple):
    spans: List[Span]                   # serve.* and the window, host clock
    window: Optional[Tuple[int, int]]   # bench.window, host clock
    devices: List[Device]
    joined_by: str                      # "run_id", "name" or "none"


# ----------------------------------------------------------------------
# pieces tested on made-up intervals
# ----------------------------------------------------------------------

def program(module: str) -> str:
    """``jit_prefill(1234)`` -> ``jit_prefill``."""
    return _SUFFIX.sub("", module)


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations of one line that contain no other operation."""
    order = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt.start >= o.end or o.end == o.start]


def fit_offset(execs: Sequence[Exec], spans: Sequence[Span],
               launches: Optional[Dict[int, int]] = None) -> Optional[int]:
    """Device clock minus host clock (ns): the median over pairs of a
    host start and the device start of what it launched (module doc).
    By name, a span's pair is the first execution of its program that
    starts no earlier than ``devtrace.MAX_OFFSET`` before it: the clocks
    sit closer than that, and one program's executions lie further apart
    (the engine reads each step's tokens back before the next launch)."""
    if launches:
        # the engine's calls find the device idle or nearly so; an eager
        # operation launched behind a step waits in the device's queue
        pairs = [x.start - launches[x.run_id] for x in execs
                 if x.program in CALLS.values() and x.run_id in launches]
        if pairs:
            return int(np.median(pairs))
    starts: Dict[str, List[int]] = defaultdict(list)
    for x in sorted(execs):
        starts[x.program].append(x.start)
    pairs = []
    for sp in spans:
        own = starts.get(CALLS.get(sp.name, ""), [])
        i = bisect.bisect_left(own, sp.start - devtrace.MAX_OFFSET)
        if i < len(own):
            pairs.append(own[i] - sp.start)
    return int(np.median(pairs)) if pairs else None


def in_window(dev: Device, window: Tuple[int, int], name: str) -> List[Exec]:
    """Executions of program ``name`` that start in the (host) window."""
    lo, hi = window[0] + dev.offset, window[1] + dev.offset
    return [x for x in dev.execs if x.program == name and lo <= x.start < hi]


def decode_gaps(dev: Device, window: Tuple[int, int]) -> List[int]:
    """Device-clock gaps (ns) from the end of one decode execution to the
    start of the next, where no prefill execution lies between."""
    runs = sorted(in_window(dev, window, DECODE) + in_window(dev, window, PREFILL))
    return [b.start - a.end for a, b in zip(runs, runs[1:])
            if a.program == b.program == DECODE]


def scope_ns(dev: Device, execs: Sequence[Exec], scope: Optional[str]) -> int:
    """Leaf-operation device time (ns) inside ``execs`` whose scope path
    has the component ``scope`` (every operation for None)."""
    execs = sorted(execs)
    starts = [x.start for x in execs]
    total = 0
    for o in dev.ops:
        i = bisect.bisect_right(starts, o.start) - 1
        if i < 0 or o.start >= execs[i].end:
            continue
        if scope is None or scope in o.scope.split("/"):
            total += o.end - o.start
    return total


# ----------------------------------------------------------------------
# the metrics' reads
# ----------------------------------------------------------------------

def _joined(trace: Optional[Trace]) -> List[Device]:
    if trace is None or trace.window is None:
        return []
    return [d for d in trace.devices if d.offset is not None]


def decode_gap_ms_p50(trace: Optional[Trace]) -> Optional[float]:
    """Median decode-to-decode device gap in the window (ms), over the
    devices' pooled gaps."""
    gaps = [g for d in _joined(trace) for g in decode_gaps(d, trace.window)]
    return float(np.median(gaps)) / 1e6 if gaps else None


def decode_scope_ms(trace: Optional[Trace], scope: Optional[str]
                    ) -> Optional[float]:
    """Leaf-operation device time under ``scope`` per decode execution in
    the window (ms), averaged over devices."""
    per = []
    for d in _joined(trace):
        runs = in_window(d, trace.window, DECODE)
        if runs:
            per.append(scope_ns(d, runs, scope) / len(runs) / 1e6)
    return float(np.mean(per)) if per else None


# ----------------------------------------------------------------------
# loading
# ----------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; length-delimited
    values come as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            val, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _each(buf, field: int) -> Iterator:
    return (v for f, v in _fields(buf) if f == field)


def _first(buf, field: int):
    return next(_each(buf, field), None)


def hlo_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{module(id): {instruction: op_name}}`` from the HLO protos that
    the trace keeps on its ``/host:metadata`` plane (XSpace.planes 1;
    XPlane.name 2, event_metadata 4 (map entry value 2);
    XEventMetadata.name 2, stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _each(memoryview(Path(path).read_bytes()), 1):
        if _text(_first(plane, 2) or b"") != "/host:metadata":
            continue
        for entry in _each(plane, 4):
            meta = _first(entry, 2)
            names = out.setdefault(_text(_first(meta, 2) or b""), {})
            for stat in _each(meta, 5):
                proto = _first(stat, 6)
                module = None if proto is None else _first(proto, 1)
                if module is None:
                    continue
                for comp in _each(module, 3):
                    for ins in _each(comp, 2):
                        name, md = _first(ins, 1), _first(ins, 7)
                        op_name = None if md is None else _first(md, 2)
                        if name is not None and op_name is not None:
                            names[_text(name)] = _text(op_name)
    return out


_CACHE: Dict[Tuple[str, float], Trace] = {}


def load(path: str) -> Trace:
    """The trace in ``path`` (an ``.xplane.pb``); loaded once per file."""
    key = (path, Path(path).stat().st_mtime)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = _load(path)
    return _CACHE[key]


def _load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    hlo = hlo_scopes(path)
    spans: List[Span] = []
    launches: Dict[int, int] = {}
    host_lines: Dict[str, List[Op]] = defaultdict(list)
    extent: Dict[int, List] = {}
    tpus = sorted((p for p in planes if devtrace._TPU_PLANE.match(p.name)),
                  key=lambda p: p.name)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, s = ev.name, int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if name.startswith("serve.") or name == devtrace.WINDOW:
                    spans.append(Span(s, e, name, dict(ev.stats)))
                    continue
                st = dict(ev.stats)
                run_id = st.get("run_id")
                if run_id is None:
                    continue
                if "hlo_module" not in st:      # a host's launch event
                    launches[int(run_id)] = min(s, launches.get(int(run_id), s))
                    continue
                if tpus:
                    continue
                # the CPU backend: its operations run on host threads
                module = f"{st['hlo_module']}({st.get('program_id')})"
                host_lines[line.name].append(
                    Op(s, e, name, hlo.get(module, {}).get(name, "")))
                ex = extent.setdefault(int(run_id), [s, e, st["hlo_module"]])
                ex[0], ex[1] = min(ex[0], s), max(ex[1], e)
    spans.sort()
    devices: List[Tuple[List[Exec], List[Op]]] = []
    for plane in tpus:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((int(ev.start_ns), int(ev.duration_ns), ev.name,
                       dict(ev.stats).get("run_id"))
                      for ev in lines.get("XLA Modules", []))
        execs = [Exec(s, s + d, program(m), None if r is None else int(r))
                 for s, d, m, r in mods]
        keys = [x.start for x in execs]
        line_ops = []
        for ev in lines.get("XLA Ops", []):
            s = int(ev.start_ns)
            i = bisect.bisect_right(keys, s) - 1
            name = devtrace._op_name(ev.name)
            scope = hlo.get(mods[i][2], {}).get(name, "") if i >= 0 else ""
            line_ops.append(Op(s, s + int(ev.duration_ns), name, scope))
        devices.append((execs, leaves(line_ops)))
    if not tpus and extent:
        execs = sorted(Exec(s, e, m, rid) for rid, (s, e, m) in extent.items())
        ops = [o for line_ops in host_lines.values() for o in leaves(line_ops)]
        devices.append((execs, ops))
    win = next(((sp.start, sp.end) for sp in spans if sp.name == devtrace.WINDOW),
               None)
    calls = [sp for sp in spans if sp.name in CALLS]
    out, joined = [], set()
    for execs, ops in devices:
        by_run = launches if any(x.run_id in launches for x in execs) else None
        off = fit_offset(execs, calls, by_run)
        joined.add("run_id" if by_run else ("name" if off is not None else "none"))
        out.append(Device(execs, sorted(ops), off))
    return Trace(spans, win, out, "+".join(sorted(joined)) or "none")


def of(run) -> Optional[Trace]:
    """The trace of ``run``'s window, or None: an untraced run, or no
    trace file where ``run.measure`` writes it."""
    if getattr(run, "trace", None) is None:
        return None
    path = devtrace.find(str(OUT_DIR / f"trace-{run.cell.name}"))
    return load(path) if path else None


def main(argv=None) -> int:
    from jax.profiler import ProfileData
    argv = sys.argv[1:] if argv is None else argv
    path = devtrace.find(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}")
        return 1
    print("file", path)
    launch_names = Counter()
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for j, ev in enumerate(evs):
                st = dict(ev.stats)
                if plane.name.startswith("/host:") and "run_id" in st \
                        and "hlo_module" not in st:
                    launch_names[ev.name] += 1
                if j < 2:
                    st = {k: str(v)[:120] for k, v in st.items()}
                    print(f"    {ev.name[:100]!r} {st}")
    print("host launch events with a run_id:", launch_names.most_common(10))
    t = load(path)
    print("spans", Counter(sp.name for sp in t.spans).most_common())
    print("window", t.window, "clock joined by", t.joined_by)
    for i, d in enumerate(t.devices):
        secs, runs = Counter(), Counter()
        for x in d.execs:
            secs[x.program] += (x.end - x.start) / 1e9
            runs[x.program] += 1
        print(f"device {i}: {len(d.execs)} executions, {len(d.ops)} leaf "
              f"operations, offset {d.offset} ns")
        for prog, s in secs.most_common(10):
            print(f"  {runs[prog]:6d} {s:10.4f}s {prog}")
        print(f"  leaf operations with a scope path: "
              f"{sum(1 for o in d.ops if o.scope)} of {len(d.ops)}")
        if t.window is None or d.offset is None:
            continue
        runs = in_window(d, t.window, DECODE)
        starts = [x.start for x in runs]
        by_scope, unscoped = Counter(), Counter()
        for o in d.ops:
            i = bisect.bisect_right(starts, o.start) - 1
            if i < 0 or o.start >= runs[i].end:
                continue
            by_scope[o.scope.rsplit("/", 1)[0]] += (o.end - o.start) / 1e6
            if not o.scope:
                unscoped[o.name] += (o.end - o.start) / 1e6
        print(f"  ms per decode execution in the window ({len(runs)}), by scope:")
        for k, v in by_scope.most_common(15):
            print(f"    {v / max(len(runs), 1):9.4f} {k}")
        print("  of it with no scope, by operation:")
        for k, v in unscoped.most_common(8):
            print(f"    {v / max(len(runs), 1):9.4f} {k}")
    for i, d in enumerate(_joined(t)):
        lo, hi = t.window
        inner = [devtrace.Span(sp.start, sp.end, sp.name) for sp in t.spans
                 if sp.name != devtrace.WINDOW]
        segs = devtrace.timeline(inner)
        idle, prev = Counter(), lo
        busy = devtrace.union([(o.start - d.offset, o.end - d.offset)
                               for o in d.ops], lo, hi)
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                devtrace.split(segs, prev, s, idle)
            prev = max(prev, e)
        n = Counter(sp.name for sp in inner if lo <= sp.start < hi)
        print(f"device {i}: idle in the window by innermost span, s "
              f"(spans in the window, idle ms per span):")
        for k, v in idle.most_common(10):
            print(f"    {v:9.4f} {k} ({n[k]}, {1e3 * v / max(n[k], 1):.4f})")
    print("decode_gap_ms_p50", decode_gap_ms_p50(t))
    for scope in (None, "embed", "attn", "ssm", "ffn", "lm_head"):
        print(f"decode ms per execution under {scope}:", decode_scope_ms(t, scope))
    return 0


if __name__ == "__main__":
    sys.exit(main())
