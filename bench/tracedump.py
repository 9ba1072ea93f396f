"""Print what a benchmark trace holds, to read it by hand.

  python3 bench/tracedump.py runs/bench/trace-<workload> [out.json]

For each device: its executions by program (count, device seconds), the
clock offset and the phase each program is attributed to; the host's
``bench.*`` spans by name. With ``out.json`` also writes every execution
and span, small enough to study the attribution away from the chip.
"""
from __future__ import annotations

import json
import sys
from collections import Counter

import devtrace


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    devices, spans = devtrace.load(devtrace.find(argv[0]))
    print("spans", Counter(sp.name for sp in spans).most_common())
    for i, dev in enumerate(devices):
        off = devtrace.clock_offset(dev.execs, spans)
        label = devtrace.attribute(dev.execs, spans, off)
        secs, runs = Counter(), Counter()
        for x in dev.execs:
            secs[x.program] += (x.end - x.start) / 1e9
            runs[x.program] += 1
        print(f"device {i}: {len(dev.execs)} executions, {len(dev.ops)} "
              f"operations, clock offset {off} ns")
        for prog, s in secs.most_common(15):
            print(f"  {label.get(prog, '-'):8s} {runs[prog]:6d} {s:10.4f}s {prog}")
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump({"spans": [list(sp) for sp in spans],
                       "execs": [[list(x) for x in d.execs] for d in devices]}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
