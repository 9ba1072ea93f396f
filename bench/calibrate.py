"""Readings that the correctness limit of a cell is set from.

  python3 bench/calibrate.py --workload internlm2-chat --seeds 11,12,13 \
      --seconds 50 [--control]

Runs the cell once for each seed in one process, as ``run.py`` does
(weights from the seed, the engine, the window at the cell's own load,
the comparison that decides ``correct``), and prints one JSON line per
seed with ``correct`` and the numbers compared. With ``--control`` the
float8 control's tokens are compared in the program's place, through the
same comparison; every seed must then read not correct.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell, peaks, devices = run.open_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                           peaks=peaks, devices=devices, control=args.control)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
        del res
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
