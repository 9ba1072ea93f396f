"""Find the knee of an open-loop cell: one process, one set-up, then the
cell's mix offered at each rate in turn.

  python3 bench/sweep.py --workload internlm2-chat --seed 7 --seconds 30 \
      --rates 1.5,2,2.5,3

Prints one JSON line per rate: offered and completed requests/s, time to
first token (p50, p90), the queue wait of the first and the last third of
the requests (a queue that grows through the window is past the knee),
the requests still waiting when the window closed, and token gaps. The
cell's fixed ``rate_per_s`` is then set at about 0.8 x the knee.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
import stats


def summarise(rate: float, seconds: float, drive) -> dict:
    recs = drive.records
    third = max(1, len(recs) // 3)
    waits = [r.admit - r.due if r.admit is not None else float("inf")
             for r in recs]
    pct = stats.percentile
    return {
        "rate": rate, "offered_per_s": len(recs) / seconds,
        "completed_in_window_per_s": sum(
            1 for r in recs if r.stamps and r.stamps[-1] <= seconds) / seconds,
        "ttft_p50_ms": 1000 * pct(stats.ttfts(recs), 50),
        "ttft_p90_ms": 1000 * pct(stats.ttfts(recs), 90),
        "wait_p50_first_third_ms": 1000 * pct(waits[:third], 50),
        "wait_p50_last_third_ms": 1000 * pct(waits[-third:], 50),
        "waiting_at_close": sum(1 for r in recs
                                if r.admit is None or r.admit > seconds),
        "tpot_p50_ms": 1000 * pct(stats.token_gaps(recs), 50),
        "tpot_p99_ms": 1000 * pct(stats.token_gaps(recs), 99),
        "unfinished": sum(1 for r in recs if not r.done),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    args = ap.parse_args(argv)
    cell, _, _ = run.open_cell(args.workload)
    counter = run.CompileCounter()
    import jax
    jax.monitoring.register_event_duration_secs_listener(counter)
    _, engine, _ = run.set_up(cell, args.seed, trace=False)
    for rate in (float(r) for r in args.rates.split(",")):
        drive, _, _ = run.measure(cell, engine, seed=args.seed,
                                  seconds=args.seconds, trace=False,
                                  counter=counter, rate=rate, drain_s=15.0)
        print(json.dumps(summarise(rate, args.seconds, drive)), flush=True)
        # what the drain left unfinished does not load the next rate
        engine.queue.clear()
        engine.active = [None] * engine.slots
    return 0


if __name__ == "__main__":
    sys.exit(main())
