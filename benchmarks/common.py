"""Shared benchmark utilities. Every bench prints ``name,us_per_call,derived``
CSV rows (plus richer derived columns per figure); rows are also
collected in-process so drivers can emit machine-readable output
(benchmarks/run.py --json)."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Callable, Dict, List

import jax

# every row() call lands here; run.py tags rows with their section and
# drains the list between sections.
RESULTS: List[Dict[str, object]] = []


def time_call(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall time (us) of a jitted call."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def row(name: str, us: float, derived: str = "") -> str:
    line = f"{name},{us:.2f},{derived}"
    print(line)
    RESULTS.append({"name": name, "us": us, "derived": derived})
    return line


def run_host_cpu_child(code: str, timeout: float = 600) -> None:
    """Run ``python -c code`` pinned to the host CPU (it sets its own
    virtual-device count) and record each ``name,us,derived`` line it
    prints as a row labelled ``host-cpu``. A failed child fails the
    calling section."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError(f"host-cpu child exited {out.returncode}:\n"
                           f"{out.stderr[-1500:]}")
    for line in out.stdout.strip().splitlines():
        name, us, derived = line.split(",", 2)
        row(name, float(us), f"host-cpu {derived}")
