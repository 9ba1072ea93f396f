"""Run one benchmark cell once on the accelerator.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
(``bench/configs/<config>.json``, which names its architecture's model
module, ``bench/models/<model>.py``), a traffic mix
(``bench/traffic/<traffic>.json``) and, where the mix needs one, a cell
file (``bench/cells/<workload>.json``: the fixed arrival rate, the
correctness limit). Every metric is read by ``bench/metrics/<name>.py``,
the part of its name before the first ``.``.

Set-up makes the weights from ``--seed`` on the device, builds the
program's ``ServeEngine`` (synchronous, no simulated clock) and warms up
each prefill bucket the mix can produce and the decode step. Then the
load generator drives ``step()`` for ``--seconds`` on the host clock and
follows every request submitted in the window to its last token. After
that, with the engine freed, a sample of the served requests is compared
with the plain float32 reference (``check.py``).

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the same run. ``--control 1``
compares the float8 control's tokens in the program's place; such a run
must read not correct. The last line
of standard output is one JSON object; the numbers compared for
``correct`` also end standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import numpy as np  # noqa: E402

import check  # noqa: E402
import devtrace  # noqa: E402
import flops  # noqa: E402
import loadgen  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402
from arch import Arch  # noqa: E402

#: traces and anything too long for standard output
OUT_DIR = ROOT / "runs" / "bench"
#: how long requests submitted in the window may take to finish after it
DRAIN_S = 60.0
NO_DEVICE = 3


def process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
    uptime = float(Path("/proc/uptime").read_text().split()[0])
    return uptime - start / ticks


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Cell:
    """A workload of ``BENCHMARK.json`` and the data files it names."""

    def __init__(self, spec: dict, name: str, *, arch: Arch | None = None):
        found = [w for w in spec["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.workload = w = found[0]
        self.name, self.chips = name, int(w["chips"])
        conf = [c for c in spec["configs"] if c["name"] == w["config"]][0]
        self.arch = arch or Arch.from_file(ROOT / conf["file"])
        self.mix = traffic.Mix.from_dict(
            json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()))
        cell_file = BENCH / "cells" / f"{name}.json"
        self.params = json.loads(cell_file.read_text()) if cell_file.exists() else {}
        self.end_to_end = [m for m in spec["end_to_end"] if self._has(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def gap_limit(self) -> float:
        return float(self.params["logit_gap_limit"])


class Metrics:
    """Loads the reader ``metrics/<base>.py`` of each metric name."""

    def __init__(self):
        self.dir = BENCH / "metrics"
        self._mods = {}

    def read(self, name: str, run) -> float | None:
        base = name.split(".", 1)[0]
        if base not in self._mods:
            path = self.dir / f"{base}.py"
            spec = importlib.util.spec_from_file_location(f"metric_{base}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._mods[base] = mod
        val = self._mods[base].read(run)
        return None if val is None else float(val)


class Run:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def work(self, phase: str) -> tuple:
        """(FLOPs, bytes) the algorithm needs for every call of ``phase``
        that the load generator made (all of them lie in the trace)."""
        f = b = 0
        for st in self.drive.steps:
            if phase == "prefill":
                for n in st.prefills:
                    df, db = flops.prefill(self.arch, n)
                    f, b = f + df, b + db
            elif st.decode_lens:
                df, db = flops.decode(self.arch, st.decode_lens)
                f, b = f + df, b + db
        return f, b


class CompileCounter:
    """Counts executables built or loaded while ``armed``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed, self.count = False, 0
        self.names: list = []

    def __call__(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1
            self.names.append(kw.get("fun_name"))


def _annotate(engine, jax):
    """Wrap the engine's calls in ``bench.*`` profiler annotations: the
    two jitted programs (``bench.prefill_call``/``bench.decode_call``) and
    the host methods of a step around them."""
    TA = jax.profiler.TraceAnnotation

    def wrap(fn, label):
        def inner(*a, **k):
            with TA(label):
                return fn(*a, **k)
        return inner

    engine._prefill = wrap(engine._prefill, "bench.prefill_call")
    engine._decode = wrap(engine._decode, "bench.decode_call")
    for meth, label in (("_prefill_request", "bench.prefill"),
                        ("_splice_cache", "bench.splice"),
                        ("_decode_compute", "bench.decode"),
                        ("_finish_decode", "bench.sample"),
                        ("step", "bench.step")):
        setattr(engine, meth, wrap(getattr(engine, meth), label))


def warm_up(engine, mix: traffic.Mix, vocab: int, Request) -> int:
    """Serve, to their end, one request for each prefill bucket the mix can
    produce, and enough to fill every slot at once; returns the buckets."""
    longest = {}
    for n in mix.prompt_lengths():
        longest[engine._bucket_len(n)] = n
    lens = list(longest.values())
    count = max(engine.slots, len(lens))
    for i in range(count):
        engine.submit(Request(rid=-1 - i, prompt=np.full(
            (lens[i % len(lens)],), i % vocab, np.int32), max_new_tokens=3))
    engine.run()
    return len(longest)


def set_up(cell: Cell, seed: int, *, trace: bool, engine_hook=None):
    """Weights from ``seed`` on the device, the engine, and its warm-up.
    ``engine_hook(engine)`` may replace parts of the engine (tests)."""
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import Request, ServeEngine

    a = cell.arch
    if cell.mix.max_positions() > a.max_len - 1:
        raise ValueError(f"{cell.mix.name} needs {cell.mix.max_positions()} "
                         f"positions, the cache holds {a.max_len}")
    ages = {"start": process_age()}
    params = weights.make(a, seed, a.weights_dtype)
    jax.block_until_ready(params)
    ages["weights"] = process_age()
    engine = ServeEngine(a.model_config(), params, slots=a.slots,
                         max_len=a.max_len, cache_dtype=jnp.dtype(a.cache_dtype))
    if engine_hook is not None:
        engine_hook(engine)
    if trace:
        _annotate(engine, jax)
    ages["engine"] = process_age()
    buckets = warm_up(engine, cell.mix, a.vocab, Request)
    ages["warm_up"] = process_age()
    log("[setup] process age at the end of each part, s: "
        + " ".join(f"{k}={v:.2f}" for k, v in ages.items()))
    return params, engine, buckets


def measure(cell: Cell, engine, *, seed: int, seconds: float, trace: bool,
            counter: CompileCounter, rate: float | None = None,
            drain_s: float = DRAIN_S):
    """The timed window and its drain; returns (drive, trace summary,
    setup_s). ``rate`` overrides the cell's (the knee sweep)."""
    import jax
    from repro.serve.engine import Request

    for k in engine.stats:
        engine.stats[k] = 0
    items = traffic.generate(cell.mix, seed=seed, seconds=seconds,
                             vocab=cell.arch.vocab,
                             rate=rate or cell.params.get("rate_per_s"))
    trace_dir = OUT_DIR / f"trace-{cell.name}"
    window = {}

    def on_open():
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            devtrace.start(str(trace_dir))
            window["span"] = jax.profiler.TraceAnnotation(devtrace.WINDOW)
            window["span"].__enter__()
        window["setup_s"] = process_age()
        counter.armed = True

    def on_close():
        if trace:
            window["span"].__exit__(None, None, None)

    def make_request(item):
        return Request(rid=item.rid, prompt=item.prompt, max_new_tokens=item.max_new)

    before = counter.count
    drive = loadgen.drive(engine, items, seconds=seconds, drain_s=drain_s,
                          backlog=cell.mix.arrivals == "backlog",
                          make_request=make_request, on_open=on_open,
                          on_close=on_close)
    counter.armed = False
    log(f"[window] setup_s={window['setup_s']} steps={len(drive.steps)} "
        f"requests={len(drive.records)} "
        f"compiles_in_window={counter.count - before} {counter.names[before:]} "
        f"closed_at={drive.closed_at}")
    summary = None
    if trace:
        jax.profiler.stop_trace()
        t = time.perf_counter()
        path = devtrace.find(str(trace_dir))
        summary = devtrace.reduce(*devtrace.load(path)) if path else None
        log(f"[trace] read in {time.perf_counter() - t:.1f}s: {summary}")
    return drive, summary, window["setup_s"]


def served(drive) -> list:
    """``(prompt, tokens)`` of every request that finished."""
    return [(np.asarray(r.request.prompt), list(r.request.out_tokens))
            for r in drive.records if r.done]


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             peaks: dict, devices, engine_hook=None,
             control: bool = False) -> dict:
    """One run of ``cell``; returns the result object (see module doc).
    With ``control`` the tokens compared are the float8 control's first
    choices at the served positions, in the program's place."""
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    params, engine, buckets = set_up(cell, seed, trace=trace,
                                     engine_hook=engine_hook)
    log(f"[setup] buckets={buckets} age={process_age()}")
    drive, summary, setup_s = measure(cell, engine, seed=seed, seconds=seconds,
                                      trace=trace, counter=counter)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:cell.chips])
    stats = dict(engine.stats)
    engine.cache = None                 # the program's state, freed
    del engine
    gc.collect()

    t_check = time.perf_counter()
    rows = check.sample(served(drive), seed)
    gap, n_tok = (check.widest_gap(cell.arch, params, rows, control=control)
                  if rows else (float("inf"), 0))
    unfinished = sum(1 for r in drive.records if not r.done)
    log(f"[check] rows={len(rows)} tokens={n_tok} control={control} "
        f"seconds={time.perf_counter() - t_check}")
    checks = {"max_logit_gap": {"value": gap, "limit": cell.gap_limit},
              "unfinished": {"value": unfinished, "limit": 0}}
    correct = bool(rows) and all(c["value"] <= c["limit"] for c in checks.values())

    run = Run(cell=cell, arch=cell.arch, peaks=peaks, drive=drive, stats=stats,
              trace=summary, setup_s=setup_s, seconds=seconds)
    reader = Metrics()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = setup_s if m["name"] == "setup_s" else reader.read(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": int(memory_peak)}
    result = {"correct": correct, "attempted": len(drive.records),
              "failed": unfinished, "metrics": metrics, "device": device}
    if trace and summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                               "idle_gaps": [list(x) for x in summary.idle_gaps]}
    result["checks"] = checks
    return result


def open_cell(name: str):
    """The cell, the chip's peaks and the devices, with the compilation
    cache in place; exits with ``NO_DEVICE`` where there is no TPU, too
    few chips, or no peaks for the chip."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(spec, name)
    log(f"[setup] process age before JAX's backend: {process_age():.2f}")
    import jax
    devices = jax.devices()
    log(f"[setup] process age with the backend up: {process_age():.2f}")
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s)")
        raise SystemExit(NO_DEVICE)
    table = json.loads((BENCH / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in table:
        log(f"no peaks for device kind {kind!r} in bench/peaks.json")
        raise SystemExit(NO_DEVICE)
    # JAX_COMPILATION_CACHE_DIR where it is set, else <checkout>/.jax_cache
    from repro.launch.compile_cache import enable_compile_cache
    log(f"[cache] {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    return cell, table[kind], devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the float8 control's tokens in the "
                         "program's place (it must come out not correct)")
    args = ap.parse_args(argv)

    cell, peaks, devices = open_cell(args.workload)
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), peaks=peaks, devices=devices,
                      control=bool(args.control))
    for name, c in result["checks"].items():
        log(f"{name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
