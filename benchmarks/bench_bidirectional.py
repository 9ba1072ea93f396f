"""Paper Figure 5: bidirectional multiplexing + path combinations.

(a) opposite-direction flows on one bidirectional link reach ~2x the
    one-way limit; same-direction flows split it (planner budget model);
(b) executable analogue: bidirectional ring all-gather vs one-way ring
    on a CPU mesh — wall time + the HLO-counted ppermute traffic."""
from __future__ import annotations

from repro.core.fabric import Alternative, Fabric, Path, Use

from benchmarks.common import row, run_host_cpu_child

N = 200e9 / 8


def model_part() -> None:
    router = Fabric.of(Path("net", N, latency=1e-6, kind="ici")).router()
    read = Alternative("read", uses=[Use("net", out=1)])
    write = Alternative("write", uses=[Use("net", in_=1)])
    read2 = Alternative("read2", uses=[Use("net", out=1)])
    relay = Alternative("relay", uses=[Use("net", out=1, in_=1)])
    for name, combo in [("read_write", [read, write]),
                        ("read_read", [read, read2]),
                        ("relay_alone", [relay]),
                        ("relay_plus_read", [relay, read])]:
        _, total = router.allocate(combo)
        row(f"fig5/{name}", 0.0, f"GBps={total * 8 / 1e9:.0f}Gbps")


def executable_part() -> None:
    """Runs the ring-collective microbench on 8 fake devices."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.collectives import all_gather_bidirectional, ring_all_gather
from jax import shard_map
import functools
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jnp.ones((1024, 256))
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    for bidir in (False, True):
        fn = jax.jit(lambda a, b=bidir: shard_map(
            functools.partial(ring_all_gather, axis="data", bidirectional=b),
            mesh=mesh, in_specs=(P("data", None),), out_specs=P(None, None),
            check_vma=False)(a))
        out = fn(xs); jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(fn(xs))
        dt = (time.perf_counter() - t0) / 10
        hlo = fn.lower(xs).compile().as_text()
        nperm = hlo.count("collective-permute(")
        print(f"fig5b/ring_ag_bidir={bidir},{dt*1e6:.1f},permutes={nperm}")
"""
    run_host_cpu_child(code)


def main() -> None:
    print("# fig5: bidirectional multiplexing (budget model + executable)")
    model_part()
    executable_part()


if __name__ == "__main__":
    main()
