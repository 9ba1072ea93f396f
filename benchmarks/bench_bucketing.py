"""Paper Figure 10 / Advice #4: doorbell batching = gradient bucketing.

B per-tensor collectives vs one fused flat collective: we lower both on
a fake 8-device mesh and count collective ops + bytes, then time them.
The analytic part applies the path latency model: B ops pay B latencies."""
from __future__ import annotations

from repro.core import hw

from benchmarks.common import row, run_host_cpu_child


def model_part() -> None:
    nbytes = 64 << 20
    for b in (1, 8, 64, 256):
        t_unbucketed = b * (hw.ICI_LAT * 30 + (nbytes / b) / hw.ICI_BW_PER_LINK)
        t_bucketed = hw.ICI_LAT * 30 + nbytes / hw.ICI_BW_PER_LINK
        row(f"fig10/model/B{b}", t_unbucketed * 1e6,
            f"bucketed_us={t_bucketed*1e6:.1f} speedup={t_unbucketed/t_bucketed:.2f}x")


def executable_part() -> None:
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
grads = [jnp.ones((64, 64)) * i for i in range(32)]
with jax.set_mesh(mesh):
    def unbucketed(gs):
        return [jax.lax.psum(g, "data") for g in gs]
    def bucketed(gs):
        flat = jnp.concatenate([g.reshape(-1) for g in gs])
        out = jax.lax.psum(flat, "data")
        return out
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    for name, fn in (("unbucketed", unbucketed), ("bucketed", bucketed)):
        f = jax.jit(lambda gs, fn=fn: shard_map(fn, mesh=mesh,
                    in_specs=([P()]*32,), out_specs=(([P()]*32) if name=="unbucketed" else P()),
                    check_vma=False)(gs))
        co = f.lower(grads).compile()
        n_ar = co.as_text().count("all-reduce(")
        out = f(grads); jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(20):
            jax.block_until_ready(f(grads))
        dt = (time.perf_counter() - t0)/20
        print(f"fig10/exec/{name},{dt*1e6:.1f},all_reduces={n_ar}")
"""
    run_host_cpu_child(code)


def main() -> None:
    print("# fig10: doorbell batching == gradient bucketing")
    model_part()
    executable_part()


if __name__ == "__main__":
    main()
