"""Multi-device correctness checks (run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 by test_distributed.py).

Covers: explicit collectives == lax oracles, EP MoE == dense ref,
context-parallel decode == local decode, compressed pod-sync training
step ~= exact, elastic resharding.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

assert len(jax.devices()) == 8, jax.devices()


def check_collectives():
    from repro.core.collectives import (all_gather_bidirectional,
                                        all_reduce_compressed,
                                        all_reduce_hierarchical)
    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        x = jax.random.normal(key, (16, 8))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        got = jax.jit(lambda a: all_gather_bidirectional(a, mesh, "data"))(xs)
        assert float(jnp.abs(got - x).max()) == 0.0
        y = jax.random.normal(key, (12, 5))
        out = jax.jit(lambda a: all_reduce_hierarchical(a, mesh, "data", "pod"))(y)
        assert float(jnp.abs(out - 8 * y).max()) < 1e-5
        out2 = jax.jit(lambda a: all_reduce_compressed(a, mesh, "pod"))(y)
        rel = float(jnp.abs(out2 - 2 * y).max() / jnp.abs(2 * y).max())
        assert rel < 0.02, rel
    print("collectives OK")


def check_moe_ep():
    from repro.models.moe import moe_ffn, moe_ffn_dense_ref
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    k0 = jax.random.PRNGKey(2)
    B, S, D, E, K, F = 4, 8, 32, 8, 2, 64
    ks = jax.random.split(k0, 4)
    x = jax.random.normal(ks[0], (B, S, D)) * 0.5
    params = {"router": jax.random.normal(ks[1], (D, E)) * 0.02,
              "w_in": jax.random.normal(ks[2], (E, D, 2, F)) * 0.05,
              "w_out": jax.random.normal(ks[3], (E, F, D)) * 0.05}
    yref = moe_ffn_dense_ref(x, params, num_experts=E, top_k=K,
                             activation=jax.nn.silu)
    with jax.set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"), None, None)))
        ps = {"router": jax.device_put(params["router"], NamedSharding(mesh, P("data", None))),
              "w_in": jax.device_put(params["w_in"], NamedSharding(mesh, P("model", "data", None, None))),
              "w_out": jax.device_put(params["w_out"], NamedSharding(mesh, P("model", None, "data")))}
        y, m = jax.jit(lambda a, b: moe_ffn(a, b, num_experts=E, top_k=K,
                                            activation=jax.nn.silu,
                                            capacity_factor=None))(xs, ps)
    err = float(jnp.abs(jnp.asarray(y, jnp.float32) - yref.astype(jnp.float32)).max())
    assert err < 5e-2, err
    assert float(m.dropped_frac) == 0.0
    print("moe EP OK")


def check_cp_decode():
    from repro.models.attention import (decode_attention,
                                        decode_attention_context_parallel)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, Hq, Hkv, d = 2, 64, 4, 2, 16
    q = jax.random.normal(ks[0], (B, 1, Hq, d))
    kc = jax.random.normal(ks[1], (B, S, Hkv, d))
    vc = jax.random.normal(ks[2], (B, S, Hkv, d))
    ref = decode_attention(q, kc, vc, jnp.asarray(40))
    with jax.set_mesh(mesh):
        qs = jax.device_put(q, NamedSharding(mesh, P("data", None, None, None)))
        kcs = jax.device_put(kc, NamedSharding(mesh, P("data", "model", None, None)))
        vcs = jax.device_put(vc, NamedSharding(mesh, P("data", "model", None, None)))
        out = jax.jit(lambda a, b, c: decode_attention_context_parallel(
            a, b, c, jnp.asarray(40), mesh=mesh, axis="model",
            batch_axes=("data",)))(qs, kcs, vcs)
    err = float(jnp.abs(ref - jnp.asarray(out)).max())
    assert err < 1e-4, err
    print("context-parallel decode OK")


def check_compressed_pod_sync():
    from repro.configs import RunConfig, get_config
    from repro.models.params import init_params
    from repro.optim.adamw import adamw_init
    from repro.train.train_step import make_train_step
    cfg = get_config("internlm2-1.8b").reduced()
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    b, s = 8, 32
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                           cfg.vocab_size))
    batch = {"tokens": tokens, "labels": tokens,
             "loss_mask": np.ones((b, s), np.float32)}
    outs = {}
    with jax.set_mesh(mesh):
        for mode in ("auto", "compressed"):
            run = RunConfig(learning_rate=1e-3, warmup_steps=1,
                            total_steps=10, pod_sync=mode)
            step = jax.jit(make_train_step(cfg, run, impl="ref", mesh=mesh))
            bput = {k: jax.device_put(jnp.asarray(v),
                                      NamedSharding(mesh, P(("pod", "data"),) ))
                    for k, v in batch.items()}
            p2, _, m = step(params, adamw_init(params), bput, jnp.asarray(0))
            outs[mode] = (p2, float(m["loss"]))
    la, lc = outs["auto"][1], outs["compressed"][1]
    assert abs(la - lc) / abs(la) < 1e-3, (la, lc)
    # params close but not necessarily identical (int8 wire format)
    diffs = [float(jnp.abs(a - c).max()) for a, c in
             zip(jax.tree.leaves(outs["auto"][0]), jax.tree.leaves(outs["compressed"][0]))]
    assert max(diffs) < 5e-3, max(diffs)
    print("compressed pod sync OK")


def check_elastic_reshard():
    from repro.configs import get_config
    from repro.ft.elastic import best_mesh_for, make_mesh, reshard
    from repro.models.params import init_params, _logical_only
    cfg = get_config("internlm2-1.8b").reduced()
    params, logical = init_params(cfg, jax.random.PRNGKey(0))
    shape, names = best_mesh_for(8, model=2)
    m8 = make_mesh(shape, names)
    p8 = reshard(params, logical, m8)
    # "lose 4 devices" -> remesh to 4 and reshard
    shape2, names2 = best_mesh_for(4, model=2)
    m4 = make_mesh(shape2, names2)
    p4 = reshard(p8, logical, m4)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p4)):
        assert float(jnp.abs(a - jnp.asarray(b)).max()) == 0.0
    print("elastic reshard OK")


if __name__ == "__main__":
    check_collectives()
    check_moe_ep()
    check_cp_decode()
    check_compressed_pod_sync()
    check_elastic_reshard()
    print("ALL DISTRIBUTED CHECKS PASSED")
