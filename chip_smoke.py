"""Smoke run of the main path on a TPU at internlm2-1.8b's published widths.

  python chip_smoke.py              # one chip: serving + attention kernels
  python chip_smoke.py --chips 4    # four chips: the mesh phase only

One chip: ``repro.launch.serve.main`` serves a few requests through the
staged engine, then again through the synchronous engine; every request
must finish with its full ``--max-new`` tokens, in ``[0, vocab)``, and
the greedy tokens of the two engines must be identical. Then the
flash-attention and decode-attention Pallas kernels run at the model's
widths against ``models/attention.py``'s references, and their compiled
programs must hold a ``tpu_custom_call`` (compiled, not interpreted).

Four chips, one process: the ring collectives of ``core/collectives.py``
against lax oracles; a few steps of the full-width train step built by
``launch/train.build`` on the local-mode mesh (finite, falling loss);
and the same step cut to two layers, sharded over the four chips and on
one device, whose losses must agree.

Weights and inputs are random, made from ``--seed``. Without a TPU the
script exits non-zero before any phase runs. Each phase prints one line
with its wall and compile seconds and the peak HBM so far; the last
line of stdout is ``{"ok": true, "device": {...}}``. These are smoke
figures, not measurements.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "internlm2-1.8b"
# decode cache: 4 slots x 1,024 f32 compiles to 12.19 GB of the v5e's
# 15.75 GB (memory_analysis of the decode step); 8 x 2,048 does not fit
SLOTS, MAX_LEN = 4, 1024
REQUESTS, PROMPT_LEN, MAX_NEW = 4, 64, 16
KERNEL_SEQ = 2048
# bf16 kernel output vs an f32 reference at "highest" matmul precision
ATOL, RTOL = 2e-2, 1e-2
# mesh phase: full-width train step on 4 chips, and the cut comparison
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 4
CUT_LAYERS, CUT_BATCH = 2, 4
LOSS_RTOL = 1e-2

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = [0.0]


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


def peak_hbm() -> int:
    """Largest ``peak_bytes_in_use`` over the local devices."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


@contextlib.contextmanager
def phase(name: str, info: dict):
    """Time one phase and print its line; ``info`` collects extras."""
    c0, t0 = _compile_s[0], time.monotonic()
    yield
    gc.collect()
    fields = {"wall_s": time.monotonic() - t0, "compile_s": _compile_s[0] - c0,
              **info, "peak_hbm_bytes": peak_hbm()}
    print(f"[phase] {name}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------

def serve(arch_args, seed: int, vocab: int) -> None:
    """Staged and synchronous engines through the serve launcher; their
    greedy tokens must match."""
    from repro.launch import serve as launcher
    argv = list(arch_args) + [
        "--requests", str(REQUESTS), "--prompt-len", str(PROMPT_LEN),
        "--max-new", str(MAX_NEW), "--slots", str(SLOTS),
        "--max-len", str(MAX_LEN), "--seed", str(seed)]
    tokens = {}
    for mode, extra in (("staged", ["--staged"]), ("sync", [])):
        info = {}
        with phase(f"serve_{mode}", info):
            reqs = launcher.main(argv + extra)
            out = [r.out_tokens for r in reqs]
            info["tokens"] = sum(len(t) for t in out)
        assert all(r.done for r in reqs), f"{mode}: unfinished requests"
        assert all(len(t) == MAX_NEW for t in out), \
            f"{mode}: lengths {[len(t) for t in out]}"
        assert all(0 <= x < vocab for t in out for x in t), \
            f"{mode}: token out of [0, {vocab})"
        tokens[mode] = out
        del reqs
    assert tokens["staged"] == tokens["sync"], \
        f"staged {tokens['staged']} != sync {tokens['sync']}"
    print(f"[check] staged == sync greedy tokens ({REQUESTS} requests x "
          f"{MAX_NEW})", flush=True)


def _compare(name: str, fn, ref_fn, args) -> dict:
    """Run ``fn`` (a jitted kernel wrapper) and ``ref_fn`` under f32
    "highest" precision; both must agree within ATOL/RTOL."""
    out = jax.block_until_ready(fn(*args))
    mosaic = "tpu_custom_call" in fn.lower(*args).compile().as_text()
    f32 = [a.astype(jnp.float32) for a in args[:3]] + list(args[3:])
    with jax.default_matmul_precision("highest"):
        ref = jax.block_until_ready(jax.jit(ref_fn)(*f32))
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(out - ref).max())
    assert out.shape == ref.shape and np.isfinite(out).all(), name
    assert np.allclose(out, ref, atol=ATOL, rtol=RTOL), f"{name}: max err {err}"
    return {"max_abs_err": err, "tpu_custom_call": mosaic}


def kernels(cfg, seed: int) -> dict:
    """The two attention-path kernels at the model's widths; returns
    ``{kernel: tpu_custom_call present}``."""
    from repro.kernels.decode_attention import decode_attention_kernel
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import attention_ref, decode_attention
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    found = {}

    info = {"shape": f"q(1,{KERNEL_SEQ},{hq},{hd})/kv(1,{KERNEL_SEQ},{hkv},{hd})"}
    with phase("flash_attention", info):
        q = jax.random.normal(ks[0], (1, KERNEL_SEQ, hq, hd), bf)
        k = jax.random.normal(ks[1], (1, KERNEL_SEQ, hkv, hd), bf)
        v = jax.random.normal(ks[2], (1, KERNEL_SEQ, hkv, hd), bf)
        info.update(_compare("flash_attention", flash_attention,
                             attention_ref, (q, k, v)))
    found["flash_attention"] = info["tpu_custom_call"]

    clen = MAX_LEN * 7 // 10        # fill line inside a KV block
    info = {"shape": f"q({SLOTS},1,{hq},{hd})/cache({SLOTS},{MAX_LEN},{hkv},{hd})",
            "cache_len": clen}
    with phase("decode_attention", info):
        q = jax.random.normal(ks[3], (SLOTS, 1, hq, hd), bf)
        kc = jax.random.normal(ks[4], (SLOTS, MAX_LEN, hkv, hd), bf)
        vc = jax.random.normal(ks[5], (SLOTS, MAX_LEN, hkv, hd), bf)
        info.update(_compare("decode_attention", decode_attention_kernel,
                             decode_attention,
                             (q, kc, vc, jnp.asarray(clen, jnp.int32))))
    found["decode_attention"] = info["tpu_custom_call"]
    return found


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------

def collectives(seed: int) -> None:
    """Ring collectives on a (pod, data) = (2, 2) mesh vs lax oracles."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.collectives import (all_gather_bidirectional,
                                        all_reduce_compressed,
                                        all_reduce_hierarchical)
    mesh = jax.make_mesh((2, 2), ("pod", "data"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    n = mesh.devices.size
    assert len({d.id for d in mesh.devices.flat}) == n
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    with jax.set_mesh(mesh):
        x = jax.random.normal(k1, (16, 8))
        xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
        got = jax.jit(lambda a: all_gather_bidirectional(a, mesh, "data"))(xs)
        assert float(jnp.abs(got - x).max()) == 0.0, "all_gather_bidirectional"
        y = jax.random.normal(k2, (12, 5))
        out = jax.jit(lambda a: all_reduce_hierarchical(a, mesh, "data", "pod"))(y)
        err = float(jnp.abs(out - n * y).max())
        assert err < 1e-5, f"all_reduce_hierarchical err {err}"
        out2 = jax.jit(lambda a: all_reduce_compressed(a, mesh, "pod"))(y)
        rel = float(jnp.abs(out2 - 2 * y).max() / jnp.abs(2 * y).max())
        assert rel < 0.02, f"all_reduce_compressed rel err {rel}"
    print(f"[check] collectives match lax oracles on {n} devices "
          f"(hierarchical err {err:.3g}, compressed rel {rel:.3g})", flush=True)


def train_losses(cfg, mesh, batch: int, seq: int, steps: int, seed: int):
    """Losses of ``steps`` train steps (``launch/train.build``) on one
    seeded batch; asserts that params and batch span the whole mesh."""
    from repro.configs import RunConfig
    from repro.configs.base import ShapeConfig
    from repro.launch.train import build
    run = RunConfig(learning_rate=1e-3, warmup_steps=0, total_steps=steps,
                    seed=seed)
    params, opt, step, put_batch = build(
        cfg, run, ShapeConfig("smoke", seq, batch, "train"), mesh)
    want = set(mesh.devices.flat)
    held = {d for leaf in jax.tree.leaves(params) for d in leaf.sharding.device_set}
    assert held == want, f"params on {held}, mesh {want}"
    rng = np.random.default_rng(seed)
    seqs = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    b = put_batch({"tokens": seqs[:, :-1], "labels": seqs[:, 1:],
                   "loss_mask": np.ones((batch, seq), np.float32)})
    shards = {s.device for s in b["tokens"].addressable_shards}
    assert shards == want, f"batch on {shards}, mesh {want}"
    losses = []
    with jax.set_mesh(mesh):
        for i in range(steps):
            params, opt, m = step(params, opt, b, jnp.asarray(i, jnp.int32))
            losses.append(float(m["loss"]))
    return losses


def mesh_phase(cfg, seed: int, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
               steps=TRAIN_STEPS, cut_layers=CUT_LAYERS, cut_batch=CUT_BATCH):
    from repro.ft.elastic import make_mesh
    from repro.launch.train import local_mesh
    devs = jax.devices()
    assert len(devs) == 4 and len({d.id for d in devs}) == 4, devs
    with phase("collectives", {}):
        collectives(seed)

    mesh = local_mesh(4)
    info = {"mesh": dict(mesh.shape), "batch": f"{batch}x{seq}"}
    with phase("train_sharded", info):
        losses = train_losses(cfg, mesh, batch, seq, steps, seed)
        info["losses"] = losses
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"

    cut = dataclasses.replace(cfg, num_layers=cut_layers)
    info = {"layers": cut_layers, "batch": f"{cut_batch}x{seq}"}
    with phase("train_cut_compare", info):
        sharded = train_losses(cut, mesh, cut_batch, seq, steps, seed)
        single = train_losses(cut, make_mesh((1, 1), ("data", "model"),
                                             devices=devs[:1]),
                              cut_batch, seq, steps, seed)
        info.update(sharded=sharded, single=single)
    assert np.allclose(sharded, single, rtol=LOSS_RTOL, atol=0), \
        f"sharded {sharded} vs single-device {single}"
    print(f"[check] sharded and single-device losses agree within "
          f"rtol {LOSS_RTOL}", flush=True)


# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the mesh phase (and nothing else)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cfg = get_config(ARCH)
    print(f"[env] jax {jax.__version__} device_kind={dev.device_kind!r} "
          f"devices={len(jax.devices())} compile_cache={cache}", flush=True)

    if args.chips == 4:
        mesh_phase(cfg, args.seed)
    else:
        serve(["--arch", ARCH], args.seed, cfg.vocab_size)
        found = kernels(cfg, args.seed)
        assert all(found.values()), f"kernel not compiled to Mosaic: {found}"
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
