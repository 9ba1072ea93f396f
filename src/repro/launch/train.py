"""Training launcher.

Three modes:
- production: the assigned mesh (16x16 / 2x16x16); on real TPU hardware
  this is the entry point a cluster scheduler invokes per host.
- local: reduced config + small mesh on whatever devices exist (CPU
  container: set JAX_PLATFORMS=cpu and --devices N with the host-device
  override) — the end-to-end example drivers use this.
- simulate: ``--simulate N`` dry-runs the config as N trainer nodes on
  a named fabric (``--fabric``, see train/cluster.TRAIN_FABRICS) — no
  real training, just the FabricRuntime timeline: roofline compute,
  path-aware allreduce, contention-scheduled checkpoint staging.
  Prints simulated tokens/s and the step breakdown. ``--buckets K``
  turns on bucketed-DDP overlap (per-layer-group gradient transfers
  issued during backward) and reports the measured win over a
  single-shot reference run plus the first step's bucket timeline.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --shape train_4k --steps 100 --reduced --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --shape train_4k --steps 20 --simulate 4 --fabric v5e \
      --ckpt-staging soc --ckpt-every 5
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, RunConfig, get_config
from repro.configs.base import ShapeConfig
from repro.ckpt.checkpoint import CheckpointManager
from repro.launch.inputs import batch_shardings, param_shardings
from repro.launch.mesh import make_production_mesh
from repro.models.params import init_params, num_groups
from repro.optim.adamw import adamw_init, opt_logical
from repro.parallel.sharding import tree_shardings
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer


def local_mesh(n_dev: int, multi_pod: bool = False):
    """Best small (data, model) mesh (TP <= 2) over the first ``n_dev``
    local devices."""
    from repro.ft.elastic import best_mesh_for, make_mesh
    shp, names = best_mesh_for(n_dev, model=min(2, n_dev),
                               prefer_pods=2 if multi_pod else 1)
    return make_mesh(shp, names)


def build(cfg, run: RunConfig, shape: ShapeConfig, mesh, *, impl="auto"):
    """Init sharded state + jitted step for (cfg, mesh). Params and
    optimizer state are created by jitted inits straight into their
    shardings: no device ever holds an unsharded copy (at full width
    the f32 AdamW moments alone exceed one chip's HBM)."""
    moments = "int8" if run.moments_int8 else "f32"
    with jax.set_mesh(mesh):
        _, logical, psh = param_shardings(cfg, mesh)
        params = jax.jit(lambda k: init_params(cfg, k)[0], out_shardings=psh)(
            jax.random.PRNGKey(run.seed))
        opt_abs = jax.eval_shape(lambda p: adamw_init(p, moments=moments),
                                 params)
        opt_sh = tree_shardings(opt_logical(logical, run.moments_int8),
                                opt_abs, mesh)
        opt = jax.jit(lambda p: adamw_init(p, moments=moments),
                      out_shardings=opt_sh)(params)
        bsh = batch_shardings(cfg, shape, mesh)
        step = jax.jit(make_train_step(cfg, run, impl=impl, mesh=mesh),
                       in_shardings=(psh, opt_sh, bsh, None),
                       out_shardings=(psh, opt_sh, None),
                       donate_argnums=(0, 1))

        def put_batch(b):
            return {k: jax.device_put(jnp.asarray(v), bsh[k]) for k, v in b.items()}

    return params, opt, step, put_batch


def simulate(cfg, shape, args):
    """--simulate: dry-run the config on a named fabric (no jax work).
    With ``--pods P``, runs P pods of ``--simulate N`` nodes each —
    per-pod fabrics merged over the shared ``dcn:pod`` trunk
    (train/pods.py) — and ``--pod-sync`` selects the inter-pod gradient
    sync (raw vs int8-compressed trunk ring, the simulated twin of
    RunConfig.pod_sync)."""
    from repro.train.cluster import (ClusterTimeModel, TRAIN_FABRICS,
                                     TrainCluster)
    if args.fabric not in TRAIN_FABRICS:
        raise SystemExit(f"unknown fabric {args.fabric!r} "
                         f"(have {sorted(TRAIN_FABRICS)})")

    def parse_pair(spec, cast):
        name, _, val = spec.partition(":")
        return name, cast(val)

    topo = None
    fabric = None
    if args.pods > 1:
        from repro.train.pods import PodTopology, pod_fabric
        topo = PodTopology(args.pods, args.simulate, sync=args.pod_sync)
        fabric = pod_fabric(args.pods, args.simulate,
                            trunk_bw=args.trunk_bw or None,
                            pod_fabric_fn=TRAIN_FABRICS[args.fabric])
        nodes = topo.total_nodes
    else:
        nodes = args.simulate
        fabric = TRAIN_FABRICS[args.fabric](nodes)

    tm = ClusterTimeModel.from_config(cfg, shape, nodes=nodes,
                                      ckpt_path=args.ckpt_staging,
                                      buckets=args.buckets,
                                      weighted_buckets=args.weighted_buckets)

    def fresh_fabric():
        if args.pods > 1:
            from repro.train.pods import pod_fabric
            return pod_fabric(args.pods, args.simulate,
                              trunk_bw=args.trunk_bw or None,
                              pod_fabric_fn=TRAIN_FABRICS[args.fabric])
        return TRAIN_FABRICS[args.fabric](nodes)

    def make(time_model, fab):
        return TrainCluster(
            nodes, time_model, fabric=fab, topology=topo,
            ckpt_every=args.ckpt_every,
            host_load=dict([parse_pair(args.host_load, float)])
            if args.host_load else None,
            fail_at=parse_pair(args.fail, int) if args.fail else None,
            mitigate_stragglers=True)

    ref = None
    if args.buckets > 1:
        # single-shot reference on an identical fresh fabric: the
        # overlap win is reported as measured, not predicted
        ref = make(dataclasses.replace(tm, buckets=1, bucket_weights=None),
                   fresh_fabric()).run(args.steps)
    cluster = make(tm, fabric)
    summary = cluster.run(args.steps)
    pods_msg = (f" pods={topo.pods}x{topo.nodes_per_pod} "
                f"pod_sync={topo.sync}" if topo is not None else "")
    print(f"[simulate] fabric={args.fabric} nodes={nodes}{pods_msg} "
          f"arch={cfg.name} shape={shape.name}")
    print(f"[simulate] compute={tm.compute_s * 1e3:.2f}ms/step "
          f"grad={tm.grad_bytes / 1e9:.2f}GB ckpt={tm.ckpt_bytes / 1e9:.2f}GB "
          f"via {tm.ckpt_path}")
    for e in summary["events"]:
        print(f"[simulate] t={e['t']:.3f}s {e['event']} "
              f"{ {k: v for k, v in e.items() if k not in ('t', 'event')} }")
    print(f"[simulate] {summary['steps']} steps in "
          f"{summary['sim_seconds']:.3f}s simulated "
          f"-> {summary.get('tokens_per_s', 0.0):,.0f} tokens/s "
          f"({len(cluster.straggler.stragglers())} stragglers flagged)")
    if ref is not None and ref["steps"] and summary["steps"]:
        t1 = ref["sim_seconds"] / ref["steps"]
        tk = summary["sim_seconds"] / summary["steps"]
        win = 100.0 * (1.0 - tk / t1) if t1 > 0 else 0.0
        print(f"[simulate] buckets={tm.buckets}: {tk * 1e3:.1f}ms/step vs "
              f"{t1 * 1e3:.1f}ms single-shot -> overlap win {win:.1f}%")
        # first step's overlap timeline, straight off the tracer's
        # bucket phase spans (the cluster's own runtime traces them)
        from repro.obs.trace import PHASE
        spans = [s for s in cluster.runtime.tracer.spans
                 if s.kind == PHASE and s.name == "bucket"
                 and not s.meta.get("aborted")]
        s0 = min((s.meta["step"] for s in spans), default=0)
        for s in sorted((s for s in spans if s.meta["step"] == s0),
                        key=lambda s: s.meta["bucket"]):
            print(f"[simulate]   bucket {s.meta['bucket']}: closed "
                  f"t={s.t_end * 1e3:.1f}ms issued t={s.t_start * 1e3:.1f}ms,"
                  f" in flight {(s.t_end - s.t_start) * 1e3:.1f}ms")
    if topo is not None:
        from repro.core.fabric import OUT
        left = cluster.runtime.ledger.reserved(topo.trunk, OUT)
        print(f"[simulate] trunk {topo.trunk}: reserved after run = "
              f"{left:.3g} (0 = every pod-sync reservation conserved)")
    off = cluster.offload.get_performance_stats()
    if off["compression_bytes_in"]:
        print(f"[simulate] offload: "
              f"{off['compression_operations_offloaded']} saves compressed "
              f"off-host, cycles_saved={off['cpu_cycles_saved']:.3g}, "
              f"ratio={off['compression_ratio']:.2f}")
    if args.trace:
        from repro.obs.export import dump
        dump(cluster.runtime.tracer, args.trace)
        print(f"[simulate] wrote Chrome trace "
              f"({len(cluster.runtime.tracer.spans)} spans) to {args.trace}")
    return cluster


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU example mode)")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--pod-sync", default="auto", choices=["auto", "compressed"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-replicas", type=int, default=0)
    ap.add_argument("--log", default="")
    ap.add_argument("--simulate", type=int, default=0, metavar="NODES",
                    help="dry-run NODES simulated trainer nodes on a "
                         "named fabric instead of training")
    ap.add_argument("--pods", type=int, default=1,
                    help="--simulate: run PODS pods of NODES nodes each, "
                         "per-pod fabrics merged over the shared dcn:pod "
                         "trunk (--pod-sync picks the inter-pod sync)")
    ap.add_argument("--trunk-bw", type=float, default=0.0,
                    help="--simulate --pods: inter-pod trunk bytes/s "
                         "(default pods * DCN_BW_PER_CHIP)")
    ap.add_argument("--buckets", type=int, default=1, metavar="K",
                    help="--simulate: split the gradient into K "
                         "per-layer-group buckets, each allreduce "
                         "issued as its backward slice completes "
                         "(bucketed-DDP overlap; K>1 also runs a "
                         "single-shot reference and prints the "
                         "measured overlap win)")
    ap.add_argument("--weighted-buckets", action="store_true",
                    help="--simulate --buckets K: size each gradient "
                         "bucket from the model's real per-layer-group "
                         "parameter counts instead of splitting "
                         "uniformly (train/cluster.layer_group_weights)")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="--simulate: write the run's span timeline as "
                         "Chrome-trace JSON (load in chrome://tracing "
                         "or ui.perfetto.dev)")
    ap.add_argument("--fabric", default="v5e",
                    help="named fabric for --simulate "
                         "(v5e | weak-soc | fast-net | linefs)")
    ap.add_argument("--ckpt-staging", default="soc",
                    choices=["soc", "host", "auto", "soc-compress",
                             "host-compress"],
                    help="--simulate: checkpoint staging mode (auto = "
                         "per-save ledger-occupancy choice over wires "
                         "AND compress-then-stage; *-compress = run the "
                         "codec on that side's device, stage only the "
                         "compressed bytes)")
    ap.add_argument("--host-load", default="",
                    help="--simulate: NODE:FRAC background host-path load, "
                         "e.g. node0:0.6")
    ap.add_argument("--fail", default="",
                    help="--simulate: NODE:STEP silences a node mid-run, "
                         "e.g. node1:8")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = SHAPES[args.shape]
    if args.batch or args.seq:
        shape = ShapeConfig("custom", args.seq or shape.seq_len,
                            args.batch or shape.global_batch, "train")

    if args.simulate:
        return simulate(cfg, shape, args)

    n_dev = len(jax.devices())
    if n_dev >= 512 and args.multi_pod:
        mesh = make_production_mesh(multi_pod=True)
    elif n_dev >= 256:
        mesh = make_production_mesh()
    else:  # local mode: best small mesh
        mesh = local_mesh(n_dev, args.multi_pod)
    print(f"[train] mesh={dict(mesh.shape)} devices={n_dev}")

    run = RunConfig(learning_rate=args.lr, total_steps=args.steps,
                    warmup_steps=max(2, args.steps // 10),
                    microbatch=args.microbatch, pod_sync=args.pod_sync,
                    ckpt_every=args.ckpt_every)
    params, opt, step, put_batch = build(cfg, run, shape, mesh)

    ckpt = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every,
                                 replicas=args.ckpt_replicas)
    with jax.set_mesh(mesh):
        tr = Trainer(cfg, run, shape, step_fn=step, params=params,
                     opt_state=opt, put_batch=put_batch, ckpt=ckpt,
                     log_path=args.log or None)
        tr.run_steps(args.steps - tr.start_step)
    last = tr.history[-1]
    print(f"[train] done: step={last['step']} loss={last['loss']:.4f} "
          f"({last['seconds']*1e3:.0f} ms/step)")
    return tr


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
