import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

import argparse
import gzip
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, RunConfig, get_config, list_archs, shape_applicable
from repro.core.charz import replay, summarize_traffic
from repro.core.paths import enumerate_paths
from repro.core.roofline import build_report, model_flops_for
from repro.launch.inputs import (batch_shardings, batch_specs, decode_shardings,
                                 decode_specs, param_shardings)
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.params import abstract_params, num_groups
from repro.optim.adamw import adamw_init, opt_logical
from repro.parallel.sharding import named_sharding, tree_shardings
from repro.train.train_step import make_train_step

RUNS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "runs", "dryrun")


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               run: Optional[RunConfig] = None, verbose: bool = True,
               save: bool = True, tag: str = "", opts: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.devices.size
    big = cfg.param_count() > 100e9
    opt_list = opts.split(",") if opts else []
    remat = "none" if "remat_none" in opt_list else (
        "full" if "remat_full" in opt_list else "minimal")
    run = run or RunConfig(
        remat_policy=remat, moments_int8=big,
        microbatch=4 if "microbatch" in opt_list else 0,
        pod_sync="compressed" if "podint8" in opt_list else "auto")
    donate_ok = "nodonate" not in opt_list

    import contextlib
    from repro.models import precision
    stack = contextlib.ExitStack()
    if "bf16" in opts.split(","):
        stack.enter_context(precision.bf16_collectives())

    t0 = time.monotonic()
    with stack, jax.set_mesh(mesh):
        params_abs, logical, psh = param_shardings(cfg, mesh)

        if shape.kind == "train":
            opt_abs = jax.eval_shape(
                lambda: adamw_init(params_abs,
                                   moments="int8" if run.moments_int8 else "f32"))
            opt_sh = tree_shardings(opt_logical(logical, run.moments_int8),
                                    opt_abs, mesh)
            bspecs = batch_specs(cfg, shape)
            bsh = batch_shardings(cfg, shape, mesh)
            cf = 1.0 if "cf1" in opt_list else 1.25
            lchunk = 2048 if "losschunk2048" in opt_list else 512
            step_fn = make_train_step(cfg, run, impl="auto", mesh=mesh,
                                      unroll=num_groups(cfg),
                                      capacity_factor=cf, loss_chunk=lchunk)
            jitted = jax.jit(step_fn,
                             in_shardings=(psh, opt_sh, bsh, None),
                             out_shardings=(psh, opt_sh, None),
                             donate_argnums=(0, 1) if donate_ok else ())
            lowered = jitted.lower(params_abs, opt_abs, bspecs,
                                   jax.ShapeDtypeStruct((), jnp.int32))
            tokens = shape.global_batch * shape.seq_len
            mf = model_flops_for(cfg.active_param_count(), tokens, "train")
        elif shape.kind == "prefill":
            bspecs = batch_specs(cfg, shape)
            bsh = batch_shardings(cfg, shape, mesh)
            _, cache_sh = decode_shardings(cfg, shape, mesh)

            def prefill_step(params, tokens, frontend_embeds=None):
                return M.prefill(cfg, params, tokens, shape.seq_len,
                                 frontend_embeds=frontend_embeds, impl="auto",
                                 unroll=num_groups(cfg))

            args = [params_abs, bspecs["tokens"]]
            in_sh = [psh, bsh["tokens"]]
            if cfg.frontend:
                args.append(bspecs["frontend_embeds"])
                in_sh.append(bsh["frontend_embeds"])
            jitted = jax.jit(prefill_step, in_shardings=tuple(in_sh),
                             out_shardings=(None, cache_sh, None))
            lowered = jitted.lower(*args)
            tokens = shape.global_batch * shape.seq_len
            mf = model_flops_for(cfg.active_param_count(), tokens, "serve")
        else:  # decode
            cp = shape.name == "long_500k"
            tok_specs, cache_abs, pos_spec = decode_specs(cfg, shape)
            tok_sh, cache_sh = decode_shardings(cfg, shape, mesh,
                                                context_parallel=cp)
            if cp:
                cp_axis = "data"
            elif cfg.num_kv_heads and cfg.num_kv_heads % mesh.shape["model"]:
                cp_axis = "model"   # cache seq-sharded over TP (inputs.py)
            else:
                cp_axis = None

            def serve_step(params, tokens, cache, pos):
                return M.decode_step(cfg, params, tokens, cache, pos,
                                     cp_axis=cp_axis, mesh=mesh,
                                     impl="auto", unroll=num_groups(cfg))

            jitted = jax.jit(serve_step,
                             in_shardings=(psh, tok_sh["tokens"], cache_sh, None),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_abs, tok_specs["tokens"], cache_abs,
                                   pos_spec)
            tokens = shape.global_batch
            mf = model_flops_for(cfg.active_param_count(), tokens, "serve")

        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower

    memstats = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    mesh_axes = [(n, int(s)) for n, s in mesh.shape.items()]
    report = build_report(
        arch=arch, shape=shape_name, mesh_name=mesh_name, mesh_axes=mesh_axes,
        cost=cost, hlo_text=hlo, model_flops=mf, chips=chips,
        memory_bytes_per_chip=(memstats.argument_size_in_bytes
                               + memstats.temp_size_in_bytes
                               + memstats.generated_code_size_in_bytes))
    traffic = summarize_traffic(hlo, mesh_axes)
    # event-driven replay: per-path transfers overlap across groups, so
    # this is <= the static sum the roofline reports (collective_s)
    replay_collective_s = replay(traffic, enumerate_paths(dict(mesh.shape)))
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind,
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
        "flops_per_chip": report.flops_per_chip,
        "hbm_bytes_per_chip": report.hbm_bytes_per_chip,
        "collective_bytes_per_path": report.collective_bytes_per_path,
        "collective_op_counts": traffic.op_counts,
        "compute_s": report.compute_s,
        "memory_s": report.memory_s,
        "collective_s": report.collective_s,
        "collective_s_per_path": report.collective_s_per_path,
        "replay_collective_s": replay_collective_s,
        "dominant": report.dominant,
        "model_flops": mf,
        "useful_flops_ratio": report.useful_flops_ratio,
        "roofline_frac": report.roofline_frac,
        "step_time_s": report.step_time_s,
        "memory": {
            "argument_bytes": memstats.argument_size_in_bytes,
            "output_bytes": memstats.output_size_in_bytes,
            "temp_bytes": memstats.temp_size_in_bytes,
            "alias_bytes": memstats.alias_size_in_bytes,
        },
        "lower_s": t_lower, "compile_s": t_compile,
        "opts": opts,
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"compile={t_compile:.1f}s dominant={report.dominant} "
              f"compute={report.compute_s*1e3:.1f}ms "
              f"memory={report.memory_s*1e3:.1f}ms "
              f"collective={report.collective_s*1e3:.1f}ms "
              f"replay={replay_collective_s*1e3:.1f}ms "
              f"useful={report.useful_flops_ratio:.2f} "
              f"frac={report.roofline_frac:.2f}")
        print(f"  memory_analysis: args={memstats.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={memstats.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={memstats.output_size_in_bytes/2**30:.2f}GiB "
              f"alias={memstats.alias_size_in_bytes/2**30:.2f}GiB")
        print(f"  collectives: {traffic.op_counts} per-path-bytes="
              f"{ {k: f'{v/2**20:.1f}MiB' for k, v in traffic.per_path.items()} }")
    if save:
        os.makedirs(RUNS_DIR, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        fname = os.path.join(RUNS_DIR, f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
        with open(fname, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=list_archs())
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="every (arch x shape) cell")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--opts", default="", help="comma list: bf16")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            if args.skip_existing:
                mesh_name = "2x16x16" if mp else "16x16"
                suffix = f"_{args.tag}" if args.tag else ""
                fname = os.path.join(RUNS_DIR, f"{arch}_{shape}_{mesh_name}{suffix}.json")
                if os.path.exists(fname):
                    print(f"[dryrun] skip existing {arch} x {shape} x {mesh_name}")
                    continue
            try:
                r = lower_cell(arch, shape, multi_pod=mp, tag=args.tag,
                               opts=args.opts)
                if "skipped" in r:
                    print(f"[dryrun] SKIP {arch} x {shape}: {r['skipped']}")
            except Exception as e:  # noqa: BLE001 — report every failing cell
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} x {shape} multi_pod={mp}: {e!r}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + "; ".join(f"{a}/{s}/mp={m}" for a, s, m, _ in failures))
    print("[dryrun] all requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()
