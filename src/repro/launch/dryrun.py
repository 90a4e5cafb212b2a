"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with 512 placeholder host devices, print memory_analysis()
and cost_analysis(), and persist the Flint capture summary for the roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all            # every cell, both meshes
                                                 # (one subprocess per cell)
"""
import os

# 512 placeholder devices live on the host platform: pin it before any
# backend starts (on a TPU machine JAX would otherwise take the chip) and
# add the device count to whatever XLA_FLAGS the caller set.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS", ""),
    "--xla_force_host_platform_device_count=512")))

import argparse
import json
import subprocess
import sys
import time
import traceback

ARTIFACT_DIR = os.environ.get("FLINT_ARTIFACTS",
                              os.path.join(os.path.dirname(__file__),
                                           "..", "..", "..", "artifacts",
                                           "dryrun"))


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             save_graph: bool = False, quiet: bool = False,
             optimized: bool = False) -> dict:
    """optimized=False: paper-faithful baseline (TP+SP model axis, XLA
    attention accounting).  optimized=True: the hillclimbed configuration —
    ZeRO-3 model axis for train cells + Pallas-fused kernel accounting
    (EXPERIMENTS.md SSPerf)."""
    import jax
    from repro.configs.registry import (cell_applicable, get_config,
                                        get_shape)
    from repro.core.capture import capture_step
    from repro.core.costmodel.analytical import (model_flops_per_step,
                                                 roofline)
    from repro.configs.base import ParallelConfig, SystemConfig
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import input_specs, step_fn_for

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cell_applicable(cfg, shape)
    mesh_tag = "multipod" if multi_pod else "singlepod"
    cell_id = f"{arch}__{shape_name}__{mesh_tag}"
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": why}
        _write(out_dir, cell_id, rec)
        if not quiet:
            print(f"[dryrun] {cell_id}: SKIPPED ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(len(mesh.devices.flat))
    base_par = None
    model_axis_size = mesh.shape.get("model", 1)
    if optimized and shape.kind == "train":
        # hillclimbed strategy (EXPERIMENTS.md SSPerf): ZeRO-3 over the model
        # axis beats TP for train shapes — except when the expert count
        # divides the model axis, where expert parallelism wins (dbrx).
        ep_capable = (cfg.num_experts > 0
                      and cfg.num_experts % model_axis_size == 0)
        if not ep_capable:
            base_par = ParallelConfig(model_axis="zero3")
    elif optimized and shape.kind in ("prefill", "decode"):
        # serving: keep weights resident (no per-step FSDP re-gather) when
        # the TP-sharded params fit comfortably next to the KV cache
        params_per_dev = cfg.param_count() * 2 / model_axis_size
        if params_per_dev < 12e9:
            base_par = ParallelConfig(fsdp=False)
    args, shardings, model, parallel, donate = input_specs(cfg, shape, mesh,
                                                           base_par)
    step = step_fn_for(model, shape, parallel, mesh)

    t0 = time.time()
    cap = capture_step(step, args, shardings, mesh,
                       meta={"arch": arch, "shape": shape_name,
                             "mesh": mesh_tag, "kind": shape.kind,
                             "optimized": optimized},
                       donate_argnums=donate, build_graph=save_graph)
    mf = model_flops_per_step(cfg, shape, n_dev)
    sysc = SystemConfig(chips=n_dev)
    rl = roofline(cap.summary, cap.cost_analysis, sysc, mf,
                  fused_kernels=optimized)

    rec = {
        "cell": cell_id, "status": "ok",
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "devices": n_dev, "kind": shape.kind,
        "t_lower_s": cap.meta["t_lower_s"], "t_compile_s": cap.meta["t_compile_s"],
        "memory_analysis": cap.memory_analysis,
        "cost_analysis": cap.cost_analysis,
        "summary": {k: v for k, v in cap.summary.items()
                    if k != "collectives"},
        "collectives_head": cap.summary["collectives"][:40],
        "roofline": rl.as_dict(),
    }
    _write(out_dir, cell_id, rec)
    if save_graph:
        cap.graph.save(os.path.join(out_dir, cell_id + ".chakra.json"))
    if not quiet:
        print(f"[dryrun] {cell_id}: OK  devices={n_dev} "
              f"compile={cap.meta['t_compile_s']:.1f}s")
        print(f"  memory_analysis: {cap.memory_analysis}")
        print(f"  cost_analysis(flops)={cap.cost_analysis.get('flops', 0):.3e} "
              f"bytes={cap.cost_analysis.get('bytes accessed', 0):.3e}")
        print(f"  flint: flops={cap.summary['parsed_flops']:.3e} "
              f"coll_bytes={cap.summary['comm_bytes']:.3e} "
              f"comm={ {k: v['count'] for k, v in cap.summary['comm'].items()} }")
        print(f"  roofline: compute={rl.compute_s*1e3:.3f}ms "
              f"memory={rl.memory_s*1e3:.3f}ms coll={rl.collective_s*1e3:.3f}ms "
              f"bound={rl.bound} useful={rl.useful_ratio:.2f}")
    return rec


def _write(out_dir, cell_id, rec):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell_id + ".json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def run_all(out_dir: str, meshes=("singlepod", "multipod"),
            archs=None, shapes=None, optimized: bool = False):
    """Run every cell in a subprocess (isolates failures + compile state)."""
    from repro.configs.registry import ARCH_NAMES
    from repro.configs.base import ALL_SHAPES
    archs = archs or ARCH_NAMES
    shapes = shapes or [s.name for s in ALL_SHAPES]
    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_tag in meshes:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out_dir]
                if mesh_tag == "multipod":
                    cmd.append("--multi-pod")
                if optimized:
                    cmd.append("--optimized")
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=1800)
                dt = time.time() - t0
                cell = f"{arch}__{shape}__{mesh_tag}"
                if r.returncode != 0:
                    print(f"[dryrun] {cell}: FAILED ({dt:.0f}s)")
                    print(r.stdout[-2000:])
                    print(r.stderr[-3000:])
                    results.append({"cell": cell, "status": "failed"})
                else:
                    tail = [l for l in r.stdout.splitlines() if l.strip()]
                    print("\n".join(tail))
                    results.append({"cell": cell, "status": "done",
                                    "wall_s": dt})
    with open(os.path.join(out_dir, "_index.json"), "w") as f:
        json.dump(results, f, indent=1)
    n_bad = sum(1 for r in results if r["status"] == "failed")
    print(f"[dryrun] {len(results)} cells, {n_bad} failures")
    return n_bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--save-graph", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="hillclimbed config (zero3 train + fused kernels)")
    args = ap.parse_args()
    if args.all:
        sys.exit(1 if run_all(args.out, optimized=args.optimized) else 0)
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.out,
                       save_graph=args.save_graph, optimized=args.optimized)
        sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
