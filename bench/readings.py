#!/usr/bin/env python3
"""The readings that a cell's limits are set from, in one process.

  python3 bench/readings.py --workload <cell> --seeds 1,2,... \
      [--control-seeds 1,2,3] [--fault-seeds 1,2,3]

For every seed: the program's set-up and first steps exactly as a run makes
them, then the float32 reference over the same steps, and the compared
numbers (``compare.numbers``). For the control seeds, the reference
computed with float8 operands (``reflib.CONTROL``) put in the program's
place; for the fault seeds, the program with half of each batch left out.
A state left unchanged reads 1 on ``update_gap`` by the measure and needs no
run. One JSON line per reading; the last line gives, per number, the
largest sound reading (lower) and the smallest control or fault reading
(upper). Runs on the chips of this machine; the benchmark's own runs do
not run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

from bench import run  # noqa: E402


def half_batch(prog):
    """Half of each batch left out, the loss's mean taken over the rest."""
    from bench import program

    def step(state, batch):
        return prog.step(state, {k: v[:v.shape[0] // 2]
                                 for k, v in batch.items()})
    return program.replace_step(prog, step)


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)
    if not set(args.control_seeds + args.fault_seeds) <= set(args.seeds):
        ap.error("control and fault seeds must be among --seeds")
    import jax
    from bench import compare, reference_step, reflib, registry
    bench = registry.benchmark()
    cell = registry.workload(args.workload, bench)
    conf = registry.config(cell["config"], bench)
    traffic = registry.traffic(cell["traffic"])
    devices, device, _ = run.check_device(cell["chips"], registry.peaks())
    devices = devices[:cell["chips"]]
    run.enable_cache()
    print(json.dumps({"device": device, "workload": args.workload}),
          flush=True)

    rows = []

    def emit(kind, seed, nums, t0):
        row = {"kind": kind, "seed": seed, **nums,
               "seconds": round(time.perf_counter() - t0, 2)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # one seed at a time: each holds its gradients on the host, several
    # GB of them
    for seed in args.seeds:
        t0 = time.perf_counter()
        ns, _ = run.setup(conf, traffic, devices, seed)
        del ns.state, ns.step
        extra = seed in args.control_seeds or seed in args.fault_seeds
        rbuild = reference_step.build(conf, ns.prog)
        ref = reference_step.readings(rbuild, ns.words, ns.batches,
                                      ns.readings[3], keep_first=extra)
        emit("program", seed, compare.numbers(ns.readings, ref), t0)
        print(json.dumps({"seed": seed, "program_losses": ns.readings[0],
                          "reference_losses": ref[0]}), flush=True)
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            ctrl = reference_step.readings(
                reference_step.build(conf, ns.prog, reflib.CONTROL),
                ns.words, ns.batches, ref[4])
            emit("control", seed,
                 compare.numbers(ctrl, ref[:3] + (ctrl[3],)), t0)
        if seed in args.fault_seeds:
            sh = jax.tree_util.tree_leaves(rbuild.param_sh)
            t0 = time.perf_counter()
            bad, _ = run.setup(conf, traffic, devices, seed, half_batch)
            del bad.state, bad.step
            cos = compare.cos_gaps(ref[4], bad.readings[3], sh)
            emit("half_batch", seed,
                 compare.numbers(bad.readings, ref[:3] + (cos,)), t0)
            del bad
        del ns, ref

    summary = {}
    for k in ("loss_gap", "grad_gap", "grad_cos", "update_gap"):
        sound = [r[k] for r in rows if r["kind"] == "program"]
        bad = [r[k] for r in rows if r["kind"] != "program"]
        summary[k] = {"lower": max(sound) if sound else None,
                      "upper": min(bad) if bad else None}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
