#!/usr/bin/env python3
"""Per-phase device time of a cell's train step, from a traced window.

  python3 bench/phase_split.py --workload <cell> --seed <n> \
      [--seconds 5] [--steps 3]

Set-up as ``run.py`` makes it; a window of ``--seconds`` with the profiler
off; then ``--steps`` steps under the profiler (``run.traced``). The
optimized HLO of the executable that ran gives every traced operation its
op name (``phases.op_names``), and so its phase. Prints a ``[scopes]`` line
(per step: forward, backward, remat, optimizer, other, their sum, the
device's busy time, the operations' summed durations, and seq_mix; then
each phase's seconds from operations whose phase comes from an inherited
op name, not their own), the operations that took most time in each
phase, and what tracing cost: the host's step time with the profiler off
and on, and the seconds of the reduction. The last line is one JSON object, with the longest
operations of each phase and their op names.

No reference runs and nothing is compared: this reads where the time goes,
it is not a benchmark run.
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

from bench import devtrace, phases, registry, run  # noqa: E402

TOP = 8


def top_ops(tr, names, steps, top=TOP):
    """{phase: [(instruction, seconds a step, op name)]}, longest first."""
    per: dict = {}
    for evs in tr.ops.values():
        for e in evs:
            k = (e.name, names.get(e.name, ""))
            per[k] = per.get(k, 0.0) + (e.end - e.start)
    scale = 1e-9 / len(tr.ops) / steps
    out: dict = {}
    for (name, op), ns in sorted(per.items(), key=lambda kv: -kv[1]):
        rows = out.setdefault(phases.phase(op), [])
        if len(rows) < top:
            rows.append((name, ns * scale, op))
    return out


def report(tr, hlo_text, steps):
    """Log the ``[scopes]`` line and the longest operations of each phase;
    returns (split, the split's seconds from inherited op names, the top
    operations, the number of instructions named)."""
    names = phases.op_names(hlo_text)
    split = phases.phase_seconds(tr, names, steps)
    borrowed = phases.phase_seconds(
        tr, phases.inherited(names, phases.own_op_names(hlo_text)), steps)
    summed = sum(e.end - e.start for evs in tr.ops.values()
                 for e in evs) / len(tr.ops) / steps * 1e-9
    total = sum(split[k] for k in phases.PHASES + (phases.OTHER,))
    busy = devtrace.busy_s(tr) / steps
    run.log("[scopes] " + " ".join(
        f"{k} {split[k]:.6f}" for k in phases.PHASES + (phases.OTHER,))
        + f" sum {total:.6f} busy_s {busy:.6f} summed_ops {summed:.6f}"
        f" seq_mix {split[phases.SEQ_MIX]:.6f} (s a step); of which by an"
        " inherited op name: " + " ".join(
            f"{k} {borrowed[k]:.6f}"
            for k in phases.PHASES + (phases.SEQ_MIX,)))
    tops = top_ops(tr, names, steps)
    for k in phases.PHASES + (phases.OTHER,):
        for name, s, op in tops.get(k, [])[:3]:
            run.log(f"[top] {k} {name} {s:.6f} {op[:140]}")
    return split, borrowed, tops, len(names)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args(argv)
    bench = registry.benchmark()
    cell = registry.workload(args.workload, bench)
    conf = registry.config(cell["config"], bench)
    traffic = registry.traffic(cell["traffic"])
    devices, device, _ = run.check_device(cell["chips"], registry.peaks())
    run.enable_cache()
    ns, parts = run.setup(conf, traffic, devices[:cell["chips"]], args.seed)
    steps, elapsed, _ = run.window(ns, args.seconds)

    t = time.perf_counter()
    tr = run.traced(ns, args.steps)
    traced_s = time.perf_counter() - t
    t = time.perf_counter()
    split, borrowed, tops, n_named = report(tr, ns.step.as_text(), args.steps)
    reduce_s = time.perf_counter() - t
    n = args.steps
    host_off, host_on = elapsed / steps, devtrace.window_s(tr) / n
    run.log(f"[cost] step {host_off:.6f}s profiler off, {host_on:.6f}s on; "
            f"traced steps with stop and load {traced_s:.2f}s; op names and "
            f"phase split {reduce_s:.3f}s")
    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "setup": parts, "phase_s": split, "inherited_s": borrowed,
           "step_s_profiler_off": host_off, "step_s_profiler_on": host_on,
           "traced_s": traced_s, "reduce_s": reduce_s,
           "instructions_named": n_named, "top": tops}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
