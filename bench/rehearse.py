#!/usr/bin/env python3
"""Compile a cell's steps for a described v5e chip, with no chip attached.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...] [--reference]

Builds the cell's train step exactly as ``run.py`` does, but on the first
devices of a described ``v5e:2x2`` host (as many as the cell's mesh takes),
compiles it with the TPU's compiler and prints ``memory_analysis`` per
device: what the chip's compiler would refuse, and whether the step fits
the chip's 16 GB. ``--reference`` compiles the
plain reference's step at the cell's size as well. Nothing runs.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

import jax  # noqa: E402

from bench import program, registry  # noqa: E402


def in_use(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def report(tag, compiled, seconds):
    ma = compiled.memory_analysis()
    print(f"[{tag}] compiled in {seconds:.1f}s; per device: args "
          f"{ma.argument_size_in_bytes} out {ma.output_size_in_bytes} alias "
          f"{ma.alias_size_in_bytes} temp {ma.temp_size_in_bytes} -> in use "
          f"{in_use(ma)} B ({in_use(ma) / 2**30:.2f} GiB)", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="+")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = registry.benchmark()
    for cell in args.cells:
        w = registry.workload(cell, bench)
        conf = registry.config(w["config"], bench)
        traffic = registry.traffic(w["traffic"])
        prog = program.build(conf, traffic, topo.devices)
        print(f"[{cell}] {prog.cfg.name} batch {traffic['batch']} x seq "
              f"{traffic['seq_len']} on mesh {dict(prog.mesh.shape)}",
              flush=True)
        t0 = time.perf_counter()
        compiled = program.lower(prog).compile()
        report(f"{cell} step", compiled, time.perf_counter() - t0)
        if args.reference:
            from bench import reference_step
            ref = reference_step.build(conf, prog)
            t0 = time.perf_counter()
            compiled = reference_step.lower(ref).compile()
            report(f"{cell} reference", compiled, time.perf_counter() - t0)
            t0 = time.perf_counter()
            compiled = reference_step.lower_update(ref).compile()
            report(f"{cell} reference update", compiled,
                   time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
