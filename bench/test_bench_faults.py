"""The comparison against the lower-precision control, on the CPU at smoke
widths."""
from __future__ import annotations

import jax

from bench import compare, reference_step, reflib, registry, run
from bench.test_bench_run import SEED


def test_float8_control_is_not_correct(tiny_root):
    """The reference computed with float8 operands, put in the program's
    place, fails the limits the program meets: its first gradient points
    elsewhere (grad_cos) by far more than the program's bfloat16 does."""
    bench = registry.benchmark()
    cell = "tiny-mamba2.t"
    w = registry.workload(cell, bench)
    conf = registry.config(w["config"], bench)
    ns, _ = run.setup(conf, registry.traffic(w["traffic"]), jax.devices(),
                      SEED)
    ref = reference_step.readings(reference_step.build(conf, ns.prog),
                                  ns.words, ns.batches, ns.readings[3],
                                  keep_first=True)
    ctrl = reference_step.readings(
        reference_step.build(conf, ns.prog, reflib.CONTROL), ns.words,
        ns.batches, ref[4])
    limits = registry.limits(cell)
    prog_nums = compare.numbers(ns.readings, ref)
    ctrl_nums = compare.numbers(ctrl, ref[:3] + (ctrl[3],))
    assert compare.verdict(prog_nums, limits)[0], prog_nums
    assert not compare.verdict(ctrl_nums, limits)[0], ctrl_nums
    assert ctrl_nums["grad_cos"] > 10 * prog_nums["grad_cos"]
