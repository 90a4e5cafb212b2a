"""The dense GQA configuration (granite-3-8b-l10) at smoke widths on the
CPU: its FLOPs by hand, its parameter count against the program's tree,
and whole runs of the harness against its plain reference, sound and with
the timed path broken; and the tool that reads the numbers a cell's limits
are set from (``bench/readings.py``). At full widths it is the cell
``granite-3-8b-l10.train-b4s4096``."""
from __future__ import annotations

import copy
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench import (compare, program, readings, reference_step, reflib,
                   registry, run)
from bench.conftest import LIMITS, TRAFFIC, cpu_check, write

NAME = "granite-3-8b-l10"
SEED = 3_000_000_019


def config_file():
    with open(os.path.join(registry.BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def tiny(conf: dict, mesh=(1, 1)) -> dict:
    """The configuration at smoke widths, in its published keys and in the
    overrides that build the program; the multipliers stay the program's."""
    c = copy.deepcopy(conf)
    c.update(name="tiny-granite", hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=128, vocab_size=512,
             embedding_multiplier=8.0, attention_multiplier=0.25)
    c["program"]["overrides"].update(
        d_model=64, num_layers=2, sb_repeat=2, num_heads=4, num_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=512)
    c["mesh"] = {"data": mesh[0], "model": mesh[1]}
    return c


@pytest.fixture
def granite_root(tmp_path, monkeypatch):
    """A checkout with the cell ``tiny-granite.t``; its code directories are
    the real ones."""
    bench_dir = tmp_path / "bench"
    for d in ("references", "metrics", "flops"):
        shutil.copytree(os.path.join(registry.BENCH, d), bench_dir / d)
    conf = tiny(config_file())
    write(str(bench_dir / "configs" / "tiny-granite.json"), conf)
    write(str(bench_dir / "limits" / "tiny-granite.t.json"), LIMITS)
    write(str(bench_dir / "traffic" / "t.json"), TRAFFIC)
    write(str(bench_dir / "peaks.json"), registry.peaks())
    bench = copy.deepcopy(registry.benchmark())
    bench["configs"] = [{"name": "tiny-granite", "source": conf["source"],
                         "file": "bench/configs/tiny-granite.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-granite.t", "config": "tiny-granite",
                           "traffic": "t", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    write(str(tmp_path / "BENCHMARK.json"), bench)
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    monkeypatch.setattr(registry, "BENCH", str(bench_dir))
    return tmp_path


def test_flops_by_hand():
    f = registry.flops("granite")
    conf = {"hidden_size": 4, "num_hidden_layers": 2,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 2, "intermediate_size": 6, "vocab_size": 10}
    # per layer: q and out 2*4*2*2=32, k and v 2*4*1*2=16, mlp 3*4*6=72,
    # norms 8 -> 128; two layers 256, embedding 40, final norm 4
    assert f.params(conf) == 300
    tr = {"batch": 2, "seq_len": 8}
    # attention: 6 * L 2 * H 2 * hd 2 * S 8 = 384 per token, 16 tokens
    assert f.model_flops_per_step(conf, tr) == 6 * 300 * 16 + 384 * 16


def test_params_match_the_program_tree():
    conf = tiny(config_file())
    prog = program.build(conf, TRAFFIC, jax.devices())
    n = sum(int(np.prod(a.shape)) for a in
            jax.tree_util.tree_leaves(prog.abstract_args[0].params))
    assert registry.flops(conf["flops"]).params(conf) == n


def test_config_file_agrees_with_the_program():
    cfg = program.model_config(config_file())
    assert (cfg.d_model, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (4096, 10, 32, 8, 12800, 49155)


def test_full_size_counts():
    """2.19e9 parameters in 10 layers; 8.17e9 in the published 40."""
    conf = config_file()
    f = registry.flops(conf["flops"])
    assert f.params(conf) == pytest.approx(2.194e9, rel=1e-3)
    assert f.params(dict(conf, num_hidden_layers=40)) == \
        pytest.approx(8.17e9, rel=1e-2)


def args(seconds=0.5):
    return run.parse_args(["--workload", "tiny-granite.t", "--seed",
                           str(SEED), "--seconds", str(seconds),
                           "--trace", "0"])


def test_sound_run_is_correct(granite_root):
    out = run.run(args(), check=cpu_check)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0


def test_half_batch_is_not_correct(granite_root):
    out = run.run(args(), check=cpu_check, alter=readings.half_batch)
    assert not out["correct"], out["compared"]


def test_float8_control_is_not_correct(granite_root):
    """The reference with float8 operands in the program's place fails the
    limits the program meets, by the angle of its first gradient."""
    bench = registry.benchmark()
    w = registry.workload("tiny-granite.t", bench)
    conf = registry.config(w["config"], bench)
    ns, _ = run.setup(conf, registry.traffic(w["traffic"]), jax.devices(),
                      SEED)
    ref = reference_step.readings(reference_step.build(conf, ns.prog),
                                  ns.words, ns.batches, ns.readings[3],
                                  keep_first=True)
    ctrl = reference_step.readings(
        reference_step.build(conf, ns.prog, reflib.CONTROL), ns.words,
        ns.batches, ref[4])
    limits = registry.limits("tiny-granite.t")
    prog_nums = compare.numbers(ns.readings, ref)
    ctrl_nums = compare.numbers(ctrl, ref[:3] + (ctrl[3],))
    assert compare.verdict(prog_nums, limits)[0], prog_nums
    assert not compare.verdict(ctrl_nums, limits)[0], ctrl_nums
    assert ctrl_nums["grad_cos"] > 10 * prog_nums["grad_cos"]


def test_readings_of_the_tiny_cell(granite_root, monkeypatch, capsys):
    """The calibration tool: a line for each seed, control and fault, a
    summary whose sound ``grad_cos`` lies under its faults', and every
    sound reading within the cell's limits."""
    monkeypatch.setattr(run, "check_device", cpu_check)
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    seeds = [SEED, 17_179_869_209]
    assert readings.main([
        "--workload", "tiny-granite.t",
        "--seeds", ",".join(map(str, seeds)),
        "--control-seeds", str(seeds[0]),
        "--fault-seeds", str(seeds[1])]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    rows = [x for x in lines if "kind" in x]
    assert sorted((r["kind"], r["seed"]) for r in rows) == sorted(
        [("program", s) for s in seeds]
        + [("control", seeds[0]), ("half_batch", seeds[1])])
    summary = lines[-1]["summary"]
    assert summary["grad_cos"]["lower"] < summary["grad_cos"]["upper"]
    limits = registry.limits("tiny-granite.t")
    for r in rows:
        if r["kind"] == "program":
            assert compare.verdict(r, limits)[0], r
