"""The harness finds a configuration, a traffic mix, a limit file and a
per-layer metric by the names in ``BENCHMARK.json``, and picks up a new one
that is only added, with no edit to a file already there."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import program, registry, weights
from bench.conftest import write

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_names_files_that_exist():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        conf = registry.config(w["config"], bench)
        assert registry.traffic(w["traffic"])["kind"] == "train"
        # a cell compares the numbers with an upper reading; grad_cos
        # (float8 arithmetic) and update_gap (a state left unchanged) are
        # the only ones that see those faults
        assert {"grad_cos", "update_gap"} <= set(
            registry.limits(w["name"])) <= {
            "loss_gap", "grad_gap", "grad_cos", "update_gap"}
        registry.reference(conf["reference"])
        registry.flops(conf["flops"])
        program.model_config(conf)
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]).read)
    for k in ("end_to_end", "per_layer", "workloads", "configs"):
        for e in bench[k]:
            assert NAME.match(e["name"]), e["name"]


def test_new_files_are_found_by_name(tiny_root):
    """Adding a traffic mix, a cell and a metric is adding files and
    entries: the harness reads them without a change to its code."""
    bench_dir = registry.BENCH
    write(os.path.join(bench_dir, "traffic", "long.json"),
          {"kind": "train", "batch": 1, "seq_len": 128})
    with open(os.path.join(bench_dir, "metrics", "steps_traced.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.traced_steps)\n")
    path = os.path.join(registry.ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": "tiny-mamba2.long",
                               "config": "tiny-mamba2", "traffic": "long",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves":
                               "train_tokens_per_s",
                               "workloads": ["tiny-mamba2.long"]})
    write(path, bench)
    bench = registry.benchmark()
    w = registry.workload("tiny-mamba2.long", bench)
    assert registry.traffic(w["traffic"])["seq_len"] == 128
    assert registry.config(w["config"], bench)["name"] == "tiny-mamba2"
    names = [m["name"] for m in registry.cell_metrics(
        "tiny-mamba2.long", bench, "per_layer")]
    assert "steps_traced" in names
    assert "steps_traced" not in [m["name"] for m in registry.cell_metrics(
        "tiny-mamba2.t", bench, "per_layer")]

    class Ctx:
        traced_steps = 3
    assert registry.metric_reader("steps_traced").read(Ctx) == 3.0


def test_a_new_expert_leaf_is_drawn_by_its_names():
    """A leaf added under a ``moe`` node, (layers, experts, in, out) beside
    the router, is a routed expert by its names and shape: drawn at fan-in
    ``in`` with no edit to ``weights.py``."""
    import math

    import jax
    import numpy as np
    sds = jax.ShapeDtypeStruct
    tree = {"blocks": {"sb": {"slot0": {"moe": {
        "router": sds((2, 64, 8), np.float32),
        "w_up": sds((2, 8, 64, 256), jax.numpy.bfloat16)}}}}}
    x = np.asarray(weights.leaf(tree, weights.seed_words(7), 1), np.float32)
    assert x.shape == (2, 8, 64, 256)
    assert x.std() * math.sqrt(64) == pytest.approx(0.8796, rel=0.02)


def test_a_reader_of_the_step_outputs_is_found_by_name(tiny_root, tmp_path,
                                                       monkeypatch):
    """A per-layer metric that reads the last traced step's outputs
    (``ctx.step_out``) is one more file and entry; a whole traced run of
    the tiny cell reports it. The CPU has no device plane to trace, so a
    hand-made trace stands in for the profiler's."""
    import math

    from bench import devtrace, run
    from bench.conftest import cpu_check
    from bench.devtrace import Span
    with open(os.path.join(registry.BENCH, "metrics", "step_loss.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx.step_out['loss']\n")
    path = os.path.join(registry.ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["per_layer"].append({"name": "step_loss", "unit": "nats",
                               "better": "lower", "source": "program_counter",
                               "layer": "model step",
                               "moves": "train_tokens_per_s"})
    write(path, bench)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(devtrace, "load", lambda _: devtrace.make(
        {0: [Span("fusion.1", 0, 10)]}, [Span("bench.window", 0, 10)]))
    monkeypatch.setattr(run, "flint_prediction", lambda *a: None)
    out = run.run(run.parse_args(["--workload", "tiny-mamba2.t", "--seed",
                                  "3000000019", "--seconds", "0.2",
                                  "--trace", "1"]), check=cpu_check)
    assert out["correct"], out["compared"]
    loss = out["metrics"]["step_loss"]
    assert loss["unit"] == "nats"
    assert math.isfinite(loss["value"]) and 0 < loss["value"] < 10


@pytest.mark.parametrize("bad", ["../peaks", "a/b", "", "x" * 65])
def test_names_that_could_leave_the_directory_are_refused(bad):
    with pytest.raises(registry.UnknownName):
        registry.traffic(bad)


def test_missing_file_is_an_error():
    with pytest.raises(registry.UnknownName):
        registry.metric_reader("no_such_metric")


def test_config_file_must_agree_with_the_program():
    bench = registry.benchmark()
    conf = registry.config(bench["configs"][0]["name"], bench)
    key = next(k for k in conf["program"]["fields"] if "." not in k)
    conf[key] = conf[key] + 1
    with pytest.raises(ValueError):
        program.model_config(conf)


def test_device_kind_without_peaks_is_refused():
    from bench import run
    peaks = registry.peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.NoDevice):
        run.check_device(1, {})


def test_seeds_of_more_than_32_bits():
    w = weights.seed_words(2**31 + 5)
    assert list(w) == [2**31 + 5, 0]
    assert list(weights.seed_words(2**40 + 1)) == [1, 2**8]
    with pytest.raises(ValueError):
        weights.seed_words(-1)
