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
