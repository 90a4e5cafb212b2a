"""Fixtures of the benchmark's own tests: a checkout holding a cell at a
size the CPU runs in seconds, built from the real configuration's file."""
from __future__ import annotations

import copy
import json
import os
import shutil

import jax
import pytest

from bench import registry

TRAFFIC = {"kind": "train", "batch": 2, "seq_len": 64, "distinct_batches": 4,
           "generator": "markov", "jumps": [1, 2, 3, 5],
           "jump_probs": [0.55, 0.2, 0.15, 0.1], "motif_len": 32}
# the configurations whose smoke-width copies the tests run
CONFIGS = ("ssd-lm-780m",)
# set from sound runs and faults at this size (sound: loss_gap < 2e-3,
# grad_gap < 5e-3, grad_cos < 1e-4, update_gap < 0.03; half a batch:
# 0.026, 0.23, 0.43; float8 control: grad_cos 6e-3)
LIMITS = {"loss_gap": 0.01, "grad_gap": 0.02, "grad_cos": 1e-3,
          "update_gap": 0.3}


def tiny(conf: dict) -> dict:
    """The configuration at smoke widths, both in its published keys and in
    the overrides that build the program."""
    c = copy.deepcopy(conf)
    over = c["program"]["overrides"]
    c.update(name="tiny-mamba2", d_model=64, n_layer=2, vocab_size=500,
             embedding_rows=512, embedding_multiplier=8.0)
    c["ssm_cfg"].update(d_state=16, headdim=16, chunk_size=32)
    over.update(d_model=64, num_layers=2, sb_repeat=2, vocab_size=512,
                ssm_state=16, ssm_head_dim=16, ssm_chunk=32)
    c["mesh"] = {"data": 1, "model": 1}
    return c


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout with the cell ``tiny-mamba2.t``; its code directories are
    the real ones."""
    real = registry.benchmark()
    bench_dir = tmp_path / "bench"
    for d in ("references", "metrics", "flops"):
        shutil.copytree(os.path.join(registry.BENCH, d), bench_dir / d)
    shutil.copy(os.path.join(registry.BENCH, "peaks.json"), bench_dir)
    bench = copy.deepcopy(real)
    bench["configs"], bench["workloads"] = [], []
    for real_name in CONFIGS:
        with open(os.path.join(registry.BENCH, "configs",
                               real_name + ".json")) as f:
            conf = tiny(json.load(f))
        name = conf["name"]
        write(str(bench_dir / "configs" / f"{name}.json"), conf)
        write(str(bench_dir / "limits" / f"{name}.t.json"), LIMITS)
        bench["configs"].append({"name": name, "source": conf["source"],
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.t", "config": name,
                                   "traffic": "t", "chips": 1, "why": "test"})
    write(str(bench_dir / "traffic" / "t.json"), TRAFFIC)
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    write(str(tmp_path / "BENCHMARK.json"), bench)
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    monkeypatch.setattr(registry, "BENCH", str(bench_dir))
    return tmp_path


def cpu_check(chips, peaks):
    """Stands in for the harness's look for a chip."""
    devs = jax.devices()
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}, peaks["TPU v5 lite"]
