"""End-to-end behaviour tests for the whole system (paper pipeline +
training/serving drivers on CPU)."""
import json
import os
import subprocess
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # the entry points turn on the persistent compile cache; tests leave none
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    r = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert r.returncode == 0, f"{args}:\n{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    return r.stdout


def test_train_driver_smoke(tmp_path):
    out = _run(["repro.launch.train", "--arch", "gemma3-4b", "--smoke",
                "--steps", "25", "--ckpt-every", "10", "--log-every", "5",
                "--ckpt-dir", str(tmp_path)])
    assert "done" in out
    m = json.load(open(tmp_path / "metrics.json"))
    assert m[-1]["loss"] < m[0]["loss"] + 0.1


def test_train_cli_writes_metrics_without_checkpoint(tmp_path):
    """No checkpoint is due in 3 steps, so nothing else creates the
    directory metrics.json goes to; mamba2's SSD gradient stays finite."""
    ckpt = tmp_path / "never_checkpointed"
    out = _run(["repro.launch.train", "--arch", "mamba2-780m", "--smoke",
                "--steps", "3", "--batch", "2", "--seq-len", "64",
                "--log-every", "1", "--ckpt-dir", str(ckpt)])
    assert "done" in out
    m = json.load(open(ckpt / "metrics.json"))
    assert len(m) == 3 and all(np.isfinite(r["loss"]) for r in m)


def test_dryrun_keeps_caller_xla_flags_and_pins_cpu():
    code = ("import os; os.environ['XLA_FLAGS'] = '--xla_dump_hlo_as_text';"
            "import repro.launch.dryrun;"
            "print(os.environ['XLA_FLAGS']); print(os.environ['JAX_PLATFORMS'])")
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    flags, platforms = r.stdout.split("\n")[:2]
    assert flags.split() == ["--xla_dump_hlo_as_text",
                             "--xla_force_host_platform_device_count=512"]
    assert platforms == "cpu"


def test_train_driver_fault_recovery(tmp_path):
    out = _run(["repro.launch.train", "--arch", "granite-3-8b", "--smoke",
                "--steps", "12", "--ckpt-every", "5", "--ckpt-dir",
                str(tmp_path), "--inject-fault-at", "7"])
    assert "retry" in out and "done" in out


def test_train_driver_resume(tmp_path):
    _run(["repro.launch.train", "--arch", "qwen3-8b", "--smoke", "--steps",
          "10", "--ckpt-every", "5", "--ckpt-dir", str(tmp_path)])
    out = _run(["repro.launch.train", "--arch", "qwen3-8b", "--smoke",
                "--steps", "14", "--ckpt-every", "5", "--ckpt-dir",
                str(tmp_path), "--resume"])
    assert "resumed from step 10" in out


def test_serve_driver_smoke():
    out = _run(["repro.launch.serve", "--arch", "mamba2-780m", "--smoke",
                "--batch", "2", "--prompt-len", "16", "--steps", "6"])
    assert "decode" in out and "tok/s" in out


def test_dryrun_single_cell_small_arch():
    """The dry-run entry point itself (512 fake devices, real cell)."""
    out = _run(["repro.launch.dryrun", "--arch", "seamless-m4t-medium",
                "--shape", "decode_32k", "--out",
                os.path.join("artifacts", "test_dryrun")])
    assert "OK" in out and "roofline" in out


def test_dryrun_skip_cell():
    out = _run(["repro.launch.dryrun", "--arch", "qwen3-8b", "--shape",
                "long_500k", "--out", os.path.join("artifacts", "test_dryrun")])
    assert "SKIPPED" in out
