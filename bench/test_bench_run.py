"""A whole run of the harness on the CPU at smoke widths: sound, and with
the timed path broken underneath."""
from __future__ import annotations

import json

import pytest

from bench import program, run
from bench.conftest import cpu_check
from bench.readings import half_batch

SEED = 3_000_000_019          # over 2**31: seeds take more than 32 bits
CELL = "tiny-mamba2.t"


def args(cell, seconds=0.5):
    return run.parse_args(["--workload", cell, "--seed", str(SEED),
                           "--seconds", str(seconds), "--trace", "0"])


def unchanged_state(prog):
    """A step that returns its state unchanged."""
    def step(state, batch):
        return state, prog.step(state, batch)[1]
    return program.replace_step(prog, step)


def test_sound_run_is_correct(tiny_root, capsys):
    out = run.run(args(CELL), check=cpu_check)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "compared"
    assert set(out["compared"]) == {"loss_gap", "grad_gap", "grad_cos",
                                    "update_gap"}
    json.dumps(out)
    err = capsys.readouterr().err.strip().splitlines()
    assert all(line.startswith("[compared]") for line in err[-4:])


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_broken_step_is_not_correct(tiny_root, fault):
    out = run.run(args(CELL), check=cpu_check, alter=fault)
    assert not out["correct"], out["compared"]


def test_no_chip_no_result(monkeypatch, capsys):
    """On the CPU the harness's own look for a chip refuses the run."""
    rc = run.main(["--workload", "ssd-lm-780m.train-b4s2048", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "no TPU" in out.err

