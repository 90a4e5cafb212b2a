"""The reduction from a profiler trace to device metrics, on hand-made
intervals and on a small trace recorded on a TPU v5e (``testdata/``: steps
of a program that sums a 2048 x 2048 bf16 matrix's square, under
``bench.*`` spans; two of them lie inside ``bench.window``)."""
from __future__ import annotations

import os
from types import SimpleNamespace

import pytest

from bench import devtrace, registry
from bench.devtrace import Span

HERE = os.path.dirname(os.path.abspath(__file__))


def trace():
    """Two devices over a 100 ns window (0..100):
    dev 0: fusion 0-40, all-gather-done 30-60, fusion 70-90; idle 60-70
           and 90-100; a loop around all of it, which is no work of its
           own.
    dev 1: fusion 10-50, all-reduce 50-80; idle 0-10 and 80-100."""
    ops = {0: [Span("fusion.1", 0, 40), Span("all-gather-done.2", 30, 60),
               Span("fusion.3", 70, 90), Span("fusion.9", 150, 160),
               Span("while.7", 0, 100)],
           1: [Span("fusion.1", 10, 50), Span("all-reduce.4", 50, 80)]}
    host = [Span("bench.window", 0, 100), Span("bench.dispatch", 55, 75),
            Span("bench.block", 85, 100)]
    return devtrace.make(ops, host)


def test_ops_outside_the_window_and_loops_are_left_out():
    assert [e.name for e in trace().ops[0]] == ["fusion.1",
                                                "all-gather-done.2",
                                                "fusion.3"]


def test_busy_and_idle():
    tr = trace()
    # busy: dev 0 0-60 + 70-90 = 80; dev 1 10-80 = 70; mean 75 of 100
    assert devtrace.window_s(tr) == pytest.approx(100e-9)
    assert devtrace.busy_s(tr) == pytest.approx(75e-9)
    assert devtrace.device_idle(tr) == pytest.approx(0.25)


def test_breakdown():
    b = devtrace.breakdown(trace())
    names = dict((k, v) for k, v in b["device_ops"])
    # fusion.1: 40 on dev 0 + 40 on dev 1, mean 40
    assert names["fusion.1"] == pytest.approx(40e-9)
    assert b["device_ops"][0][0] == "fusion.1"
    gaps = sorted(b["idle_gaps"], key=lambda g: (-g[1], g[0]))
    # dev 0: 60-70 (dispatch), 90-100 (block); dev 1: 0-10 (none),
    # 80-100 (block)
    assert gaps[0] == ["bench.block", pytest.approx(20e-9)]
    assert sorted(g[0] for g in gaps) == ["bench.block", "bench.block",
                                          "bench.dispatch", "none"]


def test_union_and_subtract():
    u = devtrace.union([Span("a", 0, 5), Span("b", 3, 8), Span("c", 10, 12)])
    assert u == [(0, 8), (10, 12)]
    assert devtrace.subtract([(0, 20)], u) == [(8, 10), (12, 20)]
    assert devtrace.subtract([(0, 4)], [(0, 4)]) == []


def test_recorded_tpu_trace():
    tr = devtrace.load(os.path.join(HERE, "testdata", "small_tpu.xplane.pb"))
    assert set(tr.ops) == {0}
    names = {e.name for e in tr.ops[0]}
    assert "fusion" in names
    assert 0 < devtrace.busy_s(tr) < devtrace.window_s(tr)
    assert 0 < devtrace.device_idle(tr) < 1
    b = devtrace.breakdown(tr)
    assert b["device_ops"][0][0] == "fusion"
    assert {g[0] for g in b["idle_gaps"]} <= {"bench.block",
                                              "bench.dispatch", "none"}


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        devtrace.make({0: [Span("fusion", 0, 1)]}, [])


def ctx(tr, steps, flops):
    return SimpleNamespace(trace=tr, traced_steps=steps, n_chips=len(tr.ops),
                           peak=registry.peaks()["TPU v5 lite"],
                           model_flops_per_step=flops)


def test_train_mfu_on_the_recorded_trace():
    """Each step's one fusion multiplies two 2048 x 2048 matrices: 2 * 2048^3
    operations in 90.2 us of device time, near the peak. Over the host's
    window (2.48 ms, mostly waiting) the share would read 7%."""
    tr = devtrace.load(os.path.join(HERE, "testdata", "small_tpu.xplane.pb"))
    flops = 2.0 * 2048 ** 3
    mfu = registry.metric_reader("train_mfu").read(ctx(tr, 2, flops))
    busy = devtrace.busy_s(tr)
    assert mfu == pytest.approx(100 * 2 * flops / busy / 197e12)
    assert 90 < mfu < 100
    assert devtrace.device_idle(tr) > 0.9


def test_a_host_stall_moves_idle_not_mfu():
    """The same operations in a window twice as long: the device waits on
    the host for the added half. device_idle reads it; train_mfu, taken
    over the device's busy time, does not move."""
    ops = {0: [Span("fusion.1", 0, 40), Span("fusion.2", 50, 100)]}
    short = devtrace.make(ops, [Span("bench.window", 0, 100)])
    long = devtrace.make(ops, [Span("bench.window", 0, 200)])
    mfu = registry.metric_reader("train_mfu")
    idle = registry.metric_reader("device_idle")
    assert mfu.read(ctx(short, 1, 1e3)) == mfu.read(ctx(long, 1, 1e3))
    assert idle.read(ctx(short, 1, 1e3)) == pytest.approx(10.0)
    assert idle.read(ctx(long, 1, 1e3)) == pytest.approx(55.0)


@pytest.mark.parametrize("name", [
    "train_mfu", "device_idle", "fwd_device_s", "bwd_device_s",
    "remat_device_s", "optimizer_device_s", "seq_mix_device_s"])
def test_readers_without_a_trace_return_nothing(name):
    untraced = SimpleNamespace(trace=None, hlo=None, step_out=None,
                               traced_steps=3, n_chips=1, peak={},
                               model_flops_per_step=1e3)
    assert registry.metric_reader(name).read(untraced) is None
