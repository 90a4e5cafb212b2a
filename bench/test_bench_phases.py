"""The split of device time by phase (``phases.py``): the phase rule, op
names read from HLO text, and the split on hand-made intervals and on a
step recorded on a TPU v5e."""
from __future__ import annotations

import os

import pytest

from bench import devtrace, phases
from bench.devtrace import Span

FWD = "jit(step)/jvp(layers)/while/body/mixer/seq_mix/dot_general"
REMAT = ("jit(step)/transpose(jvp(layers))/while/body/checkpoint/"
         "rematted_computation/mixer/seq_mix/exp")
BWD = "jit(step)/transpose(jvp(layers))/while/body/checkpoint/mlp/dot_general"
OPT = "jit(step)/optimizer/sqrt"


@pytest.mark.parametrize("op, want", [
    (FWD, "forward"), (REMAT, "remat"), (BWD, "backward"), (OPT, "optimizer"),
    ("jit(step)/jvp()/while", "forward"),
    ("jit(step)/transpose(jvp())/add_any", "backward"),
    # the optimizer scope wins over any marker; "optimizer" in another
    # segment's name is not the scope
    ("jit(step)/transpose(jvp(optimizer))/mul", "optimizer"),
    ("jit(step)/jvp(optimizer_state)/mul", "forward"),
    ("state.params['embed']['table']", "other"), ("", "other")])
def test_phase_rule(op, want):
    assert phases.phase(op) == want


def test_scopes_of():
    assert phases.scopes_of("jit(f)/transpose(jvp(mixer))/seq_mix/dot") == {
        "f", "mixer", "seq_mix", "dot"}


HLO = f"""HloModule jit_step, is_scheduled=true

%fused_computation (param_0.1: f32[8]) -> f32[8] {{
  %param_0.1 = f32[8]{{0}} parameter(0)
  ROOT %exp.1 = f32[8]{{0}} exponential(%param_0.1), metadata={{op_name="{REMAT}"}}
}}

ENTRY %main.9 (p.1: f32[8]) -> (f32[8], f32[8]) {{
  %p.1 = f32[8]{{0}} parameter(0)
  %copy-start.1 = (f32[8]{{0}}, f32[8]{{0}}, u32[]) copy-start(%p.1)
  %copy-done.1 = f32[8]{{0}} copy-done(%copy-start.1)
  %fusion.2 = f32[8]{{0}} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={{op_name="{FWD}" source_file="m.py" source_line=3}}
  %reduce-window.3 = f32[8]{{0}} reduce-window(%fusion.2), window={{size=8}}
  %add.4 = f32[8]{{0}} add(%reduce-window.3, %fusion.2), metadata={{op_name="{BWD}"}}
  %sqrt.5 = f32[8]{{0}} sqrt(%add.4), metadata={{op_name="{OPT}"}}
  %copy.6 = f32[8]{{0}} copy(%sqrt.5)
  %iota.7 = s32[8]{{0}} iota(), iota_dimension=0
  ROOT %tuple.8 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%copy.6, %copy.6)
}}
"""


def test_op_names_from_hlo_text():
    names = phases.op_names(HLO)
    # their own
    assert names["exp.1"] == REMAT
    assert names["fusion.2"] == FWD
    assert names["add.4"] == BWD and names["sqrt.5"] == OPT
    # the compiler's async copy serves the fusion that reads it, and the
    # rewritten reduction the add
    assert names["copy-done.1"] == names["copy-start.1"] == FWD
    assert names["reduce-window.3"] == BWD
    # a copy into the outputs has no user with a phase: its operand's
    assert names["copy.6"] == OPT
    # an instruction with no op name and no neighbour that has one
    assert names["iota.7"] == ""
    assert phases.phase(names["iota.7"]) == "other"


def test_inherited_op_names():
    """Exactly the instructions whose phase is not their own op name's
    (the parameters and the tuple too, which a trace never shows)."""
    own = phases.own_op_names(HLO)
    assert own["fusion.2"] == FWD and own["copy.6"] == ""
    assert set(phases.inherited(phases.op_names(HLO), own)) == {
        "param_0.1", "p.1", "copy-start.1", "copy-done.1", "reduce-window.3",
        "copy.6", "tuple.8"}


def trace():
    """Two devices over 0..100 ns, two steps:
    dev 0: fusion.2 (forward) 0-20, add.4 (backward) 20-50, sqrt.5
           (optimizer) 50-60, iota.7 (other) 60-65, unknown.9 (no name,
           other) 65-70; copy-done.1 (forward) 10-15 overlaps fusion.2.
    dev 1: fusion.2 10-30, exp.1 (remat) 30-40, add.4 40-60."""
    ops = {0: [Span("fusion.2", 0, 20), Span("copy-done.1", 10, 15),
               Span("add.4", 20, 50), Span("sqrt.5", 50, 60),
               Span("iota.7", 60, 65), Span("unknown.9", 65, 70)],
           1: [Span("fusion.2", 10, 30), Span("exp.1", 30, 40),
               Span("add.4", 40, 60)]}
    return devtrace.make(ops, [Span("bench.window", 0, 100)])


def test_phase_seconds_by_hand():
    tr = trace()
    split = phases.phase_seconds(tr, phases.op_names(HLO), steps=2)
    # mean over the devices, over 2 steps: forward (20 + 20) / 2 / 2;
    # backward (30 + 20) / 2 / 2; remat 10 / 2 / 2; optimizer 10 / 2 / 2;
    # other (10 + 0) / 2 / 2; seq_mix: fusion.2 with its copy and exp.1,
    # (20 + 30) / 2 / 2
    want = {"forward": 10, "backward": 12.5, "remat": 2.5, "optimizer": 2.5,
            "other": 2.5, "seq_mix": 12.5}
    assert split == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    # the phases and other partition the device's busy time
    total = sum(split[k] for k in phases.PHASES + (phases.OTHER,))
    assert total == pytest.approx(devtrace.busy_s(tr) / 2)


def test_an_op_without_a_name_is_other():
    tr = devtrace.make({0: [Span("fusion.1", 0, 10)]},
                       [Span("bench.window", 0, 10)])
    split = phases.phase_seconds(tr, {}, steps=1)
    assert split["other"] == pytest.approx(10e-9)
    assert sum(split.values()) == pytest.approx(10e-9)


# A tiny scoped train step recorded on a TPU v5e (``testdata/``): three
# layers of a 512-wide tanh matmul with a seq_mix scope around a cumulative
# sum, scanned under full remat, value_and_grad, and an optimizer scope
# around a momentum update; two steps. ``scoped_step.hlo.txt`` is the
# as_text() of the executable that ran (its stack-frame tables taken out);
# ``scoped_step.xplane.pb`` keeps the
# device plane's XLA Ops line of its trace (host planes and stats taken
# out). The host's bench.window span of that trace ended up after every
# device op (the host and device clocks differ by about a millisecond in
# so short a trace), so the window here is the ops' own extent.
HERE = os.path.dirname(os.path.abspath(__file__))


def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(HERE, "testdata",
                                            "scoped_step.xplane.pb"))
    ops = [Span(devtrace.op_name(e.name), e.start_ns,
                e.start_ns + e.duration_ns)
           for plane in pd.planes for line in plane.lines
           if line.name == devtrace.OPS_LINE for e in line.events]
    window = Span(devtrace.WINDOW_SPAN, min(e.start for e in ops),
                  max(e.end for e in ops))
    with open(os.path.join(HERE, "testdata", "scoped_step.hlo.txt")) as f:
        names = phases.op_names(f.read())
    return devtrace.make({0: ops}, [window]), names


def test_recorded_step_split_by_hand():
    """Per phase, the sum of its 162 ops' durations over two steps (they do
    not overlap on the core), in ns: forward 45,249.5 (60 ops), backward
    44,669.5 (38), remat 63,801.5 (36), optimizer 9,150.5 (24); every op
    has a phase; seq_mix (the cumulative sums, in the forward and the
    backward; the recomputed ones fuse into ops named otherwise) 20,371."""
    tr, names = recorded()
    assert all(e.name in names for e in tr.ops[0])
    split = phases.phase_seconds(tr, names, steps=2)
    want = {"forward": 45249.5, "backward": 44669.5, "remat": 63801.5,
            "optimizer": 9150.5, "other": 0.0, "seq_mix": 20371.0}
    assert split == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    summed = sum(e.end - e.start for e in tr.ops[0]) / 2 * 1e-9
    total = sum(split[k] for k in phases.PHASES + (phases.OTHER,))
    assert total == pytest.approx(summed)
    assert total == pytest.approx(devtrace.busy_s(tr) / 2)


def test_recorded_op_without_a_name_is_other():
    """An op whose instruction has no op name moves from its phase to
    other, and the total stays."""
    tr, names = recorded()
    split = phases.phase_seconds(tr, names, steps=2)
    op = next(e for e in tr.ops[0]
              if phases.phase(names[e.name]) == "optimizer")
    names.pop(op.name)
    moved = sum(e.end - e.start for e in tr.ops[0]
                if e.name == op.name) / 2 * 1e-9
    after = phases.phase_seconds(tr, names, steps=2)
    assert after["optimizer"] == pytest.approx(split["optimizer"] - moved)
    assert after["other"] == pytest.approx(moved)
    assert sum(after.values()) == pytest.approx(sum(split.values()))


def test_recorded_report(capsys):
    """``phase_split.report`` on the recorded step: the split as above, the
    inherited seconds a part of each phase's, and one [scopes] line."""
    from bench import phase_split
    tr, _ = recorded()
    with open(os.path.join(HERE, "testdata", "scoped_step.hlo.txt")) as f:
        text = f.read()
    split, borrowed, tops, n_named = phase_split.report(tr, text, 2)
    assert split == pytest.approx(phases.phase_seconds(
        tr, phases.op_names(text), steps=2))
    for k in phases.PHASES + (phases.SEQ_MIX,):
        assert 0 <= borrowed[k] <= split[k] + 1e-15
    assert sum(borrowed[k] for k in phases.PHASES) > 0
    assert n_named == len(phases.op_names(text))
    assert sum(len(v) for v in tops.values()) > 0
    lines = [s for s in capsys.readouterr().out.splitlines()
             if s.startswith("[scopes] ")]
    assert len(lines) == 1 and "inherited op name: forward" in lines[0]


READERS = {"fwd_device_s": "forward", "bwd_device_s": "backward",
           "remat_device_s": "remat", "optimizer_device_s": "optimizer",
           "seq_mix_device_s": "seq_mix"}


def test_readers_on_the_recorded_step(capsys):
    """The per-phase metrics of a traced run, read from its trace and the
    HLO text of the executable that ran, are the seconds ``phase_split``
    prints for that trace; with ``other`` they sum to the busy time."""
    from types import SimpleNamespace

    from bench import phase_split, registry
    tr, _ = recorded()
    with open(os.path.join(HERE, "testdata", "scoped_step.hlo.txt")) as f:
        text = f.read()
    ctx = SimpleNamespace(trace=tr, hlo=text, traced_steps=2)
    got = {n: registry.metric_reader(n).read(ctx) for n in READERS}
    split = phase_split.report(tr, text, 2)[0]
    assert got == {n: split[k] for n, k in READERS.items()}
    total = sum(got[n] for n in READERS if n != "seq_mix_device_s")
    assert total + split["other"] == pytest.approx(devtrace.busy_s(tr) / 2)
    # without the executable's text (an untraced run) there is nothing
    # to name the operations by
    ctx.hlo = None
    assert all(registry.metric_reader(n).read(ctx) is None for n in READERS)
