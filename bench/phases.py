"""Split a traced step's device time by phase, from the HLO's op names.

The trace (``devtrace``) names each device operation by its HLO instruction
name, and those names are renumbered by any change to the step. The
optimized HLO text of the executable that ran (``compiled.as_text()``)
gives each instruction its JAX name stack, ``metadata={op_name=...}``,
which holds the program's named scopes (``repro.obs.scopes``) and JAX's
own markers of differentiation. The phase of an op name, first rule that
matches:

- ``optimizer``: a path segment is the ``optimizer`` scope;
- ``remat``: it has ``rematted_computation`` (full remat's second forward);
- ``backward``: it has ``transpose(``;
- ``forward``: it has ``jvp(``;
- ``other``: none of these.

Some instructions carry no op name of their phase: those the compiler
adds after lowering (async copies and slices, layout copies, clones of
broadcasts), reductions it rewrites (a cumulative sum as a
``reduce-window``), and constants it hoists out of the differentiated
code. Each such instruction takes the op name of its nearest user in the
same computation that has a phase (the op it serves), or else, as a
copy of a result into the step's outputs, that of its nearest operand
with one; an instruction with neither keeps its own op name, or none,
and counts as ``other``.

The regular expressions here are the benchmark's own, not the program's
HLO parser, so that a change to the program cannot move this yardstick.
"""
from __future__ import annotations

import re
from typing import Dict, List

from bench import devtrace

PHASES = ("forward", "backward", "remat", "optimizer")
OTHER = "other"
SEQ_MIX = "seq_mix"
OPTIMIZER_SCOPE = "optimizer"

# "ENTRY %main.5 (...) -> ... {" or "%region_0.3 (...) -> ... {"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?[\w.\-]+\s.*\{\s*$")
# "  ROOT %fusion.3 = bf16[...] fusion(%a, %b), ..., metadata={op_name="..."}"
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
# transformations around a scope's name in the stack: "transpose(jvp(x))"
_WRAPPER = re.compile(r"^(?:\w+\()+")


def phase(op_name: str) -> str:
    """The phase of one op name (see the module's docstring)."""
    if OPTIMIZER_SCOPE in scopes_of(op_name):
        return "optimizer"
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return OTHER


def scopes_of(op_name: str) -> set:
    """The segments of a name stack with JAX's transformations taken off:
    "jit(f)/transpose(jvp(mixer))/seq_mix/dot" -> {"f", "mixer", "seq_mix",
    "dot"}."""
    return {_WRAPPER.sub("", s).rstrip(")") for s in op_name.split("/")}


def _computations(hlo_text: str) -> List[list]:
    """[[(instruction, own op name, operands), ...] per computation], in the
    text's order, in which every operand comes before its users."""
    comps: List[list] = []
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            if _COMPUTATION.match(line):
                comps.append([])
            continue
        m = _INSTRUCTION.match(line)
        if not m or not comps:
            continue
        meta = _OP_NAME.search(line)
        body = line[m.end():line.find(", metadata=") if meta else None]
        comps[-1].append((m.group(1), meta.group(1) if meta else "",
                          _OPERAND.findall(body)))
    return comps


def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: op name} for every instruction of the module, an
    instruction without a phase of its own taking its nearest user's, or
    else its nearest operand's."""
    out: Dict[str, str] = {}
    for comp in _computations(hlo_text):
        users: Dict[str, List[str]] = {}
        for name, _, operands in comp:
            for o in operands:
                users.setdefault(o, []).append(name)
        named: Dict[str, str] = {}
        for name, own, _ in reversed(comp):
            named[name] = own if phase(own) != OTHER else _first_phased(
                users.get(name, ()), named, own)
        for name, _, operands in comp:
            if phase(named[name]) == OTHER:
                named[name] = _first_phased(operands, named, named[name])
        out.update(named)
    return out


def own_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction: the op name its own metadata gives, or ""}."""
    return {name: own for comp in _computations(hlo_text)
            for name, own, _ in comp}


def inherited(names: Dict[str, str], own: Dict[str, str]) -> Dict[str, str]:
    """The part of ``op_names`` whose phase comes from another instruction's
    op name, not the instruction's own."""
    return {i: n for i, n in names.items()
            if phase(own.get(i, "")) != phase(n)}


def _first_phased(candidates, named, default):
    return next((named[c] for c in candidates
                 if phase(named.get(c, "")) != OTHER), default)


def phase_seconds(tr: devtrace.Trace, names: Dict[str, str],
                  steps: int) -> Dict[str, float]:
    """Device seconds a step of each phase, of ``other`` and of ``seq_mix``
    (ops under that scope in any phase): per device the length of the union
    of the intervals of those ops in the window, mean over the devices,
    over ``steps``."""
    keys = PHASES + (OTHER, SEQ_MIX)
    total = dict.fromkeys(keys, 0.0)
    for evs in tr.ops.values():
        by: Dict[str, list] = {k: [] for k in keys}
        for e in evs:
            op = names.get(e.name, "")
            by[phase(op)].append(e)
            if SEQ_MIX in scopes_of(op):
                by[SEQ_MIX].append(e)
        for k in keys:
            total[k] += devtrace.length(devtrace.union(by[k]))
    return {k: v / len(tr.ops) / steps * 1e-9 for k, v in total.items()}
