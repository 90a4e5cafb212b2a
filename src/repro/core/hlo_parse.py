"""Post-SPMD HLO text parser — Flint's workload-capture substrate.

Parses `compiled.as_text()` into typed instructions with:
  * shapes/dtypes (incl. tuples), SSA operand edges (the *true* data deps)
  * collective attributes (replica groups, permute pairs, channel ids)
  * the computation call graph (while bodies/conditions, fusions, conds)
  * while-loop trip counts (XLA's cost_analysis does NOT multiply loop
    bodies by trip count — we must, or a scanned 48-layer model reports
    1 layer of FLOPs)

This is deliberately a *text* parser: it needs nothing but what
`.lower().compile()` already produced, keeping capture cluster-free (paper
P4) and independent of XLA's Python bindings.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute", "collective-broadcast")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LAYOUT_RE = re.compile(r"\{[^{}]*\}")


_FLOAT_TYPES = {"f64", "f32", "bf16", "f16", "f8e4m3fn", "f8e5m2"}


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: tuple

    @property
    def bytes(self) -> int:
        return DTYPE_BYTES.get(self.dtype, 4) * int(np.prod(self.dims)) \
            if self.dims else DTYPE_BYTES.get(self.dtype, 4)

    @property
    def tpu_bytes(self) -> int:
        """Bytes with float dtypes normalized to bf16.

        XLA:CPU upcasts bf16 GEMMs to f32 and sinks the converts *before*
        the SPMD collectives, doubling apparent wire/HBM traffic vs the TPU
        compilation of the same program (DESIGN.md SS4).  Roofline terms use
        this normalization; raw bytes are reported alongside."""
        per = DTYPE_BYTES.get(self.dtype, 4)
        if self.dtype in _FLOAT_TYPES:
            per = min(per, 2)
        return per * self.elems

    @property
    def elems(self) -> int:
        return int(np.prod(self.dims)) if self.dims else 1


def parse_shape_str(s: str) -> List[Shape]:
    """'(f32[2,3]{1,0}, bf16[4])' or 'f32[2,3]{1,0}' -> list of Shape.

    Layouts are dropped first: a TPU layout carries tiling and memory space
    (``{1,0:T(8,128)(2,1)S(1)}``), which is never part of the dims."""
    out = []
    for m in _SHAPE_RE.finditer(_LAYOUT_RE.sub("", s)):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in DTYPE_BYTES:
            continue
        d = tuple(int(x) for x in dims.split(",")) if dims else ()
        out.append(Shape(dtype, d))
    if not out and s.strip().startswith(("f", "b", "s", "u", "p")):
        # scalar like 'f32[]'
        mm = re.match(r"(\w+)\[\]", s.strip())
        if mm:
            out.append(Shape(mm.group(1), ()))
    return out


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    shapes: List[Shape]            # output shape(s); tuples flattened
    operands: List[str]            # operand instruction names
    attrs: Dict[str, str]
    metadata_op: str = ""
    raw: str = ""

    @property
    def out_bytes(self) -> int:
        return sum(s.bytes for s in self.shapes)

    @property
    def out_tpu_bytes(self) -> int:
        return sum(s.tpu_bytes for s in self.shapes)

    @property
    def is_collective(self) -> bool:
        base = self.opcode.replace("-start", "").replace("-done", "")
        return base in COLLECTIVE_OPS

    @property
    def collective_kind(self) -> str:
        return self.opcode.replace("-start", "").replace("-done", "")


@dataclasses.dataclass
class Computation:
    name: str
    instructions: List[Instruction]
    is_entry: bool = False

    def find(self, name: str) -> Optional[Instruction]:
        return self._by_name.get(name)

    def __post_init__(self):
        self._by_name = {i.name: i for i in self.instructions}


@dataclasses.dataclass
class HloModule:
    name: str
    computations: Dict[str, Computation]
    entry: str
    num_partitions: int = 1

    @property
    def entry_computation(self) -> Computation:
        return self.computations[self.entry]


# instruction line:  %name = TYPE opcode(...operands...), attr=..., ...
# TYPE is a tuple '(f32[..]{..}, ..)' or 'dtype[dims]{layout}'.  A TPU layout
# holds parenthesised tiles ('{1,0:T(8,128)(2,1)S(1)}'), so the type is
# delimited by bracket balance, never by the first 'word('.
_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*")
_ARRAY_TYPE_RE = re.compile(r"\w+\[[\d,]*\]")
_OPCODE_RE = re.compile(r"\s*([\w-]+)\((.*)$")


def _balanced_end(s: str, i: int) -> int:
    """Index just past the bracket group opening at s[i]."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "({":
            depth += 1
        elif s[j] in ")}":
            depth -= 1
            if depth == 0:
                return j + 1
    return -1


def _split_instruction(st: str):
    """'%n = TYPE opcode(rest' -> (name, TYPE, opcode, rest) or None."""
    hm = _INSTR_HEAD_RE.match(st)
    if not hm:
        return None
    i = hm.end()
    if st.startswith("(", i):
        end = _balanced_end(st, i)
    else:
        am = _ARRAY_TYPE_RE.match(st, i)
        if not am:
            return None
        end = am.end()
        if st.startswith("{", end):
            end = _balanced_end(st, end)
    if end < 0:
        return None
    om = _OPCODE_RE.match(st, end)
    if not om:
        return None
    return hm.group(1), st[i:end], om.group(1), om.group(2)


def _parse_operands(argstr: str) -> List[str]:
    """Extract %operand names from the call-args portion (up to balanced ')')."""
    out = []
    depth = 1
    i = 0
    cur = ""
    while i < len(argstr) and depth > 0:
        c = argstr[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                cur += ""
                break
        cur += c
        i += 1
    for m in re.finditer(r"%([\w.-]+)", cur):
        out.append(m.group(1))
    return out, argstr[i + 1:]


def parse_hlo(text: str) -> HloModule:
    mod_name = "unknown"
    num_partitions = 1
    m = re.search(r"HloModule\s+([\w.-]+)", text)
    if m:
        mod_name = m.group(1)
    m = re.search(r"num_partitions=(\d+)", text)
    if m:
        num_partitions = int(m.group(1))

    computations: Dict[str, Computation] = {}
    entry = None
    cur_name = None
    cur_entry = False
    cur_instrs: List[Instruction] = []

    for line in text.splitlines():
        s = line.rstrip()
        st = s.strip()
        # computation header: [ENTRY] %name (args) -> type {
        hm = re.match(r"^(ENTRY\s+)?%?([\w.-]+)\s*\((.*)\)\s*->\s*.*\{\s*$", st)
        if hm and not st.startswith("%param") and "= " not in st:
            if cur_name is not None:
                computations[cur_name] = Computation(cur_name, cur_instrs,
                                                     cur_entry)
            cur_name = hm.group(2)
            cur_entry = bool(hm.group(1))
            if cur_entry:
                entry = cur_name
            cur_instrs = []
            continue
        if st == "}":
            if cur_name is not None:
                computations[cur_name] = Computation(cur_name, cur_instrs,
                                                     cur_entry)
                cur_name = None
                cur_instrs = []
            continue
        im = _split_instruction(st) if cur_name is not None else None
        if im:
            name, typestr, opcode, rest = im
            operands, tail = _parse_operands(rest)
            attrs: Dict[str, str] = {}
            for am in re.finditer(
                    r"(\w+)=((?:\{\{[^=]*?\}\})|(?:\{[^{}=]*\})|"
                    r"(?:\[[^\]=]*\](?:<=\[[^\]]*\](?:T\([\d,]+\))?)?)|"
                    r"[^,\s]+)", tail):
                attrs[am.group(1)] = am.group(2)
            meta = ""
            mm = re.search(r'op_name="([^"]*)"', tail)
            if mm:
                meta = mm.group(1)
            cur_instrs.append(Instruction(
                name=name, opcode=opcode, shapes=parse_shape_str(typestr),
                operands=operands, attrs=attrs, metadata_op=meta, raw=st))
    if cur_name is not None:
        computations[cur_name] = Computation(cur_name, cur_instrs, cur_entry)
    if entry is None:
        # fall back: the computation whose name contains 'main' or the largest
        entry = max(computations, key=lambda k: len(computations[k].instructions))
    return HloModule(mod_name, computations, entry, num_partitions)


# ---------------------------------------------------------------------------
# replica groups
# ---------------------------------------------------------------------------

def parse_replica_groups(attr: str, num_partitions: int) -> List[List[int]]:
    """'{{0,1},{2,3}}' or '[4,4]<=[16]' or '[4,4]<=[4,4]T(1,0)'."""
    if not attr:
        return [list(range(num_partitions))]
    attr = attr.strip()
    if attr.startswith("{"):
        groups = []
        for g in re.finditer(r"\{([\d,\s]+)\}", attr):
            groups.append([int(x) for x in g.group(1).split(",")])
        return groups or [list(range(num_partitions))]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", attr)
    if m:
        out_shape = [int(x) for x in m.group(1).split(",")]
        in_shape = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(in_shape))).reshape(in_shape)
        if m.group(3):
            perm = [int(x) for x in m.group(3).split(",")]
            ids = ids.transpose(perm)
        ids = ids.reshape(out_shape)
        return [list(map(int, row)) for row in ids]
    return [list(range(num_partitions))]


def parse_permute_pairs(attr: str) -> List[Tuple[int, int]]:
    return [(int(a), int(b))
            for a, b in re.findall(r"\{(\d+),(\d+)\}", attr or "")]


# ---------------------------------------------------------------------------
# while trip counts + walking
# ---------------------------------------------------------------------------

def while_trip_count(mod: HloModule, cond_name: str) -> int:
    """Heuristic: the loop bound is the max s32 constant in the condition."""
    comp = mod.computations.get(cond_name)
    if comp is None:
        return 1
    best = 1
    for ins in comp.instructions:
        if ins.opcode == "constant" and ins.shapes and \
                ins.shapes[0].dtype in ("s32", "u32", "s64"):
            m = re.search(r"constant\((-?\d+)\)", ins.raw)
            if m:
                best = max(best, int(m.group(1)))
    return best


_KNOWN_TRIPS_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def loop_trip_count(mod: HloModule, while_ins: Instruction) -> int:
    """Trips of a ``while``: the count XLA proved and recorded in the loop's
    ``backend_config`` (``"known_trip_count":{"n":"48"}``) where present,
    else the condition heuristic of ``while_trip_count``."""
    m = _KNOWN_TRIPS_RE.search(while_ins.raw)
    if m:
        return int(m.group(1))
    return while_trip_count(mod, while_ins.attrs.get("condition", "").lstrip("%"))


def walk_instructions(mod: HloModule, comp_name: Optional[str] = None,
                      multiplier: int = 1, _seen=None):
    """Yield (Instruction, multiplier, computation_name) over the entry
    computation and (recursively) while bodies, scaling by trip counts.

    Fusions are treated as leaf units (their internals never touch HBM);
    conditionals contribute each branch once (upper bound)."""
    comp_name = comp_name or mod.entry
    comp = mod.computations.get(comp_name)
    if comp is None:
        return
    for ins in comp.instructions:
        yield ins, multiplier, comp_name
        if ins.opcode == "while":
            body = ins.attrs.get("body", "").lstrip("%")
            trips = loop_trip_count(mod, ins)
            yield from walk_instructions(mod, body, multiplier * trips)
        elif ins.opcode == "conditional":
            for key in ("true_computation", "false_computation"):
                b = ins.attrs.get(key, "").lstrip("%")
                if b:
                    yield from walk_instructions(mod, b, multiplier)
            bm = re.search(r"branch_computations=\{([^}]*)\}", ins.raw)
            if bm:
                for b in bm.group(1).split(","):
                    yield from walk_instructions(mod, b.strip().lstrip("%"),
                                                 multiplier)


# ---------------------------------------------------------------------------
# dot FLOPs
# ---------------------------------------------------------------------------

def _operand_shape(mod, comp_name, op_name) -> Optional[Shape]:
    comp = mod.computations.get(comp_name)
    ins = comp.find(op_name) if comp else None
    if ins and ins.shapes:
        return ins.shapes[0]
    return None


def dot_flops(mod: HloModule, ins: Instruction, comp_name: str) -> float:
    """2 * prod(batch) * M * N * K from operand shapes + contracting dims."""
    if not ins.shapes:
        return 0.0
    out = ins.shapes[0]
    lhs = _operand_shape(mod, comp_name, ins.operands[0]) if ins.operands else None
    if lhs is None:
        return 0.0
    lc = [int(x) for x in re.findall(
        r"\d+", ins.attrs.get("lhs_contracting_dims", ""))]
    k = int(np.prod([lhs.dims[i] for i in lc])) if lc else 1
    return 2.0 * out.elems * k


def conv_flops(mod: HloModule, ins: Instruction, comp_name: str) -> float:
    """2 * out elems * (input features * window taps) of a ``convolution``.

    The TPU compiler emits matmuls as convolutions (``dim_labels=bf_io->bf``):
    the contraction is the kernel's ``i`` dim times the window taps that
    meet an input element.  Batched matmuls put their batch dims in the
    window (``size=n lhs_dilate=n``, or an input dim of 1 padded to n), where
    one tap of the n does, so window dim k counts
    min(ceil(size_k / lhs_dilate_k), input extent_k) taps.
    """
    if not ins.shapes:
        return 0.0
    out = ins.shapes[0]
    labels = ins.attrs.get("dim_labels", "")
    lhs, rhs = (
        [_operand_shape(mod, comp_name, o) for o in ins.operands[:2]]
        if len(ins.operands) > 1 else (None, None))
    k = 1
    if lhs is not None and rhs is not None and "_" in labels:
        lhs_labels, rhs_labels = labels.split("->")[0].split("_")
        if len(rhs_labels) == len(rhs.dims) and \
                len(lhs_labels) == len(lhs.dims):
            k = rhs.dims[rhs_labels.index("i")] if "i" in rhs_labels else 1
            n_sp = sum(c.isdigit() for c in rhs_labels)
            dm = re.search(r"lhs_dilate=([\dx]+)", ins.raw)
            dilate = ([int(x) for x in dm.group(1).split("x")] if dm
                      else [1] * n_sp)
            for s in range(n_sp):
                size = rhs.dims[rhs_labels.index(str(s))]
                extent = lhs.dims[lhs_labels.index(str(s))]
                k *= min(-(-size // dilate[s]), extent)
    return 2.0 * out.elems * k


def _called_flops(mod: HloModule, comp_name: str) -> float:
    """FLOPs of the dots and convolutions in a fused computation, through
    nested fusions."""
    sub = mod.computations.get(comp_name)
    if sub is None:
        return 0.0
    total = 0.0
    for i in sub.instructions:
        if i.opcode == "dot":
            total += dot_flops(mod, i, comp_name)
        elif i.opcode == "convolution":
            total += conv_flops(mod, i, comp_name)
        elif i.opcode == "fusion":
            total += _called_flops(mod, i.attrs.get("calls", "").lstrip("%"))
    return total


def instruction_flops(mod: HloModule, ins: Instruction, comp_name: str) -> float:
    """FLOPs of one instruction, counting only matmul-class work.

    A fusion counts the dots and convolutions it calls, whatever its name:
    the TPU compiler fuses matmuls into fusions named after their epilogue
    (``multiply_add_fusion``, ``bitcast_dynamic-update-slice_fusion``).
    A Pallas kernel is a ``custom-call`` (``custom_call_target=
    "tpu_custom_call"``) whose body is opaque Mosaic code: it contributes
    0 FLOPs here, and its cost only enters through the caller's own
    estimate."""
    if ins.opcode == "dot":
        return dot_flops(mod, ins, comp_name)
    if ins.opcode == "fusion":
        return _called_flops(mod, ins.attrs.get("calls", "").lstrip("%"))
    if ins.opcode == "convolution":
        return conv_flops(mod, ins, comp_name)
    return 0.0
