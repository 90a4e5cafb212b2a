"""HLO text parser: shapes, replica groups, trip counts, flops, walking."""
import numpy as np
import pytest

from repro.core.hlo_parse import (Shape, conv_flops, loop_trip_count,
                                  parse_hlo, parse_replica_groups,
                                  parse_shape_str, while_trip_count,
                                  walk_instructions, instruction_flops)

SAMPLE = """
HloModule jit_f, num_partitions=16

%cond.1 (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(12)
  ROOT %lt = pred[] compare(%gte, %c), direction=LT
}

%body.1 (p2: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p2 = (s32[], f32[8,8]{1,0}) parameter(0)
  %g0 = s32[] get-tuple-element(%p2), index=0
  %g1 = f32[8,8]{1,0} get-tuple-element(%p2), index=1
  %one = s32[] constant(1)
  %next = s32[] add(%g0, %one)
  %d = f32[8,8]{1,0} dot(%g1, %g1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), channel_id=1, replica_groups=[4,4]<=[16], to_apply=%add
  ROOT %t = (s32[], f32[8,8]) tuple(%next, %ar)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond.1, body=%body.1
  %ag = f32[32,8]{1,0} all-gather(%a), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_parse_shapes():
    s = parse_shape_str("(f32[2,3]{1,0}, bf16[4]{0})")
    assert [(x.dtype, x.dims) for x in s] == [("f32", (2, 3)), ("bf16", (4,))]
    assert parse_shape_str("s32[]")[0].dims == ()
    assert parse_shape_str("bf16[4]")[0].bytes == 8
    assert parse_shape_str("f32[4]")[0].tpu_bytes == 8   # normalized to bf16


def test_parse_module_structure():
    mod = parse_hlo(SAMPLE)
    assert mod.num_partitions == 16
    assert mod.entry == "main"
    assert set(mod.computations) == {"cond.1", "body.1", "main"}
    w = mod.entry_computation.find("w")
    assert w.opcode == "while"
    assert w.attrs["condition"].lstrip("%") == "cond.1"


def test_trip_count_and_walk_multiplier():
    mod = parse_hlo(SAMPLE)
    assert while_trip_count(mod, "cond.1") == 12
    mults = {ins.name: m for ins, m, _ in walk_instructions(mod)}
    assert mults["d"] == 12
    assert mults["ag"] == 1


def test_dot_flops_with_trip():
    mod = parse_hlo(SAMPLE)
    total = sum(instruction_flops(mod, ins, c) * m
                for ins, m, c in walk_instructions(mod))
    assert total == 12 * 2 * 8 * 8 * 8   # 12 trips x 2MNK


def test_replica_groups_explicit():
    g = parse_replica_groups("{{0,1,2,3},{4,5,6,7}}", 8)
    assert g == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_replica_groups_iota():
    g = parse_replica_groups("[4,4]<=[16]", 16)
    assert g[0] == [0, 1, 2, 3] and g[3] == [12, 13, 14, 15]


def test_replica_groups_iota_transposed():
    g = parse_replica_groups("[4,4]<=[4,4]T(1,0)", 16)
    # transpose: groups are strided (column groups of the 4x4 device grid)
    assert g[0] == [0, 4, 8, 12]


def test_replica_groups_default():
    assert parse_replica_groups("", 4) == [[0, 1, 2, 3]]


def test_collective_detection():
    mod = parse_hlo(SAMPLE)
    colls = [ins for ins, m, _ in walk_instructions(mod) if ins.is_collective]
    kinds = {c.collective_kind for c in colls}
    assert kinds == {"all-reduce", "all-gather"}


def test_real_compiled_module_roundtrip(subproc):
    """Parse a real compiled module at 8 fake devices; flops must match the
    hand-computed dot count (trip-aware)."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.mesh import make_mesh
from repro.core.hlo_parse import parse_hlo, walk_instructions, instruction_flops
mesh = make_mesh((2, 4), ("data", "model"))
L = 5
def f(stack, x):
    def body(h, w):
        return jax.nn.relu(h @ w), None
    h, _ = jax.lax.scan(body, x, stack)
    return h.sum()
ss = jax.ShapeDtypeStruct((L, 64, 64), jnp.float32)
xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
sh = (NamedSharding(mesh, P(None, None, "model")), NamedSharding(mesh, P("data", None)))
c = jax.jit(f, in_shardings=sh).lower(ss, xs).compile()
mod = parse_hlo(c.as_text())
fl = sum(instruction_flops(mod, i, cn) * m for i, m, cn in walk_instructions(mod))
expect = 5 * 2 * (32 // 2) * 64 * (64 // 4)
assert fl == expect, (fl, expect)
print("flops ok", fl)
""")
    assert "flops ok" in out


# TPU-compiled text: tiled layouts ({1,0:T(8,128)(2,1)S(1)}), matmuls as
# convolutions inside fusions named after their epilogue, a batched matmul
# with its batch dims in the convolution window, and a loop whose proven
# trip count (4) differs from its condition's constant (7)
TPU_SAMPLE = """
HloModule jit_step, num_partitions=1

%fused_computation.1 (param_0: bf16[256,512], param_1: bf16[512,2048]) -> bf16[256,2048] {
  %param_0 = bf16[256,512]{1,0:T(8,128)(2,1)} parameter(0)
  %param_1 = bf16[512,2048]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.9 = bf16[256,2048]{1,0:T(8,128)(2,1)S(1)} convolution(%param_0, %param_1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/dot_general"}
}

%cond.2 (p: (s32[], bf16[256,512], bf16[512,2048])) -> pred[] {
  %p = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}, bf16[512,2048]{1,0:T(8,128)(2,1)}) parameter(0)
  %gte = s32[]{:T(128)} get-tuple-element(%p), index=0
  %c = s32[]{:T(128)} constant(7)
  ROOT %lt = pred[]{:T(512)} compare(%gte, %c), direction=LT
}

%body.3 (q: (s32[], bf16[256,512], bf16[512,2048])) -> (s32[], bf16[256,512], bf16[512,2048]) {
  %q = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}, bf16[512,2048]{1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%q), index=0
  %x = bf16[256,512]{1,0:T(8,128)(2,1)} get-tuple-element(%q), index=1
  %w = bf16[512,2048]{1,0:T(8,128)(2,1)} get-tuple-element(%q), index=2
  %multiply_add_fusion.2 = bf16[256,2048]{1,0:T(8,128)(2,1)S(1)} fusion(%x, %w), kind=kOutput, calls=%fused_computation.1
  ROOT %t = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}, bf16[512,2048]{1,0:T(8,128)(2,1)}) tuple(%i, %x, %w)
}

ENTRY %main.4 (a: bf16[256,512], b: bf16[512,2048], l: bf16[4,8,256,128,1], r: bf16[4,8,48,128,64]) -> bf16[4,8,256,48,64] {
  %a = bf16[256,512]{1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[512,2048]{1,0:T(8,128)(2,1)} parameter(1)
  %l = bf16[4,8,256,128,1]{3,2,4,1,0:T(8,128)(2,1)} parameter(2)
  %r = bf16[4,8,48,128,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(3)
  %z = s32[]{:T(128)} constant(0)
  %t0 = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}, bf16[512,2048]{1,0:T(8,128)(2,1)}) tuple(%z, %a, %b)
  %w.5 = (s32[]{:T(128)}, bf16[256,512]{1,0:T(8,128)(2,1)}, bf16[512,2048]{1,0:T(8,128)(2,1)}) while(%t0), condition=%cond.2, body=%body.3, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %conv.6 = bf16[4,8,256,48,64]{2,4,3,1,0:T(8,128)(2,1)} convolution(%l, %r), window={size=4x8x48 stride=3x7x1 pad=0_0x0_0x47_47 lhs_dilate=4x8x1 rhs_reversal=0x0x1}, dim_labels=01bf2_012io->01b2f
}
"""


def test_tpu_layouts_never_reach_opcode_or_dims():
    mod = parse_hlo(TPU_SAMPLE)
    assert mod.entry == "main.4"
    body = mod.computations["body.3"]
    assert [i.opcode for i in body.instructions] == [
        "parameter", "get-tuple-element", "get-tuple-element",
        "get-tuple-element", "fusion", "tuple"]
    assert body.find("multiply_add_fusion.2").shapes == [
        Shape("bf16", (256, 2048))]
    assert [s.dims for s in body.find("q").shapes] == [
        (), (256, 512), (512, 2048)]
    assert parse_shape_str("bf16[512,4096]{1,0:T(8,128)(2,1)S(1)}") == [
        Shape("bf16", (512, 4096))]


def test_tpu_convolution_flops():
    mod = parse_hlo(TPU_SAMPLE)
    entry = mod.entry_computation
    # batched matmul: one tap per window dim meets an input element, so
    # the contraction is the kernel's 128 input features
    assert conv_flops(mod, entry.find("conv.6"), "main.4") == \
        2.0 * (4 * 8 * 256 * 48 * 64) * 128
    total = sum(instruction_flops(mod, ins, c) * m
                for ins, m, c in walk_instructions(mod))
    assert total == 4 * 2 * 256 * 512 * 2048 + 2.0 * (4 * 8 * 256 * 48 * 64) * 128


def test_known_trip_count_wins_over_condition_constant():
    mod = parse_hlo(TPU_SAMPLE)
    w = mod.entry_computation.find("w.5")
    assert while_trip_count(mod, "cond.2") == 7
    assert loop_trip_count(mod, w) == 4
