"""Cluster-free workload capture (Flint's runtime, paper SS4).

capture_step() is the JAX analogue of registering Flint as a torch.compile
backend: `.lower()` on ShapeDtypeStructs traces the program without touching
device memory (the meta-device illusion comes for free), `.compile()` runs
GSPMD + XLA passes for the *target* mesh — which can be any size thanks to
--xla_force_host_platform_device_count — and the resulting per-partition HLO
is parsed into a Chakra graph.

Capture levels (paper SS3.2 tradeoff):
  * "lowered"  = StableHLO before SPMD/fusion (source-faithful op counts)
  * "compiled" = scheduled per-device HLO with real collectives (default)
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Dict, Optional

import jax

from repro.core import chakra
from repro.core.convert import hlo_to_chakra
from repro.core.hlo_parse import (HloModule, instruction_flops, parse_hlo,
                                  walk_instructions)


@dataclasses.dataclass
class CaptureResult:
    meta: Dict
    lowered_text: str
    compiled_text: str
    cost_analysis: Dict
    memory_analysis: Dict
    summary: Dict                       # Flint-parsed totals (trip-count aware)
    graph: chakra.Graph

    def save_summary(self, path: str):
        with open(path, "w") as f:
            json.dump({"meta": self.meta, "cost_analysis": self.cost_analysis,
                       "memory_analysis": self.memory_analysis,
                       "summary": self.summary}, f, indent=1)


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "while", "conditional", "after-all",
                   "opt-barrier", "partition-id", "replica-id", "iota"}


def _fusion_param_read_bytes(mod: HloModule, ins) -> dict:
    """For a fusion, map parameter index -> (bytes, tpu_bytes) actually read.

    When a parameter is consumed only through a dynamic-slice inside the
    fusion (XLA fuses cache/stack slicing into consumer fusions), the read is
    the slice, not the whole buffer."""
    called = ins.attrs.get("calls", "").lstrip("%")
    sub = mod.computations.get(called)
    out = {}
    if sub is None:
        return out
    params = [i for i in sub.instructions if i.opcode == "parameter"]
    for idx, p in enumerate(params):
        consumers = [i for i in sub.instructions if p.name in i.operands]
        if consumers and all(i.opcode in ("dynamic-slice", "bitcast", "copy")
                             for i in consumers):
            ds = [i for i in consumers if i.opcode == "dynamic-slice"]
            if ds:
                out[idx] = (max(d.out_bytes for d in ds),
                            max(d.out_tpu_bytes for d in ds))
    return out


def summarize_module(mod: HloModule) -> Dict:
    """Trip-count-aware per-device totals from the parsed HLO.

    *_tpu fields normalize float tensors to bf16 (XLA:CPU upcasts bf16 GEMM
    operands to f32; on the TPU target these collectives/buffers stay bf16 —
    see DESIGN.md SS4)."""
    # computations dominated by *_vmem-scoped ops are Pallas-kernel inner
    # bodies on the TPU target: in the fused view only their block I/O
    # (dynamic-slice / dynamic-update-slice) touches HBM.  XLA rewrites strip
    # metadata from some interior dots/fusions, so a computation where >=50%
    # of substantial instructions carry the scope is flagged wholesale; ops
    # with the scope metadata are excluded wherever they appear (inline
    # kernels like local attention / RG-LRU live inside layer bodies).
    # Two-level VMEM flagging.  Level 1: a *fusion body* is VMEM-resident if
    # the majority of its metadata-carrying ops come from a *_vmem scope
    # (the fusion ROOT's metadata is often a fused-in dynamic_update_slice).
    # Level 2: a while-body computation is VMEM-resident if the majority of
    # its substantial instructions are vmem-tagged or call vmem fusions
    # (this catches interior dots whose metadata XLA rewrites stripped).
    vmem_fusion_comps = set()
    for cname, comp in mod.computations.items():
        tagged = [i for i in comp.instructions
                  if i.opcode not in _SKIP_BYTES_OPS and i.metadata_op]
        if tagged and sum(1 for i in tagged if "_vmem" in i.metadata_op) \
                >= max(1, (len(tagged) + 1) // 2):
            vmem_fusion_comps.add(cname)

    def _ins_vmem(i) -> bool:
        if "_vmem" in i.metadata_op:
            return True
        if i.opcode == "fusion":
            return i.attrs.get("calls", "").lstrip("%") in vmem_fusion_comps
        return False

    vmem_comps = set()
    for cname, comp in mod.computations.items():
        subst = [i for i in comp.instructions
                 if i.opcode not in _SKIP_BYTES_OPS]
        scored = [i for i in subst if i.metadata_op or _ins_vmem(i)]
        if not scored:
            continue
        marked = sum(1 for i in scored if _ins_vmem(i))
        if marked >= max(1, (len(scored) + 1) // 2):
            vmem_comps.add(cname)
    flops = 0.0
    hbm = 0.0
    hbm_tpu = 0.0
    hbm_tpu_fused = 0.0   # Pallas-kernel view: *_vmem scopes don't touch HBM
    comm: Dict[str, Dict] = {}
    colls = []
    for ins, mult, comp in walk_instructions(mod):
        flops += instruction_flops(mod, ins, comp) * mult
        comp_obj = mod.computations[comp]
        # copy-rooted fusions are loop double-buffering that TPU copy
        # elision/donation removes; convert-rooted fusions are the CPU
        # backend's bf16<->f32 shims that don't exist on the TPU target.
        _artifact = (ins.opcode == "copy" or
                     ins.name.split(".")[0].rstrip("0123456789")
                     in ("copy_bitcast_fusion", "wrapped_copy", "copy_fusion",
                         "wrapped_convert", "convert_bitcast_fusion",
                         "convert_fusion", "bitcast_copy_fusion",
                         "convert_copy_fusion", "copy"))
        if ins.opcode not in _SKIP_BYTES_OPS and not _artifact:
            name_op = ins.name + "|" + ins.opcode
            # ops inside a *_vmem named_scope, vmem fusions, or kernel-body
            # computations are resident in the Pallas kernels' VMEM on the
            # TPU target: the fused view counts only block reads/writes
            in_vmem_scope = _ins_vmem(ins) or comp in vmem_comps
            if "dynamic-update-slice" in name_op:
                # in-place aliased update: traffic = the touched slice (2x),
                # not the whole carried buffer.  The update is the smallest
                # non-scalar operand (the largest is the aliased buffer).
                ops_b = sorted((src.out_bytes, src.out_tpu_bytes)
                               for o in ins.operands
                               if (src := comp_obj.find(o)) is not None
                               and src.out_bytes > 64)
                upd_b, upd_bt = ops_b[0] if len(ops_b) > 1 else (0, 0)
                hbm += 2 * upd_b * mult
                hbm_tpu += 2 * upd_bt * mult
                if not in_vmem_scope:        # carry updates inside kernel
                    hbm_tpu_fused += 2 * upd_bt * mult  # bodies live in VMEM
            elif "dynamic-slice" in name_op:
                hbm += 2 * ins.out_bytes * mult
                hbm_tpu += 2 * ins.out_tpu_bytes * mult
                if not in_vmem_scope:
                    hbm_tpu_fused += 2 * ins.out_tpu_bytes * mult
            else:
                sliced = (_fusion_param_read_bytes(mod, ins)
                          if ins.opcode == "fusion" else {})
                in_b = in_bt = 0
                for oi, o in enumerate(ins.operands):
                    src = comp_obj.find(o)
                    if src is None or src.opcode == "constant":
                        continue
                    b, bt = sliced.get(oi, (src.out_bytes, src.out_tpu_bytes))
                    in_b += b
                    in_bt += bt
                hbm += (in_b + ins.out_bytes) * mult
                hbm_tpu += (in_bt + ins.out_tpu_bytes) * mult
                if not in_vmem_scope:
                    hbm_tpu_fused += (in_bt + ins.out_tpu_bytes) * mult
        if ins.is_collective and not ins.opcode.endswith("-done"):
            kind = ins.collective_kind
            # payload: operand bytes (all-gather: gathered output)
            in_bytes = sum(comp_obj.find(o).out_bytes for o in ins.operands
                           if comp_obj.find(o) is not None)
            in_tpu = sum(comp_obj.find(o).out_tpu_bytes for o in ins.operands
                         if comp_obj.find(o) is not None)
            payload = ins.out_bytes if kind == "all-gather" else in_bytes
            payload_tpu = (ins.out_tpu_bytes if kind == "all-gather"
                           else in_tpu)
            c = comm.setdefault(kind, {"count": 0, "bytes": 0.0,
                                       "bytes_tpu": 0.0})
            c["count"] += mult
            c["bytes"] += payload * mult
            c["bytes_tpu"] += payload_tpu * mult
            colls.append({"name": ins.name, "kind": kind, "bytes": payload,
                          "bytes_tpu": payload_tpu, "mult": mult,
                          "replica_groups": ins.attrs.get("replica_groups", "")})
    return {"parsed_flops": flops,
            "parsed_hbm_bytes": hbm,
            "parsed_hbm_bytes_tpu": hbm_tpu,
            "parsed_hbm_bytes_tpu_fused": hbm_tpu_fused,
            "comm": comm,
            "comm_bytes": sum(c["bytes"] for c in comm.values()),
            "comm_bytes_tpu": sum(c["bytes_tpu"] for c in comm.values()),
            "collectives": colls}


def _memory_dict(compiled) -> Dict:
    ma = compiled.memory_analysis()
    return {k: getattr(ma, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(ma, k)}


def _cost_dict(compiled) -> Dict:
    return {k: float(v) for k, v in compiled.cost_analysis().items()
            if isinstance(v, (int, float))}


def capture_step(step_fn, abstract_args, in_shardings, mesh,
                 meta: Optional[Dict] = None, donate_argnums=(),
                 out_shardings=None, build_graph: bool = True) -> CaptureResult:
    """Lower + compile a step function on a (possibly fake) mesh and parse the
    artifacts into a Chakra graph + roofline summary.  No device allocation.
    """
    t0 = time.time()
    kw = {}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    jitted = jax.jit(step_fn, donate_argnums=donate_argnums, **kw)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*abstract_args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    compiled_text = compiled.as_text()
    mod = parse_hlo(compiled_text)
    summary = summarize_module(mod)
    graph = hlo_to_chakra(mod, meta) if build_graph else chakra.Graph()
    meta = dict(meta or {})
    meta.update({"mesh_shape": dict(mesh.shape), "t_lower_s": t_lower,
                 "t_compile_s": t_compile,
                 "num_partitions": mod.num_partitions})
    return CaptureResult(
        meta=meta,
        lowered_text=lowered.as_text(),
        compiled_text=compiled_text,
        cost_analysis=_cost_dict(compiled),
        memory_analysis=_memory_dict(compiled),
        summary=summary,
        graph=graph,
    )


def stablehlo_op_counts(lowered_text: str) -> Dict[str, int]:
    """Op histogram of the pre-SPMD StableHLO (source-level counts for the
    paper's SS5.2 validation)."""
    counts: Dict[str, int] = {}
    for m in re.finditer(r"=\s+(?:stablehlo|mhlo|func)\.([\w.]+)",
                         lowered_text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts
