"""HLO -> Chakra conversion (Flint's Graph Converter, paper SS4.3).

Walks the scheduled post-SPMD HLO module and emits a Chakra graph whose
edges are the SSA operands — the true data dependencies.  Bookkeeping ops
(tuple/GTE/parameter/bitcast/constant) are aliased through to their
producers, matching how the paper drops FX input nodes from Chakra.

While loops (jax.lax.scan):
  * bodies containing collectives are *expanded* trip_count times, chaining
    loop-carried deps — the per-iteration collectives then appear explicitly
    (a post-execution trace would show exactly these);
  * collective-free bodies (e.g. flash-attention kv scans) are *collapsed*
    into one COMP node with flops/bytes scaled by trip count, keeping graphs
    compact without losing cost.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.core import chakra
from repro.core.hlo_parse import (COLLECTIVE_OPS, HloModule, Instruction,
                                  instruction_flops, parse_permute_pairs,
                                  parse_replica_groups, loop_trip_count)

# ops that never become nodes: forward deps through them
_ALIAS_OPS = {"tuple", "get-tuple-element", "parameter", "bitcast",
              "constant", "iota", "partition-id", "replica-id",
              "after-all", "opt-barrier"}

_MAX_EXPAND = 128


def _computation_has_collective(mod: HloModule, comp_name: str,
                                _seen=None) -> bool:
    _seen = _seen if _seen is not None else set()
    if comp_name in _seen:
        return False
    _seen.add(comp_name)
    comp = mod.computations.get(comp_name)
    if comp is None:
        return False
    for ins in comp.instructions:
        if ins.is_collective:
            return True
        for key in ("body", "condition", "calls"):
            sub = ins.attrs.get(key, "").lstrip("%")
            if sub and _computation_has_collective(mod, sub, _seen):
                return True
    return False


def _comp_cost(mod: HloModule, comp_name: str, mult: int = 1):
    """(flops, bytes) of a computation incl. nested whiles (for collapse)."""
    comp = mod.computations.get(comp_name)
    flops = 0.0
    bytes_ = 0.0
    if comp is None:
        return flops, bytes_
    for ins in comp.instructions:
        if ins.opcode in _ALIAS_OPS:
            continue
        if ins.opcode == "while":
            body = ins.attrs.get("body", "").lstrip("%")
            trips = loop_trip_count(mod, ins)
            f, b = _comp_cost(mod, body, 1)
            flops += f * trips
            bytes_ += b * trips
            continue
        flops += instruction_flops(mod, ins, comp_name)
        bytes_ += ins.out_bytes
        for op in ins.operands:
            src = comp.find(op)
            if src is not None:
                bytes_ += src.out_bytes
    return flops * mult, bytes_ * mult


class _Tuple:
    """Per-element dependency sets for HLO tuple values.

    Tracking tuple elements separately through while loops is what keeps
    loop-*invariant* inputs (e.g. the stacked weight tensors feeding FSDP
    all-gathers) free of false cross-iteration dependencies — the exact
    failure mode of CUDA-API-level capture the paper calls out (SS2.2)."""

    def __init__(self, elements: List[List[int]]):
        self.elements = [list(e) for e in elements]

    def flat(self) -> List[int]:
        out: List[int] = []
        for e in self.elements:
            out.extend(e)
        return list(dict.fromkeys(out))


def _flat(v) -> List[int]:
    if isinstance(v, _Tuple):
        return v.flat()
    return list(v)


class _Builder:
    def __init__(self, mod: HloModule, graph: chakra.Graph):
        self.mod = mod
        self.g = graph

    def build_computation(self, comp_name: str, param_vals=None,
                          prefix: str = ""):
        """Emit nodes for one computation instance.

        param_vals[i]: value (_Tuple or id list) backing parameter i.
        Returns the value backing the ROOT instruction."""
        comp = self.mod.computations[comp_name]
        env: Dict[str, object] = {}
        param_idx = 0
        root_val = []
        for ins in comp.instructions:
            operand_vals = [env.get(op, []) for op in ins.operands]
            dep_ids: List[int] = []
            for v in operand_vals:
                dep_ids.extend(_flat(v))
            dep_ids = list(dict.fromkeys(dep_ids))

            if ins.opcode == "parameter":
                env[ins.name] = (param_vals[param_idx]
                                 if param_vals and param_idx < len(param_vals)
                                 else [])
                param_idx += 1
            elif ins.opcode == "tuple":
                env[ins.name] = _Tuple([_flat(v) for v in operand_vals])
            elif ins.opcode == "get-tuple-element":
                idx = int(ins.attrs.get("index", "0"))
                src = operand_vals[0] if operand_vals else []
                if isinstance(src, _Tuple) and idx < len(src.elements):
                    env[ins.name] = src.elements[idx]
                else:
                    env[ins.name] = _flat(src)
            elif ins.opcode == "while":
                env[ins.name] = self._emit_while(ins, operand_vals, dep_ids,
                                                 prefix)
            elif ins.opcode in _ALIAS_OPS:
                env[ins.name] = dep_ids
            elif ins.is_collective:
                env[ins.name] = [self._emit_collective(ins, dep_ids, prefix)]
            else:
                env[ins.name] = [self._emit_comp(ins, dep_ids, prefix,
                                                 comp_name)]
            if ins.raw.strip().startswith("ROOT") or ins is comp.instructions[-1]:
                root_val = env[ins.name]
        return root_val

    def _emit_comp(self, ins: Instruction, deps, prefix, comp_name) -> int:
        flops = instruction_flops(self.mod, ins, comp_name)
        in_bytes = 0
        comp = self.mod.computations[comp_name]
        for op in ins.operands:
            src = comp.find(op)
            if src is not None:
                in_bytes += src.out_bytes
        return self.g.add(prefix + ins.name, chakra.COMP, deps=deps,
                          flops=flops, bytes=float(in_bytes + ins.out_bytes),
                          out_bytes=float(ins.out_bytes), op=ins.opcode,
                          src_op=ins.metadata_op)

    def _emit_collective(self, ins: Instruction, deps, prefix) -> int:
        kind = ins.collective_kind
        groups = parse_replica_groups(ins.attrs.get("replica_groups", ""),
                                      self.mod.num_partitions)
        comp = None
        in_bytes = 0
        for cn, c in self.mod.computations.items():
            if c.find(ins.name) is ins:
                comp = c
                break
        if comp:
            for op in ins.operands:
                src = comp.find(op)
                if src is not None:
                    in_bytes += src.out_bytes
        # comm_bytes: per-device payload (operand size; the roofline spec's
        # "sum operand sizes").  all-gather's operand is the pre-gather shard.
        payload = float(in_bytes if kind != "all-gather" else ins.out_bytes)
        attrs = dict(comm_kind=kind, comm_bytes=payload,
                     in_bytes=float(in_bytes), out_bytes=float(ins.out_bytes),
                     group_size=len(groups[0]) if groups else 1,
                     n_groups=len(groups), group=list(groups[0]) if groups else [],
                     src_op=ins.metadata_op)
        if kind == "collective-permute":
            attrs["pairs"] = parse_permute_pairs(
                ins.attrs.get("source_target_pairs", ""))
            attrs["comm_bytes"] = float(ins.out_bytes)
        return self.g.add(prefix + ins.name, chakra.COMM_COLL, deps=deps,
                          **attrs)

    def _emit_while(self, ins: Instruction, operand_vals, deps, prefix):
        body = ins.attrs.get("body", "").lstrip("%")
        trips = loop_trip_count(self.mod, ins)
        if not _computation_has_collective(self.mod, body) or trips > _MAX_EXPAND:
            f, b = _comp_cost(self.mod, body, trips)
            nid = self.g.add(prefix + ins.name, chakra.COMP, deps=deps,
                             flops=f, bytes=b, op="while.collapsed",
                             trips=trips, src_op=ins.metadata_op)
            return [nid]
        # the loop state is a single tuple parameter; thread per-element deps
        # so loop-invariant elements don't serialize across iterations
        state = operand_vals[0] if operand_vals else []
        for t in range(trips):
            state = self.build_computation(body, [state],
                                           prefix=f"{prefix}{ins.name}.it{t}/")
        return state


def hlo_to_chakra(mod: HloModule, meta: Optional[dict] = None) -> chakra.Graph:
    g = chakra.Graph(meta={"source": "flint-jax", "entry": mod.entry,
                           "num_partitions": mod.num_partitions,
                           **(meta or {})})
    b = _Builder(mod, g)
    b.build_computation(mod.entry)
    return g


def _stage_assignment(g: chakra.Graph, order: List[int], num_stages: int,
                      assignment, allow_backward: bool = False) -> List[int]:
    """nid -> stage index.  ``assignment`` is a balancing policy ("flops":
    contiguous topo segments balanced by compute flops; "nodes": balanced by
    node count) or an explicit per-node map (list/dict nid -> stage).
    Explicit maps are validated: every stage non-empty, every dependency
    pointing to the same or an earlier stage (a pipeline never sends
    activations backwards inside one step's dataflow).  ``allow_backward``
    lifts the direction check for the microbatched lowering, which turns
    backward cross-stage edges (an explicit backward pass) into gradient
    data channels instead of rejecting them."""
    n = len(g.nodes)
    S = num_stages
    if not isinstance(assignment, str):
        if not isinstance(assignment, dict) and len(assignment) != n:
            raise ValueError(f"stage_assignment covers {len(assignment)} "
                             f"nodes, graph has {n}")
        get = (assignment.get if isinstance(assignment, dict)
               else lambda nid: assignment[nid])
        stage_of = []
        for nid in range(n):
            s = get(nid)
            if s is None:
                raise ValueError(f"stage_assignment omits node {nid} "
                                 f"({g.node(nid).name!r}) — explicit maps "
                                 "must cover every node")
            stage_of.append(int(s))
        for nid, s in enumerate(stage_of):
            if not 0 <= s < S:
                raise ValueError(f"stage_assignment maps node {nid} to "
                                 f"stage {s} outside 0..{S - 1}")
        missing = set(range(S)) - set(stage_of)
        if missing:
            raise ValueError(f"stage_assignment leaves stage(s) "
                             f"{sorted(missing)} empty")
        if not allow_backward:
            for node in g.nodes:
                for d in node.all_deps:
                    if stage_of[d] > stage_of[node.id]:
                        raise ValueError(
                            f"stage_assignment creates a backward "
                            f"cross-stage dependency: node {node.id} (stage "
                            f"{stage_of[node.id]}) depends on node {d} "
                            f"(stage {stage_of[d]})")
        return stage_of
    if assignment not in ("flops", "nodes"):
        raise ValueError(f"unknown stage assignment policy {assignment!r}: "
                         "expected 'flops', 'nodes' or an explicit map")
    if assignment == "flops":
        # +1 keeps zero-flops (comm/mem) nodes from collapsing a stage
        w = [g.node(nid).attrs.get("flops", 0.0) + 1.0 for nid in range(n)]
    else:
        w = [1.0] * n
    total = sum(w)
    stage_of = [0] * n
    s = 0
    cum = 0.0
    for idx, nid in enumerate(order):
        stage_of[nid] = s
        cum += w[nid]
        left = n - idx - 1
        if s < S - 1 and (cum >= total * (s + 1) / S
                          or left == S - 1 - s):
            s += 1
    return stage_of


def split_pipeline_stages(g: chakra.Graph, num_stages: int,
                          assignment="flops", replicas: int = 1,
                          num_microbatches: int = 1,
                          schedule: str = "gpipe",
                          virtual_stages: Optional[int] = None,
                          share_replica_graphs: Optional[bool] = None):
    """Split one workload graph into an S-stage pipeline ``MPMDProgram``.

    The graph is partitioned into `num_stages` contiguous topological
    segments (see ``_stage_assignment``); each cross-stage dependency
    u(stage i) -> v(stage j) becomes a matched **send/recv P2P-collective
    pair**: a ``COMM_COLL`` node of ``comm_kind="p2p"`` with
    ``group=[rank(i), rank(j)]`` on each side, so the MPMD engine's
    (group, program-order) barrier keying synchronizes the stages exactly
    like a FIFO channel (one pair per (producer, destination stage); the
    recv materializes the producer's ``out_bytes`` on the consumer stage).

    `replicas` data-parallel replicas of the pipeline run side by side:
    rank = stage * replicas + replica (stage-major), and every original
    collective's group is rewritten to its stage's rank set — the DP
    all-reduce of a stage spans that stage's replicas (with ``replicas=1``
    collectives become stage-local and free, modeling the repartition of
    the cluster into stages).  Returns an ``MPMDProgram`` over
    ``num_stages * replicas`` ranks whose meta records the split
    (``stage_of``, ``p2p_pairs``, ``num_stages``, ``replicas``).

    ``num_microbatches`` > 1 lowers a *microbatched* pipeline instead:
    each stage's work is replayed m times at 1/m scale under the chosen
    ``schedule`` ("gpipe", "1f1b" or "interleaved" with
    ``virtual_stages`` chunks per rank), with schedule-dependent
    send/recv ordering and synthesized backward gradient channels — see
    ``repro.core.costmodel.schedule``.  ``share_replica_graphs`` (default
    on when replicas > 1 and m > 1) makes all replicas of a stage share
    one graph via relative p2p addressing.  With m == 1 every schedule is
    equivalent (one wave) and this function emits the classic split above,
    bit-identically to previous releases.  Knob values are validated up
    front: bad ``num_microbatches``/``schedule``/``virtual_stages`` raise
    ``schedule.PipelineConfigError`` listing the valid choices.
    """
    from repro.core.costmodel.mpmd import MPMDProgram
    from repro.core.costmodel.schedule import (lower_microbatched,
                                               validate_pipeline_schedule)

    S = int(num_stages)
    R = int(replicas)
    n = len(g.nodes)
    if S < 1 or R < 1:
        raise ValueError(f"num_stages={S} / replicas={R} must be >= 1")
    if n == 0 or S > n:
        raise ValueError(f"cannot split a {n}-node graph into {S} stages")
    m, sched, v = validate_pipeline_schedule(S, num_microbatches, schedule,
                                             virtual_stages)
    if m > 1:
        return lower_microbatched(g, S, assignment, R, m, sched,
                                  virtual_stages=v,
                                  share_replica_graphs=share_replica_graphs)
    order = g.topo_order()
    stage_of = _stage_assignment(g, order, S, assignment)
    stage_ranks = {s: list(range(s * R, (s + 1) * R)) for s in range(S)}

    rank_graphs: List[Optional[chakra.Graph]] = [None] * (S * R)
    n_pairs = 0
    for d in range(R):
        sgs = [chakra.Graph(meta={**g.meta, "pipeline_stage": s,
                                  "num_stages": S, "pipeline_replica": d})
               for s in range(S)]
        local: Dict[int, tuple] = {}       # orig nid -> (stage, local nid)
        xfer: Dict[tuple, int] = {}        # (orig nid, dst stage) -> recv id
        chan: Dict[tuple, tuple] = {}      # (src, dst) -> (last send, last recv)

        def cross(dd: int, dst: int) -> int:
            key = (dd, dst)
            rv = xfer.get(key)
            if rv is None:
                src, lsrc = local[dd]
                name = g.node(dd).name
                payload = float(g.node(dd).attrs.get("out_bytes", 0.0))
                pg = [src * R + d, dst * R + d]
                # FIFO channel discipline: chain same-channel sends (and
                # recvs) with ctrl edges so both sides commit their p2p
                # collectives in creation order — the MPMD engine pairs the
                # k-th send with the k-th recv of a group, and without the
                # chain a cheap late-created send could overtake an
                # expensive earlier one and cross the wires (a consumer
                # would start before its real producer finished).  A real
                # single-channel p2p stream serializes exactly like this.
                prev_s, prev_r = chan.get((src, dst), (None, None))
                snid = sgs[src].add(
                    f"send[{name}>s{dst}]", chakra.COMM_COLL,
                    deps=[lsrc],
                    ctrl_deps=[prev_s] if prev_s is not None else [],
                    comm_kind="p2p", comm_bytes=payload, out_bytes=0.0,
                    group=pg, group_size=2, p2p_src_stage=src,
                    p2p_dst_stage=dst)
                rv = xfer[key] = sgs[dst].add(
                    f"recv[{name}<s{src}]", chakra.COMM_COLL,
                    ctrl_deps=[prev_r] if prev_r is not None else [],
                    comm_kind="p2p", comm_bytes=payload, out_bytes=payload,
                    group=pg, group_size=2, p2p_src_stage=src,
                    p2p_dst_stage=dst)
                chan[(src, dst)] = (snid, rv)
            return rv

        for nid in order:
            node = g.node(nid)
            s = stage_of[nid]
            deps_l: List[int] = []
            ctrl_l: List[int] = []
            for src_deps, out in ((node.deps, deps_l),
                                  (node.ctrl_deps, ctrl_l)):
                for dd in src_deps:
                    ds, dl = local[dd]
                    out.append(dl if ds == s else cross(dd, s))
            attrs = dict(node.attrs)
            if node.type == chakra.COMM_COLL:
                # the collective now spans this stage's replica pool
                attrs["group"] = list(stage_ranks[s])
                attrs["group_size"] = R
            local[nid] = (s, sgs[s].add(node.name, node.type,
                                        deps=list(dict.fromkeys(deps_l)),
                                        ctrl_deps=list(dict.fromkeys(ctrl_l)),
                                        **attrs))
        if d == 0:
            n_pairs = len(xfer)
        for s in range(S):
            rank_graphs[s * R + d] = sgs[s]

    return MPMDProgram(rank_graphs,
                       meta={"num_stages": S, "replicas": R,
                             "assignment": (assignment if isinstance(
                                 assignment, str) else "explicit"),
                             "stage_of": list(stage_of),
                             "p2p_pairs": n_pairs,
                             "source_nodes": n})


def expand_collective_p2p(kind: str, payload: int, group: List[int],
                          algo: str = "ring"):
    """Expand one collective into point-to-point (src, dst, bytes, round)
    messages — the Chakra representation used for custom-collective studies
    (paper SS6.2) and network emulation (SS6.3)."""
    n = len(group)
    msgs = []
    if n <= 1:
        return msgs
    if algo == "ring":
        rounds = {"all-gather": n - 1, "reduce-scatter": n - 1,
                  "all-reduce": 2 * (n - 1)}.get(kind, n - 1)
        chunk = payload / n
        for r in range(rounds):
            for i in range(n):
                msgs.append((group[i], group[(i + 1) % n], chunk, r))
    elif algo == "hd":  # recursive halving/doubling
        import math
        steps = int(math.log2(n)) if n & (n - 1) == 0 else None
        if steps is None:
            return expand_collective_p2p(kind, payload, group, "ring")
        size = payload / 2
        for s in range(steps):
            stride = 2 ** s
            for i in range(n):
                msgs.append((group[i], group[i ^ stride], size, s))
            size /= 2
    elif algo == "a2a_direct":
        chunk = payload / n
        for i in range(n):
            for j in range(n):
                if i != j:
                    msgs.append((group[i], group[j], chunk, 0))
    return msgs
