"""Reduce a profiler trace of a few steady steps to device intervals.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation run on that core. The host plane ``/host:CPU``
holds the benchmark's own ``TraceAnnotation`` spans (``bench.*``), which
mark the traced window and label what the host did in each device gap.
All times here are nanoseconds on the profiler's common clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the trace names an operation by its whole HLO text, "%fusion.3 = bf16[..]
# fusion(...), ..."; its instruction name is what precedes the " = "
INSTRUCTION = re.compile(r"^%?([\w.\-]+)\s*=")
# a loop or call is an event that spans the operations of its body; it is
# no work of its own, and counting it would cover the idle time inside it
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


class Span(NamedTuple):
    name: str
    start: float
    end: float


class Trace(NamedTuple):
    ops: Dict[int, List[Span]]      # device id -> operations, by start
    host: List[Span]                # the benchmark's own annotations
    window: Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a Trace; the window is the benchmark's
    ``bench.window`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[int, List[Span]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    Span(op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events)
            elif not m:
                host.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return make(ops, host)


def op_name(text: str) -> str:
    m = INSTRUCTION.match(text)
    return m.group(1) if m else text


def make(ops: Dict[int, List[Span]], host: List[Span]) -> Trace:
    """Operations clipped to the ``bench.window`` span, loops and calls
    left out."""
    wins = [s for s in host if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    if not ops:
        raise ValueError("trace has no operation on a device")
    w = (min(s.start for s in wins), max(s.end for s in wins))
    clipped = {}
    for d, evs in ops.items():
        inside = [Span(e.name, max(e.start, w[0]), min(e.end, w[1]))
                  for e in evs if e.end > w[0] and e.start < w[1]
                  and not CONTAINER.match(e.name)]
        clipped[d] = sorted(inside, key=lambda e: e.start)
    return Trace(clipped, sorted(host, key=lambda s: s.start), w)


def union(spans) -> List[Tuple[float, float]]:
    """Merged, sorted intervals covered by ``spans``."""
    out: List[List[float]] = []
    for s in sorted(spans, key=lambda s: s.start):
        if out and s.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s.end)
        else:
            out.append([s.start, s.end])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Intervals of ``a`` not covered by ``b`` (both merged and sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) * 1e-9


def busy_s(tr: Trace) -> float:
    """Seconds in which any operation ran, averaged over the devices."""
    return sum(length(union(v)) for v in tr.ops.values()) / len(tr.ops) * 1e-9


def device_idle(tr: Trace) -> float:
    """Share of the window in which no operation ran, mean over devices."""
    return 1.0 - busy_s(tr) / window_s(tr)


def gaps(tr: Trace):
    """[(label, seconds)]: every idle gap on every device, labelled by the
    innermost benchmark span around its middle ("none" if there is none)."""
    out = []
    spans = [s for s in tr.host if s.name != WINDOW_SPAN]
    w = [(tr.window[0], tr.window[1])]
    for evs in tr.ops.values():
        for a, b in subtract(w, union(evs)):
            mid = 0.5 * (a + b)
            around = [s for s in spans if s.start <= mid <= s.end]
            label = (min(around, key=lambda s: s.end - s.start).name
                     if around else "none")
            out.append((label, (b - a) * 1e-9))
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The operations that took most device time (seconds, mean over the
    devices) and the longest idle gaps."""
    by_name: Dict[str, float] = {}
    for evs in tr.ops.values():
        for e in evs:
            by_name[e.name] = by_name.get(e.name, 0.0) + (e.end - e.start)
    n = len(tr.ops)
    ops = sorted(((k, v / n * 1e-9) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(tr), key=lambda g: -g[1])[:top]
    return {"device_ops": [list(x) for x in ops],
            "idle_gaps": [list(x) for x in idle]}
