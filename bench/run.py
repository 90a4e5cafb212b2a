#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's train step through the program's own path, makes
the weights and batches on the device from the seed, compiles (or loads
from the persistent cache in ``.jax_cache/``), and runs the first three
steps through the window's own call, reading what the comparison needs.
The window then runs whole steps until ``--seconds`` have passed. With
``--trace 1`` a few more steps run under the profiler and the per-layer
metrics come from that trace, the op names of the executable that ran and
the last traced step's scalar outputs. Last, the program's state is freed
and the plain reference follows the same three steps; ``correct`` says
whether the program stayed within the limits of ``limits/<cell>.json``.

The last line of stdout is one JSON object. A run that finds no TPU, a
device kind missing from ``peaks.json``, or fewer chips than the cell
needs exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CHECKED_STEPS = 3          # steps the reference follows
TRACED_STEPS = 3
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoDevice(RuntimeError):
    pass


def log(msg: str):
    print(msg, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_device(chips: int, peaks: dict):
    """(devices, device record, peak): refuses anything but a TPU whose kind
    has published peaks, with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX found platform {d.platform!r}")
    if d.device_kind not in peaks:
        raise NoDevice(f"device kind {d.device_kind!r} has no peaks in "
                       f"peaks.json")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs, {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devs)}, peaks[d.device_kind]


def enable_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or ``JAX_COMPILATION_CACHE_DIR``), every program in it."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts backend compilations (cache loads are not compilations)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def setup(conf: dict, traffic: dict, devices, seed: int, alter=None):
    """Everything before the first timed step.  Returns (namespace, parts)
    where parts are the seconds of each phase.  ``alter`` maps the built
    Program to another (tests break the timed path so)."""
    import jax
    from bench import compare, data, program, weights
    parts = {}
    t = time.perf_counter()
    prog = program.build(conf, traffic, devices)
    if alter is not None:
        prog = alter(prog)
    words = weights.seed_words(seed)
    abstract = prog.abstract_args[0].params
    init = jax.jit(lambda w: program.state_from_params(
        weights.params(abstract, w)), out_shardings=prog.shardings[0])
    state = init(words)
    make = jax.jit(lambda w, i: data.batch(traffic, conf["vocab_size"], w, i),
                   out_shardings=prog.shardings[1])
    batches = [make(words, i) for i in range(traffic["distinct_batches"])]
    jax.block_until_ready((state, batches))
    parts["state_init_s"] = time.perf_counter() - t

    t = time.perf_counter()
    step = program.lower(prog).compile()
    parts["compile_s"] = time.perf_counter() - t

    # the first steps go through the window's own call and feed; the
    # comparison reads their losses, the first gradient as Adam got it (its
    # norms here, the whole of it on the host) and the change of every leaf
    t = time.perf_counter()
    losses = []
    g1 = None
    for i in range(CHECKED_STEPS):
        state, m = step(state, batches[i % len(batches)])
        losses.append(m["loss"])
        if i == 0:
            g1 = compare.leaf_norms(state.opt.mu) / (1 - prog.opt.b1)
            first = jax.device_get(jax.tree_util.tree_leaves(state.opt.mu))
    delta = compare.change_norms(state.params, abstract, words)
    losses = [float(x) for x in losses]
    parts["warmup_s"] = time.perf_counter() - t
    ns = SimpleNamespace(prog=prog, step=step, state=state, batches=batches,
                         words=words, readings=(losses, g1, delta, first),
                         next_batch=CHECKED_STEPS)
    return ns, parts


def window(ns, seconds: float):
    """Whole steps, one always in flight, until ``seconds`` have passed at
    the end of a step.  Returns (steps, elapsed seconds, losses)."""
    import jax
    n = len(ns.batches)
    i = ns.next_batch
    t0 = time.perf_counter()
    state, m = ns.step(ns.state, ns.batches[i % n])
    pending, done, losses = m["loss"], 0, []
    while True:
        i += 1
        state, m = ns.step(state, ns.batches[i % n])
        jax.block_until_ready(pending)
        losses.append(pending)
        done += 1
        pending = m["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(pending)
    losses.append(pending)
    done += 1
    elapsed = time.perf_counter() - t0
    ns.state, ns.next_batch = state, i + 1
    return done, elapsed, [float(x) for x in losses]


def traced(ns, steps: int):
    """``steps`` more steps under the profiler, one in flight as in the
    window, each host phase in a ``bench.*`` span.  The ``bench.window``
    span runs from the first dispatch to the end of the last step, so it
    holds exactly ``steps`` steps.  Returns the reduced trace; the last
    step's scalar outputs are fetched to the host after the window has
    ended, into ``ns.step_out``."""
    import jax
    from bench import devtrace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    n = len(ns.batches)
    state, i = ns.state, ns.next_batch
    ann = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with ann("bench.window"):
            pending = None
            for _ in range(steps):
                with ann("bench.batch"):
                    b = ns.batches[i % n]
                    i += 1
                with ann("bench.dispatch"):
                    state, m = ns.step(state, b)
                if pending is not None:
                    with ann("bench.block"):
                        jax.block_until_ready(pending)
                pending = m["loss"]
            with ann("bench.block"):
                jax.block_until_ready(pending)
    finally:
        jax.profiler.stop_trace()
    ns.state, ns.next_batch = state, i
    ns.step_out = {k: float(v) for k, v in jax.device_get(m).items()
                   if v.ndim == 0}
    tr = devtrace.load(devtrace.find_xplane(TRACE_DIR))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return tr


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def flint_prediction(ns, measured_step_s: float, peak_bytes: int):
    """Flint's own path on the cell's step: capture from the chip's
    compiler, simulate, and set the prediction beside the measurement.
    Printed only; these are not metrics."""
    from repro.configs.base import SystemConfig
    from repro.core.capture import capture_step
    from repro.core.costmodel import simulate
    p = ns.prog
    t = time.perf_counter()
    cap = capture_step(p.step, p.abstract_args, p.shardings, p.mesh,
                       meta={"arch": p.cfg.name}, donate_argnums=(0,),
                       out_shardings=p.out_shardings)
    res = simulate(cap.graph, SystemConfig(chips=p.n_chips))
    log(f"[flint] capture + simulate {time.perf_counter() - t:.1f}s, graph "
        f"{len(cap.graph)} nodes; predicted step {res.total_time:.6f}s, "
        f"measured {measured_step_s:.6f}s, predicted/measured "
        f"{res.total_time / measured_step_s:.4f}; predicted peak_bytes "
        f"{res.peak_bytes:.6e} B, memory_stats peak {peak_bytes} B, "
        f"predicted/measured {res.peak_bytes / peak_bytes:.4f}")


def result_numbers(d):
    return {k: {"value": v, "unit": u} for k, (v, u) in d.items()}


def run(args, check=check_device, alter=None) -> dict:
    """One run; returns the result line's object.  Tests replace ``check``
    and break the timed path through ``alter`` (see ``setup``)."""
    from bench import compare, reference_step, registry
    bench = registry.benchmark()
    cell = registry.workload(args.workload, bench)
    conf = registry.config(cell["config"], bench)
    traffic = registry.traffic(cell["traffic"])
    limits = registry.limits(args.workload)
    devices, device, peak = check(cell["chips"], registry.peaks())
    devices = devices[:cell["chips"]]
    counter = CompileCounter()
    parts = {"import_device_s": time.perf_counter() - T_START}

    ns, more = setup(conf, traffic, devices, args.seed, alter)
    parts.update(more)
    setup_s = time.perf_counter() - T_START
    log("[setup] " + " ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f" -> setup_s {setup_s:.3f}")

    compiles = counter.n
    steps, elapsed, losses = window(ns, args.seconds)
    tokens_per_s = steps * ns.prog.tokens_per_step / elapsed
    log(f"[window] {steps} steps in {elapsed:.4f}s, "
        f"{elapsed / steps:.6f}s a step, {tokens_per_s:.2f} tokens/s; "
        f"compiles inside the window {counter.n - compiles}; "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    failed = sum(1 for x in losses if not math.isfinite(x))

    trace = hlo = None
    if args.trace:
        trace = traced(ns, TRACED_STEPS)
        # the executable that ran: its op names are the trace's instructions'
        hlo = ns.step.as_text()
    peak_bytes = memory_peak(devices)
    log(f"[memory] peak_bytes_in_use {peak_bytes} B on the fullest chip")

    if args.trace:
        try:
            flint_prediction(ns, elapsed / steps, peak_bytes)
        except Exception as e:  # noqa: BLE001 - a report, not a metric
            log(f"[flint] not measured: {type(e).__name__}: {e}")

    prog = ns.prog
    del ns.state, ns.step
    ref = reference_step.build(conf, prog)
    t = time.perf_counter()
    ref_readings = reference_step.readings(ref, ns.words, ns.batches,
                                           ns.readings[3], CHECKED_STEPS)
    log(f"[reference] {CHECKED_STEPS} steps in {time.perf_counter() - t:.1f}s;"
        f" losses program {ns.readings[0]} reference {ref_readings[0]}")
    nums = compare.numbers(ns.readings, ref_readings)
    correct, rows = compare.verdict(nums, limits)

    if args.trace:
        from bench import devtrace
        ctx = SimpleNamespace(
            trace=trace, hlo=hlo, step_out=ns.step_out,
            traced_steps=TRACED_STEPS, n_chips=prog.n_chips,
            peak=peak, model_flops_per_step=registry.flops(
                conf["flops"]).model_flops_per_step(conf, traffic))
        metrics = {}
        t = time.perf_counter()
        for m in registry.cell_metrics(args.workload, bench, "per_layer"):
            v = registry.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
        log(f"[per_layer] {len(metrics)} metrics read in "
            f"{time.perf_counter() - t:.2f}s from {len(hlo)} characters of "
            f"HLO")
        device = dict(device, busy_s=devtrace.busy_s(trace),
                      window_s=devtrace.window_s(trace))
    else:
        metrics = {"train_tokens_per_s": (tokens_per_s, "tokens/s"),
                   "setup_s": (setup_s, "s")}
    device["memory_peak_bytes"] = peak_bytes
    out = {"correct": correct, "attempted": steps, "failed": failed,
           "metrics": result_numbers(metrics), "device": device}
    if args.trace:
        out["breakdown"] = devtrace.breakdown(trace)
    out["compared"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"[compared] {k} {v!r} limit {lim!r}", file=sys.stderr)
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        enable_cache()
        out = run(args)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
