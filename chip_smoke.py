#!/usr/bin/env python3
"""Proof that Flint's main path runs on a TPU v5e at full width.

  python chip_smoke.py                # one chip
  python chip_smoke.py --four-chips   # the sharded path on four chips

One chip: the three Pallas kernels at real widths against their f32
oracles, mamba2-780m training and serving through ``repro.launch.train`` /
``repro.launch.serve``, then Flint's own path on that train step: capture
from the chip's compiler -> Chakra graph -> ``simulate`` -> ``dse.explore``.

Four chips: the mamba2-780m train step sharded over a 2x2 (data, model)
mesh against the same steps on one device, the state's spread over the
devices, and the collectives of the captured sharded step.

Everything runs in this one process, which holds the chip(s).  Weights and
inputs are random from fixed seeds.  Any failed check exits non-zero with
no result line; on success the last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import ShapeConfig, SystemConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "mamba2-780m"
# batch x seq of the one-chip train step: compiled for v5e, the step holds
# 14.3e9 of the chip's 16 GiB (params + f32 Adam moments 7.8e9, the rest
# activations and the (B, S, vocab) logits); twice the batch does not fit
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 2048, 6
SERVE_BATCH, SERVE_PROMPT, SERVE_STEPS = 8, 2048, 16
FOUR_CHIP_STEPS = 3
HBM_BYTES = 16 * 2**30
# SystemConfig's defaults are v5e datasheet numbers (configs/base.py); a
# device not named here has no peak to assume
SYSTEMS = {"TPU v5 lite": SystemConfig()}


class SmokeFailure(AssertionError):
    pass


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def max_err(x, ref):
    return float(jnp.max(jnp.abs(jnp.asarray(x, jnp.float32) -
                                 jnp.asarray(ref, jnp.float32))))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(min_count: int = 1):
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX found platform {d.platform!r}")
    check(d.device_kind in SYSTEMS,
          f"device kind {d.device_kind!r} is not a v5e: no peaks known")
    check(len(devs) >= min_count, f"need {min_count} devices, have {len(devs)}")
    return SYSTEMS[d.device_kind], {"platform": d.platform,
                                    "kind": d.device_kind, "count": len(devs)}


def phase_kernels(interpret=False, S=4096, KV=8, G=4, hd=128, s_ssd=2048,
                  h=48, p=64, n=128, chunk=256, d_rnn=4096):
    """Each kernel against its ref.py oracle, computed in f32 at highest
    matmul precision."""
    from repro.kernels import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 10)

    # flash attention, GQA 32 query / 8 kv heads, bf16
    q = jax.random.normal(ks[0], (1, S, KV, G, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, KV, hd), jnp.bfloat16)
    o = ops.flash_attention(q, k, v, causal=True, interpret=interpret)
    qf = jnp.moveaxis(q, 1, 3).reshape(KV * G, S, hd).astype(jnp.float32)
    kf = jnp.moveaxis(k, 1, 2).reshape(KV, S, hd).astype(jnp.float32)
    vf = jnp.moveaxis(v, 1, 2).reshape(KV, S, hd).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        # one kv head at a time: the oracle materializes (G, S, S) scores
        oref = jnp.concatenate([ref.flash_attention_oracle(
            qf[i * G:(i + 1) * G], kf[i:i + 1], vf[i:i + 1], causal=True)
            for i in range(KV)])
    oref = jnp.moveaxis(oref.reshape(1, KV, G, S, hd), 3, 1)
    # bf16 output rounding (2^-9 |o|, |o| < 4) plus bf16 probabilities in
    # the P.V matmul
    err, tol = max_err(o, oref), 3e-2
    print(f"[kernels] flash_attention (1,{S},{KV}x{G},{hd}) bf16: "
          f"max|err|={err:.3e} tol={tol:g}")
    check(err <= tol, f"flash_attention error {err} > {tol}")

    # Mamba2 SSD at mamba2-780m widths
    x = jax.random.normal(ks[3], (2, s_ssd, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (2, s_ssd, h)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[5], (h,)))
    Bm = 0.5 * jax.random.normal(ks[6], (2, s_ssd, n))
    Cm = 0.5 * jax.random.normal(ks[7], (2, s_ssd, n))
    y, sfin = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        yr, sfr = jax.jit(ref.ssd_oracle)(x, dt, A, Bm, Cm)
    # the kernel's f32 matmuls run at the MXU's default precision (bf16
    # passes, ~2^-8 relative per product) over chunk-long contractions;
    # errors are taken relative to the largest reference value
    tol = 2e-2
    ey = max_err(y, yr) / float(jnp.max(jnp.abs(yr)))
    es = max_err(sfin, sfr) / float(jnp.max(jnp.abs(sfr)))
    print(f"[kernels] ssd (2,{s_ssd},{h},{p}) n={n} chunk={chunk}: "
          f"rel err y={ey:.3e} state={es:.3e} tol={tol:g}")
    check(max(ey, es) <= tol, f"ssd relative error {max(ey, es)} > {tol}")

    # RG-LRU scan at recurrentgemma-9b's d_rnn
    a = 0.4 + 0.5 * jax.nn.sigmoid(jax.random.normal(ks[8], (2, s_ssd, d_rnn)))
    b = 0.1 * jax.random.normal(ks[9], (2, s_ssd, d_rnn))
    hk = ops.rglru_scan(a, b, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        hr = jax.jit(ref.rglru_scan_oracle)(a, b)
    # the same f32 recurrence in the same order: per-step rounding (and
    # FMA contraction) of ~1e-7, damped by a <= 0.9
    err, tol = max_err(hk, hr), 1e-5
    print(f"[kernels] rglru_scan (2,{s_ssd},{d_rnn}): max|err|={err:.3e} "
          f"tol={tol:g}")
    check(err <= tol, f"rglru_scan error {err} > {tol}")


def train_argv(ckpt_dir, batch=TRAIN_BATCH, seq=TRAIN_SEQ, steps=TRAIN_STEPS,
               smoke=False):
    return (["--arch", ARCH, "--steps", str(steps), "--batch", str(batch),
             "--seq-len", str(seq), "--log-every", "1", "--ckpt-dir",
             ckpt_dir, "--ckpt-every", str(steps + 1)]
            + (["--smoke"] if smoke else []))


def phase_train(argv):
    """Returns the median steady step time (s)."""
    from repro.launch import train
    with tempfile.TemporaryDirectory() as d:
        log = train.main(argv(d))
    losses = [m["loss"] for m in log]
    times = [m["t_ms"] / 1e3 for m in log]
    check(len(log) >= 2, f"train logged {len(log)} steps")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    med = statistics.median(times[1:])
    print(f"[train] losses {[round(x, 4) for x in losses]}")
    print(f"[train] first step (compile + run) {times[0]:.3f}s; steady steps "
          f"{[round(t, 4) for t in times[1:]]} median {med:.4f}s")
    return med


def phase_serve(argv):
    from repro.launch import serve
    toks, logits = serve.main(argv)
    check(bool(jnp.all(jnp.isfinite(logits))), "non-finite decode logits")
    print(f"[serve] tokens {tuple(toks.shape)} logits {tuple(logits.shape)} "
          f"finite")


def train_abstract_args(setup):
    from repro.train.optimizer import abstract_opt_state
    from repro.train.train_step import TrainState
    pa = setup.model.abstract_params()
    tok = jax.ShapeDtypeStruct((setup.data.global_batch, setup.data.seq_len),
                               jnp.int32)
    return (TrainState(pa, abstract_opt_state(pa), {}),
            {"tokens": tok, "labels": tok})


def in_use_bytes(mem):
    return (mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
            - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"])


def capture_summary(cap, cfg, shape, n_dev, tag):
    """Checks common to every capture; returns the model FLOPs per device."""
    from repro.core.costmodel.analytical import model_flops_per_step
    from repro.core.hlo_parse import parse_hlo
    mod = parse_hlo(cap.compiled_text)
    ops_seen = {i.opcode for c in mod.computations.values()
                for i in c.instructions}
    check("T" not in ops_seen, "layout tiling parsed as opcode 'T'")
    check(len(cap.graph) > 0, "empty captured graph")
    mf = model_flops_per_step(cfg, shape, n_dev)
    pf = cap.summary["parsed_flops"]
    mem = cap.memory_analysis
    print(f"[{tag}] graph {len(cap.graph)} nodes; parsed_flops/device "
          f"{pf:.4e}, XLA cost_analysis flops {cap.cost_analysis['flops']:.4e}"
          f", 6*N*tokens/device {mf:.4e} (ratio {pf / mf:.3f})")
    print(f"[{tag}] memory_analysis: args {mem['argument_size_in_bytes']} "
          f"out {mem['output_size_in_bytes']} alias "
          f"{mem['alias_size_in_bytes']} temp {mem['temp_size_in_bytes']} "
          f"-> in use {in_use_bytes(mem)} B per device")
    check(pf >= 0.5 * mf, f"parsed_flops {pf:.4e} < 0.5 * 6NT {mf:.4e}")
    return mem


def phase_capture(argv, measured_s, system):
    """Capture the train step run above, simulate it and explore around it."""
    from repro.core.capture import capture_step
    from repro.core.costmodel import simulate
    from repro.core.dse import Knob, explore
    from repro.launch import train
    from repro.parallel.mesh import make_mesh
    setup = train.build(train.parse_args(argv("")))
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    cap = capture_step(setup.step, train_abstract_args(setup), None, mesh,
                       meta={"arch": setup.cfg.name}, donate_argnums=(0,))
    shape = ShapeConfig("chip_smoke", "train", setup.data.seq_len,
                        setup.data.global_batch)
    print(f"[capture] {setup.cfg.name} train step batch "
          f"{setup.data.global_batch} x seq {setup.data.seq_len}, compiled "
          f"in {cap.meta['t_compile_s']:.1f}s")
    mem = capture_summary(cap, setup.cfg, shape, 1, "capture")
    check(in_use_bytes(mem) >= HBM_BYTES / 2,
          f"step holds {in_use_bytes(mem)} B, under half of the chip")
    sysc = system.replace(chips=1)
    res = simulate(cap.graph, sysc)
    print(f"[simulate] predicted step {res.total_time:.4f}s, measured median "
          f"{measured_s:.4f}s, predicted/measured "
          f"{res.total_time / measured_s:.3f}; predicted peak "
          f"{res.peak_bytes:.4e} B")
    check(math.isfinite(res.total_time) and res.total_time > 0,
          f"bad prediction {res.total_time}")
    trials = explore(lambda cfg: cap.graph, sysc,
                     [Knob("slow_chip_ratio", [0.0, 1.0], "hardware")],
                     parallel=1)
    for t in trials:
        print(f"[dse] {t.config} -> {t.objective:.4f}s")
    check(len(trials) == 2 and all(math.isfinite(t.objective) for t in trials),
          "dse.explore did not price both trials")
    by = {t.config["slow_chip_ratio"]: t.objective for t in trials}
    check(by[1.0] >= by[0.0], "a slower chip predicted faster")


def four_chip_run(cfg, shape, mesh, steps, opt_cfg):
    """The train step on `mesh` from PRNGKey(0) on one fixed batch."""
    from repro.launch.specs import input_specs, step_fn_for
    from repro.train import DataConfig, init_train_state
    from repro.train.data import make_batch
    args, shardings, model, par, donate = input_specs(cfg, shape, mesh)
    step = step_fn_for(model, shape, par, mesh, opt_cfg)
    with jax.set_mesh(mesh):
        state = jax.jit(lambda k: init_train_state(model, k, par),
                        out_shardings=shardings[0])(jax.random.PRNGKey(0))
        dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                        global_batch=shape.global_batch)
        batch = jax.device_put(make_batch(dc, 0), shardings[1])
        # the new state keeps the old one's layout, so every step runs the
        # one compiled program
        out_sh = (shardings[0], NamedSharding(mesh, P()))
        fn = jax.jit(step, in_shardings=shardings, out_shardings=out_sh,
                     donate_argnums=donate)
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, metrics = fn(state, batch)
            losses.append(float(metrics["loss"]))   # blocks on the step
            times.append(time.perf_counter() - t0)
    devs = list(mesh.devices.flat)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    held = [sum(s.data.nbytes for x in jax.tree_util.tree_leaves(state)
                for s in x.addressable_shards if s.device == d) for d in devs]
    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    del state
    return losses, times, (in_use, held, state_bytes), (step, args,
                                                         shardings, out_sh,
                                                         donate)


def phase_four_chips(system, smoke=False, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                     steps=FOUR_CHIP_STEPS):
    from repro.configs.registry import get_config
    from repro.core.capture import capture_step
    from repro.core.costmodel import simulate
    from repro.core.hlo_parse import parse_hlo
    from repro.parallel.mesh import make_mesh
    from repro.train import OptConfig
    devices = jax.devices()[:4]
    cfg = get_config(ARCH, smoke=smoke)
    shape = ShapeConfig("chip_smoke_4", "train", seq, batch)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=1, total_steps=steps)
    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    ref_losses, ref_times, _, _ = four_chip_run(cfg, shape, one, steps,
                                                opt_cfg)
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    losses, times, (in_use, held, state_bytes), prog = four_chip_run(
        cfg, shape, mesh, steps, opt_cfg)
    print(f"[4chip] {cfg.name} batch {batch} x seq {seq}, mesh "
          f"{dict(mesh.shape)}")
    print(f"[4chip] losses 1 device {ref_losses}")
    print(f"[4chip] losses 4 devices {losses}")
    # same params, batch and update; only the split of bf16 reductions
    # across devices (and the all-reduce order) differs
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    print(f"[4chip] max relative loss difference {rel:.3e} (tol 1e-2)")
    check(rel <= 1e-2, f"sharded loss differs from one device by {rel}")
    print(f"[4chip] memory_stats bytes_in_use per device {in_use}")
    print(f"[4chip] train state {state_bytes} B; its shards per device "
          f"{held}")
    check(all(0.1 * state_bytes <= b <= 0.6 * state_bytes for b in held),
          "train state is not spread over the four devices")
    step, args, shardings, out_sh, donate = prog
    cap = capture_step(step, args, shardings, mesh, donate_argnums=donate,
                       out_shardings=out_sh, meta={"arch": cfg.name})
    capture_summary(cap, cfg, shape, len(devices), "4chip")
    mod = parse_hlo(cap.compiled_text)
    in_hlo = sorted({i.collective_kind for c in mod.computations.values()
                     for i in c.instructions if i.is_collective})
    in_graph = {}
    for node in cap.graph.by_type("COMM_COLL"):
        k = node.attrs["comm_kind"]
        in_graph[k] = in_graph.get(k, 0) + 1
    print(f"[4chip] collectives in HLO {in_hlo}; graph nodes by kind "
          f"{in_graph}")
    check({"all-gather", "reduce-scatter", "all-reduce"} & set(in_hlo),
          "no all-gather / reduce-scatter / all-reduce in the sharded HLO")
    check(set(in_hlo) <= set(in_graph), "graph lacks collectives the HLO has")
    res = simulate(cap.graph, system.replace(chips=len(devices)))
    med = statistics.median(times[1:] or times)
    print(f"[4chip] step times 1 device {[round(t, 4) for t in ref_times]}, "
          f"4 devices {[round(t, 4) for t in times]}")
    print(f"[simulate] chips={len(devices)} predicted step "
          f"{res.total_time:.4f}s, measured median {med:.4f}s, "
          f"predicted/measured {res.total_time / med:.3f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip path")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[cache] {cache}: {n_cached} entries at start")
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            system, device = phase_device(min_count=4)
            phase_four_chips(system)
        else:
            system, device = phase_device()
            phase_kernels()
            measured = phase_train(train_argv)
            phase_serve(["--arch", ARCH, "--batch", str(SERVE_BATCH),
                         "--prompt-len", str(SERVE_PROMPT), "--steps",
                         str(SERVE_STEPS)])
            phase_capture(train_argv, measured, system)
    except Exception as e:  # noqa: BLE001 - any failure fails the smoke
        import traceback
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"[cache] {n_cached} entries at end; wall "
          f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
