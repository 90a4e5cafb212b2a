"""Training driver: checkpointed, preemptible, fault-tolerant.

  python -m repro.launch.train --arch qwen3-8b --smoke --steps 200

Composes the fault-tolerance substrate (DESIGN.md SS7): atomic checkpoints
with keep-last-k, resume-from-latest with exact data replay, SIGTERM
preemption save, per-step straggler detection, and transient-failure retry.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import NamedTuple

import jax


class TrainSetup(NamedTuple):
    cfg: object
    model: object
    parallel: object
    data: object
    step: object            # un-jitted (TrainState, batch) -> (state, metrics)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--inject-fault-at", type=int, default=-1)
    return ap.parse_args(argv)


def build(args) -> TrainSetup:
    """The model, configs and train step that main() runs for `args`."""
    from repro.configs.base import ParallelConfig
    from repro.configs.registry import get_config
    from repro.models import build_model
    from repro.train import DataConfig, OptConfig, make_train_step

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    par = ParallelConfig(remat="none" if args.smoke else "full",
                         microbatches=args.microbatches,
                         grad_compression=args.grad_compression)
    opt = OptConfig(lr=args.lr, warmup_steps=max(10, args.steps // 20),
                    total_steps=args.steps)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                    global_batch=args.batch, memory_len=model.memory_len(),
                    d_model=cfg.d_model)
    return TrainSetup(cfg, model, par, dc, make_train_step(model, opt, par))


def main(argv=None):
    args = parse_args(argv)

    from repro.train import (DataIterator, init_train_state, latest_step,
                             restore_checkpoint, save_checkpoint)
    from repro.train.fault import (FaultInjector, PreemptionHandler,
                                   StepTimer, StragglerMonitor, run_with_retry)

    setup = build(args)
    # the old state is dead once the step returns: donating it lets the new
    # state reuse its buffers (params + f32 Adam moments, ~12 B/param)
    step_fn = jax.jit(setup.step, donate_argnums=(0,))
    state = init_train_state(setup.model, jax.random.PRNGKey(0),
                             setup.parallel)
    start_step = 0

    ckpt_dir = args.ckpt_dir or os.path.join("checkpoints", setup.cfg.name)
    if args.resume:
        last = latest_step(ckpt_dir)
        if last is not None:
            template = jax.tree_util.tree_map(lambda x: x, state)
            state, meta = restore_checkpoint(ckpt_dir, last, template)
            start_step = meta["step"]
            print(f"[train] resumed from step {start_step}")

    it = DataIterator(setup.data, start_step=start_step)
    preempt = PreemptionHandler().install()
    monitor = StragglerMonitor()
    injector = FaultInjector(
        fail_steps=(args.inject_fault_at,) if args.inject_fault_at >= 0 else ())

    metrics_log = []
    for step in range(start_step, args.steps):
        batch = next(it)

        def run(state=state, batch=batch, step=step):
            injector.check(step)
            return step_fn(state, batch)

        with StepTimer() as t:
            state, metrics = run_with_retry(
                run, retries=2,
                on_failure=lambda e, a: print(f"[train] step {step} failed "
                                              f"({e}); retry {a + 1}"))
            jax.block_until_ready(metrics["loss"])
        if monitor.record(step, t.duration):
            print(f"[train] straggler step {step}: {t.duration:.3f}s "
                  f"(median {monitor.median:.3f}s)")

        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['gnorm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} {t.duration * 1e3:.0f}ms")
            metrics_log.append({"step": step, "loss": loss,
                                "t_ms": t.duration * 1e3})

        if (step + 1) % args.ckpt_every == 0 or preempt.should_stop:
            save_checkpoint(ckpt_dir, step + 1, state, keep=args.keep)
            if preempt.should_stop:
                print(f"[train] preempted; checkpointed at {step + 1}")
                break

    os.makedirs(ckpt_dir, exist_ok=True)
    with open(os.path.join(ckpt_dir, "metrics.json"), "w") as f:
        json.dump(metrics_log, f, indent=1)
    print(f"[train] done; final loss "
          f"{metrics_log[-1]['loss'] if metrics_log else float('nan'):.4f}")
    return metrics_log


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
