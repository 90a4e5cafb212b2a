"""Serving driver: batched prefill + decode with a KV/state cache.

  python -m repro.launch.serve --arch gemma3-4b --smoke --batch 4 --steps 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    """Returns (sampled ids (B, steps), the last decode step's logits)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    from repro.configs.base import ParallelConfig
    from repro.configs.registry import get_config
    from repro.models import build_model
    from repro.train.serve_step import (make_decode_step, make_prefill_step,
                                        sample_token)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    par = ParallelConfig()
    cache_len = args.prompt_len + args.steps

    rng = jax.random.PRNGKey(0)
    params = model.init(rng)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    memory = None
    ml = model.memory_len()
    if ml:
        memory = jax.random.normal(jax.random.PRNGKey(2),
                                   (args.batch, ml, cfg.d_model),
                                   jnp.bfloat16)

    prefill = jax.jit(make_prefill_step(model, par, cache_len=cache_len))
    decode = jax.jit(make_decode_step(model, par), donate_argnums=(2,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompt, memory)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          f"{t_prefill * 1e3:.1f}ms "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")

    tok = sample_token(logits, rng, args.temperature)
    out = [tok]
    t0 = time.perf_counter()
    for i in range(args.steps - 1):
        rng, k = jax.random.split(rng)
        logits, cache = decode(params, tok, cache)
        tok = sample_token(logits, k, args.temperature)
        out.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0
    toks = jnp.concatenate(out, axis=1)
    print(f"[serve] decode {args.steps - 1} steps: {t_dec * 1e3:.1f}ms "
          f"({args.batch * (args.steps - 1) / max(t_dec, 1e-9):.0f} tok/s)")
    print(f"[serve] sample output ids: {toks[0, :16].tolist()}")
    return toks, logits


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
