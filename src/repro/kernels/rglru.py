"""RG-LRU linear-recurrence Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t  (elementwise over the channel dim).

TPU adaptation: the GPU version of this scan is a warp-parallel chunked scan;
on TPU the natural form is a *sequential* grid walk over time blocks with the
carry state resident in VMEM scratch (the VPU processes the full channel
block per step, so sequential-in-time costs S/bt grid steps of vectorized
work).  Grid (batch, channel_blocks, time_blocks), time innermost; inside a
block a fori_loop loads one (8, bc) sublane tile of a and b from the refs,
advances eight steps on it and stores the eight rows at once (Mosaic takes
only unroll=1 or full, and no dynamic index into a loaded value).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROWS = 8   # one f32 sublane tile: time steps loaded and stored at once


def _kernel(a_ref, b_ref, o_ref, h_scr, *, block_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(i, h):
        t = pl.multiple_of(i * _ROWS, _ROWS)
        a = a_ref[0, pl.ds(t, _ROWS), :]          # (8, bc) f32
        b = b_ref[0, pl.ds(t, _ROWS), :]
        rows = []
        for k in range(_ROWS):
            h = a[k:k + 1] * h + b[k:k + 1]
            rows.append(h)
        o_ref[0, pl.ds(t, _ROWS), :] = jnp.concatenate(rows, axis=0)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_t // _ROWS, step, h_scr[...])


def rglru_scan_tpu(a, b, *, block_t=256, block_c=512, interpret=False):
    """a, b (B, S, C) f32 -> h (B, S, C)."""
    B, S, C = a.shape
    block_t = min(block_t, -(-S // _ROWS) * _ROWS)
    assert block_t % _ROWS == 0, f"block_t={block_t} not a multiple of {_ROWS}"
    block_c = min(block_c, C)
    pt, pc = (-S) % block_t, (-C) % block_c
    if pt or pc:
        a = jnp.pad(a, ((0, 0), (0, pt), (0, pc)))
        b = jnp.pad(b, ((0, 0), (0, pt), (0, pc)))
    nt, nc = (S + pt) // block_t, (C + pc) // block_c

    kernel = functools.partial(_kernel, block_t=block_t)
    out = pl.pallas_call(
        kernel,
        grid=(B, nc, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_c), lambda bi, ci, ti: (bi, ti, ci)),
            pl.BlockSpec((1, block_t, block_c), lambda bi, ci, ti: (bi, ti, ci)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_c),
                               lambda bi, ci, ti: (bi, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((B, S + pt, C + pc), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
    return out[:, :S, :C]
