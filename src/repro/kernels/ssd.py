"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

Chunked algorithm (arXiv:2405.21060): per chunk the output is
  y = (tril(C B^T * decay) * dt) x   [intra, quadratic in chunk -> MXU]
    + (C . S_prev) * exp(cum)        [inter, recurrent state]
and the running state S (n x p per head) advances chunk to chunk.

TPU adaptation: grid (batch*heads, chunks) with the chunk dim innermost;
S lives in VMEM scratch across chunk steps (sequential TPU grid), all three
contractions are MXU matmuls on (chunk x n/p) tiles.  One (batch, head) pair
per outer grid step keeps every operand in VMEM for typical sizes
(chunk<=256, n=128, p=64).  dt arrives as a (1, chunk) lane row and A whole
in SMEM; the decay cumsum is a masked reduction over the (chunk, chunk)
tile, taken once along lanes and once along sublanes for the two
orientations the kernel needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, s_scr, *,
            heads):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0]                                  # (Q, p) f32
    dt_row = dt_ref[0]                            # (1, Q)
    A = a_ref[pl.program_id(0) % heads]           # scalar (SMEM)
    Bm = b_ref[0]                                 # (Q, n)
    Cm = c_ref[0]                                 # (Q, n)

    q = dt_row.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = row >= col
    # Column and row views of dt and of the inclusive cumsum of a = dt*A,
    # as masked lane/sublane reductions over (Q, Q): Mosaic has no cumsum
    # and no (Q, 1) <-> (1, Q) transpose.
    dt_col = jnp.sum(jnp.where(row == col, dt_row, 0.0), axis=1,
                     keepdims=True)                       # (Q,1)
    a_row = dt_row * A                                    # (1,Q) log decay
    a_col = dt_col * A                                    # (Q,1)
    cum_col = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1,
                      keepdims=True)                      # (Q,1)
    cum_row = jnp.sum(jnp.where(row <= col, a_col, 0.0), axis=0,
                      keepdims=True)                      # (1,Q)
    total = jnp.sum(a_row, axis=1, keepdims=True)         # (1,1)
    seg = cum_col - cum_row                               # cum_i - cum_j
    L = jnp.where(causal, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    scores = cb * L * dt_row                      # * dt_j
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q,p)

    s_prev = s_scr[...]                           # (n,p)
    y += jax.lax.dot_general(Cm, s_prev, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) * \
        jnp.exp(cum_col)

    decay_end = jnp.exp(total - cum_col)          # (Q,1)
    wB = Bm * (dt_col * decay_end)                # (Q,n)
    s_new = jax.lax.dot_general(wB, x, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (n,p)
    s_scr[...] = jnp.exp(total) * s_prev + s_new
    y_ref[0] = y

    @pl.when(ci == pl.num_programs(1) - 1)
    def _fin():
        sfin_ref[0] = s_scr[...]


def ssd_tpu(x, dt, A, B, C, *, chunk=256, interpret=False):
    """x (b,s,h,p) f32; dt (b,s,h); A (h,); B,C (b,s,n).

    Returns (y (b,s,h,p), S_final (b,h,n,p)) — matches models.ssm.ssd_chunked.
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    S = s + pad
    nc = S // chunk

    # flatten (b,h): x -> (b*h, S, p); dt -> (b*h, 1, S) as lane rows;
    # A (h,) sits whole in SMEM; B/C shared per b
    xf = jnp.moveaxis(x, 2, 1).reshape(b * h, S, p)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(b * h, 1, S)

    kernel = functools.partial(_kernel, heads=h)
    y, sfin = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, h=h: (bh // h, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, n, p), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, S, p), jnp.float32),
            jax.ShapeDtypeStruct((b * h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xf.astype(jnp.float32), dtf.astype(jnp.float32), A.astype(jnp.float32),
      B.astype(jnp.float32), C.astype(jnp.float32))

    y = jnp.moveaxis(y.reshape(b, h, S, p), 1, 2)[:, :s]
    sfin = sfin.reshape(b, h, n, p)
    return y, sfin
