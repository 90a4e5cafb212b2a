"""Plain numerics shared by the references in ``references/``.

Nothing here imports the program. Every matrix product goes through
``Numerics.mm``: float32 at ``Precision.HIGHEST`` for the reference, or,
for the lower-precision control, float8 (e4m3) operands with one scale per
tensor, the step below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0                      # largest finite float8_e4m3fn


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> F8_MAX).
    The rounding passes the gradient straight through, so the backward
    products take the rounded operands and a float32 cotangent."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


class Numerics(NamedTuple):
    quant: Optional[str] = None      # None | "fp8"

    def mm(self, eq, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.quant == "fp8":
            a, b = fp8(a), fp8(b)
        return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


REFERENCE = Numerics()
CONTROL = Numerics("fp8")


def rms_norm(x, scale, eps):
    """RMSNorm with the weight stored as an offset from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def silu(x):
    return x * jax.nn.sigmoid(x)


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def layer_stack(layer, h, stacked, group: int):
    """h through every layer of ``stacked`` (leading axis = layers), keeping
    only the input of each group of (at most) ``group`` layers for the
    backward pass, and of each layer while one group is recomputed."""
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    group = max(g for g in range(1, min(group, n) + 1) if n % g == 0)
    grouped = jax.tree_util.tree_map(
        lambda a: a.reshape((n // group, group) + a.shape[1:]), stacked)
    one = jax.checkpoint(lambda x, p: (layer(x, p), None))

    @jax.checkpoint
    def run_group(x, ps):
        return jax.lax.scan(one, x, ps)[0], None

    return jax.lax.scan(run_group, h, grouped)[0]


def token_loss(h, table, labels, num: Numerics, z_coef: float,
               rows: int = 1024):
    """Mean next-token cross-entropy plus z_coef * mean(logsumexp^2), with
    logits = h @ table.T, taken over blocks of ``rows`` rows
    so the (rows, vocab) logits never exist all at once."""
    d = h.shape[-1]
    h = h.reshape(-1, d)
    labels = labels.reshape(-1)
    n = h.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows do not split into blocks of {rows}")

    @jax.checkpoint
    def block(args):
        hb, lb = args
        logits = num.mm("rd,vd->rv", hb, table)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked), jnp.sum(lse * lse)

    nll, z = jax.lax.map(block, (h.reshape(n // rows, rows, d),
                                 labels.reshape(n // rows, rows)))
    return (jnp.sum(nll) + z_coef * jnp.sum(z)) / n


def lr_at(opt: dict, step: int) -> float:
    """The stated schedule: linear warm-up, then cosine to min_lr_ratio."""
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return lr * step / max(warm, 1)
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return lr * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


def step_scalars(opt: dict, step: int):
    """(lr, 1 - b1^step, 1 - b2^step) of step ``step`` (from 1), as arrays,
    so that one compiled update serves every step."""
    return tuple(jnp.float32(x) for x in (lr_at(opt, step),
                                           1 - opt["b1"] ** step,
                                           1 - opt["b2"] ** step))


def adamw(opt: dict, scalars, params, grads, mu, nu):
    """One AdamW step in float32: the gradient clipped to global norm
    ``grad_clip``; decoupled weight decay on every leaf of rank >= 2 of the
    layer-stacked tree, as the configuration states. ``scalars`` from
    ``step_scalars``. Returns (params, mu, nu, clipped grads)."""
    lr, c1, c2 = scalars
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(
        grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    b1, b2 = opt["b1"], opt["b2"]

    def one(p, g, m, v):
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            upd = upd + opt["weight_decay"] * p
        return p - lr * upd, m, v, g

    flat, treedef = jax.tree_util.tree_flatten(params)
    out = [one(*a) for a in zip(flat, treedef.flatten_up_to(grads),
                                treedef.flatten_up_to(mu),
                                treedef.flatten_up_to(nu))]
    return tuple(jax.tree_util.tree_unflatten(treedef, [o[i] for o in out])
                 for i in range(4))
