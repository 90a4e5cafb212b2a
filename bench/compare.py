"""The comparison that decides ``correct`` for a training cell.

Four numbers, each against a limit from ``limits/<cell>.json``:

- ``loss_gap``: the largest relative gap, over the checked steps, between
  the program's loss and the reference's;
- ``grad_gap``: the first clipped gradient as the optimizer got it (the
  program's first Adam moment after one step, over 1 - b1), by the worst
  leaf: |norm(program) - norm(reference)| over the larger of the
  reference's norm of that leaf and of the median leaf;
- ``grad_cos``: the same gradient by the worst leaf, 1 - its cosine with
  the reference's. Rounding noise of each element averages out of norms
  and losses but not out of this angle, so this is the number that tells
  float8 arithmetic from the program's bfloat16;
- ``update_gap``: the norm gap of each leaf's change over the checked
  steps, by the worst leaf, measured as ``grad_gap`` is.

``grad_cos`` and ``update_gap`` leave out leaves whose reference gradient
is under a thousandth of the median leaf's: they move by round-off alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

QUIET_GRAD = 1e-3


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


def leaf_norms(tree) -> np.ndarray:
    """Norm of every leaf, in flattening order, computed in float32."""
    return np.asarray(_norms(jax.tree_util.tree_leaves(tree)), np.float64)


def _change_norm(i, abstract):
    def f(x, words):
        x0 = weights.leaf(abstract, words, i).astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - x0)))
    return jax.jit(f)


def change_norms(params, abstract, words) -> np.ndarray:
    """Per leaf, the norm of its change from the seed's weights, which are
    drawn again one leaf at a time so that no second copy is held."""
    return np.array([float(_change_norm(i, abstract)(x, words))
                     for i, x in enumerate(jax.tree_util.tree_leaves(params))])


@jax.jit
def _cos_terms(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sum(a * b), jnp.sum(a * a), jnp.sum(b * b)


def cos_gaps(leaves, others, shardings) -> np.ndarray:
    """Per leaf, 1 - cos(leaf, other).  Host arrays are moved to the
    device one pair at a time, each to its leaf's sharding."""
    out = []
    for x, y, sh in zip(leaves, others, shardings):
        ab, aa, bb = (float(v) for v in _cos_terms(jax.device_put(x, sh),
                                                   jax.device_put(y, sh)))
        out.append(1.0 - ab / max(np.sqrt(aa * bb), 1e-300))
    return np.array(out)


def gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def numbers(prog, ref) -> dict:
    """``prog``: (losses, first-gradient norms, change norms, ...);
    ``ref``: the same and, fourth, per leaf 1 - cos of the first gradient
    with the program's."""
    pl, pg, pd = prog[:3]
    rl, rg, rd, cos = ref[:4]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
    keep = rg >= QUIET_GRAD * np.median(rg)
    return {"loss_gap": float(loss_gap),
            "grad_gap": float(np.max(gaps(pg, rg))),
            "grad_cos": float(np.max(np.asarray(cos)[keep])),
            "update_gap": float(np.max(gaps(pd, rd)[keep]))}


def verdict(nums: dict, limits: dict):
    """(correct, [(name, value, limit)]): correct when every number is
    finite and within its limit."""
    rows = [(k, nums[k], limits[k]) for k in sorted(limits)]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return bool(ok), rows
