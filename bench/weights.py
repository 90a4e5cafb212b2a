"""The benchmark's weights, made on the device from ``--seed``.

Each leaf of the program's parameter tree is drawn from its own key,
``fold_in(key(seed), leaf index)``, by a rule chosen from the leaf's name, so
any one leaf can be drawn again alone and comes out the same. The program
and the plain reference both take their weights from here, never from each
other.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, DATA = 0, 1


def seed_words(seed: int) -> np.ndarray:
    """Any seed of up to 64 bits as two uint32 words (a traced argument, so
    one compiled program serves every seed)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def base_key(words, stream: int):
    k = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    return jax.random.fold_in(k, stream)


def leaves_with_names(tree):
    """[(names, leaf)] in the tree's flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(tuple(getattr(k, "key", getattr(k, "name", k)) for k in path),
             leaf) for path, leaf in flat]


def _draw(names, shape, key, siblings):
    """One leaf in float32.  Layers stacked for the scan ("sb") keep their
    leading axis; fan-in is read from the per-layer shape."""
    core = shape[1:] if "sb" in names else shape
    last = names[-1]
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if last == "scale":                       # RMSNorm weight 1 = (1 + scale)
        return jnp.zeros(shape, jnp.float32)
    if last == "A_log":                       # A = -exp(A_log) in [-16, -1]
        return jnp.log(u(1.0, 16.0))
    if last == "dt_bias":                     # softplus(dt_bias) = dt
        dt = jnp.maximum(jnp.exp(u(math.log(1e-3), math.log(1e-1))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if last == "D":
        return jnp.ones(shape, jnp.float32)
    if last in ("conv_w", "conv_b"):          # depthwise conv, fan-in = width
        width = siblings["conv_w"][1 if "sb" in names else 0]
        b = 1.0 / math.sqrt(width)
        return u(-b, b)
    if len(core) >= 2:
        if last == "table":                   # embedding, tied head
            fan_in = core[1]
        elif _routed_expert(names, core):     # (E, fan-in, out)
            fan_in = core[1]
        elif last == "wo" and len(core) == 3:  # attention out (heads, hd, d)
            fan_in = core[0] * core[1]
        else:
            fan_in = core[0]
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                / math.sqrt(fan_in))
    raise ValueError(f"no init rule for parameter {'/'.join(names)} {shape}")


def _routed_expert(names, core) -> bool:
    """A leaf under a ``moe`` node whose per-layer core is (experts, in, out):
    each expert is a matrix of its own, with fan-in ``in``.  The experts
    held may be fewer than the router's outputs (a chip's share of them)."""
    return len(names) >= 2 and names[-2] == "moe" and len(core) == 3


def _siblings(named, i):
    parent = named[i][0][:-1]
    return {n[-1]: leaf.shape for n, leaf in named if n[:-1] == parent}


def leaf(abstract_params, words, i: int):
    """Leaf ``i`` of the parameter tree, in its own dtype."""
    named = leaves_with_names(abstract_params)
    names, a = named[i]
    key = jax.random.fold_in(base_key(words, WEIGHTS), i)
    return _draw(names, a.shape, key, _siblings(named, i)).astype(a.dtype)


def params(abstract_params, words):
    """Every leaf; traced inside one jitted call."""
    named = leaves_with_names(abstract_params)
    vals = [leaf(abstract_params, words, i) for i in range(len(named))]
    treedef = jax.tree_util.tree_structure(abstract_params)
    return jax.tree_util.tree_unflatten(treedef, vals)
