"""The weights' rules (``weights.py``): routed experts drawn at their own
fan-in, on the registry's MoE trees scanned and unscanned, and every other
leaf of the benchmark's configurations drawn bit for bit as before the
experts had a rule of their own."""
from __future__ import annotations

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program, weights
from bench.conftest import TRAFFIC, tiny as mamba_tiny
from bench.test_bench_granite import config_file as granite_file
from bench.test_bench_granite import tiny as granite_tiny

SEED = 3_000_000_019
# std of the standard normal truncated to [-2, 2]
TRUNC_STD = math.sqrt(1 - 4 * math.exp(-2) / math.sqrt(2 * math.pi)
                      / math.erf(2 / math.sqrt(2)))
EXPERT = ("wi", "wg", "wo")


def _old_draw(names, shape, key, siblings):
    """``weights._draw`` as it was before routed experts had a rule."""
    core = shape[1:] if "sb" in names else shape
    last = names[-1]
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if last == "scale":
        return jnp.zeros(shape, jnp.float32)
    if last == "A_log":
        return jnp.log(u(1.0, 16.0))
    if last == "dt_bias":
        dt = jnp.maximum(jnp.exp(u(math.log(1e-3), math.log(1e-1))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if last == "D":
        return jnp.ones(shape, jnp.float32)
    if last in ("conv_w", "conv_b"):
        width = siblings["conv_w"][1 if "sb" in names else 0]
        b = 1.0 / math.sqrt(width)
        return u(-b, b)
    if len(core) >= 2:
        if last == "table":
            fan_in = core[1]
        elif last == "wo" and len(core) == 3:
            fan_in = core[0] * core[1]
        else:
            fan_in = core[0]
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                / math.sqrt(fan_in))
    raise ValueError(f"no init rule for parameter {'/'.join(names)} {shape}")


def _old_params(abstract, words):
    named = weights.leaves_with_names(abstract)
    vals = []
    for i, (names, a) in enumerate(named):
        key = jax.random.fold_in(weights.base_key(words, weights.WEIGHTS), i)
        vals.append(_old_draw(names, a.shape, key, weights._siblings(named, i))
                    .astype(a.dtype))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract), vals)


def _draws(fn, abstract):
    words = weights.seed_words(SEED)
    return jax.tree_util.tree_leaves(jax.jit(lambda w: fn(abstract, w))(words))


def _moe_tree(name, scanned):
    from repro.configs.registry import get_config
    from repro.models.model import build_model
    cfg = get_config(name, smoke=True)
    if not scanned:
        cfg = cfg.replace(sb_repeat=0,
                          remainder=tuple(cfg.superblock) * cfg.sb_repeat)
    return cfg, build_model(cfg).abstract_params()


def _bench_tree(name):
    """The abstract parameters of a benchmark configuration at the smoke
    widths its tests run."""
    from bench import registry
    if name == "granite-3-8b-l10":
        conf = granite_tiny(granite_file())
    else:
        conf = mamba_tiny(registry.config(name, registry.benchmark()))
    return program.build(conf, TRAFFIC, jax.devices()).abstract_args[0].params


@pytest.mark.parametrize("scanned", [True, False], ids=["scanned",
                                                         "unscanned"])
@pytest.mark.parametrize("name", ["mixtral-8x7b", "dbrx-132b"])
def test_experts_are_drawn_at_their_own_fan_in(name, scanned):
    """wi and wg (E, d_model, d_ff) at fan-in d_model, wo (E, d_ff,
    d_model) at d_ff: each leaf's std times sqrt(fan-in) is the truncated
    normal's."""
    cfg, abstract = _moe_tree(name, scanned)
    words = weights.seed_words(SEED)
    named = weights.leaves_with_names(abstract)
    seen = set()
    for i, (names, _) in enumerate(named):
        if "moe" not in names or names[-1] not in EXPERT:
            continue
        x = np.asarray(weights.leaf(abstract, words, i), np.float32)
        fan_in = cfg.d_ff if names[-1] == "wo" else cfg.d_model
        assert x.std() * math.sqrt(fan_in) == pytest.approx(
            TRUNC_STD, rel=0.02), names
        seen.add(names[-1])
    assert seen == set(EXPERT)


@pytest.mark.parametrize("scanned", [True, False], ids=["scanned",
                                                         "unscanned"])
def test_held_experts_fewer_than_the_router_routes(scanned):
    """A chip's share of the experts, 9 held of the router's 72 outputs:
    wi and wg (9, d_model, d_ff) at fan-in d_model, wo (9, d_ff, d_model)
    at d_ff, the router (d_model, 72) at d_model."""
    sds = jax.ShapeDtypeStruct
    d, f, lead = 256, 64, (2,) if scanned else ()
    moe = {"router": sds(lead + (d, 72), np.float32),
           "wi": sds(lead + (9, d, f), jnp.bfloat16),
           "wg": sds(lead + (9, d, f), jnp.bfloat16),
           "wo": sds(lead + (9, f, d), jnp.bfloat16)}
    tree = ({"blocks": {"sb": {"slot0": {"moe": moe}}}} if scanned
            else {"blocks": {"rem0": {"moe": moe}}})
    words = weights.seed_words(SEED)
    fans = {"router": d, "wi": d, "wg": d, "wo": f}
    for i, (names, _) in enumerate(weights.leaves_with_names(tree)):
        x = np.asarray(weights.leaf(tree, words, i), np.float32)
        assert x.std() * math.sqrt(fans[names[-1]]) == pytest.approx(
            TRUNC_STD, rel=0.02), names


@pytest.mark.parametrize("name", ["mixtral-8x7b", "dbrx-132b"])
def test_moe_trees_draw_other_leaves_as_before(name):
    """The router, attention, norms and embeddings of an MoE tree keep
    their rules and keys; only the experts move."""
    _, abstract = _moe_tree(name, scanned=True)
    new, old = _draws(weights.params, abstract), _draws(_old_params, abstract)
    named = weights.leaves_with_names(abstract)
    for (names, _), a, b in zip(named, new, old):
        expert = names[-2] == "moe" and names[-1] in EXPERT
        assert np.array_equal(np.asarray(a), np.asarray(b)) != expert, names


# sha256 of every leaf's bytes, in the tree's order, seed SEED, at the
# conftest/test_bench_granite smoke widths
DIGESTS = {
    "ssd-lm-780m":
        "c17d9690cf52a5894e7d5bb26870c9fc926b53b1fe852b28f000ce3bc31b727f",
    "granite-3-8b-l10":
        "7a1ae8ec06b45cdf8edc7a47c6dae08f7b06051358e1ac1f573d2975991ce156",
}


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_bench_configurations_draw_as_before(name):
    """Every leaf of the benchmark's configurations is the old rule's, bit
    for bit, and the draws are pinned."""
    abstract = _bench_tree(name)
    new, old = _draws(weights.params, abstract), _draws(_old_params, abstract)
    for a, b in zip(new, old, strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    h = hashlib.sha256()
    for a in new:
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == DIGESTS[name]
