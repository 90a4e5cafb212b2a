"""The plain reference's training steps at the cell's size, on its chips.

The reference (``references/<name>.py``) gets the benchmark's weights from
the seed in float32, the same batches, and the configuration's AdamW
(``reflib.adamw``). On several chips each leaf is split over all of them
along its largest divisible axis and the batch along its rows; the compiler
places the rest. Nothing here imports the program.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import reflib, registry, weights

AXIS = "ref"


class RefStep(NamedTuple):
    conf: dict
    abstract: Any            # the program's parameter tree, shapes and dtypes
    tokens: Any              # ShapeDtypeStruct of one batch's tokens
    param_sh: Any
    batch_sh: Any
    grads: Any               # jitted (params, tokens, labels) -> (loss, grads)
    update: Any              # jitted AdamW, params/grads/mu/nu donated
    init: Any                # jitted words -> float32 params
    zeros: Any               # jitted () -> zero Adam moments


def _spec(shape, n, stacked):
    first = 1 if stacked else 0
    dims = sorted(range(first, len(shape)), key=lambda i: -shape[i])
    for i in dims:
        if shape[i] % n == 0 and n > 1:
            spec = [None] * len(shape)
            spec[i] = AXIS
            return P(*spec)
    return P()


def build(conf: dict, prog, num: reflib.Numerics = reflib.REFERENCE
          ) -> RefStep:
    """``prog`` gives only shapes and devices; ``num`` is the reference's
    arithmetic or the float8 control's."""
    mod = registry.reference(conf["reference"])
    devices = list(prog.mesh.devices.flat)
    mesh = Mesh(np.array(devices), (AXIS,))
    n = len(devices)
    abstract = prog.abstract_args[0].params
    named = weights.leaves_with_names(abstract)
    treedef = jax.tree_util.tree_structure(abstract)
    param_sh = jax.tree_util.tree_unflatten(treedef, [
        NamedSharding(mesh, _spec(a.shape, n, "sb" in names))
        for names, a in named])
    b = prog.abstract_args[1]["tokens"].shape[0]
    batch_sh = NamedSharding(mesh, P(AXIS) if b % n == 0 else P())
    scalar = NamedSharding(mesh, P())

    grads = jax.jit(jax.value_and_grad(
        lambda p, t, l: mod.loss(p, t, l, conf, num)),
                    in_shardings=(param_sh, batch_sh, batch_sh),
                    out_shardings=(scalar, param_sh))
    opt = conf["optimizer"]
    update = jax.jit(lambda s, p, g, m, v: reflib.adamw(opt, s, p, g, m, v),
                     in_shardings=(scalar, param_sh, param_sh, param_sh,
                                   param_sh),
                     out_shardings=(param_sh,) * 4,
                     donate_argnums=(1, 2, 3, 4))
    init = jax.jit(lambda w: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), weights.params(abstract, w)),
        out_shardings=param_sh)
    zeros = jax.jit(lambda: 2 * (jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), abstract),),
        out_shardings=(param_sh,) * 2)
    return RefStep(conf, abstract, prog.abstract_args[1]["tokens"],
                   param_sh, batch_sh, grads, update, init, zeros)


def lower(ref: RefStep):
    """The reference's gradient step, for the rehearsal's compile."""
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), ref.abstract)
    return ref.grads.lower(f32, ref.tokens, ref.tokens)


def lower_update(ref: RefStep):
    """The reference's AdamW update, for the rehearsal's compile."""
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), ref.abstract)
    s = jax.ShapeDtypeStruct((), jnp.float32)
    return ref.update.lower((s, s, s), f32, f32, f32, f32)


def readings(ref: RefStep, words, batches, against, steps: int = 3,
             keep_first=False):
    """The reference's side of the comparison over ``steps`` steps from the
    seed's weights on ``batches[:steps]``: (losses, per-leaf norms of the
    clipped first gradient, per-leaf norms of the change after ``steps``,
    per-leaf 1 - cos of that gradient with ``against`` (host arrays), and,
    with ``keep_first``, the gradient itself on the host, else None)."""
    from bench import compare
    params = ref.init(words)
    losses, g1, mu, nu = [], None, None, None
    for t in range(1, steps + 1):
        b = batches[t - 1]
        loss, g = ref.grads(params, jax.device_put(b["tokens"], ref.batch_sh),
                            jax.device_put(b["labels"], ref.batch_sh))
        # the second Adam moment waits on the host while the gradient is
        # taken: params, gradient and both moments in float32 with the
        # backward pass's temporaries would not fit one chip
        if mu is None:
            mu, nu = ref.zeros()
        else:
            nu = jax.device_put(nu, ref.param_sh)
        params, mu, nu, g = ref.update(
            reflib.step_scalars(ref.conf["optimizer"], t), params, g, mu, nu)
        if t == 1:
            leaves = jax.tree_util.tree_leaves(g)
            g1 = compare.leaf_norms(g)
            cos = compare.cos_gaps(leaves, against,
                                   [x.sharding for x in leaves])
            first = jax.device_get(leaves) if keep_first else None
            del leaves
        del g
        losses.append(float(loss))
        nu = jax.device_get(nu) if t < steps else None
    del mu, nu
    delta = compare.change_norms(params, ref.abstract, words)
    return losses, g1, delta, cos, first
