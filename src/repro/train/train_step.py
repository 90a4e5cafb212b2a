"""Train / eval step builders (pjit-ready pure functions).

make_train_step(model, ...) returns a function
    (TrainState, batch) -> (TrainState, metrics)
with optional microbatched gradient accumulation (overlaps the DP gradient
collective of microbatch i with the backward compute of microbatch i+1 under
XLA's latency-hiding scheduler) and optional int8-compressed DP reduction.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ParallelConfig
from repro.models.model import Ctx, Model
from repro.obs import scopes
from repro.parallel.collectives import make_compressed_value_and_grad
from repro.parallel.mesh import POD_AXIS, DATA_AXIS
from repro.parallel.sharding import make_shard_fn
from repro.train.optimizer import (OptConfig, OptState, adamw_update,
                                   init_opt_state)


class TrainState(NamedTuple):
    params: object
    opt: OptState
    err: object            # error-feedback state for compressed DP ({} if off)


def make_ctx(parallel: ParallelConfig, mesh) -> Ctx:
    from repro.parallel.mesh import dp_size, model_size
    groups = 1
    if mesh is not None:
        groups = dp_size(mesh)
        if parallel.model_axis == "zero3":
            groups *= model_size(mesh)     # the model axis is DP in zero3
    return Ctx(attn_impl=parallel.attn_impl, remat=parallel.remat,
               shard_fn=make_shard_fn(mesh, parallel),
               moe_groups=groups)


def init_train_state(model: Model, rng, parallel: ParallelConfig,
                     mesh=None) -> TrainState:
    params = model.init(rng)
    return TrainState(params=params, opt=init_opt_state(params), err={})


def _microbatch(batch, m):
    def split(x):
        b = x.shape[0]
        assert b % m == 0, f"batch {b} not divisible by microbatches {m}"
        return x.reshape(m, b // m, *x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def make_train_step(model: Model, opt_cfg: OptConfig,
                    parallel: ParallelConfig, mesh=None):
    ctx = make_ctx(parallel, mesh)

    def loss_fn(params, batch):
        return model.loss(params, batch, ctx)

    use_comp = parallel.grad_compression and mesh is not None
    if use_comp:
        dp_axes = tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.shape)
        comp_vag = make_compressed_value_and_grad(loss_fn, mesh, dp_axes)

    def train_step(state: TrainState, batch):
        params = state.params
        if use_comp:
            loss, metrics, grads, new_err = comp_vag(params, batch, state.err)
        elif parallel.microbatches > 1:
            m = parallel.microbatches
            mbs = _microbatch(batch, m)

            def body(acc, mb):
                (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), acc_g, g)
                return (acc_g, acc_l + l), met

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), mets = jax.lax.scan(body, (zero_g, 0.0), mbs)
            grads = jax.tree_util.tree_map(lambda g: g / m, gsum)
            loss = lsum / m
            metrics = jax.tree_util.tree_map(lambda x: x[-1], mets)
            new_err = state.err
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            new_err = state.err

        with jax.named_scope(scopes.OPTIMIZER):
            new_params, new_opt, opt_metrics = adamw_update(
                opt_cfg, params, grads, state.opt)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return TrainState(new_params, new_opt, new_err), metrics

    return train_step


def make_eval_step(model: Model, parallel: ParallelConfig, mesh=None):
    ctx = make_ctx(parallel, mesh)

    def eval_step(params, batch):
        loss, metrics = model.loss(params, batch, ctx)
        return {"loss": loss, **metrics}

    return eval_step
