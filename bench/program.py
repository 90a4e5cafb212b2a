"""The system under test for one cell, built through the program's own path.

``repro.launch.specs.input_specs`` gives the state and batch shapes and
their shardings on the cell's mesh, and ``step_fn_for`` the train step; the
step is jitted with the state donated, as ``repro.launch.train`` runs it.
Weights and batches are the benchmark's own, made on the device from the
seed (``weights.py``, ``data.py``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


class Program(NamedTuple):
    cfg: Any                 # repro ModelConfig
    shape: Any               # repro ShapeConfig
    mesh: Any
    parallel: Any
    opt: Any                 # repro OptConfig
    model: Any
    abstract_args: tuple     # (TrainState, batch) of ShapeDtypeStructs
    shardings: tuple         # (TrainState, batch) of NamedShardings
    out_shardings: tuple
    step: Any                # un-jitted (state, batch) -> (state, metrics)
    jitted: Any              # the step as the window calls it

    @property
    def n_chips(self) -> int:
        return self.mesh.devices.size

    @property
    def tokens_per_step(self) -> int:
        return self.shape.global_batch * self.shape.seq_len


def _get(conf: dict, dotted: str):
    v = conf
    for k in dotted.split("."):
        v = v[k]
    return v


def model_config(conf: dict):
    """The registry model with the file's overrides, checked against every
    size the configuration file states."""
    from repro.configs.registry import get_config
    prog = conf["program"]
    cfg = get_config(prog["registry"]).replace(**prog.get("overrides", {}))
    for key, field in prog["fields"].items():
        want, got = _get(conf, key), getattr(cfg, field)
        if want != got:
            raise ValueError(f"{conf['name']}: {key} is {want} in the file, "
                             f"{field} = {got} in the program")
    return cfg


def build(conf: dict, traffic: dict, devices) -> Program:
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.launch.specs import input_specs, step_fn_for
    from repro.parallel.mesh import make_mesh
    from repro.train.optimizer import OptConfig

    cfg = model_config(conf)
    if traffic["kind"] != "train":
        raise ValueError(f"traffic kind {traffic['kind']!r} is not built here")
    shape = ShapeConfig(f"{conf['name']}.train", "train", traffic["seq_len"],
                        traffic["batch"])
    axes = tuple(conf["mesh"])
    dims = tuple(conf["mesh"][a] for a in axes)
    n = 1
    for d in dims:
        n *= d
    if len(devices) < n:
        raise RuntimeError(f"{conf['name']} needs {n} devices, "
                           f"found {len(devices)}")
    mesh = make_mesh(dims, axes, devices=list(devices)[:n])
    parallel = ParallelConfig(**conf["parallel"])
    args, shardings, model, parallel, donate = input_specs(cfg, shape, mesh,
                                                           parallel)
    opt = OptConfig(**conf["optimizer"])
    step = step_fn_for(model, shape, parallel, mesh, opt)
    # the new state keeps the old one's layout, so every step runs one program
    out_sh = (shardings[0], NamedSharding(mesh, P()))
    jitted = jax.jit(step, in_shardings=shardings, out_shardings=out_sh,
                     donate_argnums=donate)
    return Program(cfg, shape, mesh, parallel, opt, model, args, shardings,
                   out_sh, step, jitted)


def lower(prog: Program):
    with jax.set_mesh(prog.mesh):
        return prog.jitted.lower(*prog.abstract_args)


def replace_step(prog: Program, step) -> Program:
    """The same cell with another step function (tests plant faults so)."""
    jitted = jax.jit(step, in_shardings=prog.shardings,
                     out_shardings=prog.out_shardings, donate_argnums=(0,))
    return prog._replace(step=step, jitted=jitted)


def state_from_params(params):
    """The program's TrainState around ``params``: zero Adam moments."""
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import TrainState
    return TrainState(params=params, opt=init_opt_state(params), err={})

