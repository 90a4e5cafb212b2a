"""The FLOPs functions against counts made by hand at smoke sizes, and
their parameter counts against the tree the program builds."""
from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from bench import program, registry
from bench.conftest import CONFIGS, TRAFFIC, tiny

TR = {"batch": 2, "seq_len": 8}


def mamba2_conf():
    return {"d_model": 4, "n_layer": 2, "vocab_size": 9, "embedding_rows": 10,
            "ssm_cfg": {"d_state": 3, "headdim": 2, "expand": 2, "d_conv": 4,
                        "chunk_size": 4}}


def test_mamba2_by_hand():
    f = registry.flops("mamba2")
    # di 8, H 4: in_proj 4*(16+6+4)=104, conv (4+1)*(8+6)=70, A/D/dt 12,
    # out 32, norm 4 -> 222; two layers 444, embedding 40, final norm 4
    assert f.params(mamba2_conf()) == 488
    # per sequence and layer: 2*8*4*3=192, 2*8*4*4*2=512, 4*8*4*3*2=768
    ssd = 192 + 512 + 768
    assert f.model_flops_per_step(mamba2_conf(), TR) == \
        6 * 488 * 16 + 3 * 2 * 2 * ssd


def config_file(name):
    with open(os.path.join(registry.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_params_match_the_program_tree(name):
    """The FLOPs count every weight of the tree the program trains."""
    conf = tiny(config_file(name))
    prog = program.build(conf, TRAFFIC, jax.devices())
    n = sum(int(np.prod(a.shape)) for a in
            jax.tree_util.tree_leaves(prog.abstract_args[0].params))
    assert registry.flops(conf["flops"]).params(conf) == n


@pytest.mark.parametrize("name", CONFIGS)
def test_full_size_counts(name):
    """At the configurations' own sizes."""
    conf = config_file(name)
    n = registry.flops(conf["flops"]).params(conf)
    want = {"ssd-lm-780m": 780e6}[name]
    assert n == pytest.approx(want, rel=0.01)
