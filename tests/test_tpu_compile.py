"""Compiles for a described TPU v5e, no chip attached: the Pallas kernels at
their real widths, and TPU-compiled HLO through Flint's capture path.

The topology is described only inside the fixture (never at import, in a
skipif or in parametrize): one process at a time may load the TPU library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.capture import summarize_module
from repro.core.convert import hlo_to_chakra
from repro.core.hlo_parse import parse_hlo
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


bf16, f32 = jnp.bfloat16, jnp.float32
KERNELS = {
    # GQA 32 query / 8 kv heads, head_dim 128, seq 4096
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
        [((1, 4096, 8, 4, 128), bf16), ((1, 4096, 8, 128), bf16),
         ((1, 4096, 8, 128), bf16)]),
    # mamba2-780m: 48 heads, head_dim 64, state 128, chunk 256
    "ssd": (
        lambda x, dt, A, B, C: ops.ssd(x, dt, A, B, C, chunk=256),
        [((1, 2048, 48, 64), f32), ((1, 2048, 48), f32), ((48,), f32),
         ((1, 2048, 128), f32), ((1, 2048, 128), f32)]),
    # recurrentgemma-9b: d_rnn 4096
    "rglru_scan": (
        lambda a, b: ops.rglru_scan(a, b),
        [((1, 2048, 4096), f32), ((1, 2048, 4096), f32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    text = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in text
    assert "T" not in {i.opcode for c in parse_hlo(text).computations.values()
                       for i in c.instructions}


def _mlp_train(L, D, F, B):
    def loss(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w[0]) @ w[1], None
        h, _ = jax.lax.scan(body, x, ws)
        return jnp.mean(h.astype(f32) ** 2)

    def step(w1, w2, x):
        lval, g = jax.value_and_grad(loss)((w1, w2), x)
        return lval, w1 - 1e-3 * g[0], w2 - 1e-3 * g[1]

    # forward 2 matmuls per layer, backward twice that
    return step, [((L, D, F), bf16), ((L, F, D), bf16), ((B, D), bf16)], \
        3 * L * 2 * (2 * B * D * F)


def _batched_matmul(b, i, j, k):
    # TPU emits this as a convolution with the batch dim in its window
    return (lambda x, y: jnp.einsum("bij,bjk->bik", x, y),
            [((b, i, j), bf16), ((b, j, k), bf16)], 2 * b * i * j * k)


PROGRAMS = {
    "scanned_mlp_train_step": lambda: _mlp_train(4, 512, 2048, 256),
    "batched_matmul": lambda: _batched_matmul(8, 256, 512, 128),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_tpu_hlo_capture(name, one_chip):
    fn, shapes, flops = PROGRAMS[name]()
    mod = parse_hlo(_compile(fn, shapes, one_chip))
    ops_seen = {i.opcode for c in mod.computations.values()
                for i in c.instructions}
    assert "T" not in ops_seen and "S" not in ops_seen
    assert {"convolution", "fusion", "parameter"} <= ops_seen, ops_seen
    summary = summarize_module(mod)
    assert abs(summary["parsed_flops"] - flops) <= 0.1 * flops, \
        (summary["parsed_flops"], flops)
    graph = hlo_to_chakra(mod)
    assert len(graph) > 0
    assert sum(n.attrs.get("flops", 0.0) for n in graph.nodes) == \
        pytest.approx(summary["parsed_flops"])
