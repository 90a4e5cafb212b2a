"""int8-compressed DP gradient all-reduce (error feedback) on fake devices."""


def test_compressed_allreduce_matches_mean(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.mesh import make_mesh
from repro.parallel.collectives import (make_compressed_value_and_grad,
                                        init_error_state)
mesh = make_mesh((4, 2), ("data", "model"))
D, F, B = 16, 8, 32
def loss_fn(w, batch):
    y = batch["x"] @ w
    l = jnp.mean(y ** 2)
    return l, {"l2": l}
w = jax.device_put(np.random.RandomState(0).randn(D, F).astype(np.float32),
                   NamedSharding(mesh, P(None, "model")))
x = jax.device_put(np.random.RandomState(1).randn(B, D).astype(np.float32),
                   NamedSharding(mesh, P("data", None)))
batch = {"x": x}
run = make_compressed_value_and_grad(loss_fn, mesh, ("data",))
err = init_error_state(w, 4)
with jax.set_mesh(mesh):
    loss, met, g, err = jax.jit(run)(w, batch, err)
(ref_loss, _), ref_g = jax.value_and_grad(loss_fn, has_aux=True)(w, batch)
assert abs(float(loss) - float(ref_loss)) < 1e-5
rel = float(jnp.linalg.norm(g - ref_g) / jnp.linalg.norm(ref_g))
assert rel < 0.02, rel
print("compressed ok", rel)
""")
    assert "compressed ok" in out


def test_error_feedback_reduces_bias_over_steps(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.mesh import make_mesh
from repro.parallel.collectives import (make_compressed_value_and_grad,
                                        init_error_state)
mesh = make_mesh((8,), ("data",))
D = 64
def loss_fn(w, batch):
    l = jnp.mean((batch["x"] - w) ** 2)
    return l, {}
w = jnp.zeros((D,), jnp.float32)
x = jax.device_put(np.random.RandomState(0).randn(64, D).astype(np.float32) * 0.01,
                   NamedSharding(mesh, P("data")))
run = jax.jit(make_compressed_value_and_grad(loss_fn, mesh, ("data",)))
err = init_error_state(w, 8)
accum_c = jnp.zeros((D,))
accum_r = jnp.zeros((D,))
with jax.set_mesh(mesh):
    for i in range(20):
        loss, met, g, err = run(w, {"x": x}, err)
        (_, _), gr = jax.value_and_grad(loss_fn, has_aux=True)(w, {"x": x})
        accum_c += g
        accum_r += gr
# with error feedback the accumulated compressed grads track the true sum
rel = float(jnp.linalg.norm(accum_c - accum_r) / jnp.linalg.norm(accum_r))
assert rel < 0.01, rel
print("errfb ok", rel)
""")
    assert "errfb ok" in out


def test_train_step_with_compression_learns(subproc):
    out = subproc("""
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.mesh import make_mesh
from repro.configs.base import ParallelConfig
from repro.configs.registry import get_config
from repro.models import build_model
from repro.train import (OptConfig, DataConfig, DataIterator,
                         init_train_state, make_train_step)
from repro.parallel.collectives import init_error_state
mesh = make_mesh((4,), ("data",))
cfg = get_config("qwen3-8b", smoke=True)
m = build_model(cfg)
par = ParallelConfig(grad_compression=True, fsdp=False)
state = init_train_state(m, jax.random.PRNGKey(0), par)
state = state._replace(err=init_error_state(state.params, 4))
step = jax.jit(make_train_step(m, OptConfig(lr=1e-2, warmup_steps=5,
                                            total_steps=50), par, mesh))
it = DataIterator(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                             global_batch=8))
losses = []
with jax.set_mesh(mesh):
    for i in range(30):
        state, metrics = step(state, next(it))
        losses.append(float(metrics["loss"]))
assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])
print("comp train ok", losses[0], losses[-1])
""")
    assert "comp train ok" in out
