"""GPipe pipeline over a stage axis: forward equivalence + trainability."""


def test_pipeline_matches_sequential(subproc):
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.mesh import make_mesh
from repro.parallel.pipeline import pipeline_apply

mesh = make_mesh((4,), ("pod",))
L, D, M, mb = 8, 16, 6, 4
rng = np.random.RandomState(0)
w = jnp.asarray(rng.randn(L, D, D).astype(np.float32) * 0.3)
x = jnp.asarray(rng.randn(M, mb, D).astype(np.float32))

def block(p, h):
    return jnp.tanh(h @ p)

y_pipe = pipeline_apply(block, w, x, mesh, "pod")
# sequential reference
def seq(h):
    for i in range(L):
        h = block(w[i], h)
    return h
y_ref = jax.vmap(seq)(x)
np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref), atol=1e-5)
print("pipeline fwd ok")

# differentiable: grad wrt stacked params flows through ppermute
def loss(w):
    y = pipeline_apply(block, w, x, mesh, "pod")
    return jnp.mean(y ** 2)
g = jax.grad(loss)(w)
def loss_ref(w):
    def seq(h):
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return h
    return jnp.mean(jax.vmap(seq)(x) ** 2)
g_ref = jax.grad(loss_ref)(w)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)
print("pipeline grad ok")
""", devices=4)
    assert "pipeline fwd ok" in out and "pipeline grad ok" in out


def test_pipeline_two_stage_multipod_shape(subproc):
    """2-stage pipeline on the multi-pod production mesh's pod axis."""
    out = subproc("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.mesh import make_mesh
from repro.parallel.pipeline import pipeline_apply
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
L, D, M, mb = 4, 8, 4, 2
w = jnp.ones((L, D, D), jnp.float32) * 0.1
x = jnp.ones((M, mb, D), jnp.float32)
y = pipeline_apply(lambda p, h: jnp.tanh(h @ p), w, x, mesh, "pod")
assert y.shape == (M, mb, D)
assert np.isfinite(np.asarray(y)).all()
print("multipod pipeline ok", y.shape)
""", devices=8)
    assert "multipod pipeline ok" in out
