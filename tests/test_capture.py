"""End-to-end Flint capture: cluster-free lower/compile -> Chakra graph ->
passes -> simulator (the paper's pipeline on an 8-fake-device mesh)."""


def test_capture_pipeline_end_to_end(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.mesh import make_mesh
from repro.core import capture_step, passes
from repro.core.costmodel import simulate, build_topology
from repro.configs.base import SystemConfig

mesh = make_mesh((8,), ("data",))
L = 4
def step(stack, x):
    def body(h, w):
        return jax.nn.relu(h @ w), None
    h, _ = jax.lax.scan(body, x, stack)
    return jnp.mean(h ** 2)
g = jax.value_and_grad(step)
ss = jax.ShapeDtypeStruct((L, 512, 512), jnp.bfloat16)
xs = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
sh = (NamedSharding(mesh, P(None, "data", None)),   # FSDP weights
      NamedSharding(mesh, P("data", None)))
cap = capture_step(g, (ss, xs), sh, mesh, meta={"case": "test"})

# graph has per-layer weight all-gathers with true deps
ags = [n for n in cap.graph.by_type("COMM_COLL")
       if n.attrs["comm_kind"] == "all-gather"]
assert len(ags) >= L, len(ags)
assert cap.summary["parsed_flops"] > 0
assert cap.summary["comm_bytes"] > 0
assert cap.meta["num_partitions"] == 8
cap.graph.validate()

# memory/cost analyses present
assert "temp_size_in_bytes" in cap.memory_analysis
assert cap.cost_analysis.get("flops", 0) > 0

# passes + sim: sync version must not be faster than prefetched
sysc = SystemConfig(chips=8, link_bw=400e9)
topo = build_topology(sysc, 8)
g_sync = passes.inject_fsdp_sync(cap.graph)
g_pre = passes.reorder_prefetch(g_sync, prefetch=4)
r_sync = simulate(g_sync, sysc, topo)
r_pre = simulate(g_pre, sysc, topo)
assert r_pre.total_time <= r_sync.total_time + 1e-12
assert r_pre.peak_bytes > 0 and r_sync.peak_bytes > 0
print("capture ok", len(cap.graph), r_sync.total_time, r_pre.total_time)
""")
    assert "capture ok" in out


def test_stablehlo_op_counts(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from repro.core import stablehlo_op_counts
def f(x, w):
    return jnp.tanh(x @ w).sum()
low = jax.jit(f).lower(jax.ShapeDtypeStruct((4, 8), jnp.float32),
                       jax.ShapeDtypeStruct((8, 4), jnp.float32))
c = stablehlo_op_counts(low.as_text())
assert c.get("dot_general", 0) == 1, c
assert c.get("tanh", 0) == 1, c
print("stablehlo ok")
""", devices=1)
    assert "stablehlo ok" in out


def test_capture_counts_match_model_structure(subproc):
    """Paper SS5.2 analogue: captured per-layer collective counts must track
    the layer count when depth doubles."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.parallel.mesh import make_mesh
from repro.core import capture_step

mesh = make_mesh((2, 4), ("data", "model"))
def make(L):
    def step(stack, x):
        def body(h, w):
            return jax.nn.relu(h @ w), None
        h, _ = jax.lax.scan(body, x, stack)
        return jnp.mean(h ** 2)
    g = jax.value_and_grad(step)
    ss = jax.ShapeDtypeStruct((L, 256, 256), jnp.bfloat16)
    xs = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
    sh = (NamedSharding(mesh, P(None, None, "model")),
          NamedSharding(mesh, P("data", None)))
    return capture_step(g, (ss, xs), sh, mesh, build_graph=False)

c4 = make(4).summary
c8 = make(8).summary
r = c8["parsed_flops"] / c4["parsed_flops"]
assert 1.9 < r < 2.1, r
ar4 = c4["comm"].get("all-reduce", {"count": 0})["count"]
ar8 = c8["comm"].get("all-reduce", {"count": 0})["count"]
assert ar8 > ar4
print("structure ok", r, ar4, ar8)
""")
    assert "structure ok" in out
