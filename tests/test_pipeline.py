"""GPipe pipeline parallelism: pipelined == sequential (multi-device)."""

from tests._mp import run_multidevice


def test_pipeline_matches_sequential():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel import pipeline as pp
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("pipe",))
key = jax.random.PRNGKey(0)
n_stage, d, batch, micro = 4, 16, 8, 4
ws = jax.random.normal(key, (n_stage, d, d)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w[0] if w.ndim == 3 else x @ w)

# stage params carry a leading per-rank dim of 1 inside shard_map
def stage(wslice, x):
    return jnp.tanh(x @ wslice)

runner = pp.make_pipelined_fn(stage, mesh, n_micro=micro)
x = jax.random.normal(jax.random.fold_in(key, 1), (batch, d))
y_pipe = runner(ws, x)
y_seq = x
for i in range(n_stage):
    y_seq = jnp.tanh(y_seq @ ws[i])
err = float(jnp.abs(y_pipe - y_seq).max())
print("ERR", err)
assert err < 1e-5, err
# differentiability through the pipeline
def loss(ws):
    return jnp.sum(runner(ws, x) ** 2)
g = jax.grad(loss)(ws)
assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
assert float(jnp.abs(g).max()) > 0
print("OK")
""", n_devices=4)
    assert "OK" in out
