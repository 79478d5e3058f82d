"""shard_map expert-parallel MoE == dense reference (multi-device)."""

from tests._mp import run_multidevice


def test_moe_ep_matches_dense():
    out = run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models import moe as moe_lib
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
cfg = moe_lib.MoEConfig(d_model=32, d_ff=64, n_experts=8, top_k=2,
                        capacity_factor=8.0)
ax = moe_lib.init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
y_ref, aux_ref = moe_lib.moe_dense(ax.params, cfg, x)

def ep(params, x):
    return moe_lib.moe_ep(params, cfg, x, "model",
                          jax.lax.axis_size("model"))[0]

param_specs = {"router": P(), "w_in": P("model"), "w_gate": P("model"),
               "w_out": P("model")}
f = jax.jit(jax.shard_map(ep, mesh=mesh,
                          in_specs=(param_specs, P("data", None, None)),
                          out_specs=P("data", None, None), check_vma=False))
y_ep = f(ax.params, x)
err = float(jnp.abs(y_ref - y_ep).max())
print("ERR", err)
assert err < 2e-4, err
print("OK")
""", n_devices=8)
    assert "OK" in out
