"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

Stages hold equal slices of a homogeneous layer stack; microbatches stream
through a collective-permute ring. The schedule is the classic (M + P - 1)
rotation: rank 0 injects microbatch t at tick t, rank P-1 emits microbatch
t - (P-1); bubble fraction = (P-1)/(M+P-1).

Differentiable end-to-end (the tick loop is a lax.scan; JAX transposes the
ppermutes), so training uses autodiff-GPipe semantics with remat on stages.
At the 256/512-chip roofline scale this framework defaults to DP x TP
(pipeline helps most when model layers >> chips or HBM is param-bound);
PP is exercised by tests/test_pipeline.py on small meshes and available via
TrainConfig.pipeline_stages.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def pipeline_apply(stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
                   local_params: PyTree, microbatches: jnp.ndarray,
                   axis_name: str) -> jnp.ndarray:
    """Run inside shard_map: stream microbatches through pipeline stages.

    local_params: this rank's stage parameters (already sharded over
    ``axis_name``, leading stage dim stripped to this rank's slice).
    microbatches: (M, mb, ...) identical on every rank (replicated input).
    Returns (M, mb, ...) final-stage outputs (identical on every rank).
    """
    p = jax.lax.axis_index(axis_name)
    n_stage = jax.lax.axis_size(axis_name)
    m = microbatches.shape[0]
    ticks = m + n_stage - 1
    state0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def tick(carry, t):
        state, outputs = carry
        inject = microbatches[jnp.clip(t, 0, m - 1)]
        x_in = jnp.where(p == 0, inject, state)
        active = (t - p >= 0) & (t - p < m)
        y = stage_fn(local_params, x_in)
        y = jnp.where(active, y, state)
        out_idx = jnp.clip(t - (n_stage - 1), 0, m - 1)
        emit = (p == n_stage - 1) & (t - (n_stage - 1) >= 0) \
            & (t - (n_stage - 1) < m)
        cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, y, cur), out_idx, 0)
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (state0, out0), jnp.arange(ticks))
    # outputs are only populated on the last stage; share them ring-wide
    return jax.lax.psum(jnp.where(p == n_stage - 1, outputs, 0.0), axis_name)


def make_pipelined_fn(stage_fn: Callable, mesh: Mesh, n_micro: int,
                      axis_name: str = "pipe") -> Callable:
    """Wrap ``stage_fn(params_slice, x) -> x`` into a pjit-able pipelined map.

    stacked_params leaves have a leading stage dim == mesh.shape[axis_name];
    x is (batch, ...) and is split into ``n_micro`` microbatches.
    """
    n_stage = mesh.shape[axis_name]

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis_name), P()),
             out_specs=P(), check_vma=False)
    def _run(stacked_params, x):
        local_params = jax.tree.map(lambda a: a[0], stacked_params)
        b = x.shape[0]
        mb = b // n_micro
        micro = x.reshape(n_micro, mb, *x.shape[1:])
        y = pipeline_apply(stage_fn, local_params, micro, axis_name)
        return y.reshape(b, *y.shape[2:])

    return _run
