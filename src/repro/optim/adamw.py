"""AdamW with mixed precision and quantized optimizer-state options.

State layouts (``state_dtype``):
  * "fp32"  — classic: fp32 m/v (+ fp32 master when params are bf16)
  * "bf16"  — m/v in bf16 (halves optimizer HBM; update math in fp32)
  * "int8"  — m/v block-quantized int8 (8-bit-Adam style, per-tensor absmax
              scale) — the paper's "quantize what you can" insight applied to
              optimizer state; this is what lets kimi-k2-1t fit the 512-chip
              multi-pod budget (see EXPERIMENTS.md §Dry-run).

All state shards like its param (ZeRO-free TP sharding; the DP axes see
replicated state, grads are all-reduced by SPMD).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: Union[float, Callable[[jnp.ndarray], jnp.ndarray]] = 1e-3
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: str = "fp32"        # "fp32" | "bf16" | "int8"
    use_master: bool = True          # keep fp32 master when params are low-prec

    def lr_at(self, step: jnp.ndarray) -> jnp.ndarray:
        if callable(self.lr):
            return jnp.asarray(self.lr(step), jnp.float32)
        return jnp.asarray(self.lr, jnp.float32)


# -- quantized moment storage --------------------------------------------------

def _q8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-20) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _dq8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def _store(x: jnp.ndarray, mode: str):
    if mode == "fp32":
        return x.astype(jnp.float32)
    if mode == "bf16":
        return x.astype(jnp.bfloat16)
    q, s = _q8(x)
    return {"q": q, "s": s}


def _load(x, mode: str) -> jnp.ndarray:
    if mode == "int8":
        return _dq8(x["q"], x["s"])
    return x.astype(jnp.float32)


# -- state ---------------------------------------------------------------------

def init_opt_state(params: PyTree, cfg: AdamWConfig) -> Dict[str, PyTree]:
    zeros = jax.tree.map(lambda p: _store(jnp.zeros(p.shape, jnp.float32),
                                          cfg.state_dtype), params)
    zeros2 = jax.tree.map(lambda p: _store(jnp.zeros(p.shape, jnp.float32),
                                           cfg.state_dtype), params)
    state: Dict[str, PyTree] = {"m": zeros, "v": zeros2,
                                "step": jnp.zeros((), jnp.int32)}
    if cfg.use_master and any(p.dtype != jnp.float32
                              for p in jax.tree.leaves(params)):
        # a copy even of leaves that are already fp32 (norm scales): a
        # master aliasing its param would be donated twice by a train tick
        state["master"] = jax.tree.map(
            lambda p: jnp.array(p, jnp.float32, copy=True), params)
    return state


def global_norm(tree: PyTree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads: PyTree, max_norm: float) -> Tuple[PyTree, jnp.ndarray]:
    norm = global_norm(grads)
    factor = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * factor), grads), norm


def apply_updates(params: PyTree, grads: PyTree, state: Dict[str, PyTree],
                  cfg: AdamWConfig) -> Tuple[PyTree, Dict[str, PyTree],
                                             Dict[str, jnp.ndarray]]:
    step = state["step"] + 1
    lr = cfg.lr_at(step)
    if cfg.grad_clip > 0:
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    else:
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        gnorm = global_norm(grads)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    masters = state.get("master", params)

    is_q = lambda x: isinstance(x, dict) and set(x) == {"q", "s"}

    def upd(p, master, g, m, v):
        m32 = _load(m, cfg.state_dtype)
        v32 = _load(v, cfg.state_dtype)
        m32 = cfg.b1 * m32 + (1 - cfg.b1) * g
        v32 = cfg.b2 * v32 + (1 - cfg.b2) * jnp.square(g)
        mhat = m32 / b1c
        vhat = v32 / b2c
        base = master.astype(jnp.float32)
        new = base - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                           + cfg.weight_decay * base)
        return new, _store(m32, cfg.state_dtype), _store(v32, cfg.state_dtype)

    flat_p, treedef = jax.tree.flatten(params)
    flat_master = jax.tree.leaves(masters)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["m"], is_leaf=is_q)
    flat_v = jax.tree.leaves(state["v"], is_leaf=is_q)
    new_p, new_m, new_v = [], [], []
    for p, ms, g, m, v in zip(flat_p, flat_master, flat_g, flat_m, flat_v):
        np_, nm, nv = upd(p, ms, g, m, v)
        new_p.append(np_)
        new_m.append(nm)
        new_v.append(nv)
    new_master_tree = jax.tree.unflatten(treedef, new_p)
    new_params = jax.tree.map(lambda old, new: new.astype(old.dtype),
                              params, new_master_tree)
    new_state: Dict[str, PyTree] = {
        "m": jax.tree.unflatten(treedef, new_m),
        "v": jax.tree.unflatten(treedef, new_v),
        "step": step,
    }
    if "master" in state:
        new_state["master"] = new_master_tree
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, new_state, metrics


def opt_state_axes(params_axes: PyTree, cfg: AdamWConfig) -> Dict[str, PyTree]:
    """Logical axes for the optimizer state (mirrors params; int8 scales are
    scalars)."""
    def ax_state(ax):
        if cfg.state_dtype == "int8":
            return {"q": ax, "s": ()}
        return ax
    is_ax = lambda x: isinstance(x, tuple)
    out = {"m": jax.tree.map(ax_state, params_axes, is_leaf=is_ax),
           "v": jax.tree.map(ax_state, params_axes, is_leaf=is_ax),
           "step": ()}
    out["master"] = params_axes
    return out
