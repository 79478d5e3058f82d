"""The half of the plain reference that every model family shares: the
AdamW loop that trains a family's reference loss (:func:`train_steps`),
its learning-rate schedule, the int8 rounding of the control
(:func:`fake_int8`) and the per-leaf norms that the checks compare
(:func:`leaf_norms`). A family's own equations, its loss and its served
gaps, are in ``bench/families/<family>.py``. Nothing here uses code of
the program.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def fake_int8(p):
    """Weights rounded to int8 with one scale per output channel (the
    maximum over the contracted axes), straight through in the backward
    pass: the control, one precision below bfloat16. ``p`` is a tree of
    weights under the program's names: a leaf of two axes or more is a
    matrix contracted over its first axis, ``wo`` (heads, head_dim, out)
    over its first two, and ``head`` (an embedding used as the head,
    vocabulary x hidden) over its second; a leaf of one axis is kept."""
    def q(a, axes):
        s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-30)
        r = jnp.clip(jnp.round(a / s), -127, 127) * s
        return a + jax.lax.stop_gradient(r - a)

    def walk(t):
        out = {}
        for k, v in t.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k == "head":
                out[k] = q(v, (1,))
            elif k == "wo":
                out[k] = q(v, (0, 1))
            elif v.ndim >= 2:
                out[k] = q(v, (0,))
            else:
                out[k] = v
        return out
    return walk(p)


def warmup_cosine(step, peak, warmup, total):
    s = float(step)
    if s < warmup:
        return peak * s / max(warmup, 1)
    t = min(max((s - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return 0.5 * peak * (1 + math.cos(math.pi * t))


def train_steps(loss: Callable, w, m: dict, opt: dict,
                batches: List[Dict[str, np.ndarray]],
                quant: bool = False) -> Dict[str, object]:
    """AdamW steps of a family's reference ``loss(params, tokens, labels,
    m, quant)`` from weights ``w`` (upcast to float32 masters) over
    ``batches``: global-norm clipping, bias-corrected moments, decoupled
    weight decay on every leaf. Returns each step's loss and global
    gradient norm (before clipping), and per leaf the first moment's norm
    and the masters' change after the last step, and the first step's
    gradient norm."""
    master = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), w)
    mom = jax.tree.map(jnp.zeros_like, master)
    vel = jax.tree.map(jnp.zeros_like, master)
    # the forward sees the weights as the configuration holds them
    # (bfloat16 values of the float32 masters), computed in float32
    held = jax.tree.map(lambda a: a.dtype, w)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t, l: loss(
        jax.tree.map(lambda a, dt: a.astype(dt).astype(jnp.float32), p,
                     held), t, l, m, quant)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(master, mom, vel, g, step, lr):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(g)))
        f = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-12))
        g = jax.tree.map(lambda x: x * f, g)
        b1, b2 = opt["b1"], opt["b2"]
        mom = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mom, g)
        vel = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, vel, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        master = jax.tree.map(
            lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2)
                                                  + opt["eps"])
                                      + opt["weight_decay"] * p),
            master, mom, vel)
        return master, mom, vel, norm

    losses, norms, first_grad = [], [], None
    for i, b in enumerate(batches):
        value, g = grad_fn(master, jnp.asarray(b["tokens"]),
                           jnp.asarray(b["labels"]))
        if first_grad is None:
            first_grad = leaf_norms(g)
        lr = warmup_cosine(i + 1, opt["lr"], opt["warmup_steps"],
                           opt["schedule_steps"])
        master, mom, vel, norm = update(master, mom, vel, g, float(i + 1),
                                        lr)
        del g
        losses.append(float(value))
        norms.append(float(norm))
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    change = leaf_norms(jax.tree.map(jnp.subtract, master, w32))
    return {"loss": losses, "grad_norm": norms, "moment": leaf_norms(mom),
            "change": change, "first_grad": first_grad}


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, by its path; a stacked layer group
    (the program stacks each ``pat<i>`` group over its repeats) counts
    each layer as a leaf of its own."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        a = jnp.asarray(a, jnp.float32)
        if "pat" in name and a.ndim >= 2:
            ns = jnp.sqrt(jnp.sum(jnp.square(a),
                                  axis=tuple(range(1, a.ndim))))
            for i, v in enumerate(np.asarray(ns)):
                out[f"{name}[{i}]"] = float(v)
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(a))))
    return out
