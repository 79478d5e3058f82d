"""Plain reference of the decoder-only model the configurations describe,
in float32 at the highest matmul precision, written from the model's
equations with no code of the program.

The layer is pre-norm: ``x += attn(rms(x))``, ``x += mlp(rms(x))``, then a
final RMS norm and the tied embedding as the head. RMS norm:
``x * rsqrt(mean(x^2) + eps) * scale``. Attention: rotary positions on
queries and keys (the two halves of each head rotate against each other,
frequency ``theta^(-i/half)``), grouped-query heads (query head ``j``
reads key head ``j // (H/KV)``), causal, scaled by ``1/sqrt(head_dim)``,
softmax in float32. MLP: ``gelu_tanh(x W_in) W_out`` (gated:
``gelu_tanh(x W_gate) * (x W_in)``).

Serving: :func:`served_gaps` runs a whole sequence once, layer by layer,
and returns by how much the logit of each served token lies below the
reference's best at its position. Training: :func:`train_steps` runs
AdamW steps from the same weights and rows.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512
_PAD = 512


def rms(x, scale, eps):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, Dh), pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attention(h, p, pos, theta):
    """Causal grouped-query attention of one sequence, queries in blocks
    so that the score matrix stays small. h: (S, D)."""
    q = jnp.einsum("sd,dhk->shk", h, p["wq"], precision=HIGHEST)
    k = jnp.einsum("sd,dhk->shk", h, p["wk"], precision=HIGHEST)
    v = jnp.einsum("sd,dhk->shk", h, p["wv"], precision=HIGHEST)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    s, nh, dh = q.shape
    rep = nh // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for b in range(0, s, min(_Q_BLOCK, s)):
        qb = q[b:b + _Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        sc = sc / math.sqrt(dh)
        qi = jnp.arange(b, b + qb.shape[0])[:, None]
        ki = jnp.arange(s)[None, :]
        sc = jnp.where((ki <= qi)[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST))
    o = jnp.concatenate(outs, 0)
    return jnp.einsum("shk,hkd->sd", o, p["wo"], precision=HIGHEST)


def mlp(h, p):
    a = jnp.einsum("sd,df->sf", h, p["w_in"], precision=HIGHEST)
    if "w_gate" in p:
        g = jnp.einsum("sd,df->sf", h, p["w_gate"], precision=HIGHEST)
        a = gelu_tanh(g) * a
    else:
        a = gelu_tanh(a)
    return jnp.einsum("sf,fd->sd", a, p["w_out"], precision=HIGHEST)


def layer(x, p, pos, m):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    p = f32(p)
    eps = m["rms_norm_eps"]
    x = x + attention(rms(x, p["norm_attn"]["scale"], eps), p["attn"], pos,
                      m["rope_theta"])
    return x + mlp(rms(x, p["norm_ffn"]["scale"], eps), p["mlp"])


def layer_params(w, i: int):
    return jax.tree.map(lambda a: a[i], w["pat0"])


def _model_key(m: dict) -> Tuple:
    return (m["rms_norm_eps"], m["rope_theta"])


@functools.lru_cache(maxsize=None)
def _jitted(key, quant: bool):
    eps, theta = key
    m = {"rms_norm_eps": eps, "rope_theta": theta}
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    lay = jax.jit(lambda x, p, pos: layer(
        x, fake_int8(f32(p)) if quant else p, pos, m))

    def head(x, final, emb, toks):
        h = rms(x, final.astype(jnp.float32), eps)
        lg = jnp.einsum("sd,vd->sv", h, emb.astype(jnp.float32),
                        precision=HIGHEST)
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, toks[:, None], axis=-1)[:, 0]

    def first(x, final, emb):
        """The token the int8 reference puts first at each row."""
        emb = fake_int8({"head": emb.astype(jnp.float32)})["head"]
        h = rms(x, final.astype(jnp.float32), eps)
        lg = jnp.einsum("sd,vd->sv", h, emb, precision=HIGHEST)
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    return lay, jax.jit(head), jax.jit(first)


def _hidden(w, m, toks, quant):
    lay = _jitted(_model_key(m), quant)[0]
    x = w["embed"]["w"][jnp.asarray(toks)].astype(jnp.float32)
    pos = jnp.arange(len(toks), dtype=jnp.int32)
    for i in range(m["num_hidden_layers"]):
        x = lay(x, layer_params(w, i), pos)
    return x


def served_gaps(w, m: dict, prompt: np.ndarray, served: Sequence[int],
                control: bool = False) -> np.ndarray:
    """Gap below the reference's best logit of every served token: token
    ``i`` of ``served`` was produced at position ``len(prompt) - 1 + i``.
    ``control``: the gap, at the same positions, of the token that the
    reference with int8 weights (:func:`fake_int8`) puts first instead.
    The sequence is padded at its end to a multiple of 512 (causal, so the
    padding changes nothing before it)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    n = len(seq)
    sp = -(-n // _PAD) * _PAD
    toks = np.zeros(sp, np.int32)
    toks[:n] = seq
    _, head, first_tok = _jitted(_model_key(m), False)
    first = len(prompt) - 1
    k = len(served)
    kp = -(-k // 128) * 128
    rows = jnp.asarray(np.minimum(np.arange(first, first + kp), sp - 1))
    final, emb = w["final_norm"]["scale"], w["embed"]["w"]
    if control:
        want = first_tok(_hidden(w, m, toks, True)[rows], final, emb)
    else:
        want = np.zeros(kp, np.int32)
        want[:k] = served
        want = jnp.asarray(want)
    gaps = head(_hidden(w, m, toks, False)[rows], final, emb, want)
    return np.asarray(gaps)[:k]


# -- training -----------------------------------------------------------------

def _loss(params, tokens, labels, m, quant):
    """Mean next-token cross-entropy of one batch (B, S)."""
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def one(tok, lab):
        x = params["embed"]["w"][tok]
        for i in range(m["num_hidden_layers"]):
            p = layer_params(params, i)
            if quant:
                p = fake_int8(p)
            x = jax.checkpoint(lambda x, p: layer(x, p, pos, m))(x, p)
        h = rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])
        emb = params["embed"]["w"]
        if quant:
            emb = fake_int8({"head": emb})["head"]
        lg = jnp.einsum("sd,vd->sv", h, emb, precision=HIGHEST)
        lz = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lz - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])
    return jnp.mean(jax.vmap(one)(tokens, labels))


def fake_int8(p):
    """Weights rounded to int8 with one scale per output channel (the
    maximum over the contracted axes), straight through in the backward
    pass: the control, one precision below bfloat16. ``p`` is a layer's
    group, or ``{"head": embedding}`` (contracted over the hidden axis)."""
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


def train_steps(w, m: dict, opt: dict, batches: List[Dict[str, np.ndarray]],
                quant: bool = False) -> Dict[str, object]:
    """AdamW steps from weights ``w`` (upcast to float32 masters) over
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
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t, l: _loss(
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
        loss, g = grad_fn(master, jnp.asarray(b["tokens"]),
                          jnp.asarray(b["labels"]))
        if first_grad is None:
            first_grad = leaf_norms(g)
        lr = warmup_cosine(i + 1, opt["lr"], opt["warmup_steps"],
                           opt["schedule_steps"])
        master, mom, vel, norm = update(master, mom, vel, g, float(i + 1),
                                        lr)
        del g
        losses.append(float(loss))
        norms.append(float(norm))
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    change = leaf_norms(jax.tree.map(jnp.subtract, master, w32))
    return {"loss": losses, "grad_norm": norms, "moment": leaf_norms(mom),
            "change": change, "first_grad": first_grad}


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, by its path; a stacked layer group
    counts each layer as a leaf of its own."""
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
