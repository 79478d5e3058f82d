"""A model family that ``dense`` cannot express, kept here as the source of
a family module that ``test_families.py`` writes into a fresh checkout:
Qwen1.5's decoder, pre-norm layers in one stacked group with biases on
the query, key and value projections, a gated SiLU MLP and an untied
head. It provides what a training cell calls (see ``bench/families/
dense.py`` for the contract): ``program_config``, ``make_weights``,
``loss`` and the training counts.

Equations: ``x += attn(rms(x))``, ``x += mlp(rms(x))``, a final RMS norm,
then the head ``x W_head`` (hidden x vocabulary). Attention: ``q = x W_q +
b_q`` (and so ``k``, ``v``), rotary positions on ``q`` and ``k`` (the two
halves of each head rotate against each other, frequency
``theta^(-i/half)``), grouped-query heads, causal, scaled by
``1/sqrt(head_dim)``, softmax in float32. MLP: ``silu(x W_gate) * (x
W_in)`` times ``W_out``.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp

from harness import program
from harness.reference import fake_int8

HIGHEST = jax.lax.Precision.HIGHEST

_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "mlp_gated": "mlp_gated", "tie_word_embeddings": "tie_embeddings",
           "attention_bias": "qkv_bias"}


def program_config(model: dict):
    program.import_program()
    from repro.models.transformer import BlockSpec
    name, _, variant = model["program_config"].partition(":")
    mod = importlib.import_module(f"repro.configs.{name}")
    make = mod.make_smoke if variant == "smoke" else mod.make_config
    cfg = dataclasses.replace(make(), repeats=model["num_hidden_layers"],
                              rope_theta=float(model["rope_theta"]))
    for key, attr in _WIDTHS.items():
        if getattr(cfg, attr) != model[key]:
            raise ValueError(f"{key}: the file says {model[key]}, the "
                             f"program's config {attr}={getattr(cfg, attr)}")
    if (cfg.act != "silu" or cfg.padded_vocab != cfg.vocab
            or cfg.pattern != (BlockSpec(),) or cfg.tail):
        raise ValueError("the reference assumes one stacked group of "
                         "attention layers, a SiLU MLP and an unpadded "
                         "vocabulary")
    return cfg


# -- weights ----------------------------------------------------------------

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _normal(key, shape, fan_in, dtype):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x / math.sqrt(fan_in)).astype(dtype)


def _make(key, d, h, kv, dh, ff, vocab, layers, dtype):
    ks = jax.random.split(key, 12)
    ones = jnp.ones((layers, d), jnp.float32)
    # biases of deviation 0.1, so that a program that drops one is caught
    return {
        "embed": {"w": _normal(ks[0], (vocab, d), d, dtype)},
        "unembed": {"w": _normal(ks[1], (d, vocab), d, dtype)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "pat0": {
            "attn": {"wq": _normal(ks[2], (layers, d, h, dh), d, dtype),
                     "wk": _normal(ks[3], (layers, d, kv, dh), d, dtype),
                     "wv": _normal(ks[4], (layers, d, kv, dh), d, dtype),
                     "wo": _normal(ks[5], (layers, h, dh, d), h * dh,
                                   dtype),
                     "bq": _normal(ks[6], (layers, h, dh), 100, dtype),
                     "bk": _normal(ks[7], (layers, kv, dh), 100, dtype),
                     "bv": _normal(ks[8], (layers, kv, dh), 100, dtype)},
            "mlp": {"w_in": _normal(ks[9], (layers, d, ff), d, dtype),
                    "w_gate": _normal(ks[10], (layers, d, ff), d, dtype),
                    "w_out": _normal(ks[11], (layers, ff, d), ff, dtype)},
            "norm_attn": {"scale": ones},
            "norm_ffn": {"scale": ones},
        },
    }


@functools.lru_cache(maxsize=None)
def _maker(d, h, kv, dh, ff, vocab, layers, dtype_name):
    return jax.jit(functools.partial(
        _make, d=d, h=h, kv=kv, dh=dh, ff=ff, vocab=vocab, layers=layers,
        dtype=_DTYPES[dtype_name]))


def make_weights(m: dict, seed: int, dtype: str = "bfloat16"):
    fn = _maker(m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"], m["head_dim"],
                m["intermediate_size"], m["vocab_size"],
                m["num_hidden_layers"], dtype)
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return fn(jax.random.key_data(key))


# -- the reference ------------------------------------------------------------

def _rms(x, scale, eps):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, p, pos, theta):
    q = jnp.einsum("sd,dhk->shk", h, p["wq"], precision=HIGHEST) + p["bq"]
    k = jnp.einsum("sd,dhk->shk", h, p["wk"], precision=HIGHEST) + p["bk"]
    v = jnp.einsum("sd,dhk->shk", h, p["wv"], precision=HIGHEST) + p["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    s, nh, dh = q.shape
    k = jnp.repeat(k, nh // k.shape[1], axis=1)
    v = jnp.repeat(v, nh // v.shape[1], axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(dh)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
    return jnp.einsum("shk,hkd->sd", o, p["wo"], precision=HIGHEST)


def _mlp(h, p):
    a = jnp.einsum("sd,df->sf", h, p["w_in"], precision=HIGHEST)
    g = jnp.einsum("sd,df->sf", h, p["w_gate"], precision=HIGHEST)
    return jnp.einsum("sf,fd->sd", jax.nn.silu(g) * a, p["w_out"],
                      precision=HIGHEST)


def _layer(x, p, pos, m):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms(x, p["norm_attn"]["scale"], eps), p["attn"],
                       pos, m["rope_theta"])
    return x + _mlp(_rms(x, p["norm_ffn"]["scale"], eps), p["mlp"])


def _int8(p):
    """The layer's matrices rounded to int8; biases and norms kept."""
    mats = {k: v for k, v in p["attn"].items() if k.startswith("w")}
    return dict(p, attn=dict(p["attn"], **fake_int8(mats)),
                mlp=fake_int8(p["mlp"]))


def loss(params, tokens, labels, m, quant):
    """Mean next-token cross-entropy of one batch (B, S)."""
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)

    def one(tok, lab):
        x = params["embed"]["w"][tok]
        for i in range(m["num_hidden_layers"]):
            p = jax.tree.map(lambda a: a[i], params["pat0"])
            x = _layer(x, _int8(p) if quant else p, pos, m)
        h = _rms(x, params["final_norm"]["scale"], m["rms_norm_eps"])
        head = params["unembed"]["w"]
        if quant:
            head = fake_int8({"w": head})["w"]
        lg = jnp.einsum("sd,dv->sv", h, head, precision=HIGHEST)
        lz = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lz - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])
    return jnp.mean(jax.vmap(one)(tokens, labels))


# -- counts -------------------------------------------------------------------

def _layer_matmul_params(m: dict) -> int:
    d, h, kv, dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    return 2 * d * h * dh + 2 * d * kv * dh + 3 * d * m["intermediate_size"]


def param_count(m: dict) -> int:
    """The layers' matrices, biases and norm scales, the embedding, the
    head and the final norm."""
    d, h, kv, dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    per_layer = _layer_matmul_params(m) + (h + 2 * kv) * dh + 2 * d
    return (2 * m["vocab_size"] * d + m["num_hidden_layers"] * per_layer
            + d)


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """6 per matmul weight (the head included) plus causal attention, 3 x
    the forward's 2 * H*Dh * S per layer."""
    L = m["num_hidden_layers"]
    return (6.0 * (_layer_matmul_params(m) * L
                   + m["vocab_size"] * m["hidden_size"])
            + 6.0 * m["num_attention_heads"] * m["head_dim"] * L * seq_len)


def attention_kernels(m: dict, batch: int, seq_len: int, steps: int,
                      dtype_bytes: int = 2):
    """(FLOPs, bytes) of the flash attention backward kernels, as
    ``dense.attention_kernels`` counts them: the attention is the same."""
    L, h, kv, dh = (m["num_hidden_layers"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    pairs = seq_len * (seq_len + 1) / 2.0
    flops = 4.0 * 2.0 * pairs * dh * h * batch * L * steps
    per = (4 * seq_len * h * dh * dtype_bytes
           + 4 * seq_len * kv * dh * dtype_bytes + seq_len * h * 4)
    return flops, float(per * batch * L * steps)
