"""Random weights of a decoder-only model, made on the device from the
seed in one jitted call, in the layout the program's model takes: a
stacked ``pat0`` group of ``num_hidden_layers`` layers, a tied embedding
and a final norm. Matrices are normal with standard deviation
1/sqrt(fan-in), cut at two deviations; norm scales are ones in float32.
The same seed gives the same weights, so the reference rebuilds them
rather than taking them from the program."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def _normal(key, shape, fan_in, dtype):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x / math.sqrt(fan_in)).astype(dtype)


def _make(key, d, h, kv, dh, ff, vocab, layers, gated, dtype):
    ks = jax.random.split(key, 8)
    mlp = {"w_in": _normal(ks[5], (layers, d, ff), d, dtype),
           "w_out": _normal(ks[6], (layers, ff, d), ff, dtype)}
    if gated:
        mlp["w_gate"] = _normal(ks[7], (layers, d, ff), d, dtype)
    ones = jnp.ones((layers, d), jnp.float32)
    return {
        "embed": {"w": _normal(ks[0], (vocab, d), d, dtype)},
        "final_norm": {"scale": jnp.ones((d,), jnp.float32)},
        "pat0": {
            "attn": {"wq": _normal(ks[1], (layers, d, h, dh), d, dtype),
                     "wk": _normal(ks[2], (layers, d, kv, dh), d, dtype),
                     "wv": _normal(ks[3], (layers, d, kv, dh), d, dtype),
                     "wo": _normal(ks[4], (layers, h, dh, d), h * dh,
                                   dtype)},
            "mlp": mlp,
            "norm_attn": {"scale": ones},
            "norm_ffn": {"scale": ones},
        },
    }


@functools.lru_cache(maxsize=None)
def _maker(d, h, kv, dh, ff, vocab, layers, gated, dtype_name):
    return jax.jit(functools.partial(
        _make, d=d, h=h, kv=kv, dh=dh, ff=ff, vocab=vocab, layers=layers,
        gated=gated, dtype=_DTYPES[dtype_name]))


def make_weights(m: dict, seed: int, dtype: str = "bfloat16"):
    """The weights of model ``m`` for ``seed``, on the default device."""
    fn = _maker(m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"], m["head_dim"],
                m["intermediate_size"], m["vocab_size"],
                m["num_hidden_layers"], bool(m.get("mlp_gated")), dtype)
    # a key holds 32 bits of the seed; the bits above are folded in, so
    # that seeds past 2**32 give weights of their own
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return fn(jax.random.key_data(key))
