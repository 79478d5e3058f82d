"""The ``dense`` family: a decoder of identical pre-norm layers in one
stacked group, RMS norms, rotary grouped-query attention, a tanh-GELU MLP
(gated or not) and the embedding tied as the head. StarCoder2 runs as
this family.

A family module holds everything that belongs to one architecture. A
configuration file under ``bench/configs`` names its family with the key
``"family"``, and the runner loads ``bench/families/<family>.py`` by that
name, as it loads ``bench/metrics/<metric>.py``. ``model`` below is the
configuration file's object. A family provides:

* ``program_config(model)``: the program's model config (the
  ``LMConfig`` of ``repro.configs.<program_config>``, or its CPU-sized
  sibling for ``<module>:smoke``) at the file's depth and cuts. It raises
  where a width in the file differs from the program's, or where the
  program's config is one that this family's reference does not compute.
* ``make_weights(model, seed, dtype)``: the program's weight tree in its
  layout, made on the device from the seed in one jitted call, in
  ``dtype`` (``"bfloat16"`` or ``"float32"``). The reference makes them
  again from the seed rather than take them from the program.
* The plain reference, float32 at ``jax.lax.Precision.HIGHEST``, written
  from the model's equations with no code of the program:
  ``loss(params, tokens, labels, model, quant)``, the mean next-token
  cross-entropy of a batch (B, S), for training cells, which the shared
  AdamW loop (``harness.reference.train_steps``) differentiates; and
  ``served_gaps(params, model, prompt, served, control=False)``, by how
  much each served token's logit lies below the reference's best, for
  serving cells. ``quant`` and ``control`` put the weights rounded by
  ``harness.reference.fake_int8`` in the program's place.
* Counts from shapes, the work the algorithm needs and not what a
  program happens to do (recomputed work does not count), so that a
  share of a roofline or a peak cannot pass 100% unless a time leaves out
  work. Each is read by the metrics named:
  ``param_count(model)`` (``hbm_share.adamw``);
  ``train_flops_per_token(model, seq_len)`` (``mfu.train``,
  ``mfu.forward``, ``mfu.backward``);
  ``attention_kernels(model, batch, seq_len, steps)``, the (FLOPs,
  bytes) of the flash attention backward kernels (``flash_bwd_roofline``);
  ``decode_flops(model, ctxs)`` and ``decode_bytes(model, ctxs)``
  (``mfu.decode``, ``hbm_share.decode``, ``mfu.serve``);
  ``extend_flops(model, rows)`` (``mfu.prefill``, ``mfu.serve``);
  ``paged_decode_kernel(model, ctxs)`` and
  ``paged_prefill_kernel(model, rows)`` (their rooflines); and
  ``kv_bytes_per_token(model)``, which the serving counts build on.

A family provides the functions that its cells' metrics and checks call:
a family trained but never served needs no serving functions.

This family's equations. The layer is pre-norm: ``x += attn(rms(x))``,
``x += mlp(rms(x))``, then a final RMS norm and the tied embedding as the
head. RMS norm: ``x * rsqrt(mean(x^2) + eps) * scale``. Attention: rotary
positions on queries and keys (the two halves of each head rotate
against each other, frequency ``theta^(-i/half)``), grouped-query heads
(query head ``j`` reads key head ``j // (H/KV)``), causal, scaled by
``1/sqrt(head_dim)``, softmax in float32. MLP: ``gelu_tanh(x W_in)
W_out`` (gated: ``gelu_tanh(x W_gate) * (x W_in)``). The weights: one
stacked ``pat0`` group of ``num_hidden_layers`` layers, the embedding and
a final norm; matrices normal with deviation 1/sqrt(fan-in), cut at two
deviations, norm scales ones in float32.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import math
from typing import Iterable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import program
from harness.reference import fake_int8

HIGHEST = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512
_PAD = 512

# configuration-file keys that must equal the program's own config
_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "mlp_gated": "mlp_gated", "tie_word_embeddings": "tie_embeddings"}


def program_config(model: dict):
    """The program's model config named by ``program_config``
    (``<module>`` or ``<module>:smoke`` for its CPU-sized sibling), at the
    file's depth and RoPE base; every width in the file must equal the
    program's."""
    program.import_program()
    name, _, variant = model["program_config"].partition(":")
    mod = importlib.import_module(f"repro.configs.{name}")
    make = mod.make_smoke if variant == "smoke" else mod.make_config
    cfg = dataclasses.replace(make(), repeats=model["num_hidden_layers"],
                              rope_theta=float(model["rope_theta"]))
    for key, attr in _WIDTHS.items():
        got = getattr(cfg, attr)
        if key in model and got != model[key]:
            raise ValueError(f"{key}: the file says {model[key]}, the "
                             f"program's config {attr}={got}")
    if cfg.act != "gelu" or cfg.padded_vocab != cfg.vocab:
        raise ValueError("the reference assumes a tanh-GELU MLP and an "
                         "unpadded vocabulary")
    return cfg


# -- weights ----------------------------------------------------------------

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


# -- the reference ------------------------------------------------------------

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
    reference with int8 weights (``fake_int8``) puts first instead.
    The sequence is padded at its end to a multiple of 512 (causal, so the
    padding changes nothing before it). Run layer by layer."""
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


def loss(params, tokens, labels, m, quant):
    """Mean next-token cross-entropy of one batch (B, S); each layer is
    recomputed in the backward pass, so that a 2048-token row fits."""
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


# -- counts -------------------------------------------------------------------
# ``m`` holds ``hidden_size``, ``num_attention_heads``,
# ``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
# ``vocab_size``, ``num_hidden_layers`` and ``mlp_gated``; ``dtype_bytes``
# is the weight and KV element size.

def layer_matmul_params(m: dict) -> int:
    """Matmul weight elements of one decoder layer (q, k, v, o, MLP)."""
    d, h, kv, dh = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    ff = m["intermediate_size"]
    attn = d * h * dh * 2 + d * kv * dh * 2
    mlp = d * ff * (3 if m.get("mlp_gated") else 2)
    return attn + mlp


def head_params(m: dict) -> int:
    """The (tied) unembedding: one matmul of vocab x hidden per token."""
    return m["vocab_size"] * m["hidden_size"]


def param_count(m: dict) -> int:
    """Parameters of the model in the configuration ``m``: the layers'
    matrices and norm scales, the (tied) embedding and the final norm."""
    d = m["hidden_size"]
    emb = m["vocab_size"] * d * (1 if m.get("tie_word_embeddings") else 2)
    layers = m["num_hidden_layers"] * (layer_matmul_params(m) + 2 * d)
    return emb + layers + d


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Bytes of the weights a forward pass streams: the layers' matmul
    weights and the embedding table (tied, so streamed once), norms in
    fp32."""
    n = (layer_matmul_params(m) * m["num_hidden_layers"] + head_params(m))
    norms = m["hidden_size"] * (2 * m["num_hidden_layers"] + 1) * 4
    return n * dtype_bytes + norms


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """Key and value bytes one token holds across all layers."""
    return (2 * m["num_key_value_heads"] * m["head_dim"] * dtype_bytes
            * m["num_hidden_layers"])


def attn_width(m: dict) -> int:
    return m["num_attention_heads"] * m["head_dim"]


def causal_keys(start: int, n: int) -> int:
    """Sum over the queries at positions ``start .. start+n-1`` of the keys
    each attends (itself and everything before it)."""
    end = start + n
    return (end * (end + 1) - start * (start + 1)) // 2


def decode_flops(m: dict, ctxs: Iterable[int]) -> float:
    """One decode step over slots whose query attends ``ctx`` keys each
    (its own included): weights, then QK and PV over the keys."""
    ctxs = list(ctxs)
    per_tok = 2.0 * (layer_matmul_params(m) * m["num_hidden_layers"]
                     + head_params(m))
    attn = 4.0 * attn_width(m) * m["num_hidden_layers"] * sum(ctxs)
    return per_tok * len(ctxs) + attn


def decode_bytes(m: dict, ctxs: Iterable[int], dtype_bytes: int = 2
                 ) -> float:
    """One decode step: the weights once, every live key and value read,
    the new token's key and value written."""
    ctxs = list(ctxs)
    kv = kv_bytes_per_token(m, dtype_bytes)
    return weight_bytes(m, dtype_bytes) + kv * (sum(ctxs) + len(ctxs))


def extend_flops(m: dict, rows: Iterable[Tuple[int, int]]) -> float:
    """One chunked-prefill call over rows ``(start, n)``: every token
    through the layers, causal attention over its prefix, and the head
    for the one token per row whose logits are sampled."""
    rows = [(s, n) for s, n in rows if n > 0]
    toks = sum(n for _, n in rows)
    keys = sum(causal_keys(s, n) for s, n in rows)
    return (2.0 * layer_matmul_params(m) * m["num_hidden_layers"] * toks
            + 4.0 * attn_width(m) * m["num_hidden_layers"] * keys
            + 2.0 * head_params(m) * len(rows))


def paged_decode_kernel(m: dict, ctxs: Iterable[int], dtype_bytes: int = 2
                        ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged decode attention kernel, all layers of
    one step: QK and PV over the live keys; keys and values read once,
    the query read and the output written."""
    ctxs = list(ctxs)
    L, w = m["num_hidden_layers"], attn_width(m)
    flops = 4.0 * w * L * sum(ctxs)
    byt = (kv_bytes_per_token(m, dtype_bytes) * sum(ctxs)
           + 2.0 * w * dtype_bytes * L * len(ctxs))
    return flops, byt


def paged_prefill_kernel(m: dict, rows: Iterable[Tuple[int, int]],
                         dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged prefill attention kernel, all layers of
    one call over rows ``(start, n)``: causal QK and PV; the cached keys
    and values behind each chunk and the chunk's own read once, its
    queries read and its output written."""
    rows = [(s, n) for s, n in rows if n > 0]
    L, w = m["num_hidden_layers"], attn_width(m)
    flops = 4.0 * w * L * sum(causal_keys(s, n) for s, n in rows)
    byt = (kv_bytes_per_token(m, dtype_bytes) * sum(s + n for s, n in rows)
           + 2.0 * w * dtype_bytes * L * sum(n for _, n in rows))
    return flops, byt


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a training step: 6 per
    matmul weight (head included) plus causal attention, 3 x the forward's
    2 * H*Dh * S per layer. Recomputed work does not count."""
    return (6.0 * (layer_matmul_params(m) * m["num_hidden_layers"]
                   + head_params(m))
            + 6.0 * attn_width(m) * m["num_hidden_layers"] * seq_len)


def attention_kernels(m: dict, batch: int, seq_len: int, steps: int,
                      dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of the flash attention backward (the dq and the dkv
    kernels together) for ``steps`` causal steps: the four products of an
    attention backward (dP, dS K, dS^T Q, P^T dO) over the lower triangle,
    without rebuilding P; q, o, dO, dq and k, v, dk, dv each moved once,
    and the row statistics."""
    L, h, kv, dh = (m["num_hidden_layers"], m["num_attention_heads"],
                    m["num_key_value_heads"], m["head_dim"])
    pairs = seq_len * (seq_len + 1) / 2.0
    flops = 4.0 * 2.0 * pairs * dh * h * batch * L * steps
    per = (4 * seq_len * h * dh * dtype_bytes
           + 4 * seq_len * kv * dh * dtype_bytes + seq_len * h * 4)
    return flops, float(per * batch * L * steps)
