"""Operations and bytes that the model's work needs, from its shapes.

These count what the algorithm must do, not what a program happens to
do: a decode step streams each weight once and reads the live keys and
values; causal attention over a span costs its lower triangle; work a
program recomputes (activation rematerialization, the probabilities a
flash backward rebuilds) does not count. A share of a roofline computed
from them therefore cannot exceed 100% unless a time leaves out work.

``m`` is a model dict as in ``bench/configs/*.json`` (``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``vocab_size``, ``num_hidden_layers``,
``mlp_gated``); ``dtype_bytes`` is the weight and KV element size.
"""

from __future__ import annotations

from typing import Iterable, Tuple


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


def flash_bwd_kernels(m: dict, batch: int, seq_len: int, steps: int,
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
