"""Transformer building blocks: norms, dense, embeddings, RoPE/M-RoPE, GQA.

All ``init_*`` return common.Axed; all ``apply`` are plain functions.
Attention supports: grouped-query (n_kv <= n_heads), causal masking, sliding
windows (gemma3's 5:1 local:global), optional QKV bias (qwen1.5), incremental
KV-cache decode, and M-RoPE (qwen2-vl).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Axed, group, leaf
from repro.parallel.ctx import constrain, sharding_active

def wl(w, dtype):
    """Weight loader: dequantize int8-served weights at use (fused into the
    consuming matmul's operand load on TPU; the paper's C5 quantized
    inference — see quant.int8.quantize_params_for_serving /
    quantize_weight). ``s8`` is a scalar, per-layer, or keepdims per-channel
    scale — all broadcast against ``q8``."""
    if isinstance(w, dict) and "q8" in w:
        return w["q8"].astype(dtype) * w["s8"].astype(dtype)
    return w.astype(dtype)


def q8_matmul(x: jnp.ndarray, w: dict, contract_ndim: int = 1) -> jnp.ndarray:
    """x (..., contract dims) @ int8-quantized weight via the fused Pallas
    kernel (kernels/int8_matmul.py): int8 loads from HBM, in-register widen,
    per-channel scale on the output tile. The quantized serving fast path's
    weight matmul (DESIGN.md §12); the XLA fallback is wl()+einsum.

    ``w`` is {"q8","s8"} with the first ``contract_ndim`` dims contracted;
    returns (..., *w.shape[contract_ndim:]).
    """
    from repro.kernels import ops as kops
    q = w["q8"]
    kdim = math.prod(q.shape[:contract_ndim])
    out_shape = q.shape[contract_ndim:]
    sv = jnp.broadcast_to(w["s8"], (1,) * contract_ndim + out_shape)
    lead = x.shape[:-contract_ndim]
    y = kops.int8_matmul(x.reshape(*lead, kdim), q.reshape(kdim, -1),
                         sv.reshape(-1))
    return y.reshape(*lead, *out_shape)


# -----------------------------------------------------------------------------
# Norms
# -----------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32) -> Axed:
    return group(scale=leaf(jnp.ones((d,), dtype), "embed"))


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_core(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def _rms_fwd(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    inv32 = jax.lax.rsqrt(var + eps)
    return x * inv32.astype(x.dtype) * scale.astype(x.dtype), (x, inv32, scale)


def _rms_bwd(eps, res, dy):
    # backward stays in x.dtype with fp32 REDUCTIONS only. An fp32 cotangent
    # here forces the whole scanned-layer backward into fp32 and XLA then
    # hoists convert(saved-activation-stack) into a +25 GB/device buffer
    # (measured on mamba2 train_4k; EXPERIMENTS.md §Perf iter 0).
    x, inv32, scale = res
    inv = inv32.astype(x.dtype)
    s = scale.astype(x.dtype)
    d = x.shape[-1]
    g = dy * s                                               # (.., D)
    dot = jnp.sum((g * x).astype(jnp.float32), axis=-1, keepdims=True)
    corr = (inv32 ** 3) * (dot / d)
    dx = g * inv - x * corr.astype(x.dtype)
    dscale = jnp.sum((dy * x * inv).astype(jnp.float32),
                     axis=tuple(range(x.ndim - 1))).astype(scale.dtype)
    return dx, dscale


_rms_core.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return _rms_core(x, params["scale"], eps)


def init_layernorm(d: int, dtype=jnp.float32) -> Axed:
    return group(scale=leaf(jnp.ones((d,), dtype), "embed"),
                 bias=leaf(jnp.zeros((d,), dtype), "embed"))


def layer_norm(params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    # same no-fp32-copy discipline as rms_norm
    mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True,
                   dtype=jnp.float32) - jnp.square(mu)
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
    return (y * params["scale"].astype(x.dtype)
            + params["bias"].astype(x.dtype))


# -----------------------------------------------------------------------------
# Embedding / unembedding
# -----------------------------------------------------------------------------

def init_embed(key, vocab: int, d: int, dtype=jnp.float32) -> Axed:
    # 1/sqrt(d) keeps tied-unembedding logits O(1) at init
    w = common.trunc_normal(key, (vocab, d), 1.0 / math.sqrt(d), dtype)
    return group(w=leaf(w, "vocab", "embed"))


def embed(params, tokens: jnp.ndarray, compute_dtype=None) -> jnp.ndarray:
    """Activations follow the param dtype unless overridden (bf16 in prod,
    fp32 in equivalence tests)."""
    dt = compute_dtype or params["w"].dtype
    return params["w"].astype(dt)[tokens]


def unembed(params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied unembedding: logits in fp32 (standard for loss stability)."""
    return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                      params["w"].astype(jnp.float32))


def init_unembed(key, d: int, vocab: int, dtype=jnp.float32) -> Axed:
    w = common.fan_in_init(key, (d, vocab), fan_in=d, dtype=dtype)
    return group(w=leaf(w, "embed", "vocab"))


def apply_unembed(params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                      params["w"].astype(jnp.float32))


# -----------------------------------------------------------------------------
# Dense / MLP
# -----------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, axes=("embed", "ffn")) -> Axed:
    w = common.fan_in_init(key, (d_in, d_out), dtype=dtype)
    parts = {"w": leaf(w, *axes)}
    if bias:
        parts["b"] = leaf(jnp.zeros((d_out,), dtype), axes[-1])
    return common.group_dict(parts)


def dense(params, x: jnp.ndarray) -> jnp.ndarray:
    y = jnp.einsum("...d,df->...f", x, wl(params["w"], x.dtype))
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def init_mlp(key, d: int, d_ff: int, *, gated: bool = True,
             dtype=jnp.float32) -> Axed:
    k1, k2, k3 = jax.random.split(key, 3)
    parts = {
        "w_in": leaf(common.fan_in_init(k1, (d, d_ff), dtype=dtype), "embed", "ffn"),
        "w_out": leaf(common.fan_in_init(k3, (d_ff, d), dtype=dtype), "ffn", "embed"),
    }
    if gated:
        parts["w_gate"] = leaf(common.fan_in_init(k2, (d, d_ff), dtype=dtype),
                               "embed", "ffn")
    return common.group_dict(parts)


def mlp(params, x: jnp.ndarray, act: str = "silu",
        int8_kernel: bool = False) -> jnp.ndarray:
    act_fn = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
              "relu": jax.nn.relu}[act]
    if int8_kernel and isinstance(params["w_in"], dict) and "q8" in params["w_in"]:
        h = q8_matmul(x, params["w_in"])
        if "w_gate" in params:
            h = act_fn(q8_matmul(x, params["w_gate"])) * h
        else:
            h = act_fn(h)
        return q8_matmul(h, params["w_out"])
    h = jnp.einsum("...d,df->...f", x, wl(params["w_in"], x.dtype))
    if "w_gate" in params:
        g = jnp.einsum("...d,df->...f", x, wl(params["w_gate"], x.dtype))
        h = act_fn(g) * h
    else:
        h = act_fn(h)
    return jnp.einsum("...f,fd->...d", h, wl(params["w_out"], x.dtype))


# -----------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE)
# -----------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                    # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions_thw: jnp.ndarray,
                sections: Tuple[int, int, int], theta: float = 10000.0,
                ) -> jnp.ndarray:
    """Multimodal RoPE (qwen2-vl): 3 position streams (t,h,w) rotate disjoint
    frequency sections of the head dim.

    x: (B, S, H, Dh); positions_thw: (B, S, 3) int32; sections sum to Dh//2.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                    # (half,)
    # pick, per frequency index, which of the 3 position streams drives it
    sec_id = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                              for i, s in enumerate(sections)])  # (half,)
    pos = positions_thw.astype(jnp.float32)[..., sec_id]         # (B,S,half)
    angles = pos * freqs                                       # (B,S,half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -----------------------------------------------------------------------------
# Attention (GQA, windows, cache)
# -----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    # sliding window in tokens; <0 = global/full attention
    window: int = -1
    # "rope" | "mrope" | "none"
    pos_emb: str = "rope"
    mrope_sections: Tuple[int, int, int] = (0, 0, 0)
    softmax_scale: Optional[float] = None
    # sequence-parallel attention: shard q/k/v activations on seq over the
    # model axis (context parallelism) — the TP fallback for archs whose head
    # counts don't divide the mesh (starcoder2 36H, whisper 20H); §Perf HC-A
    sp: bool = False
    # route int8-quantized projection matmuls through the fused Pallas
    # int8 kernel (set by LMConfig.attn_cfg on the quantized serving fast
    # path; XLA dequant+einsum elsewhere)
    int8_kernel: bool = False
    # training fast path (DESIGN.md §13): full-sequence attention through
    # the custom-VJP flash Pallas kernel — forward saves only (O, lse), the
    # backward runs the fused recompute kernels instead of autodiff through
    # sdpa's materialized probability tensor
    flash_vjp: bool = False

    @property
    def scale(self) -> float:
        return self.softmax_scale or (1.0 / math.sqrt(self.head_dim))


def init_attention(key, cfg: AttnConfig, dtype=jnp.float32) -> Axed:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    parts = {
        "wq": leaf(common.fan_in_init(kq, (d, h, dh), fan_in=d, dtype=dtype),
                   "embed", "heads", "head_dim"),
        "wk": leaf(common.fan_in_init(kk, (d, kvh, dh), fan_in=d, dtype=dtype),
                   "embed", "kv_heads", "head_dim"),
        "wv": leaf(common.fan_in_init(kv, (d, kvh, dh), fan_in=d, dtype=dtype),
                   "embed", "kv_heads", "head_dim"),
        "wo": leaf(common.fan_in_init(ko, (h, dh, d), fan_in=h * dh, dtype=dtype),
                   "heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        parts["bq"] = leaf(jnp.zeros((h, dh), dtype), "heads", "head_dim")
        parts["bk"] = leaf(jnp.zeros((kvh, dh), dtype), "kv_heads", "head_dim")
        parts["bv"] = leaf(jnp.zeros((kvh, dh), dtype), "kv_heads", "head_dim")
    return common.group_dict(parts)


def _q8_active(cfg, w) -> bool:
    return cfg.int8_kernel and isinstance(w, dict) and "q8" in w


def _project_qkv(params, cfg: AttnConfig, x: jnp.ndarray, positions):
    if _q8_active(cfg, params["wq"]):
        q = q8_matmul(x, params["wq"])
        k = q8_matmul(x, params["wk"])
        v = q8_matmul(x, params["wv"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, wl(params["wq"], x.dtype))
        k = jnp.einsum("bsd,dhk->bshk", x, wl(params["wk"], x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, wl(params["wv"], x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_emb == "mrope":
        q = apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    if cfg.sp:
        # context parallel: queries shard on seq over "model"; K/V stay
        # seq-replicated (the partitioner gathers them once per layer)
        q = constrain(q, "batch", "seq_tp", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    return q, k, v


def attention_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, *, causal: bool,
                   window) -> jnp.ndarray:
    """(.., Sq, Sk) bool mask. ``window`` may be a traced scalar; window<0
    means full attention (so one scanned stack can mix local/global layers)."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    m = jnp.ones(diff.shape, bool)
    if causal:
        m &= diff >= 0
    w = jnp.asarray(window)
    m &= jnp.where(w > 0, diff < w, True)
    return m


def sdpa(q, k, v, mask, scale: float) -> jnp.ndarray:
    """Reference scaled-dot-product attention with GQA head grouping.

    q: (B,Sq,H,Dh), k/v: (B,Sk,Hkv,Dh); mask broadcastable to (B,H,Sq,Sk).
    fp32 softmax for stability; returns q.dtype.
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    qg = q.reshape(b, sq, hkv, rep, dh)
    logits = jnp.einsum("bqhrd,bnhd->bhrqn", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    # logits: (B, Hkv, rep, Sq, Sk)
    mask_b = jnp.broadcast_to(mask[:, None, None] if mask.ndim == 3
                              else mask[None, None, None], logits.shape)
    logits = jnp.where(mask_b, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrqn,bnhd->bqhrd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dh).astype(q.dtype)


# above this many KV positions the S x S logits tensor cannot live in HBM;
# the exact q-chunked path (XLA-level stand-in for the flash Pallas kernel)
# takes over. 8k: chunk logits are (B,Hkv,rep,1024,S) fp32.
_CHUNKED_SDPA_THRESHOLD = 8192
_SDPA_Q_CHUNK = 1024


def sdpa_q_chunked(q, k, v, q_pos, k_pos, *, causal: bool, window,
                   scale: float, chunk: int = _SDPA_Q_CHUNK) -> jnp.ndarray:
    """Exact attention scanning over query chunks (O(chunk*Sk) live memory).

    Semantics identical to sdpa+attention_mask; used for long sequences where
    the full (Sq, Sk) logits tensor would not fit. On TPU the flash Pallas
    kernel (kernels/flash_attention.py) replaces this at runtime.
    """
    b, sq, h, dh = q.shape
    nc = -(-sq // chunk)
    pad = nc * chunk - sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
    qc = q.reshape(b, nc, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(b, nc, chunk).transpose(1, 0, 2)

    def one(_, inp):
        q_i, p_i = inp                                   # (B,chunk,H,dh)
        mask = attention_mask(p_i, k_pos, causal=causal, window=window)
        mask &= (p_i >= 0)[..., None]
        return None, sdpa(q_i, k, v, mask, scale)

    _, out = jax.lax.scan(one, None, (qc, pc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nc * chunk, h, dh)
    return out[:, :sq]


def attention(params, cfg: AttnConfig, x: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None,
              window=None, arange_positions: bool = False) -> jnp.ndarray:
    """Full (training/prefill) self-attention.

    ``arange_positions``: static promise from the caller that ``positions``
    is the standard 0..S-1 arange (or None, which synthesizes it) — the
    precondition for the flash-kernel route, whose masking is by block
    index, not by the positions tensor.
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        arange_positions = True
    q, k, v = _project_qkv(params, cfg, x, positions)
    pos1d = positions[..., 0] if positions.ndim == 3 else positions
    w = cfg.window if window is None else window
    # sequence-parallel attention only changes anything under an active
    # sharding context (its constraints are no-ops without one), so only
    # there does it keep the unpartitionable kernel off
    seq_sharded = cfg.sp and sharding_active()
    if (cfg.flash_vjp and arange_positions and cfg.causal
            and isinstance(w, int) and not seq_sharded
            and cfg.pos_emb != "mrope"):
        # training fast path: block-index masking is exact because the
        # caller vouched positions == arange (packed/custom-position
        # batches stay on the mask-from-positions sdpa paths below)
        from repro.kernels import ops as kops
        out = kops.flash_attention_train(q, k, v, scale=cfg.scale,
                                         causal=True, window=w)
    elif s > _CHUNKED_SDPA_THRESHOLD:
        out = sdpa_q_chunked(q, k, v, pos1d, pos1d, causal=cfg.causal,
                             window=w, scale=cfg.scale)
    else:
        mask = attention_mask(pos1d, pos1d, causal=cfg.causal, window=w)
        out = sdpa(q, k, v, mask, cfg.scale)
    if _q8_active(cfg, params["wo"]):
        return q8_matmul(out, params["wo"], contract_ndim=2)
    return jnp.einsum("bshk,hkd->bsd", out, wl(params["wo"], out.dtype))


# -- incremental decode -------------------------------------------------------

@dataclasses.dataclass
class KVCache:
    """Ring-less append cache: k/v (B, S_max, Hkv, Dh), scalar write index."""
    k: jnp.ndarray
    v: jnp.ndarray

jax.tree_util.register_dataclass(KVCache, data_fields=["k", "v"], meta_fields=[])


def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> KVCache:
    return KVCache(k=jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
                   v=jnp.zeros((batch, max_len, n_kv, head_dim), dtype))


def attention_decode(params, cfg: AttnConfig, x: jnp.ndarray,
                     cache: KVCache, pos: jnp.ndarray,
                     window=None) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: x (B,1,D), pos scalar int32 (same for all rows).

    Attends over cache[0:pos] + the new token; respects sliding windows.
    """
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)) if pos.ndim == 0 else pos
    if cfg.pos_emb == "mrope":
        positions = jnp.broadcast_to(positions[..., None], positions.shape + (3,))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    k = jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                     (0, pos.astype(jnp.int32), 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                     (0, pos.astype(jnp.int32), 0, 0))
    s_max = k.shape[1]
    k_pos = jnp.arange(s_max)[None]                         # (1, S)
    q_pos = positions[..., 0] if positions.ndim == 3 else positions
    mask = attention_mask(q_pos, k_pos, causal=True,
                          window=cfg.window if window is None else window)
    mask &= (k_pos <= q_pos[..., :, None])                  # exclude unwritten slots
    out = sdpa(q, k, v, mask, cfg.scale)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(out.dtype))
    return y, KVCache(k=k, v=v)


# -- cross attention (whisper decoder) ----------------------------------------

def cross_attention(params, cfg: AttnConfig, x: jnp.ndarray,
                    kv_src: jnp.ndarray) -> jnp.ndarray:
    """x: (B,Sq,D) queries; kv_src: (B,Sk,D) encoder output (no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, wl(params["wq"], x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(q.dtype)
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    mask = jnp.ones((x.shape[0], q.shape[1], k.shape[1]), bool)
    out = sdpa(q, k, v, mask, cfg.scale)
    return jnp.einsum("bshk,hkd->bsd", out, wl(params["wo"], out.dtype))
