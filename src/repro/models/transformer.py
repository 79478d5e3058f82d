"""Decoder-only LM with composable per-layer block schedules.

One config drives all assigned LM architectures:

* dense GQA transformers (starcoder2, granite, qwen1.5, qwen2-vl backbone)
* sliding-window:global patterns (gemma3's 5:1)
* MoE FFNs (moonshot 64e/top-6, kimi-k2 384e/top-8)
* SSM stacks (mamba2) and hybrid stacks with a shared attention block
  invoked periodically (zamba2)

The layer schedule is ``pattern x repeats + tail``. The repeated pattern is
executed with ``jax.lax.scan`` over stacked parameters (HLO size independent
of depth — essential for 512-device compiles); the tail runs unrolled.
Blocks marked ``shared_attn`` reuse a single parameter set across all scan
iterations (zamba2) while still owning per-invocation KV cache slots.

Entry points: ``forward`` (training / logits), ``prefill`` (logits + caches),
``decode_step`` (one token with caches) — the three things the dry-run cells
lower.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common, layers, moe as moe_lib, ssd as ssd_lib
from repro.models.common import Axed, group_dict
from repro.models.layers import AttnConfig, KVCache
from repro.parallel.ctx import constrain

PyTree = Any


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    kind: str = "attn"          # "attn" | "ssd"
    window: int = -1            # sliding window (attn); <0 = global
    moe: bool = False           # MoE FFN instead of dense FFN
    shared_attn: bool = False   # zamba2: use the single shared attention block
    has_ffn: bool = True        # pure mamba blocks have no separate FFN


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Serving-time quantization policy (DESIGN.md §12).

    ``weights``: "none" | "int8" — int8 keeps linear-layer weights int8 in
    HBM with per-output-channel fp32 scales (quantize_lm); embeddings,
    norms, and routers stay high-precision.
    ``kv``: "none" | "int8" — int8 stores the KV cache as (int8 codes,
    one fp32 scale per (slot, position, kv-head)); dequant happens inside
    the attention kernel body, so full-precision K/V never round-trip
    through memory.
    """
    weights: str = "none"
    kv: str = "none"

    @property
    def weights_int8(self) -> bool:
        return self.weights == "int8"

    @property
    def kv_int8(self) -> bool:
        return self.kv == "int8"


INT8_QUANT = QuantPolicy(weights="int8", kv="int8")


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    pattern: Tuple[BlockSpec, ...]
    repeats: int
    tail: Tuple[BlockSpec, ...] = ()
    head_dim: Optional[int] = None
    act: str = "silu"
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    pos_emb: str = "rope"                    # "rope" | "mrope" | "none"
    mrope_sections: Tuple[int, int, int] = (0, 0, 0)
    moe_cfg: Optional[moe_lib.MoEConfig] = None
    ssd_cfg: Optional[ssd_lib.SSDConfig] = None
    tie_embeddings: bool = True
    vision_tokens: int = 0                   # qwen2-vl stub frontend
    logit_softcap: float = 0.0
    remat: str = "full"                      # "none" | "full" | "dots"
    moe_group_size: int = 4096
    ring_cache: bool = False                 # window-sized ring KV caches
    z_loss: float = 0.0
    mlp_gated: bool = True                   # False: classic 2-matrix MLP
    # embedding/logit tables pad up so the vocab dim TP-shards (mamba2's
    # 50280 and whisper's 51866 don't divide 16 — unpadded logits replicate
    # at 13 GB/device; EXPERIMENTS.md §Perf iter 0). labels never reference
    # pad ids; decode/prefill slice logits back to the true vocab.
    vocab_pad_multiple: int = 128
    # sequence-parallel knobs (§Perf HC-A / HC-B):
    sp_attention: bool = False    # shard attention q on seq over model
    sp_residual: bool = False     # keep the residual stream seq-sharded
    # KV-cache storage dtype (§Perf HC-C): "bf16" | "fp8" (f8_e4m3; sdpa
    # upcasts to fp32 so only storage/traffic changes)
    kv_cache_dtype: str = "bf16"
    # serve-core: route batched (per-slot position) decode attention through
    # the Pallas decode kernel (kernels/decode_attention.py). Off by default —
    # the serving engine flips it on for TPU backends (DESIGN.md §serve)
    decode_kernel: bool = False
    # serving-time quantization policy (DESIGN.md §12): int8 weights and/or
    # int8 KV cache. The serving engine sets this from ServeConfig.quant.
    quant: QuantPolicy = QuantPolicy()
    # training fast path (DESIGN.md §13): route full-sequence attention
    # through the custom-VJP flash Pallas kernel so the backward runs the
    # fused recompute-from-lse kernels. Off by default — the TrainEngine
    # flips it on for TPU backends (interpret mode is correctness-only).
    flash_train: bool = False

    @property
    def padded_vocab(self) -> int:
        m = max(self.vocab_pad_multiple, 1)
        return ((self.vocab + m - 1) // m) * m

    @property
    def n_layers(self) -> int:
        return len(self.pattern) * self.repeats + len(self.tail)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def use_int8_matmul(self) -> bool:
        """Fused Pallas int8 matmul on the quantized fast path; XLA
        dequant+einsum elsewhere (CPU tests, unquantized serving)."""
        return self.quant.weights_int8 and self.decode_kernel

    def attn_cfg(self, window: int = -1) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.resolved_head_dim,
            qkv_bias=self.qkv_bias, rope_theta=self.rope_theta,
            causal=True, window=window, pos_emb=self.pos_emb,
            mrope_sections=self.mrope_sections, sp=self.sp_attention,
            int8_kernel=self.use_int8_matmul, flash_vjp=self.flash_train)


# -----------------------------------------------------------------------------
# Parameter init
# -----------------------------------------------------------------------------

def _init_block(key, cfg: LMConfig, spec: BlockSpec, dtype) -> Axed:
    parts: Dict[str, Axed] = {}
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if spec.kind == "attn" and not spec.shared_attn:
        parts["norm_attn"] = layers.init_rmsnorm(cfg.d_model)
        parts["attn"] = layers.init_attention(k1, cfg.attn_cfg(spec.window), dtype)
    elif spec.kind == "ssd":
        parts["norm_ssd"] = layers.init_rmsnorm(cfg.d_model)
        parts["ssd"] = ssd_lib.init_ssd(k2, cfg.ssd_cfg, dtype)
    if spec.kind == "attn" and spec.has_ffn:
        parts["norm_ffn"] = layers.init_rmsnorm(cfg.d_model)
        if spec.moe:
            parts["moe"] = moe_lib.init_moe(k3, cfg.moe_cfg, dtype)
        else:
            parts["mlp"] = layers.init_mlp(k4, cfg.d_model, cfg.d_ff,
                                           gated=cfg.mlp_gated, dtype=dtype)
    return group_dict(parts)


def _has_shared(cfg: LMConfig) -> bool:
    return any(s.shared_attn for s in tuple(cfg.pattern) + tuple(cfg.tail))


def init_lm(key, cfg: LMConfig, dtype=jnp.bfloat16) -> Axed:
    keys = jax.random.split(key, 8)
    parts: Dict[str, Axed] = {"embed": layers.init_embed(
        keys[0], cfg.padded_vocab, cfg.d_model, dtype)}
    # repeated pattern: one stacked entry per pattern position
    for i, spec in enumerate(cfg.pattern):
        if spec.shared_attn:
            continue
        parts[f"pat{i}"] = common.vmap_init(
            lambda k, sp=spec: _init_block(k, cfg, sp, dtype),
            jax.random.fold_in(keys[1], i), cfg.repeats)
    for i, spec in enumerate(cfg.tail):
        if spec.shared_attn:
            continue
        parts[f"tail{i}"] = _init_block(jax.random.fold_in(keys[2], i), cfg, spec, dtype)
    if _has_shared(cfg):
        shared = {"norm_attn": layers.init_rmsnorm(cfg.d_model),
                  "attn": layers.init_attention(keys[3], cfg.attn_cfg(-1), dtype),
                  "norm_ffn": layers.init_rmsnorm(cfg.d_model),
                  "mlp": layers.init_mlp(keys[4], cfg.d_model, cfg.d_ff, dtype=dtype)}
        parts["shared_attn"] = group_dict(shared)
    parts["final_norm"] = layers.init_rmsnorm(cfg.d_model)
    if not cfg.tie_embeddings:
        parts["unembed"] = layers.init_unembed(keys[5], cfg.d_model,
                                               cfg.padded_vocab, dtype)
    return group_dict(parts)


def quantize_lm(params: PyTree) -> PyTree:
    """Weight-tree int8 quantization for serving (QuantPolicy.weights_int8).

    Linear-layer leaves (quant.int8.SERVING_QUANT_KEYS) become
    ``{"q8": int8, "s8": fp32}`` with **per-output-channel** scales;
    embeddings, norms, and routers pass through untouched. Structure-aware:
    ``pat*`` groups carry a leading repeats dim and ``moe`` groups a leading
    expert dim — both are kept as independent scale dims, never reduced
    over. Consumed transparently by models.layers.wl (XLA dequant+einsum)
    or layers.q8_matmul (fused Pallas kernel) on the serving fast path.
    """
    from repro.quant import int8 as int8_lib

    def walk(p: dict, lead: int) -> dict:
        out = {}
        for k, v in p.items():
            if isinstance(v, dict):
                if "q8" in v:           # already quantized
                    out[k] = v
                elif k == "ssd":
                    # SSD blocks consume projections without the wl()
                    # dequant seam (and their state is not a KV cache) —
                    # they stay full precision
                    out[k] = v
                else:
                    out[k] = walk(v, lead + (1 if k == "moe" else 0))
            elif (k in int8_lib.SERVING_QUANT_KEYS
                  and getattr(v, "ndim", 0) >= lead + 2):
                out_dims = min(int8_lib.weight_out_dims(k), v.ndim - lead - 1)
                out[k] = int8_lib.quantize_weight(v, lead=lead,
                                                  out_dims=out_dims)
            else:
                out[k] = v
        return out

    return {k: (walk(v, 1 if k.startswith("pat") else 0)
                if isinstance(v, dict) else v)
            for k, v in params.items()}


# -----------------------------------------------------------------------------
# Block application (full-sequence)
# -----------------------------------------------------------------------------

def _apply_block(params, shared_params, cfg: LMConfig, spec: BlockSpec,
                 x: jnp.ndarray, positions,
                 arange_pos: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x, aux_loss). ``arange_pos``: static flag that ``positions``
    is the synthesized 0..S-1 arange (flash-kernel eligibility)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.kind == "attn":
        p = shared_params if spec.shared_attn else params
        acfg = cfg.attn_cfg(spec.window)
        with jax.named_scope("attn"):
            h = layers.rms_norm(p["norm_attn"], x)
            x = x + layers.attention(p["attn"], acfg, h, positions,
                                     arange_positions=arange_pos)
        if spec.shared_attn:
            with jax.named_scope("mlp"):
                h = layers.rms_norm(p["norm_ffn"], x)
                x = x + layers.mlp(p["mlp"], h, cfg.act,
                                   int8_kernel=cfg.use_int8_matmul)
            return x, aux
    elif spec.kind == "ssd":
        with jax.named_scope("ssd"):
            h = layers.rms_norm(params["norm_ssd"], x)
            x = x + ssd_lib.ssd_block(params["ssd"], cfg.ssd_cfg, h)
    if spec.kind == "attn" and spec.has_ffn and not spec.shared_attn:
        with jax.named_scope("mlp"):
            h = layers.rms_norm(params["norm_ffn"], x)
            if spec.moe:
                y, aux = moe_lib.moe_capacity(params["moe"], cfg.moe_cfg, h,
                                              cfg.moe_group_size)
                x = x + y
            else:
                x = x + layers.mlp(params["mlp"], h, cfg.act,
                                   int8_kernel=cfg.use_int8_matmul)
    if cfg.sp_residual:
        x = constrain(x, "batch", "seq_tp", None)
    return x, aux


def _remat(cfg: LMConfig, fn):
    if cfg.remat == "none":
        return fn

    def fn_ob(carry, xs):
        # barrier stops XLA hoisting convert(saved-carry-stack) out of the
        # backward loop, which otherwise materializes a full fp32 copy of
        # every layer's saved activations (+25 GB/device on mamba2 train_4k;
        # EXPERIMENTS.md §Perf iter 0)
        carry = jax.lax.optimization_barrier(carry)
        return fn(carry, xs)

    if cfg.remat == "dots":
        return jax.checkpoint(
            fn_ob, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn_ob)


def _pattern_stack_params(params, cfg: LMConfig):
    return {f"pat{i}": params[f"pat{i}"]
            for i, s in enumerate(cfg.pattern) if not s.shared_attn}


def forward(params, cfg: LMConfig, tokens: jnp.ndarray,
            positions: Optional[jnp.ndarray] = None,
            vision_embeds: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. tokens (B,S) -> (logits (B,S,V) fp32, aux)."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], tokens)
        if vision_embeds is not None and cfg.vision_tokens > 0:
            x = jax.lax.dynamic_update_slice(
                x, vision_embeds.astype(x.dtype), (0, 0, 0))
        x = constrain(x, "batch", "seq", None)
    arange_pos = positions is None
    if positions is None:
        pos1d = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        positions = (jnp.broadcast_to(pos1d[..., None], (b, s, 3))
                     if cfg.pos_emb == "mrope" else pos1d)
    shared = params.get("shared_attn")

    def body(carry, pat_params):
        x, aux = carry
        for i, spec in enumerate(cfg.pattern):
            p = pat_params.get(f"pat{i}")
            x, a = _apply_block(p, shared, cfg, spec, x, positions,
                                arange_pos=arange_pos)
            aux = aux + a
        return (x, aux), None

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.repeats > 0:
        (x, aux), _ = jax.lax.scan(_remat(cfg, body), (x, aux0),
                                   _pattern_stack_params(params, cfg))
    else:
        aux = aux0
    for i, spec in enumerate(cfg.tail):
        p = params.get(f"tail{i}")
        x, a = _apply_block(p, shared, cfg, spec, x, positions,
                            arange_pos=arange_pos)
        aux = aux + a
    with jax.named_scope("head"):
        x = layers.rms_norm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = layers.unembed(params["embed"], x)
        else:
            logits = layers.apply_unembed(params["unembed"], x)
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        logits = constrain(logits, "batch", "seq", "vocab")
    return logits, aux


def loss_fn(params, cfg: LMConfig, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy (+ MoE aux + optional z-loss)."""
    logits, aux = forward(params, cfg, batch["tokens"],
                          positions=batch.get("positions"),
                          vision_embeds=batch.get("vision_embeds"))
    with jax.named_scope("loss"):
        labels = batch["labels"]
        mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(logits, labels[..., None],
                                          axis=-1)[..., 0]
        nll = (logz - label_logit) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = nll.sum() / denom
        total = ce + aux
        if cfg.z_loss > 0:
            total = total + cfg.z_loss * ((logz * mask) ** 2).sum() / denom
    return total, {"ce": ce, "aux": aux, "tokens": denom}


# -----------------------------------------------------------------------------
# Caches (decode)
# -----------------------------------------------------------------------------

def _cache_len(cfg: LMConfig, spec: BlockSpec, max_len: int) -> int:
    if cfg.ring_cache and spec.window > 0:
        return min(spec.window, max_len)
    return max_len


def init_caches(cfg: LMConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Dict[str, PyTree]:
    """Cache pytree: pattern positions stacked over repeats, tail single.

    Under ``cfg.quant.kv_int8`` the K/V arrays are int8 codes and each attn
    cache gains a ``kv_scale`` pair — one fp32 scale per (slot, position,
    kv-head) — so the resident cache is ~4x smaller than fp32 (``dtype`` is
    ignored for K/V in that mode).
    """
    caches: Dict[str, PyTree] = {}
    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    kv_dtype = jnp.int8 if cfg.quant.kv_int8 else dtype

    def one(spec: BlockSpec, stacked: bool):
        if spec.kind == "attn":
            clen = _cache_len(cfg, spec, max_len)
            shape = (cfg.repeats,) if stacked else ()
            kv = KVCache(
                k=jnp.zeros(shape + (batch, clen, kvh, dh), kv_dtype),
                v=jnp.zeros(shape + (batch, clen, kvh, dh), kv_dtype))
            # per-row ring position tags (rows decode at independent positions
            # under the serving engine's vmapped path)
            pos = jnp.full(shape + (batch, clen), -1, jnp.int32)
            if cfg.quant.kv_int8:
                sc = KVCache(
                    k=jnp.zeros(shape + (batch, clen, kvh), jnp.float32),
                    v=jnp.zeros(shape + (batch, clen, kvh), jnp.float32))
                return {"kv": kv, "kv_scale": sc, "pos": pos}
            return {"kv": kv, "pos": pos}
        st = ssd_lib.init_ssd_state(cfg.ssd_cfg, batch, dtype)
        if stacked:
            st = jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.repeats,) + a.shape), st)
        return {"ssd": st}

    for i, spec in enumerate(cfg.pattern):
        caches[f"pat{i}"] = one(spec, stacked=True)
    for i, spec in enumerate(cfg.tail):
        caches[f"tail{i}"] = one(spec, stacked=False)
    return caches


def _decode_attn(p, cfg: LMConfig, spec: BlockSpec, x, cache, pos):
    """One-token attention against a (possibly ring) cache.

    ``pos`` is a scalar () shared by every row (classic decode, dry-run
    cells) or a (B,) vector of independent per-slot positions (the serving
    engine's slot-major batched decode).
    """
    acfg = cfg.attn_cfg(spec.window)
    b = x.shape[0]
    kv, pos_tags = cache["kv"], cache["pos"]
    kv_int8 = "kv_scale" in cache
    clen = kv.k.shape[1]
    batched_pos = pos.ndim > 0
    if batched_pos:
        positions = pos[:, None].astype(jnp.int32)            # (B, 1)
    else:
        positions = jnp.broadcast_to(pos[None, None], (b, 1))
    if cfg.pos_emb == "mrope":
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = layers._project_qkv(p["attn"], acfg, x, positions)
    if kv_int8:
        # per-(row, head) int8: the cache stores codes + one fp32 scale per
        # (slot, position, kv-head); full-precision K/V exist only for the
        # one new token, in registers
        from repro.quant import int8 as int8_lib
        sc = cache["kv_scale"]
        k_q, k_s = int8_lib.quantize_rowwise(k_new)     # (B,1,H,D),(B,1,H)
        v_q, v_s = int8_lib.quantize_rowwise(v_new)
    if batched_pos:
        # per-row ring slot: one scatter row per sequence
        slot = (pos % clen).astype(jnp.int32)                  # (B,)
        rows = jnp.arange(b)
        if kv_int8:
            k = kv.k.at[rows, slot].set(k_q[:, 0])
            v = kv.v.at[rows, slot].set(v_q[:, 0])
            k_scale = sc.k.at[rows, slot].set(k_s[:, 0])
            v_scale = sc.v.at[rows, slot].set(v_s[:, 0])
        else:
            k = kv.k.at[rows, slot].set(k_new[:, 0].astype(kv.k.dtype))
            v = kv.v.at[rows, slot].set(v_new[:, 0].astype(kv.v.dtype))
        pos_tags = pos_tags.at[rows, slot].set(pos.astype(jnp.int32))
    else:
        slot = pos % clen      # ring slot; == pos when the cache is full-length
        if kv_int8:
            k = jax.lax.dynamic_update_slice(kv.k, k_q, (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(kv.v, v_q, (0, slot, 0, 0))
            k_scale = jax.lax.dynamic_update_slice(sc.k, k_s, (0, slot, 0))
            v_scale = jax.lax.dynamic_update_slice(sc.v, v_s, (0, slot, 0))
        else:
            k = jax.lax.dynamic_update_slice(kv.k, k_new.astype(kv.k.dtype),
                                             (0, slot, 0, 0))
            v = jax.lax.dynamic_update_slice(kv.v, v_new.astype(kv.v.dtype),
                                             (0, slot, 0, 0))
        pos_col = jnp.broadcast_to(pos.astype(jnp.int32), (b, 1))
        pos_tags = jax.lax.dynamic_update_slice(pos_tags, pos_col, (0, slot))
    q_pos = positions[..., 0] if positions.ndim == 3 else positions
    if batched_pos and cfg.decode_kernel and not cfg.ring_cache:
        # Pallas decode kernel: per-slot lengths => dead/short slots cost no
        # FLOPs. Valid cache rows are the contiguous prefix [0, pos] (the
        # serving engine's invariant for non-ring caches). Int8 caches hand
        # the kernel codes + scales; dequant happens inside the kernel body.
        from repro.kernels import ops as kops
        out = kops.decode_attention(
            q[:, 0], k, v, pos.astype(jnp.int32) + 1, scale=acfg.scale,
            window=spec.window,
            k_scale=k_scale if kv_int8 else None,
            v_scale=v_scale if kv_int8 else None)[:, None]
    else:
        if kv_int8:
            # XLA fallback: dequantize at use (fused into the attention
            # matmul's operand load; storage/traffic stays int8)
            k_at = int8_lib.dequantize_rowwise(k, k_scale, dtype=q.dtype)
            v_at = int8_lib.dequantize_rowwise(v, v_scale, dtype=q.dtype)
        else:
            k_at, v_at = k, v
        mask = layers.attention_mask(q_pos, pos_tags, causal=True,
                                     window=spec.window)
        mask &= (pos_tags >= 0)[:, None, :]
        out = layers.sdpa(q, k_at, v_at, mask, acfg.scale)
    if layers._q8_active(acfg, p["attn"]["wo"]):
        y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
    else:
        y = jnp.einsum("bshk,hkd->bsd", out,
                       layers.wl(p["attn"]["wo"], out.dtype))
    new_cache = {"kv": KVCache(k=k, v=v), "pos": pos_tags}
    if kv_int8:
        new_cache["kv_scale"] = KVCache(k=k_scale, v=v_scale)
    return y, new_cache


def _decode_block(params, shared_params, cfg: LMConfig, spec: BlockSpec,
                  x, cache, pos):
    if spec.kind == "attn":
        p = shared_params if spec.shared_attn else params
        with jax.named_scope("attn"):
            h = layers.rms_norm(p["norm_attn"], x)
            y, cache = _decode_attn(p, cfg, spec, h, cache, pos)
            x = x + y
        if spec.shared_attn:
            with jax.named_scope("mlp"):
                h = layers.rms_norm(p["norm_ffn"], x)
                return x + layers.mlp(p["mlp"], h, cfg.act,
                                      int8_kernel=cfg.use_int8_matmul), cache
    else:
        with jax.named_scope("ssd"):
            h = layers.rms_norm(params["norm_ssd"], x)
            y, st = ssd_lib.ssd_block_decode(params["ssd"], cfg.ssd_cfg, h,
                                             cache["ssd"])
            x = x + y
        cache = {"ssd": st}
    if spec.kind == "attn" and spec.has_ffn and not spec.shared_attn:
        with jax.named_scope("mlp"):
            h = layers.rms_norm(params["norm_ffn"], x)
            if spec.moe:
                y, _ = moe_lib.moe_capacity(
                    params["moe"], cfg.moe_cfg, h,
                    group_size=h.shape[0] * h.shape[1])
                x = x + y
            else:
                x = x + layers.mlp(params["mlp"], h, cfg.act,
                                   int8_kernel=cfg.use_int8_matmul)
    return x, cache


def decode_step(params, cfg: LMConfig, token: jnp.ndarray, pos: jnp.ndarray,
                caches: Dict[str, PyTree]
                ) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One decode step. token (B,1) int32 -> (logits (B,1,V), caches).

    pos is () int32 (all rows at the same position) or (B,) int32 (per-slot
    positions — the serving engine's continuous-batching decode tick).
    """
    x = layers.embed(params["embed"], token)
    shared = params.get("shared_attn")

    pat_caches = {f"pat{i}": caches[f"pat{i}"] for i in range(len(cfg.pattern))}

    def body(x, inp):
        pat_params, pat_cache = inp
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = _decode_block(pat_params.get(f"pat{i}"), shared, cfg, spec,
                                  x, pat_cache[f"pat{i}"], pos)
            new_cache[f"pat{i}"] = nc
        return x, new_cache

    new_caches: Dict[str, PyTree] = {}
    if cfg.repeats > 0:
        x, new_pat = jax.lax.scan(body, x,
                                  (_pattern_stack_params(params, cfg), pat_caches))
        new_caches.update(new_pat)
    for i, spec in enumerate(cfg.tail):
        x, nc = _decode_block(params.get(f"tail{i}"), shared, cfg, spec, x,
                              caches[f"tail{i}"], pos)
        new_caches[f"tail{i}"] = nc
    return _lm_head(params, cfg, x), new_caches


def _lm_head(params, cfg: LMConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Shared head: final norm -> (un)tied unembed -> softcap ->
    true-vocab slice. Used by every cached-decode entry point (decode,
    prefill, the paged paths) so admission and decode sample from the same
    distribution family. prefill historically skipped logit_softcap —
    harmless for argmax (tanh is monotonic) but it biased first-token
    *temperature* sampling on softcap archs; unified here (no shipped
    config sets softcap > 0, so no behavior shift today)."""
    with jax.named_scope("head"):
        x = layers.rms_norm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = layers.unembed(params["embed"], x)
        else:
            logits = layers.apply_unembed(params["unembed"], x)
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return logits[..., :cfg.vocab]


# -----------------------------------------------------------------------------
# Paged KV cache (DESIGN.md §14): block pool + page-table indirection
# -----------------------------------------------------------------------------

def paged_supported(cfg: LMConfig) -> bool:
    """The paged path is attention-only (SSM states are not position-
    addressable) and replaces ring caches (pages are not reclaimed by
    window; window masking still applies)."""
    return (not cfg.ring_cache
            and all(sp.kind == "attn"
                    for sp in tuple(cfg.pattern) + tuple(cfg.tail)))


def init_paged_caches(cfg: LMConfig, num_pages: int, page_size: int,
                      dtype=jnp.bfloat16) -> Dict[str, PyTree]:
    """KV block pools: ``num_pages + 1`` pages of ``page_size`` tokens per
    attention layer (same pattern/tail tree shape as :func:`init_caches`,
    pool-major instead of slot-major). The extra page is the **sink** —
    writes from dead/padded lanes land there, so the host allocator can
    recycle pages without any device-side scrub. One page table (built by
    the serve engine) maps every layer's logical blocks to the same
    physical page ids, which is what makes block-granular prefix sharing a
    page-table copy instead of a per-layer KV copy.

    No position-tag array: the engine maintains the contiguous-prefix
    invariant (slot b's valid logical positions are exactly
    ``[0, len_b)`` through its page chain), so validity is ``pos < len``.
    Under ``cfg.quant.kv_int8`` pools hold int8 codes plus per-(page,
    offset, kv-head) fp32 scale pools, exactly mirroring the dense int8
    cache representation.
    """
    if not paged_supported(cfg):
        raise NotImplementedError(
            "paged KV caches are attention-only and incompatible with "
            "ring_cache; use init_caches for SSD/hybrid or ring archs")
    caches: Dict[str, PyTree] = {}
    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    kv_dtype = jnp.int8 if cfg.quant.kv_int8 else dtype
    p = num_pages + 1                              # +1: sink page

    def one(stacked: bool):
        shape = (cfg.repeats,) if stacked else ()
        kv = KVCache(
            k=jnp.zeros(shape + (p, page_size, kvh, dh), kv_dtype),
            v=jnp.zeros(shape + (p, page_size, kvh, dh), kv_dtype))
        if cfg.quant.kv_int8:
            sc = KVCache(
                k=jnp.zeros(shape + (p, page_size, kvh), jnp.float32),
                v=jnp.zeros(shape + (p, page_size, kvh), jnp.float32))
            return {"kv": kv, "kv_scale": sc}
        return {"kv": kv}

    for i, _ in enumerate(cfg.pattern):
        caches[f"pat{i}"] = one(stacked=True)
    for i, _ in enumerate(cfg.tail):
        caches[f"tail{i}"] = one(stacked=False)
    return caches


def _paged_gather(cache, page_table: jnp.ndarray, compute_dtype):
    """Gather a slot-major (B, NB*page_size, kvh, dh) K/V view through the
    page table (XLA fallback path; the Pallas kernel's index_map does this
    per-tile without materializing the view). Int8 pools dequantize at
    gather so attention sees exactly what the dense int8 path sees."""
    kv = cache["kv"]
    b, nb = page_table.shape
    ps = kv.k.shape[1]

    def flat(pool):
        g = pool[page_table]                       # (B, NB, ps, ...)
        return g.reshape((b, nb * ps) + g.shape[3:])

    k_all, v_all = flat(kv.k), flat(kv.v)
    if "kv_scale" in cache:
        from repro.quant import int8 as int8_lib
        sc = cache["kv_scale"]
        k_all = int8_lib.dequantize_rowwise(k_all, flat(sc.k),
                                            dtype=compute_dtype)
        v_all = int8_lib.dequantize_rowwise(v_all, flat(sc.v),
                                            dtype=compute_dtype)
    else:
        k_all = k_all.astype(compute_dtype)
        v_all = v_all.astype(compute_dtype)
    return k_all, v_all


def move_pages(caches: Dict[str, PyTree], src: jnp.ndarray,
               dst: jnp.ndarray) -> Dict[str, PyTree]:
    """Copy pool page ``src[i]`` -> ``dst[i]`` in every layer's K/V (and
    scale) pool — the device half of page-table compaction (DESIGN.md §16).
    ``src``/``dst`` are (M,) int32; padding entries may point both at the
    sink page (a sink->sink copy is the identity). The caller (serve
    engine) owns the host-side invariants: destinations are freshly
    allocated private pages, sources are released after the copy, and the
    slot's page-table row is rewritten in the same device call."""
    def per_key(key, sub):
        ax = 1 if key.startswith("pat") else 0

        def mv(pool):
            if ax == 0:
                return pool.at[dst].set(pool[src])
            return pool.at[:, dst].set(pool[:, src])

        return jax.tree.map(mv, sub)

    return {k: per_key(k, v) for k, v in caches.items()}


def cow_pages(caches: Dict[str, PyTree], page_table: jnp.ndarray,
              src: jnp.ndarray, dst: jnp.ndarray, slot_idx: jnp.ndarray,
              blk_idx: jnp.ndarray, entry: jnp.ndarray
              ) -> Tuple[Dict[str, PyTree], jnp.ndarray]:
    """Copy-on-write divergence, device half (DESIGN.md §18): duplicate
    pool pages ``src[i] -> dst[i]`` in every layer (``move_pages``) and
    redirect the forked slots' table entries ``page_table[slot_idx[i],
    blk_idx[i]] = entry[i]`` in the same call. All three index vectors are
    (M,) and sink/OOB-padded — a sink->sink copy is the identity and an
    out-of-bounds slot row drops — so one executable serves every event
    count. Retain-only redirects (the last co-owner adopting a page
    without a byte copy) pass ``src == dst == sink``; the engine bills
    only real copies as COW bytes."""
    caches = move_pages(caches, src, dst)
    pt = page_table.at[slot_idx, blk_idx].set(entry, mode="drop")
    return caches, pt


def _paged_decode_attn(p, cfg: LMConfig, spec: BlockSpec, x, cache,
                       pos: jnp.ndarray, page_table: jnp.ndarray,
                       active: jnp.ndarray):
    """One-token attention against the paged pool. ``pos`` is (B,) per-slot
    positions (the paged path is serve-engine-only, always batched);
    ``active`` routes dead lanes' writes to the sink page — their table
    entries may point at pages since recycled to other slots."""
    acfg = cfg.attn_cfg(spec.window)
    b = x.shape[0]
    kv = cache["kv"]
    kv_int8 = "kv_scale" in cache
    ps = kv.k.shape[1]
    nb = page_table.shape[1]
    sink = kv.k.shape[0] - 1
    positions = pos[:, None].astype(jnp.int32)                  # (B, 1)
    if cfg.pos_emb == "mrope":
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    q, k_new, v_new = layers._project_qkv(p["attn"], acfg, x, positions)
    rows = jnp.arange(b)
    blk = jnp.clip(pos // ps, 0, nb - 1).astype(jnp.int32)
    page = jnp.where(active, page_table[rows, blk], sink)
    off = (pos % ps).astype(jnp.int32)
    if kv_int8:
        from repro.quant import int8 as int8_lib
        sc = cache["kv_scale"]
        k_q, k_s = int8_lib.quantize_rowwise(k_new)     # (B,1,H,D),(B,1,H)
        v_q, v_s = int8_lib.quantize_rowwise(v_new)
        k = kv.k.at[page, off].set(k_q[:, 0])
        v = kv.v.at[page, off].set(v_q[:, 0])
        k_scale = sc.k.at[page, off].set(k_s[:, 0])
        v_scale = sc.v.at[page, off].set(v_s[:, 0])
        new_cache = {"kv": KVCache(k=k, v=v),
                     "kv_scale": KVCache(k=k_scale, v=v_scale)}
    else:
        k = kv.k.at[page, off].set(k_new[:, 0].astype(kv.k.dtype))
        v = kv.v.at[page, off].set(v_new[:, 0].astype(kv.v.dtype))
        new_cache = {"kv": KVCache(k=k, v=v)}
    lengths = (pos + 1).astype(jnp.int32)
    if cfg.decode_kernel:
        from repro.kernels import ops as kops
        out = kops.paged_decode_attention(
            q[:, 0], k, v, page_table, lengths, scale=acfg.scale,
            window=spec.window,
            k_scale=new_cache["kv_scale"].k if kv_int8 else None,
            v_scale=new_cache["kv_scale"].v if kv_int8 else None)[:, None]
    else:
        k_all, v_all = _paged_gather(new_cache, page_table, q.dtype)
        j_abs = jnp.arange(nb * ps, dtype=jnp.int32)[None]      # (1, W)
        tags = jnp.where(j_abs < lengths[:, None], j_abs, -1)
        q_pos = positions[..., 0] if positions.ndim == 3 else positions
        mask = layers.attention_mask(q_pos, tags, causal=True,
                                     window=spec.window)
        mask &= (tags >= 0)[:, None, :]
        out = layers.sdpa(q, k_all, v_all, mask, acfg.scale)
    if layers._q8_active(acfg, p["attn"]["wo"]):
        y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
    else:
        y = jnp.einsum("bshk,hkd->bsd", out,
                       layers.wl(p["attn"]["wo"], out.dtype))
    return y, new_cache


def _paged_verify_attn(p, cfg: LMConfig, spec: BlockSpec, x, cache,
                       pos: jnp.ndarray, page_table: jnp.ndarray,
                       active: jnp.ndarray):
    """Multi-query attention for speculative verification (DESIGN.md §15):
    a q-block of T tokens per slot — the committed pending token plus the
    drafts — written through the page table and attended causally.

    Every key, including the chunk's own tokens, is read back *through the
    storage dtype* (the gathered pool / the paged kernel), which is exactly
    what sequential ``paged_decode_step`` ticks would see — so lane t's
    logits match the plain single-token tick bit for bit and temp=0
    rejection sampling reproduces the plain stream. (``paged_extend``
    deliberately differs: it attends the in-flight chunk in full precision
    to match *prefill* numerics.)

    Writes of inactive lanes and of positions past the page-table capacity
    go to the sink page; rejected lanes need no cleanup at all — the
    engine simply does not advance ``pos`` past the accepted prefix, the
    ``pos < length`` validity invariant masks the stale writes, and the
    next tick overwrites them.
    """
    acfg = cfg.attn_cfg(spec.window)
    b, t = x.shape[:2]
    kv = cache["kv"]
    kv_int8 = "kv_scale" in cache
    ps = kv.k.shape[1]
    nb = page_table.shape[1]
    sink = kv.k.shape[0] - 1
    rel = jnp.arange(t, dtype=jnp.int32)[None]                  # (1, T)
    pos_abs = pos[:, None].astype(jnp.int32) + rel              # (B, T)
    positions = (jnp.broadcast_to(pos_abs[..., None], (b, t, 3))
                 if cfg.pos_emb == "mrope" else pos_abs)
    q, k_new, v_new = layers._project_qkv(p["attn"], acfg, x, positions)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
    blk = jnp.clip(pos_abs // ps, 0, nb - 1)
    writable = active[:, None] & (pos_abs < nb * ps)
    page = jnp.where(writable, page_table[rows, blk], sink)     # (B, T)
    off = pos_abs % ps
    if kv_int8:
        from repro.quant import int8 as int8_lib
        sc = cache["kv_scale"]
        k_q, k_s = int8_lib.quantize_rowwise(k_new)
        v_q, v_s = int8_lib.quantize_rowwise(v_new)
        new_cache = {
            "kv": KVCache(k=kv.k.at[page, off].set(k_q),
                          v=kv.v.at[page, off].set(v_q)),
            "kv_scale": KVCache(k=sc.k.at[page, off].set(k_s),
                                v=sc.v.at[page, off].set(v_s))}
    else:
        new_cache = {"kv": KVCache(
            k=kv.k.at[page, off].set(k_new.astype(kv.k.dtype)),
            v=kv.v.at[page, off].set(v_new.astype(kv.v.dtype)))}
    # total valid length per slot INCLUDING the chunk; lanes clipped to the
    # sink (pos_abs >= nb*ps, only possible at the max_len edge) simply
    # have no key to attend — the engine never emits from those lanes
    lengths = (pos + t).astype(jnp.int32)
    if cfg.decode_kernel:
        from repro.kernels import ops as kops
        out = kops.paged_verify_attention(
            q, new_cache["kv"].k, new_cache["kv"].v, page_table, lengths,
            scale=acfg.scale, window=spec.window,
            k_scale=new_cache["kv_scale"].k if kv_int8 else None,
            v_scale=new_cache["kv_scale"].v if kv_int8 else None)
    else:
        k_all, v_all = _paged_gather(new_cache, page_table, q.dtype)
        j_abs = jnp.arange(nb * ps, dtype=jnp.int32)[None]      # (1, W)
        tags = jnp.where(j_abs < lengths[:, None], j_abs, -1)
        mask = layers.attention_mask(pos_abs, tags, causal=True,
                                     window=spec.window)
        mask &= (tags >= 0)[:, None, :]
        out = layers.sdpa(q, k_all, v_all, mask, acfg.scale)
    if layers._q8_active(acfg, p["attn"]["wo"]):
        y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
    else:
        y = jnp.einsum("bshk,hkd->bsd", out,
                       layers.wl(p["attn"]["wo"], out.dtype))
    return y, new_cache


def _paged_decode_block(params, shared_params, cfg: LMConfig,
                        spec: BlockSpec, x, cache, pos, page_table, active,
                        attn=_paged_decode_attn):
    p = shared_params if spec.shared_attn else params
    with jax.named_scope("attn"):
        h = layers.rms_norm(p["norm_attn"], x)
        y, cache = attn(p, cfg, spec, h, cache, pos, page_table, active)
        x = x + y
    if spec.shared_attn:
        with jax.named_scope("mlp"):
            h = layers.rms_norm(p["norm_ffn"], x)
            return x + layers.mlp(p["mlp"], h, cfg.act,
                                  int8_kernel=cfg.use_int8_matmul), cache
    if spec.has_ffn:
        with jax.named_scope("mlp"):
            h = layers.rms_norm(params["norm_ffn"], x)
            if spec.moe:
                y, _ = moe_lib.moe_capacity(
                    params["moe"], cfg.moe_cfg, h,
                    group_size=h.shape[0] * h.shape[1])
                x = x + y
            else:
                x = x + layers.mlp(params["mlp"], h, cfg.act,
                                   int8_kernel=cfg.use_int8_matmul)
    return x, cache


def paged_decode_step(params, cfg: LMConfig, token: jnp.ndarray,
                      pos: jnp.ndarray, page_table: jnp.ndarray,
                      caches: Dict[str, PyTree],
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """One decode step against the paged pools. token (B,1) int32, pos (B,)
    per-slot positions, page_table (B, NB) -> (logits (B,1,V), caches).

    ``active`` (B,) bool: lanes that are really decoding. Inactive lanes
    still flow through the batch (the engine tick is one fused call) but
    their K/V writes are routed to the sink page — their page-table rows
    may reference pages that have been recycled to other slots.
    """
    if active is None:
        active = jnp.ones(token.shape[0], bool)
    x = layers.embed(params["embed"], token)
    shared = params.get("shared_attn")
    pat_caches = {f"pat{i}": caches[f"pat{i}"]
                  for i in range(len(cfg.pattern))}

    def body(x, inp):
        pat_params, pat_cache = inp
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = _paged_decode_block(pat_params.get(f"pat{i}"), shared,
                                        cfg, spec, x, pat_cache[f"pat{i}"],
                                        pos, page_table, active)
            new_cache[f"pat{i}"] = nc
        return x, new_cache

    new_caches: Dict[str, PyTree] = {}
    if cfg.repeats > 0:
        x, new_pat = jax.lax.scan(
            body, x, (_pattern_stack_params(params, cfg), pat_caches))
        new_caches.update(new_pat)
    for i, spec in enumerate(cfg.tail):
        x, nc = _paged_decode_block(params.get(f"tail{i}"), shared, cfg,
                                    spec, x, caches[f"tail{i}"], pos,
                                    page_table, active)
        new_caches[f"tail{i}"] = nc
    return _lm_head(params, cfg, x), new_caches


def paged_verify_step(params, cfg: LMConfig, tokens: jnp.ndarray,
                      pos: jnp.ndarray, page_table: jnp.ndarray,
                      caches: Dict[str, PyTree],
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """Speculative verification step (DESIGN.md §15): score a q-block of T
    tokens per slot in ONE forward pass against the paged pools.

    tokens (B, T) int32 — per slot, the committed pending token followed by
    T-1 drafted tokens; pos (B,) — cache length before the tick (token t
    lands at logical position ``pos + t``). Returns (logits (B, T, V),
    caches): logits row t is the target distribution for the token *after*
    ``tokens[:, :t+1]`` — draft t (``tokens[:, t]``, t >= 1) is accepted
    against row t-1 (serve/spec.speculative_accept), and row T-1 supplies
    the bonus token. All T lanes' K/V are written through the
    page table (rejection rolls back by rewinding ``pos``, never by
    scrubbing); attention is causal over the slot's whole chain *through
    the storage dtype*, matching sequential ``paged_decode_step`` numerics
    exactly — at temperature 0 the accepted stream is the plain paged
    stream, token for token.

    Lane-coupled blocks (MoE capacity routing) make verify numerics
    batch-dependent; parity there is measured, not structural (the test
    matrix covers dense-FFN archs).
    """
    if active is None:
        active = jnp.ones(tokens.shape[0], bool)
    x = layers.embed(params["embed"], tokens)
    shared = params.get("shared_attn")
    pat_caches = {f"pat{i}": caches[f"pat{i}"]
                  for i in range(len(cfg.pattern))}

    def body(x, inp):
        pat_params, pat_cache = inp
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = _paged_decode_block(pat_params.get(f"pat{i}"), shared,
                                        cfg, spec, x, pat_cache[f"pat{i}"],
                                        pos, page_table, active,
                                        attn=_paged_verify_attn)
            new_cache[f"pat{i}"] = nc
        return x, new_cache

    new_caches: Dict[str, PyTree] = {}
    if cfg.repeats > 0:
        x, new_pat = jax.lax.scan(
            body, x, (_pattern_stack_params(params, cfg), pat_caches))
        new_caches.update(new_pat)
    for i, spec in enumerate(cfg.tail):
        x, nc = _paged_decode_block(params.get(f"tail{i}"), shared, cfg,
                                    spec, x, caches[f"tail{i}"], pos,
                                    page_table, active,
                                    attn=_paged_verify_attn)
        new_caches[f"tail{i}"] = nc
    return _lm_head(params, cfg, x), new_caches


def paged_extend(params, cfg: LMConfig, tokens: jnp.ndarray,
                 starts: jnp.ndarray, lens: jnp.ndarray,
                 page_table: jnp.ndarray, caches: Dict[str, PyTree]
                 ) -> Tuple[jnp.ndarray, Dict[str, PyTree]]:
    """Extend-prefill: run a chunk of prompt tokens against pre-populated
    paged caches. The single primitive behind suffix-after-prefix-hit
    admission AND chunked prefill (DESIGN.md §14).

    tokens: (B, C) right-padded chunk per row; starts: (B,) absolute
    position of each row's first chunk token (0 = plain prefill;
    ``shared_len`` after a prefix-cache hit; ``k*chunk`` mid-chunking);
    lens: (B,) valid tokens per row this call (0 = dead row — its writes
    go to the sink page). Chunk K/V is written into the row's pages, then
    each chunk query attends over the gathered cache window [0, start)
    **plus the chunk itself in full precision** — exactly the dense
    prefill's numerics for the in-chunk part and the dense decode's
    (storage-dtype round-tripped) numerics for the cached part.

    Returns per-row logits at the chunk's last valid token, (B, 1, V) —
    meaningful only for rows whose prompt ends in this chunk.
    """
    b, c = tokens.shape
    nb = page_table.shape[1]
    x = layers.embed(params["embed"], tokens)
    rel = jnp.arange(c, dtype=jnp.int32)[None]                  # (1, C)
    valid = rel < lens[:, None]                                 # (B, C)
    pos_abs = starts[:, None].astype(jnp.int32) + rel           # (B, C)
    shared = params.get("shared_attn")

    def fill_attn(p, spec, x, cache):
        acfg = cfg.attn_cfg(spec.window)
        kv = cache["kv"]
        kv_int8 = "kv_scale" in cache
        ps = kv.k.shape[1]
        sink = kv.k.shape[0] - 1
        w = nb * ps
        positions = (jnp.broadcast_to(pos_abs[..., None], (b, c, 3))
                     if cfg.pos_emb == "mrope" else pos_abs)
        h = layers.rms_norm(p["norm_attn"], x)
        q, k_new, v_new = layers._project_qkv(p["attn"], acfg, h, positions)
        # scatter the chunk's K/V into the rows' pages (invalid lanes ->
        # sink); rope-rotated K is what lands in HBM, same as prefill
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
        blk = jnp.clip(pos_abs // ps, 0, nb - 1)
        page = jnp.where(valid, page_table[rows, blk], sink)    # (B, C)
        off = pos_abs % ps
        if kv_int8:
            from repro.quant import int8 as int8_lib
            sc = cache["kv_scale"]
            k_st, k_sc = int8_lib.quantize_rowwise(k_new)
            v_st, v_sc = int8_lib.quantize_rowwise(v_new)
            kc = kv.k.at[page, off].set(k_st)
            vc = kv.v.at[page, off].set(v_st)
            new_cache = {
                "kv": KVCache(k=kc, v=vc),
                "kv_scale": KVCache(k=sc.k.at[page, off].set(k_sc),
                                    v=sc.v.at[page, off].set(v_sc))}
        else:
            kc = kv.k.at[page, off].set(k_new.astype(kv.k.dtype))
            vc = kv.v.at[page, off].set(v_new.astype(kv.v.dtype))
            new_cache = {"kv": KVCache(k=kc, v=vc)}
        # attend over the cached window [0, start) plus the chunk itself in
        # full precision (dense-prefill numerics for the in-chunk part, the
        # dense decode's storage-dtype numerics for the cached part).
        # Kernel path (DESIGN.md §16): the page table rides in scalar-
        # prefetch SMEM and each K/V tile is DMA'd straight from its pool
        # page — per-row gather traffic is ceil(start/ps) pages instead of
        # the XLA fallback's whole-window materialization.
        if cfg.decode_kernel:
            from repro.kernels import ops as kops
            out = kops.paged_prefill_attention(
                q, k_new.astype(q.dtype), v_new.astype(q.dtype),
                new_cache["kv"].k, new_cache["kv"].v, page_table,
                starts, lens, scale=acfg.scale, window=spec.window,
                k_scale=new_cache["kv_scale"].k if kv_int8 else None,
                v_scale=new_cache["kv_scale"].v if kv_int8 else None)
            if layers._q8_active(acfg, p["attn"]["wo"]):
                y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
            else:
                y = jnp.einsum("bshk,hkd->bsd", out,
                               layers.wl(p["attn"]["wo"], out.dtype))
            return x + y, new_cache
        k_all, v_all = _paged_gather(new_cache, page_table, q.dtype)
        j_abs = jnp.arange(w, dtype=jnp.int32)[None]            # (1, W)
        rel_w = j_abs - starts[:, None]                         # (B, W)
        in_chunk = (rel_w >= 0) & (rel_w < lens[:, None])
        idx = jnp.clip(rel_w, 0, c - 1)
        k_att = jnp.where(in_chunk[..., None, None],
                          jnp.take_along_axis(k_new.astype(q.dtype),
                                              idx[..., None, None], axis=1),
                          k_all)
        v_att = jnp.where(in_chunk[..., None, None],
                          jnp.take_along_axis(v_new.astype(q.dtype),
                                              idx[..., None, None], axis=1),
                          v_all)
        tags = jnp.where(j_abs < (starts + lens)[:, None], j_abs, -1)
        mask = layers.attention_mask(pos_abs, tags, causal=True,
                                     window=spec.window)
        mask &= (tags >= 0)[:, None, :]
        out = layers.sdpa(q, k_att, v_att, mask, acfg.scale)
        if layers._q8_active(acfg, p["attn"]["wo"]):
            y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
        else:
            y = jnp.einsum("bshk,hkd->bsd", out,
                           layers.wl(p["attn"]["wo"], out.dtype))
        return x + y, new_cache

    def fill_block(p, spec, x, cache):
        pp = shared if spec.shared_attn else p
        with jax.named_scope("attn"):
            x, cache = fill_attn(pp, spec, x, cache)
        if spec.shared_attn:
            with jax.named_scope("mlp"):
                h = layers.rms_norm(pp["norm_ffn"], x)
                return x + layers.mlp(pp["mlp"], h, cfg.act,
                                      int8_kernel=cfg.use_int8_matmul), cache
        if spec.has_ffn:
            with jax.named_scope("mlp"):
                h = layers.rms_norm(p["norm_ffn"], x)
                if spec.moe:
                    y, _ = moe_lib.moe_capacity(p["moe"], cfg.moe_cfg, h,
                                                cfg.moe_group_size)
                    x = x + y
                else:
                    x = x + layers.mlp(p["mlp"], h, cfg.act,
                                       int8_kernel=cfg.use_int8_matmul)
        return x, cache

    def body(x, inp):
        pat_params, pat_cache = inp
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = fill_block(pat_params.get(f"pat{i}"), spec, x,
                               pat_cache[f"pat{i}"])
            new_cache[f"pat{i}"] = nc
        return x, new_cache

    pat_caches = {f"pat{i}": caches[f"pat{i}"]
                  for i in range(len(cfg.pattern))}
    new_caches: Dict[str, PyTree] = {}
    if cfg.repeats > 0:
        x, new_pat = jax.lax.scan(
            body, x, (_pattern_stack_params(params, cfg), pat_caches))
        new_caches.update(new_pat)
    for i, spec in enumerate(cfg.tail):
        x, nc = fill_block(params.get(f"tail{i}"), spec, x,
                           caches[f"tail{i}"])
        new_caches[f"tail{i}"] = nc
    # per-row last valid chunk token (rows are right-padded to C)
    idx = jnp.clip(lens - 1, 0, c - 1).astype(jnp.int32)[:, None, None]
    x_last = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    return _lm_head(params, cfg, x_last), new_caches


def caches_axes(cfg: LMConfig) -> Dict[str, PyTree]:
    """Logical-axes tree mirroring init_caches (dataclass fields as dicts —
    the form parallel.sharding._tree_map2 consumes)."""
    def one(spec: BlockSpec, stacked: bool):
        pre = ("stack",) if stacked else ()
        if spec.kind == "attn":
            kv_ax = pre + ("batch", "seq", "kv_heads", "head_dim")
            out = {"kv": {"k": kv_ax, "v": kv_ax},
                   "pos": pre + ("batch", "seq")}
            if cfg.quant.kv_int8:
                sc_ax = pre + ("batch", "seq", "kv_heads")
                out["kv_scale"] = {"k": sc_ax, "v": sc_ax}
            return out
        st = {"conv_x": ("batch", "conv", "heads", "head_dim"),
              "conv_b": ("batch", "conv", "ssm_group", "ssm_state"),
              "conv_c": ("batch", "conv", "ssm_group", "ssm_state"),
              "ssm": ("batch", "heads", "ssm_state", "head_dim")}
        if stacked:
            st = {k: ("stack",) + v for k, v in st.items()}
        return {"ssd": st}

    out: Dict[str, PyTree] = {}
    for i, spec in enumerate(cfg.pattern):
        out[f"pat{i}"] = one(spec, stacked=True)
    for i, spec in enumerate(cfg.tail):
        out[f"tail{i}"] = one(spec, stacked=False)
    return out


# -----------------------------------------------------------------------------
# Prefill: forward + cache construction
# -----------------------------------------------------------------------------

def prefill(params, cfg: LMConfig, tokens: jnp.ndarray,
            max_len: Optional[int] = None,
            vision_embeds: Optional[jnp.ndarray] = None,
            cache_dtype=jnp.bfloat16,
            lengths: Optional[jnp.ndarray] = None):
    """Process a prompt, returning (last-token logits, filled caches).

    Implemented as full-sequence forward per block, materializing K/V into
    decode caches (sized ``max_len``, default prompt length).

    ``lengths`` (B,) int32 enables padded multi-prompt prefill: rows are
    right-padded to a shared length S, logits are taken at ``lengths - 1``
    per row, and cache position tags past each row's true length are
    invalidated (-1) so decode masks the padding. Causality guarantees the
    tokens before each row's length are unaffected by its padding.
    """
    b, s = tokens.shape
    max_len = max_len or s
    if lengths is not None and any(
            sp.kind == "ssd" for sp in tuple(cfg.pattern) + tuple(cfg.tail)):
        # SSM states integrate over the padded steps — padded prefill would
        # corrupt short rows. The scheduler groups equal-length prompts for
        # SSD/hybrid archs instead.
        raise NotImplementedError("padded prefill is attention-only; "
                                  "group equal-length prompts for SSD archs")
    caches = init_caches(cfg, b, max_len, cache_dtype)
    x = layers.embed(params["embed"], tokens)
    if vision_embeds is not None and cfg.vision_tokens > 0:
        x = jax.lax.dynamic_update_slice(x, vision_embeds.astype(x.dtype), (0, 0, 0))
    pos1d = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    positions = (jnp.broadcast_to(pos1d[..., None], (b, s, 3))
                 if cfg.pos_emb == "mrope" else pos1d)
    shared = params.get("shared_attn")

    def fill_attn(p, spec, x, cache):
        acfg = cfg.attn_cfg(spec.window)
        h = layers.rms_norm(p["norm_attn"], x)
        q, k, v = layers._project_qkv(p["attn"], acfg, h, positions)
        if s > layers._CHUNKED_SDPA_THRESHOLD:
            out = layers.sdpa_q_chunked(q, k, v, pos1d, pos1d, causal=True,
                                        window=spec.window, scale=acfg.scale)
        else:
            mask = layers.attention_mask(pos1d, pos1d, causal=True,
                                         window=spec.window)
            out = layers.sdpa(q, k, v, mask, acfg.scale)
        if layers._q8_active(acfg, p["attn"]["wo"]):
            y = layers.q8_matmul(out, p["attn"]["wo"], contract_ndim=2)
        else:
            y = jnp.einsum("bshk,hkd->bsd", out,
                           layers.wl(p["attn"]["wo"], out.dtype))
        kv, pos_tags = cache["kv"], cache["pos"]
        kv_int8 = "kv_scale" in cache
        clen = kv.k.shape[1]
        bsz = x.shape[0]
        if kv_int8:
            # prompt K/V enter the cache quantized: attention above used the
            # full-precision activations (registers/VMEM), but what lands in
            # HBM is int8 codes + per-(row, position, head) fp32 scales —
            # the same representation decode appends (DESIGN.md §12)
            from repro.quant import int8 as int8_lib
            k_st, k_sc = int8_lib.quantize_rowwise(k)    # (B,S,H,D),(B,S,H)
            v_st, v_sc = int8_lib.quantize_rowwise(v)
        else:
            k_st, v_st = k, v
        if clen >= s:
            kc = jax.lax.dynamic_update_slice(kv.k, k_st.astype(kv.k.dtype),
                                              (0, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(kv.v, v_st.astype(kv.v.dtype),
                                              (0, 0, 0, 0))
            if kv_int8:
                ksc = jax.lax.dynamic_update_slice(
                    cache["kv_scale"].k, k_sc, (0, 0, 0))
                vsc = jax.lax.dynamic_update_slice(
                    cache["kv_scale"].v, v_sc, (0, 0, 0))
            ptags = jax.lax.dynamic_update_slice(
                pos_tags,
                jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (bsz, s)),
                (0, 0))
        else:  # ring: keep the last clen positions
            kc = k_st[:, s - clen:].astype(kv.k.dtype)
            vc = v_st[:, s - clen:].astype(kv.v.dtype)
            ptags1 = jnp.arange(s - clen, s, dtype=jnp.int32)
            # rotate so that slot j holds the position with pos % clen == j
            roll = (s - clen) % clen
            kc, vc = jnp.roll(kc, roll, 1), jnp.roll(vc, roll, 1)
            if kv_int8:
                ksc = jnp.roll(k_sc[:, s - clen:], roll, 1)
                vsc = jnp.roll(v_sc[:, s - clen:], roll, 1)
            ptags = jnp.broadcast_to(jnp.roll(ptags1, roll, 0)[None], (bsz, clen))
        if lengths is not None:
            # invalidate tags past each row's true length — decode masks
            # padded K/V by tag, so the garbage rows are never attended
            ptags = jnp.where(ptags < lengths[:, None], ptags, -1)
        new_cache = {"kv": KVCache(k=kc, v=vc), "pos": ptags}
        if kv_int8:
            new_cache["kv_scale"] = KVCache(k=ksc, v=vsc)
        return x + y, new_cache

    def fill_block(p, spec, x, cache):
        if spec.kind == "attn":
            pp = shared if spec.shared_attn else p
            x, cache = fill_attn(pp, spec, x, cache)
            if spec.shared_attn:
                h = layers.rms_norm(pp["norm_ffn"], x)
                return x + layers.mlp(pp["mlp"], h, cfg.act,
                                      int8_kernel=cfg.use_int8_matmul), cache
        else:
            h = layers.rms_norm(p["norm_ssd"], x)
            scfg = cfg.ssd_cfg
            z, xin, b_raw, c_raw, dt_raw = ssd_lib._projections(p["ssd"], scfg, h)
            # conv states carry the last d_conv-1 *pre-activation* inputs
            conv_x_state = xin[:, -(scfg.d_conv - 1):]
            conv_b_state = b_raw[:, -(scfg.d_conv - 1):]
            conv_c_state = c_raw[:, -(scfg.d_conv - 1):]
            xin_c = jax.nn.silu(ssd_lib._causal_dwconv(xin, p["ssd"]["conv_x"]))
            b_c = jax.nn.silu(ssd_lib._causal_dwconv(b_raw, p["ssd"]["conv_b"]))
            c_c = jax.nn.silu(ssd_lib._causal_dwconv(c_raw, p["ssd"]["conv_c"]))
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["ssd"]["dt_bias"])
            a = -jnp.exp(p["ssd"]["a_log"])
            bm = ssd_lib._expand_groups(b_c, scfg.n_heads).astype(jnp.float32)
            cm = ssd_lib._expand_groups(c_c, scfg.n_heads).astype(jnp.float32)
            y, final = ssd_lib.ssd_chunked(xin_c.astype(jnp.float32), dt, a, bm, cm,
                                           scfg.chunk)
            x = x + ssd_lib._finish(p["ssd"], scfg, y, xin_c, z)
            st = ssd_lib.SSDState(conv_x=conv_x_state.astype(cache["ssd"].conv_x.dtype),
                                  conv_b=conv_b_state.astype(cache["ssd"].conv_b.dtype),
                                  conv_c=conv_c_state.astype(cache["ssd"].conv_c.dtype),
                                  ssm=final)
            cache = {"ssd": st}
        if spec.kind == "attn" and spec.has_ffn and not spec.shared_attn:
            h = layers.rms_norm(p["norm_ffn"], x)
            if spec.moe:
                y, _ = moe_lib.moe_capacity(p["moe"], cfg.moe_cfg, h,
                                            cfg.moe_group_size)
                x = x + y
            else:
                x = x + layers.mlp(p["mlp"], h, cfg.act,
                               int8_kernel=cfg.use_int8_matmul)
        return x, cache

    def body(x, inp):
        pat_params, pat_cache = inp
        new_cache = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = fill_block(pat_params.get(f"pat{i}") if not spec.shared_attn
                               else None, spec, x, pat_cache[f"pat{i}"])
            new_cache[f"pat{i}"] = nc
        return x, new_cache

    pat_caches = {f"pat{i}": caches[f"pat{i}"] for i in range(len(cfg.pattern))}
    new_caches: Dict[str, PyTree] = {}
    if cfg.repeats > 0:
        # no remat: prefill is inference (no gradient tape to save)
        x, new_pat = jax.lax.scan(body, x,
                                  (_pattern_stack_params(params, cfg), pat_caches))
        new_caches.update(new_pat)
    for i, spec in enumerate(cfg.tail):
        x, nc = fill_block(params.get(f"tail{i}"), spec, x, caches[f"tail{i}"])
        new_caches[f"tail{i}"] = nc
    if lengths is not None:
        # per-row last real token (rows are right-padded to a shared S)
        idx = (lengths - 1).astype(jnp.int32)[:, None, None]
        x_last = jnp.take_along_axis(x, jnp.broadcast_to(
            idx, (b, 1, x.shape[-1])), axis=1)
    else:
        x_last = x[:, -1:]
    return _lm_head(params, cfg, x_last), new_caches
