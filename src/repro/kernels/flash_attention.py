"""Flash attention (online-softmax) Pallas TPU kernel.

The serving/long-context hot spot: tiled attention with O(bq*bk) VMEM working
set instead of O(Sq*Sk) HBM traffic. Supports causal masking, sliding windows
(gemma3's local layers), and GQA via the kv-head index map (no K/V
replication in memory).

Grid: (batch, q_heads, Sq/bq, Sk/bk) with the K sweep innermost; running
max/denominator/accumulator live in VMEM scratch. Block sizes MXU/VPU-aligned
(128 lanes).

Validated in interpret mode against ref.attention_ref across a shape/dtype/
mask sweep (tests/test_kernels_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _tile_mask(qi, ki, block_q: int, block_k: int, causal: bool, window: int):
    """Valid-position mask for one (qi, ki) tile — THE masking rule, shared
    by the forward and both backward kernels so the semantics cannot drift."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    diff = q_pos - k_pos
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= diff >= 0
    if window > 0:
        mask &= diff < window
    return mask


def _kv_block_range(qi, block_q: int, block_k: int, n_k_blocks: int,
                    causal: bool, window: int):
    """First and last K block that query block ``qi`` needs under
    :func:`_tile_mask`'s rule (scalars, for the index maps)."""
    first, last = 0, n_k_blocks - 1
    if causal:
        last = jnp.minimum(last, ((qi + 1) * block_q - 1) // block_k)
    if window > 0:
        first = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
    return first, last


def _q_block_range(ki, block_q: int, block_k: int, n_q_blocks: int,
                   causal: bool, window: int):
    """First and last query block that K block ``ki`` is needed by."""
    first, last = 0, n_q_blocks - 1
    if causal:
        first = (ki * block_k) // block_q
    if window > 0:
        last = jnp.minimum(last, ((ki + 1) * block_k + window - 2) // block_q)
    return first, last


def _clamp_block(i, first, last):
    """Steps outside [first, last] re-map to the nearest needed block: the
    pipeline sees the index of the step before (or after) and issues no DMA
    for the fully masked tiles, whose compute ``_tile_mask`` skips."""
    if isinstance(first, int) and isinstance(last, int):
        return i                   # no mask limit: every block is needed
    return jnp.minimum(jnp.maximum(i, first), last)


def _dot_nt(a, b):
    """a @ b.T on the MXU in the operands' storage dtype, fp32 result: a
    bf16 x bf16 product is exact in fp32, so no fp32 copies of the tiles."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b, fp32 operands and result."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_kernel(*refs, scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, n_k_blocks: int,
                  quantized: bool = False, save_lse: bool = False):
    lse_ref = None
    if quantized:
        q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    elif save_lse:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mask = _tile_mask(qi, ki, block_q, block_k, causal, window)

    # skip fully-masked K blocks (the causal upper triangle / outside-window)
    @pl.when(jnp.any(mask))
    def _compute():
        if quantized:
            # int8-KV fast path: per-row fp32 scale applied in-register,
            # right after the narrow HBM->VMEM DMA (DESIGN.md §12)
            q = q_ref[0, 0].astype(jnp.float32) * scale     # (bq, d)
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            # scale the fp32 score, not a storage-dtype operand
            s = _dot_nt(q_ref[0, 0], k_ref[0, 0]) * scale   # (bq, bk)
            v = v_ref[0, 0].astype(jnp.float32)             # (bk, d)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                              # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if save_lse:
            # per-row softmax normalizer, the residual the backward kernels
            # recompute p = exp(s - lse) from (no O(Sq*Sk) probs in memory)
            lse_ref[0, 0] = m_ref[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: float, causal: bool = True, window: int = -1,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    k_scale=None, v_scale=None) -> jnp.ndarray:
    """q: (B, Sq, H, D), k/v: (B, Sk, Hkv, D) with H % Hkv == 0.

    ``k_scale``/``v_scale`` (B, Sk, Hkv) fp32 switch on int8-KV mode: k/v
    are int8 codes dequantized tile-by-tile inside the kernel body (pass
    both or neither) — full-precision K/V never round-trip through memory.

    Sq % block_q == 0 and Sk % block_k == 0 (ops.py pads otherwise).
    Returns (B, Sq, H, D) in q.dtype.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "pass both scales or neither"
    nq, nk = sq // block_q, sk // block_k

    # layout: heads-major so each (b, h) pair owns contiguous seq blocks
    qt = q.transpose(0, 2, 1, 3)       # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)       # (B, Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3)

    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        kv_spec,
    ]
    operands = [qt, kt]
    if quantized:
        sc_spec = pl.BlockSpec(
            (1, 1, block_k, 1),
            lambda bi, hi, qi, ki, rep=rep: (bi, hi // rep, ki, 0))
        kst = k_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
        vst = v_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
        in_specs += [sc_spec, kv_spec, sc_spec]
        operands += [kst, vt, vst]
    else:
        in_specs += [kv_spec]
        operands += [vt]

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_k_blocks=nk, quantized=quantized),
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Training fast path: custom VJP (DESIGN.md §13)
#
# Forward saves only O and the per-row softmax normalizer lse = m + log(l)
# (B, H, Sq, 1) — the backward kernels recompute p = exp(s - lse) tile by
# tile, so the O(Sq*Sk) probability matrix never exists in memory:
#
#   delta = rowsum(dO * O)                       (cheap jnp preprocess)
#   dV    = p^T @ dO
#   dS    = p * (dO @ V^T - delta)
#   dQ    = scale * dS @ K ;  dK = scale * dS^T @ Q
#
# Two kernels: dQ sweeps K blocks innermost (grid b,h,nq,nk; dq tile
# accumulates in VMEM scratch), dK/dV sweep Q blocks innermost (grid
# b,h,nk,nq; dk/dv tiles in scratch). Both skip fully-masked tiles exactly
# like the forward, and all three clamp the swept operand's block index to
# the blocks the mask needs, so masked steps fetch nothing. q, k, v and dO
# reach the MXU in their storage dtype; p and dS stay fp32. GQA: dK/dV come
# out per *query* head and are sum-reduced over the head group outside the
# kernel (fp32).
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, scale: float, causal: bool,
                         window: int, block_q: int, block_k: int,
                         n_k_blocks: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    mask = _tile_mask(qi, ki, block_q, block_k, causal, window)

    @pl.when(jnp.any(mask))
    def _compute():
        s = _dot_nt(q_ref[0, 0], k_ref[0, 0]) * scale      # (bq, bk)
        # explicit mask (not just s=NEG_INF): rows whose every block is
        # masked have lse ~ NEG_INF and exp(s - lse) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        dp = _dot_nt(do_ref[0, 0], v_ref[0, 0])             # (bq, bk)
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] += jax.lax.dot(ds, k_ref[0, 0].astype(jnp.float32),
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                          causal: bool, window: int, block_q: int,
                          block_k: int, n_q_blocks: int):
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    mask = _tile_mask(qi, ki, block_q, block_k, causal, window)

    @pl.when(jnp.any(mask))
    def _compute():
        s = _dot_nt(q_ref[0, 0], k_ref[0, 0]) * scale      # (bq, bk)
        p = jnp.where(mask, jnp.exp(s - lse_ref[0, 0]), 0.0)
        dv_acc[...] += _dot_tn(p, do_ref[0, 0].astype(jnp.float32))
        dp = _dot_nt(do_ref[0, 0], v_ref[0, 0])             # (bq, bk)
        ds = p * (dp - delta_ref[0, 0])
        dk_acc[...] += _dot_tn(ds, q_ref[0, 0].astype(jnp.float32))

    @pl.when(qi == n_q_blocks - 1)
    def _finish():
        # fp32 out: the GQA head-group sum happens outside the kernel;
        # dK = scale * dS^T @ Q, scaled once here
        dk_ref[0, 0] = dk_acc[...] * scale
        dv_ref[0, 0] = dv_acc[...]


def _kv_sweep_spec(block_q, block_k, d, nk, rep, causal, window):
    """K/V block spec of a (b, h, qi, ki) grid: the kv head of query head
    ``hi``, K block ``ki`` clamped to the ones query block ``qi`` needs."""
    def index(bi, hi, qi, ki):
        first, last = _kv_block_range(qi, block_q, block_k, nk, causal,
                                      window)
        return bi, hi // rep, _clamp_block(ki, first, last), 0
    return pl.BlockSpec((1, 1, block_k, d), index)


def _fwd_with_lse(q, k, v, statics):
    """Forward pass that also returns the per-row lse residual (B,H,Sq,1)."""
    scale, causal, window, block_q, block_k, interpret = statics
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kv_spec = _kv_sweep_spec(block_q, block_k, d, nk, rep, causal, window)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_k_blocks=nk, save_lse=True),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_vjp(q, k, v, statics):
    """Differentiable flash attention. ``statics`` is the hashable tuple
    (scale, causal, window, block_q, block_k, interpret); shapes must be
    block multiples (ops.flash_attention_train pads)."""
    out, _ = _fwd_with_lse(q, k, v, statics)
    return out


def _flash_vjp_fwd(q, k, v, statics):
    out, lse = _fwd_with_lse(q, k, v, statics)
    return out, (q, k, v, out, lse)


def _bwd_dq(qt, kt, vt, dot, lse, delta, *, scale, causal, window, block_q,
            block_k, rep, interpret):
    """dQ (B, H, Sq, D) in q's dtype; grid (b, h, nq, nk)."""
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    nq, nk = sq // block_q, sk // block_k
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = _kv_sweep_spec(block_q, block_k, d, nk, rep, causal, window)
    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_k_blocks=nk),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)


def _bwd_dkv(qt, kt, vt, dot, lse, delta, *, scale, causal, window, block_q,
             block_k, rep, interpret):
    """Per-query-head fp32 dK and dV (B, H, Sk, D); grid (b, h, nk, nq):
    K blocks outer, Q sweep innermost, clamped to the Q blocks that K block
    ``ki`` is needed by."""
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    nq, nk = sq // block_q, sk // block_k

    def q_index(bi, hi, ki, qi):
        first, last = _q_block_range(ki, block_q, block_k, nq, causal,
                                     window)
        return bi, hi, _clamp_block(qi, first, last), 0

    q_spec = pl.BlockSpec((1, 1, block_q, d), q_index)
    row_spec = pl.BlockSpec((1, 1, block_q, 1), q_index)
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda bi, hi, ki, qi: (bi, hi // rep, ki, 0))
    kv_out_spec = pl.BlockSpec((1, 1, block_k, d),
                               lambda bi, hi, ki, qi: (bi, hi, ki, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          n_q_blocks=nq),
        grid=(b, h, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)


def _flash_vjp_bwd(statics, res, dout):
    scale, causal, window, block_q, block_k, interpret = statics
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = dout.transpose(0, 2, 1, 3)
    delta = jnp.sum(dot.astype(jnp.float32)
                    * out.transpose(0, 2, 1, 3).astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B, H, Sq, 1)
    kw = dict(scale=scale, causal=causal, window=window, block_q=block_q,
              block_k=block_k, rep=rep, interpret=interpret)
    dq = _bwd_dq(qt, kt, vt, dot, lse, delta, **kw)
    dkh, dvh = _bwd_dkv(qt, kt, vt, dot, lse, delta, **kw)
    # GQA: per-query-head dK/dV sum over the head group (fp32), then layout
    # back to (B, Sk, Hkv, D)
    dk = dkh.reshape(b, hkv, rep, sk, d).sum(axis=2)
    dv = dvh.reshape(b, hkv, rep, sk, d).sum(axis=2)
    return (dq.transpose(0, 2, 1, 3),
            dk.transpose(0, 2, 1, 3).astype(k.dtype),
            dv.transpose(0, 2, 1, 3).astype(v.dtype))


flash_attention_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Paged flash prefill (DESIGN.md §16)
#
# The long-context serving tier's prefill kernel: an in-flight prompt chunk
# attends over its slot's paged KV pool DIRECTLY — the per-sequence page
# table rides in scalar-prefetch SMEM (the paged_decode_attention idiom) and
# the K/V BlockSpec index_map resolves pt[b, j] per tile, so the page gather
# IS the HBM->VMEM DMA. This replaces paged_extend's XLA fallback, which
# materializes the slot's ENTIRE (NB * page_size) window per chunk — O(chunks
# x window) gather bytes on a fragmented long context, the exact DRAM term
# the paper says dominates edge energy.
#
# Numerics contract (must match the paged_extend oracle token-for-token):
# the cached prefix [0, start) is read from the pool in STORAGE dtype (int8
# codes dequantized in-kernel — decode numerics), while the chunk's own
# K/V arrive as separate full-precision operands (dense-prefill numerics).
# The K sweep therefore runs NB page steps plus ONE chunk step; pages past
# the cached window are clamped in the index_map to the last needed page,
# so the revolving-window pipeline issues no DMA for them — gather traffic
# is ceil(start/ps) pages per row, independent of fragmentation.
# ---------------------------------------------------------------------------


def _paged_prefill_kernel(start_ref, len_ref, pt_ref, *refs, scale: float,
                          window: int, page_size: int, n_blocks: int,
                          block_q: int, rep: int, quantized: bool):
    """Online-softmax body. Grid (B, Hkv, NQ, NB + 1): page steps
    ki < NB score the cached window in storage dtype; the final step
    ki == NB scores the full-precision in-flight chunk with the causal
    in-chunk mask. Query rows flatten (token, rep) row-major, ``block_q``
    chunk tokens per tile."""
    del pt_ref                                   # consumed by the index_maps
    if quantized:
        (q_ref, kp_ref, kps_ref, vp_ref, vps_ref, kc_ref, vc_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, kp_ref, vp_ref, kc_ref, vc_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
        kps_ref = vps_ref = None
    bi, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = start_ref[bi]                        # cached-prefix length
    ln = len_ref[bi]                             # valid chunk tokens; 0=dead
    rows = block_q * rep
    # chunk-relative token index of each flattened q row ((t, rep) major)
    q_rel = (qi * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
             ) // rep
    q_abs = start + q_rel                        # absolute position

    def _accumulate(s, valid, v):
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # -- page step: cached window [0, start), storage dtype ------------------
    k_pos = ki * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid_p = k_pos < start                      # chunk's own pages excluded
    if window > 0:
        valid_p &= (q_abs - k_pos) < window

    @pl.when(jnp.logical_and(ln > 0,
                             jnp.logical_and(ki < n_blocks,
                                             ki * page_size < start)))
    def _page():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, d)
        k = kp_ref[0, 0].astype(jnp.float32)                 # (ps, d)
        if quantized:
            k = k * kps_ref[0, 0]                            # (ps, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        v = vp_ref[0, 0].astype(jnp.float32)
        if quantized:
            v = v * vps_ref[0, 0]
        _accumulate(s, valid_p, v)

    # -- chunk step: in-flight tokens, full precision, causal ----------------
    c = kc_ref.shape[2]
    k_rel = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    valid_c = (k_rel <= q_rel) & (k_rel < ln)
    if window > 0:
        valid_c &= (q_rel - k_rel) < window

    @pl.when(jnp.logical_and(ln > 0, ki == n_blocks))
    def _chunk():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (rows, d)
        k = kc_ref[0, 0].astype(jnp.float32)                 # (c, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        _accumulate(s, valid_c, vc_ref[0, 0].astype(jnp.float32))

    @pl.when(ki == n_blocks)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_q",
                                             "interpret"))
def paged_prefill_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                            v_new: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, page_table: jnp.ndarray,
                            starts: jnp.ndarray, lens: jnp.ndarray, *,
                            scale: float, window: int = -1,
                            block_q: int = 128, interpret: bool = False,
                            k_scale=None, v_scale=None) -> jnp.ndarray:
    """Chunk prefill attention through a paged KV pool.

    q: (B, C, H, D) chunk queries (rope applied); k_new/v_new: (B, C, Hkv,
    D) the chunk's FULL-PRECISION K/V (what the in-chunk attention sees —
    dense-prefill numerics); k_pool/v_pool: (P, page_size, Hkv, D) storage
    pools, already holding the scattered chunk (the kernel only reads pages
    covering [0, start)); page_table: (B, NB) int32 (entries past a slot's
    chain must be in-bounds — the engine points them at the sink page);
    starts: (B,) cached-prefix length per row; lens: (B,) valid chunk
    tokens (0 = dead row -> zeros). ``k_scale``/``v_scale`` (P, page_size,
    Hkv) fp32 switch on int8-KV in-kernel dequant for the cached window.
    ``block_q`` is in chunk TOKENS (C % block_q == 0; ops.py pads).
    Returns (B, C, H, D) in q.dtype; rows past ``lens`` are garbage (the
    caller's padding contract, same as paged_extend)."""
    b, c, h, d = q.shape
    p_pages, page_size, hkv, _ = k_pool.shape
    nb = page_table.shape[1]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    assert c % block_q == 0, (c, block_q)
    nq = c // block_q
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "pass both scales or neither"

    # (B, C, Hkv, rep, D) -> (B, Hkv, C*rep, D): rows (t, rep) row-major,
    # matching the kernel's q_rel = row // rep decode
    qg = q.reshape(b, c, hkv, rep, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, c * rep, d)
    kpt = k_pool.transpose(0, 2, 1, 3)           # (P, Hkv, ps, D)
    vpt = v_pool.transpose(0, 2, 1, 3)
    kct = k_new.transpose(0, 2, 1, 3)            # (B, Hkv, C, D)
    vct = v_new.transpose(0, 2, 1, 3)

    def kv_map(bi, hi, qi, ki, starts, lens, pt):
        del lens
        # pages past the cached window re-map to the last needed page: the
        # revolving-window pipeline skips their DMA, so gather traffic is
        # ceil(start/ps) pages per row regardless of NB or fragmentation
        last = jnp.maximum(starts[bi] - 1, 0) // page_size
        return (pt[bi, jnp.minimum(ki, last)], hi, 0, 0)

    def chunk_map(bi, hi, qi, ki, starts, lens, pt):
        del starts, lens, pt
        return (bi, hi, 0, 0)

    rows = block_q * rep
    kv_spec = pl.BlockSpec((1, 1, page_size, d), kv_map)
    chunk_spec = pl.BlockSpec((1, 1, c, d), chunk_map)
    in_specs = [
        pl.BlockSpec((1, 1, rows, d),
                     lambda bi, hi, qi, ki, starts, lens, pt:
                     (bi, hi, qi, 0)),
        kv_spec,
    ]
    operands = [qg, kpt]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, page_size, 1), kv_map)
        kst = k_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
        vst = v_scale.astype(jnp.float32).transpose(0, 2, 1)[..., None]
        in_specs += [sc_spec, kv_spec, sc_spec]
        operands += [kst, vpt, vst]
    else:
        in_specs += [kv_spec]
        operands += [vpt]
    in_specs += [chunk_spec, chunk_spec]
    operands += [kct, vct]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nq, nb + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, d),
                               lambda bi, hi, qi, ki, starts, lens, pt:
                               (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max
            pltpu.VMEM((rows, 1), jnp.float32),     # running denom
            pltpu.VMEM((rows, d), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=scale, window=window,
                          page_size=page_size, n_blocks=nb, block_q=block_q,
                          rep=rep, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * rep, d), q.dtype),
        interpret=interpret,
    )(starts.astype(jnp.int32), lens.astype(jnp.int32),
      page_table.astype(jnp.int32), *operands)
    return out.reshape(b, hkv, c, rep, d).transpose(0, 2, 1, 3, 4
                                                    ).reshape(b, c, h, d)
