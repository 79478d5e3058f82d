"""Jit'd public wrappers for the Pallas kernels.

Handle padding to block multiples, GQA head grouping, dtype policy, and the
CPU fallback: on non-TPU backends the kernels execute in Pallas interpret
mode (bit-accurate kernel-body semantics, Python-speed) — use
``force_interpret=False`` + a TPU runtime for production.
"""

from __future__ import annotations

import base64
import collections
import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import int8_matmul as _im
from repro.kernels import ternary_matmul as _tm
from repro.kernels import ref as _ref
from repro.quant.ternary import TernaryWeight


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_MOSAIC_BODY = re.compile(
    r'custom_call_target="tpu_custom_call".*?"body":"([^"]+)"')
# the first such symbol in a serialized Mosaic body is the kernel function;
# later ones are source locations
_KERNEL_SYMBOL = re.compile(rb"[A-Za-z0-9_]+_kernel")


def compiled_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels that a compiled TPU program (``compiled.as_text()``)
    runs as Mosaic custom calls, counted by kernel function name. Interpret
    mode lowers a kernel body to plain HLO ops, so a kernel missing here
    does not run compiled in that program."""
    names: collections.Counter = collections.Counter()
    for body in _MOSAIC_BODY.findall(hlo_text):
        m = _KERNEL_SYMBOL.search(base64.b64decode(body))
        names[m.group(0).decode() if m else "?"] += 1
    return names


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0.0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _tiled_matmul_call(kernel, x: jnp.ndarray, q: jnp.ndarray,
                       scale: jnp.ndarray, block_m: int, block_n: int,
                       block_k: int, interpret: bool) -> jnp.ndarray:
    """Shared pad-and-launch wrapper for the quantized matmul kernels:
    flattens leading dims, derives a sublane-aligned small-batch M tile,
    pads every operand to block multiples, and slices the result back."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = q.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    # small-batch inference tiles, kept sublane-aligned (multiples of 8)
    bm = min(block_m, max(8, -(-m // 8) * 8))
    x2 = _pad_to(_pad_to(x2, 0, bm), 1, block_k)
    qp = _pad_to(_pad_to(q, 0, block_k), 1, block_n)
    sp = _pad_to(scale, 0, block_n)
    y = kernel(x2, qp, sp, block_m=bm, block_n=block_n, block_k=block_k,
               interpret=interpret, out_dtype=x.dtype)
    return y[:m, :n].reshape(*lead, n)


def ternary_matmul(x: jnp.ndarray, w: TernaryWeight, *,
                   block_m: int = 128, block_n: int = 128, block_k: int = 512,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (..., K) @ ternary weight (K, N) -> (..., N)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return _tiled_matmul_call(_tm.ternary_matmul, x, w.q,
                              w.scale.reshape(-1), block_m, block_n,
                              block_k, interpret)


def ternary_dense(x: jnp.ndarray, w: TernaryWeight, bias=None, **kw) -> jnp.ndarray:
    y = ternary_matmul(x, w, **kw)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def int8_matmul(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
                block_m: int = 128, block_n: int = 128, block_k: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (..., K) @ int8 weight (K, N) with per-channel scale -> (..., N).

    ``scale`` may be () per-tensor, (N,) per-channel, or any keepdims shape
    broadcastable to (1, N) (quant.int8.quantize_weight's ``s8``).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = q.shape[1]
    sc = jnp.broadcast_to(scale.astype(jnp.float32).reshape(-1, n)
                          if scale.ndim else scale.astype(jnp.float32),
                          (1, n)).reshape(n)
    return _tiled_matmul_call(_im.int8_matmul, x, q, sc, block_m, block_n,
                              block_k, interpret)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None, causal: bool = True,
                    window: int = -1, block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None,
                    k_scale: Optional[jnp.ndarray] = None,
                    v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Padded/GQA-aware flash attention. q (B,Sq,H,D), k/v (B,Sk,Hkv,D).

    ``k_scale``/``v_scale`` (B, Sk, Hkv) enable int8-KV mode (k/v int8
    codes, dequantized inside the kernel body).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    # block sizes: the requested block, shrunk to the (pow2, <=128) envelope
    # of the actual sequence so short sequences get one small block
    bq = min(block_q, _round_up_pow2(sq))
    bk = min(block_k, _round_up_pow2(sk))
    # Padded keys sit at positions >= sk. Causal masking hides them from
    # every real query iff sq <= sk; otherwise (non-causal, or causal with
    # q positions past sk) they would be attended — dispatch to the reference
    # path BEFORE launching the kernel (these ragged encoder shapes are small).
    assert (k_scale is None) == (v_scale is None), \
        "pass both KV scales or neither"
    if (-sk) % bk != 0 and (not causal or sq > sk):
        if k_scale is not None:
            from repro.quant.int8 import dequantize_rowwise
            k = dequantize_rowwise(k, k_scale, dtype=q.dtype)
            v = dequantize_rowwise(v, v_scale, dtype=q.dtype)
        return _ref.attention_ref(q, k, v, scale=scale, causal=causal,
                                  window=window)
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    if k_scale is not None:
        k_scale = _pad_to(k_scale, 1, bk)
        v_scale = _pad_to(v_scale, 1, bk)
    out = _fa.flash_attention(qp, kp, vp, scale=scale, causal=causal,
                              window=window, block_q=bq, block_k=bk,
                              interpret=interpret,
                              k_scale=k_scale, v_scale=v_scale)
    return out[:, :sq]


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, scale: Optional[float] = None,
                     window: int = -1, block_k: int = 128,
                     interpret: Optional[bool] = None,
                     k_scale: Optional[jnp.ndarray] = None,
                     v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Serve-core decode attention with per-slot lengths.

    q: (B, H, D) — the one new token per slot; k/v: (B, Sk, Hkv, D) slot-major
    KV cache; lengths: (B,) valid prefix per slot (0 = dead slot -> zeros).
    ``k_scale``/``v_scale`` (B, Sk, Hkv) enable the int8-KV cache mode: k/v
    are int8 codes dequantized inside the kernel body (DESIGN.md §12).
    Pads Sk up to a block multiple; padded keys sit past every length so the
    kernel's length test masks them.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    d = q.shape[-1]
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bk = min(block_k, _round_up_pow2(sk))
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    assert (k_scale is None) == (v_scale is None), \
        "pass both KV scales or neither"
    if k_scale is not None:
        k_scale = _pad_to(k_scale, 1, bk)
        v_scale = _pad_to(v_scale, 1, bk)
    return _da.decode_attention(q, kp, vp, lengths, scale=scale,
                                window=window, block_k=bk,
                                interpret=interpret,
                                k_scale=k_scale, v_scale=v_scale)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           scale: Optional[float] = None, window: int = -1,
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Serve-core decode attention through a paged KV pool (DESIGN.md §14).

    q: (B, H, D) — the one new token per slot; k_pool/v_pool:
    (P, page_size, Hkv, D) shared block pool; page_table: (B, NB) int32
    (entries past a slot's length must be in-bounds — the engine points
    them at the sink page); lengths: (B,) valid logical prefix per slot.
    ``k_scale``/``v_scale`` (P, page_size, Hkv) enable the int8-KV mode.

    No padding is needed: the pool's page dimension is the block unit, and
    the table indirection replaces the dense kernel's contiguous K sweep.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    assert (k_scale is None) == (v_scale is None), \
        "pass both KV scales or neither"
    return _da.paged_decode_attention(q, k_pool, v_pool, page_table, lengths,
                                      scale=scale, window=window,
                                      interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)


def paged_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, page_table: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           scale: Optional[float] = None, window: int = -1,
                           interpret: Optional[bool] = None,
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Speculative-verify attention through a paged KV pool (DESIGN.md §15).

    q: (B, T, H, D) — T query tokens per slot (the committed pending token
    + the drafts), already written into the pool at logical positions
    ``lengths - T + t``; lengths: (B,) valid prefix per slot INCLUDING the
    T chunk tokens. Causal within the chunk: lane t attends positions
    ``<= lengths - T + t``. ``k_scale``/``v_scale`` (P, page_size, Hkv)
    enable the int8-KV mode. Like the single-token paged kernel, no
    padding is needed — pages are the block unit.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    assert (k_scale is None) == (v_scale is None), \
        "pass both KV scales or neither"
    return _da.paged_verify_attention(q, k_pool, v_pool, page_table, lengths,
                                      scale=scale, window=window,
                                      interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)


def paged_prefill_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                            v_new: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, page_table: jnp.ndarray,
                            starts: jnp.ndarray, lens: jnp.ndarray, *,
                            scale: Optional[float] = None, window: int = -1,
                            block_q: int = 128,
                            interpret: Optional[bool] = None,
                            k_scale: Optional[jnp.ndarray] = None,
                            v_scale: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """Chunk-prefill attention through a paged KV pool (DESIGN.md §16).

    q: (B, C, H, D) chunk queries; k_new/v_new: (B, C, Hkv, D) the chunk's
    full-precision K/V (in-chunk attention sees these — dense-prefill
    numerics); k_pool/v_pool: (P, page_size, Hkv, D) the shared block pool
    holding the cached prefix [0, starts[b]) (read in storage dtype —
    decode numerics); page_table: (B, NB) int32 (out-of-chain entries must
    point at the sink page); starts/lens: (B,) cached-prefix length and
    valid chunk tokens per row (lens 0 = dead row). ``k_scale``/``v_scale``
    (P, page_size, Hkv) enable int8-KV in-kernel dequant.

    The page gather is the DMA: the scalar-prefetched table resolves each
    K/V tile's pool page in the BlockSpec index_map, and pages past the
    cached window collapse onto the last needed one — per-row gather
    traffic is ceil(start/page_size) pages, independent of how fragmented
    the chain is. Pads C up to a ``block_q`` multiple; rows past ``lens``
    return garbage (the caller's padding contract, same as paged_extend).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, c, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    assert (k_scale is None) == (v_scale is None), \
        "pass both KV scales or neither"
    bq = min(block_q, _round_up_pow2(c))
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k_new, 1, bq)
    vp = _pad_to(v_new, 1, bq)
    out = _fa.paged_prefill_attention(qp, kp, vp, k_pool, v_pool, page_table,
                                      starts, lens, scale=scale,
                                      window=window, block_q=bq,
                                      interpret=interpret,
                                      k_scale=k_scale, v_scale=v_scale)
    return out[:, :c]


def _round_up_pow2(n: int, cap: int = 128) -> int:
    p = 8
    while p < n and p < cap:
        p *= 2
    return p


# -----------------------------------------------------------------------------
# Training fast path: differentiable wrappers (custom-VJP kernels, §13)
# -----------------------------------------------------------------------------

# Training flash tiles. Past 128 rows a tile grows in powers of two while
# it divides the row padded to 128 (so padding is what a 128 tile gives)
# and stays within a cap: FLASH_TILE_MAX, halved while the kernels' VMEM
# need exceeds FLASH_VMEM_BUDGET, and halved while two tiles span more than
# a sliding window, so that most of a windowed row's tiles lie in it.
FLASH_TILE_MAX = 1024
# v5e's default scoped VMEM limit for a Mosaic kernel
FLASH_VMEM_BUDGET = 16 * 2**20


def flash_vmem_bytes(block_q: int, block_k: int, d: int,
                     itemsize: int) -> int:
    """VMEM the training flash kernels need for one (block_q, block_k)
    tile, an upper bound fitted to the v5e compiler's need for the dK/dV
    kernel, the largest of the three: two fp32 (bq, bk) tiles; per q row,
    q and dO double-buffered in ``itemsize`` bytes and their fp32 copies;
    per k row, k and v double-buffered, fp32 dK and dV double-buffered and
    their accumulators; 1 MiB of the compiler's own. Rows pad to 128
    lanes."""
    lanes = -(-d // 128) * 128
    q_side = block_q * lanes * (4 * itemsize + 8)
    k_side = block_k * lanes * (4 * itemsize + 24)
    return 8 * block_q * block_k + q_side + k_side + 2**20


def flash_train_tiles(sq: int, sk: int, d: int, window: int,
                      itemsize: int) -> tuple:
    """(block_q, block_k) of the training flash kernels, from shapes only.
    Rows of 128 or fewer keep the pow2 envelope of the row, as the
    inference wrapper gives."""
    cap = FLASH_TILE_MAX
    while cap > 128 and flash_vmem_bytes(cap, cap, d, itemsize) \
            > FLASH_VMEM_BUDGET:
        cap //= 2
    while window > 0 and cap > 128 and 2 * cap > window:
        cap //= 2

    def tile(n):
        t = _round_up_pow2(n)
        padded = -(-n // t) * t
        while 2 * t <= cap and padded % (2 * t) == 0:
            t *= 2
        return t
    return tile(sq), tile(sk)


def flash_attention_train(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          scale: Optional[float] = None, causal: bool = True,
                          window: int = -1, block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Differentiable flash attention (custom-VJP Pallas kernels).

    Same shapes/semantics as :func:`flash_attention`, but ``jax.grad``
    through it runs the fused backward kernels (recompute-from-lse; no
    O(Sq*Sk) probability tensor) instead of failing on the pallas_call.
    Padding/slicing here is plain jnp, so its VJP composes with the kernel's.
    Tiles come from :func:`flash_train_tiles`; ``block_q``/``block_k``
    override them (shrunk to the row's pow2 envelope, as in
    :func:`flash_attention`).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    bq, bk = flash_train_tiles(sq, sk, d, window, q.dtype.itemsize)
    if block_q is not None:
        bq = min(block_q, _round_up_pow2(sq, block_q))
    if block_k is not None:
        bk = min(block_k, _round_up_pow2(sk, block_k))
    # same ragged-shape escape as the inference wrapper: padded keys are
    # only hidden by causal masking when sq <= sk
    if (-sk) % bk != 0 and (not causal or sq > sk):
        return _ref.attention_ref(q, k, v, scale=scale, causal=causal,
                                  window=window)
    qp = _pad_to(q, 1, bq)
    kp = _pad_to(k, 1, bk)
    vp = _pad_to(v, 1, bk)
    statics = (float(scale), bool(causal), int(window), bq, bk,
               bool(interpret))
    out = _fa.flash_attention_vjp(qp, kp, vp, statics)
    return out[:, :sq]


def int8_matmul_train(x: jnp.ndarray, q: jnp.ndarray, scale: jnp.ndarray, *,
                      block_m: int = 128, block_n: int = 128,
                      block_k: int = 512,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Differentiable int8 matmul: dx runs the fused in-kernel-dequant
    backward, dscale is recovered from the saved fp32 forward output, and
    the int8 codes are frozen (float0 cotangent — pair with an STE at the
    call site for quantization-aware training). Returns x.dtype."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = q.shape[-1]
    sc = jnp.broadcast_to(scale.astype(jnp.float32).reshape(-1, n)
                          if scale.ndim else scale.astype(jnp.float32),
                          (1, n)).reshape(n)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = min(block_m, max(8, -(-m // 8) * 8))
    x2 = _pad_to(_pad_to(x2, 0, bm), 1, block_k)
    qp = _pad_to(_pad_to(q, 0, block_k), 1, block_n)
    # pad scale with ones, not zeros: the dscale residual divides by it
    sp = _pad_to(sc, 0, block_n, value=1.0)
    statics = (bm, block_n, block_k, bool(interpret), jnp.dtype(x.dtype).name)
    y = _im.int8_matmul_vjp(x2, qp, sp, statics)
    return y[:m, :n].reshape(*lead, n).astype(x.dtype)


def attention_auto(q, k, v, *, scale=None, causal=True, window=-1,
                   use_flash: bool = True):
    """Dispatch: flash kernel on TPU / interpret-validated path, else oracle."""
    if use_flash:
        return flash_attention(q, k, v, scale=scale, causal=causal, window=window)
    return _ref.attention_ref(q, k, v, scale=scale or q.shape[-1] ** -0.5,
                              causal=causal, window=window)
