"""Fwd+bwd parity matrix for the custom-VJP Pallas kernels (DESIGN.md §13).

The contract that makes the training fast path trustworthy: for every
(dtype, block shape, odd/even sequence length, mask mode) cell,
``jax.grad`` through the custom-VJP kernel wrappers must match ``jax.grad``
through the pure-jnp references in kernels/ref.py within per-dtype
tolerance. Kernels run in interpret mode (bit-accurate kernel-body
semantics) so the matrix is CPU-checkable.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as kops
from repro.kernels import ref as kref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=6e-2, atol=6e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _flash_grads(q, k, v, ct, *, scale, causal, window, block_q, block_k):
    def f(q, k, v):
        out = kops.flash_attention_train(
            q, k, v, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k)
        return jnp.sum(out.astype(jnp.float32) * ct)
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


def _ref_grads(q, k, v, ct, *, scale, causal, window):
    def f(q, k, v):
        out = kref.attention_ref(q, k, v, scale=scale, causal=causal,
                                 window=window)
        return jnp.sum(out.astype(jnp.float32) * ct)
    return jax.grad(f, argnums=(0, 1, 2))(q, k, v)


# -- flash attention: dtype x block x odd-length x mask matrix ----------------

FLASH_CASES = [
    # (sq, sk, h, hkv, d, causal, window, block_q, block_k)
    (16, 16, 2, 2, 8, True, -1, 8, 8),          # aligned, MHA
    (16, 16, 4, 2, 8, True, -1, 8, 8),          # GQA
    (13, 13, 2, 1, 8, True, -1, 8, 8),          # odd seq -> padded blocks
    (24, 24, 2, 2, 8, True, 7, 8, 8),           # sliding window
    (16, 16, 2, 2, 8, False, -1, 8, 8),         # non-causal
    (13, 16, 2, 2, 8, True, -1, 8, 16),         # sq < sk (chunked prefill)
    (16, 16, 2, 2, 16, True, -1, 16, 8),        # asymmetric blocks
    (9, 9, 2, 2, 8, True, 4, 8, 8),             # odd + window
    # tiles past 128, several per row: the clamped index maps at work
    (512, 512, 2, 2, 8, True, -1, 256, 128),    # large, rectangular
    (512, 512, 2, 2, 8, True, -1, 128, 256),    # large, bk > bq
    (512, 512, 2, 2, 8, True, -1, None, None),  # the shape rule's tiles
    (300, 300, 2, 2, 8, True, -1, None, None),  # rule + padded tail
    (64, 64, 2, 2, 8, True, -1, 16, 8),         # causal, 8 K tiles a row
    (64, 64, 2, 2, 8, True, 20, 16, 16),        # window cuts through tiles
    (64, 64, 2, 2, 8, True, 20, 32, 8),         # window, rectangular
    (48, 48, 2, 2, 8, False, 12, 16, 16),       # window, non-causal
    (64, 64, 6, 2, 8, True, -1, 32, 16),        # GQA, rep 3
    (64, 64, 4, 1, 8, True, 24, 16, 32),        # MQA + window
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize(
    "sq,sk,h,hkv,d,causal,window,bq,bk", FLASH_CASES,
    ids=[f"sq{c[0]}sk{c[1]}h{c[2]}kv{c[3]}d{c[4]}"
         f"{'c' if c[5] else 'f'}w{c[6]}b{c[7]}x{c[8]}" for c in FLASH_CASES])
def test_flash_attention_grad_parity(sq, sk, h, hkv, d, causal, window,
                                     bq, bk, dtype):
    rng = np.random.default_rng(hash((sq, sk, h, causal, window)) % 2**32)
    b = 2
    q = _rand(rng, (b, sq, h, d), dtype)
    k = _rand(rng, (b, sk, hkv, d), dtype)
    v = _rand(rng, (b, sk, hkv, d), dtype)
    ct = _rand(rng, (b, sq, h, d), jnp.float32)
    scale = 0.4
    got = _flash_grads(q, k, v, ct, scale=scale, causal=causal,
                       window=window, block_q=bq, block_k=bk)
    want = _ref_grads(q, k, v, ct, scale=scale, causal=causal, window=window)
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == w.dtype, (name, g.dtype, w.dtype)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            **TOL[dtype], err_msg=f"d{name}")


def test_flash_train_tiles_rule():
    """The training tiles come from shapes: large at StarCoder2-7B's row,
    the pow2 envelope (at most 128) for short rows, capped by a sliding
    window, and within the VMEM budget at every head_dim the configs use."""
    tiles = kops.flash_train_tiles
    assert tiles(2048, 2048, 128, -1, 2) == (1024, 1024)
    for s in (1, 8, 13, 64, 100, 128):
        t = kops._round_up_pow2(s)
        assert tiles(s, s, 128, -1, 2) == (t, t) and t <= 128
    big = tiles(4096, 4096, 128, -1, 2)[0]
    windowed = tiles(4096, 4096, 128, 1024, 2)[0]
    assert windowed < big and 2 * windowed <= 1024
    for d in (64, 112, 128, 256):
        for itemsize in (2, 4):
            bq, bk = tiles(2048, 2048, d, -1, itemsize)
            assert bq >= 128 and bk >= 128
            assert (kops.flash_vmem_bytes(bq, bk, d, itemsize)
                    <= kops.FLASH_VMEM_BUDGET), (d, itemsize, bq, bk)
    # the tile always divides the row as padded to 128: no extra padding
    for s in range(129, 4200, 37):
        bq, bk = tiles(s, s, 128, -1, 2)
        assert -(-s // bq) * bq == -(-s // 128) * 128 == -(-s // bk) * bk


@pytest.mark.parametrize("causal,window", [(True, -1), (True, 5),
                                           (True, 40), (False, 9)])
@pytest.mark.parametrize("bq,bk", [(8, 8), (16, 8), (8, 32)])
def test_flash_clamped_block_ranges_match_tile_mask(causal, window, bq, bk):
    """The index maps' block ranges are exactly the tiles that
    ``_tile_mask`` keeps: a needed block is never re-mapped away, and every
    re-mapped step is one the kernel skips."""
    from repro.kernels import flash_attention as fa
    n = 64
    nq, nk = n // bq, n // bk
    live = np.array([[bool(jnp.any(fa._tile_mask(qi, ki, bq, bk, causal,
                                                 window)))
                      for ki in range(nk)] for qi in range(nq)])
    for qi in range(nq):
        first, last = fa._kv_block_range(qi, bq, bk, nk, causal, window)
        want = [ki for ki in range(nk) if live[qi, ki]]
        assert want == list(range(int(first), int(last) + 1)), (qi, want)
        clamped = [int(fa._clamp_block(ki, first, last)) for ki in range(nk)]
        assert set(clamped) == set(want)
    for ki in range(nk):
        first, last = fa._q_block_range(ki, bq, bk, nq, causal, window)
        want = [qi for qi in range(nq) if live[qi, ki]]
        assert want == list(range(int(first), int(last) + 1)), (ki, want)


def test_flash_attention_fwd_matches_inference_wrapper():
    """The trainable wrapper's forward is the same kernel math as the
    serving wrapper (no train/serve numerics drift)."""
    rng = np.random.default_rng(0)
    q = _rand(rng, (2, 13, 4, 8), jnp.float32)
    k = _rand(rng, (2, 13, 2, 8), jnp.float32)
    v = _rand(rng, (2, 13, 2, 8), jnp.float32)
    a = kops.flash_attention_train(q, k, v, scale=0.35)
    b = kops.flash_attention(q, k, v, scale=0.35)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_flash_attention_grad_jits():
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 16, 2, 8), jnp.float32)
    k = _rand(rng, (1, 16, 2, 8), jnp.float32)
    v = _rand(rng, (1, 16, 2, 8), jnp.float32)

    @jax.jit
    def g(q, k, v):
        return jax.grad(lambda q: jnp.sum(
            kops.flash_attention_train(q, k, v, scale=0.3)))(q)

    want = jax.grad(lambda q: jnp.sum(
        kops.flash_attention_train(q, k, v, scale=0.3)))(q)
    np.testing.assert_allclose(np.asarray(g(q, k, v)), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# -- int8 matmul: dtype x block x ragged-shape matrix -------------------------

INT8_CASES = [
    # (m, k, n, block_n, block_k)
    (8, 32, 16, 16, 32),            # aligned
    (5, 40, 24, 16, 32),            # ragged everything
    (3, 17, 9, 8, 16),              # tiny + odd
    (16, 64, 32, 32, 64),           # bigger blocks
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("m,k,n,bn,bk", INT8_CASES,
                         ids=[f"m{c[0]}k{c[1]}n{c[2]}b{c[3]}x{c[4]}"
                              for c in INT8_CASES])
def test_int8_matmul_grad_parity(m, k, n, bn, bk, dtype):
    rng = np.random.default_rng(hash((m, k, n)) % 2**32)
    x = _rand(rng, (m, k), dtype)
    q = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (n,)), jnp.float32)
    ct = _rand(rng, (m, n), jnp.float32)

    def f_kernel(x):
        y = kops.int8_matmul_train(x, q, scale, block_n=bn, block_k=bk)
        return jnp.sum(y.astype(jnp.float32) * ct)

    def f_ref(x):
        y = kref.ternary_matmul_ref(x, q, scale, out_dtype=jnp.float32)
        return jnp.sum(y * ct)

    gx = jax.grad(f_kernel)(x)
    rx = jax.grad(f_ref)(x)
    assert gx.dtype == x.dtype
    tol = dict(TOL[dtype])
    if dtype == jnp.bfloat16:
        # bf16 grads differ only by accumulation-order rounding; compare at
        # the scale of the gradient (near-zero elements cancel differently)
        tol["atol"] = 0.02 * float(np.max(np.abs(np.asarray(rx, np.float32))))
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(rx, np.float32), **tol)


def test_int8_matmul_dscale_parity():
    """scale gets a real gradient, recovered from the saved fp32 output."""
    rng = np.random.default_rng(7)
    x = _rand(rng, (6, 32), jnp.float32)
    q = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.02, 0.2, (16,)), jnp.float32)
    ct = _rand(rng, (6, 16), jnp.float32)

    gs = jax.grad(lambda s: jnp.sum(
        kops.int8_matmul_train(x, q, s, block_n=16, block_k=32) * ct))(scale)
    rs = jax.grad(lambda s: jnp.sum(
        (x @ q.astype(jnp.float32)) * s * ct))(scale)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                               rtol=1e-4, atol=1e-4)


def test_int8_matmul_codes_not_differentiable():
    """The int8 codes are frozen: their cotangent is symbolic-zero (float0),
    and grads wrt x still flow through a jit boundary."""
    rng = np.random.default_rng(8)
    x = _rand(rng, (4, 32), jnp.float32)
    q = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
    scale = jnp.ones((16,), jnp.float32)

    @jax.jit
    def g(x):
        return jax.grad(lambda x: jnp.sum(
            kops.int8_matmul_train(x, q, scale, block_n=16, block_k=32)))(x)

    assert g(x).shape == x.shape
    out, vjp = jax.vjp(
        lambda x, q, s: kops.int8_matmul_train(x, q, s, block_n=16,
                                               block_k=32), x, q, scale)
    dx, dq, ds = vjp(jnp.ones_like(out))
    assert dq.dtype == jax.dtypes.float0
    assert dx.shape == x.shape and ds.shape == scale.shape


# -- the model-level route: attention() with flash_vjp on ---------------------

def test_attention_layer_flash_vjp_grad_parity():
    """layers.attention with cfg.flash_vjp routes through the kernel; its
    grads wrt the projection weights match the sdpa path."""
    from repro.models import layers

    cfg = dict(d_model=32, n_heads=2, n_kv_heads=2, head_dim=16)
    acfg_ref = layers.AttnConfig(**cfg)
    acfg_fast = layers.AttnConfig(**cfg, flash_vjp=True)
    key = jax.random.PRNGKey(0)
    params = layers.init_attention(key, acfg_ref, jnp.float32).params
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 12, 32)),
                    jnp.float32)

    def loss(p, acfg):
        return jnp.sum(jnp.square(layers.attention(p, acfg, x)))

    g_ref = jax.grad(lambda p: loss(p, acfg_ref))(params)
    g_fast = jax.grad(lambda p: loss(p, acfg_fast))(params)
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    flat_fast = dict(jax.tree_util.tree_leaves_with_path(g_fast))
    assert flat_ref and len(flat_ref) == len(flat_fast)
    for path, a in flat_ref:
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(flat_fast[path]),
                                   rtol=2e-4, atol=2e-4, err_msg=str(path))
