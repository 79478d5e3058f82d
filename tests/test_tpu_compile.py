"""The main path's Pallas kernels compile for a TPU v5e at StarCoder2-7B
widths, and the training flash kernels also at a windowed (gemma3-27b's
local layers) and an MQA (granite-34b) width, each with the tiles that
``ops.flash_train_tiles`` chooses.

Interpret mode (every other kernel test) accepts kernels that the chip's
compiler refuses for tiling or VMEM limits. These tests hand the real TPU
compiler a described v5e (no chip attached) and check that each kernel is
kept as a Mosaic ``tpu_custom_call`` in the compiled program. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only the worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import starcoder2_7b
from repro.kernels import ops as kops
from repro.models import transformer as tf_lib

# StarCoder2-7B attention and MLP widths (configs/starcoder2_7b.py)
H, HKV, D, D_MODEL, D_FF = 36, 4, 128, 4608, 18432
PAGE, PAGES, SLOTS, MAX_LEN = 16, 4096, 8, 1088
NB = MAX_LEN // PAGE


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the filesystem
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *specs) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pools(int8: bool):
    p = PAGES + 1
    kv = [((p, PAGE, HKV, D), jnp.int8 if int8 else jnp.bfloat16)] * 2
    scales = [((p, PAGE, HKV), jnp.float32)] * 2 if int8 else []
    return kv, scales


def _paged_decode(int8):
    kv, scales = _pools(int8)

    def fn(q, k, v, pt, lens, *sc):
        return kops.paged_decode_attention(
            q, k, v, pt, lens, interpret=False,
            k_scale=sc[0] if sc else None, v_scale=sc[1] if sc else None)
    return fn, [((SLOTS, H, D), jnp.bfloat16), *kv,
                ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32), *scales]


def _paged_verify():
    kv, _ = _pools(False)

    def fn(q, k, v, pt, lens):
        return kops.paged_verify_attention(q, k, v, pt, lens,
                                           interpret=False)
    return fn, [((SLOTS, 5, H, D), jnp.bfloat16), *kv,
                ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32)]


def _paged_prefill(int8):
    kv, scales = _pools(int8)
    c = 256

    def fn(q, kn, vn, k, v, pt, starts, lens, *sc):
        return kops.paged_prefill_attention(
            q, kn, vn, k, v, pt, starts, lens, interpret=False,
            k_scale=sc[0] if sc else None, v_scale=sc[1] if sc else None)
    return fn, [((SLOTS, c, H, D), jnp.bfloat16),
                ((SLOTS, c, HKV, D), jnp.bfloat16),
                ((SLOTS, c, HKV, D), jnp.bfloat16), *kv,
                ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32),
                ((SLOTS,), jnp.int32), *scales]


def _dense_decode():
    def fn(q, k, v, lens):
        return kops.decode_attention(q, k, v, lens, interpret=False)
    return fn, [((SLOTS, H, D), jnp.bfloat16),
                ((SLOTS, MAX_LEN, HKV, D), jnp.bfloat16),
                ((SLOTS, MAX_LEN, HKV, D), jnp.bfloat16),
                ((SLOTS,), jnp.int32)]


def _flash_shapes(h=H, hkv=HKV):
    return [((1, 2048, h, D), jnp.bfloat16),
            ((1, 2048, hkv, D), jnp.bfloat16),
            ((1, 2048, hkv, D), jnp.bfloat16)]


_FLASH = _flash_shapes()


def _flash_fwd():
    def fn(q, k, v):
        return kops.flash_attention_train(q, k, v, interpret=False)
    return fn, _FLASH


def _flash_bwd(shapes=_FLASH, window=-1):
    def fn(q, k, v):
        def loss(q, k, v):
            o = kops.flash_attention_train(q, k, v, window=window,
                                           interpret=False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return fn, shapes


_INT8_MM = [((256, D_MODEL), jnp.bfloat16), ((D_MODEL, D_FF), jnp.int8),
            ((D_FF,), jnp.float32)]


def _int8_fwd():
    def fn(x, q, s):
        return kops.int8_matmul_train(x, q, s, interpret=False)
    return fn, _INT8_MM


def _int8_bwd():
    def fn(x, q, s):
        def loss(x):
            y = kops.int8_matmul_train(x, q, s, interpret=False)
            return jnp.sum(y.astype(jnp.float32))
        return jax.grad(loss)(x)
    return fn, _INT8_MM


_FLASH_KERNELS = {"_flash_kernel", "_flash_bwd_dq_kernel",
                  "_flash_bwd_dkv_kernel"}

# case -> (shape-and-function factory, the Pallas kernels the compiled
# program must keep)
KERNELS = {
    "paged_decode_bf16": (lambda: _paged_decode(False), {"_paged_kernel"}),
    "paged_decode_int8kv": (lambda: _paged_decode(True), {"_paged_kernel"}),
    "paged_verify": (_paged_verify, {"_paged_verify_kernel"}),
    "paged_prefill_bf16": (lambda: _paged_prefill(False),
                           {"_paged_prefill_kernel"}),
    "paged_prefill_int8kv": (lambda: _paged_prefill(True),
                             {"_paged_prefill_kernel"}),
    "dense_decode": (_dense_decode, {"_decode_kernel"}),
    "flash_fwd": (_flash_fwd, {"_flash_kernel"}),
    "flash_bwd": (_flash_bwd, _FLASH_KERNELS),
    # gemma3-27b's local layers: 32/16 heads of 128, window 1024
    "flash_bwd_window1024": (
        lambda: _flash_bwd(_flash_shapes(32, 16), window=1024),
        _FLASH_KERNELS),
    # granite-34b: MQA, 48 heads over one kv head of 128
    "flash_bwd_mqa": (lambda: _flash_bwd(_flash_shapes(48, 1)),
                      _FLASH_KERNELS),
    "int8_matmul_fwd": (_int8_fwd, {"_int8_matmul_kernel"}),
    "int8_matmul_bwd": (_int8_bwd, {"_int8_bwd_dx_kernel"}),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    build, kernels = KERNELS[name]
    fn, specs = build()
    text = _compiled_text(one_chip, fn, *specs)
    assert "tpu_custom_call" in text
    assert kernels <= set(kops.compiled_kernels(text))


def test_published_config_train_step_keeps_flash_kernel(one_chip,
                                                        monkeypatch):
    """A 2-layer train step of the PUBLISHED StarCoder2-7B config (which
    sets ``sp_attention``) routes attention through the flash kernels when
    no sharding context is active. The wrappers pick interpret mode from
    the backend, which is the CPU here, so the test steers that check."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(starcoder2_7b.make_config(), repeats=2,
                              flash_train=True)
    assert cfg.sp_attention
    shapes = jax.eval_shape(
        lambda: tf_lib.init_lm(jax.random.PRNGKey(0), cfg).params)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shapes)
    tok = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)

    def step(p, tokens, labels):
        grad_fn = jax.grad(lambda p: tf_lib.loss_fn(
            p, cfg, {"tokens": tokens, "labels": labels})[0])
        return grad_fn(p)

    text = jax.jit(step).lower(params, tok, tok).compile().as_text()
    assert "tpu_custom_call" in text
    assert {"_flash_kernel", "_flash_bwd_dq_kernel",
            "_flash_bwd_dkv_kernel"} <= set(kops.compiled_kernels(text))
