"""Process set-up shared by the launchers and ``chip_smoke.py``: JAX's
persistent compilation cache, and the device the carbon accountant bills.
"""

from __future__ import annotations

import os
import pathlib

import jax

from repro.core import hw

# <checkout>/src/repro/launch/runtime.py -> <checkout>
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where compiled programs persist between processes: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``.
    The fallback is fixed (no temporary directory, pid or timestamp), so
    every later process started from this checkout finds the entries."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads
    it, and nothing is set here. Returns the cache directory."""
    path = compile_cache_dir()
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def accountant_device() -> str:
    """Name of the ``hw.DEVICES`` spec the accountant bills. On a TPU it is
    the attached chip's, looked up by ``device_kind`` (an unknown kind
    raises). Off the TPU no device time is measured, and the modeled bill
    stays with the v5e target."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return hw.spec_for_kind(dev.device_kind).name
    return hw.TPU_V5E.name
