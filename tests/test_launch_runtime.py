"""Process set-up helpers (launch/runtime.py) and the device_kind table
(core/hw.py). Nothing here turns the compilation cache on."""

from __future__ import annotations

import pathlib

import jax
import pytest

from repro.core import hw
from repro.launch import runtime

REPO = pathlib.Path(__file__).resolve().parents[1]


class TestCompileCache:
    def test_env_var_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert runtime.compile_cache_dir() == str(tmp_path)
        assert runtime.enable_compile_cache() == str(tmp_path)
        assert calls == []

    def test_fallback_is_fixed_and_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
        path = pathlib.Path(runtime.compile_cache_dir())
        assert path == REPO / ".jax_cache"
        assert runtime.compile_cache_dir() == str(path)
        assert (path.parent / "pyproject.toml").is_file()


class TestDeviceKinds:
    def test_v5e_kind_maps_to_its_spec(self):
        assert hw.spec_for_kind("TPU v5 lite") is hw.TPU_V5E

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError, match="DEVICE_KINDS"):
            hw.spec_for_kind("TPU v9 imaginary")

    def test_off_tpu_bill_stays_with_the_modeled_target(self):
        assert jax.devices()[0].platform != "tpu"
        assert runtime.accountant_device() == hw.TPU_V5E.name
