"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
serve and train phases run end to end at a tiny size on the CPU (kernels in
interpret mode; the compiled-kernel checks need the chip and are skipped).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import pathlib
import types

import jax
import pytest

from repro.configs import starcoder2_7b
from repro.launch import serve as launch_serve
from repro.launch import train as launch_train
from repro.serve import engine as serve_engine
from repro.train import engine as train_engine

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_off_tpu(smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.main()
    assert '"ok"' not in capsys.readouterr().out


def test_phases_run_at_tiny_size(smoke, monkeypatch, capsys):
    for name, value in dict(PROMPT_LEN=(40, 80), SHARED_PREFIX=32,
                            MAX_TOKENS=5, PREFILL_CHUNK=32, NUM_PAGES=96,
                            MAX_LEN=96, TRAIN_SEQ=32, LOGIT_STEPS=3).items():
        monkeypatch.setattr(smoke, name, value)
    monkeypatch.setattr(starcoder2_7b, "make_config",
                        starcoder2_7b.make_smoke)
    # the engines turn the kernel routes on only on a TPU; force them so
    # the interpret-mode kernels run the same paths
    monkeypatch.setattr(
        launch_serve, "ServeEngine",
        lambda p, c, s, **kw: serve_engine.ServeEngine(
            p, c, dataclasses.replace(s, decode_kernel=True), **kw))
    monkeypatch.setattr(
        launch_train, "TrainEngineConfig",
        functools.partial(train_engine.TrainEngineConfig,
                          use_flash_vjp=True))
    monkeypatch.setattr(smoke, "check_kernels", lambda *a: None)
    device = types.SimpleNamespace(memory_stats=lambda: {
        "bytes_limit": 1 << 40, "peak_bytes_in_use": 0})
    clog = smoke.CompileLog(jax.monitoring)
    try:
        smoke.serve_phase(clog)
        smoke.train_phase(clog, device)
    finally:
        clog.close()
    out = capsys.readouterr().out
    for phase in ("serve bf16", "serve int8", "serve spec_k4",
                  "serve logit check", "train run"):
        assert f"[{phase}]" in out
