"""Training launcher: the host-loop FT Trainer or the fused TrainEngine.

``--smoke`` runs the arch's reduced config end to end (real steps, real
checkpoints, real accounting), on the CPU (kernels in interpret mode) or on
a TPU. The fused tick at published widths runs on one TPU v5e through
``python chip_smoke.py``, which builds its engine with :func:`build_engine`
and cuts the depth to what one chip holds.

Example (the (b) end-to-end driver uses this):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-27b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt --grid-mix NY
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base as cfgbase
from repro.core import accounting
from repro.data import DataConfig, make_pipeline
from repro.launch import runtime
from repro.models import encdec as encdec_lib
from repro.models import transformer as tf_lib
from repro.optim import AdamWConfig
from repro.optim.schedules import warmup_cosine
from repro.checkpoint import CheckpointConfig
from repro.train import (TrainConfig, Trainer, TrainEngine,
                         TrainEngineConfig)
from repro.train.ft import HeartbeatWriter


def build_smoke_trainer(arch_id: str, *, steps: int, ckpt_dir: Optional[str],
                        grid_mix: str = "NY", seed: int = 0,
                        global_batch: int = 8, seq_len: int = 64,
                        heartbeat_dir: Optional[str] = None,
                        lr: float = 3e-3) -> Trainer:
    arch = cfgbase.get(arch_id)
    cfg = arch.make_smoke()
    key = jax.random.PRNGKey(seed)
    if arch.kind == "encdec":
        params = encdec_lib.init_encdec(key, cfg, dtype=jnp.float32).params
        frames = np.zeros((global_batch, cfg.n_audio_ctx, cfg.d_model),
                          np.float32)

        def loss_fn(p, batch):
            b = dict(batch)
            b["frames"] = jnp.asarray(frames)
            return encdec_lib.loss_fn(p, cfg, b)
        vocab = cfg.vocab
    else:
        params = tf_lib.init_lm(key, cfg, dtype=jnp.float32).params
        vision = (np.zeros((global_batch, cfg.vision_tokens, cfg.d_model),
                           np.float32) if cfg.vision_tokens else None)

        def loss_fn(p, batch):
            b = dict(batch)
            if vision is not None:
                b["vision_embeds"] = jnp.asarray(vision)
            return tf_lib.loss_fn(p, cfg, b)
        vocab = cfg.vocab

    pipeline = make_pipeline(DataConfig(
        vocab=vocab, seq_len=seq_len, global_batch=global_batch,
        seed=seed, source="markov"))
    acct = _accountant(grid_mix)
    hb = (HeartbeatWriter(heartbeat_dir, host_id="host0")
          if heartbeat_dir else None)
    trainer = Trainer(
        loss_fn=loss_fn, params=params,
        opt_cfg=AdamWConfig(lr=warmup_cosine(lr, max(steps // 10, 1), steps)),
        train_cfg=TrainConfig(num_steps=steps, log_every=max(steps // 10, 1),
                              checkpoint_every=max(steps // 4, 1)),
        pipeline=pipeline,
        ckpt_cfg=(CheckpointConfig(directory=ckpt_dir) if ckpt_dir else None),
        accountant=acct, heartbeat=hb)
    return trainer


def _accountant(grid_mix: str) -> accounting.CarbonAccountant:
    return accounting.CarbonAccountant(accounting.AccountantConfig(
        device=runtime.accountant_device(), n_devices=jax.device_count(),
        grid_mix=grid_mix))


def build_engine(params, cfg, pipeline, *, steps: int,
                 steps_per_tick: int = 8, lr: float = 3e-3,
                 grid_mix: str = "NY") -> TrainEngine:
    """The fused TrainEngine as this launcher and ``chip_smoke.py`` build
    it: AdamW on a warmup-cosine schedule over ``steps``, billed to the
    device that runs it (``runtime.accountant_device``)."""
    return TrainEngine.for_lm(
        params, cfg,
        opt_cfg=AdamWConfig(lr=warmup_cosine(lr, max(steps // 10, 1), steps)),
        pipeline=pipeline,
        engine_cfg=TrainEngineConfig(steps_per_tick=steps_per_tick),
        accountant=_accountant(grid_mix))


def build_smoke_engine(arch_id: str, *, steps: int, grid_mix: str = "NY",
                       seed: int = 0, global_batch: int = 8,
                       seq_len: int = 64, steps_per_tick: int = 8,
                       lr: float = 3e-3) -> TrainEngine:
    """Fused-engine variant of build_smoke_trainer (DESIGN.md §13): same
    arch smoke config, data stream, and AdamW schedule, but the steps run
    through the device-resident TrainEngine tick with per-phase energy
    accounting. Decoder-only archs only (the engine's cost model and the
    flash-VJP routing are LM-shaped; encdec smokes stay on the Trainer)."""
    arch = cfgbase.get(arch_id)
    if arch.kind == "encdec":
        raise SystemExit(f"{arch_id}: encdec smoke runs use --engine loop")
    cfg = arch.make_smoke()
    params = tf_lib.init_lm(jax.random.PRNGKey(seed), cfg,
                            dtype=jnp.float32).params
    pipeline = make_pipeline(DataConfig(
        vocab=cfg.vocab, seq_len=seq_len, global_batch=global_batch,
        seed=seed, source="markov"))
    return build_engine(params, cfg, pipeline, steps=steps,
                        steps_per_tick=steps_per_tick, lr=lr,
                        grid_mix=grid_mix)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grid-mix", default="NY")
    ap.add_argument("--report", default=None, help="write accounting JSON")
    ap.add_argument("--engine", choices=("loop", "fused"), default="loop",
                    help="loop: host-loop Trainer (checkpoint/FT path); "
                         "fused: device-resident TrainEngine tick with "
                         "per-phase energy accounting (DESIGN.md §13)")
    ap.add_argument("--steps-per-tick", type=int, default=8,
                    help="fused engine: optimizer steps per jitted tick")
    args = ap.parse_args()

    if not args.smoke:
        raise SystemExit(
            "this launcher trains the reduced config only; pass --smoke. "
            "The fused tick at published widths runs on one TPU v5e through "
            "`python chip_smoke.py` (depth cut to fit the chip).")
    runtime.enable_compile_cache()

    if args.engine == "fused":
        eng = build_smoke_engine(args.arch, steps=args.steps,
                                 grid_mix=args.grid_mix,
                                 steps_per_tick=args.steps_per_tick)
        metrics = eng.run(args.steps)
        print("final metrics:", json.dumps(metrics))
        print("engine summary:", json.dumps(eng.summary()))
        rep = eng.accountant.report()
        print("carbon report:", json.dumps(rep, default=float))
        if args.report:
            with open(args.report, "w") as f:
                json.dump({"metrics": metrics, "summary": eng.summary(),
                           "carbon": rep}, f, default=float)
        return

    tr = build_smoke_trainer(args.arch, steps=args.steps,
                             ckpt_dir=args.ckpt_dir, grid_mix=args.grid_mix)
    tr.install_preemption_handler()
    if args.resume:
        restored = tr.maybe_restore()
        print(f"resume: {'restored step ' + str(tr.step_num) if restored else 'fresh'}")
    metrics = tr.run()
    print("final metrics:", json.dumps(metrics))
    if tr.accountant:
        rep = tr.accountant.report()
        print("carbon report:", json.dumps(rep, default=float))
        if args.report:
            with open(args.report, "w") as f:
                json.dump({"metrics": metrics, "carbon": rep}, f, default=float)


if __name__ == "__main__":
    main()
