"""End-to-end training driver at ~100M scale (deliverable b).

On a TPU fleet this trains a ~100M-param gemma3-family model for a few
hundred steps with the full production stack (sharding, checkpointing,
heartbeats, carbon accounting). On this CPU container the same driver runs
with ``--cpu-scale`` (a ~2M model, identical code path); the 100M config's
distribution story is proven by `repro.launch.dryrun`.

    PYTHONPATH=src python examples/train_e2e.py --cpu-scale --steps 60
"""

import argparse
import json
import tempfile

import jax
import jax.numpy as jnp

from repro.core import accounting
from repro.launch import runtime
from repro.data import DataConfig, make_pipeline
from repro.models import transformer as tf
from repro.optim import AdamWConfig
from repro.optim.schedules import warmup_cosine
from repro.checkpoint import CheckpointConfig
from repro.train import TrainConfig, Trainer
from repro.train.ft import HeartbeatWriter


def model_100m() -> tf.LMConfig:
    """~100M params: 12L, d=768, gemma3-style 5:1 local:global pattern."""
    local, glob = tf.BlockSpec(window=256), tf.BlockSpec(window=-1)
    return tf.LMConfig(name="e2e-100m", d_model=768, n_heads=12,
                       n_kv_heads=4, d_ff=3072, vocab=32768,
                       pattern=(local,) * 5 + (glob,), repeats=2,
                       act="gelu", remat="none")


def model_cpu() -> tf.LMConfig:
    local, glob = tf.BlockSpec(window=64), tf.BlockSpec(window=-1)
    return tf.LMConfig(name="e2e-cpu", d_model=128, n_heads=4, n_kv_heads=2,
                       d_ff=256, vocab=512, pattern=(local, glob), repeats=2,
                       act="gelu", remat="none")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--cpu-scale", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grid-mix", default="NY")
    args = ap.parse_args()

    cfg = model_cpu() if args.cpu_scale else model_100m()
    params = tf.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32).params
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{cfg.n_layers} layers")

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="e2e_ckpt_")
    hb_dir = tempfile.mkdtemp(prefix="e2e_hb_")
    acct = accounting.CarbonAccountant(accounting.AccountantConfig(
        device=runtime.accountant_device(), n_devices=jax.device_count(),
        grid_mix=args.grid_mix))
    trainer = Trainer(
        loss_fn=lambda p, b: tf.loss_fn(p, cfg, b),
        params=params,
        opt_cfg=AdamWConfig(lr=warmup_cosine(3e-3, args.steps // 10,
                                             args.steps)),
        train_cfg=TrainConfig(num_steps=args.steps,
                              log_every=max(args.steps // 10, 1),
                              checkpoint_every=max(args.steps // 4, 1),
                              grad_accum=1),
        pipeline=make_pipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                          global_batch=args.batch,
                                          source="markov")),
        ckpt_cfg=CheckpointConfig(directory=ckpt_dir, keep_last=2),
        accountant=acct,
        heartbeat=HeartbeatWriter(hb_dir, host_id="host0"))
    trainer.install_preemption_handler()
    resumed = trainer.maybe_restore()
    print(f"{'resumed from step ' + str(trainer.step_num) if resumed else 'fresh start'}; "
          f"training {args.steps} steps...")
    trainer.run()
    for e in trainer.metrics_log:
        print(f"  step {e['step']:5d} loss={e['loss']:.3f} "
              f"gnorm={e.get('grad_norm', 0):.2f} "
              f"({e['step_time_s']*1e3:.0f} ms)")
    trainer.save(wait=True)
    print(f"checkpoints in {ckpt_dir}: latest step {trainer.ckpt.latest_step()}")
    print("carbon report:", json.dumps(acct.report(), default=float, indent=2))


if __name__ == "__main__":
    main()
