"""Quickstart: train a tiny LM with live carbon accounting, then generate.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accounting
from repro.launch import runtime
from repro.data import DataConfig, make_pipeline
from repro.models import transformer as tf
from repro.optim import AdamWConfig
from repro.optim.schedules import warmup_cosine
from repro.serve import ServeConfig, ServeEngine
from repro.train import TrainConfig, Trainer


def main():
    cfg = tf.LMConfig(name="quickstart", d_model=96, n_heads=4, n_kv_heads=2,
                      d_ff=192, vocab=128, pattern=(tf.BlockSpec(),),
                      repeats=3, remat="none")
    params = tf.init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32).params

    acct = accounting.CarbonAccountant(accounting.AccountantConfig(
        device=runtime.accountant_device(), n_devices=jax.device_count(),
        grid_mix="CA"))
    trainer = Trainer(
        loss_fn=lambda p, b: tf.loss_fn(p, cfg, b),
        params=params,
        opt_cfg=AdamWConfig(lr=warmup_cosine(3e-3, 10, 100)),
        train_cfg=TrainConfig(num_steps=100, log_every=20),
        pipeline=make_pipeline(DataConfig(vocab=128, seq_len=64,
                                          global_batch=8, source="markov")),
        accountant=acct)
    print("training 100 steps on markov data...")
    trainer.run()
    for e in trainer.metrics_log:
        print(f"  step {e['step']:4d} loss {e['loss']:.3f} "
              f"({e['step_time_s']*1e3:.0f} ms/step)")

    print("\ncarbon report (the paper's holistic accounting, live):")
    for k, v in acct.report().items():
        print(f"  {k}: {v}")

    print("\ngreedy generation from the trained model:")
    eng = ServeEngine(trainer.params, cfg,
                      ServeConfig(max_slots=2, max_len=96,
                                  cache_dtype=jnp.float32))
    eng.submit(np.arange(8), max_tokens=12)
    for r in eng.run_until_drained():
        print(f"  prompt={list(r.prompt)} -> {r.generated}")


if __name__ == "__main__":
    main()
