"""Fused TrainEngine tick vs the host-loop reference step (train/loop.py).

The contract: one engine tick scanning K optimizer steps must be
step-identical (loss + param update within per-dtype tolerance — bit-exact
on CPU fp32) to K iterations of make_train_step, and training through the
engine must actually learn (loss decreases over 20 steps on the Markov
stream).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accounting
from repro.data import DataConfig, make_pipeline
from repro.models import transformer as tf_lib
from repro.optim import AdamWConfig, init_opt_state
from repro.train import (TrainEngine, TrainEngineConfig, make_train_step)

VOCAB, SEQ, BATCH = 64, 16, 4


def _cfg(**kw):
    base = dict(name="t", d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                vocab=VOCAB, pattern=(tf_lib.BlockSpec(),), repeats=2,
                remat="none", vocab_pad_multiple=1)
    base.update(kw)
    return tf_lib.LMConfig(**base)


def _params(cfg, seed=0):
    return tf_lib.init_lm(jax.random.PRNGKey(seed), cfg,
                          dtype=jnp.float32).params


def _pipe(seed=0):
    return make_pipeline(DataConfig(vocab=VOCAB, seq_len=SEQ,
                                    global_batch=BATCH, seed=seed,
                                    source="markov"))


def _engine(cfg, opt, k, **kw):
    return TrainEngine.for_lm(_params(cfg), cfg, opt_cfg=opt,
                              pipeline=_pipe(),
                              engine_cfg=TrainEngineConfig(steps_per_tick=k),
                              **kw)


class TestStepParity:
    def test_tick_matches_loop_steps(self):
        """One fused 6-step tick == six host-loop reference steps."""
        cfg = _cfg()
        opt = AdamWConfig(lr=2e-3)
        eng = _engine(cfg, opt, k=6)
        last = eng.run(6)

        step = jax.jit(make_train_step(
            lambda p, b: tf_lib.loss_fn(p, cfg, b), opt))
        params = _params(cfg)
        state = init_opt_state(params, opt)
        pipe = _pipe()
        losses = []
        for i in range(6):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            params, state, metrics = step(params, state, batch)
            losses.append(float(metrics["loss"]))

        assert last["loss"] == pytest.approx(losses[-1], rel=1e-6)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                             eng.params, params)
        assert max(jax.tree.leaves(diffs)) <= 1e-6
        sdiff = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            eng.opt_state["m"], state["m"])
        assert max(jax.tree.leaves(sdiff)) <= 1e-6

    def test_partial_tick_and_multi_tick_agree(self):
        """12 steps as 3 ticks of 4 == 12 steps as 2 ticks of 8+4 (the
        remainder tick compiles separately but computes the same stream)."""
        cfg = _cfg()
        opt = AdamWConfig(lr=1e-3)
        a = _engine(cfg, opt, k=4)
        a.run(12)
        b = _engine(cfg, opt, k=8)
        b.run(12)
        diffs = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(x - y))),
                             a.params, b.params)
        assert max(jax.tree.leaves(diffs)) <= 1e-6
        assert a.step_num == b.step_num == 12

    def test_tick_stays_fused(self):
        """One trace per scan length; one host readback per tick."""
        cfg = _cfg()
        eng = _engine(cfg, AdamWConfig(lr=1e-3), k=4)
        eng.run(8)          # 2 ticks, same scan length
        assert eng.tick_trace_count == 1
        assert eng.host_readbacks == 2
        eng.run(2)          # remainder tick: one new trace
        assert eng.tick_trace_count == 2
        assert eng.host_readbacks == 3

    def test_bf16_params_with_fp32_master_donate_cleanly(self):
        """bf16 weights keep fp32 norm scales; their fp32 master must be a
        copy, or the donated tick would receive one buffer twice."""
        cfg = _cfg()
        params = tf_lib.init_lm(jax.random.PRNGKey(0), cfg,
                                dtype=jnp.bfloat16).params
        assert {p.dtype for p in jax.tree.leaves(params)} == {
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
        eng = TrainEngine.for_lm(
            params, cfg, opt_cfg=AdamWConfig(lr=1e-3), pipeline=_pipe(),
            engine_cfg=TrainEngineConfig(steps_per_tick=2))
        eng.run(4)
        assert np.isfinite(eng.last_metrics.loss)


class TestLearning:
    def test_loss_decreases_over_20_steps(self):
        cfg = _cfg()
        eng = _engine(cfg, AdamWConfig(lr=5e-3), k=5)
        eng.run(20)
        first = eng.metrics_log[0]
        last = eng.metrics_log[-1]
        assert last.loss < first.loss_mean - 0.1, (
            first.loss_mean, last.loss)

    def test_schedule_advances_across_ticks(self):
        """The lr schedule sees the global step, not the within-tick step."""
        from repro.optim.schedules import warmup_cosine
        cfg = _cfg()
        opt = AdamWConfig(lr=warmup_cosine(1e-2, 10, 40))
        eng = _engine(cfg, opt, k=4)
        r1 = eng.run(4)
        r2 = eng.run(4)
        assert 0 < r1["lr"] < r2["lr"]   # still in warmup, monotonic


class TestMetricsAndAccounting:
    def test_metrics_and_accountant_billing(self):
        cfg = _cfg()
        acct = accounting.CarbonAccountant(accounting.AccountantConfig(
            device="tpu_v5e", n_devices=1))
        eng = _engine(cfg, AdamWConfig(lr=1e-3), k=4, accountant=acct)
        eng.run(8)
        assert len(eng.metrics_log) == 2
        m = eng.metrics_log[0]
        assert m.steps == 4
        assert m.tokens == 4 * BATCH * SEQ
        assert m.samples == 4 * BATCH
        assert m.fwd_flops > 0 and m.bwd_flops == 2.0 * m.fwd_flops
        assert m.bytes_moved > 0
        rep = acct.train_report()
        assert rep["steps"] == 8
        assert rep["fwd_flops"] == pytest.approx(2 * m.fwd_flops)
        s = eng.summary()
        assert s["steps"] == 8 and s["ticks"] == 2

    def test_run_requires_pipeline(self):
        cfg = _cfg()
        eng = TrainEngine(
            loss_fn=lambda p, b: tf_lib.loss_fn(p, cfg, b),
            params=_params(cfg), opt_cfg=AdamWConfig(lr=1e-3))
        with pytest.raises(AssertionError):
            eng.run(1)


class TestFlashVjpRoute:
    def test_engine_flash_vjp_matches_sdpa_engine(self):
        """The engine with flash-VJP attention (interpret mode) computes the
        same updates as the sdpa engine — the kernel route is numerics-
        neutral end to end."""
        cfg = _cfg(repeats=1)
        opt = AdamWConfig(lr=2e-3)
        ref = _engine(cfg, opt, k=2)
        ref.run(2)
        fast = TrainEngine.for_lm(
            _params(cfg), cfg, opt_cfg=opt, pipeline=_pipe(),
            engine_cfg=TrainEngineConfig(steps_per_tick=2,
                                         use_flash_vjp=True))
        assert fast.model_cfg.flash_train
        fast.run(2)
        diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                             ref.params, fast.params)
        assert max(jax.tree.leaves(diffs)) <= 2e-5
