"""Energy-accounting regression for the training fast path (DESIGN.md §13).

Pins the cost model: training StepMetrics byte/FLOP totals must match
hand-computed values for a tiny config, and the accountant must report
backward-phase energy separately from (and, with the documented 2x FLOPs
ratio, larger than) the forward phase.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import accounting, energy
from repro.core import hw
from repro.data import DataConfig, make_pipeline
from repro.models import costing
from repro.models import transformer as tf_lib
from repro.optim import AdamWConfig, init_opt_state
from repro.train import TrainEngine, TrainEngineConfig

# tiny config, small enough to hand-count every matmul weight
D, H, KV, DFF, VOCAB, SEQ, BATCH = 16, 2, 2, 32, 32, 8, 2


def _cfg():
    return tf_lib.LMConfig(name="tiny", d_model=D, n_heads=H, n_kv_heads=KV,
                           d_ff=DFF, vocab=VOCAB,
                           pattern=(tf_lib.BlockSpec(),), repeats=1,
                           remat="none", vocab_pad_multiple=1)


def _params(cfg):
    return tf_lib.init_lm(jax.random.PRNGKey(0), cfg,
                          dtype=jnp.float32).params


def _hand_matmul_elems(cfg):
    """Every matmul weight in the one-block model, counted by hand:
    wq/wk/wv (D*D each: head_dim = D/H, H heads), wo (D*D), gated MLP
    (3 * D*DFF), plus the tied unembedding (VOCAB*D)."""
    head = cfg.resolved_head_dim
    attn = cfg.d_model * cfg.n_heads * head * 2          # wq + wo
    attn += cfg.d_model * cfg.n_kv_heads * head * 2      # wk + wv
    mlp = 3 * cfg.d_model * cfg.d_ff                     # w_in, w_gate, w_out
    unembed = cfg.vocab * cfg.d_model                    # tied embedding
    return attn + mlp + unembed


class TestCostModel:
    def test_matmul_elems_match_hand_count(self):
        cfg = _cfg()
        params = _params(cfg)
        assert costing.matmul_weight_elems(params, cfg) == \
            _hand_matmul_elems(cfg)

    def test_step_cost_matches_hand_computed(self):
        cfg = _cfg()
        params = _params(cfg)
        opt_state = init_opt_state(params, AdamWConfig(lr=1e-3))
        cost = costing.lm_train_step_cost(params, cfg, batch=BATCH,
                                          seq_len=SEQ, opt_state=opt_state)
        tokens = BATCH * SEQ
        w = _hand_matmul_elems(cfg)
        attn_dims = cfg.n_heads * cfg.resolved_head_dim
        # forward: 2 FLOPs per weight element per token + the causal
        # attention term 2 * n_attn_layers * (H*Dh) * S per token
        fwd = (2.0 * w + 2.0 * 1 * attn_dims * SEQ) * tokens
        assert cost.fwd_flops == pytest.approx(fwd)
        assert cost.bwd_flops == pytest.approx(2.0 * fwd)
        weight_bytes = sum(l.nbytes for l in jax.tree.leaves(params))
        n_params = sum(l.size for l in jax.tree.leaves(params))
        grad_bytes = 4.0 * n_params
        opt_bytes = sum(l.nbytes for l in jax.tree.leaves(opt_state))
        assert cost.fwd_bytes == pytest.approx(weight_bytes)
        assert cost.bwd_bytes == pytest.approx(weight_bytes + grad_bytes)
        assert cost.opt_bytes == pytest.approx(
            grad_bytes + 2.0 * opt_bytes + 2.0 * weight_bytes)
        assert cost.tokens == tokens and cost.samples == BATCH

    def test_scaled(self):
        c = energy.TrainStepCost(1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0)
        s = c.scaled(3)
        assert (s.fwd_flops, s.bwd_flops, s.fwd_bytes, s.bwd_bytes,
                s.opt_bytes, s.tokens, s.samples) == \
            (3.0, 6.0, 9.0, 12.0, 15.0, 18.0, 21.0)


class TestPhaseEnergy:
    def test_phase_split_formula(self):
        cost = energy.TrainStepCost(fwd_flops=1e9, bwd_flops=2e9,
                                    fwd_bytes=1e6, bwd_bytes=3e6,
                                    opt_bytes=2e6)
        ph = energy.train_phase_energy_j(cost)
        spec = hw.TPU_V5E
        assert ph["fwd_j"] == pytest.approx(
            1e9 * spec.power.active_w / spec.peak_flops
            + energy.dram_energy_j(1e6))
        assert ph["bwd_j"] == pytest.approx(
            2e9 * spec.power.active_w / spec.peak_flops
            + energy.dram_energy_j(3e6))
        assert ph["opt_j"] == pytest.approx(energy.dram_energy_j(2e6))
        assert ph["total_j"] == pytest.approx(
            ph["fwd_j"] + ph["bwd_j"] + ph["opt_j"])


class TestAccountantTrainLedger:
    def _run(self, steps=4, tick=2):
        cfg = _cfg()
        acct = accounting.CarbonAccountant(accounting.AccountantConfig(
            device="tpu_v5e", n_devices=1))
        eng = TrainEngine.for_lm(
            _params(cfg), cfg, opt_cfg=AdamWConfig(lr=1e-3),
            pipeline=make_pipeline(DataConfig(
                vocab=VOCAB, seq_len=SEQ, global_batch=BATCH,
                source="markov")),
            engine_cfg=TrainEngineConfig(steps_per_tick=tick),
            accountant=acct)
        eng.run(steps)
        return eng, acct

    def test_totals_are_per_step_cost_times_steps(self):
        eng, acct = self._run(steps=4, tick=2)
        rep = acct.train_report()
        c = eng.cost
        assert rep["steps"] == 4
        assert rep["fwd_flops"] == pytest.approx(4 * c.fwd_flops)
        assert rep["bwd_flops"] == pytest.approx(4 * c.bwd_flops)
        assert rep["fwd_bytes"] == pytest.approx(4 * c.fwd_bytes)
        assert rep["bwd_bytes"] == pytest.approx(4 * c.bwd_bytes)
        assert rep["opt_bytes"] == pytest.approx(4 * c.opt_bytes)
        assert rep["samples"] == 4 * BATCH

    def test_backward_reported_separately_and_dominates(self):
        _, acct = self._run()
        rep = acct.train_report()
        assert rep["bwd_j"] > rep["fwd_j"] > 0
        assert rep["bwd_fwd_ratio"] > 1.5
        assert rep["j_per_step"] == pytest.approx(rep["total_j"] / 4)
        assert rep["j_per_sample"] == pytest.approx(
            rep["total_j"] / rep["samples"])

    def test_train_ledger_in_full_report_and_grand_totals(self):
        eng, acct = self._run(steps=2, tick=2)
        rep = acct.report()
        assert "train" in rep
        c = eng.cost.scaled(2)
        assert rep["bytes_moved"] == pytest.approx(
            c.fwd_bytes + c.bwd_bytes + c.opt_bytes)
        assert rep["modeled_flops"] == pytest.approx(
            c.fwd_flops + c.bwd_flops)
        assert rep["tokens"] == 2 * BATCH * SEQ

    def test_no_train_block_without_training(self):
        acct = accounting.CarbonAccountant(accounting.AccountantConfig(
            device="tpu_v5e"))
        assert acct.train_report() is None
        assert "train" not in acct.report()
