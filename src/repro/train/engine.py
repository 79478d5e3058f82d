"""Device-resident training engine: the serve core's discipline for training.

One jitted **train tick** does everything on device: forward, backward
(through the custom-VJP Pallas kernels when the model config routes
attention through them — DESIGN.md §13), the AdamW update, and metric
accumulation, scanned over ``steps_per_tick`` optimizer steps. Params and
optimizer state are donated and never leave the device; the host stages one
stacked batch block per tick and reads back ONE compact metrics pytree
(per-step loss/grad-norm/lr) per tick — not per step. Step time is therefore
a property of the hardware, not of Python dispatch, loss-readback syncs, or
per-step batch staging (the host-loop Trainer in train/loop.py is exactly
that baseline, and stays on as the correctness oracle and the benchmark's
"before").

Every tick produces a :class:`TrainStepMetrics` billed to the
CarbonAccountant's *training* ledger: forward and backward FLOPs/bytes land
in separate phase accounts (models/costing.py is the shared cost model), so
J/step and J/sample — with the backward phase reported separately — sit next
to the serve path's J/token.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accounting, energy
from repro.optim import AdamWConfig, apply_updates, init_opt_state

PyTree = Any
LossFn = Callable[[PyTree, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict]]


@dataclasses.dataclass
class TrainEngineConfig:
    # optimizer steps fused into one jitted tick (the scan length): Python
    # dispatch, donation bookkeeping, and the metrics readback amortize over
    # this many steps
    steps_per_tick: int = 8
    donate: bool = True
    # route full-sequence attention through the custom-VJP flash Pallas
    # kernel (kernels/flash_attention.py). None = auto: on for TPU backends,
    # off elsewhere (interpret mode is correctness-only). Only meaningful
    # via for_lm(), which stamps it into the model config.
    use_flash_vjp: Optional[bool] = None


@dataclasses.dataclass
class TrainStepMetrics:
    """What one train tick did — the unit core/accounting.py bills.

    The modeled phase terms come from the engine's TrainStepCost (one
    step's cost scaled by ``steps``); forward and backward stay separate so
    the accountant can report per-phase energy (DESIGN.md §13).
    """
    steps: int                  # optimizer steps in this tick
    tokens: int                 # label tokens consumed
    samples: int                # sequences consumed
    wall_s: float               # host wall time of the tick (incl. staging)
    loss: float                 # last step's loss
    loss_mean: float            # mean loss over the tick
    grad_norm: float            # last step's global grad norm
    fwd_flops: float = 0.0
    bwd_flops: float = 0.0
    fwd_bytes: float = 0.0
    bwd_bytes: float = 0.0
    opt_bytes: float = 0.0

    @property
    def flops(self) -> float:
        return self.fwd_flops + self.bwd_flops

    @property
    def bytes_moved(self) -> float:
        return self.fwd_bytes + self.bwd_bytes + self.opt_bytes


# TrainStepMetrics fields that are deliberately NOT energy channels —
# training-quality telemetry (loss curves, gradient norms) with no joule
# interpretation. Everything else MUST be billed in
# CarbonAccountant.observe_train; the accounting-completeness lint pass
# (repro-lint L401, DESIGN.md §20) fails CI otherwise.
TRAIN_ACCOUNTING_EXEMPT = frozenset({"loss", "loss_mean", "grad_norm"})


class TrainEngine:
    def __init__(self, *, loss_fn: LossFn, params: PyTree,
                 opt_cfg: AdamWConfig,
                 engine_cfg: Optional[TrainEngineConfig] = None,
                 pipeline=None,
                 accountant: Optional[accounting.CarbonAccountant] = None,
                 cost: Optional[energy.TrainStepCost] = None,
                 jit_kwargs: Optional[dict] = None):
        self.loss_fn = loss_fn
        self.params = params
        self.opt_cfg = opt_cfg
        self.cfg = engine_cfg or TrainEngineConfig()
        self.pipeline = pipeline
        self.accountant = accountant
        self.cost = cost
        self.opt_state = init_opt_state(params, opt_cfg)
        self.step_num = 0
        self.last_metrics: Optional[TrainStepMetrics] = None
        self.metrics_log: List[TrainStepMetrics] = []
        # instrumentation (tests assert the tick stays fused: one trace per
        # scan length, one host readback per tick)
        self.tick_trace_count = 0
        self.host_readbacks = 0
        self._build_tick(jit_kwargs)

    @classmethod
    def for_lm(cls, params: PyTree, cfg, *, opt_cfg: AdamWConfig,
               pipeline, engine_cfg: Optional[TrainEngineConfig] = None,
               accountant: Optional[accounting.CarbonAccountant] = None,
               jit_kwargs: Optional[dict] = None) -> "TrainEngine":
        """LM-aware constructor: stamps the flash-VJP routing into the model
        config, builds the loss closure, and derives the per-step cost model
        from the live param/opt-state trees."""
        from repro.models import costing
        from repro.models import transformer as tf_lib
        ecfg = engine_cfg or TrainEngineConfig()
        use_flash = ecfg.use_flash_vjp
        if use_flash is None:
            use_flash = jax.default_backend() == "tpu"
        mcfg = dataclasses.replace(cfg, flash_train=bool(use_flash))

        def loss_fn(p, batch):
            return tf_lib.loss_fn(p, mcfg, batch)

        eng = cls(loss_fn=loss_fn, params=params, opt_cfg=opt_cfg,
                  engine_cfg=ecfg, pipeline=pipeline, accountant=accountant,
                  jit_kwargs=jit_kwargs)
        eng.model_cfg = mcfg
        if pipeline is not None:
            eng.cost = costing.lm_train_step_cost(
                params, mcfg, batch=pipeline.cfg.local_batch,
                seq_len=pipeline.cfg.seq_len, opt_state=eng.opt_state)
        return eng

    # -- compiled path --------------------------------------------------------

    def _build_tick(self, jit_kwargs: Optional[dict]) -> None:
        loss_fn, opt_cfg = self.loss_fn, self.opt_cfg

        def tick(params, opt_state, batches):
            self.tick_trace_count += 1      # python side effect: trace count

            def one(carry, batch):
                p, s = carry
                (loss, _aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, batch)
                p, s, om = apply_updates(p, grads, s, opt_cfg)
                out = {"loss": loss, "grad_norm": om["grad_norm"],
                       "lr": om["lr"]}
                return (p, s), out

            (params, opt_state), ms = jax.lax.scan(
                one, (params, opt_state), batches)
            return params, opt_state, ms

        kwargs = dict(jit_kwargs or {})
        if self.cfg.donate:
            kwargs.setdefault("donate_argnums", (0, 1))
        self._tick = jax.jit(tick, **kwargs)

    # -- host loop ------------------------------------------------------------

    def _stage(self, start: int, k: int) -> Tuple[Dict[str, jnp.ndarray],
                                                  int, int]:
        """Stack pipeline batches [start, start+k) into one (k, ...) block."""
        batches = [self.pipeline.batch_at(start + i) for i in range(k)]
        stacked = {key: jnp.asarray(np.stack([b[key] for b in batches]))
                   for key in batches[0]}
        tok = batches[0].get("labels", batches[0].get("tokens"))
        samples = k * int(tok.shape[0])
        tokens = k * int(tok.size)
        return stacked, tokens, samples

    def run(self, num_steps: int) -> Dict[str, float]:
        """Run ``num_steps`` optimizer steps in fused ticks.

        Staging is double-buffered: tick i+1's batch block is synthesized
        and staged while the device is still crunching tick i (dispatch is
        async; the metrics readback is the only sync point, after staging).
        The host-loop Trainer pays stage -> dispatch -> sync serially every
        step; here the pipeline cost hides behind device compute.

        In a profile each tick is the step span ``train`` (numbered by
        tick) holding ``train.dispatch``, ``train.stage``,
        ``train.readback`` and ``train.account``; the first block's staging
        before the loop is a ``train.stage`` span of its own.
        """
        assert self.pipeline is not None, "run() needs a pipeline"
        if num_steps <= 0:
            return {}
        plan: List[int] = []
        left = num_steps
        while left > 0:
            k = min(self.cfg.steps_per_tick, left)
            plan.append(k)
            left -= k
        last: Dict[str, float] = {}
        t_prev = time.monotonic()
        with jax.profiler.TraceAnnotation("train.stage"):
            staged = self._stage(self.step_num, plan[0])
        for i, k in enumerate(plan):
            # one step span per tick in a profile, numbered by tick
            with jax.profiler.StepTraceAnnotation(
                    "train", step_num=len(self.metrics_log)):
                batches, tokens, samples = staged
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    self.params, self.opt_state, ms = self._tick(
                        self.params, self.opt_state, batches)
                if i + 1 < len(plan):   # overlap: stage while the device runs
                    with jax.profiler.TraceAnnotation("train.stage"):
                        staged = self._stage(self.step_num + k, plan[i + 1])
                with jax.profiler.TraceAnnotation("train.readback"):
                    ms_host = jax.device_get(ms)    # the ONE per-tick readback
                self.host_readbacks += 1
                with jax.profiler.TraceAnnotation("train.account"):
                    now = time.monotonic()
                    wall = now - t_prev
                    t_prev = now
                    self.step_num += k
                    self.pipeline.restore({"step": self.step_num})
                    c = (self.cost.scaled(k) if self.cost is not None
                         else energy.TrainStepCost(0.0, 0.0, 0.0, 0.0))
                    m = TrainStepMetrics(
                        steps=k, tokens=tokens, samples=samples, wall_s=wall,
                        loss=float(ms_host["loss"][-1]),
                        loss_mean=float(np.mean(ms_host["loss"])),
                        grad_norm=float(ms_host["grad_norm"][-1]),
                        fwd_flops=c.fwd_flops, bwd_flops=c.bwd_flops,
                        fwd_bytes=c.fwd_bytes, bwd_bytes=c.bwd_bytes,
                        opt_bytes=c.opt_bytes)
                    self.last_metrics = m
                    self.metrics_log.append(m)
                    if self.accountant is not None:
                        self.accountant.observe_train(m)
                    last = {"loss": m.loss, "grad_norm": m.grad_norm,
                            "lr": float(ms_host["lr"][-1]),
                            "step": float(self.step_num)}
        return last

    # -- aggregate metrics ----------------------------------------------------

    def summary(self) -> Dict[str, float]:
        steps = sum(m.steps for m in self.metrics_log)
        wall = sum(m.wall_s for m in self.metrics_log)
        return {"ticks": len(self.metrics_log),
                "steps": steps,
                "tokens": sum(m.tokens for m in self.metrics_log),
                "wall_s": wall,
                "steps_per_s": steps / wall if wall > 0 else 0.0,
                "s_per_step": wall / steps if steps else 0.0}
