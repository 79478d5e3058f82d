"""A training cell: set-up builds the train engine and drives it through
its first tick (the first ``steps_per_tick`` optimizer steps, on the
window's own call and feed); the window then runs whole ticks of the
same engine, ``ticks_per_call`` of the mix at a time, as an on-line
trainer hands its engine a stretch of its stream. A traced run keeps the
window's first calls, ``TRACE_TICKS`` ticks or more, in its trace. After
the window the reference repeats the first tick's steps from the same
weights and rows.

Numbers compared (each against the reference's, by the worst leaf where
there are leaves; a stacked layer counts each layer as a leaf):

* ``loss_gap``: largest relative gap of a step's loss;
* ``moment_gap``: gap of the norms of the first AdamW moment (the
  gradients as the optimizer received them) after the first tick;
* ``update_gap``: gap of the norms of the masters' change over the first
  tick.

A norm's gap is measured against the larger of the reference's norm of
that leaf and of the median leaf. Leaves whose reference gradient at the
first step is under a thousandth of the median leaf's move by round-off
alone and are left out of both norm gaps.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, Optional

import numpy as np

from . import program
from .common import CompileLog, Run
from .traffic import TrainTraffic

# ticks a traced run keeps in its trace at the least (about 5 s); the
# per-layer metrics read those ticks alone
TRACE_TICKS = 10


def train_run(cell: dict, family, model: dict, mix: dict, seed: int,
              seconds: float, trace_dir: Optional[str], t_proc: float,
              device, fault=None) -> Run:
    import jax
    from . import reference
    train = model["train"]
    clog = CompileLog()
    cfg = family.program_config(model)
    feed = TrainTraffic(mix, seed, model["vocab_size"])
    k = train["steps_per_tick"]
    eng = program.train_engine(
        family.make_weights(model, seed, train["dtype"]), cfg, feed, train)
    if fault is not None:
        fault(eng)
    ticks = program.TickLog(eng)
    eng.run(k)
    first = jax.device_get(ticks.outs[0])
    mom, master = program.opt_state_view(eng)
    w0 = family.make_weights(model, seed, train["dtype"])
    prog = {
        "loss": [float(x) for x in first["loss"]],
        "grad_norm": [float(x) for x in first["grad_norm"]],
        "moment": reference.leaf_norms(mom),
        "change": reference.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(np.float32) - b.astype(np.float32),
            master, w0)),
    }
    del w0, mom, master
    kernels = (program.train_kernels(eng, k, mix["batch"], mix["seq_len"])
               if trace_dir else {})
    ticks.outs.clear()
    window_span = None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    compiles0 = clog.compiles
    setup_s = time.perf_counter() - t_proc
    t0 = time.perf_counter()
    per_call = mix["ticks_per_call"]
    n_ticks = traced = 0
    walls = []
    while time.perf_counter() - t0 < seconds:
        c0 = time.perf_counter()
        eng.run(k * per_call)
        walls.append(time.perf_counter() - c0)
        n_ticks += per_call
        if window_span is not None and n_ticks >= TRACE_TICKS:
            window_span, traced = _stop_trace(window_span), n_ticks
    window = time.perf_counter() - t0
    compiles = clog.compiles - compiles0
    if window_span is not None:
        window_span, traced = _stop_trace(window_span), n_ticks
    peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    losses = [float(x) for ms in jax.device_get(ticks.outs)
              for x in ms["loss"]]
    del eng, ticks
    gc.collect()
    run = Run(cell=cell, model=model, mix=mix, seed=seed, window_s=window,
              setup_s=setup_s, compiles_in_window=compiles,
              memory_peak=peak, kernels=kernels, family=family)
    run.attempted = n_ticks * k
    run.failed = sum(1 for x in losses if not math.isfinite(x))
    run.train = {"ticks": n_ticks, "steps": n_ticks * k,
                 "tokens": n_ticks * k * mix["batch"] * mix["seq_len"],
                 "traced_steps": traced * k,
                 "traced_tokens": traced * k * mix["batch"] * mix["seq_len"]}
    run.program_train = prog
    run.notes.append(
        f"{len(walls)} calls of {per_call} ticks: median "
        f"{float(np.median(walls)) if walls else None} s, slowest "
        f"{max(walls) if walls else None} s")
    run.notes.append(
        f"{n_ticks} ticks of {k} steps in {window:.6f} s; losses in the "
        f"window from {losses[0] if losses else None} to "
        f"{losses[-1] if losses else None}; first tick losses "
        f"{prog['loss']}, global gradient norms {prog['grad_norm']}")
    return run


def _stop_trace(span) -> None:
    import jax
    span.__exit__(None, None, None)
    jax.profiler.stop_trace()


def train_check(run: Run, model: dict, seed: int, control: bool = False
                ) -> Dict[str, float]:
    """The first tick's numbers against the reference's: the AdamW loop
    over the family's reference loss. ``control``: the reference with
    int8 weights (:func:`reference.fake_int8`) in the program's place."""
    from . import reference
    train, mix, family = model["train"], run.mix, run.family
    feed = TrainTraffic(mix, seed, model["vocab_size"])
    batches = [feed.batch_at(i) for i in range(train["steps_per_tick"])]
    w = family.make_weights(model, seed, train["dtype"])
    ref = reference.train_steps(family.loss, w, model, train, batches)
    prog = run.program_train
    if control:
        prog = reference.train_steps(family.loss, w, model, train, batches,
                                     quant=True)
        run.notes.append(f"int8 control losses {prog['loss']}")
    run.notes.append(f"reference losses {ref['loss']}, global gradient "
                     f"norms {ref['grad_norm']}")
    return compare_train(prog, ref, run.notes)


def compare_train(prog: dict, ref: dict, notes: list) -> Dict[str, float]:
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    grad = ref["first_grad"]
    floor = 1e-3 * float(np.median(list(grad.values())))
    kept = [name for name, g in grad.items() if g >= floor]
    dropped = sorted(set(grad) - set(kept))
    if dropped:
        notes.append(f"left out (reference gradient under a thousandth of "
                     f"the median leaf's): {dropped}")
    out = {"loss_gap": loss_gap}
    for key, label in (("moment", "moment_gap"), ("change", "update_gap")):
        r, p = ref[key], prog[key]
        med = float(np.median([r[n] for n in kept]))
        worst, at = 0.0, None
        for n in kept:
            g = abs(p[n] - r[n]) / max(r[n], med)
            if g > worst:
                worst, at = g, n
        out[label] = worst
        notes.append(f"{label} worst leaf {at}: program {p.get(at)}, "
                     f"reference {r.get(at)}, median leaf {med}")
    return out
