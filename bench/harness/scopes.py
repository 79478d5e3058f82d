"""The train tick's device time by phase, and idle time by the program's
own host spans.

JAX names each operation of a compiled program after the scopes it was
traced in, and the compiled HLO text keeps that name as the ``op_name``
of the instruction's ``metadata``. Autodiff adds scopes of its own:
``jvp(...)`` for the forward pass and ``transpose(jvp(...))`` for the
backward pass, whose rematerialised forward sits inside the transpose.
The program runs its optimizer step under the scope ``adamw``. Each
device operation of the train tick in the trace takes the phase of its
instruction's ``op_name`` (:func:`phase`), and a phase's time is the sum
of its operations' self times, so a loop is not counted twice.

The program's host spans are ``train`` and ``serve`` (one step span per
tick) and ``train.*`` and ``serve.*`` inside them. An idle gap of the
device is put down to the innermost of those that covers its midpoint;
the profiler's frames of Python functions are left out, since they would
always be innermost. A program without these scopes and spans (the
optimizer unscoped, no ``train.*`` span) gives nothing to read.

The instruction names of the tick are read from its compiled HLO text:
the engine is built again after the run, as the run built it, and its
tick compiled for the run's shapes, which the persistent compilation
cache answers with the program that ran. The result is kept on the run
(``run.scopes``) for the readers that follow.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import re
from typing import Dict, Optional

from . import trace as trace_lib

TICK = "jit_tick"
PHASES = ("forward", "backward", "optimizer", "other")
# fp32 master, first and second moment each read and written, the bf16
# gradient read and the bf16 weight written: the least AdamW moves
ADAMW_BYTES_PER_PARAM = 28
_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%([A-Za-z0-9_.\-]+) = .*?\bmetadata=\{op_name="([^"]*)"',
    re.M)
_ADAMW = re.compile(r"(^|[/(])adamw([/)]|$)")
_PROGRAM_SPAN = re.compile(r"^(train|serve)(\.|$)")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name``, for every instruction of a
    compiled program's HLO text that carries one."""
    return dict(_OP_NAME.findall(hlo_text))


def phase(op_name: str) -> str:
    """``backward`` under a transpose, else ``optimizer`` under the
    ``adamw`` scope, else ``forward`` under a jvp, else ``other``."""
    if "transpose(" in op_name:
        return "backward"
    if _ADAMW.search(op_name):
        return "optimizer"
    if "jvp(" in op_name:
        return "forward"
    return "other"


def phase_ns(tr: trace_lib.Trace, names: Dict[str, str],
             module: str = TICK) -> Dict[str, int]:
    """Self time of the program ``module``'s operations in the window, by
    phase. An instruction missing from ``names`` counts as ``other``."""
    out = dict.fromkeys(PHASES, 0)
    for op in tr.ops:
        if op.module == module:
            out[phase(names.get(op.instr, ""))] += op.self_ns
    return out


def idle_by_program_span(tr: trace_lib.Trace) -> Dict[str, float]:
    """Device idle seconds in the window by the innermost program span
    over each gap's midpoint (``no host span`` where none is); empty when
    the trace holds no program span."""
    spans = [h for h in tr.host if _PROGRAM_SPAN.match(h[2])]
    if not spans:
        return {}
    return dict(trace_lib.idle_gaps(dataclasses.replace(tr, host=spans),
                                    n=None))


def train_tick_hlo(family, model: dict, mix: dict, seed: int) -> str:
    """Compiled HLO text of the train tick as a run of this configuration
    of the model family ``family`` and this mix builds it."""
    import jax
    import jax.numpy as jnp
    from . import program
    from .traffic import TrainTraffic
    train = model["train"]
    eng = program.train_engine(
        family.make_weights(model, seed, train["dtype"]),
        family.program_config(model),
        TrainTraffic(mix, seed, model["vocab_size"]), train)
    block = jax.ShapeDtypeStruct(
        (train["steps_per_tick"], mix["batch"], mix["seq_len"]), jnp.int32)
    text = eng._tick.lower(eng.params, eng.opt_state,
                           {"tokens": block, "labels": block}
                           ).compile().as_text()
    del eng
    gc.collect()
    return text


def train_scopes(run) -> Optional[Dict[str, str]]:
    """Instruction name -> ``op_name`` of the train tick the run traced, or
    ``None`` where the run holds no traced train tick or the compiled
    program is not the one that ran (its Pallas kernels sit at other
    instructions)."""
    if "scopes" in vars(run):
        return run.scopes
    run.scopes = None
    if (run.trace is None or not run.train.get("traced_steps")
            or TICK not in run.kernels):
        return None
    text = train_tick_hlo(run.family, run.model, run.mix, run.seed)
    if trace_lib.kernel_instructions(text) != run.kernels[TICK]:
        run.notes.append("scopes: the train tick compiled again is not the "
                         "program that ran; no phase is read")
        return None
    names = op_names(text)
    ns = phase_ns(run.trace, names)
    total = sum(ns.values())
    if not ns["optimizer"]:
        run.notes.append("scopes: no operation of the train tick sits under "
                         "the adamw scope; no phase is read")
        return None
    run.scopes = names
    run.notes.append(
        "scopes: train tick self time by phase (s): " + ", ".join(
            f"{k} {v / 1e9:.6f}" for k, v in ns.items())
        + f"; forward, backward and optimizer cover "
        f"{100.0 * (total - ns['other']) / total:.3f}% of it")
    _note_other(run, names)
    return names


def _note_other(run, names: Dict[str, str], n: int = 5) -> None:
    acc: Dict[str, int] = collections.Counter()
    for op in run.trace.ops:
        if op.module == TICK and phase(names.get(op.instr, "")) == "other":
            acc[names.get(op.instr, "?")] += op.self_ns
    if acc:
        run.notes.append("scopes: largest other: " + "; ".join(
            f"{k} {v / 1e9:.6f} s" for k, v in acc.most_common(n)))


def _phase_s(run, name: str) -> Optional[float]:
    names = train_scopes(run)
    if names is None or not run.peaks:
        return None
    t = phase_ns(run.trace, names)[name]
    return t / 1e9 if t else None


def train_phase_mfu(run, name: str) -> Optional[float]:
    """Model FLOPs of the traced steps' forward (a third of the family's
    ``train_flops_per_token``) or backward (the other two thirds)
    over that phase's self time at the chip's bf16 peak, in percent."""
    t = _phase_s(run, name)
    if t is None:
        return None
    share = {"forward": 1.0 / 3.0, "backward": 2.0 / 3.0}[name]
    f = share * run.family.train_flops_per_token(run.model,
                                                 run.mix["seq_len"])
    return 100.0 * f * run.train["traced_tokens"] / (
        t * run.peaks["bf16_flops"])


def adamw_hbm_share(run) -> Optional[float]:
    """The least bytes of the traced steps' AdamW updates over the
    optimizer's self time at the chip's HBM peak, in percent."""
    t = _phase_s(run, "optimizer")
    if t is None:
        return None
    byt = (ADAMW_BYTES_PER_PARAM * run.family.param_count(run.model)
           * run.train["traced_steps"])
    return 100.0 * byt / (t * run.peaks["hbm_bytes_per_s"])


def span_idle_share(run, span: str) -> Optional[float]:
    """Device idle time whose innermost program span is ``span``, over the
    traced window, in percent."""
    if run.trace is None or not run.trace.window_ns:
        return None
    idle = idle_by_program_span(run.trace)
    if not idle:
        return None
    return 100.0 * idle.get(span, 0.0) / (run.trace.window_ns / 1e9)
