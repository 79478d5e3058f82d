"""The phase split and the idle time by program span, on a made compiled
program and a made trace whose times are known by hand
(``data/scopes_trace.pbtxt``: two runs of the train tick, 30 and 6 us,
in a 40 us window; the first holds a loop over a forward, a loss, a
backward loop and an AdamW fusion, then a copy and a second AdamW
fusion; the host's main thread holds two ticks' program spans, with
Python frames innermost over both idle gaps), and on pieces of a real
trace of the train tick on a TPU v5e, cut by ``trim_scopes.py`` to 40
ms around the end of a tick, its last AdamW update, the idle gap and the
next tick's forward (``data/scopes_adamw``), and to 60 ms around the
first ``_flash_bwd_dkv_kernel``, where the forward gives way to the
backward (``data/scopes_fwdbwd``), each with the sums its events
give."""

import json
import pathlib

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from harness import readers, runner, scopes
from harness import trace as trace_lib
from harness.common import Run

import tiny

DATA = pathlib.Path(__file__).parent / "data"
dense = runner.load_family(pathlib.Path(__file__).resolve().parents[2],
                           "dense")
US = 1000
FWD = "jit(tick)/while/body/closed_call/jvp()/while/body/closed_call"
BWD = ("jit(tick)/while/body/closed_call/transpose(jvp())/while/body/"
       "closed_call")
HLO = "\n".join([
    "HloModule jit_tick, is_scheduled=true",
    "",
    "ENTRY %main.9 (p: s32[]) -> f32[8,16] {",
    '  %while.1 = (s32[]) while(s32[] %p), condition=%c, body=%b, '
    'metadata={op_name="jit(tick)/while" source_file="e.py" '
    'source_line=160}',
    '  %fusion.1 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %x), '
    'kind=kOutput, calls=%fc.1, metadata={op_name="' + FWD
    + '/attn/bsd,dhk->bshk/dot_general" source_file="t.py" '
    'source_line=264 stack_frame_id=7}',
    '  %fusion.2 = f32[] fusion(f32[8,128]{1,0} %x), kind=kLoop, '
    'calls=%fc.2, metadata={op_name="jit(tick)/while/body/closed_call/'
    'jvp()/loss/reduce_sum"}',
    '  %while.2 = (s32[]) while(s32[] %q), condition=%c2, body=%b2, '
    'metadata={op_name="jit(tick)/while/body/closed_call/transpose(jvp())'
    '/while"}',
    '  %fusion.3 = bf16[8,16]{1,0} fusion(bf16[8,16]{1,0} %y), '
    'kind=kOutput, calls=%fc.3, metadata={op_name="' + BWD
    + '/rematted_computation/mlp/...d,df->...f/dot_general"}',
    '  %fusion.4 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %z), kind=kLoop, '
    'calls=%fc.4, metadata={op_name="jit(tick)/while/body/closed_call/'
    'adamw/jit(clip)/mul;jit(tick)/while/body/closed_call/adamw/sqrt"}',
    "  %copy.1 = bf16[8,16]{1,0} copy(bf16[8,16]{1,0} %w)",
    '  ROOT %fusion.5 = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %v), '
    'kind=kLoop, calls=%fc.5, metadata={op_name="jit(tick)/while/body/'
    'closed_call/adamw/sub"}',
    "}",
])


@pytest.fixture(scope="module")
def tr():
    return trace_lib.load(ProfileData.from_text_proto(
        (DATA / "scopes_trace.pbtxt").read_text()))


@pytest.fixture(scope="module")
def names():
    return scopes.op_names(HLO)


def test_op_names_from_hlo(names):
    assert sorted(names) == ["fusion.1", "fusion.2", "fusion.3", "fusion.4",
                             "fusion.5", "while.1", "while.2"]
    assert names["while.1"] == "jit(tick)/while"
    assert names["fusion.5"].endswith("/adamw/sub")


@pytest.mark.parametrize("op_name,want", [
    (FWD + "/attn/dot_general", "forward"),
    ("jit(tick)/while/body/closed_call/jvp()/loss/reduce_sum", "forward"),
    (BWD + "/attn/add_any", "backward"),
    (BWD + "/rematted_computation/mlp/dot_general", "backward"),
    ("jit(tick)/while/body/closed_call/adamw/add", "optimizer"),
    ("jit(tick)/while/body/closed_call/adamw/jit(clip)/max", "optimizer"),
    ("jit(tick)/while/body/closed_call/adamw_like/add", "other"),
    ("jit(tick)/while/body/dynamic_update_slice", "other"),
    ("params['embed']['w']", "other"),
    ("", "other"),
])
def test_phase(op_name, want):
    assert scopes.phase(op_name) == want


def test_phase_self_times(tr, names):
    # loop 1.5 us of its own, forward 6 + 2 + 6 (the second run), the
    # backward loop 2 us of its own around a 4 us fusion, AdamW 4.5 + 9;
    # the copy has no op_name
    assert scopes.phase_ns(tr, names) == {
        "forward": 14 * US, "backward": 6 * US, "optimizer": 13500,
        "other": 2500}
    assert sum(scopes.phase_ns(tr, names).values()) == \
        trace_lib.module_ns(tr, "jit_tick")
    assert scopes.phase_ns(tr, names, module="jit_admit_b") == dict.fromkeys(
        scopes.PHASES, 0)


def test_idle_by_innermost_program_span(tr):
    # the Python frames are innermost over both gaps: [30, 32] us falls in
    # the first tick's readback, [38, 40] in the second tick's accounting
    assert dict(trace_lib.idle_gaps(tr)) == {
        "$_array.py:631 _value": 2e-6, "$_fromnumeric.py:3476 mean": 2e-6}
    assert scopes.idle_by_program_span(tr) == {"train.readback": 2e-6,
                                               "train.account": 2e-6}


def test_no_program_span_gives_nothing():
    tr = trace_lib.load(ProfileData.from_text_proto(
        (DATA / "small_trace.pbtxt").read_text()))
    assert scopes.idle_by_program_span(tr) == {}


def test_param_count_matches_the_weights():
    m = tiny.train_model()
    w = dense.make_weights(m, 3, "float32")
    assert dense.param_count(m) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(w))


def _run(tr, names, model, steps=4, seq=32):
    run = Run(cell={}, model=model, mix={"batch": 1, "seq_len": seq},
              seed=1, window_s=1.0, setup_s=1.0, compiles_in_window=0,
              memory_peak=0, kernels={"jit_tick": {}}, family=dense)
    run.trace = tr
    run.peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    run.train = {"traced_steps": steps, "traced_tokens": steps * seq}
    run.scopes = names
    return run


def test_readers_by_hand(tr, names):
    m = tiny.train_model()
    run = _run(tr, names, m)
    f = dense.train_flops_per_token(m, 32) * 4 * 32
    assert scopes.train_phase_mfu(run, "forward") == pytest.approx(
        100 * f / 3 / (14e-6 * 1e12))
    assert scopes.train_phase_mfu(run, "backward") == pytest.approx(
        100 * f * 2 / 3 / (6e-6 * 1e12))
    assert scopes.adamw_hbm_share(run) == pytest.approx(
        100 * 28 * dense.param_count(m) * 4 / (13.5e-6 * 1e11))
    assert scopes.span_idle_share(run, "train.readback") == pytest.approx(
        100 * 2 / 40)
    assert scopes.span_idle_share(run, "serve.readback") == 0.0


def test_nothing_to_read_without_scopes(tr, names):
    run = _run(tr, None, tiny.train_model())
    assert scopes.train_phase_mfu(run, "forward") is None
    assert scopes.adamw_hbm_share(run) is None
    run = _run(None, names, tiny.train_model())
    assert scopes.span_idle_share(run, "train.readback") is None


def test_full_coverage_recombines_to_mfu_train(names):
    # ops that tile one run of the tick, no time outside a phase
    ops = [trace_lib.Op(0, 7 * US, "fusion.1", "jit_tick"),
           trace_lib.Op(7 * US, 20 * US, "fusion.3", "jit_tick"),
           trace_lib.Op(20 * US, 26 * US, "fusion.5", "jit_tick")]
    for op in ops:
        op.self_ns = op.end - op.start
    tr = trace_lib.Trace(0, 30 * US, ops, [(0, 26 * US, "jit_tick(1)")], [])
    m = tiny.train_model()
    run = _run(tr, names, m)
    f_tok = dense.train_flops_per_token(m, 32) * run.train["traced_tokens"]
    t_opt = 6e-6
    fwd = scopes.train_phase_mfu(run, "forward")
    bwd = scopes.train_phase_mfu(run, "backward")
    inv = ((1 / 3) / fwd + (2 / 3) / bwd
           + t_opt * run.peaks["bf16_flops"] / (100 * f_tok))
    assert readers.train_mfu(run) == pytest.approx(1 / inv)


@pytest.mark.parametrize("name", ["scopes_adamw", "scopes_fwdbwd"])
def test_real_trace_pieces(name):
    base = DATA / name
    want = json.loads(base.with_suffix(".json").read_text())
    tr = trace_lib.load(ProfileData.from_text_proto(
        base.with_suffix(".pbtxt").read_text()))
    exp = want["expected"]
    assert scopes.phase_ns(tr, want["scopes"]) == exp["phase_ns"]
    idle = scopes.idle_by_program_span(tr)
    assert {k: round(v * 1e9) for k, v in idle.items()} == \
        exp["idle_by_program_span_ns"]
