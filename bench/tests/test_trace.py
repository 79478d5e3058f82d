"""The trace reduction on a small made trace whose busy, idle and kernel
times are known by hand (``data/small_trace.pbtxt``: a decode tick of 10
us holding a loop with a fusion and the paged decode kernel, then an
extend of 8 us holding the paged prefill kernel, in a 25 us window), and
on two pieces of a real one: the training cell's trace on a TPU v5e, cut
by ``trim_trace.py`` to 20 ms around the first flash backward kernels
(``data/train_bwd``) and to 40 ms around the gap between two ticks
(``data/train_gap``), each with the numbers that plain sums over its
events give."""

import base64
import json
import pathlib

import pytest
from jax.profiler import ProfileData

from harness import trace

DATA = pathlib.Path(__file__).parent / "data" / "small_trace.pbtxt"
REAL = ["train_bwd", "train_gap"]
KERNELS = {"jit_tick": {"paged_decode_attention.9": "_paged_kernel"},
           "jit_admit_b": {"paged_prefill_attention.4":
                           "_paged_prefill_kernel"}}


@pytest.fixture(scope="module")
def tr():
    return trace.load(ProfileData.from_text_proto(DATA.read_text()))


def test_window_busy_and_idle(tr):
    assert tr.window_ns == 25000
    # [0, 10000] and [12000, 19000]; the loop's body is inside it
    assert trace.busy_ns(tr) == 17000


def test_program_and_kernel_times(tr):
    assert trace.module_ns(tr, "jit_tick") == 10000
    assert trace.module_ns(tr, "jit_admit_b") == 8000
    assert trace.kernel_ns(tr, KERNELS, ["_paged_kernel"]) == (4000, 1)
    assert trace.kernel_ns(tr, KERNELS, ["_paged_prefill_kernel"]) == (6000, 1)
    assert trace.kernel_ns(tr, KERNELS, ["_flash_kernel"]) == (0, 0)


def test_self_times_and_top_ops(tr):
    top = dict(trace.top_ops(tr, KERNELS))
    assert top == {"jit_admit_b/_paged_prefill_kernel": 6e-6,
                   "jit_tick/_paged_kernel": 4e-6,
                   "jit_tick/while": 3e-6, "jit_tick/fusion": 2e-6,
                   "jit_tick/copy": 1e-6, "jit_admit_b/fusion": 1e-6}


def test_idle_gaps_by_innermost_host_span(tr):
    # [10000, 12000] falls inside the first step's host call, [19000,
    # 25000] mostly in the idle span
    assert dict(trace.idle_gaps(tr)) == {"bench.idle": 6e-6,
                                         "$engine.py:2223 step": 2e-6}


def test_kernel_instructions_from_hlo():
    body = base64.b64encode(b"\x00module @x func _paged_kernel loc(a.py:1)"
                            b" _other_kernel").decode()
    hlo = ('  %paged_decode_attention.9 = bf16[4] custom-call(s32[4] %a), '
           'custom_call_target="tpu_custom_call", backend_config='
           '{"custom_call_config": {"body":"' + body + '"}}\n'
           '  %copy.2 = bf16[4] copy(bf16[4] %b)\n')
    assert trace.kernel_instructions(hlo) == {
        "paged_decode_attention.9": "_paged_kernel"}


@pytest.mark.parametrize("name", REAL)
def test_real_trace_pieces(name):
    base = DATA.parent / name
    want = json.loads(base.with_suffix(".json").read_text())
    tr = trace.load(ProfileData.from_text_proto(
        base.with_suffix(".pbtxt").read_text()))
    exp, kernels = want["expected"], want["kernels"]
    assert tr.window_ns == exp["window_ns"]
    assert trace.busy_ns(tr) == exp["busy_ns"]
    assert trace.module_ns(tr, "jit_tick") == exp["jit_tick_ns"]
    for k, (ns, calls) in exp["kernels"].items():
        assert trace.kernel_ns(tr, kernels, [k]) == (ns, calls)
    idle = sum(s for _, s in trace.idle_gaps(tr, n=1000))
    assert idle == pytest.approx((exp["window_ns"] - exp["busy_ns"]) / 1e9)
