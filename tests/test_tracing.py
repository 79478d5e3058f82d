"""What an operator sees in a profile of the engines: the host spans of
``TrainEngine.run`` and ``ServeEngine.step`` on the main thread's line of
a ``jax.profiler`` trace, one step span per tick with its phases nested
inside, and the named scopes of the compiled train tick. The scopes change
the program's metadata only: with them switched off the compiled program
is the same."""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data import DataConfig, make_pipeline
from repro.models import transformer as tf_lib
from repro.optim import AdamWConfig
from repro.serve import ServeConfig, ServeEngine
from repro.train import TrainEngine, TrainEngineConfig

VOCAB, SEQ, BATCH, K = 64, 16, 2, 2
TRAIN_PHASES = ("train.dispatch", "train.stage", "train.readback",
                "train.account")


def _cfg():
    return tf_lib.LMConfig(name="t", d_model=32, n_heads=2, n_kv_heads=2,
                           d_ff=64, vocab=VOCAB, pattern=(tf_lib.BlockSpec(),),
                           repeats=2, remat="full", vocab_pad_multiple=1)


def _train_engine():
    cfg = _cfg()
    params = tf_lib.init_lm(jax.random.PRNGKey(0), cfg,
                            dtype=jnp.float32).params
    pipe = make_pipeline(DataConfig(vocab=VOCAB, seq_len=SEQ,
                                    global_batch=BATCH, seed=0,
                                    source="markov"))
    return TrainEngine.for_lm(params, cfg, opt_cfg=AdamWConfig(lr=1e-3),
                              pipeline=pipe,
                              engine_cfg=TrainEngineConfig(steps_per_tick=K))


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; the host's program spans (``train``,
    ``serve`` and their ``.*``) as (start, end, name, stats, line)."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if re.match(r"^(train|serve)(\.|$)", e.name):
                    s = int(e.start_ns)
                    out.append((s, s + int(e.duration_ns), e.name,
                                dict(e.stats), line.name))
    return sorted(out, key=lambda x: (x[0], -x[1]))


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_train_run_spans(tmp_path):
    eng = _train_engine()
    eng.run(K)                       # compile outside the profile
    ticks = 3
    spans = _profile(tmp_path, lambda: eng.run(K * ticks))
    assert len({s[4] for s in spans}) == 1          # one thread's line
    steps = [s for s in spans if s[2] == "train"]
    assert [s[3]["step_num"] for s in steps] == [1, 2, 3]
    by = {n: [s for s in spans if s[2] == n] for n in TRAIN_PHASES}
    for n in ("train.dispatch", "train.readback", "train.account"):
        assert len(by[n]) == ticks
    # the first block is staged before the first tick, the others inside
    # the tick before them, behind the device
    assert len(by["train.stage"]) == ticks
    assert by["train.stage"][0][1] <= steps[0][0]
    for i, tick in enumerate(steps):
        mine = [s for s in spans if s[2] != "train" and _inside(s, tick)]
        want = ["train.dispatch", "train.stage", "train.readback",
                "train.account"]
        if i == ticks - 1:
            want.remove("train.stage")
        assert [s[2] for s in mine] == want
    assert eng.host_readbacks == 1 + ticks          # one per tick


def _serve_engine():
    cfg = tf_lib.LMConfig(name="t", d_model=48, n_heads=4, n_kv_heads=2,
                          d_ff=96, vocab=61, pattern=(tf_lib.BlockSpec(),),
                          repeats=2, remat="none", vocab_pad_multiple=1)
    params = tf_lib.init_lm(jax.random.PRNGKey(0), cfg,
                            dtype=jnp.float32).params
    return ServeEngine(params, cfg, ServeConfig(
        paged=True, page_size=4, max_slots=4, max_len=64, prefix_cache=True,
        temperature=0.0))


def test_serve_step_spans(tmp_path):
    eng = _serve_engine()
    prefix = np.arange(1, 13, dtype=np.int32)
    eng.submit(np.concatenate([prefix, [20, 21]]), max_tokens=3)
    eng.run_until_drained()          # compile and publish the prefix
    uids = [eng.submit(np.concatenate([prefix, [30 + i, 31]]), max_tokens=3)
            for i in range(2)]
    rb0, tick0, log0 = eng.host_readbacks, eng._tick_idx, len(eng.metrics_log)
    finished = []
    spans = _profile(tmp_path,
                     lambda: finished.extend(eng.run_until_drained()))
    assert sorted(r.uid for r in finished) == uids
    assert sum(m.prefix_hit_tokens for m in eng.metrics_log[log0:]) > 0
    assert len({s[4] for s in spans}) == 1
    steps = [s for s in spans if s[2] == "serve"]
    assert [s[3]["step_num"] for s in steps] == list(
        range(tick0, eng._tick_idx))
    children = [s for s in spans if s[2] != "serve"]
    for s in children:                               # all nested in a tick
        assert sum(_inside(s, t) for t in steps) == 1
    names = [s[2] for s in children]
    for n in ("serve.admit", "serve.compact"):
        assert names.count(n) == len(steps)
    assert names.count("serve.dispatch") == names.count("serve.cow_barrier")
    assert names.count("serve.dispatch") >= 2
    # every host readback is a span: the decode ticks', the extends' and
    # the finished requests' tokens
    assert names.count("serve.readback") == eng.host_readbacks - rb0
    for t in steps:
        mine = [s for s in children if _inside(s, t)]
        order = [s[2] for s in mine if s[2] in (
            "serve.admit", "serve.compact", "serve.cow_barrier",
            "serve.dispatch")]
        assert order == ["serve.admit", "serve.compact", "serve.cow_barrier",
                         "serve.dispatch"][:len(order)]
    # one request's spans share its uid
    admitted = "".join(str(s[3].get("uids", "")) for s in children
                       if s[2] == "serve.admit")
    for u in uids:
        assert re.search(rf"\b{u}\b", admitted)
    assert sorted(s[3]["uid"] for s in children
                  if s[2] == "serve.finish") == uids


# -- the compiled train tick ---------------------------------------------------

_DEBUG_TABLE = re.compile(
    r"^(\d+ .*|FileNames|FunctionNames|FileLocations|StackFrames)$")


def _tick_hlo():
    eng = _train_engine()
    block = jax.ShapeDtypeStruct((K, BATCH, SEQ), jnp.int32)
    return eng._tick.lower(eng.params, eng.opt_state,
                           {"tokens": block, "labels": block}
                           ).compile().as_text()


def _without_metadata(text):
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    return "\n".join(ln for ln in text.splitlines()
                     if not _DEBUG_TABLE.match(ln))


@pytest.fixture(scope="module")
def tick_hlo():
    return _tick_hlo()


def _in_scope(op_name, scope):
    """``scope`` is a component of the name stack: ``.../attn/...``, or
    ``jvp(head)`` where the scope is the differentiated function's own."""
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


def test_tick_scopes(tick_hlo):
    names = re.findall(r'op_name="([^"]*)"', tick_hlo)
    fwd = [n for n in names if "jvp(" in n and "transpose(" not in n]
    bwd = [n for n in names if "transpose(jvp(" in n]
    for scope in ("embed", "attn", "mlp", "head", "loss"):
        assert any(_in_scope(n, scope) for n in fwd), scope
        assert any(_in_scope(n, scope) for n in bwd), scope
    # the rematerialised forward is backward work
    assert any("rematted_computation" in n for n in bwd)
    opt = [n for n in names if _in_scope(n, "adamw")]
    assert opt and not any("jvp(" in n for n in opt)
    # the global norm that clipping takes is the optimizer's
    assert {"square", "reduce_sum", "sqrt"} <= {
        n.rsplit("/", 1)[-1] for n in opt}


def test_scopes_leave_the_device_program_unchanged(tick_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _tick_hlo()
    assert "/adamw/" not in plain and "/adamw/" in tick_hlo
    assert _without_metadata(plain) == _without_metadata(tick_hlo)
