"""``correct`` at a size a CPU test can hold: a sound run passes; the
control and every fault that a cell can have fail it. The harness's look
for a chip is skipped; the rest of a run is driven as on the chip, with
the timed path broken underneath.

Faults (planted in the program, never in the reference):

* serving: a served token altered where it is sampled; the decode step
  returning its key/value state unchanged;
* training: the optimizer returning the state unchanged; half of each
  batch left out of the loss (the mean taken over the rest); one label
  token of every row altered in the program's feed.

The exchange between chips does not exist in these one-chip cells.
"""

import pathlib

import pytest

import tiny
from harness import runner

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def chat_root(tmp_path_factory):
    return tiny.root_with_chat(tmp_path_factory.mktemp("root"))
CHAT, TRAIN = "sc2-serve8.chat", "sc2-train2.online"


def _serve(root, **kw):
    return runner.run_cell(root, CHAT, 11, 1.5, False, require_tpu=False,
                           model=tiny.serve_model(), mix=tiny.chat_mix(),
                           **kw)


def _train(**kw):
    return runner.run_cell(ROOT, TRAIN, 11, 0.5, False, require_tpu=False,
                           model=tiny.train_model(), mix=tiny.train_mix(),
                           **kw)


def _values(out):
    return {k: c["value"] for k, c in out["checks"].items()}


@pytest.fixture(scope="module")
def sound_serve(chat_root):
    return _serve(chat_root)


@pytest.fixture(scope="module")
def sound_train():
    return _train()


def test_sound_runs_are_correct(sound_serve, sound_train):
    assert sound_serve["correct"] and sound_train["correct"]
    assert sound_serve["attempted"] > 0 and sound_serve["failed"] == 0


def test_control_goes_through_the_run(chat_root, sound_serve, sound_train):
    """The control, the reference with int8 weights put in the program's
    place, is judged by the same decision of ``correct`` as the program.
    At these smoke widths (72 wide, vocabulary 128) int8 and the
    program's bfloat16 read alike (gaps 0 to 0.022 against 0 to 0.0077;
    loss gaps 2.9e-4 to 7.6e-4 against 1.9e-4 to 4.3e-4), so the control
    is shown to fail on the chip at the cells' own sizes
    (``readings.py ... control``), not here."""
    for run, ref in ((lambda **kw: _serve(chat_root, **kw), sound_serve),
                     (_train, sound_train)):
        out = run(control=True)
        got = _values(out)
        assert set(got) == set(_values(ref))
        assert all(v >= 0 and v == v for v in got.values())
        assert out["correct"] == all(
            v <= out["checks"][k]["limit"] for k, v in got.items())


def test_serve_config_selects_int8_and_speculation(chat_root):
    """The configuration's ``serve`` block alone selects the program's
    int8 path and speculative decoding."""
    for extra in ({"quant": "int8"}, {"spec_k": 2}):
        model = tiny.serve_model()
        model["serve"].update(extra)
        out = runner.run_cell(chat_root, CHAT, 12, 1.0, False, require_tpu=False,
                              model=model, mix=tiny.chat_mix())
        assert out["attempted"] > 0 and out["failed"] == 0
        assert _values(out)["max_logit_gap"] < 1.0


def test_serve_token_altered(chat_root, monkeypatch):
    from repro.serve import engine
    orig = engine._sample

    def altered(logits, keys, temp):
        tok, rng = orig(logits, keys, temp)
        return (tok + 1) % logits.shape[-1], rng
    monkeypatch.setattr(engine, "_sample", altered)
    assert not _serve(chat_root)["correct"]


def test_serve_state_unchanged(chat_root, monkeypatch):
    from repro.models import transformer as tf_lib
    orig = tf_lib.paged_decode_step

    def frozen(params, cfg, token, pos, table, caches, **kw):
        logits, _ = orig(params, cfg, token, pos, table, caches, **kw)
        return logits, caches
    monkeypatch.setattr(tf_lib, "paged_decode_step", frozen)
    assert not _serve(chat_root)["correct"]


def test_train_state_unchanged(monkeypatch):
    from repro.train import engine
    orig = engine.apply_updates

    def unchanged(params, grads, state, cfg):
        _, _, metrics = orig(params, grads, state, cfg)
        return params, state, metrics
    monkeypatch.setattr(engine, "apply_updates", unchanged)
    out = _train()
    assert not out["correct"]
    assert _values(out)["update_gap"] == pytest.approx(1.0)


def test_train_half_batch(monkeypatch):
    from repro.models import transformer as tf_lib
    orig = tf_lib.loss_fn

    def half(params, cfg, batch):
        import jax.numpy as jnp
        mask = jnp.ones_like(batch["labels"], jnp.float32)
        mask = mask.at[..., mask.shape[-1] // 2:].set(0.0)
        return orig(params, cfg, dict(batch, loss_mask=mask))
    monkeypatch.setattr(tf_lib, "loss_fn", half)
    assert not _train()["correct"]


def test_train_token_altered():
    def fault(eng):
        orig = eng.pipeline.batch_at

        def altered(i):
            b = orig(i)
            labels = b["labels"].copy()
            labels[:, 0] = (labels[:, 0] + 1) % 128
            return dict(b, labels=labels)
        eng.pipeline.batch_at = altered
    assert not _train(fault=fault)["correct"]
