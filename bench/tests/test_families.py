"""The model-family seam. A family's reference loss equals the program's
at a smoke size; a family that ``dense`` cannot express (Qwen1.5's
biases, gated SiLU MLP and untied head, ``qwen_family.py``) enters the
benchmark as new files only, and its training cell runs through the
unchanged harness, correct when sound and not correct when the
program's labels are altered, with the int8 control judged by the same
decision; and a configuration that names no family is refused by its
file's name."""

import json
import pathlib
import shutil

import jax.numpy as jnp
import pytest

import tiny
from harness import runner
from harness.traffic import TrainTraffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
QWEN = pathlib.Path(__file__).with_name("qwen_family.py")
CELL = "qwen-smoke.online"


def qwen_model():
    """Qwen1.5's smoke widths in the program (``qwen1_5_110b:smoke``), 2
    layers, trained as the training cell's configuration is."""
    train = tiny.train_model()["train"]
    return {
        "source": "https://huggingface.co/Qwen/Qwen1.5-110B",
        "program_config": "qwen1_5_110b:smoke", "family": "qwen",
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 128, "hidden_act": "silu", "mlp_gated": True,
        "tie_word_embeddings": False, "attention_bias": True,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
        "num_hidden_layers": 2, "reduced": ["num_hidden_layers"],
        "train": train,
        # readings at these widths on the CPU (seeds 11, 21, 31, 41, 51),
        # as (loss_gap, moment_gap, update_gap): sound runs up to (8.1e-4,
        # 6.9e-3, 7.9e-3); half of each row's labels altered at least
        # (7.5e-3, 0.079, 0.016); the int8 control (1.5e-4 to 1.3e-3,
        # 2.3e-3 to 6.1e-3, 2.1e-3 to 3.9e-3), which at these widths
        # reads as the program's bfloat16 does
        "limits": {"loss_gap": 0.002, "moment_gap": 0.02,
                   "update_gap": 0.012},
    }


def _checkout(root: pathlib.Path) -> pathlib.Path:
    """A checkout at ``root`` that holds the benchmark's metrics and mixes,
    and, as new files, the qwen family, its configuration, a mix of short
    rows and a cell, listed where a new cell is listed: in
    ``BENCHMARK.json``'s ``configs`` and ``workloads``, and in the
    ``workloads`` of the end-to-end metric it reports."""
    bench_dir = root / "bench"
    for sub in ("metrics", "traffic"):
        shutil.copytree(BENCH / sub, bench_dir / sub)
    (bench_dir / "families").mkdir()
    shutil.copy(QWEN, bench_dir / "families" / "qwen.py")
    (bench_dir / "configs").mkdir()
    (bench_dir / "configs" / "qwen-smoke.json").write_text(
        json.dumps(qwen_model()))
    (bench_dir / "traffic" / "short_rows.json").write_text(
        json.dumps(tiny.train_mix()))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "qwen-smoke", "source": "test",
                             "file": "bench/configs/qwen-smoke.json",
                             "reduced": ["num_hidden_layers"],
                             "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "qwen-smoke",
                               "traffic": "short_rows", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def qwen_root(tmp_path_factory):
    return _checkout(tmp_path_factory.mktemp("root"))


def _run(root, **kw):
    return runner.run_cell(root, CELL, 11, 0.5, False, require_tpu=False,
                           **kw)


@pytest.mark.parametrize("name", ["dense", "qwen"])
def test_reference_loss_matches_the_program(name, qwen_root):
    if name == "dense":
        family, model = (runner.load_family(BENCH.parent, "dense"),
                         tiny.train_model())
    else:
        family, model = runner.load_family(qwen_root, "qwen"), qwen_model()
    w = family.make_weights(model, 5, "float32")
    rows = TrainTraffic(tiny.train_mix(), 5, model["vocab_size"]).batch_at(0)
    batch = {k: jnp.asarray(v) for k, v in rows.items()}
    cfg = family.program_config(model)
    from repro.models import transformer
    got, _ = transformer.loss_fn(w, cfg, batch)
    want = family.loss(w, batch["tokens"], batch["labels"], model, False)
    # both in float32: the sums run in another order
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_qwen_counts(qwen_root):
    """The qwen family's parameter count is its weights' size, and its
    FLOPs per token follow by hand: 6 per matmul weight (q, o 64*4*16
    each; k, v 64*2*16 each; MLP 64*128 three times; the head 64*128),
    and 6 * 4*16 per layer per key of a 32-token row."""
    import jax
    import numpy as np
    family, model = runner.load_family(qwen_root, "qwen"), qwen_model()
    w = family.make_weights(model, 3, "float32")
    assert family.param_count(model) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(w))
    per_layer = 2 * 4096 + 2 * 2048 + 3 * 8192
    assert family.train_flops_per_token(model, 32) == (
        6 * (2 * per_layer + 8192) + 6 * 64 * 2 * 32)


def test_second_family_needs_only_new_files(qwen_root):
    out = _run(qwen_root)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_second_family_labels_altered(qwen_root):
    """Half of each row's labels shifted in the program's feed."""
    def fault(eng):
        orig = eng.pipeline.batch_at

        def altered(i):
            b = orig(i)
            labels = b["labels"].copy()
            half = labels.shape[-1] // 2
            labels[:, :half] = (labels[:, :half] + 1) % 128
            return dict(b, labels=labels)
        eng.pipeline.batch_at = altered
    assert _run(qwen_root, fault=fault)["correct"] is False


def test_second_family_control(qwen_root):
    """The int8 control, the family's reference with int8 weights in the
    program's place, is judged by the same decision of ``correct``. At
    these widths it reads as the program's bfloat16 does (the limits'
    readings), so that here it shows the control's path, not a failure:
    a cell at its own size shows that on the chip."""
    out = _run(qwen_root, control=True)
    got = {k: c["value"] for k, c in out["checks"].items()}
    assert set(got) == {"loss_gap", "moment_gap", "update_gap"}
    assert all(0 < v < 0.1 for v in got.values())
    assert out["correct"] == all(v <= out["checks"][k]["limit"]
                                 for k, v in got.items())


def test_configuration_without_family_is_refused(tmp_path):
    model = tiny.train_model()
    del model["family"]
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "bare.json").write_text(
        json.dumps(model))
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0],
                             file="bench/configs/bare.json")]
    with pytest.raises(ValueError, match="bench/configs/bare.json"):
        runner.cell_files(tmp_path, bench, bench["workloads"][0]["name"])
