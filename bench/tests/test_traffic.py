"""The traffic generator: deterministic per seed, the same sizes and gaps
for every seed in another order, inside its clips and the 4096-token
limit; and a new mix, cell and metric that need only new files."""

import collections
import json
import pathlib
import shutil

import numpy as np
import pytest

from harness import runner, traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
MIXES = {n: json.loads((BENCH / "traffic" / f"{n}.json").read_text())
         for n in ("chat", "prefill_batch", "online")}
SEEDS = (1, 2 ** 31 + 12345, 3_000_000_007)


def _open(seed):
    """The chat mix's sizes offered as an open loop, as the rate sweep
    offers them."""
    mix = dict(MIXES["chat"], loop="open", rate_per_s=1.8)
    return traffic.ServeTraffic(mix, seed, 49152).open_loop(51.0)


def _closed(seed, n=256):
    it = traffic.ServeTraffic(MIXES["prefill_batch"], seed,
                              49152).closed_loop()
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("make", [_open, _closed])
def test_deterministic_per_seed(make):
    a, b = make(SEEDS[1]), make(SEEDS[1])
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) and
               x.max_tokens == y.max_tokens for x, y in zip(a, b))
    c = make(SEEDS[2])
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


@pytest.mark.parametrize("make", [_open, _closed])
def test_same_work_for_every_seed(make):
    def shape(reqs):
        return (sorted(r.max_tokens for r in reqs),
                sorted(len(r.prompt) - r.prefix_len for r in reqs),
                collections.Counter(r.prefix_len for r in reqs))
    runs = [make(s) for s in SEEDS]
    assert all(shape(r) == shape(runs[0]) for r in runs)
    if make is _open:
        gaps = [sorted(np.diff([0.0] + [r.due for r in reqs]))
                for reqs in runs]
        assert all(np.allclose(g, gaps[0]) for g in gaps)


@pytest.mark.parametrize("name,make", [("chat", _open),
                                       ("prefill_batch", _closed)])
def test_clips_and_context_limit(name, make):
    mix = MIXES[name]
    for r in make(SEEDS[0]):
        user = len(r.prompt) - r.prefix_len
        assert mix["prompt"]["min"] <= user <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_tokens <= mix["output"]["max"]
        assert len(r.prompt) + r.max_tokens <= mix["max_context"] <= 4096
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49152
        if "prefix" in mix:
            lo, hi = mix["prefix"]["len"]["min"], mix["prefix"]["len"]["max"]
            assert lo <= r.prefix_len <= hi


def test_open_loop_rate_and_tenants():
    reqs = _open(SEEDS[0])
    rate = 1.8
    assert len(reqs) == int(np.ceil(rate * 51.0))
    assert reqs[-1].due == pytest.approx(51.0, rel=0.15)
    # Zipf(1.1) over 8 tenants: the most popular one sends the most
    counts = collections.Counter(
        r.prompt[:r.prefix_len].tobytes() for r in reqs).most_common()
    assert len(counts) <= 8 and counts[0][1] > counts[-1][1]


def test_train_rows():
    t = traffic.TrainTraffic(MIXES["online"], SEEDS[1], 49152)
    a, b = t.batch_at(0), t.batch_at(1)
    assert a["tokens"].shape == (1, 2048)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert not np.array_equal(a["tokens"], b["tokens"])
    again = traffic.TrainTraffic(MIXES["online"], SEEDS[1], 49152)
    assert np.array_equal(again.batch_at(1)["labels"], b["labels"])


def test_new_mix_cell_and_metric_need_only_new_files(tmp_path):
    """A mix, a cell and a per-layer metric added as files and entries
    run through the unchanged harness."""
    import tiny
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "configs").mkdir()
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(BENCH / "families", tmp_path / "bench" / "families")
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    model = tiny.serve_model()
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(model))
    mix = tiny.chat_mix()
    mix.update(lead_in_s=0.2)
    del mix["prefix"]
    (tmp_path / "bench" / "traffic" / "dummy.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "requests_per_s.py").write_text(
        "def read(run):\n    return run.attempted / run.window_s\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.dummy", "config": "tiny",
                               "traffic": "dummy", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "requests_per_s", "unit": "req/s",
                                "better": "higher", "bound": 0.1,
                                "source": "host_clock",
                                "workloads": ["tiny.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    out = runner.run_cell(tmp_path, "tiny.dummy", 7, 1.0, False,
                          require_tpu=False)
    assert out["correct"] is True
    # the chat cell's latency metrics list their cells; setup_s is
    # every cell's
    assert set(out["metrics"]) == {"setup_s", "requests_per_s"}
    assert out["metrics"]["requests_per_s"]["value"] > 0
