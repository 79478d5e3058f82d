"""CPU-sized stand-ins of the benchmark's configurations and mixes: the
program's StarCoder2 smoke widths, the same keys as the real files."""

import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _load(rel):
    return json.loads((BENCH / rel).read_text())


def _shrink(model):
    m = copy.deepcopy(model)
    m.update(program_config="starcoder2_7b:smoke", hidden_size=72,
             num_attention_heads=6, num_key_value_heads=2, head_dim=16,
             intermediate_size=144, vocab_size=128, rope_theta=10000.0)
    return m


def serve_model(layers=2):
    m = _shrink(_load("configs/starcoder2-7b-serve8.json"))
    m["num_hidden_layers"] = layers
    m["serve"].update(max_slots=4, max_len=256, num_pages=96,
                      prefill_chunk=32)
    # readings at these widths on the CPU, outputs of 10-24 tokens: sound
    # runs 0 to 0.0066; an altered token 3.9-4.1, a decode step that drops
    # its key/value write 0.79-0.98 (outputs of 2-12 left it 0 to 0.11)
    m["limits"] = {"max_logit_gap": 0.1}
    return m


def train_model(layers=2):
    m = _shrink(_load("configs/starcoder2-7b-train2.json"))
    m["num_hidden_layers"] = layers
    m["train"].update(schedule_steps=20, warmup_steps=2, steps_per_tick=2)
    # readings at these widths on the CPU: sound runs 1.9e-4, 1.1e-3,
    # 1.2e-3; half the batch 0.017, 0.13, 0.017; state unchanged -, 1, 1
    m["limits"] = {"loss_gap": 0.002, "moment_gap": 0.02, "update_gap": 0.01}
    return m


def chat_mix():
    m = _load("traffic/chat.json")
    m.update(outstanding=4, block=4, lead_in_s=0.5, max_context=256)
    m["prefix"].update(groups=3, len={"dist": "uniform", "min": 32,
                                      "max": 64})
    m["prompt"].update(median=20, min=4, max=64)
    m["output"].update(median=16, min=10, max=24)
    return m


def batch_mix():
    m = _load("traffic/prefill_batch.json")
    m.update(outstanding=6, block=6, max_context=256)
    m["prompt"].update(median=80, min=40, max=160)
    m["output"].update(min=2, max=6)
    return m


def train_mix():
    m = _load("traffic/online.json")
    m.update(seq_len=32)
    return m


def root_with_chat(path: pathlib.Path) -> pathlib.Path:
    """A checkout root at ``path`` whose ``BENCHMARK.json`` also lists the
    chat cell (built in ``bench/``, not yet in the benchmark), with
    ``bench`` linked to this directory."""
    bench = _load("../BENCHMARK.json")
    if not any(c["name"] == "starcoder2-7b-serve8" for c in bench["configs"]):
        bench["configs"].append({
            "name": "starcoder2-7b-serve8", "source": "test",
            "file": "bench/configs/starcoder2-7b-serve8.json",
            "reduced": ["num_hidden_layers"], "why": "test"})
    if not any(w["name"] == "sc2-serve8.chat" for w in bench["workloads"]):
        bench["workloads"].append({
            "name": "sc2-serve8.chat", "config": "starcoder2-7b-serve8",
            "traffic": "chat", "chips": 1, "why": "test"})
    (path / "bench").symlink_to(BENCH, target_is_directory=True)
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return path
