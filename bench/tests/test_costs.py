"""Operation and byte counts of the ``dense`` family, checked by hand at
small shapes (hidden 8, 2 query heads and 1 key/value head of 4, MLP 16,
vocabulary 10, 3 layers), and pinned at StarCoder2-7B's widths to the
numbers the benchmark has read since its first chip runs."""

import json
import pathlib

import pytest

from harness import runner

ROOT = pathlib.Path(__file__).resolve().parents[2]
dense = runner.load_family(ROOT, "dense")

M = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
     "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
     "num_hidden_layers": 3, "mlp_gated": False}


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_parameters_and_bytes():
    # q, o: 8*2*4 each; k, v: 8*1*4 each; MLP 8*16 twice
    assert dense.layer_matmul_params(M) == 64 + 64 + 32 + 32 + 256
    assert dense.head_params(M) == 80
    # (3 layers * 448 + 80) bf16 + 7 norms of 8 float32
    assert dense.weight_bytes(M) == 1424 * 2 + 7 * 8 * 4
    assert dense.kv_bytes_per_token(M) == 2 * 1 * 4 * 2 * 3
    assert dense.causal_keys(2, 3) == 3 + 4 + 5
    assert dense.causal_keys(0, 1) == 1


def test_decode():
    # two slots attending 3 and 5 keys: 2 * 1424 per token, 4 * 8 per
    # key per layer
    assert dense.decode_flops(M, [3, 5]) == 2 * 1424 * 2 + 4 * 8 * 3 * 8
    assert dense.decode_bytes(M, [3, 5]) == 3072 + 48 * (8 + 2)
    flops, byt = dense.paged_decode_kernel(M, [3, 5])
    assert flops == 4 * 8 * 3 * 8
    # keys and values of 8 tokens, q and out of 2 tokens, 3 layers
    assert byt == 48 * 8 + 2 * 8 * 2 * 3 * 2


def test_extend():
    rows = [(0, 2), (4, 1), (7, 0)]
    # 3 tokens through the layers, 1+2 and 5 keys, the head for 2 rows
    assert dense.extend_flops(M, rows) == (2 * 448 * 3 * 3 + 4 * 8 * 3 * 8
                                           + 2 * 80 * 2)
    flops, byt = dense.paged_prefill_kernel(M, rows)
    assert flops == 4 * 8 * 3 * 8
    assert byt == 48 * (2 + 5) + 2 * 8 * 2 * 3 * 3


def test_train():
    assert dense.train_flops_per_token(M, 6) == 6 * 1424 + 6 * 8 * 3 * 6
    flops, byt = dense.attention_kernels(M, 1, 4, 2)
    # 10 causal pairs, 4 products of 2 * Dh each, 2 heads, 3 layers, 2 steps
    assert flops == pytest.approx(4 * 2 * 10 * 4 * 2 * 3 * 2)
    assert byt == (4 * 4 * 2 * 4 * 2 + 4 * 4 * 1 * 4 * 2 + 4 * 2 * 4) * 3 * 2


def test_starcoder2_counts_are_pinned():
    """The counts behind ``mfu.train``, ``mfu.forward``, ``mfu.backward``,
    ``hbm_share.adamw``, ``flash_bwd_roofline`` and the serving metrics,
    at the configurations' own widths: exact, so that moving or rewriting
    a formula cannot move a metric unseen."""
    train = _config("starcoder2-7b-train2")
    assert dense.param_count(train) == 660625920
    assert dense.train_flops_per_token(train, 2048) == 4076863488.0
    assert dense.attention_kernels(train, 1, 2048, 4) == (618777280512.0,
                                                          673447936.0)
    serve = _config("starcoder2-7b-serve8")
    ctxs, rows = [100, 2048, 4095], [(0, 256), (512, 256), (1000, 7)]
    assert dense.decode_flops(serve, ctxs) == 12698173440.0
    assert dense.decode_bytes(serve, ctxs) == 4028516352
    assert dense.extend_flops(serve, rows) == 1833850699776.0
    assert dense.paged_decode_kernel(serve, ctxs) == (920567808.0,
                                                      102727680.0)
    assert dense.paged_prefill_kernel(serve, rows) == (30065098752.0,
                                                       109805568.0)
    assert dense.kv_bytes_per_token(serve) == 16384
