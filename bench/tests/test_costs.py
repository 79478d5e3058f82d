"""Operation and byte counts, checked by hand at small shapes: hidden 8,
2 query heads and 1 key/value head of 4, MLP 16, vocabulary 10, 3
layers."""

import pytest

from harness import costs

M = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
     "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
     "num_hidden_layers": 3, "mlp_gated": False}


def test_parameters_and_bytes():
    # q, o: 8*2*4 each; k, v: 8*1*4 each; MLP 8*16 twice
    assert costs.layer_matmul_params(M) == 64 + 64 + 32 + 32 + 256
    assert costs.head_params(M) == 80
    # (3 layers * 448 + 80) bf16 + 7 norms of 8 float32
    assert costs.weight_bytes(M) == 1424 * 2 + 7 * 8 * 4
    assert costs.kv_bytes_per_token(M) == 2 * 1 * 4 * 2 * 3
    assert costs.causal_keys(2, 3) == 3 + 4 + 5
    assert costs.causal_keys(0, 1) == 1


def test_decode():
    # two slots attending 3 and 5 keys: 2 * 1424 per token, 4 * 8 per
    # key per layer
    assert costs.decode_flops(M, [3, 5]) == 2 * 1424 * 2 + 4 * 8 * 3 * 8
    assert costs.decode_bytes(M, [3, 5]) == 3072 + 48 * (8 + 2)
    flops, byt = costs.paged_decode_kernel(M, [3, 5])
    assert flops == 4 * 8 * 3 * 8
    # keys and values of 8 tokens, q and out of 2 tokens, 3 layers
    assert byt == 48 * 8 + 2 * 8 * 2 * 3 * 2


def test_extend():
    rows = [(0, 2), (4, 1), (7, 0)]
    # 3 tokens through the layers, 1+2 and 5 keys, the head for 2 rows
    assert costs.extend_flops(M, rows) == (2 * 448 * 3 * 3 + 4 * 8 * 3 * 8
                                           + 2 * 80 * 2)
    flops, byt = costs.paged_prefill_kernel(M, rows)
    assert flops == 4 * 8 * 3 * 8
    assert byt == 48 * (2 + 5) + 2 * 8 * 2 * 3 * 3


def test_train():
    assert costs.train_flops_per_token(M, 6) == 6 * 1424 + 6 * 8 * 3 * 6
    flops, byt = costs.flash_bwd_kernels(M, 1, 4, 2)
    # 10 causal pairs, 4 products of 2 * Dh each, 2 heads, 3 layers, 2 steps
    assert flops == pytest.approx(4 * 2 * 10 * 4 * 2 * 3 * 2)
    assert byt == (4 * 4 * 2 * 4 * 2 + 4 * 4 * 1 * 4 * 2 + 4 * 2 * 4) * 3 * 2
