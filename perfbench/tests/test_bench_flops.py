"""Operation and byte counters against hand counts at small shapes."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import flops  # noqa: E402
from reference import Model  # noqa: E402

# d 8, 2 heads of 4, 1 KV head, d_ff 16, vocab 10, 3 layers
M = Model(d=8, heads=2, kv_heads=1, d_ff=16, vocab=10, layers=3,
          theta=1e4, eps=1e-6)


def test_gemm_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, mlp 3 x 8x16
    assert flops.layer_gemm_params(M) == 64 + 32 + 32 + 64 + 384
    assert flops.head_params(M) == 80
    assert flops.gemm_params(M) == 3 * 576 + 80


def test_embedding_not_counted():
    bigger_vocab = Model(**{**M.__dict__, "vocab": 1000})
    # only the head grows with the vocabulary: d per extra token
    assert flops.gemm_params(bigger_vocab) - flops.gemm_params(M) \
        == 8 * (1000 - 10)


def test_train_flops_by_hand():
    b, s = 2, 4
    pairs = 4 * 5 // 2                              # causal query-key pairs
    attn_fwd = 4 * pairs * 2 * 4 * 3               # 2 heads x 4 dims x 3
    want = 6 * (3 * 576 + 80) * b * s + 3 * attn_fwd * b
    assert flops.attn_pairs_causal(s) == 10
    assert flops.train_flops(M, b, s) == want


def test_remat_adds_one_forward_of_the_layers_only():
    b, s = 2, 4
    base = flops.train_flops(M, b, s)
    extra = flops.train_gemm_flops_executed(M, b, s, remat=True) - base
    assert flops.train_gemm_flops_executed(M, b, s, remat=False) == base
    assert extra == 2 * 3 * 576 * b * s + 4 * 10 * 2 * 4 * 3 * b


def test_serving_counts():
    p, n = 5, 3
    pre = 2 * 3 * 576 * p + 2 * 80 + 4 * 15 * 2 * 4 * 3
    assert flops.prefill_flops(M, p) == pre
    dec = sum(2 * (3 * 576 + 80) + 4 * (p + j) * 2 * 4 * 3
              for j in (1, 2))
    assert flops.served_flops(M, p, n) == pre + dec
    assert flops.served_rows(p, n) == 6 + 7


def test_decode_bytes_by_hand():
    small = 3 * (2 * 8 + (2 + 2) * 4) + 8          # norms + biases, bf16
    weights = (3 * 576 + 80) * 0.5625 + 2 * small
    row = 2 * 3 * 1 * 4 * 0.5625                    # K and V, all layers
    assert flops.packed_weight_bytes(M) == weights
    assert flops.kv_row_bytes(M) == row
    assert flops.decode_step_bytes(M, slots=2, rows=9) == \
        weights + 2 * 2 * 8 + row * 11
