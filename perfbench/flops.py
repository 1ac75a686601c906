"""Operations and bytes the algorithm needs, from shapes alone.

A GEMM of an (M, K) by (K, N) product is 2MKN operations.  The embedding
is a table lookup and counts nothing; the head is a GEMM over the
vocabulary (its padding is not part of the model).  Causal attention
needs, per layer and sequence of S tokens, S(S+1)/2 query-key pairs, each
2 operations per head dimension for the scores and 2 for the weighted sum
of values.  Backward costs twice the forward.
"""
from __future__ import annotations

from reference import Model

NVFP4_BYTES_PER_PARAM = 0.5 + 1.0 / 16      # E2M1 code + one E4M3 per 16


def layer_gemm_params(m: Model) -> int:
    qd, kvd = m.heads * m.hd, m.kv_heads * m.hd
    return m.d * qd + 2 * m.d * kvd + qd * m.d + 3 * m.d * m.d_ff


def head_params(m: Model) -> int:
    return m.d * m.vocab


def gemm_params(m: Model) -> int:
    """Weights of every GEMM: the layers and the head, not the embedding."""
    return m.layers * layer_gemm_params(m) + head_params(m)


def attn_pairs_causal(s: int) -> int:
    return s * (s + 1) // 2


def attn_fwd_flops(m: Model, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` query-key pairs, all
    layers."""
    return 4 * pairs * m.heads * m.hd * m.layers


def train_flops(m: Model, batch: int, seq: int) -> int:
    """Operations one training step needs: 6 per GEMM weight per token
    (forward, and backward at twice that) plus causal attention at three
    times its forward.  No recompute."""
    return (6 * gemm_params(m) * batch * seq
            + 3 * attn_fwd_flops(m, attn_pairs_causal(seq)) * batch)


def train_gemm_flops_executed(m: Model, batch: int, seq: int,
                              remat: bool) -> int:
    """GEMM operations a step executes: ``train_flops`` plus the forward
    of every layer again in backward when layers are rematerialized (the
    head is not)."""
    tokens = batch * seq
    layers = m.layers * layer_gemm_params(m)
    attn = attn_fwd_flops(m, attn_pairs_causal(seq)) * batch
    extra = (2 * layers * tokens + attn) if remat else 0
    return train_flops(m, batch, seq) + extra


def prefill_flops(m: Model, prompt: int) -> int:
    """Forward over a prompt; the head only at its last position."""
    return 2 * m.layers * layer_gemm_params(m) * prompt \
        + 2 * head_params(m) + attn_fwd_flops(m, attn_pairs_causal(prompt))


def decode_token_flops(m: Model, context: int) -> int:
    """One generated token attending ``context`` cached rows (its own
    included)."""
    return 2 * gemm_params(m) + attn_fwd_flops(m, context)


def served_flops(m: Model, prompt: int, served: int) -> int:
    """A request's prefill and the decode steps that produced its served
    tokens after the first: step j reads prompt + j rows."""
    return prefill_flops(m, prompt) + sum(
        decode_token_flops(m, prompt + j) for j in range(1, served))


def served_rows(prompt: int, served: int) -> int:
    """Cache rows the decode steps of one request read, summed."""
    return sum(prompt + j for j in range(1, served))


def packed_weight_bytes(m: Model) -> float:
    """Packed NVFP4 store of every GEMM weight, plus the bf16 norms and
    biases a step reads.  (The embedding is gathered by rows.)"""
    small = m.layers * (2 * m.d + (m.heads + 2 * m.kv_heads) * m.hd) + m.d
    return gemm_params(m) * NVFP4_BYTES_PER_PARAM + 2 * small


def kv_row_bytes(m: Model, fmt_bytes: float = NVFP4_BYTES_PER_PARAM) -> float:
    """Bytes of one token's K and V rows over all layers."""
    return 2 * m.layers * m.kv_heads * m.hd * fmt_bytes


def decode_step_bytes(m: Model, slots: int, rows: float) -> float:
    """Bytes a decode step must move: the packed weights once, the
    embedding rows of its tokens, and ``rows`` live cache rows (summed
    over slots) read, one new row per slot written."""
    return (packed_weight_bytes(m) + 2 * slots * m.d
            + kv_row_bytes(m) * (rows + slots))
