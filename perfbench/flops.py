"""Shape-free conventions for counting operations and bytes.

A GEMM of an (M, K) by (K, N) product is 2MKN operations.  The embedding
is a table lookup and counts nothing; the head is a GEMM over the
vocabulary (its padding is not part of the model).  Causal attention
needs, per layer and sequence of S tokens, S(S+1)/2 query-key pairs.
Backward costs twice the forward.  Each architecture module
(``arch/<name>.py``) counts its own shapes with these.
"""
from __future__ import annotations

NVFP4_BYTES_PER_PARAM = 0.5 + 1.0 / 16      # E2M1 code + one E4M3 per 16


def attn_pairs_causal(s: int) -> int:
    return s * (s + 1) // 2


def served_rows(prompt: int, served: int) -> int:
    """Cache rows the decode steps of one request read, summed."""
    return sum(prompt + j for j in range(1, served))
