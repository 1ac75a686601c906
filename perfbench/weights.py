"""The benchmark's weights, drawn on the device from the seed.

The run's root key comes from the seed; each architecture module
(``arch/<name>.py``) folds its own layout of keys out of it and draws every
leaf with ``leaf``, or the vocabulary tables with ``embedding`` and
``head``.  Rows and columns of the padded vocabulary are zero: they are
not part of the model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


def root_key(seed: int):
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def leaf(key, name, shape):
    """One bf16 leaf by its name: a GEMM weight (``w...``) fan-in scaled, a
    bias (``b...``) small, anything else a norm gain near 1."""
    z = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("w"):
        return (z * shape[0] ** -0.5).astype(BF16)
    if name.startswith("b"):
        return (0.1 * z).astype(BF16)
    return (1.0 + 0.1 * z).astype(BF16)


def padded_vocab(vocab: int, multiple: int = 256) -> int:
    return -(-vocab // multiple) * multiple


def embedding(key, vocab: int, d: int):
    """The (padded vocabulary, d) embedding table."""
    e = 0.02 * jax.random.normal(key, (vocab, d), jnp.float32)
    e = jnp.pad(e, ((0, padded_vocab(vocab) - vocab), (0, 0)))
    return e.astype(BF16)


def head(key, d: int, vocab: int):
    """The (d, padded vocabulary) output head, fan-in scaled."""
    w = jax.random.normal(key, (d, vocab), jnp.float32) * d ** -0.5
    return jnp.pad(w, ((0, 0), (0, padded_vocab(vocab) - vocab))).astype(BF16)
