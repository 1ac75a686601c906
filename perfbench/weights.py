"""The benchmark's weights, made on the device from the seed.

One layout for both the program and the reference: the program's
parameter tree of a dense decoder (``embed``, stacked ``layers`` with
``attn``/``mlp``/norms, ``ln_f``, ``lm_head``).  Each leaf is drawn from
its own key, so one layer can be made alone (serving makes and packs the
layers one at a time; the reference makes them again, layer by layer).
Rows and columns of the padded vocabulary are zero: they are not part of
the model.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import Model

BF16 = jnp.bfloat16
LAYER_LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "w_gate", "w_up",
                "w_down", "ln1", "ln2")


def root_key(seed: int):
    """A PRNG key from any whole number (seeds may pass 2**31)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def layer_shapes(m: Model):
    qd, kvd = m.heads * m.hd, m.kv_heads * m.hd
    return {"wq": (m.d, qd), "wk": (m.d, kvd), "wv": (m.d, kvd),
            "wo": (qd, m.d), "bq": (qd,), "bk": (kvd,), "bv": (kvd,),
            "w_gate": (m.d, m.d_ff), "w_up": (m.d, m.d_ff),
            "w_down": (m.d_ff, m.d), "ln1": (m.d,), "ln2": (m.d,)}


def _leaf(key, name, shape):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.startswith("w"):                       # fan-in scaled GEMM weight
        return (z * shape[0] ** -0.5).astype(BF16)
    if name.startswith("b"):
        return (0.1 * z).astype(BF16)
    return (1.0 + 0.1 * z).astype(BF16)            # norm gains


def make_layer(m: Model, key, layer):
    """Layer ``layer``'s weights (bf16), from the run's root key."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    shapes = layer_shapes(m)
    return {n: _leaf(jax.random.fold_in(k, i), n, shapes[n])
            for i, n in enumerate(LAYER_LEAVES)}


def padded_vocab(m: Model, multiple: int = 256) -> int:
    return -(-m.vocab // multiple) * multiple


def make_embed(m: Model, key):
    e = 0.02 * jax.random.normal(jax.random.fold_in(key, 0),
                                 (m.vocab, m.d), jnp.float32)
    e = jnp.pad(e, ((0, padded_vocab(m) - m.vocab), (0, 0)))
    return e.astype(BF16)


def make_head(m: Model, key):
    w = jax.random.normal(jax.random.fold_in(key, 2), (m.d, m.vocab),
                          jnp.float32) * m.d ** -0.5
    return jnp.pad(w, ((0, 0), (0, padded_vocab(m) - m.vocab))).astype(BF16)


def make_ln_f(m: Model, key):
    return _leaf(jax.random.fold_in(key, 3), "ln_f", (m.d,))


def nest(layer):
    """Flat layer dict -> the program's nesting (attn / mlp / norms)."""
    return {"attn": {n: layer[n] for n in ("wq", "wk", "wv", "wo", "bq",
                                           "bk", "bv")},
            "mlp": {n: layer[n] for n in ("w_gate", "w_up", "w_down")},
            "ln1": layer["ln1"], "ln2": layer["ln2"]}


@partial(jax.jit, static_argnums=0)
def make_params(m: Model, key):
    """The whole model in one call (training cells, where it fits)."""
    layers = [make_layer(m, key, i) for i in range(m.layers)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return {"embed": make_embed(m, key), "layers": nest(stacked),
            "ln_f": make_ln_f(m, key), "lm_head": make_head(m, key)}


def flat_layers(params):
    """The reference's view: layer leaves by plain name, stacked."""
    lay = params["layers"]
    return {**lay["attn"], **lay["mlp"], "ln1": lay["ln1"],
            "ln2": lay["ln2"]}
