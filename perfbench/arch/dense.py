"""The dense decoder: pre-norm RMSNorm, QKV bias, RoPE, causal softmax
attention with grouped KV heads, SwiGLU MLP, untied head (Qwen2).

An architecture module of the harness (``common.arch``), found by the
configuration file's ``"arch"``.  It holds everything that knows this
block's shapes:

  * the sizes (``Model``, ``model_of``) and the program's ``ModelConfig``
    for them (``program_config``, checked against the file);
  * the weights, made on the device from the run's root key in the
    program's parameter tree (``make_params``, ``make_embed``,
    ``make_layer``), the reference's view of that tree
    (``reference_params``) and the packed serving tree (``pack_params``);
  * the plain reference's layers: training loss (``seq_loss``) and the
    serving layer and head (``serve_layer``, ``serve_head``);
  * the operations and bytes the algorithm needs, from shapes alone.

The reference half imports nothing of the program; ``program_config`` and
``pack_params`` import it where they run.  Each leaf is drawn from its own
key, so one layer can be made alone (serving packs the layers one at a
time; the serving reference makes them again, layer by layer).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

import flops
import reference as ref
import weights

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "w_gate", "w_up",
                "w_down", "ln1", "ln2")
GEMM_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the reference needs, read from a configuration file."""
    d: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    layers: int
    theta: float
    eps: float

    @property
    def hd(self) -> int:
        return self.d // self.heads


def model_of(config: dict) -> Model:
    c = config
    return Model(d=c["hidden_size"], heads=c["num_attention_heads"],
                 kv_heads=c["num_key_value_heads"],
                 d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                 layers=c["num_hidden_layers"],
                 theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]))


def program_config(config: dict):
    """The program's ModelConfig for the file: its registered entry, cut
    with dataclasses.replace (depth and vocabulary), and given the KV
    heads the file states (the published count, where the registered
    entry differs); every other width must agree with the file."""
    from repro.configs import get_config
    base = get_config(config["program_arch"])
    if config.get("program_preset") == "smoke":     # CPU tests only
        base = base.smoke()
    cfg = dataclasses.replace(base, n_layers=config["num_hidden_layers"],
                              vocab_size=config["vocab_size"],
                              n_kv_heads=config["num_key_value_heads"])
    m = model_of(config)
    got = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.hd,
           cfg.rope_theta, cfg.norm_eps, cfg.qkv_bias, cfg.act,
           cfg.tie_embeddings, cfg.family)
    want = (m.d, m.heads, m.kv_heads, m.d_ff, m.hd, m.theta, m.eps,
            bool(config["attention_bias"]), "swiglu",
            bool(config["tie_word_embeddings"]), "dense")
    if got != want:
        raise SystemExit(f"perfbench: program config {got} differs from "
                         f"the configuration file {want}")
    return cfg


# ---- weights ----------------------------------------------------------------


def layer_shapes(m: Model):
    qd, kvd = m.heads * m.hd, m.kv_heads * m.hd
    return {"wq": (m.d, qd), "wk": (m.d, kvd), "wv": (m.d, kvd),
            "wo": (qd, m.d), "bq": (qd,), "bk": (kvd,), "bv": (kvd,),
            "w_gate": (m.d, m.d_ff), "w_up": (m.d, m.d_ff),
            "w_down": (m.d_ff, m.d), "ln1": (m.d,), "ln2": (m.d,)}


def make_layer(m: Model, key, layer):
    """Layer ``layer``'s weights (bf16) by plain name, from the run's root
    key."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    shapes = layer_shapes(m)
    return {n: weights.leaf(jax.random.fold_in(k, i), n, shapes[n])
            for i, n in enumerate(LAYER_LEAVES)}


def make_embed(m: Model, key):
    return weights.embedding(jax.random.fold_in(key, 0), m.vocab, m.d)


def make_head(m: Model, key):
    return weights.head(jax.random.fold_in(key, 2), m.d, m.vocab)


def make_ln_f(m: Model, key):
    return weights.leaf(jax.random.fold_in(key, 3), "ln_f", (m.d,))


def nest(layer):
    """Flat layer dict -> the program's nesting (attn / mlp / norms)."""
    return {"attn": {n: layer[n] for n in ("wq", "wk", "wv", "wo", "bq",
                                           "bk", "bv")},
            "mlp": {n: layer[n] for n in ("w_gate", "w_up", "w_down")},
            "ln1": layer["ln1"], "ln2": layer["ln2"]}


@partial(jax.jit, static_argnums=0)
def make_params(m: Model, key):
    """The whole model in the program's parameter tree, in one call
    (training cells, where it fits)."""
    layers = [make_layer(m, key, i) for i in range(m.layers)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return {"embed": make_embed(m, key), "layers": nest(stacked),
            "ln_f": make_ln_f(m, key), "lm_head": make_head(m, key)}


def reference_params(params):
    """The reference's view of ``make_params``'s tree: layer leaves by
    plain name, stacked."""
    lay = params["layers"]
    return {**params, "layers": {**lay["attn"], **lay["mlp"],
                                 "ln1": lay["ln1"], "ln2": lay["ln2"]}}


def pack_params(m: Model, key, spec):
    """The packed serving tree, made and packed one layer at a time with
    the program's quantize-once packer (the whole model in bf16 need not
    fit the chip)."""
    from repro.core.quantize import pack_quantize

    def pack_layer(k, i):
        lay = make_layer(m, k, i)
        return {n: (pack_quantize(v, spec, axis=-2) if n in GEMM_LEAVES
                    else v) for n, v in lay.items()}

    shapes = jax.eval_shape(pack_layer, key, 0)
    buf = jax.tree.map(lambda s: jnp.zeros((m.layers,) + s.shape, s.dtype),
                       shapes)

    def fill(b, k, i):
        return jax.tree.map(lambda a, x: a.at[i].set(x), b, pack_layer(k, i))

    fill = jax.jit(fill, donate_argnums=0)
    for i in range(m.layers):
        buf = fill(buf, key, i)
    head = jax.jit(lambda k: pack_quantize(make_head(m, k), spec,
                                           axis=-2))(key)
    return {"embed": jax.jit(lambda k: make_embed(m, k))(key),
            "layers": nest(buf),
            "ln_f": jax.jit(lambda k: make_ln_f(m, k))(key),
            "lm_head": head}


# ---- the reference: training --------------------------------------------------


def _train_layer(m: Model, fmt: str, lp, x, key, head_groups: int):
    """One decoder layer on one sequence (S, d), every linear through qmm."""
    S = x.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    keys = jax.random.split(key, 7)
    h = ref.rmsnorm(x, lp["ln1"], m.eps)
    q = ref.qmm(h, lp["wq"], keys[0], fmt) + lp["bq"]
    k = ref.qmm(h, lp["wk"], keys[1], fmt) + lp["bk"]
    v = ref.qmm(h, lp["wv"], keys[2], fmt) + lp["bv"]
    q = ref.rope(q.reshape(S, m.heads, m.hd), pos, m.theta)
    k = ref.rope(k.reshape(S, m.kv_heads, m.hd), pos, m.theta)
    v = v.reshape(S, m.kv_heads, m.hd)
    # heads in groups (whole KV groups), each group's scores recomputed
    # in backward
    g = math.gcd(head_groups, m.kv_heads)
    qs = q.reshape(S, g, m.heads // g, m.hd).swapaxes(0, 1)
    ks = k.reshape(S, g, m.kv_heads // g, m.hd).swapaxes(0, 1)
    vs = v.reshape(S, g, m.kv_heads // g, m.hd).swapaxes(0, 1)
    att = jax.checkpoint(lambda a, b, c: ref.attention(a, b, c, pos, pos))
    o = jax.lax.map(lambda t: att(*t), (qs, ks, vs))
    o = o.swapaxes(0, 1).reshape(S, m.heads * m.hd).astype(x.dtype)
    x = x + ref.qmm(o, lp["wo"], keys[3], fmt)
    h = ref.rmsnorm(x, lp["ln2"], m.eps)
    gt = ref.qmm(h, lp["w_gate"], keys[4], fmt)
    up = ref.qmm(h, lp["w_up"], keys[5], fmt)
    act = jax.nn.silu(gt.astype(ref.F32)).astype(gt.dtype) * up
    return x + ref.qmm(act, lp["w_down"], keys[6], fmt)


def seq_loss(m: Model, fmt: str, params, tokens, key, scale,
             head_groups: int = 8):
    """Sum of next-token NLL over one sequence, times ``scale`` (1 / all
    tokens of the batch, so that the per-sequence sums add to the mean);
    ``params`` in ``reference_params``'s view."""
    x = params["embed"][tokens[:-1]]
    for i in range(m.layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = jax.checkpoint(partial(_train_layer, m, fmt, head_groups=
                                   head_groups))(
            lp, x, jax.random.fold_in(key, i))
    x = ref.rmsnorm(x, params["ln_f"], m.eps)
    logits = ref.qmm(x, params["lm_head"][:, :m.vocab],
                     jax.random.fold_in(key, 10_000), fmt)
    logp = jax.nn.log_softmax(logits.astype(ref.F32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(nll) * scale


# ---- the reference: serving, teacher-forced through the KV cache's semantics --


def serve_layer(m: Model, fmt: ref.Fmt, kv_fmt: ref.Fmt, lp, x, plen):
    """One layer (``make_layer``'s weights) over one padded sequence x
    (T, d) whose first ``plen`` positions are the prompt: prompt queries
    attend the fresh bf16 K/V, later queries every row as the cache stores
    it."""
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = ref.rmsnorm(x, lp["ln1"], m.eps)
    q = ref.dense_fwd(h, lp["wq"], fmt) + lp["bq"]
    k = ref.dense_fwd(h, lp["wk"], fmt) + lp["bk"]
    v = ref.dense_fwd(h, lp["wv"], fmt) + lp["bv"]
    q = ref.rope(q.reshape(T, m.heads, m.hd), pos, m.theta)
    k = ref.rope(k.reshape(T, m.kv_heads, m.hd), pos, m.theta)
    v = v.reshape(T, m.kv_heads, m.hd)
    o_fresh = ref.attention(q, k, v, pos, pos)
    o_cache = ref.attention(q, ref.kv_rows(k, kv_fmt), ref.kv_rows(v, kv_fmt),
                            pos, pos)
    o = jnp.where((pos < plen)[:, None, None], o_fresh, o_cache)
    x = x + ref.dense_fwd(o.reshape(T, -1).astype(x.dtype), lp["wo"], fmt)
    h = ref.rmsnorm(x, lp["ln2"], m.eps)
    gt = ref.dense_fwd(h, lp["w_gate"], fmt)
    up = ref.dense_fwd(h, lp["w_up"], fmt)
    act = jax.nn.silu(gt.astype(ref.F32)).astype(gt.dtype) * up
    return x + ref.dense_fwd(act, lp["w_down"], fmt)


def serve_head(m: Model, fmt: ref.Fmt, key, x):
    """float32 logits over the vocabulary for rows x (R, d), with the final
    norm and head made from the run's root key."""
    h = ref.rmsnorm(x, make_ln_f(m, key), m.eps)
    return ref.dot(ref.quantize(h, fmt, -1),
                    ref.quantize(make_head(m, key)[:, :m.vocab], fmt, 0))


# ---- operations and bytes (conventions in flops.py) ----------------------------


def layer_gemm_params(m: Model) -> int:
    qd, kvd = m.heads * m.hd, m.kv_heads * m.hd
    return m.d * qd + 2 * m.d * kvd + qd * m.d + 3 * m.d * m.d_ff


def head_params(m: Model) -> int:
    return m.d * m.vocab


def gemm_params(m: Model) -> int:
    """Weights of every GEMM: the layers and the head, not the embedding."""
    return m.layers * layer_gemm_params(m) + head_params(m)


def attn_fwd_flops(m: Model, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` query-key pairs, all
    layers."""
    return 4 * pairs * m.heads * m.hd * m.layers


def train_flops(m: Model, batch: int, seq: int) -> int:
    """Operations one training step needs: 6 per GEMM weight per token
    (forward, and backward at twice that) plus causal attention at three
    times its forward.  No recompute."""
    return (6 * gemm_params(m) * batch * seq
            + 3 * attn_fwd_flops(m, flops.attn_pairs_causal(seq)) * batch)


def train_gemm_flops_executed(m: Model, batch: int, seq: int,
                              remat: bool) -> int:
    """GEMM operations a step executes: ``train_flops`` plus the forward
    of every layer again in backward when layers are rematerialized (the
    head is not)."""
    tokens = batch * seq
    layers = m.layers * layer_gemm_params(m)
    attn = attn_fwd_flops(m, flops.attn_pairs_causal(seq)) * batch
    extra = (2 * layers * tokens + attn) if remat else 0
    return train_flops(m, batch, seq) + extra


def prefill_flops(m: Model, prompt: int) -> int:
    """Forward over a prompt; the head only at its last position."""
    return 2 * m.layers * layer_gemm_params(m) * prompt \
        + 2 * head_params(m) \
        + attn_fwd_flops(m, flops.attn_pairs_causal(prompt))


def decode_token_flops(m: Model, context: int) -> int:
    """One generated token attending ``context`` cached rows (its own
    included)."""
    return 2 * gemm_params(m) + attn_fwd_flops(m, context)


def served_flops(m: Model, prompt: int, served: int) -> int:
    """A request's prefill and the decode steps that produced its served
    tokens after the first: step j reads prompt + j rows."""
    return prefill_flops(m, prompt) + sum(
        decode_token_flops(m, prompt + j) for j in range(1, served))


def packed_weight_bytes(m: Model) -> float:
    """Packed NVFP4 store of every GEMM weight, plus the bf16 norms and
    biases a step reads.  (The embedding is gathered by rows.)"""
    small = m.layers * (2 * m.d + (m.heads + 2 * m.kv_heads) * m.hd) + m.d
    return gemm_params(m) * flops.NVFP4_BYTES_PER_PARAM + 2 * small


def kv_row_bytes(m: Model,
                 fmt_bytes: float = flops.NVFP4_BYTES_PER_PARAM) -> float:
    """Bytes of one token's K and V rows over all layers."""
    return 2 * m.layers * m.kv_heads * m.hd * fmt_bytes


def decode_step_bytes(m: Model, slots: int, rows: float) -> float:
    """Bytes a decode step must move: the packed weights once, the
    embedding rows of its tokens, and ``rows`` live cache rows (summed
    over slots) read, one new row per slot written."""
    return (packed_weight_bytes(m) + 2 * slots * m.d
            + kv_row_bytes(m) * (rows + slots))
