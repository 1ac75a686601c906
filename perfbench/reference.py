"""Plain reference for the benchmark's correctness checks.

A straightforward jax.numpy implementation of the pieces the cells' models
are made of, and of their stated numerics (each architecture's layers,
loss and serving head are in ``arch/<name>.py``, built from these):

  * every linear layer block-quantizes both operands along the contraction
    axis, NVFP4 (E2M1 codes, one E4M3 scale per 16 values under a
    power-of-two tensor scale) or MXFP4 (E2M1 codes, one E8M0 scale per 32
    values), and accumulates in float32;
  * training quantizes the six GEMM operands of the paper (forward RtN,
    gradients and the update's activations stochastically rounded);
  * activations are stored in bfloat16 where the configuration states it;
  * serving stores K/V rows in NVFP4 (one E4M3 scale per 16 values of a
    head row) and reads every row through the cache in decode, while the
    prompt attends its own fresh K/V in prefill.

It imports nothing of the program under test.  Quantized operands are
exact in bfloat16 (a 2-bit E2M1 significand times a 4-bit E4M3 or E8M0
scale times a power of two), so the dots feed them as bfloat16 with
float32 accumulation; the attention's float32 dots run at HIGHEST.
Weights come from ``weights.py`` (the benchmark's own generator) through
the architecture module.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16


# ---- element formats ----------------------------------------------------------


def _pow2_floor_exp(a):
    """floor(log2 a) for a > 0 (float32), exact."""
    _, e = jnp.frexp(a)
    return e - 1


def e2m1_rtn(x):
    """Round-half-even onto the E2M1 grid {0, .5, 1, 1.5, 2, 3, 4, 6},
    saturating at 6."""
    a = jnp.minimum(jnp.abs(x), 6.0)
    step = jnp.where(a < 2.0, 0.5, jnp.where(a < 4.0, 1.0, 2.0))
    return jnp.sign(x) * jnp.minimum(jnp.round(a / step) * step, 6.0)


def e2m1_sr(x, u):
    """Stochastic rounding onto the E2M1 grid with uniforms u in [0, 1)."""
    a = jnp.minimum(jnp.abs(x), 6.0)
    step = jnp.where(a < 2.0, 0.5, jnp.where(a < 4.0, 1.0, 2.0))
    return jnp.sign(x) * jnp.minimum(jnp.floor(a / step + u) * step, 6.0)


def e4m3_rtn(x):
    """Round-half-even of x >= 0 onto E4M3 (fn: max 448, subnormals to
    2^-9), saturating at 448."""
    a = jnp.minimum(x, 448.0)
    e = jnp.maximum(_pow2_floor_exp(jnp.maximum(a, 2.0 ** -30)), -6)
    ulp = jnp.ldexp(jnp.ones_like(a), e - 3)
    return jnp.minimum(jnp.round(a / ulp) * ulp, 448.0)


@dataclasses.dataclass(frozen=True)
class Fmt:
    """A block format: NVFP4 or MXFP4."""
    name: str
    block: int

    def scales(self, amax_blocks, amax_tensor):
        """(block scales, tensor scale) from block and tensor absmax."""
        if self.name == "nvfp4":
            raw = amax_tensor / (6.0 * 448.0)
            ts = jnp.where(amax_tensor > 0,
                           jnp.ldexp(jnp.ones((), F32),
                                     _pow2_floor_exp(jnp.maximum(
                                         raw, 2.0 ** -126)) + 1), 1.0)
            s = e4m3_rtn(amax_blocks / (6.0 * ts))
            return jnp.where(s > 0, s, 1.0), ts
        # MXFP4: E8M0 scale 2^(floor(log2 amax) - 2), no tensor scale
        s = jnp.ldexp(jnp.ones_like(amax_blocks),
                      _pow2_floor_exp(jnp.maximum(amax_blocks, 2.0 ** -126))
                      - 2)
        return jnp.where(amax_blocks > 0, s, 1.0), jnp.ones((), F32)


NVFP4 = Fmt("nvfp4", 16)
MXFP4 = Fmt("mxfp4", 32)
FORMATS = {"nvfp4": NVFP4, "mxfp4": MXFP4}


def quantize(x, fmt: Fmt, axis: int, u=None):
    """Quantize-dequantize x (any float dtype) in blocks along ``axis``;
    RtN, or SR when uniforms ``u`` are given.  Returns float32 values that
    are exact in bfloat16."""
    xf = x.astype(F32)
    axis = axis % x.ndim
    shp = x.shape[:axis] + (x.shape[axis] // fmt.block, fmt.block) \
        + x.shape[axis + 1:]
    xb = xf.reshape(shp)
    amax_b = jnp.max(jnp.abs(xb), axis=axis + 1, keepdims=True)
    s, ts = fmt.scales(amax_b, jnp.max(jnp.abs(xf)))
    y = xb / (s * ts)
    q = e2m1_rtn(y) if u is None else e2m1_sr(y, u.reshape(shp))
    return (q * s * ts).reshape(x.shape)


def kv_rows(x, fmt: Fmt):
    """A cache row as stored and read back: blocks along the head dim with
    one scale each and no tensor scale (rows are written one at a time)."""
    xf = x.astype(F32)
    shp = x.shape[:-1] + (x.shape[-1] // fmt.block, fmt.block)
    xb = xf.reshape(shp)
    amax_b = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    if fmt.name == "nvfp4":
        s = e4m3_rtn(amax_b / 6.0)
        s = jnp.where(s > 0, s, 1.0)
    else:
        s, _ = fmt.scales(amax_b, jnp.ones((), F32))
    return (e2m1_rtn(xb / s) * s).reshape(x.shape)


# ---- the FQT matmul (the paper's six points) ------------------------------------


def dot(a, b):
    """a @ b for operands exact in bf16: bf16 in, float32 accumulation."""
    return jnp.matmul(a.astype(BF16), b.astype(BF16),
                      preferred_element_type=F32)


def _uniform(key, i, shape):
    return jax.random.uniform(jax.random.fold_in(key, i), shape, F32)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def qmm(x, w, key, fmt_name):
    """x (M, K) @ w (K, N) with both operands quantized along K (RtN),
    output in x's dtype.  Backward: dx = Q_sr(g) Q(w)^T blocked along N,
    dw = Q_sr(x)^T Q_sr(g) blocked along M (the tokens)."""
    f = FORMATS[fmt_name]
    return dot(quantize(x, f, -1), quantize(w, f, 0)).astype(x.dtype)


def _qmm_fwd(x, w, key, fmt_name):
    return qmm(x, w, key, fmt_name), (x, w, key)


def _qmm_bwd(fmt_name, res, g):
    x, w, key = res
    f = FORMATS[fmt_name]
    dx = dot(quantize(g, f, -1, _uniform(key, 0, g.shape)),
             quantize(w, f, 1).T).astype(x.dtype)
    dw = dot(quantize(x, f, 0, _uniform(key, 1, x.shape)).T,
             quantize(g, f, 0, _uniform(key, 2, g.shape))).astype(w.dtype)
    return dx, dw, np.zeros(key.shape, jax.dtypes.float0)


qmm.defvjp(_qmm_fwd, _qmm_bwd)


def dense_fwd(x, w, fmt: Fmt):
    """Forward-only quantized linear layer (serving): RtN both operands."""
    return dot(quantize(x, fmt, -1), quantize(w, fmt, 0)).astype(x.dtype)


# ---- model pieces -----------------------------------------------------------------


def rmsnorm(x, w, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def rope(x, pos, theta):
    """Rotary embedding on (S, H, D), rotating the two halves of D."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


def attention(q, k, v, qpos, kpos):
    """Causal softmax attention in float32.  q (Sq, H, D), k/v (Sk, KVH, D)."""
    H, KVH, D = q.shape[1], k.shape[1], q.shape[2]
    G = H // KVH
    qg = q.astype(F32).reshape(q.shape[0], KVH, G, D)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k.astype(F32),
                   precision=HIGHEST) * D ** -0.5
    s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                  s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v.astype(F32), precision=HIGHEST)
    return o.reshape(q.shape[0], H, D)


# ---- training: AdamW and the reference's steps --------------------------------


@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    b1: float
    b2: float
    eps: float
    wd: float
    clip: float


def _sq(a):
    return jnp.sum(jnp.square(a.astype(F32)))


@jax.jit
def leaf_norms(tree):
    """The float32 norm of every leaf."""
    return jax.tree.map(lambda a: jnp.sqrt(_sq(a)), tree)


@partial(jax.jit, static_argnums=0, donate_argnums=(2, 3, 4, 5))
def adamw(opt: Adam, step, grads, master, mom, vel):
    """One AdamW step with global-norm clipping, in float32 on the device.
    Returns the clipped gradients and the new master weights and
    moments (the old ones are donated)."""
    norm = jnp.sqrt(sum(_sq(g) for g in jax.tree.leaves(grads)))
    c = jnp.minimum(1.0, opt.clip / jnp.maximum(norm, 1e-9))
    t = (step + 1).astype(F32)
    c1, c2 = 1.0 - opt.b1 ** t, 1.0 - opt.b2 ** t
    grads = jax.tree.map(lambda g: g * c, grads)
    mom = jax.tree.map(lambda m, g: opt.b1 * m + (1 - opt.b1) * g, mom,
                       grads)
    vel = jax.tree.map(lambda v, g: opt.b2 * v + (1 - opt.b2) * g * g, vel,
                       grads)
    master = jax.tree.map(
        lambda w, m, v: w - opt.lr * ((m / c1) / (jnp.sqrt(v / c2) + opt.eps)
                                      + opt.wd * w), master, mom, vel)
    return grads, master, mom, vel


def train_reference(seq_loss, opt: Adam, params, batches, key):
    """Run len(batches) training steps of the reference from ``params``
    (bf16 on the host, the benchmark's weights), with the architecture's
    loss ``seq_loss(params, tokens, key, scale)`` of one sequence (the sum
    of its next-token NLL times ``scale``).  Returns per-step losses,
    the clipped first-step gradient's norm per leaf, the first gradient's
    own norm per leaf, and the norm per leaf of the master weights'
    change.

    Each step sums per-sequence gradients (so at most one sequence's
    activations live at once) and applies AdamW; compute weights are the
    master weights rounded to bf16, as stated for the configuration.  The
    device holds only the master weights, the moments, one step's bf16
    weights and the summed gradients."""
    vg = jax.value_and_grad(seq_loss, argnums=0)

    def acc_grad(acc, weights, tokens, key, scale):
        loss, g = vg(weights, tokens, key, scale)
        return loss, jax.tree.map(lambda a, b: a + b.astype(F32), acc, g)

    grad_fn = jax.jit(acc_grad, donate_argnums=0)
    to_f32 = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(F32), t))
    to_bf16 = jax.jit(lambda t: jax.tree.map(lambda a: a.astype(BF16), t))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    master = to_f32(params)
    mom, vel = zeros(master), zeros(master)

    losses, first_g, first_raw = [], None, None
    for s, tokens in enumerate(batches):
        weights = to_bf16(master)
        B, T = tokens.shape[0], tokens.shape[1] - 1
        acc = zeros(master)
        loss = 0.0
        for b in range(B):
            lb, acc = grad_fn(acc, weights, jnp.asarray(tokens[b]),
                              jax.random.fold_in(jax.random.fold_in(key, s),
                                                 b), 1.0 / (B * T))
            loss += float(lb)
        del weights
        losses.append(loss)
        if s == 0:
            first_raw = jax.device_get(leaf_norms(acc))
        g, master, mom, vel = adamw(opt, jnp.int32(s), acc, master, mom,
                                    vel)
        if s == 0:
            first_g = jax.device_get(leaf_norms(g))
        del g, acc
    del mom, vel
    diff = jax.jit(lambda a, b: jnp.sqrt(_sq(a - b.astype(F32))))
    change = jax.tree.map(lambda a, b: diff(a, jnp.asarray(b)), master,
                          params)
    return losses, first_g, first_raw, jax.device_get(change)
