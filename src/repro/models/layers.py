"""Shared neural building blocks (pure JAX, FQT-quantized linears).

Every matmul-bearing layer routes through ``QCtx.dense`` -> fp4_matmul, so
the paper's six quantization points apply uniformly across the zoo.  The
attention *score/value* batched matmuls stay in bf16 (the paper's scope is
the three weight GEMMs; same choice as the FP8 FQT line of work — DESIGN.md
§5).
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fqt
from repro.core.fqt import QuantConfig
from repro.distributed.sharding import constrain
from repro.obs import scopes


def _scoped(name: str):
    """Trace a method under ``jax.named_scope(name)`` (HLO metadata only;
    a fresh scope per call, so nested and concurrent traces are safe)."""
    def wrap(f):
        @wraps(f)
        def inner(*args, **kw):
            with jax.named_scope(name):
                return f(*args, **kw)
        return inner
    return wrap


# A large-but-finite mask value: keeps fully-masked rows NaN-free without
# inf-inf arithmetic anywhere.
NEG_INF = -1e30


class QCtx:
    """Quantization context: static QuantConfig + per-call SR seed stream.

    A fresh QCtx is created per (layer, step); each ``dense`` call gets a
    distinct deterministic seed (trace-time counter — stable across jit).
    """

    def __init__(self, qcfg: QuantConfig, seed: jax.Array):
        self.qcfg = qcfg
        self.seed = jnp.asarray(seed, jnp.uint32)
        self._n = 0

    def fold(self, idx) -> "QCtx":
        """Child context for layer/expert ``idx`` (idx may be traced)."""
        mixed = self.seed + jnp.asarray(idx, jnp.uint32) * jnp.uint32(2654435761)
        return QCtx(self.qcfg, mixed)

    def dense(self, x: jax.Array, w: jax.Array,
              b: Optional[jax.Array] = None) -> jax.Array:
        s = self.seed + jnp.uint32(self._n * 40503)
        self._n += 1
        return fqt.dense(x, w, b, cfg=self.qcfg, seed=s)

    def dense_hp(self, x: jax.Array, w: jax.Array,
                 b: Optional[jax.Array] = None) -> jax.Array:
        """High-precision (bf16) dense — routers, gates (never quantized)."""
        y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
        if b is not None:
            y = y + b
        return y.astype(x.dtype)


# ---- initializers ------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16,
               scale: Optional[float] = None) -> jax.Array:
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# ---- norms / activations ------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)) * w


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def smooth_swiglu(gate: jax.Array, up: jax.Array,
                  smooth: jax.Array) -> jax.Array:
    """Smooth-SwiGLU [Fishman et al. 2024]: per-channel smoothing factor
    migrates outlier scale out of the quantized down-projection input,
    preventing the late-training FP8/FP4 instability of SwiGLU.  The factor
    is divided out of ``up`` before the product and multiplied back after
    the down projection (caller applies ``smooth`` inverse on the output
    side), so the function is numerically equivalent in high precision.
    """
    z = jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype)
    return z * (up / smooth)


# ---- rotary embeddings ---------------------------------------------------------


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for given absolute positions: (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---- chunked (flash-style) attention -------------------------------------------


def _attn_dense(q, k, v, qpos, kpos, causal, window):
    """Reference dense-softmax attention for short sequences.

    q: (B, Sq, KVH, G, D); k/v: (B, Sk, KVH, D); *pos: (Sq,)/(Sk,) absolute.
    """
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o


def _flash_mask(qpch, kp, causal, window, nq, qc, kc):
    mask = jnp.ones((nq, qc, kc), bool)
    if causal:
        mask &= kp[None, None, :] <= qpch[:, :, None]
    if window is not None:
        mask &= kp[None, None, :] > qpch[:, :, None] - window
    return mask[None, :, None, None, :, :]       # broadcast to (B,nq,h,g,q,k)


def _flash_fwd_impl(q, k, v, qpos, kpos, causal, window, qc, kc):
    """Flash forward: q blocks are a PARALLEL leading dim, kv chunks a
    sequential scan with running (max, denom, acc).

    Keeping the q-block dim parallel (instead of the classic outer scan)
    exposes it to GSPMD: when the head count does not divide the TP degree
    (qwen2.5: 40 heads on a 16-way "model" axis; whisper: 8) the q-block
    dim shards on "model" instead — context-parallel attention.  The
    ``constrain(..., "qblocks")`` rule picks whichever of (heads, q-blocks)
    divides.  Returns (out, m, l) blocked as (B, nq, qc, KVH, G, ·).
    """
    from repro.distributed.sharding import constrain
    B, Sq, KVH, G, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // qc, Sk // kc
    scale = D ** -0.5

    qch = constrain(q.reshape(B, nq, qc, KVH, G, D), "qblocks")
    qpch = qpos.reshape(nq, qc)
    kch = k.reshape(B, nk, kc, KVH, D).swapaxes(0, 1)        # (nk, B, kc, ...)
    vch = v.reshape(B, nk, kc, KVH, D).swapaxes(0, 1)
    kpch = kpos.reshape(nk, kc)
    # dots take the native (bf16) inputs with f32 accumulation: full MXU
    # rate and half the operand traffic; softmax stats stay f32.
    def kv_step(carry, kin):
        m, l, acc = carry                                    # (B,nq,KVH,G,qc)
        ki, vi, kp = kin
        s = jnp.einsum("bnqhgd,bkhd->bnhgqk", qch, ki,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_flash_mask(qpch, kp, causal, window, nq, qc, kc),
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bnhgqk,bkhd->bnqhgd", p.astype(ki.dtype), vi,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr.transpose(0, 1, 4, 2, 3)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, nq, KVH, G, qc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, KVH, G, qc), jnp.float32)
    a0 = jnp.zeros((B, nq, qc, KVH, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kch, vch, kpch))
    denom = jnp.maximum(l, 1e-30).transpose(0, 1, 4, 2, 3)[..., None]
    return acc / denom, m, l


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _attn_flash(q, k, v, qpos, kpos, causal, window, qc, kc):
    """custom_vjp flash attention.

    Plain autodiff of the kv scan stacks its (m, l, acc) carries per step —
    ~2 GiB × layers × chunks of dynamic-update-slice traffic at the 7B
    train cell (EXPERIMENTS.md §Perf).  The custom backward recomputes
    s/p per kv chunk from the saved (q, k, v, out, m, l) instead — the
    standard flash-attention backward, O(B·S·H·D) residuals.
    """
    out, _, _ = _flash_fwd_impl(q, k, v, qpos, kpos, causal, window, qc, kc)
    B, Sq, KVH, G, D = q.shape
    return out.reshape(B, Sq, KVH, G, D)


def _flash_fwd_rule(q, k, v, qpos, kpos, causal, window, qc, kc):
    out, m, l = _flash_fwd_impl(q, k, v, qpos, kpos, causal, window, qc, kc)
    B, Sq, KVH, G, D = q.shape
    return (out.reshape(B, Sq, KVH, G, D),
            (q, k, v, qpos, kpos, out, m, l))


def _flash_bwd_rule(causal, window, qc, kc, res, dout):
    q, k, v, qpos, kpos, out, m, l = res
    B, Sq, KVH, G, D = q.shape
    Sk = k.shape[1]
    nq, nk = Sq // qc, Sk // kc
    scale = D ** -0.5

    qch = q.reshape(B, nq, qc, KVH, G, D)
    qpch = qpos.reshape(nq, qc)
    kch = k.reshape(B, nk, kc, KVH, D).swapaxes(0, 1)
    vch = v.reshape(B, nk, kc, KVH, D).swapaxes(0, 1)
    kpch = kpos.reshape(nk, kc)
    do = dout.reshape(B, nq, qc, KVH, G, D).astype(jnp.float32)
    l_safe = jnp.maximum(l, 1e-30)                           # (B,nq,h,g,qc)
    # D_i = rowsum(dout * out)
    Dsum = jnp.sum(do * out, axis=-1)                        # (B,nq,qc,h,g)
    Dsum = Dsum.transpose(0, 1, 3, 4, 2)                     # (B,nq,h,g,qc)

    dob = do.astype(q.dtype)

    def kv_step(dq, kin):
        ki, vi, kp = kin
        s = jnp.einsum("bnqhgd,bkhd->bnhgqk", qch, ki,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(_flash_mask(qpch, kp, causal, window, nq, qc, kc),
                      s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / l_safe[..., None]    # (B,nq,h,g,q,k)
        pb = p.astype(q.dtype)
        dv = jnp.einsum("bnhgqk,bnqhgd->bkhd", pb, dob,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bnqhgd,bkhd->bnhgqk", dob, vi,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - Dsum[..., None]) * scale
        dsb = ds.astype(q.dtype)
        dq = dq + jnp.einsum("bnhgqk,bkhd->bnqhgd", dsb, ki,
                             preferred_element_type=jnp.float32)
        dk = jnp.einsum("bnhgqk,bnqhgd->bkhd", dsb, qch,
                        preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((B, nq, qc, KVH, G, D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(kv_step, dq0, (kch, vch, kpch))
    dq = dq.reshape(B, Sq, KVH, G, D).astype(q.dtype)
    dk = dk.swapaxes(0, 1).reshape(B, Sk, KVH, D).astype(k.dtype)
    dv = dv.swapaxes(0, 1).reshape(B, Sk, KVH, D).astype(v.dtype)
    zero_pos = np.zeros(qpos.shape, dtype=jax.dtypes.float0)
    zero_kpos = np.zeros(kpos.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zero_pos, zero_kpos


_attn_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@_scoped(scopes.ATTN)
def attention_core(q, k, v, *, qpos, kpos, causal=True,
                   window: Optional[int] = None, chunk: int = 1024,
                   kv_len: Optional[jax.Array] = None) -> jax.Array:
    """GQA attention.  q: (B,Sq,H,D), k/v: (B,Sk,KVH,D).

    ``kv_len``: optional dynamic valid-length of k/v (decode with a
    pre-allocated cache) — positions >= kv_len are masked via kpos trick.
    """
    B, Sq, H, D = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, D)
    if kv_len is not None:
        # invalidate unwritten cache slots by pushing their kpos above any qpos
        kpos = jnp.where(jnp.arange(k.shape[1]) < kv_len, kpos,
                         jnp.int32(2 ** 30))
    if Sq * k.shape[1] <= chunk * chunk or Sq % min(chunk, Sq) != 0 \
            or k.shape[1] % chunk != 0:
        o = _attn_dense(qg, k, v, qpos, kpos, causal, window)
    else:
        qc = min(chunk, Sq)
        o = _attn_flash(qg, k, v, qpos, kpos, causal, window, qc, chunk)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


# ---- attention layer (projections + rope + cache) -------------------------------


def attn_params(key, d_model: int, n_heads: int, n_kv: int, hd: int,
                bias: bool = False, dtype=jnp.bfloat16, qk_norm=False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * hd, dtype),
        "wk": dense_init(ks[1], d_model, n_kv * hd, dtype),
        "wv": dense_init(ks[2], d_model, n_kv * hd, dtype),
        "wo": dense_init(ks[3], n_heads * hd, d_model, dtype),
    }
    if bias:
        p["bq"] = jnp.zeros((n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((n_kv * hd,), dtype)
        p["bv"] = jnp.zeros((n_kv * hd,), dtype)
    if qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


@dataclasses.dataclass
class KVCache:
    """Per-layer KV cache.  For SWA the buffer is a rolling window."""
    k: jax.Array          # (B, S_buf, KVH, D)
    v: jax.Array
    length: jax.Array     # scalar int32: tokens written so far

    @staticmethod
    def init(batch: int, buf: int, n_kv: int, hd: int, dtype=jnp.bfloat16):
        z = jnp.zeros((batch, buf, n_kv, hd), dtype)
        return KVCache(z, jnp.zeros_like(z), jnp.zeros((), jnp.int32))


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "length"], meta_fields=[])


@dataclasses.dataclass
class PackedKVCache:
    """Block-quantized per-layer KV cache (serving decode path).

    K/V rows are quantized along the head dim at write time
    (core/quantize.kv_quant_rows, RtN) and stored packed — uint8 E2M1
    nibble pairs + float8 block scales for ``fmt="nvfp4"`` (0.5625
    bytes/elem vs 2 for bf16), float8 codes + bf16 block scales for
    ``fmt="fp8"`` (1.125 bytes/elem).  Decode attention dequantizes
    blocks on the fly (``_attn_decode_packed`` / kernels.flash_attn's
    packed kernel) so the bf16 cache is never materialized in HBM.
    Same write semantics as ``KVCache`` (linear or SWA rolling buffer).
    """

    k_codes: jax.Array    # (B, S_buf, KVH, D/2) u8  | (B, S_buf, KVH, D) f8
    k_scales: jax.Array   # (B, S_buf, KVH, D/block) f8e4m3 | bf16
    v_codes: jax.Array
    v_scales: jax.Array
    length: jax.Array     # scalar int32: tokens written so far
    fmt: str = "nvfp4"
    block: int = 16

    @staticmethod
    def init(batch: int, buf: int, n_kv: int, hd: int, fmt: str = "nvfp4",
             block: int = 16) -> "PackedKVCache":
        if hd % block or hd % 2:
            raise ValueError(
                f"packed KV cache needs head_dim divisible by block={block} "
                f"(and even), got head_dim={hd}")
        if fmt == "nvfp4":
            codes = jnp.zeros((batch, buf, n_kv, hd // 2), jnp.uint8)
            scales = jnp.ones((batch, buf, n_kv, hd // block),
                              jnp.float8_e4m3fn)
        elif fmt == "fp8":
            codes = jnp.zeros((batch, buf, n_kv, hd), jnp.float8_e4m3fn)
            scales = jnp.ones((batch, buf, n_kv, hd // block), jnp.bfloat16)
        else:
            raise ValueError(f"unknown packed KV format {fmt!r}")
        return PackedKVCache(codes, scales, jnp.copy(codes),
                             jnp.copy(scales), jnp.zeros((), jnp.int32),
                             fmt, block)

    def dequant(self, dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
        """Full-cache (k, v) reconstruction — test oracle / fallback path."""
        from repro.core.quantize import kv_dequant
        return (kv_dequant(self.k_codes, self.k_scales, self.fmt,
                           self.block, dtype),
                kv_dequant(self.v_codes, self.v_scales, self.fmt,
                           self.block, dtype))

    def nbytes(self) -> int:
        """Stored cache bytes (codes + scales, k and v)."""
        return int(sum(a.size * a.dtype.itemsize for a in
                       (self.k_codes, self.k_scales,
                        self.v_codes, self.v_scales)))


jax.tree_util.register_dataclass(
    PackedKVCache,
    data_fields=["k_codes", "k_scales", "v_codes", "v_scales", "length"],
    meta_fields=["fmt", "block"])


def _kv_quant_any(x: jax.Array, fmt: str, block: int):
    """``kv_quant_rows`` plus the bf16 passthrough (codes = values, scales
    a (..., 1) placeholder) so paged caches treat all formats uniformly."""
    if fmt == "bf16":
        return (x.astype(jnp.bfloat16),
                jnp.ones(x.shape[:-1] + (1,), jnp.bfloat16))
    from repro.core.quantize import kv_quant_rows
    return kv_quant_rows(x, fmt, block)


def _kv_dequant_any(codes: jax.Array, scales: jax.Array, fmt: str,
                    block: int, dtype=jnp.bfloat16) -> jax.Array:
    if fmt == "bf16":
        return codes.astype(dtype)
    from repro.core.quantize import kv_dequant
    return kv_dequant(codes, scales, fmt, block, dtype)


# Physical page 0 is reserved as the TRASH page: freed slots' page-table
# rows point at it, so the static-shape decode program can keep writing for
# inactive slots without corrupting pages reallocated to other requests.
TRASH_PAGE = 0


@dataclasses.dataclass
class PagedKVCache:
    """Paged per-layer KV cache: pages allocated from a shared pool.

    vLLM-style continuous batching needs per-slot sequence lengths and
    block-granular storage reuse; this container provides both on top of
    the existing packed row formats:

      * ``k_codes``/``v_codes``: the PHYSICAL page pool, (P, page, KVH, Dc)
        where Dc follows ``fmt`` (nvfp4: D/2 uint8 nibble pairs, fp8: D
        float8 codes, bf16: D bf16 — the escape hatch);
      * ``k_scales``/``v_scales``: per-row block scales, (P, page, KVH, nb);
      * ``page_table``: (B, n_pages_slot) int32 physical page per logical
        page of each slot.  Rows of freed slots point at the reserved
        ``TRASH_PAGE`` so inactive slots' decode writes land harmlessly;
      * ``lengths``: (B,) int32 tokens written per slot — the per-slot
        ``kv_len``/``q_offset`` of continuous batching.

    The logical per-slot buffer is ``n_pages_slot * page_size`` tokens.
    SWA reuses the rolling-write rule of ``KVCache`` on the LOGICAL index
    (``pos % buf``), which then maps through the page table — the rolling
    buffer migrates onto pages instead of being special-cased again.
    """

    k_codes: jax.Array    # (P, page, KVH, Dc) physical pool
    k_scales: jax.Array   # (P, page, KVH, nb)
    v_codes: jax.Array
    v_scales: jax.Array
    page_table: jax.Array  # (B, n_pages_slot) int32
    lengths: jax.Array     # (B,) int32 per-slot tokens written
    fmt: str = "nvfp4"
    block: int = 16
    page_size: int = 16

    # KV-heads axis of every pool leaf (codes AND scales), also after
    # vmap stacks a leading layer dim — the axis tensor-parallel serving
    # shards over "model" (distributed/sharding.serve_cache_shardings):
    # each device holds the pages of exactly the heads it attends with.
    HEADS_AXIS = -2

    @property
    def n_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def buf(self) -> int:
        """Logical per-slot capacity in tokens."""
        return self.page_table.shape[1] * self.page_size

    @staticmethod
    def init(slots: int, buf: int, n_kv: int, hd: int, fmt: str = "nvfp4",
             block: int = 16, page_size: int = 16,
             total_pages: Optional[int] = None) -> "PagedKVCache":
        if buf % page_size:
            raise ValueError(f"slot buffer {buf} not a multiple of "
                             f"page_size {page_size}")
        if fmt in ("nvfp4", "fp8") and (hd % block or hd % 2):
            raise ValueError(
                f"packed KV cache needs head_dim divisible by block={block} "
                f"(and even), got head_dim={hd}")
        n_pages_slot = buf // page_size
        if total_pages is None:
            total_pages = 1 + slots * n_pages_slot    # +1: the trash page
        if fmt == "nvfp4":
            codes = jnp.zeros((total_pages, page_size, n_kv, hd // 2),
                              jnp.uint8)
            scales = jnp.ones((total_pages, page_size, n_kv, hd // block),
                              jnp.float8_e4m3fn)
        elif fmt == "fp8":
            codes = jnp.zeros((total_pages, page_size, n_kv, hd),
                              jnp.float8_e4m3fn)
            scales = jnp.ones((total_pages, page_size, n_kv, hd // block),
                              jnp.bfloat16)
        elif fmt == "bf16":
            codes = jnp.zeros((total_pages, page_size, n_kv, hd),
                              jnp.bfloat16)
            scales = jnp.ones((total_pages, page_size, n_kv, 1),
                              jnp.bfloat16)
        else:
            raise ValueError(f"unknown paged KV format {fmt!r}")
        return PagedKVCache(
            codes, scales, jnp.copy(codes), jnp.copy(scales),
            jnp.full((slots, n_pages_slot), TRASH_PAGE, jnp.int32),
            jnp.zeros((slots,), jnp.int32), fmt, block, page_size)

    # ---- writes ---------------------------------------------------------

    def write_prompt(self, slot, k: jax.Array, v: jax.Array,
                     plen) -> "PagedKVCache":
        """Prefill-into-slot: write a fresh (1, Sp, KVH, D) sequence into
        ``slot``'s pages at logical positions [0, Sp) and reset the slot's
        length to ``plen`` (the true prompt length; rows in [plen, Sp) are
        right-pad garbage masked out by ``lengths`` at read time).
        ``Sp <= buf`` so logical indices never collide (static check)."""
        if k.shape[1] > self.buf:
            raise ValueError(f"prefill length {k.shape[1]} exceeds slot "
                             f"capacity {self.buf}")
        return self.write_prompt_at(slot, k, v, 0, plen)

    @_scoped(scopes.KV_WRITE)
    def write_prompt_at(self, slot, k: jax.Array, v: jax.Array, start,
                        plen) -> "PagedKVCache":
        """Suffix prefill (shared-prefix admission): write a fresh
        (1, Sp, KVH, D) sequence into ``slot``'s pages at logical
        positions [start, start + Sp) and set the slot's length to
        ``plen`` (the TOTAL sequence length — cached prefix + true
        suffix).  ``start`` may be a traced scalar; right-pad positions
        that run past the slot buffer are redirected to the trash page
        so a static pad width never corrupts allocated pages."""
        Sp = k.shape[1]
        t = jnp.asarray(start, jnp.int32) + jnp.arange(Sp, dtype=jnp.int32)
        page = jnp.clip(t // self.page_size, 0,
                        self.page_table.shape[1] - 1)
        phys = self.page_table[slot, page]                      # (Sp,)
        phys = jnp.where(t < self.buf, phys, TRASH_PAGE)
        off = t % self.page_size
        kcod, ksc = _kv_quant_any(k[0], self.fmt, self.block)
        vcod, vsc = _kv_quant_any(v[0], self.fmt, self.block)
        return PagedKVCache(
            self.k_codes.at[phys, off].set(kcod),
            self.k_scales.at[phys, off].set(ksc),
            self.v_codes.at[phys, off].set(vcod),
            self.v_scales.at[phys, off].set(vsc),
            self.page_table,
            self.lengths.at[slot].set(jnp.asarray(plen, jnp.int32)),
            self.fmt, self.block, self.page_size)

    @_scoped(scopes.KV_WRITE)
    def write_token(self, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array] = None) -> "PagedKVCache":
        """Batched decode write: one (B, 1, KVH, D) token per slot at each
        slot's own length.  Inactive slots (freed mid-tick) write into the
        trash page their table rows point at — different live slots hold
        disjoint pages, so the scatter is collision-free where it matters.

        ``mask`` ((B,) bool, optional): slots with mask False are NOT
        decoding this step — their write is redirected to the trash page
        and their length does not advance.  This is how chunked prefill
        coexists with the static batched decode program: a mid-prefill
        slot's row points at REAL pages and its length is mid-prompt, so
        an unmasked decode write would scribble on prompt pages (and a
        length bump near the buffer edge could wrap ``lengths % buf``
        back onto page 0 of the slot).  Masked slots touch nothing."""
        posl = self.lengths % self.buf           # rolling == linear < buf
        page = posl // self.page_size
        off = posl % self.page_size
        phys = jnp.take_along_axis(self.page_table, page[:, None], 1)[:, 0]
        if mask is None:
            step = jnp.int32(1)
        else:
            m = jnp.asarray(mask, bool)
            phys = jnp.where(m, phys, TRASH_PAGE)
            step = m.astype(jnp.int32)
        kcod, ksc = _kv_quant_any(k[:, 0], self.fmt, self.block)
        vcod, vsc = _kv_quant_any(v[:, 0], self.fmt, self.block)
        return PagedKVCache(
            self.k_codes.at[phys, off].set(kcod),
            self.k_scales.at[phys, off].set(ksc),
            self.v_codes.at[phys, off].set(vcod),
            self.v_scales.at[phys, off].set(vsc),
            self.page_table, self.lengths + step,
            self.fmt, self.block, self.page_size)

    @_scoped(scopes.KV_WRITE)
    def write_tokens(self, k: jax.Array, v: jax.Array,
                     mask: Optional[jax.Array] = None) -> "PagedKVCache":
        """Batched multi-token write: S tokens per slot at each slot's own
        logical positions [len, len + S) — the teacher-forced verify block
        of speculative decoding.  Linear addressing only (the speculative
        path rejects SWA upstream: a rolling write could not be rolled
        back exactly).  Reuses the TRASH-page machinery of ``write_token``
        twice over: positions past the slot buffer AND every row of
        masked-off slots are redirected to the trash page, and masked
        slots' lengths do not advance.  Rejected rows are later undone by
        ``truncate_to`` — the pool keeps the stale codes but ``lengths``
        masks them out of every read."""
        B, S = k.shape[0], k.shape[1]
        t = (self.lengths[:, None]
             + jnp.arange(S, dtype=jnp.int32)[None, :])       # (B, S)
        page = jnp.clip(t // self.page_size, 0,
                        self.page_table.shape[1] - 1)
        phys = jnp.take_along_axis(self.page_table, page, 1)  # (B, S)
        phys = jnp.where(t < self.buf, phys, TRASH_PAGE)
        if mask is None:
            step = jnp.int32(S)
        else:
            m = jnp.asarray(mask, bool)
            phys = jnp.where(m[:, None], phys, TRASH_PAGE)
            step = m.astype(jnp.int32) * S
        off = t % self.page_size
        kcod, ksc = _kv_quant_any(k, self.fmt, self.block)
        vcod, vsc = _kv_quant_any(v, self.fmt, self.block)
        return PagedKVCache(
            self.k_codes.at[phys, off].set(kcod),
            self.k_scales.at[phys, off].set(ksc),
            self.v_codes.at[phys, off].set(vcod),
            self.v_scales.at[phys, off].set(vsc),
            self.page_table, self.lengths + step,
            self.fmt, self.block, self.page_size)

    def truncate_to(self, slot, new_len) -> "PagedKVCache":
        """Exact rollback of rejected appends: shrink length(s) to
        ``new_len`` without touching pool contents.  Rows in
        [new_len, old_len) become invisible immediately — every read
        masks by ``lengths`` (kv_len), the same mechanism that hides
        right-pad garbage — and the next append overwrites them in
        place, so no zeroing pass exists to diverge bit-wise.  Page
        refcounts live host-side (the scheduler's PagePool) and are
        untouched: pages stay with the slot, only the logical
        high-water mark moves.

        ``slot=None``: batched rollback, ``new_len`` a (B,) vector
        (broadcasts over a scan-stacked (L, B) ``lengths``).  Clamped so
        truncation can never extend a slot."""
        nl = jnp.asarray(new_len, jnp.int32)
        if slot is None:
            lens = jnp.minimum(self.lengths, nl)
        else:
            cur = self.lengths[slot]
            lens = self.lengths.at[slot].set(jnp.minimum(cur, nl))
        return dataclasses.replace(self, lengths=lens)

    # ---- reads ----------------------------------------------------------

    @_scoped(scopes.KV_READ)
    def gather_slots(self):
        """Gather the logical (B, buf, KVH, ·) packed views through the
        page table (the jnp mirror of the Pallas kernel's per-page DMA)."""
        pt = self.page_table

        def g(pool):
            a = pool[pt]                  # (B, n_pages, page, KVH, ·)
            return a.reshape((pt.shape[0], -1) + pool.shape[2:])

        return (g(self.k_codes), g(self.k_scales),
                g(self.v_codes), g(self.v_scales))

    @_scoped(scopes.KV_READ)
    def gather_slot(self, slot):
        """ONE slot's logical (1, buf, KVH, ·) packed views (``slot`` may
        be traced) — the read side of suffix prefill, which attends a
        single slot's pages while other slots keep decoding."""
        row = jax.lax.dynamic_index_in_dim(
            self.page_table, jnp.asarray(slot, jnp.int32), 0,
            keepdims=False)               # (n_pages,)

        def g(pool):
            a = pool[row]                 # (n_pages, page, KVH, ·)
            return a.reshape((1, -1) + pool.shape[2:])

        return (g(self.k_codes), g(self.k_scales),
                g(self.v_codes), g(self.v_scales))

    @_scoped(scopes.KV_READ)
    def dequant(self, dtype=jnp.bfloat16) -> Tuple[jax.Array, jax.Array]:
        """Full logical (B, buf, KVH, D) reconstruction — test oracle."""
        kc, ks, vc, vs = self.gather_slots()
        return (_kv_dequant_any(kc, ks, self.fmt, self.block, dtype),
                _kv_dequant_any(vc, vs, self.fmt, self.block, dtype))

    def nbytes(self) -> int:
        """Stored pool bytes (codes + scales, k and v)."""
        return int(sum(a.size * a.dtype.itemsize for a in
                       (self.k_codes, self.k_scales,
                        self.v_codes, self.v_scales)))


jax.tree_util.register_dataclass(
    PagedKVCache,
    data_fields=["k_codes", "k_scales", "v_codes", "v_scales",
                 "page_table", "lengths"],
    meta_fields=["fmt", "block", "page_size"])


def swa_kpos(lengths: jax.Array, buf: int) -> jax.Array:
    """Absolute position held by each logical slot of a rolling buffer:
    slot j holds the most recent token with pos % buf == j.  ``lengths``:
    (B,) per-slot lengths -> (B, buf); unwritten slots come out negative
    (mask with ``kpos >= 0`` or the kv_len rule)."""
    last = lengths[:, None] - 1
    slot = jnp.arange(buf, dtype=jnp.int32)[None, :]
    return last - ((last % buf - slot) % buf)


def make_kv_cache(batch: int, buf: int, n_kv: int, hd: int,
                  dtype=jnp.bfloat16, kv_format: str = "bf16",
                  page_size: Optional[int] = None,
                  total_pages: Optional[int] = None):
    """Cache-shape API: bf16 ``KVCache``, block-quantized ``PackedKVCache``,
    or (``page_size`` set) a ``PagedKVCache`` over a shared page pool."""
    if page_size:
        return PagedKVCache.init(batch, buf, n_kv, hd, fmt=kv_format,
                                 page_size=page_size,
                                 total_pages=total_pages)
    if kv_format == "bf16":
        return KVCache.init(batch, buf, n_kv, hd, dtype)
    return PackedKVCache.init(batch, buf, n_kv, hd, fmt=kv_format)


@_scoped(scopes.ATTN)
def _attn_decode_fused(q, k_codes, k_scales, v_codes, v_scales, fmt: str,
                       block: int, *, qpos, kpos, causal, window, kv_len,
                       chunk: int = 1024) -> jax.Array:
    """Fused decode attention core: flash-style scan over kv chunks with
    running (max, denom, acc) stats, dequantizing each chunk's K/V blocks
    inside the scan body — only one chunk of bf16 K/V ever exists at a
    time (the jnp mirror of the Pallas kernel's in-VMEM dequant).

    Positions may be SHARED or PER-SLOT (continuous batching):
      * qpos: (Sq,) or (B, Sq) absolute query positions;
      * kpos: (S_buf,) or (B, S_buf) absolute position held by each slot;
      * kv_len: None, scalar, or (B,) valid-slot counts.
    q: (B, Sq, H, D) with Sq small (decode: 1); codes/scales: the packed
    (B, S_buf, KVH, ·) layouts (``fmt`` "nvfp4"/"fp8"/"bf16").
    """
    B, Sq, H, D = q.shape
    KVH = k_codes.shape[2]
    G = H // KVH
    buf = k_codes.shape[1]
    kc = chunk if buf % chunk == 0 else buf
    nk = buf // kc
    scale = D ** -0.5
    qf = q.reshape(B, Sq, KVH, G, D).astype(jnp.float32)
    qpos = jnp.broadcast_to(jnp.atleast_2d(qpos), (B, Sq))
    kpos = jnp.broadcast_to(jnp.atleast_2d(kpos), (B, buf))
    if kv_len is not None:
        kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
        kpos = jnp.where(jnp.arange(buf)[None, :] < kv_len[:, None], kpos,
                         jnp.int32(2 ** 30))

    def chunked(a):
        return a.reshape((B, nk, kc) + a.shape[2:]).swapaxes(0, 1)

    kin = (chunked(k_codes), chunked(k_scales),
           chunked(v_codes), chunked(v_scales),
           kpos.reshape(B, nk, kc).swapaxes(0, 1))

    def kv_step(carry, xs):
        m, l, acc = carry                                  # (B,KVH,G,Sq[,D])
        kc_, ks_, vc_, vs_, kp = xs
        with jax.named_scope(scopes.KV_READ):
            ki = _kv_dequant_any(kc_, ks_, fmt, block, jnp.float32)
            vi = _kv_dequant_any(vc_, vs_, fmt, block, jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, ki) * scale
        mask = jnp.ones((B, Sq, kc), bool)
        if causal:
            mask &= kp[:, None, :] <= qpos[:, :, None]
        if window is not None:
            mask &= kp[:, None, :] > qpos[:, :, None] - window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vi)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), kin)
    o = acc / jnp.maximum(l, 1e-30)[..., None]             # (B,KVH,G,Sq,D)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


def _attn_decode_packed(q, cache: PackedKVCache, *, qpos, kpos, causal,
                        window, kv_len, chunk: int = 1024) -> jax.Array:
    """Decode attention over a (non-paged) packed cache — see
    ``_attn_decode_fused`` for the scan; positions are shared scalars here."""
    return _attn_decode_fused(q, cache.k_codes, cache.k_scales,
                              cache.v_codes, cache.v_scales, cache.fmt,
                              cache.block, qpos=qpos, kpos=kpos,
                              causal=causal, window=window, kv_len=kv_len,
                              chunk=chunk)


def _attn_decode_paged(q, cache: PagedKVCache, *, qpos, kpos, causal,
                       window, kv_len, chunk: int = 1024) -> jax.Array:
    """Decode attention over a PAGED cache with per-slot lengths: gather
    the packed K/V tiles through the page table (still at packed width —
    the bf16 cache never exists), then run the fused per-slot scan."""
    kc, ks, vc, vs = cache.gather_slots()
    return _attn_decode_fused(q, kc, ks, vc, vs, cache.fmt, cache.block,
                              qpos=qpos, kpos=kpos, causal=causal,
                              window=window, kv_len=kv_len, chunk=chunk)


def attn_apply(p, x, ctx: QCtx, *, n_heads: int, n_kv: int, hd: int,
               rope_theta: float, causal: bool = True,
               window: Optional[int] = None, chunk: int = 1024,
               positions: Optional[jax.Array] = None,
               cache=None, slot=None, plen=None, pfx=None,
               write_mask: Optional[jax.Array] = None,
               xkv: Optional[jax.Array] = None,
               norm_eps: float = 1e-5, use_rope: bool = True):
    """Self- (or cross-, via xkv) attention with optional KV cache update.

    Returns (out, new_cache).  With a cache (``KVCache`` or block-quantized
    ``PackedKVCache``), x is the *new* tokens (decode: S=1; prefill:
    S=prompt) written at positions [cache.length, cache.length + S).  For
    SWA the cache buffer is min(window, S_buf) and written modulo buffer
    size (rolling).  Packed caches quantize writes (RtN along the head dim)
    and the decode read dequantizes blocks on the fly.

    With a ``PagedKVCache`` each batch row is an independent SLOT with its
    own length: decode (S=1, ``slot=None``) writes every slot's token at
    that slot's position and attends with per-slot kv_len/q_offset;
    prefill-into-slot (``slot`` given, B=1) writes a fresh right-padded
    prompt into one slot's pages and resets its length to ``plen``.
    With ``pfx`` (shared-prefix admission) x is only the SUFFIX of the
    prompt: its K/V rows are written at [pfx, pfx + S) and the queries
    attend THROUGH the paged cache — the shared prefix pages plus the
    just-written suffix rows, dequantized on the fly — so one compiled
    suffix program serves every (pfx, plen) warm admission.
    ``write_mask`` ((B,) bool, batched paged decode only): slots mid-
    chunked-prefill write to the trash page and keep their length (see
    ``PagedKVCache.write_token``).
    """
    B, S, d = x.shape
    src = x if xkv is None else xkv
    q = ctx.dense(x, p["wq"], p.get("bq"))
    k = ctx.dense(src, p["wk"], p.get("bk"))
    v = ctx.dense(src, p["wv"], p.get("bv"))
    q = constrain(q.reshape(B, S, n_heads, hd), "heads")
    k = k.reshape(B, src.shape[1], n_kv, hd)
    v = v.reshape(B, src.shape[1], n_kv, hd)

    paged = isinstance(cache, PagedKVCache)
    if positions is None:
        if paged:
            # per-slot positions (continuous batching); a fresh prefill
            # slot starts at 0, a suffix prefill at the cached prefix
            if slot is not None:
                base = 0 if pfx is None else jnp.asarray(pfx, jnp.int32)
                positions = base + jnp.arange(S, dtype=jnp.int32)
            else:
                positions = (cache.lengths[:, None]
                             + jnp.arange(S, dtype=jnp.int32)[None, :])
        else:
            base = cache.length if cache is not None else 0
            positions = base + jnp.arange(S, dtype=jnp.int32)

    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], norm_eps)
        k = rmsnorm(k, p["k_norm"], norm_eps)

    if use_rope and xkv is None:
        cos_q, sin_q = rope_tables(positions, hd, rope_theta)
        if positions.ndim == 1:                # shared -> add batch dim;
            cos_q, sin_q = cos_q[None], sin_q[None]   # per-slot is (B, S, ·)
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_q, sin_q)

    new_cache = None
    if paged and xkv is None:
        buf = cache.buf
        if slot is not None and pfx is not None:
            # SUFFIX prefill (shared-prefix admission, B == 1): the slot's
            # prefix pages already hold [0, pfx); write the fresh suffix
            # rows at [pfx, pfx + S) and attend THROUGH the paged cache —
            # shared prefix + just-written suffix, dequantized on the fly
            # (right-pad rows land masked or on the trash page).
            if window is not None:
                raise NotImplementedError(
                    "shared-prefix suffix prefill needs a linear cache; "
                    "SWA rolling buffers rewrite shared pages")
            total = S if plen is None else plen
            new_cache = cache.write_prompt_at(slot, k, v, pfx, total)
            kc, ks, vc, vs = new_cache.gather_slot(slot)
            o = _attn_decode_fused(
                q, kc, ks, vc, vs, new_cache.fmt, new_cache.block,
                qpos=positions, kpos=jnp.arange(buf, dtype=jnp.int32),
                causal=causal, window=None,
                kv_len=jnp.asarray(total, jnp.int32), chunk=chunk)
        elif slot is not None:
            # prefill-into-slot (B == 1): write the fresh sequence into the
            # slot's pages; attend within the fresh tokens directly (right-
            # pad rows are garbage queries whose outputs the caller drops).
            new_cache = cache.write_prompt(
                slot, k, v, S if plen is None else plen)
            o = attention_core(q, k, v, qpos=positions, kpos=positions,
                               causal=causal, window=window, chunk=chunk)
        elif S == 1:
            # batched decode: per-slot write + per-slot read
            new_cache = cache.write_token(k, v, mask=write_mask)
            lengths = new_cache.lengths                   # post-write
            if window is not None:
                kpos = swa_kpos(lengths, buf)
            else:
                kpos = jnp.broadcast_to(
                    jnp.arange(buf, dtype=jnp.int32)[None, :], (B, buf))
            kv_len = jnp.minimum(lengths, buf)
            o = _attn_decode_paged(q, new_cache, qpos=positions, kpos=kpos,
                                   causal=causal, window=window,
                                   kv_len=kv_len, chunk=chunk)
        else:
            # batched verify (speculative decode, S == k): write the k
            # teacher-forced rows at each slot's [len, len + k), then read
            # through the page table with per-slot positions.  Causal
            # masking makes query row j see exactly rows [0, len + j] —
            # the same set the sequential decode of token j would see —
            # and RtN row quantization is neighbor-independent, so each
            # row's logits are BIT-identical to non-speculative decode.
            if window is not None:
                raise NotImplementedError(
                    "speculative verify needs a linear paged cache; SWA "
                    "rolling buffers cannot roll back exactly")
            new_cache = cache.write_tokens(k, v, mask=write_mask)
            kpos = jnp.broadcast_to(
                jnp.arange(buf, dtype=jnp.int32)[None, :], (B, buf))
            kv_len = jnp.minimum(new_cache.lengths, buf)
            o = _attn_decode_paged(q, new_cache, qpos=positions, kpos=kpos,
                                   causal=causal, window=None,
                                   kv_len=kv_len, chunk=chunk)
    elif cache is not None and xkv is None:
        packed = isinstance(cache, PackedKVCache)
        buf = (cache.k_codes if packed else cache.k).shape[1]
        start = cache.length % buf if window is not None else cache.length
        # rolling write (SWA) or linear write; S tokens, may wrap for SWA.
        # If more new tokens than buffer slots, only the last `buf` survive —
        # slice first so `.at[idx].set` never sees duplicate indices.
        kw, vw, Sw = k, v, S
        if S > buf:
            kw, vw, Sw = k[:, S - buf:], v[:, S - buf:], buf
            start = (cache.length + (S - buf)) % buf
        idx = (start + jnp.arange(Sw, dtype=jnp.int32)) % buf
        new_len = cache.length + S
        if packed:
            from repro.core.quantize import kv_quant_rows
            kcod, ksc = kv_quant_rows(kw, cache.fmt, cache.block)
            vcod, vsc = kv_quant_rows(vw, cache.fmt, cache.block)
            new_cache = PackedKVCache(
                cache.k_codes.at[:, idx].set(kcod),
                cache.k_scales.at[:, idx].set(ksc),
                cache.v_codes.at[:, idx].set(vcod),
                cache.v_scales.at[:, idx].set(vsc),
                new_len, cache.fmt, cache.block)
        else:
            ck = cache.k.at[:, idx].set(kw)
            cv = cache.v.at[:, idx].set(vw)
            new_cache = KVCache(ck, cv, new_len)
        if S > 1:
            # Prefill (assumed from an empty cache): attend within the fresh
            # sequence directly — correct for SWA even when S > buf, since
            # every query's window lies inside the fresh K/V.
            o = attention_core(q, k, v, qpos=positions, kpos=positions,
                               causal=causal, window=window, chunk=chunk)
        else:
            # Decode: attend the cache buffer.  Absolute position held by
            # each slot: for SWA, slot j holds the most recent token with
            # pos % buf == j; linear caches store pos == slot.
            if window is not None:
                slot = jnp.arange(buf, dtype=jnp.int32)
                last = new_len - 1
                kpos = last - ((last % buf - slot) % buf)
            else:
                kpos = jnp.arange(buf, dtype=jnp.int32)
            kv_len = jnp.minimum(new_len, buf)
            if packed:
                # dequantize-fused read: K/V blocks decode inside the score
                # loop instead of materializing a bf16 cache first
                o = _attn_decode_packed(q, new_cache, qpos=positions,
                                        kpos=kpos, causal=causal,
                                        window=window, kv_len=kv_len,
                                        chunk=chunk)
            else:
                o = attention_core(q, ck, cv, qpos=positions, kpos=kpos,
                                   causal=causal, window=window, chunk=chunk,
                                   kv_len=kv_len)
    else:
        kpos = (positions if xkv is None
                else jnp.arange(src.shape[1], dtype=jnp.int32))
        o = attention_core(q, k, v, qpos=positions, kpos=kpos,
                           causal=causal and xkv is None, window=window,
                           chunk=chunk)

    o = constrain(o, "heads")
    out = ctx.dense(o.reshape(B, S, n_heads * hd), p["wo"])
    return out, new_cache


# ---- MLP block -------------------------------------------------------------------


def mlp_params(key, d_model: int, d_ff: int, act: str, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    if act in ("swiglu", "smooth_swiglu"):
        p = {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
        if act == "smooth_swiglu":
            p["smooth"] = jnp.ones((d_ff,), dtype)
        return p
    return {
        "w_in": dense_init(ks[0], d_model, d_ff, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(ks[1], d_ff, d_model, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def mlp_apply(p, x, ctx: QCtx, act: str):
    if act in ("swiglu", "smooth_swiglu"):
        g = constrain(ctx.dense(x, p["w_gate"]), "hidden")
        u = constrain(ctx.dense(x, p["w_up"]), "hidden")
        if act == "smooth_swiglu":
            h = smooth_swiglu(g, u, p["smooth"])
            return ctx.dense(h, p["w_down"]) * 1.0  # scale folded into w_down
        h = swiglu(g, u)
        return ctx.dense(h, p["w_down"])
    h = jax.nn.gelu(ctx.dense(x, p["w_in"], p["b_in"]).astype(jnp.float32))
    h = constrain(h, "hidden")
    return ctx.dense(h.astype(x.dtype), p["w_out"], p["b_out"])
