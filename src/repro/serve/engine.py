"""Serving engines: FP4 forward, prefill + decode with KV caches.

The deployed artifact of the paper's pipeline is an *FP4-forward* model (the
QAF phase keeps the forward path in FP4 precisely so the served model is
FP4-inference-compatible).  Both engines run every weight GEMM through the
same NVFP4 RtN forward quantization used in training — serving is
numerically identical to the training forward pass.

Two engines share the packed-weight/packed-cache machinery:

  * ``Engine`` — LOCKSTEP batches: all requests prefill together and the
    batch decodes until every sequence finishes.  Simple, and the numeric
    reference for the continuous engine.
  * ``ContinuousEngine`` — vLLM-style CONTINUOUS batching over a paged
    NVFP4 KV cache.  Request lifecycle (admission queue, per-slot lengths,
    slot free/reuse on EOS/max_len, demand-driven paging + preemption,
    abort/timeout cancellation, the exact shared-prefix cache) lives in
    ``serve/scheduler.py`` on the host; the device side is EXACTLY FIVE
    jitted programs with static shapes —

        prefill-into-slot : right-padded (1, prefill_len) prompt into one
                            slot's pages (dynamic slot/plen operands)
        prefill-suffix    : warm shared-prefix admission — only the
                            prompt SUFFIX (dynamic pfx/plen/slot), the
                            prefix pages are shared from the prefix cache
        prefill-chunk     : one FULL intermediate chunk of a long prompt
                            (chunked prefill; dynamic slot/offset
                            operands, no sampling — the final short
                            chunk reuses prefill-suffix)
        batched decode    : one token for every slot, per-slot
                            kv_len/q_offset VECTOR operands + an active
                            mask freezing mid-prefill slots
        verify-k          : speculative decoding (``spec_k``) — the
                            layer-truncated self-draft proposes k-1
                            tokens per slot, one teacher-forced pass
                            verifies the block bit-exactly, rejected
                            cache rows roll back (truncate_to); static
                            (slots, k) shapes, accepted length is a
                            dynamic OUTPUT

    so admitting a queued request into a freed slot never recompiles.
    Host sync happens once per scheduler TICK (``decode_chunk`` steps),
    not per token.

  * quantize-once packed weights: GEMM weights are packed to NVFP4 storage
    (uint8 nibble codes + float8 block scales, ~0.56 bytes/param) at
    engine build — bit-identical tokens (serve/packing.py).
  * block-quantized KV cache (``ServeConfig.kv_cache_format``): "nvfp4"
    (default, 0.5625 bytes/elem), "fp8", or the "bf16" escape hatch; the
    continuous engine stores the same formats per PAGE
    (models/layers.PagedKVCache).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fqt
from repro.distributed import sharding as shd
from repro.models import registry
from repro.models.config import ModelConfig
from repro.models.layers import TRASH_PAGE, PagedKVCache
from repro.obs.trace import NULL_TRACER
from repro.serve import packing
from repro.serve.metrics import MetricsRecorder
from repro.serve.scheduler import Request, Scheduler


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_size: int = 8
    max_len: int = 2048
    temperature: float = 0.0      # 0 => greedy
    top_k: int = 0                # 0 => no top-k filtering
    eos_id: int = 2
    seed: int = 0
    # KV cache storage: "nvfp4" (E2M1 nibble codes + f8 block scales along
    # the head dim, 0.5625 bytes/elem, ~3.56x less decode-attention HBM
    # traffic), "fp8" (f8 codes + bf16 block scales, 1.125 bytes/elem), or
    # "bf16" — the unquantized escape hatch.  Cache writes are quantized
    # with RtN (the paper's inference forward rounding); decode attention
    # dequantizes K/V blocks on the fly, never materializing a bf16 cache.
    kv_cache_format: str = "nvfp4"
    # ---- continuous batching (ContinuousEngine) -------------------------
    page_size: int = 16           # tokens per KV page
    max_slots: Optional[int] = None    # decode slots (default: batch_size)
    total_pages: Optional[int] = None  # page-pool size (None: one full
                                       # reservation per slot + trash page)
    prefill_len: Optional[int] = None  # static prefill pad (None: derived
                                       # from the submitted trace)
    decode_chunk: int = 8         # decode steps per scheduler tick — the
                                  # host-sync cadence for BOTH engines
    # chunked prefill: a prompt enters its slot ``prefill_chunk`` tokens
    # per scheduler TICK (a fourth jitted program with dynamic offset
    # operands), interleaved with decode — one long prompt never stalls a
    # decode tick by more than one chunk.  Each chunk attends THROUGH the
    # quantized paged cache (the prefix-cache suffix machinery), so
    # chunking is bit-exact for every kv_cache_format.  Dense/moe,
    # linear (non-SWA) caches only.  None = full prefill at admission.
    prefill_chunk: Optional[int] = None
    # exact shared-prefix cache (serve/prefix_cache.py): admissions whose
    # prompt shares cached full pages point their page-table rows at the
    # shared physical pages and prefill only the suffix.  Dense/moe,
    # linear (non-SWA) caches only.
    prefix_cache: bool = False
    prefix_cache_pages: Optional[int] = None   # cap on cached pages (LRU)
    # ---- speculative decoding (ContinuousEngine) ------------------------
    # self-draft verify-k: every decode tick, a draft model made of the
    # FIRST ``draft_layers`` layers of the SAME packed weights (a trace-
    # level slice of the stacked layer axis — zero extra HBM for weights)
    # proposes spec_k - 1 greedy tokens per slot from a sliced, discarded
    # copy of the paged caches; ONE batched teacher-forced verify pass
    # through the quantized paged cache then accepts the longest matching
    # greedy prefix plus one corrected token (1..spec_k tokens per slot
    # per tick) and rolls the rejected rows back exactly
    # (PagedKVCache.truncate_to).  Greedy verification is provably
    # output-identical: speculative streams are BIT-identical to the
    # non-speculative engine for every kv_cache_format.  Greedy only
    # (temperature == 0), dense/moe families, linear (non-SWA) caches.
    spec_k: Optional[int] = None        # verify block size (None = off)
    draft_layers: Optional[int] = None  # draft depth (None with spec_k on:
                                        # n_layers // 2)
    # ---- mesh-native serving --------------------------------------------
    # "--mesh" spec ("tp=2", "dp=2,tp=4", ...) for the explicit serving
    # Mesh BOTH engines place their weights and KV pools under.  None means
    # the degenerate 1-device mesh — the SAME code path (placement under a
    # 1-device mesh is the identity), never an ``if sharded:`` fork.  TP
    # shards heads/hidden/vocab on "model" (Megatron column/row-parallel
    # packed GEMMs via GSPMD); KV page pools shard their KV-heads axis.
    mesh: Optional[str] = None


def _sample(logits: jax.Array, key, scfg: ServeConfig) -> jax.Array:
    """logits: (B, V) -> (B,) int32."""
    if scfg.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / scfg.temperature
    if scfg.top_k > 0:
        kth = jax.lax.top_k(logits, scfg.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def _greedy_margin(logits: jax.Array) -> jax.Array:
    """Top1-top2 logit gap per row — how decisive the greedy pick is.
    Near-tied rows are where bounded numeric perturbations flip greedy
    tokens (random-init smoke models have near-flat logits); the engine
    tests gate token-identity assertions on this margin."""
    top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
    return top2[..., 0] - top2[..., 1]


class Engine:
    """Single-model LOCKSTEP serving engine over the uniform registry API."""

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 qcfg: Optional[fqt.QuantConfig] = None,
                 pack_weights: bool = True,
                 mesh: Optional[Mesh] = None):
        self.cfg, self.scfg = cfg, scfg
        # serving default: the paper's FP4 forward (RtN), nothing else
        self.qcfg = qcfg if qcfg is not None else fqt.qaf_config()
        # ONE mesh-native path: scfg.mesh == None resolves to the 1-device
        # mesh, whose placement is the identity — no ``if sharded:`` fork.
        self.mesh = mesh if mesh is not None \
            else shd.make_serve_mesh(scfg.mesh)
        self._rep = NamedSharding(self.mesh, P())
        # quantize ONCE: every GEMM weight becomes packed NVFP4 storage;
        # the forward consumes it directly (fqt._packed_forward), token-
        # identical to re-fake-quantizing per GEMM.  Packed or not, the
        # tree is placed under the serving mesh (congruent code/scale
        # specs for packed leaves, rank+name rules otherwise).
        spec = self.qcfg.fwd_w \
            if (pack_weights and self.qcfg.fwd_w is not None) else None
        self.params = packing.pack_model_params(cfg, params, spec,
                                                mesh=self.mesh)

        # the weights are an ARGUMENT of every compiled program, never a
        # closed-over constant: a constant is copied into the executable
        # (GBs at published widths — host RAM and cache entries blow up)
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(3,))

    def _replicate(self, *xs):
        """Pin small host-facing arrays (tokens/masks/keys) replicated on
        the serving mesh, so every jit call sees the SAME input shardings
        (no-recompile guarantee) and GSPMD never scatters token vectors."""
        out = tuple(jax.device_put(x, self._rep) for x in xs)
        return out if len(out) > 1 else out[0]

    # ---- compiled kernels --------------------------------------------------

    def _prefill_impl(self, params, tokens, carry, extras):
        logits, carry = registry.prefill(params, self.cfg, self.qcfg,
                                         tokens, carry, extras=extras)
        return logits, shd.constrain_serve_cache(carry, self.mesh)

    def _decode_impl(self, params, tokens, done, carry, key):
        """One lockstep decode step with ON-DEVICE done/EOS bookkeeping:
        emit = the masked output token for this step, done accumulates the
        EOS mask, and the PRNG chain advances on device — the host only
        syncs once per ``decode_chunk`` tick."""
        eos = jnp.int32(self.scfg.eos_id)
        emit = jnp.where(done, eos, tokens)
        done = done | (tokens == eos)
        key, sub = jax.random.split(key)
        logits, carry = registry.decode_step(params, self.cfg,
                                             self.qcfg, emit[:, None],
                                             carry)
        nxt = _sample(logits[:, -1], sub, self.scfg)
        # pin the small host-facing outputs replicated: the NEXT call's
        # input shardings equal this call's (one compile per program, on
        # any mesh); purely a layout annotation after sampling — the
        # GEMM/attention numerics upstream are untouched.
        emit, done, nxt, key = (
            jax.lax.with_sharding_constraint(x, self._rep)
            for x in (emit, done, nxt, key))
        return emit, done, nxt, shd.constrain_serve_cache(carry,
                                                          self.mesh), key

    # ---- public API ----------------------------------------------------------

    def generate(self, prompts: List[np.ndarray], max_new: int = 32,
                 extras: Optional[dict] = None) -> List[np.ndarray]:
        """Greedy/temperature generation for a batch of token prompts."""
        scfg, cfg = self.scfg, self.cfg
        B = len(prompts)
        if B > scfg.batch_size:
            raise ValueError(f"{B} prompts > batch_size {scfg.batch_size}")
        # pad the batch to the fixed slot count
        plen = max(len(p) for p in prompts)
        toks = np.zeros((scfg.batch_size, plen), np.int32)
        for i, p in enumerate(prompts):
            toks[i, plen - len(p):] = p       # left-pad (simplest static shape)
        toks = jnp.asarray(toks)

        carry = registry.make_decode_state(
            cfg, scfg.batch_size, scfg.max_len,
            kv_cache_format=scfg.kv_cache_format)
        carry = shd.place_serve_cache(carry, self.mesh)
        toks = self._replicate(toks)
        extras = extras or {}
        last_logits, carry = self._prefill(self.params, toks, carry, extras)

        # PRNG hygiene: split the root key FIRST — the first sampled token
        # uses a child, never the parent of the per-step chain.
        key, sub = jax.random.split(jax.random.PRNGKey(scfg.seed))
        nxt = _sample(last_logits, sub, scfg)
        key, nxt = self._replicate(key, nxt)
        done = self._replicate(jnp.zeros((scfg.batch_size,), bool))
        emitted = []                      # device arrays; no per-step sync
        sync = max(1, scfg.decode_chunk)
        for t in range(max_new):
            emit, done, nxt, carry, key = self._decode(self.params, nxt, done,
                                                       carry, key)
            emitted.append(emit)
            # transfer the done mask once per tick, not per token
            if (t + 1) % sync == 0 and bool(np.asarray(done).all()):
                break
        if not emitted:                   # max_new == 0
            return [np.zeros((0,), np.int32) for _ in range(B)]
        out = np.asarray(jnp.stack(emitted, axis=1))     # one transfer
        # truncate at the first step where every row had emitted its EOS
        seen = np.cumsum(out == scfg.eos_id, axis=1) > 0
        alldone = seen.all(axis=0)
        if alldone.any():
            out = out[:, : int(np.argmax(alldone)) + 1]
        return [out[i] for i in range(B)]


class ContinuousEngine:
    """Continuous batching over a paged, block-quantized KV cache.

    Requests arrive on a (deterministic, tick-indexed) trace, wait in the
    scheduler's FIFO queue, and are admitted whenever a slot AND enough
    free pages exist; slots free on EOS/max_new and are reused without
    recompilation.  Families: dense/moe transformers and the whisper
    decoder (``encdec``).  The recurrent families absorb pad tokens into
    O(1) state, so a static right-padded prefill can't serve them — they
    stay on the lockstep ``Engine`` (registry.prefill_slot raises).
    """

    def __init__(self, cfg: ModelConfig, params, scfg: ServeConfig,
                 qcfg: Optional[fqt.QuantConfig] = None,
                 pack_weights: bool = True,
                 mesh: Optional[Mesh] = None,
                 tracer=None):
        if cfg.family not in ("dense", "moe", "encdec"):
            raise NotImplementedError(
                f"continuous batching serves dense/moe/encdec families; "
                f"{cfg.family!r} stays on the lockstep Engine")
        # host-side trace emission only (obs/trace.py): spans per tick,
        # instants per jit compile — NEVER inside the jitted bodies below
        # (fp4lint's obs-in-jit rule enforces this), so an attached tracer
        # cannot perturb tokens or compile counts
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cfg, self.scfg = cfg, scfg
        self.qcfg = qcfg if qcfg is not None else fqt.qaf_config()
        # same mesh-native path as the lockstep Engine (1-device default)
        self.mesh = mesh if mesh is not None \
            else shd.make_serve_mesh(scfg.mesh)
        self._rep = NamedSharding(self.mesh, P())
        spec = self.qcfg.fwd_w \
            if (pack_weights and self.qcfg.fwd_w is not None) else None
        self.params = packing.pack_model_params(cfg, params, spec,
                                                mesh=self.mesh)

        self.n_slots = scfg.max_slots or scfg.batch_size
        psz = scfg.page_size
        buf = (scfg.max_len if cfg.sliding_window is None
               else min(scfg.max_len, cfg.sliding_window))
        self.slot_buf = -(-buf // psz) * psz   # logical tokens per slot
        self.n_pages_slot = self.slot_buf // psz
        if scfg.prefix_cache and (cfg.family not in ("dense", "moe")
                                  or cfg.sliding_window is not None):
            raise NotImplementedError(
                "prefix_cache needs prompt-pure K/V and a linear cache: "
                "dense/moe families without a sliding window")
        if scfg.prefill_chunk is not None:
            if cfg.family not in ("dense", "moe") or \
                    cfg.sliding_window is not None:
                raise NotImplementedError(
                    "prefill_chunk needs prompt-pure K/V and a linear "
                    "cache: dense/moe families without a sliding window "
                    "(chunks attend THROUGH the quantized paged cache)")
            if not 1 <= scfg.prefill_chunk <= self.slot_buf:
                raise ValueError(
                    f"prefill_chunk {scfg.prefill_chunk} out of range "
                    f"[1, {self.slot_buf}]")
        self.spec = scfg.spec_k is not None
        self.draft_layers = 0
        if scfg.draft_layers is not None and not self.spec:
            raise ValueError("draft_layers requires spec_k (speculative "
                             "decoding off)")
        if self.spec:
            if cfg.family not in ("dense", "moe"):
                raise NotImplementedError(
                    "speculative decoding needs an exactly rewindable "
                    "paged cache: dense/moe families only")
            if cfg.sliding_window is not None:
                raise NotImplementedError(
                    "speculative decoding needs a linear cache; SWA "
                    "rolling buffers cannot roll back exactly")
            if scfg.temperature > 0.0:
                raise NotImplementedError(
                    "speculative verify is greedy-only (temperature 0): "
                    "the acceptance rule is exact argmax agreement")
            if scfg.spec_k < 2:
                raise ValueError(f"spec_k must be >= 2, got {scfg.spec_k}")
            dl = (scfg.draft_layers if scfg.draft_layers is not None
                  else max(1, cfg.n_layers // 2))
            if not 1 <= dl <= cfg.n_layers:
                raise ValueError(
                    f"draft_layers {dl} out of range [1, {cfg.n_layers}]")
            self.draft_layers = dl
            self._draft_cfg = dataclasses.replace(cfg, n_layers=dl)
        self._root = jax.random.PRNGKey(scfg.seed)

        # weights are the first argument of every program (see Engine)
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(5,))
        self._prefill_sfx = jax.jit(self._prefill_suffix_impl,
                                    donate_argnums=(6,))
        self._prefill_chk = jax.jit(self._prefill_chunk_impl,
                                    donate_argnums=(4,))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(2,))
        self._verify = jax.jit(self._verify_k_impl, donate_argnums=(2,))

    def _replicate(self, *xs):
        """See ``Engine._replicate`` — stable input shardings under the
        mesh for the host-facing token/step vectors."""
        out = tuple(jax.device_put(x, self._rep) for x in xs)
        return out if len(out) > 1 else out[0]

    def _pin(self, *xs):
        """In-jit counterpart of ``_replicate``: annotate already-computed
        outputs replicated so the next call's input shardings match this
        call's (the three-program / no-recompile guarantee holds on any
        mesh).  Applied after sampling — upstream numerics untouched."""
        out = tuple(jax.lax.with_sharding_constraint(x, self._rep)
                    for x in xs)
        return out if len(out) > 1 else out[0]

    # ---- the two compiled programs ----------------------------------------

    def _request_key(self, rid, step):
        """Per-request sampling stream, keyed by REQUEST ID (not slot), so
        slot reuse never replays another request's stream."""
        return jax.random.fold_in(jax.random.fold_in(self._root, rid), step)

    def _prefill_impl(self, params, tokens, plen, slot, rid, carry, extras):
        """Prefill one slot from a right-padded (1, prefill_len) prompt and
        sample that request's first token.  slot/plen/rid are DYNAMIC
        operands — one compiled program serves every admission."""
        logits, carry = registry.prefill_slot(
            params, self.cfg, self.qcfg, tokens, carry, slot, plen,
            extras=extras)
        tok = _sample(logits, self._request_key(rid, 0), self.scfg)[0]
        tok, margin = self._pin(tok, _greedy_margin(logits)[0])
        return tok, margin, shd.constrain_serve_cache(carry, self.mesh)

    def _prefill_suffix_impl(self, params, tokens, plen, pfx, slot, rid,
                             carry):
        """Warm-prefix prefill: the slot's page row already shares the
        cached prefix pages; write + attend only the SUFFIX of the prompt
        (right-padded (1, prefill_len), dynamic pfx/plen/slot/rid
        operands — one compiled program serves every warm admission)."""
        logits, carry = registry.prefill_suffix(
            params, self.cfg, self.qcfg, tokens, carry, slot, plen, pfx)
        tok = _sample(logits, self._request_key(rid, 0), self.scfg)[0]
        tok, margin = self._pin(tok, _greedy_margin(logits)[0])
        return tok, margin, shd.constrain_serve_cache(carry, self.mesh)

    def _prefill_chunk_impl(self, params, tokens, slot, off, carry):
        """Chunked prefill, intermediate chunk: write one FULL
        (1, prefill_chunk) slice of a long prompt into a slot's pages at
        positions [off, off + C) — the fourth jitted program (dynamic
        slot/off operands; no logits, no sampling — the final, possibly
        short chunk reuses the suffix program and samples there)."""
        carry = registry.prefill_chunk(params, self.cfg, self.qcfg,
                                       tokens, carry, slot, off)
        return shd.constrain_serve_cache(carry, self.mesh)

    def _decode_impl(self, params, tokens, carry, rids, steps, active):
        """One token for every slot; per-slot kv_len/q_offset ride inside
        the paged caches (``PagedKVCache.lengths``) as vector state.

        ``active`` ((n_slots,) bool): in chunked-prefill mode, slots that
        are NOT decoding this tick (mid-prefill or empty) write to the
        trash page with frozen lengths, so a decode tick can never
        corrupt a partially-prefilled slot's pages.  Without chunked
        prefill the operand is dropped at trace time (write_mask=None),
        keeping the non-chunked program byte-identical to before."""
        mask = active if self.scfg.prefill_chunk is not None else None
        logits, carry = registry.decode_step(params, self.cfg,
                                             self.qcfg, tokens[:, None],
                                             carry, write_mask=mask)
        lg = logits[:, -1]
        if self.scfg.temperature <= 0.0:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        else:
            keys = jax.vmap(self._request_key)(rids, steps)
            nxt = jax.vmap(
                lambda l, k: _sample(l[None], k, self.scfg)[0])(lg, keys)
        nxt, margin, steps = self._pin(nxt, _greedy_margin(lg), steps + 1)
        return nxt, margin, steps, shd.constrain_serve_cache(carry,
                                                             self.mesh)

    def _verify_k_impl(self, params, tokens, carry, rids, steps, active):
        """Fifth jitted program — speculative verify-k, static (slots, k)
        shapes with the accepted length as a dynamic OUTPUT, so one
        compile serves every tick whatever each slot accepts.

        Three phases, all inside one jit:
          1. DRAFT: the layer-truncated self-draft model (first
             ``draft_layers`` layers of the same packed weights) greedily
             proposes k-1 tokens per slot from a SLICED COPY of the paged
             caches.  The copy is discarded after drafting — functional
             purity means the real carry is never touched, so there is no
             draft state to merge or roll back, and the sliced layers'
             cache rows are exactly the draft model's own history (layer
             l < draft_layers of the target computes the identical
             rows).
          2. VERIFY: one teacher-forced pass of the block [t0, d1..dk-1]
             through the full model's paged quantized cache.  Causal
             masking + per-slot kv_len give query row j exactly the rows
             [0, len + j] sequential decode would see, so row j's greedy
             pick is BIT-identical to non-speculative decode.
          3. ACCEPT + ROLLBACK: the longest prefix of drafts matching the
             verify argmaxes plus one corrected token is emitted
             (n_emit in 1..k); ``truncate_to`` rewinds every layer's
             lengths over the rejected rows (the pool keeps their stale
             codes — reads mask by length, the next append overwrites).
        """
        scfg = self.scfg
        k = scfg.spec_k
        mask = active if scfg.prefill_chunk is not None else None
        dparams, dcarry = registry.draft_view(params, carry,
                                              self.draft_layers)
        blk = [tokens]
        tok = tokens
        for _ in range(k - 1):
            lg, dcarry = registry.decode_step(
                dparams, self._draft_cfg, self.qcfg, tok[:, None], dcarry,
                write_mask=mask)
            tok = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
            blk.append(tok)
        blk = jnp.stack(blk, axis=1)                         # (B, k)
        lg, carry = registry.verify_k(params, self.cfg, self.qcfg,
                                      blk, carry, write_mask=mask)
        g = jnp.argmax(lg, axis=-1).astype(jnp.int32)        # (B, k)
        margin = _greedy_margin(lg)                          # (B, k)
        match = (g[:, :k - 1] == blk[:, 1:]).astype(jnp.int32)
        acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)    # (B,) 0..k-1
        n_emit = acc + 1                                     # (B,) 1..k
        wrote = (jnp.ones_like(n_emit) if mask is None
                 else mask.astype(jnp.int32))
        if mask is not None:
            n_emit = n_emit * wrote              # masked slots emit nothing
        # exact rollback: post-write lengths are base + k*wrote; rewind
        # to base + n_emit (a pure lengths update, pool bytes untouched)
        delta = n_emit - jnp.int32(k) * wrote

        def rb(c):
            if isinstance(c, PagedKVCache):
                return c.truncate_to(None, c.lengths + delta)
            return c

        carry = jax.tree_util.tree_map(
            rb, carry, is_leaf=lambda x: isinstance(x, PagedKVCache))
        # next tick's t0: the LAST emitted token, g[slot, n_emit - 1]
        nxt = jnp.take_along_axis(
            g, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        g, margin, n_emit, nxt, steps = self._pin(
            g, margin, n_emit, nxt, steps + n_emit)
        return g, margin, n_emit, nxt, steps, \
            shd.constrain_serve_cache(carry, self.mesh)

    # ---- jit-cache introspection (no-recompile guarantees) -----------------

    @property
    def prefill_compiles(self) -> int:
        return self._prefill._cache_size()

    @property
    def prefill_suffix_compiles(self) -> int:
        return self._prefill_sfx._cache_size()

    @property
    def chunk_compiles(self) -> int:
        return self._prefill_chk._cache_size()

    @property
    def decode_compiles(self) -> int:
        return self._decode._cache_size()

    @property
    def verify_compiles(self) -> int:
        return self._verify._cache_size()

    # ---- host-side plumbing ------------------------------------------------

    def _set_page_row(self, carry, slot: int, row: np.ndarray):
        """Point one slot's page-table row (all layers) at new pages —
        the only carry mutation done outside the two compiled programs
        (a few hundred int32s per admission)."""
        row = jnp.asarray(row, jnp.int32)

        def upd(c):
            if isinstance(c, PagedKVCache):
                return dataclasses.replace(
                    c, page_table=c.page_table.at[..., slot, :].set(row))
            return c

        return jax.tree_util.tree_map(
            upd, carry, is_leaf=lambda x: isinstance(x, PagedKVCache))

    def _derive_prefill_len(self, requests: List[Request]) -> int:
        if self.scfg.prefill_len is not None:
            pad = self.scfg.prefill_len
        else:
            pad = max((len(r.prompt) for r in requests), default=1)
        pad = min(-(-pad // self.scfg.page_size) * self.scfg.page_size,
                  self.slot_buf)
        long = [r.rid for r in requests if len(r.prompt) > pad]
        if long:
            raise ValueError(
                f"requests {long}: prompt exceeds the static prefill "
                f"length {pad} (slot capacity {self.slot_buf})")
        return pad

    # ---- serving loop ------------------------------------------------------

    def run(self, requests: List[Request],
            extras: Optional[Dict[int, dict]] = None,
            forced: Optional[Dict[int, np.ndarray]] = None
            ) -> Dict[int, np.ndarray]:
        """Serve a request trace to completion; returns {rid: tokens}.

        ``extras``: per-rid extras (encdec frames).  ``forced``: per-rid
        teacher-forcing streams — the engine FEEDS the forced tokens but
        records its own picks (and greedy margins, ``self.margins``); used
        by the token-identity tests to compare across near-tied logits.

        Aborted/timed-out requests never appear in the result dict; their
        partial streams live in ``self.scheduler.cancelled``.  Lifecycle
        timestamps (simulated ticks: TTFT/TPOT/goodput/queue depth) land
        in ``self.metrics`` (serve/metrics.py) — one recorder per run.

        With ``scfg.prefix_cache`` on, the scheduler (page pool + radix
        cache) AND the device carry (quantized prefix pages) PERSIST
        across run() calls, so tenants keep warm prefixes between traces;
        results/cancellations/metrics/margins are per-run.
        """
        scfg = self.scfg
        forced = forced or {}
        extras = extras or {}
        chunked = scfg.prefill_chunk is not None
        if self.spec and forced:
            raise NotImplementedError(
                "teacher-forced streams are incompatible with speculative "
                "decoding (the verify block IS the fed stream)")
        sched = self.scheduler if (scfg.prefix_cache and
                                   getattr(self, "scheduler", None)
                                   is not None) else None
        if sched is None:
            sched = Scheduler(self.n_slots, scfg.max_len, scfg.page_size,
                              total_pages=scfg.total_pages,
                              slot_pages=self.n_pages_slot,
                              prefix_cache=scfg.prefix_cache,
                              prefix_cache_pages=scfg.prefix_cache_pages,
                              prefill_chunk=scfg.prefill_chunk,
                              tracer=self.tracer)
            carry = registry.make_decode_state(
                self.cfg, self.n_slots, scfg.max_len,
                kv_cache_format=scfg.kv_cache_format,
                page_size=scfg.page_size, total_pages=sched.total_pages)
            # KV page pools shard their heads axis over the TP axis;
            # page-table rows / lengths stay replicated (host mutates them
            # identically everywhere).  Identity on the 1-device mesh.
            carry = shd.place_serve_cache(carry, self.mesh)
        else:
            carry = self._last_carry    # warm prefix pages persist
            sched.results = {}
            sched.cancelled = {}
        self.scheduler = sched
        for r in requests:
            sched.submit(r)
        met = MetricsRecorder(tracer=self.tracer)
        self.metrics = met
        for r in requests:
            met.submitted(r.rid, r.arrival, deadline=r.deadline)
        if chunked:
            # the chunk/suffix programs have static width prefill_chunk;
            # prompts stream in over ticks, so only slot capacity caps them
            prefill_pad = scfg.prefill_chunk
            long = [r.rid for r in requests
                    if len(r.prompt) > self.slot_buf]
            if long:
                raise ValueError(f"requests {long}: prompt exceeds the "
                                 f"slot capacity {self.slot_buf}")
        else:
            prefill_pad = self._derive_prefill_len(requests)
        # partial-suffix preemption: re-admission suffixes must fit the
        # static prefill pad (chunked mode streams any suffix length)
        sched.resume_pad = None if chunked else prefill_pad

        tokens, rids, steps = self._replicate(
            jnp.zeros((self.n_slots,), jnp.int32),
            jnp.zeros((self.n_slots,), jnp.int32),
            jnp.ones((self.n_slots,), jnp.int32))
        self.margins: Dict[int, list] = {}
        trash_row = np.full((self.n_pages_slot,), TRASH_PAGE, np.int32)
        slot_rid = [None] * self.n_slots
        slot_fed = {}                       # slot -> host index into forced
        pending = {}                        # slot -> (tok, margin) DEVICE
                                            # scalars from prefill, synced
                                            # with the tick's one transfer

        # jit-compile observation: cache-size polling costs a few python
        # attribute reads per tick, so it runs only with a live tracer —
        # the sizes are read, never asserted on, and emission is host-side
        trc = self.tracer
        if trc.enabled:
            jit_progs = [["prefill", self._prefill, 0],
                         ["prefill_suffix", self._prefill_sfx, 0],
                         ["prefill_chunk", self._prefill_chk, 0],
                         ["decode", self._decode, 0],
                         ["verify", self._verify, 0]]
            for rec in jit_progs:
                rec[2] = rec[1]._cache_size()

        tick = 0
        while sched.has_work():
            trc.set_time(tick)
            trc.begin("engine", "tick")
            # -- lifecycle: hard aborts/timeouts due NOW fire before any
            # admission or prefill/decode work is issued this tick
            for slot, rid, stage, reason in sched.expire(tick):
                met.cancelled(rid, tick, stage, reason)
                if slot is not None:        # was on-device: park its row
                    carry = self._set_page_row(carry, slot, trash_row)
                    self.margins.pop(rid, None)
                    slot_rid[slot] = None
                    pending.pop(slot, None)
                    slot_fed.pop(slot, None)

            # -- admissions (host): pages + slot, then ONE prefill program
            # (warm shared-prefix admissions run the SUFFIX program; a
            # later admission in the same batch may share pages a prior
            # one writes, so prefills run strictly in placed order).
            # Chunked mode defers ALL prompt writes to prefill_work below.
            for slot, req, row, pfx in sched.admit(tick):
                carry = self._set_page_row(carry, slot, row)
                slot_rid[slot] = req.rid
                rids = rids.at[slot].set(req.rid)
                met.admitted(req.rid, tick)
                if chunked:
                    continue
                padded = np.zeros((1, prefill_pad), np.int32)
                sfx = np.asarray(req.prompt[pfx:], np.int32)
                padded[0, :len(sfx)] = sfx
                if sched.prefix_cache is not None:
                    # prefix-cache mode: EVERY admission (cold: pfx == 0)
                    # runs the quantize-then-attend suffix program, so the
                    # suffix hidden states are a pure function of the
                    # quantized pages — warm admission is BIT-IDENTICAL to
                    # a cold start of the same prompt, for every page fmt
                    tok, margin, carry = self._prefill_sfx(
                        self.params, jnp.asarray(padded),
                        jnp.asarray(len(req.prompt)),
                        jnp.asarray(pfx), jnp.asarray(slot),
                        jnp.asarray(req.rid), carry)
                else:
                    tok, margin, carry = self._prefill(
                        self.params, jnp.asarray(padded),
                        jnp.asarray(len(req.prompt)), jnp.asarray(slot),
                        jnp.asarray(req.rid), carry, extras.get(req.rid, {}))
                steps = steps.at[slot].set(1)
                pending[slot] = (tok, margin)
                if req.rid in forced:
                    slot_fed[slot] = 0
                    tokens = tokens.at[slot].set(int(forced[req.rid][0]))
                else:
                    tokens = tokens.at[slot].set(tok)

            # -- chunked prefill: at most ONE chunk per mid-prefill slot
            # per tick, interleaved with this tick's decode.  Chunks
            # attend THROUGH the slot's quantized pages, so the final
            # (short) chunk — which reuses the suffix program, writes the
            # tail rows and samples the first token — produces streams
            # BIT-IDENTICAL to an unchunked admission of the same prompt.
            for slot, req, start, clen, last in sched.prefill_work(tick):
                if not last:
                    chunk = np.asarray(req.prompt[start:start + clen],
                                       np.int32)[None]
                    carry = self._prefill_chk(
                        self.params, jnp.asarray(chunk), jnp.asarray(slot),
                        jnp.asarray(start), carry)
                    continue
                padded = np.zeros((1, prefill_pad), np.int32)
                padded[0, :clen] = req.prompt[start:]
                tok, margin, carry = self._prefill_sfx(
                    self.params, jnp.asarray(padded),
                    jnp.asarray(len(req.prompt)),
                    jnp.asarray(start), jnp.asarray(slot),
                    jnp.asarray(req.rid), carry)
                steps = steps.at[slot].set(1)
                pending[slot] = (tok, margin)
                if req.rid in forced:
                    slot_fed[slot] = 0
                    tokens = tokens.at[slot].set(int(forced[req.rid][0]))
                else:
                    tokens = tokens.at[slot].set(tok)

            # -- decode tick: no host transfer inside the loop.  Slots
            # still mid-prefill neither emit nor commit (their cache
            # writes are masked to the trash page with frozen lengths).
            active = sched.decoding_slots()
            if self.spec:
                # one verify pass per tick; pages must cover the k
                # CANDIDATE rows, but written advances by the ACCEPTED
                # length only (advance_written, after the host sync)
                T = 1 if active else 0
                growth, preempted = sched.ensure_capacity(
                    scfg.spec_k if active else 0, advance=False)
            else:
                T = sched.tick_steps(scfg.decode_chunk,
                                     {s: 1 for s in pending})
                # demand-driven paging: grow rows for this tick's writes;
                # on pool exhaustion the youngest slot is preempted
                # (requeued, its pages released) — drop its host state
                # and trash its row
                growth, preempted = sched.ensure_capacity(T)
            for slot, row in growth:
                carry = self._set_page_row(carry, slot, row)
            for slot in preempted:
                carry = self._set_page_row(carry, slot, trash_row)
                self.margins.pop(slot_rid[slot], None)
                slot_rid[slot] = None
                pending.pop(slot, None)
                slot_fed.pop(slot, None)
            active = [s for s in active if s not in preempted]
            amask = np.zeros((self.n_slots,), bool)
            amask[active] = True
            amask = self._replicate(jnp.asarray(amask))
            ne = np.zeros((self.n_slots,), np.int32)
            if self.spec and active:
                # -- speculative tick: draft + verify + rollback, one call
                g, margin, ne_d, nxt, steps, carry = self._verify(
                    self.params, tokens, carry, rids, steps, amask)
                tokens = nxt
                em_s = np.asarray(g)                  # (n_slots, k)
                mg_s = np.asarray(margin)
                ne = np.asarray(ne_d)
                em = em_s.T                           # commit reads [:, slot]
                mg = mg_s.T
            elif self.spec:
                em = np.zeros((0, self.n_slots), np.int32)
                mg = np.zeros((0, self.n_slots), np.float32)
            else:
                picks, margs = [], []
                for _ in range(T):
                    nxt, margin, steps, carry = self._decode(
                        self.params, tokens, carry, rids, steps, amask)
                    picks.append(nxt)
                    margs.append(margin)
                    tokens = nxt
                    for slot, idx in slot_fed.items():  # teacher forcing
                        stream = forced[slot_rid[slot]]
                        nxt_idx = min(idx + 1, len(stream) - 1)
                        tokens = tokens.at[slot].set(int(stream[nxt_idx]))
                        slot_fed[slot] = nxt_idx

                # ONE host sync per tick: emitted picks + margins + firsts
                em = (np.asarray(jnp.stack(picks, 0)) if picks
                      else np.zeros((0, self.n_slots), np.int32))
                mg = (np.asarray(jnp.stack(margs, 0)) if margs
                      else np.zeros((0, self.n_slots), np.float32))
            first_slots = sorted(pending)
            firsts = {} if not first_slots else dict(zip(first_slots, zip(
                np.asarray(jnp.stack([pending[s][0] for s in first_slots])),
                np.asarray(jnp.stack([pending[s][1] for s in first_slots])))))
            pending.clear()
            emitted_counts = []
            for slot in active:
                rid = slot_rid[slot]
                toks, margins = [], self.margins.setdefault(rid, [])
                if slot in firsts:
                    met.first_token(rid, tick)
                    toks.append(int(firsts[slot][0]))
                    margins.append(float(firsts[slot][1]))
                if self.spec:
                    # variable per-slot advance: the accepted prefix + the
                    # corrected token; written grows by the SAME count so
                    # the high-water mark tracks the rolled-back lengths
                    n = int(ne[slot])
                    sched.advance_written(slot, n)
                    emitted_counts.append(n)
                    toks += [int(t) for t in em[:n, slot]]
                    margins += [float(m) for m in mg[:n, slot]]
                else:
                    toks += [int(t) for t in em[:, slot]]
                    margins += [float(m) for m in mg[:, slot]]
                sched.commit(slot, toks, scfg.eos_id)
                if sched.slots[slot] is None:           # freed: park pages
                    carry = self._set_page_row(carry, slot, trash_row)
                    slot_rid[slot] = None
                    slot_fed.pop(slot, None)
                    met.finished(rid, tick, len(sched.results[rid]))
            if self.spec:
                met.spec_tick(emitted_counts, scfg.spec_k)
            sched.count_tick(T, n_active=len(active))
            met.tick(queue_depth=len(sched.queue), n_active=len(active))
            if trc.enabled:
                for rec in jit_progs:
                    n = rec[1]._cache_size()
                    if n != rec[2]:
                        trc.instant("engine", "jit_compile", program=rec[0],
                                    cache_size=n)
                        trc.counter("jit_compiles", n - rec[2])
                        rec[2] = n
            trc.end("engine", "tick")
            tick += 1

        self.margins = {rid: np.asarray(ms, np.float32)
                        for rid, ms in self.margins.items()}
        met.set_counters(sched.stats)
        self._last_carry = carry    # page-table invariant tests + the
                                    # prefix-cache persistence above
        return dict(sched.results)

    def generate(self, prompts: List[np.ndarray],
                 max_new: int = 32) -> List[np.ndarray]:
        """Lockstep-``Engine``-style convenience: all prompts arrive at
        tick 0; returns outputs in prompt order (stops after EOS)."""
        reqs = [Request(rid=i, prompt=np.asarray(p, np.int32),
                        max_new=max_new) for i, p in enumerate(prompts)]
        res = self.run(reqs)
        return [res[i] for i in range(len(prompts))]


def serve_step_fn(cfg: ModelConfig, qcfg: fqt.QuantConfig):
    """The dry-run's ``serve_step``: one decode token against a full cache.

    Returns f(params, tokens, carry) -> (logits, carry); tokens: (B, 1).
    """

    def serve_step(params, tokens, carry):
        return registry.decode_step(params, cfg, qcfg, tokens, carry)

    return serve_step
