"""Decoder-only LM transformer (dense / MoE / VLM-backbone families).

Layers are *stacked* and applied with lax.scan so HLO size (and dry-run
compile time) is O(1) in depth — essential for llama3-405b's 126 layers on a
512-device mesh.  Optional per-layer remat (jax.checkpoint) bounds activation
memory for the train shapes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fqt import QuantConfig
from repro.distributed.sharding import constrain
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig
from repro.models.layers import (QCtx, attn_apply, attn_params, dense_init,
                                 embed_init, make_kv_cache, mlp_apply,
                                 mlp_params, rmsnorm)
from repro.obs import scopes

_SEED_STRIDE = jnp.uint32(0x9E3779B9)


def init(cfg: ModelConfig, key, dtype=jnp.bfloat16):
    kE, kL, kH = jax.random.split(key, 3)

    def layer_init(k):
        ka, km, kn = jax.random.split(k, 3)
        p = {
            "attn": attn_params(ka, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.hd, cfg.qkv_bias, dtype,
                                qk_norm=cfg.use_qk_norm),
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
        }
        if cfg.n_experts:
            p["moe"] = moe_mod.moe_params(km, cfg, dtype)
        else:
            p["mlp"] = mlp_params(km, cfg.d_model, cfg.d_ff, cfg.act, dtype)
        return p

    layers = jax.vmap(layer_init)(jax.random.split(kL, cfg.n_layers))
    params = {
        "embed": embed_init(kE, cfg.padded_vocab, cfg.d_model, dtype),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kH, cfg.d_model, cfg.padded_vocab,
                                       dtype)
    return params


def _layer_apply(cfg: ModelConfig, lp, x, seed, *, positions, cache,
                 qcfg: QuantConfig, slot=None, plen=None, pfx=None,
                 write_mask=None):
    ctx = QCtx(qcfg, seed)
    x = constrain(x, "res")
    h, new_cache = attn_apply(
        lp["attn"], rmsnorm(x, lp["ln1"], cfg.norm_eps), ctx,
        n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, hd=cfg.hd,
        rope_theta=cfg.rope_theta, window=cfg.sliding_window,
        chunk=cfg.attn_chunk, positions=positions, cache=cache,
        slot=slot, plen=plen, pfx=pfx, write_mask=write_mask,
        norm_eps=cfg.norm_eps)
    x = x + h
    hin = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        B, S, d = hin.shape
        y2, aux = moe_mod.moe_apply(lp["moe"], hin.reshape(B * S, d), ctx, cfg)
        y = y2.reshape(B, S, d)
    else:
        y = mlp_apply(lp["mlp"], hin, ctx, cfg.act)
        aux = jnp.zeros((), jnp.float32)
    return x + y, new_cache, aux


def apply_layers(params, cfg: ModelConfig, qcfg: QuantConfig, x, seed, *,
                 positions=None, caches=None, remat: bool = False,
                 slot=None, plen=None, pfx=None, write_mask=None):
    """Scan the stacked layers.  Returns (x, new_caches, aux_loss_sum)."""
    L = cfg.n_layers
    seeds = jnp.asarray(seed, jnp.uint32) + jnp.arange(
        L, dtype=jnp.uint32) * _SEED_STRIDE

    def body(x, per_layer):
        lp, s, c = per_layer
        y, nc, aux = _layer_apply(cfg, lp, x, s, positions=positions,
                                  cache=c, qcfg=qcfg, slot=slot, plen=plen,
                                  pfx=pfx, write_mask=write_mask)
        return y, (nc, aux)

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    xs = (params["layers"], seeds, caches)
    with jax.named_scope(scopes.LAYER_SCAN):
        x, (new_caches, auxes) = jax.lax.scan(body, x, xs)
    return x, new_caches, jnp.sum(auxes)


def _logits(params, cfg: ModelConfig, qcfg: QuantConfig, x, seed):
    head_cfg = qcfg if cfg.quantize_lm_head else QuantConfig()
    ctx = QCtx(head_cfg, jnp.asarray(seed, jnp.uint32) + jnp.uint32(0xABCDEF))
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = constrain(ctx.dense(x, w), "logits")
    if cfg.padded_vocab != cfg.vocab_size:   # mask padded ids
        neg = jnp.full((cfg.padded_vocab - cfg.vocab_size,), -1e30,
                       logits.dtype)
        logits = logits.at[..., cfg.vocab_size:].set(neg)
    return logits


def forward(params, cfg: ModelConfig, qcfg: QuantConfig, tokens, *,
            seed=0, prefix_embeds: Optional[jax.Array] = None,
            remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward (train/prefill).  Returns (logits, aux_loss).

    ``prefix_embeds``: (B, P, d) pre-computed modality embeddings (VLM stub)
    prepended to the token embeddings.
    """
    x = constrain(params["embed"][tokens], "res")
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, aux = apply_layers(params, cfg, qcfg, x, seed,
                             positions=positions, caches=None, remat=remat)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    return _logits(params, cfg, qcfg, x, seed), aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, kv_format: str = "bf16",
               page_size=None, total_pages=None):
    buf = max_len if cfg.sliding_window is None else min(
        max_len, cfg.sliding_window)
    if page_size:                      # paged: round up to whole pages
        buf = -(-buf // page_size) * page_size

    def one(_):
        return make_kv_cache(batch, buf, cfg.n_kv_heads, cfg.hd, dtype,
                             kv_format, page_size=page_size,
                             total_pages=total_pages)

    return jax.vmap(one)(jnp.arange(cfg.n_layers))


def prefill_slot(params, cfg: ModelConfig, qcfg: QuantConfig, tokens,
                 caches, slot, plen, *, seed=0):
    """Prefill ONE paged slot from a right-padded (1, Sp) prompt.

    ``plen`` (dynamic) is the true prompt length; rows in [plen, Sp) are
    pad whose cache writes are masked by the slot length at read time.
    Returns (logits_at_last_prompt_token (1, V), caches)."""
    x = params["embed"][tokens]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=positions, caches=caches,
                                    remat=False, slot=slot, plen=plen)
    x = jax.lax.dynamic_slice_in_dim(
        x, jnp.asarray(plen, jnp.int32) - 1, 1, axis=1)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _logits(params, cfg, qcfg, x, seed)[:, 0], new_caches


def prefill_suffix(params, cfg: ModelConfig, qcfg: QuantConfig, tokens,
                   caches, slot, plen, pfx, *, seed=0):
    """Prefill ONE paged slot from a right-padded (1, Sp) prompt SUFFIX
    whose prefix of ``pfx`` tokens is already cached in the slot's shared
    pages (warm shared-prefix admission).

    ``plen`` is the TOTAL prompt length (prefix + true suffix); both it
    and ``pfx`` are dynamic scalars, so one compiled program serves every
    warm admission.  Suffix K/V rows are written at logical positions
    [pfx, plen); the queries attend through the paged cache (dequantized
    shared prefix + fresh suffix).  Returns
    (logits_at_last_prompt_token (1, V), caches)."""
    x = params["embed"][tokens]
    positions = (jnp.asarray(pfx, jnp.int32)
                 + jnp.arange(x.shape[1], dtype=jnp.int32))
    x, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=positions, caches=caches,
                                    remat=False, slot=slot, plen=plen,
                                    pfx=pfx)
    x = jax.lax.dynamic_slice_in_dim(
        x, jnp.asarray(plen, jnp.int32) - jnp.asarray(pfx, jnp.int32) - 1,
        1, axis=1)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _logits(params, cfg, qcfg, x, seed)[:, 0], new_caches


def prefill_chunk(params, cfg: ModelConfig, qcfg: QuantConfig, tokens,
                  caches, slot, off, *, seed=0):
    """Write ONE full intermediate chunk of a prompt into a paged slot.

    ``tokens`` is a (1, C) chunk of the prompt covering logical positions
    [off, off + C) — always exactly full (the FINAL, possibly short chunk
    goes through ``prefill_suffix``, which also samples the first token).
    ``off`` is a dynamic scalar, so one compiled program serves every
    chunk of every admission.  Reuses the quantize-then-attend suffix
    machinery (write the chunk's quantized K/V rows, then attend through
    the paged cache over [0, off + C)), so each token's hidden state is a
    pure function of the quantized rows before it — the chunk partition
    cannot change any value, and chunked prefill is BIT-identical to an
    unchunked suffix prefill.  No lm_head / no sampling: intermediate
    chunks emit nothing.  Returns the updated caches only."""
    x = params["embed"][tokens]
    C = x.shape[1]
    off = jnp.asarray(off, jnp.int32)
    positions = off + jnp.arange(C, dtype=jnp.int32)
    _, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=positions, caches=caches,
                                    remat=False, slot=slot, plen=off + C,
                                    pfx=off)
    return new_caches


def prefill(params, cfg, qcfg, tokens, caches, *, seed=0,
            prefix_embeds=None):
    """Run the prompt through the model, filling caches; returns
    (last_token_logits, caches)."""
    x = params["embed"][tokens]
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    x, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=None, caches=caches,
                                    remat=False)
    x = rmsnorm(x[:, -1:], params["ln_f"], cfg.norm_eps)
    return _logits(params, cfg, qcfg, x, seed), new_caches


def decode_step(params, cfg: ModelConfig, qcfg: QuantConfig, tokens, caches,
                *, seed=0, write_mask=None):
    """One new token per sequence.  tokens: (B, 1).  Returns (logits, caches).

    ``write_mask`` ((B,) bool, paged caches only): slots mid-chunked-
    prefill write to the trash page and keep their length — see
    ``PagedKVCache.write_token``."""
    x = params["embed"][tokens]
    x, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=None, caches=caches,
                                    remat=False, write_mask=write_mask)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _logits(params, cfg, qcfg, x, seed), new_caches


def verify_k(params, cfg: ModelConfig, qcfg: QuantConfig, tokens, caches,
             *, seed=0, write_mask=None):
    """Teacher-forced verify pass over a speculative block: ``tokens`` is
    (B, k) — each slot's last committed token followed by k-1 drafted
    tokens — written into the paged caches at [len, len + k) and attended
    with per-slot causal positions.  Query row j sees exactly the rows
    [0, len + j] a sequential decode of token j would see, and RtN row
    quantization is neighbor-independent, so row j's logits are
    BIT-identical to non-speculative decode — the acceptance check can
    use strict argmax equality.  Rejected rows are rolled back by the
    caller via ``PagedKVCache.truncate_to``.

    ``write_mask`` ((B,) bool): masked-off slots write to the trash page
    and keep their length.  Returns (logits (B, k, V), caches)."""
    x = params["embed"][tokens]
    x, new_caches, _ = apply_layers(params, cfg, qcfg, x, seed,
                                    positions=None, caches=caches,
                                    remat=False, write_mask=write_mask)
    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return _logits(params, cfg, qcfg, x, seed), new_caches


def draft_view(params, caches, draft_layers: int):
    """Self-draft view: the SAME stacked weights (and caches) truncated to
    the first ``draft_layers`` layers.  A pure trace-level slice of the
    leading layer axis — no copy of the packed store persists, so the
    draft model costs zero extra HBM for weights.  Embedding, final norm
    and lm_head are shared as-is.  Pair with
    ``dataclasses.replace(cfg, n_layers=draft_layers)`` so scan sees the
    truncated depth.  Returns (draft_params, draft_caches)."""
    dp = dict(params)
    dp["layers"] = jax.tree_util.tree_map(lambda a: a[:draft_layers],
                                          params["layers"])
    dc = (None if caches is None else
          jax.tree_util.tree_map(lambda a: a[:draft_layers], caches))
    return dp, dc


def loss_fn(params, cfg: ModelConfig, qcfg: QuantConfig, batch, *, seed=0,
            remat: bool = True):
    """Next-token cross-entropy (+ MoE aux).  batch: {tokens, (prefix_embeds)}."""
    tokens = batch["tokens"]
    logits, aux = forward(params, cfg, qcfg, tokens[:, :-1], seed=seed,
                          prefix_embeds=batch.get("prefix_embeds"),
                          remat=remat)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    return loss + cfg.router_aux_weight * aux, {"nll": loss, "aux": aux}
