"""Attention: GQA/MQA/MHA with RoPE, sliding-window, logit softcap, KV cache.

Two compute paths:
  - dense: full (Sq × Skv) logits — decode steps and short sequences.
  - kv-chunked: online-softmax scan over KV chunks (flash-style) — long
    prefill/train.  Keeps the live score block at (Sq_chunk? no — full Sq ×
    chunk) which is bounded by ``attn_chunk``; compatible with head-sharded
    TP (scan axis is unsharded).

Head padding for TP: q/kv head counts may be padded to the mesh's model-axis
size; grouping uses an explicit ``kv_of_q`` index map so original GQA
grouping is preserved and padded heads (zeroed wo rows) never contaminate
real outputs.

Decode KV caches are sequence-sharded over the model axis (DESIGN.md §5:
split-KV / FlashDecoding-style) — softmax reductions over the sharded axis
lower to psums under SPMD, so no shard_map is needed.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain, AXIS_BATCH, AXIS_MODEL
from .common import linear, linear_init, apply_rope, softcap, norm_init, \
    norm_apply
from .attention_mha import mha, NEG_INF, _mask  # grouped-layout core op
from .paged import (scatter_kv, scatter_kv_quant, gather_kv,
                    gather_kv_dequant, paged_attn_decode)
from repro.kernels.paged_attention import paged_attn, gqa_group


def kv_of_q_map(n_heads: int, n_kv: int, n_heads_p: int, n_kv_p: int
                ) -> np.ndarray:
    """Static q-head → kv-head index map preserving original grouping.

    MHA (group 1) with equal padding keeps the identity map — padded q heads
    attend their own padded kv head (outputs zeroed by wo rows anyway), which
    keeps the map shard-preserving (no gather → no all-gather of K/V)."""
    group = max(1, n_heads // max(n_kv, 1))
    if group == 1 and n_heads_p == n_kv_p:
        return np.arange(n_heads_p, dtype=np.int32)
    idx = np.minimum(np.arange(n_heads_p) // group, n_kv_p - 1)
    idx[n_heads:] = n_kv_p - 1          # padded q heads → last (padded) kv
    return idx.astype(np.int32)


def attn_init(key, cfg, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.head_dim_r
    ks = jax.random.split(key, 4)
    p = {}
    p.update(linear_init(ks[0], d, cfg.n_heads_p * hd, "wq", cfg.mac,
                         cfg.qkv_bias, cfg.pdtype))
    p.update(linear_init(ks[1], d, cfg.n_kv_p * hd, "wk", cfg.mac,
                         cfg.qkv_bias, cfg.pdtype))
    p.update(linear_init(ks[2], d, cfg.n_kv_p * hd, "wv", cfg.mac,
                         cfg.qkv_bias, cfg.pdtype))
    wo = linear_init(ks[3], cfg.n_heads_p * hd, d, "wo", cfg.mac,
                     cfg.attn_out_bias, cfg.pdtype)
    if cfg.n_heads_p != cfg.n_heads:    # zero padded-head output rows
        mask = np.zeros((cfg.n_heads_p, 1, 1), np.float32)
        mask[:cfg.n_heads] = 1.0        # static mask — vmap/eval_shape safe
        wo["wo"] = (wo["wo"].reshape(cfg.n_heads_p, hd, d) * mask
                    ).reshape(cfg.n_heads_p * hd, d).astype(cfg.pdtype)
    p.update(wo)
    if cfg.qk_norm:
        p.update(norm_init(hd, "rms", cfg.pdtype, "qnorm"))
        p.update(norm_init(hd, "rms", cfg.pdtype, "knorm"))
    return p


def attn_apply(p: dict, x: jnp.ndarray, cfg, *, layer_window=None,
               cache=None, positions=None) -> tuple:
    """Self-attention over x (B, S, d).

    cache: None (train/prefill-no-cache) or dict {k, v, pos} for decode /
    prefill-fill.  Returns (out, new_cache_or_None).
    ``layer_window``: per-layer override (traced scalar or None) for
    local/global alternating patterns — None means cfg.sliding_window.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim_r
    cdt = cfg.cdtype
    q = linear(p, "wq", x, cfg.mac, cdt).reshape(B, S, cfg.n_heads_p, hd)
    k = linear(p, "wk", x, cfg.mac, cdt).reshape(B, S, cfg.n_kv_p, hd)
    v = linear(p, "wv", x, cfg.mac, cdt).reshape(B, S, cfg.n_kv_p, hd)
    if cfg.qk_norm:
        q = norm_apply(p, q, "rms", cfg.norm_eps, "qnorm")
        k = norm_apply(p, k, "rms", cfg.norm_eps, "knorm")

    if positions is None:
        pos0 = 0 if cache is None else cache["pos"]
        positions = pos0 + jnp.arange(S)
    if cfg.rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    window = layer_window if layer_window is not None else cfg.sliding_window
    scale = cfg.attn_scale or (1.0 / np.sqrt(hd))
    kv_map = kv_of_q_map(cfg.n_heads, cfg.n_kv_heads, cfg.n_heads_p,
                         cfg.n_kv_p)

    pad = cache.get("pad") if isinstance(cache, dict) else None

    def parallel_attn(q, k, v):
        if cfg.flash_attention and positions.ndim == 1 and (
                window is None or isinstance(window, int)):
            from repro.kernels.ops import flash_mha
            return flash_mha(q, k, v, scale=scale, causal=True,
                             window=window if isinstance(window, int)
                             else None, cap=cfg.attn_softcap,
                             backend=cfg.flash_attention)
        k_valid = None if pad is None else positions >= 0   # left-pad keys
        return mha(q, k, v, kv_map, scale=scale, q_pos=positions,
                   k_pos=positions, window=window, cap=cfg.attn_softcap,
                   chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
                   k_valid=k_valid)

    new_cache = None
    if cache is None:
        out = parallel_attn(q, k, v)
    elif "pool_k" in cache:
        # paged serving path (repro.serve): write-through into the shared
        # page pool, then attend through the page table.  The cache holds
        # the stage's stacked lane-dense pools (L, n_pages, ps, n_kv·D)
        # and ``layer``, this layer's index into them (models/lm.py
        # carries the pools through the layer scan).  ``positions``
        # is (B, S) here (per-slot ragged lens from the scheduler), so
        # decode (S == 1) and prefill chunks starting at arbitrary offsets
        # (chunked prefill, partial-prefix prefill after a prefix-cache
        # hit) share one code path: every query row sees all tokens cached
        # for its slot plus its in-chunk causal prefix.  Decode steps with
        # a regular GQA layout route through the fused flash-decoding
        # kernel when ``cfg.attention_backend != 'xla'`` (DESIGN.md §8) —
        # work scales with each row's cached tokens instead of the table
        # width; everything else keeps the gathered-view reference path.
        # Quantized pools (cfg.kv_cache_dtype int8/int4, DESIGN.md §11)
        # carry scale_k/scale_v side pools in the cache dict: fresh K/V
        # quantizes on scatter (deterministically — the spec-decode
        # verify overwrite reproduces non-spec bytes exactly), the fused
        # kernels dequantize per page block in-loop, and the gather
        # reference dequantizes its page view.
        pages, lens = cache["pages"], cache["lens"]
        layer = cache["layer"]           # index into the stacked pools
        quant = "scale_k" in cache
        if quant:
            pk, sk = scatter_kv_quant(cache["pool_k"], cache["scale_k"],
                                      layer, pages, positions, k)
            pv, sv = scatter_kv_quant(cache["pool_v"], cache["scale_v"],
                                      layer, pages, positions, v)
        else:
            sk = sv = None
            pk = scatter_kv(cache["pool_k"], layer, pages, positions, k)
            pv = scatter_kv(cache["pool_v"], layer, pages, positions, v)
        # ``paged_fused_max_sq`` (default 1) widens the fused gate for the
        # speculative-decoding verify step: the kernel scores Sq query
        # rows at positions lens..lens+Sq-1, which is exactly this
        # branch's contract (positions = lens[:, None] + arange(S))
        fused = (S <= max(1, cfg.paged_fused_max_sq)
                 and cfg.attention_backend != "xla"
                 and gqa_group(kv_map, cfg.n_heads_p, cfg.n_kv_p)
                 is not None)
        if fused:
            out = paged_attn(q, pk, pv, pages, lens, layer=layer,
                             scale=scale, window=window,
                             cap=cfg.attn_softcap, kv_of_q=kv_map,
                             backend=cfg.attention_backend,
                             scale_k=sk, scale_v=sv)
        else:
            if quant:
                ck = gather_kv_dequant(pk, sk, layer, pages)
                cv = gather_kv_dequant(pv, sv, layer, pages)
            else:
                ck = gather_kv(pk, layer, pages, cfg.n_kv_p)
                cv = gather_kv(pv, layer, pages, cfg.n_kv_p)
            k_pos = jnp.arange(ck.shape[1])
            k_valid = k_pos[None, :] < (lens + S)[:, None]
            out = paged_attn_decode(q, ck, cv, kv_map, scale=scale,
                                    q_pos=positions, k_pos=k_pos,
                                    k_valid=k_valid, window=window,
                                    cap=cfg.attn_softcap)
        new_cache = {"pool_k": pk, "pool_v": pv}
        if quant:
            new_cache.update(scale_k=sk, scale_v=sv)
    else:
        ck, cv, pos = cache["k"], cache["v"], cache["pos"]
        # write new k/v at [pos : pos+S) (decode S=1; prefill S=prompt)
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                          (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                          (0, pos, 0, 0))
        ck = constrain(ck, AXIS_BATCH, AXIS_MODEL, None, None)
        cv = constrain(cv, AXIS_BATCH, AXIS_MODEL, None, None)
        if S > 1:
            # prefill (from position 0): chunked parallel attention over the
            # freshly projected k/v — never materializes S×S scores
            out = parallel_attn(q, k, v)
        else:
            # decode: dense row against the sequence-sharded cache
            Smax = ck.shape[1]
            k_pos = jnp.arange(Smax)
            k_valid = k_pos < (pos + S)
            if pad is not None:
                # left-padded batch: cache slot s holds the token at
                # logical position s - pad (garbage for s < pad) — shift
                # key positions per row and mask the pad slots
                k_valid = k_valid[None, :] & (k_pos[None, :] >= pad[:, None])
                k_pos = k_pos[None, :] - pad[:, None]
            out = mha(q, ck, cv, kv_map, scale=scale, q_pos=positions,
                      k_pos=k_pos, window=window, cap=cfg.attn_softcap,
                      chunk=0, k_valid=k_valid)
        new_cache = {"k": ck, "v": cv, "pos": pos + S}

    out = out.reshape(B, S, cfg.n_heads_p * hd)
    return linear(p, "wo", out, cfg.mac, cdt), new_cache


def init_kv_cache(cfg, batch: int, max_len: int, n_layers: int,
                  dtype=None) -> dict:
    dt = dtype or cfg.cdtype
    hd = cfg.head_dim_r
    return {
        "k": jnp.zeros((n_layers, batch, max_len, cfg.n_kv_p, hd), dt),
        "v": jnp.zeros((n_layers, batch, max_len, cfg.n_kv_p, hd), dt),
        "pos": jnp.zeros((), jnp.int32),
    }
