"""Paged KV-cache primitives (vLLM-style block paging) — pure array ops.

Layout: each attention stage shares one stacked, lane-dense pool of
fixed-size pages per K and V,

    pool_k / pool_v : (L, n_pages, page_size, n_kv_heads·head_dim)

addressed by layer index (the layer scan carries the stage's pools and
each layer reads and writes its own rows in place) and, per sequence,
through a page table

    pages : (B, max_pages) int32 — pool page ids.  Page 0 is reserved as
        the *scratch* page (the allocator never hands it out), so
        unassigned table entries and padded-token writes land in scratch
        and are masked on read.
    lens  : (B,) int32 — tokens already cached (positions < lens valid).

Everything here is shape-static and jit/scan-safe; allocation policy
(refcounted pages, prefix index, admission, eviction) lives host-side in
``repro.serve.paged_cache`` / ``repro.serve.scheduler``.  The attention
op takes per-row absolute positions, so decode steps and prefill chunks
starting at arbitrary offsets (chunked prefill, partial-prefix prefill
after a prefix-cache hit — DESIGN.md §7) share one code path.

``paged_attn_decode`` over the gathered view is the *reference* path
(``cfg.attention_backend == 'xla'``); decode steps — and the
speculative-decoding verify pass, whose Sq == k+1 query rows all start
at ``lens`` (DESIGN.md §10) — can instead route through the fused
page-walk kernel in ``repro.kernels.paged_attention`` (DESIGN.md §8),
which this op also validates (the k-query parity sweep scores both
against each other).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import (ATTN_PRECISION, gqa_group,
                                           pool_pages)
from repro.quant.kvcache import (dequantize_kv, kv_mode_of, quantize_kv,
                                 rows_to_scales, scales_to_rows)
from .common import softcap
from .attention_mha import NEG_INF


def _page_slots(pages: jnp.ndarray, positions: jnp.ndarray, ps: int):
    """(page id, in-page offset) per (B, S) position.  Positions past
    the table width and positions in unassigned entries both resolve to
    the scratch page (0) — never a real page, whose offsets may hold
    live tokens."""
    P = pages.shape[1]
    pi = positions // ps                                  # (B, S) table idx
    pid = jnp.take_along_axis(pages, jnp.minimum(pi, P - 1), axis=1)
    pid = jnp.where(pi < P, pid, 0)                       # oob → scratch
    return pid, positions % ps


def scatter_kv(pool: jnp.ndarray, layer, pages: jnp.ndarray,
               positions: jnp.ndarray, val: jnp.ndarray) -> jnp.ndarray:
    """Write ``val`` (B, S, H, D) into layer ``layer`` of the stacked
    lane-dense pool (L, n_pages, ps, H·D) at absolute ``positions``
    (B, S) through the page table (scratch-page routing per
    ``_page_slots``): one scatter of B·S rows, in place when the pool is
    donated or carried."""
    pid, off = _page_slots(pages, positions, pool.shape[2])
    rows = val.reshape(*val.shape[:2], -1).astype(pool.dtype)
    return pool.at[layer, pid, off].set(rows)


def _scatter_scale_rows(scale: jnp.ndarray, layer, pages: jnp.ndarray,
                        positions: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Write per-token scales ``s`` (B, S, H) at ``positions`` (B, S) —
    contiguous per row, ``lens[:, None] + arange(S)`` as the paged branch
    passes them — into layer ``layer`` of the head-major (L, n_pages,
    H·ps) side pool.  Each page the chunk touches gets its whole row
    rewritten, its other tokens' scales read back: a scatter whose window
    is a whole row, which a TPU applies in place and a mesh splits by head
    with no collective.  Pages past the table resolve to scratch."""
    B, S, H = s.shape
    ps = scale.shape[-1] // H
    P = pages.shape[1]
    n = (S + ps - 2) // ps + 1                     # pages a chunk can touch
    start = positions[:, :1]                                  # (B, 1)
    slot = start // ps + jnp.arange(n)                        # (B, n)
    pid = jnp.take_along_axis(pages, jnp.minimum(slot, P - 1), axis=1)
    pid = jnp.where(slot < P, pid, 0)
    tok = (slot[..., None] * ps + jnp.arange(ps)
           - start[..., None]).reshape(B, n * ps)             # chunk index
    fresh = jnp.take_along_axis(s, jnp.clip(tok, 0, S - 1)[..., None], 1)
    old = rows_to_scales(pool_pages(scale, layer, pid), ps)
    new = jnp.where(((tok >= 0) & (tok < S))[..., None], fresh,
                    old.reshape(B, n * ps, H))
    return scale.at[layer, pid].set(scales_to_rows(new.reshape(B, n, ps, H)))


def scatter_kv_quant(pool: jnp.ndarray, scale: jnp.ndarray, layer,
                     pages: jnp.ndarray, positions: jnp.ndarray,
                     val: jnp.ndarray):
    """Quantize-on-scatter (DESIGN.md §11): quantize fresh rows ``val``
    (B, S, H, D) to the pool's storage mode and write value bytes + f32
    per-token per-head scales (head-major page rows: lane ``h·ps + off``
    of the (L, n_pages, H·ps) side pool) into layer ``layer`` through the
    page table in one pass.  Returns ``(pool, scale)`` updated."""
    mode = kv_mode_of(pool)
    q, s = quantize_kv(val, mode)
    pid, off = _page_slots(pages, positions, pool.shape[2])
    rows = q.reshape(*q.shape[:2], -1)
    return (pool.at[layer, pid, off].set(rows),
            _scatter_scale_rows(scale, layer, pages, positions, s))


def gather_kv(pool: jnp.ndarray, layer, pages: jnp.ndarray,
              n_kv: int) -> jnp.ndarray:
    """Layer ``layer`` of the (L, n_pages, ps, H·D) pool + (B, P) table →
    (B, P·ps, H, D) view, one gather on (layer, page)."""
    B, P = pages.shape
    g = pool_pages(pool, layer, pages)                  # (B, P, ps, H·D)
    return g.reshape(B, P * g.shape[2], n_kv, -1)


def gather_kv_dequant(pool: jnp.ndarray, scale: jnp.ndarray, layer,
                      pages: jnp.ndarray) -> jnp.ndarray:
    """Quantized-pool gather for the reference path: layer ``layer`` of
    the (L, n_pages, ps, H·Dp) pool + (L, n_pages, H·ps) scale rows +
    (B, P) table → dequantized f32 (B, P·ps, H, D) view.  The fused
    kernels dequantize per page block instead and never build this
    view."""
    mode = kv_mode_of(pool)
    B, P = pages.shape
    s = rows_to_scales(pool_pages(scale, layer, pages), pool.shape[2])
    q = pool_pages(pool, layer, pages).reshape(*s.shape, -1)  # (B,P,ps,H,·)
    out = dequantize_kv(q, s, mode)
    return out.reshape(B, P * s.shape[2], *out.shape[3:])


def paged_attn_decode(q, k, v, kv_of_q: np.ndarray, *, scale: float,
                      q_pos, k_pos, k_valid, window=None, cap=None):
    """Attention over a gathered page view with per-row positions.

    q (B, Sq, Hq, D); k/v (B, Sk, Hkv, D); q_pos (B, Sq); k_pos (Sk,);
    k_valid (B, Sk).  ``Sq == 1`` is the decode step; ``Sq > 1`` is a
    prefill chunk whose rows start at arbitrary per-slot offsets
    (partial-prefix prefill after a prefix-cache hit, chunked prefill of
    a long prompt) — the causal mask is evaluated in absolute positions,
    so queries see every already-cached token plus the in-chunk prefix.
    Mirrors the dense ``mha`` op order — grouped (kv-head, group) layout,
    f32 accumulation, identical einsum strings — so paged greedy decode
    stays token-identical to the dense-cache path.  Contractions run at
    full f32 precision, as the fused kernel's do, so the two paths agree
    on a TPU as well.  Fully-masked rows
    (idle slots, lens == 0) stay finite because NEG_INF is a finite f32
    sentinel.
    """
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    f32 = jnp.float32
    kv_np = np.asarray(kv_of_q)
    group = gqa_group(kv_np, Hq, Hkv)    # one classifier for both paths
    if group is not None:
        G, He = group, Hq // group
    else:                                # irregular map: gather to q heads
        k = jnp.take(k, jnp.asarray(kv_np), axis=2)
        v = jnp.take(v, jnp.asarray(kv_np), axis=2)
        G, He = 1, Hq

    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, He, G, D)
    lg = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(f32), k.astype(f32),
                    precision=ATTN_PRECISION, preferred_element_type=f32)
    lg = softcap(lg, cap)
    d = q_pos[:, :, None] - k_pos[None, None, :]          # (B, Sq, Sk)
    ok = (d >= 0) & k_valid[:, None, :]
    if window is not None:
        ok = ok & (d < window)
    lg = jnp.where(ok[:, None, None], lg, NEG_INF)
    p = jax.nn.softmax(lg, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(f32),
                     precision=ATTN_PRECISION, preferred_element_type=f32)
    return out.reshape(B, Sq, Hq, -1).astype(q.dtype)
