"""KV-cache quantization for the paged serving pools (DESIGN.md §11).

The paged-attention decode path is pool-bandwidth-bound (TMA /
Digital-Neuron: memory traffic is the ceiling once multiplication is
cheap), so pages are stored quantized and dequantized inside the
kernel's page loop — the pool read shrinks 2–4x and no dense f32/bf16
K/V view is ever materialized.

Layout (``cfg.kv_cache_dtype``):

  * ``bf16`` — dense storage in ``cfg.cdtype`` (the pre-quantization
    layout; literally bf16 under production configs).  No scale pools.
  * ``int8`` — symmetric per-token per-kv-head scales:
    ``q = clip(round(x / s), -127, 127)`` with ``s = amax|x| / 127``
    over the head_dim axis.  Pool dtype int8, same shape.
  * ``int4`` — same scale granularity with ``s = amax|x| / 7``; two
    values pack per byte along head_dim (low nibble holds dim ``i``,
    high nibble dim ``i + D/2``; stored offset-by-8 so zero-filled
    pool bytes stay decodable), pool dtype uint8 at ``head_dim // 2``.

Scales live in f32 *side pools* ``scale_k/scale_v (L, n_pages,
n_kv·page_size)`` inside the same per-stage layers dict as the page
pools: one lane-dense row per page, head-major (lane ``h·page_size + t``
holds token t's scale for kv head h; ``rows_to_scales`` turns rows back
into per-token ``(page_size, n_kv)``), so a TPU stores them unpadded in
its default layout, pages on sublanes, and a scatter of whole page rows
(``nn/paged.py``) updates them in place.  The page axis sits at
position 1 in every leaf, so the COW ``copy_page`` tree_map carries
scale rows alongside page contents with no special casing, and the
head-major lane axis (last) shards over the model axis in whole heads
like the pools' merged head axis does.  Per-token rows (not whole-page
amax) because pages fill incrementally: decode appends one token at a
time and each write must quantize independently without requantizing
its page neighbours.

Quantization is deterministic (round-half-even via ``jnp.round``), so
speculative decoding's verify-overwrites-draft invariant survives: the
verifier's scatter over drafted positions reproduces exactly the bytes
non-speculative decode would have written, and greedy spec output stays
token-identical to ``spec_decode=0`` *per kv-dtype*.
"""
from __future__ import annotations

import jax.numpy as jnp

KV_DTYPES = ("bf16", "int8", "int4")
_EPS = 1e-12                      # guards 0/0 on all-zero rows


def kv_mode_of(pool) -> str:
    """Classify a pool leaf (or its dtype) statically at trace time:
    int8 → 'int8', uint8 → packed 'int4', floats → dense 'bf16'."""
    dt = jnp.dtype(pool.dtype if hasattr(pool, "dtype") else pool)
    if dt == jnp.int8:
        return "int8"
    if dt == jnp.uint8:
        return "int4"
    return "bf16"


def kv_pool_layout(cfg):
    """(pool_dtype, packed_head_dim, quantized?) for ``cfg``'s paged
    pools."""
    mode = getattr(cfg, "kv_cache_dtype", "bf16")
    hd = cfg.head_dim_r
    if mode == "int8":
        return jnp.int8, hd, True
    if mode == "int4":
        if hd % 2:
            raise ValueError(
                f"kv_cache_dtype='int4' packs head_dim pairs per byte; "
                f"head_dim {hd} must be even")
        return jnp.uint8, hd // 2, True
    if mode != "bf16":
        raise ValueError(f"unknown kv_cache_dtype {mode!r}; expected one "
                         f"of {KV_DTYPES}")
    return cfg.cdtype, hd, False


def scale_pool_shape(n_layers: int, n_pages: int, page_size: int,
                     n_kv: int) -> tuple:
    """Shape of a stage's scale side pool: one head-major row of
    ``n_kv·page_size`` f32 scales per page."""
    return (n_layers, n_pages, n_kv * page_size)


def rows_to_scales(rows: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """Head-major page rows ``(..., n_kv·page_size)`` → per-token scales
    ``(..., page_size, n_kv)``."""
    x = rows.reshape(*rows.shape[:-1], -1, page_size)
    return jnp.swapaxes(x, -1, -2)


def scales_to_rows(s: jnp.ndarray) -> jnp.ndarray:
    """Per-token scales ``(..., page_size, n_kv)`` → head-major page rows
    ``(..., n_kv·page_size)``; the inverse of ``rows_to_scales``."""
    return jnp.swapaxes(s, -1, -2).reshape(*s.shape[:-2], -1)


def stored_layout(pages: jnp.ndarray, scale=None):
    """Per-head pages ``(..., page_size, n_kv, Dp)`` (and their per-token
    scales ``(..., page_size, n_kv)``) → the pools' stored layout:
    lane-dense ``(..., page_size, n_kv·Dp)`` (and head-major scale
    rows)."""
    flat = pages.reshape(*pages.shape[:-2], -1)
    return flat if scale is None else (flat, scales_to_rows(scale))


def heads_layout(pages: jnp.ndarray, scale=None, *, n_kv: int):
    """The inverse of ``stored_layout``: lane-dense pages ``(...,
    page_size, n_kv·Dp)`` (and head-major scale rows) → per head ``(...,
    page_size, n_kv, Dp)`` (and per-token scales ``(..., page_size,
    n_kv)``)."""
    heads = pages.reshape(*pages.shape[:-1], n_kv, -1)
    return heads if scale is None else \
        (heads, rows_to_scales(scale, pages.shape[-2]))


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int levels in [-7, 7] (last axis = head_dim, even) into
    uint8 nibbles: byte ``i`` holds dim ``i`` (low) and dim ``i + D/2``
    (high), each stored as ``level + 8`` ∈ [1, 15]."""
    D = q.shape[-1]
    u = (q + 8).astype(jnp.uint8)
    lo, hi = u[..., : D // 2], u[..., D // 2:]
    return lo | (hi << 4)


def unpack_int4(b: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``pack_int4`` → f32 levels in [-7, 7] (zero bytes —
    never written — decode to -8, masked/zero-scaled upstream)."""
    w = b.astype(jnp.int32)          # Mosaic has no uint8 → f32 cast
    lo = (w & 0xF).astype(jnp.float32) - 8.0
    hi = (w >> 4).astype(jnp.float32) - 8.0
    return jnp.concatenate([lo, hi], axis=-1)


def quantize_kv(val: jnp.ndarray, mode: str):
    """Quantize fresh K/V rows ``val (..., H, D)`` → ``(q, scale)``:
    ``q`` in the pool's storage dtype/width, ``scale (..., H)`` f32."""
    f = val.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=-1)
    if mode == "int8":
        s = amax / 127.0
        q = jnp.clip(jnp.round(f / (s[..., None] + _EPS)), -127, 127)
        return q.astype(jnp.int8), s
    if mode == "int4":
        s = amax / 7.0
        q = jnp.clip(jnp.round(f / (s[..., None] + _EPS)), -7, 7)
        return pack_int4(q.astype(jnp.int8)), s
    raise ValueError(f"quantize_kv: dense mode {mode!r} has no scales")


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  mode: str) -> jnp.ndarray:
    """Dequantize pool rows ``q (..., H, Dp)`` with ``scale (..., H)``
    → f32 ``(..., H, D)``.  This is the exact op both kernel lowerings
    inline inside their page loop."""
    if mode == "int8":
        f = q.astype(jnp.float32)
    elif mode == "int4":
        f = unpack_int4(q)
    else:
        raise ValueError(f"dequantize_kv: dense mode {mode!r}")
    return f * scale.astype(jnp.float32)[..., None]
