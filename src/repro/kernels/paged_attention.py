"""Pallas fused paged-attention decode kernel (flash-decoding over the page
table) — DESIGN.md §8.

The serving hot path after the encoded-MAC fold is decode attention: the
reference path gathers the full page view ``pool[pages]`` into a dense
``(B, max_seq_pages·page_size, H, D)`` tensor and computes logits over the
whole table width every step, regardless of the actual ``lens`` — exactly
the memory-traffic ceiling TMA/Digital-Neuron identify once multiplication
is cheap.  This kernel instead walks each sequence's page chain directly:

  * grid ``(B, max_seq_pages)`` with the page axis innermost; the softmax
    statistics ``(m, l, acc)`` live in VMEM scratch and are revisited
    across page blocks (same pattern as the flash and encoded kernels);
  * the page table, ``lens`` and the layer index are scalar-prefetched,
    so the K/V block index maps resolve ``(layer, pages[b, p])`` *before*
    the body runs — K/V pages stream HBM→VMEM one page at a time straight
    out of the stage's stacked pool, and neither the dense gathered view
    nor a per-layer slice of the pool is ever materialized;
  * pools are lane-dense, ``(L, n_pages, page_size, n_kv·Dp)``: a page
    block is a ``(page_size, n_kv·Dp)`` tile in the TPU's default layout,
    and the body takes each head's ``Dp`` lanes as a static slice in VMEM;
  * per-row early exit: blocks past ``lens[b] // page_size`` clamp their
    index map to the last needed page (no new DMA is issued for a
    repeated block) and skip compute via ``pl.when`` — a slot at 40
    cached tokens touches 3 pages of a 1024-token-wide table, not 64;
  * grouped GQA layout and f32 accumulation mirror the dense ``mha`` op
    order (q scaled in storage dtype, logits/softcap/mask/softmax in f32)
    so greedy decode stays token-identical to the gather path;
  * 1..k query tokens per slot: the ``Sq`` query tokens fold into the GQA
    group axis (rows ``s·G + g``) with a per-row causal mask at positions
    ``lens[b] + s`` — the speculative-decoding verify step (DESIGN.md §10)
    scores all k+1 positions in one pass at decode-kernel cost.

Backends (``paged_attn(..., backend=...)``):

  * ``pallas``           — the Pallas kernel compiled by Mosaic; TPU only
                           (raises on any other platform);
  * ``pallas_interpret`` — the same kernel in interpret mode, a
                           correctness path, not a fast one (tests);
  * ``blocked``          — the kernel's XLA reference lowering: the same
                           page-block online-softmax recurrence as a
                           ``fori_loop`` bounded by ``max(lens)``, so
                           non-TPU backends keep the algorithmic win
                           (work scales with cached tokens, not table
                           width) without Mosaic;
  * ``auto``             — ``pallas`` on TPU, ``blocked`` elsewhere.

Under an active mesh (parallel.sharding.set_mesh) the op runs shard-local
over the model axis via shard_map — q sharded on q-heads, pools on their
merged head axis in whole kv heads (mirroring parallel.statesharding's
pool rule and ``ops.encoded_matmul``'s role dispatch); attention is
independent per kv head, so no collectives are needed and the output
leaves head-sharded.  Where the kv-head count does not divide the model
axis the pools are replicated and every shard attends all heads on them.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import AXIS_MODEL, get_mesh
from repro.parallel.statesharding import pool_head_axis
from repro.quant.kvcache import kv_mode_of, rows_to_scales, unpack_int4
from .ops import interpret_mode

NEG_INF = -2.0e38                    # finite f32 sentinel (matches mha)
# f32 contractions at full f32 precision in every lowering, so the kernel,
# its blocked lowering and the gather reference agree on the chip too (a
# TPU's default f32 matmul rounds operands to bf16)
ATTN_PRECISION = jax.lax.Precision.HIGHEST
_NO_WINDOW = np.int32(2 ** 30)       # "no sliding window" resolves to huge
# page rows per scale-pool block: a TPU block spans a multiple of 8
# sublanes (or the whole axis), so a page's scale row arrives with 7 others
SCALE_ROWS = 8


def _dequant_block(x, scale, mode):
    """Per-page-block dequant shared by both lowerings (DESIGN.md §11):
    pool bytes ``x (..., Dp)`` times f32 scales ``(..., 1)`` (one per
    token and head, broadcast over the head dim) → f32 ``(..., D)``.
    ``mode == 'bf16'`` is the dense passthrough."""
    if mode == "int8":
        return x.astype(jnp.float32) * scale
    if mode == "int4":
        return unpack_int4(x) * scale
    return x.astype(jnp.float32)


def gqa_group(kv_of_q, n_q: int, n_kv: int) -> Optional[int]:
    """Group size G when ``kv_of_q`` is the identity (MHA) or the uniform
    grouped map (GQA/MQA) — the layouts the fused kernel handles; ``None``
    for irregular maps (callers fall back to the gather path)."""
    kv_np = np.asarray(kv_of_q)
    if n_kv == n_q and np.array_equal(kv_np, np.arange(n_q)):
        return 1
    group = n_q // n_kv if n_kv and n_q % n_kv == 0 else 0
    if group > 1 and np.array_equal(
            kv_np, np.minimum(np.arange(n_q) // group, n_kv - 1)):
        return group
    return None


def _softcap(s, cap):
    return s if cap is None else cap * jnp.tanh(s / cap)


# ---------------------------------------------------------------------------
# BlockSpec index maps (module level so analysis/kernelcheck.py can evaluate
# exactly the functions the kernel traces — not a re-derivation of them).
# Scalar-prefetch signature: (b, p, pages_s, lens_s, win_s, layer_s); Sq/ps
# are bound by functools.partial at call-site.
# ---------------------------------------------------------------------------

def paged_kv_block_map(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps):
    """Stacked K/V pool block index for grid cell (b, p): layer
    ``layer_s[0]``, the page id holding page block p of row b.  Past-lens
    blocks clamp to the last needed page — positions <= lens[b] + Sq - 1
    — so the index map repeats and no new DMA is issued for blocks the
    kernel body skips via ``pl.when``."""
    p_eff = jnp.minimum(p, (lens_s[b] + Sq - 1) // ps)
    return (layer_s[0], pages_s[b, p_eff], 0, 0)


def paged_scale_block_map(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps,
                          rows):
    """Same page clamp for the (L, n_pages, Hkv·ps) f32 scale side pools of
    quantized KV modes (DESIGN.md §11): the block of ``rows`` page rows
    (``SCALE_ROWS``) holding the page's scales, which stream with its
    value page."""
    p_eff = jnp.minimum(p, (lens_s[b] + Sq - 1) // ps)
    return (layer_s[0], pages_s[b, p_eff] // rows, 0)


def paged_q_block_map(b, p, *_):
    """Folded q / output block index: row b, whole (Hkv, Sq·G, D) block."""
    return (b, 0, 0, 0)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _decode_kernel(pages_s, lens_s, win_s, layer_s, q_ref, k_ref, v_ref,
                   *rest, ps, n_pb, cap, G, Sq, Dp, rows, mode="bf16"):
    if mode == "bf16":
        sk_ref = sv_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    else:                            # quantized pools: scale-row refs ride
        sk_ref, sv_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = lens_s[b]                   # tokens already cached for this row
    nb = (ln + Sq - 1) // ps + 1     # page blocks holding positions <= ln+Sq-1

    @pl.when(p < nb)
    def _block():
        f32 = jnp.float32
        hkv, R = q_ref.shape[1], q_ref.shape[2]      # R = Sq·G query rows
        # row r = s·G + g is query s of group g; query s sits at ln + s
        t = p * ps + jax.lax.broadcasted_iota(jnp.int32, (R, ps), 1)
        rq = jax.lax.broadcasted_iota(jnp.int32, (R, ps), 0)
        if G > 1:
            rq = rq // G
        d = (ln + rq) - t                            # q_pos - k_pos
        ok = (d >= 0) & (d < win_s[0])
        if sk_ref is not None:
            # the page's head-major scale row out of its block of rows
            # (an aligned load, a select and a sublane sum: exact)
            r = pages_s[b, p] % rows
            hit = jax.lax.broadcasted_iota(jnp.int32, sk_ref.shape, 0) == r
            sk_row = jnp.where(hit, sk_ref[...], 0.0).sum(0, keepdims=True)
            sv_row = jnp.where(hit, sv_ref[...], 0.0).sum(0, keepdims=True)
            eye = (jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 1))

        def scale_col(row, h):
            # head h's ps lanes of a (1, Hkv·ps) scale row as a per-token
            # (ps, 1) column: a select and a lane sum, exact
            seg = row[:, h * ps:(h + 1) * ps]
            return jnp.where(eye, seg, 0.0).sum(-1, keepdims=True)

        # one kv head at a time: its (ps, Dp) lanes of the lane-dense
        # page block, 2-D contractions only — no in-kernel reshape/transpose
        for h in range(hkv):
            lanes = slice(h * Dp, (h + 1) * Dp)
            # in-loop dequant (DESIGN.md §11): quantized pools stream
            # their narrow bytes HBM→VMEM and widen to f32 here
            k = _dequant_block(
                k_ref[0, :, lanes],
                None if sk_ref is None else scale_col(sk_row, h), mode)
            v = _dequant_block(
                v_ref[0, :, lanes],
                None if sv_ref is None else scale_col(sv_row, h), mode)
            q = q_ref[0, h].astype(f32)                       # (R, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=ATTN_PRECISION,
                                    preferred_element_type=f32)  # (R, ps)
            s = jnp.where(ok, _softcap(s, cap), NEG_INF)
            m_prev = m_ref[h]                                 # (R, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + pexp.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                pexp, v, precision=ATTN_PRECISION, preferred_element_type=f32)
            m_ref[h] = m_new

    @pl.when(p == n_pb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def fold_q(q, scale: float, G: int):
    """(B, Sq, Hq, D) → (B, Hkv, Sq·G, D), scaled in the storage dtype
    (dense-op-order numerics): row r = s·G + g of kv head h is query s of
    q head h·G + g.  Done outside the kernel — Mosaic does not lower the
    reshapes this needs."""
    B, S, Hq, D = q.shape
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hq // G, G, D)
    return qg.transpose(0, 2, 1, 3, 4).reshape(B, Hq // G, S * G, D)


def unfold_q(out, S: int, G: int):
    """Inverse of ``fold_q``'s layout: (B, Hkv, Sq·G, D) → (B, Sq, Hq, D)."""
    B, hkv, _, D = out.shape
    out = out.reshape(B, hkv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, hkv * G, D)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "G",
                                             "interpret"))
def paged_attn_pallas(q, pool_k, pool_v, pages, lens, window, layer, *,
                      scale: float, cap=None, G: int = 1,
                      interpret: bool = False,
                      scale_k=None, scale_v=None):
    """q (B, Sq, Hq, D); pool_k/v (L, n_pages, ps, Hkv·Dp), a stage's
    lane-dense stacked pools; pages (B, P) int32; lens (B,) int32; window
    () int32 (``_NO_WINDOW`` ⇒ global); layer () int32, the pool layer to
    read.  Query s of row b sits at absolute position ``lens[b] + s``; its
    K/V must already be scattered into the pools.  Quantized pools (int8,
    or uint8 = packed int4 with Dp = D/2) pass their ``scale_k/scale_v
    (L, n_pages, Hkv·ps)`` f32 head-major page rows; page blocks of values
    and scales stream together and widen in-loop (DESIGN.md §11)."""
    B, S, Hq, D = q.shape
    Hkv = Hq // G
    ps, HDp = pool_k.shape[2], pool_k.shape[3]
    Dp = HDp // Hkv
    mode = kv_mode_of(pool_k)        # static: dtype is a trace constant
    n_pb = pages.shape[1]
    R = S * G
    win = jnp.asarray(window, jnp.int32).reshape(1)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    rows = min(SCALE_ROWS, pool_k.shape[1])  # page rows per scale block
    page_idx = functools.partial(paged_kv_block_map, Sq=S, ps=ps)
    scale_idx = functools.partial(paged_scale_block_map, Sq=S, ps=ps,
                                  rows=rows)

    kern = functools.partial(_decode_kernel, ps=ps, n_pb=n_pb, cap=cap, G=G,
                             Sq=S, Dp=Dp, rows=rows, mode=mode)
    in_specs = [
        pl.BlockSpec((1, Hkv, R, D), paged_q_block_map),
        pl.BlockSpec((None, 1, ps, HDp), page_idx),
        pl.BlockSpec((None, 1, ps, HDp), page_idx),
    ]
    operands = [fold_q(q, scale, G), pool_k, pool_v]
    if mode != "bf16":
        in_specs += [pl.BlockSpec((None, rows, Hkv * ps), scale_idx),
                     pl.BlockSpec((None, rows, Hkv * ps), scale_idx)]
        operands += [scale_k, scale_v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, n_pb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, D), paged_q_block_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, 1), jnp.float32),
            pltpu.VMEM((Hkv, R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q.dtype),
        interpret=interpret,
        name="paged_attn_pallas",        # the device op's name in a trace
    )(pages, lens, win, lyr, *operands)
    return unfold_q(out, S, G)


# ---------------------------------------------------------------------------
# XLA reference lowering (same recurrence, fori_loop over page blocks)
# ---------------------------------------------------------------------------

def pool_pages(pool, layer, pid):
    """Layer ``layer``'s rows of the stacked pool at page ids ``pid``
    (any int shape): one gather on (layer, page), never a slice of the
    layer's whole pool first."""
    return pool[layer, pid]


def _paged_attn_blocked(q, pool_k, pool_v, pages, lens, window, layer, *,
                        scale: float, cap=None, G: int = 1, bk: int = 128,
                        scale_k=None, scale_v=None):
    """The kernel's algorithm in plain XLA: a ``fori_loop`` over K blocks
    of ``max(1, bk // page_size)`` pages (~``bk`` tokens, the flash
    kernel's K-block width — single-page steps drown in loop overhead on
    CPU), bounded by ``max(lens)`` — the batch-wide early exit (the
    Pallas path additionally skips per row).  Rows whose blocks are fully
    masked contribute exp(NEG_INF − m) == 0, so short rows match the
    per-row skip exactly."""
    B, S, Hq, D = q.shape
    Hkv = Hq // G
    ps = pool_k.shape[2]
    Dp = pool_k.shape[3] // Hkv
    mode = kv_mode_of(pool_k)
    f32 = jnp.float32
    # fold the Sq query tokens into the group axis (row r = s·G + g), same
    # layout as the Pallas kernel
    qg = fold_q(q, scale, G).astype(f32)
    win = jnp.asarray(window, jnp.int32)
    bp = max(1, bk // ps)                            # pages per K block
    blk = bp * ps                                    # tokens per K block
    P = pages.shape[1]
    if P % bp:                                       # pad table → scratch
        pages = jnp.pad(pages, ((0, 0), (0, bp - P % bp)))
    nb = (jnp.max(lens) + S - 1) // blk + 1
    t0 = jnp.arange(blk)
    rq = jnp.arange(S * G, dtype=jnp.int32) // G     # query index per row

    def block(pool, sc, pid):
        # gather narrow pool bytes, then widen per block — the same
        # in-loop dequant as the Pallas kernel (DESIGN.md §11)
        x = pool_pages(pool, layer, pid).reshape(B, bp, ps, Hkv, Dp)
        s = None if sc is None else \
            rows_to_scales(pool_pages(sc, layer, pid), ps)[..., None]
        return _dequant_block(x, s, mode).reshape(B, blk, Hkv, D)

    def body(j, carry):
        m, l, acc = carry
        pid = jax.lax.dynamic_slice_in_dim(pages, j * bp, bp, 1)  # (B, bp)
        kb = block(pool_k, scale_k, pid)
        vb = block(pool_v, scale_v, pid)
        s = jnp.einsum("bhgd,bphd->bhgp", qg, kb, precision=ATTN_PRECISION,
                       preferred_element_type=f32)
        s = _softcap(s, cap)
        # q_pos(=lens+s) - k_pos, per (query-row, key) pair: (B, S·G, blk)
        d = (lens[:, None, None] + rq[None, :, None]
             - (j * blk + t0)[None, None, :])
        ok = (d >= 0) & (d < win)
        s = jnp.where(ok[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l = l * alpha + pexp.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgp,bphd->bhgd", pexp, vb, precision=ATTN_PRECISION,
            preferred_element_type=f32)
        return m_new, l, acc

    init = (jnp.full((B, Hkv, S * G), NEG_INF, f32),
            jnp.zeros((B, Hkv, S * G), f32),
            jnp.zeros((B, Hkv, S * G, D), f32))
    m, l, acc = jax.lax.fori_loop(0, nb, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return unfold_q(out, S, G).astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry: backend + shard-local dispatch
# ---------------------------------------------------------------------------

def _local(q, pool_k, pool_v, pages, lens, win, layer, *, scale, cap, G,
           backend, scale_k=None, scale_v=None):
    if backend == "blocked":
        return _paged_attn_blocked(q, pool_k, pool_v, pages, lens, win,
                                   layer, scale=scale, cap=cap, G=G,
                                   scale_k=scale_k, scale_v=scale_v)
    interpret = interpret_mode(backend)
    return paged_attn_pallas(q, pool_k, pool_v, pages, lens, win, layer,
                             scale=scale, cap=cap, G=G, interpret=interpret,
                             scale_k=scale_k, scale_v=scale_v)


def paged_attn(q, pool_k, pool_v, pages, lens, *, layer, scale: float,
               window=None, cap=None, kv_of_q=None,
               backend: str = "auto",
               scale_k=None, scale_v=None) -> jnp.ndarray:
    """Fused paged-attention step over 1..k query tokens per slot.

    q (B, Sq, Hq, D) · pool_k/v (L, n_pages, ps, Hkv·Dp) · pages (B, P) ·
    lens (B,) · layer (int or traced scalar) → (B, Sq, Hq, D) in q.dtype.
    The pools are a stage's lane-dense stacked pools; only layer
    ``layer``'s pages are read.  Query s of row b sits at absolute
    position ``lens[b] + s`` (causal within the block), and its K/V must
    already be scattered into the pools — the decode step uses Sq == 1,
    the speculative-decoding verify step Sq == k+1 (DESIGN.md §10).
    Callers must keep ``lens[b] + Sq <= P·page_size``.  ``kv_of_q`` must
    be the identity or uniform grouped map (see ``gqa_group``); callers
    with irregular maps use the gather path.  ``window`` is None, an int,
    or a traced scalar (negative never reaches here — blocks resolve −1
    to a huge window).  Sq is static: each distinct value compiles its
    own kernel (the engine uses exactly two).

    Quantized pools (``cfg.kv_cache_dtype`` int8/int4 — detected from the
    pool dtype) require ``scale_k``/``scale_v`` ``(L, n_pages, Hkv·ps)``
    f32 per-token per-head scales, one head-major row per page
    (``quant.kvcache.scale_pool_shape``); both lowerings dequantize per
    page block
    inside the loop (DESIGN.md §11), keeping the f32 softmax/accumulation
    op order unchanged.

    With an active mesh whose kv-head count divides the model axis, the
    chosen backend runs shard-local per kv-head shard (q/output
    head-sharded, pools and scale rows sharded on their head-major last
    axis in whole heads, page table, lens and layer replicated) —
    attention never mixes kv heads, so the fused path composes with
    ``--mesh`` serving without collectives.  Where the count does not
    divide the axis, the pools are replicated (``cache_specs``) and each
    shard attends every head on them, replicated q in, replicated
    output out.
    """
    B, S, Hq, D = q.shape
    # kv heads of the lane-dense pool (packed int4 stores D/2 bytes a head)
    Hkv = pool_k.shape[-1] // (D // 2 if kv_mode_of(pool_k) == "int4" else D)
    G = Hq // Hkv if kv_of_q is None else gqa_group(kv_of_q, Hq, Hkv)
    if G is None:
        raise ValueError("paged_attn needs an identity or uniform grouped "
                         "kv_of_q map; fall back to the gather path")
    if backend not in ("auto", "pallas", "pallas_interpret", "blocked"):
        raise ValueError(f"unknown paged-attention backend {backend!r}; "
                         "expected auto | pallas | pallas_interpret | "
                         "blocked (or attention_backend 'xla' for the "
                         "gather path)")
    if (kv_mode_of(pool_k) != "bf16") != (scale_k is not None):
        raise ValueError("quantized pools need scale_k/scale_v rows "
                         "(and dense pools must not pass them)")
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "blocked"
    win = _NO_WINDOW if window is None else window
    win = jnp.asarray(win, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    kw = dict(scale=scale, cap=cap, G=G, backend=backend)

    mesh = get_mesh()
    if mesh is not None and AXIS_MODEL in mesh.axis_names \
            and mesh.shape[AXIS_MODEL] > 1:
        # shard-local in whole kv heads where they divide the model axis;
        # else every shard attends all heads over the replicated pools
        # (a Mosaic kernel cannot be partitioned automatically)
        ax = pool_head_axis(Hkv, mesh)
        specs = [P(None, None, ax, None), P(None, None, None, ax),
                 P(None, None, None, ax), P(None, None), P(None), P(), P()]
        args = [q, pool_k, pool_v, pages, lens, win, layer]
        if scale_k is not None:
            specs += [P(None, None, ax), P(None, None, ax)]
            args += [scale_k, scale_v]

        def shard(ql, kl, vl, pg, ln, w, lyr, *sc):
            sk, sv = sc if sc else (None, None)
            return _local(ql, kl, vl, pg, ln, w, lyr, scale_k=sk,
                          scale_v=sv, **kw)

        return jax.shard_map(shard, mesh=mesh, in_specs=tuple(specs),
                             out_specs=P(None, None, ax, None),
                             check_vma=False)(*args)
    return _local(q, pool_k, pool_v, pages, lens, win, layer,
                  scale_k=scale_k, scale_v=scale_v, **kw)
