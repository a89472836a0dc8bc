"""Pallas fused paged-attention decode kernel (flash-decoding over the page
table) — DESIGN.md §8.

The serving hot path after the encoded-MAC fold is decode attention: the
reference path gathers the full page view ``pool[pages]`` into a dense
``(B, max_seq_pages·page_size, H, D)`` tensor and computes logits over the
whole table width every step, regardless of the actual ``lens`` — exactly
the memory-traffic ceiling TMA/Digital-Neuron identify once multiplication
is cheap.  This kernel instead walks each sequence's page chain directly:

  * grid ``(B,)``, one row a grid step; inside it a ``fori_loop`` walks
    the row's needed pages only, from the window's first page
    (``first_block``; slot 0 without a window) to the page of its last
    query, in compute blocks of ``ppb`` pages (``pages_per_block``: 128
    keys, so each head's score tile is one full lane row, cut only where
    the buffers would not fit the scoped VMEM).  No grid step and no
    copy is spent on a table slot outside that range: a row at 40
    cached tokens reads 3 pages of a 1024-token-wide table, a window
    layer its window's pages;
  * the pools stay in HBM; the page table, ``lens``, the window and the
    layer index are scalar-prefetched, and the kernel copies each live
    page ``pool[layer, pages[b, slot]]`` (and, for quantized pools, the
    aligned block of scale rows holding its own) into a double buffer in
    VMEM: block j+1's copies, or the next row's first block's, start
    before block j computes, so the copies hide under the compute.  The
    dense gathered view is never materialized, nor a per-layer slice of
    a pool whose rows are whole 128-lane rows (``whole_lane_rows``);
  * pools are lane-dense, ``(L, n_pages, page_size, n_kv·Dp)``: a page is
    a ``(page_size, n_kv·Dp)`` tile in the TPU's default layout.  Where
    each kv head has one query row (``R = Sq·G == 1``: MHA decode) over
    f32 or bf16 pools, the body takes every kv head of a block in one
    lane-dense pass (``head_batched``, chosen from shapes and dtype at
    trace time): q is one ``(1, n_kv·Dp)`` row; its f32 products with
    the whole ``(ppb·page_size, n_kv·Dp)`` K block are summed per head
    (the block turned on the XLU, each head's ``Dp`` sublanes added on
    the VPU); the online softmax runs per head in ``(n_kv,
    ppb·page_size)``; the weights, repeated over their heads' lanes,
    multiply V and are summed over the keys into a lane-dense f32 row.
    A one-row dot would load each head's tile into the MXU, over six
    bf16 passes at ``HIGHEST``, to push one row through it.  Otherwise
    (GQA groups, the verify step's Sq > 1, quantized pools) the body
    takes each head's ``Dp`` lanes as a static slice, one ``(R,
    D)·(D, ppb·page_size)`` score dot and one ``(R,
    ppb·page_size)·(ppb·page_size, D)`` value dot a kv head a block,
    where R rows share each tile's passes;
  * zero by select: a block's slots past the row's last page are never
    copied and hold whatever the buffer held, a page's unwritten tail
    whatever the pool held (NaN included), so their scores are replaced
    by a select, and their values by a select to 0 — never multiplied
    by a masked weight, since 0·NaN is NaN;
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
# The walk: which pool pages each compute block reads.  Module level so
# analysis/kernelcheck.py evaluates exactly the functions the kernel calls,
# not a re-derivation of them.  The scalars are the kernel's scalar
# prefetch, pages_s (B, P), lens_s (B,), win_s (1,) and layer_s (2,), the
# value and the scale pools' layer; arrays index the same way.
# ---------------------------------------------------------------------------

# VMEM the double-buffered K and V blocks may take: half the 16 MiB of
# scoped VMEM a v5e gives a kernel by default, the rest left to the q and
# output blocks, the softmax scratch and the compiler's temporaries
KV_VMEM_BYTES = 8 << 20
LANES = 128                          # a TPU vreg row, and its copy unit


def pages_per_block(ps: int, page_bytes: int) -> int:
    """Pages a compute block holds: ``LANES`` keys, so each head's score
    tile is one full lane row, cut only where two buffers of K and V
    blocks would pass ``KV_VMEM_BYTES``.  ``page_bytes``: one page of
    one pool in VMEM, its block of scale rows included."""
    return max(1, min(LANES // ps, KV_VMEM_BYTES // (4 * page_bytes)))


def page_vmem_bytes(ps: int, lanes: int, dtype, scale_lanes: int,
                    rows: int) -> int:
    """One page of one pool in VMEM: its ``(ps, lanes)`` values and, for
    a quantized pool, the block of ``rows`` scale rows of
    ``scale_lanes`` copied with it (0 for a dense pool)."""
    return ps * lanes * jnp.dtype(dtype).itemsize + rows * scale_lanes * 4


def whole_lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane rows."""
    return -(-width // LANES) * LANES


def whole_lane_rows(pool, layer):
    """``(pool, layer)`` as the kernel copies from them.  A TPU copies a
    slice of an array only in whole 128-lane rows, so a pool whose rows
    are narrower (few kv heads, or scale rows of ``n_kv·page_size``
    lanes) comes as layer ``layer`` alone, its rows padded to whole
    lanes (a copy of that layer), and is read at layer 0; a pool of
    whole rows, as every stacked pool of 128·k lanes, is read in
    place."""
    width = pool.shape[-1]
    if width % LANES == 0:
        return pool, layer
    one = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=True)
    pad = [(0, 0)] * (pool.ndim - 1) + [(0, whole_lanes(width) - width)]
    return jnp.pad(one, pad), jnp.int32(0)


def first_block(lens_b, win, ps: int):
    """The table slot of the oldest key that query ``lens_b`` (a row's
    earliest query) may see under a window of ``win`` keys
    (``q_pos - k_pos < win``): pages before it hold no key any query of
    the row attends.  0 with ``_NO_WINDOW``."""
    return jnp.maximum(0, (lens_b - win + 1) // ps)


def walk_bounds(lens_b, win, *, Sq: int, ps: int, windowed: bool):
    """(first, last): the table slots of the first and the last page a
    row with ``lens_b`` cached keys needs.  ``windowed`` is static: a
    model with no sliding window compiles the walk from slot 0, without
    the window's arithmetic."""
    last = (lens_b + Sq - 1) // ps
    first = first_block(lens_b, win, ps) if windowed else 0
    return first, last


def n_blocks(lens_b, win, *, Sq: int, ps: int, ppb: int, windowed: bool):
    """Compute blocks of ``ppb`` pages that cover the row's needed slots
    ``first..last``: at least one."""
    first, last = walk_bounds(lens_b, win, Sq=Sq, ps=ps, windowed=windowed)
    return (last - first) // ppb + 1


def block_pages(b, j, lens_s, win_s, *, Sq: int, ps: int, ppb: int,
                windowed: bool = False):
    """Compute block j of row b: ``(slot0, n_live)``, its first table
    slot ``first + j·ppb`` (the walk starts at the window's first page
    itself, not at a block boundary of the table) and how many of its
    ``ppb`` slots are live, at or before the row's last needed page.
    The kernel copies the live slots ``slot0 .. slot0 + n_live − 1``
    only; the rest of the block's buffer holds whatever it held."""
    first, last = walk_bounds(lens_s[b], win_s[0], Sq=Sq, ps=ps,
                              windowed=windowed)
    slot0 = first + j * ppb
    return slot0, jnp.minimum(ppb, last - slot0 + 1)


def pool_page(b, slot, pages_s, layer_s):
    """The ``(layer, page)`` value-pool index the copy of row b's table
    slot ``slot`` reads (``layer_s``: the value pools' layer, then the
    scale pools')."""
    return layer_s[0], pages_s[b, slot]


def scale_rows_start(page, rows: int):
    """First row of the ``rows`` scale-pool rows copied with ``page``:
    the aligned block that holds the page's row.  A TPU copies whole
    8-row tiles at tile-aligned offsets only, and lays the page axis out
    in whole tiles, so the block of the last pages may read past
    ``n_pages`` into the tile padding."""
    return pl.multiple_of(page // rows * rows, rows)


def paged_q_block_map(b, *_):
    """Folded q / output block index: row b, whole (Hkv, Sq·G, D) block
    (or, head-batched, its whole lane-dense ``(1, 1, lanes)`` row)."""
    return (b, 0, 0, 0)


def head_batched(R: int, pool_dtype) -> bool:
    """The kernel's body, chosen statically: every kv head of a block in
    one lane-dense pass where each head has one query row (``R = Sq·G ==
    1``) over dense pools; the per-head MXU loop for ``R > 1`` and for
    quantized pools."""
    return R == 1 and kv_mode_of(pool_dtype) == "bf16"


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _decode_kernel(pages_s, lens_s, win_s, layer_s, q_ref, k_hbm, v_hbm,
                   *rest, ps, ppb, cap, G, Sq, Dp, rows, mode="bf16",
                   windowed=False, Hkv=0):
    quant = mode != "bf16"
    if quant:                        # quantized pools: scale rows ride along
        (sk_hbm, sv_hbm, o_ref, kbuf, vbuf, skbuf, svbuf, sems, slot_ref,
         m_ref, l_ref, acc_ref) = rest
        pools = ((k_hbm, kbuf), (v_hbm, vbuf), (sk_hbm, skbuf),
                 (sv_hbm, svbuf))
    else:
        o_ref, kbuf, vbuf, sems, slot_ref, m_ref, l_ref, acc_ref = rest
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    b = pl.program_id(0)
    walk = dict(Sq=Sq, ps=ps, ppb=ppb, windowed=windowed)

    def fetch(row, j, slot, op):
        """Start (``op='start'``) or wait for the copies of block j of
        ``row`` into buffer ``slot``: per live page its K and V page and,
        quantized, the block of scale rows that holds its own."""
        slot0, n_live = block_pages(row, j, lens_s, win_s, **walk)

        def page(i, carry):
            lyr, pid = pool_page(row, slot0 + i, pages_s, layer_s)
            at = [(lyr, pid)] * 2
            if quant:                # the scale pools' own layer index
                at += [(layer_s[1], pl.ds(scale_rows_start(pid, rows),
                                          rows))] * 2
            for (hbm, buf), a in zip(pools, at):
                cp = pltpu.make_async_copy(hbm.at[a], buf.at[slot, i],
                                           sems.at[slot])
                if op == "start":
                    cp.start()
                else:
                    cp.wait()
            return carry
        jax.lax.fori_loop(0, n_live, page, 0)

    # the grid runs the rows in order on one core: row 0 fetches its own
    # first block, every later row finds it fetched by the row before
    @pl.when(b == 0)
    def _first_fetch():
        slot_ref[0] = 0
        fetch(0, 0, 0, "start")

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ln = lens_s[b]                   # tokens already cached for this row
    nblk = n_blocks(ln, win_s[0], Sq=Sq, ps=ps, ppb=ppb, windowed=windowed)
    slot_first = slot_ref[0]
    f32 = jnp.float32
    hkv, R, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]  # R = Sq·G
    W = ppb * ps                     # keys a block
    batched = Hkv > 0                # head-batched: one query row a head
    if batched:
        # q and the accumulator are lane-dense rows: head h on lanes
        # h·Dp .. h·Dp + Dp − 1 of the page rows' ``width`` lanes
        width = kbuf.shape[-1]
        q_row = q_ref[0, 0].astype(f32)                  # (1, width)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Hkv, width), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (Hkv, width), 0) * Dp
        on_head = (lane >= head) & (lane < head + Dp)

        def head_sums(x):
            # (W, width) → (Hkv, W), each head's Dp lanes added: the
            # block turned on the XLU, each head's Dp sublanes added in
            # f32 on the VPU
            t = x.T
            return jnp.concatenate([t[h * Dp:(h + 1) * Dp].sum(
                0, keepdims=True) for h in range(Hkv)], 0)

        def head_lanes(x):
            # (Hkv, W) → (W, width), each head's weights on its Dp lanes
            # (0 on padding lanes): repeated over sublanes, turned back
            full = jnp.broadcast_to(x[:, None, :], (Hkv, Dp, W))
            full = full.reshape(Hkv * Dp, W)
            if width > Hkv * Dp:
                full = jnp.concatenate(
                    [full, jnp.zeros((width - Hkv * Dp, W), f32)], 0)
            return full.T

        def spread(col):
            # (Hkv, 1) → (1, width): a select and a sublane sum, exact
            return jnp.where(on_head, col, 0.0).sum(0, keepdims=True)

    def block(j, _):
        slot = (slot_first + j) % 2
        nxt = 1 - slot

        # block j+1, or the next row's first block, lands while j computes
        last_blk = j + 1 == nblk

        @pl.when(~last_blk | (b + 1 < pl.num_programs(0)))
        def _():
            fetch(jnp.where(last_blk, b + 1, b),
                  jnp.where(last_blk, 0, j + 1), nxt, "start")

        fetch(b, j, slot, "wait")
        slot0, n_live = block_pages(b, j, lens_s, win_s, **walk)
        # row r = s·G + g is query s of group g; query s sits at ln + s
        t = slot0 * ps + jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
        rq = jax.lax.broadcasted_iota(jnp.int32, (R, W), 0)
        if G > 1:
            rq = rq // G
        d = (ln + rq) - t                            # q_pos - k_pos
        ok = (d >= 0) & (d < win_s[0])
        # keys up to the last query's position; the rest of the block (a
        # page's unwritten tail, slots past the last page that no copy
        # filled) may hold anything, NaN included
        key_ok = (slot0 * ps + jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
                  < ln + Sq)
        if quant:
            # each page's head-major scale row out of its block of rows
            # (an aligned load, a select and a sublane sum: exact); dead
            # slots take the last live page's id, their rows unused
            hit = jax.lax.broadcasted_iota(jnp.int32, skbuf.shape[2:], 0)
            pids = [pool_page(b, slot0 + jnp.minimum(i, n_live - 1),
                              pages_s, layer_s)[1] for i in range(ppb)]
            r = [pid - scale_rows_start(pid, rows) for pid in pids]
            sk_rows = [jnp.where(hit == r[i], skbuf[slot, i], 0.0)
                       .sum(0, keepdims=True) for i in range(ppb)]
            sv_rows = [jnp.where(hit == r[i], svbuf[slot, i], 0.0)
                       .sum(0, keepdims=True) for i in range(ppb)]
            eye = (jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (ps, ps), 1))

        if batched:
            # every kv head at once: f32 products of q and the block's
            # lane-dense K on the VPU, summed per head; the online
            # softmax per head in (Hkv, W); the weights spread back over
            # their heads' lanes, times V, summed over the keys
            k = kbuf[slot].reshape(W, width).astype(f32)
            v = jnp.where(key_ok, vbuf[slot].reshape(W, width).astype(f32),
                          0.0)               # a select: 0·NaN is NaN
            s = jnp.where(ok, _softcap(head_sums(k * q_row), cap), NEG_INF)
            m_prev = m_ref[...]                               # (Hkv, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.exp(s - m_new)                         # (Hkv, W)
            l_ref[...] = l_ref[...] * alpha + pexp.sum(-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * spread(alpha) + (
                v * head_lanes(pexp)).sum(0, keepdims=True)
            m_ref[...] = m_new
            return 0

        def scale_cols(sc_rows, h):
            # head h's ps lanes of each page's (1, Hkv·ps) scale row as
            # per-token (ps, 1) columns: a select and a lane sum, exact
            if not quant:
                return None
            return jnp.stack([
                jnp.where(eye, row[:, h * ps:(h + 1) * ps], 0.0)
                .sum(-1, keepdims=True) for row in sc_rows])  # (ppb, ps, 1)

        # one kv head at a time: its (ppb, ps, Dp) lanes of the block's
        # lane-dense pages, 2-D contractions only
        for h in range(hkv):
            lanes = slice(h * Dp, (h + 1) * Dp)
            # in-loop dequant (DESIGN.md §11): quantized pools stream
            # their narrow bytes HBM→VMEM and widen to f32 here
            k = _dequant_block(kbuf[slot, :, :, lanes],
                               scale_cols(sk_rows if quant else None, h),
                               mode).reshape(W, D)
            v = _dequant_block(vbuf[slot, :, :, lanes],
                               scale_cols(sv_rows if quant else None, h),
                               mode).reshape(W, D)
            v = jnp.where(key_ok, v, 0.0)    # a select: 0·NaN is NaN
            q = q_ref[0, h].astype(f32)                       # (R, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=ATTN_PRECISION,
                                    preferred_element_type=f32)  # (R, W)
            s = jnp.where(ok, _softcap(s, cap), NEG_INF)
            m_prev = m_ref[h]                                 # (R, 1)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pexp = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + pexp.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                pexp, v, precision=ATTN_PRECISION, preferred_element_type=f32)
            m_ref[h] = m_new
        return 0

    jax.lax.fori_loop(0, nblk, block, 0)
    slot_ref[0] = (slot_first + nblk) % 2
    if batched:
        out = acc_ref[...] / jnp.maximum(spread(l_ref[...]), 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        return
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def fold_q(q, scale: float, G: int, lanes: int = 0):
    """(B, Sq, Hq, D) → (B, Hkv, Sq·G, D), scaled in the storage dtype
    (dense-op-order numerics): row r = s·G + g of kv head h is query s of
    q head h·G + g.  With ``lanes`` (head-batched, Sq·G == 1): the
    lane-dense row (B, 1, 1, lanes) instead, head h on lanes h·D..,
    padding lanes 0.  Done outside the kernel — Mosaic does not lower
    the reshapes this needs."""
    B, S, Hq, D = q.shape
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, S, Hq // G, G, D)
    if lanes:
        row = qg.reshape(B, 1, 1, Hq * D)
        return jnp.pad(row, ((0, 0),) * 3 + ((0, lanes - Hq * D),))
    return qg.transpose(0, 2, 1, 3, 4).reshape(B, Hq // G, S * G, D)


def unfold_q(out, S: int, G: int, heads=None):
    """Inverse of ``fold_q``'s layout: (B, Hkv, Sq·G, D) → (B, Sq, Hq, D);
    with ``heads = (Hq, D)``, of its lane-dense row (B, 1, 1, lanes)."""
    if heads is not None:
        Hq, D = heads
        return out[..., :Hq * D].reshape(out.shape[0], 1, Hq, D)
    B, hkv, _, D = out.shape
    out = out.reshape(B, hkv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, hkv * G, D)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "G",
                                             "interpret", "windowed"))
def paged_attn_pallas(q, pool_k, pool_v, pages, lens, window, layer, *,
                      scale: float, cap=None, G: int = 1,
                      interpret: bool = False, windowed: bool = True,
                      scale_k=None, scale_v=None):
    """q (B, Sq, Hq, D); pool_k/v (L, n_pages, ps, Hkv·Dp), a stage's
    lane-dense stacked pools; pages (B, P) int32; lens (B,) int32; window
    () int32 (``_NO_WINDOW`` ⇒ global); layer () int32, the pool layer to
    read; ``windowed`` False (static) only where ``window`` is
    ``_NO_WINDOW``: the walk then leaves out the window's arithmetic.
    Query s of row b sits at absolute position ``lens[b] + s``; its
    K/V must already be scattered into the pools.  Quantized pools (int8,
    or uint8 = packed int4 with Dp = D/2) pass their ``scale_k/scale_v
    (L, n_pages, Hkv·ps)`` f32 head-major page rows; page blocks of values
    and scales stream together and widen in-loop (DESIGN.md §11)."""
    B, S, Hq, D = q.shape
    Hkv = Hq // G
    n_pages, ps, HDp = pool_k.shape[1:]
    Dp = HDp // Hkv
    mode = kv_mode_of(pool_k)        # static: dtype is a trace constant
    quant = mode != "bf16"
    R = S * G
    win = jnp.asarray(window, jnp.int32).reshape(1)
    layer = jnp.asarray(layer, jnp.int32)
    rows = min(SCALE_ROWS, n_pages)  # page rows per scale block
    pool_k, lyr_kv = whole_lane_rows(pool_k, layer)
    pool_v, _ = whole_lane_rows(pool_v, layer)
    lyr_sc = layer
    if quant:
        scale_k, lyr_sc = whole_lane_rows(scale_k, layer)
        scale_v, _ = whole_lane_rows(scale_v, layer)
        if interpret and n_pages % rows:
            # the TPU's tile padding of the page axis, which the block of
            # the last pages reads (``scale_rows_start``)
            pad = ((0, 0), (0, -n_pages % rows), (0, 0))
            scale_k, scale_v = jnp.pad(scale_k, pad), jnp.pad(scale_v, pad)
    lanes = pool_k.shape[-1]
    sc_lanes = scale_k.shape[-1] if quant else 0
    ppb = pages_per_block(ps, page_vmem_bytes(ps, lanes, pool_k.dtype,
                                              sc_lanes, rows))
    batched = head_batched(R, pool_k.dtype)
    kern = functools.partial(_decode_kernel, ps=ps, ppb=ppb, cap=cap, G=G,
                             Sq=S, Dp=Dp, rows=rows, mode=mode,
                             windowed=windowed, Hkv=Hkv if batched else 0)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)  # copied by hand
    # q and output: row b's (Hkv, R, D) block, or head-batched its
    # lane-dense (1, lanes) row
    qo = (1, 1, 1, lanes) if batched else (1, Hkv, R, D)
    in_specs = [pl.BlockSpec(qo, paged_q_block_map), hbm, hbm]
    operands = [fold_q(q, scale, G, lanes if batched else 0), pool_k, pool_v]
    # double buffers: block j+1's pages land while block j computes
    scratch = [pltpu.VMEM((2, ppb, ps, lanes), pool_k.dtype),
               pltpu.VMEM((2, ppb, ps, lanes), pool_v.dtype)]
    if quant:
        in_specs += [hbm, hbm]
        operands += [scale_k, scale_v]
        scratch += [pltpu.VMEM((2, ppb, rows, sc_lanes), jnp.float32)] * 2
    # the online softmax's m and l per (head, row), and its accumulator
    ml, acc = ((Hkv, 1), (1, lanes)) if batched else ((Hkv, R, 1),
                                                      (Hkv, R, D))
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),         # one a buffer slot
        pltpu.SMEM((1,), jnp.int32),           # slot of the row's block 0
        pltpu.VMEM(ml, jnp.float32),
        pltpu.VMEM(ml, jnp.float32),
        pltpu.VMEM(acc, jnp.float32),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(qo, paged_q_block_map),
        scratch_shapes=scratch,
    )
    out_shape = (B, 1, 1, lanes) if batched else (B, Hkv, R, D)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        # each row prefetches the next row's first block: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attn_pallas",        # the device op's name in a trace
    )(pages, lens, win, jnp.stack([lyr_kv, lyr_sc]), *operands)
    return unfold_q(out, S, G, (Hq, D) if batched else None)


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
    CPU), bounded by ``max(lens)`` — the batch-wide early exit — and
    started at the earliest block any row's window reaches (the Pallas
    path skips per row at both ends).  Rows whose blocks are fully
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
    # the earliest block any row's window reaches (0 with no window)
    j0 = jnp.min(first_block(lens, win, ps)) // bp
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
    m, l, acc = jax.lax.fori_loop(j0, nb, body, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return unfold_q(out, S, G).astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry: backend + shard-local dispatch
# ---------------------------------------------------------------------------

def resolve_backend(backend: str) -> str:
    """``auto`` → ``pallas`` on a TPU, ``blocked`` elsewhere."""
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


def _local(q, pool_k, pool_v, pages, lens, win, layer, *, scale, cap, G,
           backend, windowed, scale_k=None, scale_v=None):
    if backend == "blocked":
        return _paged_attn_blocked(q, pool_k, pool_v, pages, lens, win,
                                   layer, scale=scale, cap=cap, G=G,
                                   scale_k=scale_k, scale_v=scale_v)
    interpret = interpret_mode(backend)
    return paged_attn_pallas(q, pool_k, pool_v, pages, lens, win, layer,
                             scale=scale, cap=cap, G=G, interpret=interpret,
                             windowed=windowed, scale_k=scale_k,
                             scale_v=scale_v)


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
    to a huge window); None compiles the walk without the window's
    arithmetic.  Sq is static: each distinct value compiles its
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
    backend = resolve_backend(backend)
    win = _NO_WINDOW if window is None else window
    win = jnp.asarray(win, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    kw = dict(scale=scale, cap=cap, G=G, backend=backend,
              windowed=window is not None)

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
