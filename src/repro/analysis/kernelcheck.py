"""Pallas kernel bounds checker (DESIGN.md §12).

Proves, before anything runs on a device, that the kernels' copies and
BlockSpec index maps stay inside their operand pools:

  * **paged attention, Pallas lowering** — evaluates the REAL module-level
    walk (``block_pages`` / ``pool_page`` / ``scale_rows_start`` /
    ``paged_q_block_map``, exactly what ``_decode_kernel`` calls for its
    copies) for every row and every block of the kernel's loop
    (``n_blocks``) × boundary ``lens`` values (page and compute-block
    edges, the last the table holds) × windows (none, ps, 2ps+1, a
    block and a bit) × the block sizes the registry's geometries and kv
    dtypes give, and asserts the blocks' live slots tile the row's
    needed slots ``first..(lens[b]+Sq−1)//ps`` exactly once, in order,
    ``first`` the window's first page ``max(0, (lens[b]−window+1)//ps)``
    — never a past-lens or before-window slot, never past the table —
    that each copy reads ``(layer, pages[b, slot])``, and that a page's
    scale copy reads the aligned block of rows holding its own, inside
    the pool's tiles;
  * **paged attention, blocked (XLA) lowering** — executes
    ``_paged_attn_blocked`` under ``jax.disable_jit()`` with
    ``jax.lax.dynamic_slice_in_dim`` / ``pool_pages`` replaced by guards
    that assert every page-table slice and (layer, page) pool gather is
    in bounds, for all three kv dtypes and both the multi-block and the
    pad-the-table block widths;
  * **encoded matmul** — checks the grid index maps (``x_block_map`` …
    ``out_block_map``) against the padded operand shapes produced by
    ``kernels.ops``' padding helpers, over every registry linear
    geometry and the decode m-buckets.

Geometry coverage is driven by the configs registry: every paged-servable
arch × page sizes (8, 16) × kv dtypes (bf16, int8, int4) × Sq ∈ {1, 5}
(decode and spec-verify shapes); each geometry's pool layout is
``eval_shape``d and cross-checked against the page the kernel copies.

The walk-evaluation core takes the walk's functions as arguments so the
self-test can inject seeded mutations (off-by-one, missing clamp,
missing window start, wrong layer) and prove they are caught.
"""
from __future__ import annotations

import functools
import itertools
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.lint import Finding, repo_root

RULE = "kernel-bounds"

PAGE_SIZES = (8, 16)
KV_DTYPES = ("bf16", "int8", "int4")
SQ_VALUES = (1, 5)                   # decode step / spec-verify (k=4) shapes


def _loc(fn) -> Tuple[str, int]:
    """repo-relative file:line of a (possibly partial'd) map function."""
    import os
    while isinstance(fn, functools.partial):
        fn = fn.func
    code = getattr(fn, "__code__", None)
    if code is None:
        return "<unknown>", 0
    try:
        rel = os.path.relpath(code.co_filename, repo_root())
    except ValueError:
        rel = code.co_filename
    return rel, code.co_firstlineno


def _boundary_lens(ps: int, P: int, Sq: int, ppb: int = 1) -> List[int]:
    """Lens at page and compute-block boundaries, and the last a table
    of P pages holds."""
    max_len = P * ps - Sq             # caller contract: lens + Sq <= P*ps
    blk = ppb * ps
    vals = {0, 1, ps - 1, ps, ps + 1, 2 * ps - 1, blk - 1, blk, blk + 1,
            2 * blk + ps // 2, max_len}
    return sorted(v for v in vals if 0 <= v <= max_len)


def check_paged_walk(block_fn: Optional[Callable] = None,
                     page_fn: Optional[Callable] = None,
                     scale_fn: Optional[Callable] = None,
                     q_map: Optional[Callable] = None, *,
                     ps: int, Sq: int, ppb: int, B: int = 3,
                     P: Optional[int] = None, label: str = "",
                     windowed: bool = True) -> List[Finding]:
    """Evaluate the paged-attention walk over every row × every block
    the kernel's loop runs (``n_blocks``) × boundary lens × windows
    (``windowed``: the walk a windowed model compiles; else only
    ``_NO_WINDOW``).  The contract: the blocks' live slots tile the
    row's needed slots ``first..last`` exactly, in order, at most
    ``ppb`` a block, where ``last = (lens[b]+Sq−1)//ps`` and ``first``
    is the window's first page ``max(0, (lens[b]−window+1)//ps)`` (0
    unwindowed); each copy reads ``(layer, pages[b, slot])``; a page's
    scale copy reads an aligned block of ``rows`` rows that holds its
    row and lies inside the pool's 8-row tiles.

    Defaults to the kernel's own functions (``block_pages``,
    ``pool_page``, ``scale_rows_start``, ``paged_q_block_map``); pass
    mutated ones to prove the checker catches them (self-test).
    Returns findings (empty = sound).
    """
    import numpy as np
    from repro.kernels import paged_attention as pa

    walk = dict(Sq=Sq, ps=ps, ppb=ppb, windowed=windowed)
    if block_fn is None:
        block_fn = functools.partial(pa.block_pages, **walk)
    if page_fn is None:
        page_fn = pa.pool_page
    if scale_fn is None:
        scale_fn = pa.scale_rows_start
    if q_map is None:
        q_map = pa.paged_q_block_map
    if P is None:
        P = 2 * ppb + 3               # room for three blocks a row
    n_pages = B * P + 1               # not a multiple of 8 in general
    rows = min(pa.SCALE_ROWS, n_pages)
    padded = -(-n_pages // rows) * rows   # the page axis in whole tiles

    layer_id = 2                      # a nonzero layer, so layer 0 is wrong
    layer = np.asarray([layer_id, layer_id], np.int32)  # values, scales
    # distinct nonzero page ids per (b, slot) so any mis-indexing is
    # visible as a wrong id, not a coincidental match
    pages = np.arange(1, n_pages).reshape(B, P).astype(np.int32)
    out: List[Finding] = []
    lens_vals = _boundary_lens(ps, P, Sq, ppb)
    # uniform sweeps plus one mixed row assignment
    configs = [[v] * B for v in lens_vals]
    configs.append([lens_vals[i % len(lens_vals)] for i in range(B)])
    windows = (int(pa._NO_WINDOW),) + ((ps, 2 * ps + 1, ppb * ps + 3)
                                       if windowed else ())

    bl_loc, pg_loc = _loc(block_fn), _loc(page_fn)
    sc_loc, q_loc = _loc(scale_fn), _loc(q_map)
    for lens_list, window in itertools.product(configs, windows):
        lens = np.asarray(lens_list, np.int32)
        win = np.asarray([window], np.int32)
        for b in range(B):
            last = (lens_list[b] + Sq - 1) // ps
            first = max(0, (lens_list[b] - window + 1) // ps)
            nblk = int(pa.n_blocks(lens[b], win[0], **walk))
            ctx = (f"{label} ps={ps} Sq={Sq} ppb={ppb} lens[b]={lens_list[b]}"
                   f" window={window} b={b}")
            want = list(range(first, last + 1))
            seen: List[int] = []
            for j in range(nblk):
                slot0, n_live = (int(x) for x in block_fn(b, j, lens, win))
                if not 1 <= n_live <= ppb:
                    out.append(Finding(
                        RULE, bl_loc[0], bl_loc[1],
                        f"block {j} has {n_live} live slots, not 1..{ppb} "
                        f"[{ctx}]"))
                    continue
                for slot in range(slot0, slot0 + n_live):
                    seen.append(slot)
                    if slot > last or slot >= P:
                        out.append(Finding(
                            RULE, bl_loc[0], bl_loc[1],
                            f"block {j} copies slot {slot} past the last "
                            f"needed slot {last} (past-lens block; table "
                            f"of {P}) [{ctx}]"))
                        continue
                    if slot < first:
                        out.append(Finding(
                            RULE, bl_loc[0], bl_loc[1],
                            f"block {j} copies slot {slot} before the "
                            f"window's first slot {first} (before-window "
                            f"block) [{ctx}]"))
                        continue
                    lyr, pid = (int(x) for x in page_fn(b, slot, pages,
                                                        layer))
                    if lyr != layer_id:
                        out.append(Finding(
                            RULE, pg_loc[0], pg_loc[1],
                            f"copy of slot {slot} reads layer {lyr}, not "
                            f"{layer_id} (wrong layer) [{ctx}]"))
                    if pid != pages[b, slot]:
                        out.append(Finding(
                            RULE, pg_loc[0], pg_loc[1],
                            f"copy of slot {slot} reads page {pid}, "
                            f"contract says pages[b, {slot}] = "
                            f"{pages[b, slot]} (wrong page) [{ctx}]"))
                        continue
                    r0 = int(scale_fn(pid, rows))
                    if r0 % rows or not r0 <= pid < r0 + rows \
                            or r0 + rows > padded:
                        out.append(Finding(
                            RULE, sc_loc[0], sc_loc[1],
                            f"scale copy of page {pid} reads rows "
                            f"[{r0}, {r0 + rows}): not the aligned "
                            f"{rows}-row block holding it inside the "
                            f"{padded} tiled rows [{ctx}]"))
            if seen != want and all(first <= x <= last for x in seen):
                out.append(Finding(
                    RULE, bl_loc[0], bl_loc[1],
                    f"the blocks copy slots {seen}, not each of "
                    f"{first}..{last} once in order [{ctx}]"))
            rq = q_map(b, pages, lens, win, layer)
            if tuple(int(x) for x in rq) != (b, 0, 0, 0):
                out.append(Finding(
                    RULE, q_loc[0], q_loc[1],
                    f"q map returned {rq}, expected ({b}, 0, 0, 0) "
                    f"[{ctx}]"))
    return out


def _make_pools(mode: str, L: int, n_pages: int, ps: int, Hkv: int,
                D: int):
    """Stacked lane-dense pools (L, n_pages, ps, Hkv·Dp) and, for
    quantized modes, head-major scale rows (L, n_pages, Hkv·ps)."""
    import jax.numpy as jnp
    from repro.quant.kvcache import scale_pool_shape
    if mode == "bf16":
        k = jnp.zeros((L, n_pages, ps, Hkv * D), jnp.bfloat16)
        return k, k, None, None
    dt, dp = (jnp.int8, D) if mode == "int8" else (jnp.uint8, D // 2)
    k = jnp.zeros((L, n_pages, ps, Hkv * dp), dt)
    s = jnp.zeros(scale_pool_shape(L, n_pages, ps, Hkv), jnp.float32)
    return k, k, s, s


def check_blocked_lowering(*, ps: int, Sq: int, mode: str = "bf16",
                           bk: int, B: int = 2, P: int = 4) -> List[Finding]:
    """Run the XLA reference lowering eagerly with guarded slice/gather
    primitives: every ``dynamic_slice_in_dim`` over the page table and
    every (layer, page) gather into a pool must be in bounds, across boundary
    lens values.  ``bk < ps`` exercises bp=1 multi-block stepping;
    ``bk=128`` exercises the pad-the-table path."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pa

    Hq, Hkv, D, L = 2, 1, 4, 3
    n_pages = B * P + 1
    pages = jnp.asarray(
        np.arange(1, n_pages).reshape(B, P).astype(np.int32))
    pool_k, pool_v, scale_k, scale_v = _make_pools(
        mode, L, n_pages, ps, Hkv, D)
    q = jnp.zeros((B, Sq, Hq, D), jnp.float32)
    win = jnp.asarray(pa._NO_WINDOW, jnp.int32)
    errors: List[str] = []

    orig_ds = jax.lax.dynamic_slice_in_dim
    orig_pages = pa.pool_pages

    def guard_ds(operand, start, size, axis=0):
        s = int(start)
        if not (0 <= s and s + size <= operand.shape[axis]):
            errors.append(
                f"dynamic_slice_in_dim [{s}, {s + size}) exceeds axis "
                f"{axis} of shape {operand.shape}")
        return orig_ds(operand, s, size, axis)

    def guard_pages(pool, layer, pid):
        lyr = int(layer)
        lo, hi = int(jnp.min(pid)), int(jnp.max(pid))
        if not 0 <= lyr < pool.shape[0]:
            errors.append(f"pool gather reads layer {lyr} of a "
                          f"{pool.shape[0]}-layer pool")
        if lo < 0 or hi >= pool.shape[1]:
            errors.append(f"pool gather reads pages [{lo}, {hi}] from a "
                          f"pool of {pool.shape[1]} pages")
        return orig_pages(pool, layer, pid)

    loc = _loc(pa._paged_attn_blocked)
    out: List[Finding] = []
    try:
        jax.lax.dynamic_slice_in_dim = guard_ds
        pa.pool_pages = guard_pages
        with jax.disable_jit():
            for ln in _boundary_lens(ps, P, Sq):
                lens = jnp.asarray([ln] * B, jnp.int32)
                pa._paged_attn_blocked(
                    q, pool_k, pool_v, pages, lens, win, L - 1, scale=1.0,
                    G=Hq // Hkv, bk=bk, scale_k=scale_k, scale_v=scale_v)
                for e in errors:
                    out.append(Finding(
                        RULE, loc[0], loc[1],
                        f"blocked lowering (ps={ps} Sq={Sq} mode={mode} "
                        f"bk={bk} lens={ln}): {e}"))
                errors.clear()
    finally:
        jax.lax.dynamic_slice_in_dim = orig_ds
        pa.pool_pages = orig_pages
    return out


def check_encoded_maps(x_map: Optional[Callable] = None,
                       w_map: Optional[Callable] = None,
                       b_map: Optional[Callable] = None,
                       o_map: Optional[Callable] = None, *,
                       m: int, k: int, n: int, U: int = 48,
                       bm: Optional[int] = None, bn: int = 128,
                       bk: int = 128, label: str = "") -> List[Finding]:
    """Check the encoded-matmul grid maps against the shapes
    ``kernels.ops`` actually pads to for an (m, k) × (U, k, n) call."""
    from repro.kernels import encoded_matmul as em
    from repro.kernels import ops

    if x_map is None:
        x_map = em.x_block_map
    if w_map is None:
        w_map = em.w_block_map
    if b_map is None:
        b_map = em.bias_block_map
    if o_map is None:
        o_map = em.out_block_map
    if bm is None:
        bm = ops._pick_bm(m)

    def pad(size, mult):
        return size + (-size) % mult

    mp, kp, np_ = pad(m, bm), pad(k, bk), pad(n, bn)
    grid = (mp // bm, np_ // bn, kp // bk)
    shapes = {
        "x": (x_map, (bm, bk), (mp, kp)),
        "w": (w_map, (U, bk, bn), (U, kp, np_)),
        "bias": (b_map, (1, bn), (1, np_)),
        "out": (o_map, (bm, bn), (mp, np_)),
    }
    out: List[Finding] = []
    for i, j, kk in itertools.product(*(range(g) for g in grid)):
        for name, (fn, blk, full) in shapes.items():
            idx = fn(i, j, kk)
            loc = _loc(fn)
            ctx = (f"{label} m={m} k={k} n={n} bm={bm} grid cell "
                   f"({i},{j},{kk})")
            if len(idx) != len(blk):
                out.append(Finding(
                    RULE, loc[0], loc[1],
                    f"encoded {name} map returned rank-{len(idx)} index "
                    f"for a rank-{len(blk)} block [{ctx}]"))
                continue
            for d, (bi, bd, fd) in enumerate(zip(idx, blk, full)):
                bi = int(bi)
                if bi < 0 or (bi + 1) * bd > fd:
                    out.append(Finding(
                        RULE, loc[0], loc[1],
                        f"encoded {name} map block {bi} on dim {d} "
                        f"spans [{bi * bd}, {(bi + 1) * bd}) outside the "
                        f"padded extent {fd} [{ctx}]"))
    return out


# ---------------------------------------------------------------------------
# registry-driven geometry sweep
# ---------------------------------------------------------------------------

def _registry_geometries():
    """(arch, cfg, kv_dtype) for every paged-servable registry arch × kv
    dtype, on the ``reduced()`` shape family (same head/dim structure)."""
    import dataclasses
    from repro.configs.registry import get_config, list_archs
    from repro.models import supports_paged_cache
    for arch in list_archs():
        cfg = get_config(arch).reduced()
        if not supports_paged_cache(cfg):
            continue
        for dt in KV_DTYPES:
            if dt == "int4" and cfg.head_dim_r % 2:
                continue          # int4 packs head-dim pairs; odd → no-op
            yield arch, dataclasses.replace(cfg, kv_cache_dtype=dt), dt


def _pages_per_block(cfg, ps: int) -> int:
    """The compute block the kernel picks for ``cfg``'s pools at page
    size ``ps`` (``paged_attn_pallas``: ``pages_per_block`` of a page's
    VMEM bytes)."""
    from repro.kernels import paged_attention as pa
    from repro.quant.kvcache import kv_pool_layout
    dtype, dp, quant = kv_pool_layout(cfg)
    n_kv = cfg.n_kv_p
    return pa.pages_per_block(ps, pa.page_vmem_bytes(
        ps, pa.whole_lanes(n_kv * dp), dtype,
        pa.whole_lanes(n_kv * ps) if quant else 0, pa.SCALE_ROWS))


def _check_pool_layout(arch: str, cfg, dt: str, ps: int) -> List[Finding]:
    """eval_shape the geometry's pools and cross-check what the kernel
    copies from them: (ps, Hkv·Dp) value pages and head-major (Hkv·ps)
    scale rows."""
    import jax
    from repro.models import init_paged_cache
    out: List[Finding] = []
    n_pages = 9
    abs_ = jax.eval_shape(
        lambda: init_paged_cache(cfg, n_pages, ps))["layers"]
    quant = dt != "bf16"
    want_dp = cfg.head_dim_r // 2 if dt == "int4" else cfg.head_dim_r
    hkv = cfg.n_kv_p
    for stage, st in abs_.items():
        pk = st["pool_k"]
        if pk.shape[1:] != (n_pages, ps, hkv * want_dp):
            out.append(Finding(
                RULE, "src/repro/models/lm.py", 0,
                f"{arch}/{stage} kv_dtype={dt}: pool shape "
                f"{pk.shape} does not match the kernel block "
                f"(1, {ps}, {hkv}·{want_dp})"))
        if quant != ("scale_k" in st):
            out.append(Finding(
                RULE, "src/repro/models/lm.py", 0,
                f"{arch}/{stage} kv_dtype={dt}: scale side pool "
                f"{'missing' if quant else 'unexpected'}"))
        elif quant and st["scale_k"].shape[1:] != (n_pages, hkv * ps):
            out.append(Finding(
                RULE, "src/repro/models/lm.py", 0,
                f"{arch}/{stage} kv_dtype={dt}: scale pool shape "
                f"{st['scale_k'].shape} mismatches (n_pages, Hkv·ps)"))
    return out


def run_kernelcheck() -> Tuple[List[Finding], Dict]:
    """Full sweep: the walk for every (ps, Sq, block size), pool layout
    for every registry geometry × kv dtype, the blocked lowering under
    guarded primitives, and the encoded-matmul maps over registry linear
    shapes."""
    findings: List[Finding] = []
    geoms = list(_registry_geometries())
    archs = sorted({a for a, _, _ in geoms})
    # the walk depends on (ps, Sq) and the pages a block holds, which
    # the kernel derives from each geometry's page bytes and kv dtype:
    # check it once per distinct block size, plus 1 and an odd 3
    for ps, sq in itertools.product(PAGE_SIZES, SQ_VALUES):
        sizes = {1: "one page", 3: "three pages"}
        for _, cfg, dt in geoms:
            sizes.setdefault(_pages_per_block(cfg, ps), dt)
        for ppb, why in sorted(sizes.items()):
            for windowed in (False, True):
                findings.extend(check_paged_walk(
                    ps=ps, Sq=sq, ppb=ppb, label=f"pallas ({why})",
                    windowed=windowed))
    for arch, cfg, dt in geoms:
        for ps in PAGE_SIZES:
            findings.extend(_check_pool_layout(arch, cfg, dt, ps))
    for ps, sq, mode in itertools.product(PAGE_SIZES, SQ_VALUES,
                                          KV_DTYPES):
        for bk in (ps, 128):
            findings.extend(check_blocked_lowering(ps=ps, Sq=sq,
                                                   mode=mode, bk=bk))
    # encoded matmul over registry linear geometries × decode m-buckets
    lin_shapes = set()
    for _, cfg, _ in geoms:
        d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
        lin_shapes |= {(d, d), (d, f), (f, d), (d, v)}
    for (k, n), m in itertools.product(sorted(lin_shapes),
                                       (1, 8, 33, 128)):
        findings.extend(check_encoded_maps(m=m, k=k, n=n,
                                           label="encoded"))
    coverage = {
        "archs": archs,
        "page_sizes": list(PAGE_SIZES),
        "kv_dtypes": list(KV_DTYPES),
        "sq_values": list(SQ_VALUES),
        "lowerings": ["pallas", "blocked"],
        "encoded_linear_shapes": sorted(lin_shapes),
        "encoded_m_values": [1, 8, 33, 128],
    }
    return findings, coverage
