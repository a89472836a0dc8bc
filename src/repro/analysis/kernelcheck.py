"""Pallas kernel bounds checker (DESIGN.md §12).

Proves, before anything runs on a device, that the kernels' BlockSpec
index maps stay inside their operand pools:

  * **paged attention, Pallas lowering** — evaluates the REAL module-level
    index maps (``paged_kv_block_map`` / ``paged_scale_block_map`` /
    ``paged_q_block_map``, exactly what ``paged_attn_pallas`` partials
    into its BlockSpecs) over the full ``(B, max_seq_pages)`` grid ×
    boundary ``lens`` values (0, 1, ps−1, ps, ps+1, 2ps−1, max_seq−Sq)
    and asserts every returned block reads the layer it was given and
    the page of the clamp contract ``pages[b, min(p, (lens[b]+Sq−1)//ps)]``
    — in-bounds AND never a past-lens block;
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
``eval_shape``d and cross-checked against the BlockSpec block shape.

The map-evaluation cores take the maps as arguments so the self-test can
inject seeded mutations (off-by-one, missing clamp) and prove they are
caught.
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


def _boundary_lens(ps: int, P: int, Sq: int) -> List[int]:
    max_len = P * ps - Sq             # caller contract: lens + Sq <= P*ps
    vals = {0, 1, ps - 1, ps, ps + 1, 2 * ps - 1, max_len}
    return sorted(v for v in vals if 0 <= v <= max_len)


def check_paged_index_maps(kv_map: Optional[Callable] = None,
                           scale_map: Optional[Callable] = None,
                           q_map: Optional[Callable] = None, *,
                           ps: int, Sq: int, B: int = 3, P: int = 4,
                           label: str = "") -> List[Finding]:
    """Evaluate the paged-attention index maps over grid × boundary lens.

    Defaults to the real kernel maps; pass mutated maps to prove the
    checker catches them (self-test).  Returns findings (empty = sound).
    """
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import paged_attention as pa

    if kv_map is None:
        kv_map = functools.partial(pa.paged_kv_block_map, Sq=Sq, ps=ps)
    n_pages = B * P + 1
    rows = min(pa.SCALE_ROWS, n_pages)    # page rows per scale block
    if scale_map is None:
        scale_map = functools.partial(pa.paged_scale_block_map, Sq=Sq, ps=ps,
                                      rows=rows)
    if q_map is None:
        q_map = pa.paged_q_block_map

    layer_id = 2                      # a nonzero layer, so layer 0 is wrong
    layer = jnp.asarray([layer_id], jnp.int32)
    # distinct nonzero page ids per (b, p) cell so any mis-indexing is
    # visible as a wrong id, not a coincidental match
    pages_np = np.arange(1, n_pages).reshape(B, P).astype(np.int32)
    pages = jnp.asarray(pages_np)
    win = jnp.asarray([pa._NO_WINDOW], jnp.int32)
    out: List[Finding] = []
    lens_vals = _boundary_lens(ps, P, Sq)
    # uniform sweeps plus one mixed row assignment
    configs = [[v] * B for v in lens_vals]
    configs.append([lens_vals[i % len(lens_vals)] for i in range(B)])

    kv_loc = _loc(kv_map)
    sc_loc = _loc(scale_map)
    q_loc = _loc(q_map)
    for lens_list in configs:
        lens = jnp.asarray(lens_list, jnp.int32)
        for b, p in itertools.product(range(B), range(P)):
            last = (lens_list[b] + Sq - 1) // ps
            want = int(pages_np[b, min(p, last)])
            ctx = (f"{label} ps={ps} Sq={Sq} lens[b]={lens_list[b]} "
                   f"(b={b}, p={p})")
            r = kv_map(b, p, pages, lens, win, layer)
            if len(r) != 4 or int(r[0]) != layer_id or \
                    any(int(x) != 0 for x in r[2:]):
                out.append(Finding(RULE, kv_loc[0], kv_loc[1],
                                   f"kv map returned {r} — expected "
                                   f"({layer_id}, page, 0, 0) [{ctx}]"))
                continue
            pid = int(r[1])
            if not 0 <= pid < n_pages:
                out.append(Finding(
                    RULE, kv_loc[0], kv_loc[1],
                    f"kv map reads page {pid} outside the "
                    f"[0, {n_pages}) pool [{ctx}]"))
            elif pid != want:
                kind = ("past-lens block (clamp violated)"
                        if p > last else "wrong page")
                out.append(Finding(
                    RULE, kv_loc[0], kv_loc[1],
                    f"kv map reads page {pid}, contract says "
                    f"pages[b, min(p, {last})] = {want} — {kind} [{ctx}]"))
            rs = tuple(int(x) for x in scale_map(b, p, pages, lens, win,
                                                 layer))
            if rs != (layer_id, want // rows, 0):
                out.append(Finding(
                    RULE, sc_loc[0], sc_loc[1],
                    f"scale map returned {rs}, expected ({layer_id}, "
                    f"{want // rows}, 0): the {rows}-row block of page "
                    f"{want} [{ctx}]"))
            rq = q_map(b, p, pages, lens, win, layer)
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


def _check_pool_layout(arch: str, cfg, dt: str, ps: int) -> List[Finding]:
    """eval_shape the geometry's pools and cross-check the BlockSpec block
    shapes the kernel would carve from them: (1, ps, Hkv·Dp) value pages
    and head-major (Hkv·ps) scale rows."""
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
    """Full sweep: index maps for every (ps, Sq), pool layout for every
    registry geometry × kv dtype, the blocked lowering under guarded
    primitives, and the encoded-matmul maps over registry linear shapes.
    """
    findings: List[Finding] = []
    geoms = list(_registry_geometries())
    archs = sorted({a for a, _, _ in geoms})
    # the index maps depend only on (ps, Sq) — evaluate once per pair,
    # then pin every registry geometry to a layout cross-check
    for ps, sq in itertools.product(PAGE_SIZES, SQ_VALUES):
        findings.extend(check_paged_index_maps(ps=ps, Sq=sq,
                                               label="pallas"))
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
