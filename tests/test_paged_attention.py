"""Fused paged-attention decode kernel (DESIGN.md §8): the Pallas kernel
(interpret mode) and its blocked XLA lowering must match the gathered-view
reference op numerically, and greedy decode through the engine must stay
token-identical to the gather path across page sizes, ragged lens, GQA
groupings, sliding windows, logit caps, and chunked-prefill offsets —
single-device here, 2-fake-device mesh via paged_attn_mesh_script.py."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import (block_pages, gqa_group,
                                           head_batched, n_blocks,
                                           paged_attn, pages_per_block)
from repro.models import init_model
from repro.nn.paged import gather_kv, gather_kv_dequant, paged_attn_decode
from repro.quant.kvcache import quantize_kv, stored_layout
from repro.serve import Engine, generate


# ---------------------------------------------------------------------------
# op-level parity vs the gathered-view reference
# ---------------------------------------------------------------------------

N_LAYERS, LAYER = 3, 1               # stacked pools; the layer read


def _pool_case(rng, B, Hq, Hkv, D, ps, P):
    """Random stacked lane-dense pools (L, n_pages, ps, Hkv·D) + per-row
    page tables + the uniform q→kv head map."""
    n_pages = 1 + B * P
    shape = (N_LAYERS, n_pages, ps, Hkv * D)
    pool_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pages = np.zeros((B, P), np.int32)
    for b in range(B):
        pages[b] = 1 + b * P + np.arange(P)
    g = max(1, Hq // Hkv)
    kv_map = np.minimum(np.arange(Hq) // g, Hkv - 1).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    return q, pool_k, pool_v, jnp.asarray(pages), kv_map


def _reference(q, pool_k, pool_v, pages, lens, kv_map, *, scale, window,
               cap, layer=LAYER):
    S = q.shape[1]
    n_kv = int(kv_map.max()) + 1
    ck = gather_kv(pool_k, layer, pages, n_kv)
    cv = gather_kv(pool_v, layer, pages, n_kv)
    k_pos = jnp.arange(ck.shape[1])
    k_valid = k_pos[None, :] < (lens + S)[:, None]
    q_pos = lens[:, None] + jnp.arange(S)[None, :]
    return paged_attn_decode(q, ck, cv, kv_map, scale=scale,
                             q_pos=q_pos, k_pos=k_pos,
                             k_valid=k_valid, window=window, cap=cap)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("ps,Hq,Hkv,window,cap", [
    (4, 4, 2, None, None),       # GQA group 2
    (4, 4, 4, None, None),       # MHA identity map
    (8, 4, 1, None, None),       # MQA
    (4, 4, 2, 7, None),          # sliding window
    (4, 4, 2, None, 30.0),       # logit softcap
    (16, 6, 3, 9, 20.0),         # both + odd head counts
])
def test_op_matches_gather_reference(backend, ps, Hq, Hkv, window, cap):
    rng = np.random.default_rng(hash((ps, Hq, Hkv, window or 0)) % 2**32)
    B, D, P = 3, 16, 6
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, B, Hq, Hkv, D, ps, P)
    # ragged rows: empty, mid-page, page-aligned boundary, near table end
    lens = jnp.asarray([0, ps + 1, 2 * ps][:B], jnp.int32)
    scale = 1.0 / np.sqrt(D)
    ref = _reference(q, pool_k, pool_v, pages, lens, kv_map, scale=scale,
                     window=window, cap=cap)
    out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                     scale=scale, window=window, cap=cap, kv_of_q=kv_map,
                     backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("S", [1, 3])
@pytest.mark.parametrize("window", [5, 12, 13, 40])
def test_window_walk_matches_the_masked_full_walk(backend, S, window):
    """The walk that starts at the window's first page against the gather
    reference's full masked walk, for windows smaller than, equal to and
    larger than the contexts (12 and 13 keys for the first query of
    rows 1 and 2).  Pallas: pages wholly before every query's window are
    filled with NaN, so a walk that still read them could not match."""
    rng = np.random.default_rng(window * 10 + S)
    B, D, P, ps, Hq, Hkv = 3, 16, 6, 4, 8, 2
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, B, Hq, Hkv, D, ps, P)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    lens = np.asarray([3, 11, 12], np.int32)
    if backend == "pallas_interpret":
        for b in range(B):
            for blk in range(max(0, (lens[b] - window + 1) // ps)):
                pid = int(pages[b, blk])
                pool_k = pool_k.at[LAYER, pid].set(jnp.nan)
                pool_v = pool_v.at[LAYER, pid].set(jnp.nan)
    lens = jnp.asarray(lens)
    scale = 1.0 / np.sqrt(D)
    ref = _reference(q, jnp.nan_to_num(pool_k), jnp.nan_to_num(pool_v),
                     pages, lens, kv_map, scale=scale, window=window,
                     cap=None)
    out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                     scale=scale, window=window, kv_of_q=kv_map,
                     backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
def test_op_lens_sweep_page_boundaries(backend):
    """Every lens around each page boundary, incl. the last table slot."""
    rng = np.random.default_rng(7)
    ps, P = 4, 4
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, 2, 4, 2, 8, ps, P)
    scale = 0.3
    for ln in (0, 1, ps - 1, ps, ps + 1, 2 * ps, P * ps - 1):
        lens = jnp.asarray([ln, max(0, ln - 1)], jnp.int32)
        ref = _reference(q, pool_k, pool_v, pages, lens, kv_map,
                         scale=scale, window=None, cap=None)
        out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                         scale=scale, kv_of_q=kv_map, backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6, err_msg=f"lens {ln}")


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
def test_op_reads_only_its_layer(backend):
    """Every other layer of the stacked pools holds NaN: the given layer
    still matches the reference, so only its pages are read."""
    rng = np.random.default_rng(5)
    ps = 4
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, 3, 4, 2, 8, ps, 5)
    other = (jnp.arange(N_LAYERS) != LAYER)[:, None, None, None]
    pool_k = jnp.where(other, jnp.nan, pool_k)
    pool_v = jnp.where(other, jnp.nan, pool_v)
    lens = jnp.asarray([0, ps + 2, 3 * ps], jnp.int32)
    ref = _reference(q, pool_k, pool_v, pages, lens, kv_map, scale=0.3,
                     window=None, cap=None)
    assert np.isfinite(np.asarray(ref)).all()
    out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                     scale=0.3, kv_of_q=kv_map, backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("S", [2, 4])
def test_op_kquery_matches_gather_reference(backend, S):
    """k-query decode (speculative verify, DESIGN.md §10): Sq > 1 query
    tokens per slot at positions lens..lens+Sq-1 — fast tier-1 case."""
    rng = np.random.default_rng(11 + S)
    ps, P, B, D = 4, 6, 3, 16
    q1, pool_k, pool_v, pages, kv_map = _pool_case(rng, B, 4, 2, D, ps, P)
    q = jnp.asarray(rng.normal(size=(B, S, 4, D)), jnp.float32)
    lens = jnp.asarray([0, ps - 1, 2 * ps + 1][:B], jnp.int32)
    scale = 1.0 / np.sqrt(D)
    ref = _reference(q, pool_k, pool_v, pages, lens, kv_map, scale=scale,
                     window=None, cap=None)
    out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                     scale=scale, kv_of_q=kv_map, backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("ps,Hq,Hkv,window,cap", [
    (4, 4, 2, None, None),       # GQA group 2
    (8, 4, 1, None, None),       # MQA
    (4, 4, 2, 7, None),          # sliding window
    (4, 4, 2, None, 30.0),       # logit softcap
])
@pytest.mark.parametrize("S", [1, 2, 4, 8])
def test_op_kquery_sweep(backend, S, ps, Hq, Hkv, window, cap):
    """Full Sq × geometry × feature sweep at page-boundary lens (slow:
    the spec-decode CI job runs it; tier-1 keeps the fast case above)."""
    rng = np.random.default_rng(hash((S, ps, Hq, Hkv, window or 0)) % 2**32)
    B, D, P = 4, 16, 6
    _, pool_k, pool_v, pages, kv_map = _pool_case(rng, B, Hq, Hkv, D, ps, P)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    # page-boundary lens; keep lens + S within the page table
    lens = jnp.asarray([0, ps - 1, ps, min(2 * ps + 1, P * ps - S)][:B],
                       jnp.int32)
    scale = 1.0 / np.sqrt(D)
    ref = _reference(q, pool_k, pool_v, pages, lens, kv_map, scale=scale,
                     window=window, cap=cap)
    out = paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                     scale=scale, window=window, cap=cap, kv_of_q=kv_map,
                     backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-6)


def _nan_walk_case(rng, kv, lens_np, S, G, Hkv, window, *, ps=16, D=64,
                   P=20, backend="pallas_interpret"):
    """The kernel (interpret mode) and the gather reference over stacked
    pools of ``kv`` whose every page no row needs (the scratch page 0,
    the table's tail, pages before a window) holds NaN: no block may copy
    them, and slots past a row's last page, which no copy fills, must
    count for exactly nothing.  Returns (kernel out, reference out)."""
    Hq = Hkv * G
    B = len(lens_np)
    _, _, _, pages, kv_map = _pool_case(rng, B, Hq, Hkv, D, ps, P)
    n_pages = 1 + B * P
    # the pages some row needs; the rest, scratch page included, is NaN
    need = np.zeros(n_pages, bool)
    for b, ln in enumerate(lens_np):
        first = 0 if window is None else max(0, (ln - window + 1) // ps)
        need[np.asarray(pages)[b, first:(ln + S - 1) // ps + 1]] = True
    assert not need[0] and not need.all()
    shape = (N_LAYERS, n_pages, ps, Hkv, D)
    dense = [np.where(need[None, :, None, None, None],
                      rng.normal(size=shape), np.nan).astype(np.float32)
             for _ in range(2)]
    if kv in ("int8", "int4"):
        (pk, sk), (pv, sv) = (stored_layout(*quantize_kv(jnp.asarray(x), kv))
                              for x in dense)
        ck, cv = (gather_kv_dequant(p, jnp.nan_to_num(s), LAYER, pages)
                  for p, s in ((pk, sk), (pv, sv)))
    else:
        pk, pv = (stored_layout(jnp.asarray(x).astype(kv)) for x in dense)
        sk = sv = None
        ck, cv = (gather_kv(jnp.nan_to_num(p), LAYER, pages, Hkv)
                  for p in (pk, pv))
    assert pages_per_block(ps, ps * Hkv * D * 4) == 8
    lens = jnp.asarray(lens_np)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    out = paged_attn(q, pk, pv, pages, lens, layer=LAYER, scale=scale,
                     window=window, kv_of_q=kv_map,
                     backend=backend, scale_k=sk, scale_v=sv)
    k_pos = jnp.arange(ck.shape[1])
    ref = paged_attn_decode(q, ck, cv, kv_map, scale=scale,
                            q_pos=lens[:, None] + jnp.arange(S)[None, :],
                            k_pos=k_pos,
                            k_valid=k_pos[None, :] < (lens + S)[:, None],
                            window=window)
    return out, ref


@pytest.mark.parametrize("G", [1, 8])
@pytest.mark.parametrize("S", [1, 5])
@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8", "int4"])
def test_block_walk_matches_gather_reference(kv, S, G):
    """The kernel's walk in compute blocks of ``ppb`` 16-token pages
    (8 here: 128 keys) against the gather reference: rows of different
    lengths in one batch, at ``ppb·ps − 1``, ``ppb·ps`` and ``ppb·ps + 1``
    keys, one page, and three blocks; 2 kv heads of 64, so the values
    are read in place and int4's 64-lane pages and every pool's 32-lane
    scale rows come padded to whole lanes; G = 8 under a 100-key window,
    whose first page falls mid-block (slot 1 of a 129-key row, 12 of a
    296-key one).  Every page no row needs holds NaN."""
    rng = np.random.default_rng(hash((kv, S, G)) % 2 ** 32)
    ps = 16
    window = 100 if G > 1 else None
    blk = 8 * ps
    lens_np = np.asarray([blk - 1, blk, blk + 1, 3, 2 * blk + 40], np.int32)
    out, ref = _nan_walk_case(rng, kv, lens_np, S, G, 2, window)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # the walk the kernel ran: a window starts mid-block; the long row
    # takes three blocks, one under the window
    B = len(lens_np)
    win = np.asarray([window or 2 ** 30], np.int32)
    walk = dict(Sq=S, ps=ps, ppb=8, windowed=window is not None)
    starts = [int(block_pages(b, 0, lens_np, win, **walk)[0])
              for b in range(B)]
    assert starts == ([0] * B if window is None else [1, 1, 1, 0, 12])
    assert int(n_blocks(lens_np[4], win[0], **walk)) == (3 if window is None
                                                         else 1)


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("kv", ["float32", "bfloat16"])
def test_head_batched_walk_matches_gather_reference(kv, window):
    """The head-batched body (one query row a kv head, dense pools) at
    the dense cell's geometry: 16 kv heads of 64 in 1024-lane pages of
    16 tokens, 8 pages a block; rows of one block (a partial last page,
    and one key short of a block), two blocks, and four (a partial last
    page, and a full one); a 200-key window starts the long rows' walks
    mid-table.  Every page no row needs holds NaN."""
    rng = np.random.default_rng([len(kv), window or 0])
    lens_np = np.asarray([100, 127, 200, 500, 511], np.int32)
    out, ref = _nan_walk_case(rng, kv, lens_np, 1, 1, 16, window, P=40)
    assert head_batched(1, jnp.dtype(kv))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    win = np.asarray([window or 2 ** 30], np.int32)
    walk = dict(Sq=1, ps=16, ppb=8, windowed=window is not None)
    assert [int(n_blocks(ln, win[0], **walk)) for ln in lens_np] == (
        [1, 1, 2, 4, 4] if window is None else [1, 1, 2, 2, 2])


def _pallas_out_shape(fn, *args):
    """Output shape of the one ``pallas_call`` that ``fn`` traces."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.outvars[0].aval.shape
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    got = find(getattr(inner, "jaxpr", inner))
                    if got:
                        return got
        return None
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("G,S,kv,batched", [
    (1, 1, "float32", True), (1, 1, "bfloat16", True),
    (8, 1, "float32", False), (1, 5, "float32", False),
    (1, 1, "int8", False), (1, 1, "int4", False)])
def test_head_batched_path_choice(G, S, kv, batched):
    """The body is chosen statically from what the kernel sees: the
    head-batched pass for one query row a kv head (Sq·G == 1) over f32 or
    bf16 pools, the per-head MXU loop for G = 8, for Sq = 5 and for
    quantized pools; the traced kernel's output is the lane-dense row
    exactly when the predicate says so."""
    from repro.kernels.paged_attention import paged_attn_pallas
    B, ps, Hkv, D, n_pages = 3, 16, 2, 64, 9
    dtype, width = ((jnp.uint8, D // 2) if kv == "int4"
                    else (jnp.dtype(kv), D))
    assert head_batched(S * G, dtype) == batched
    pool = jax.ShapeDtypeStruct((N_LAYERS, n_pages, ps, Hkv * width), dtype)
    args = [jax.ShapeDtypeStruct((B, S, Hkv * G, D), jnp.float32), pool,
            pool, jax.ShapeDtypeStruct((B, 4), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32)]
    if kv in ("int8", "int4"):
        args += [jax.ShapeDtypeStruct((N_LAYERS, n_pages, Hkv * ps),
                                      jnp.float32)] * 2

    def call(q, pk, pv, pg, ln, *sc):
        return paged_attn_pallas(q, pk, pv, pg, ln, jnp.int32(2 ** 30),
                                 jnp.int32(LAYER), scale=D ** -0.5, G=G,
                                 interpret=True, scale_k=sc[0] if sc else
                                 None, scale_v=sc[1] if sc else None)
    shape = _pallas_out_shape(call, *args)
    assert shape == ((B, 1, 1, Hkv * D) if batched else (B, Hkv, S * G, D))


def test_op_rejects_irregular_maps():
    """The fused kernel requires a uniform GQA grouping; irregular q→kv
    maps must fall back (or raise when forced)."""
    rng = np.random.default_rng(0)
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, 2, 4, 2, 8, 4, 4)
    lens = jnp.asarray([3, 5], jnp.int32)
    irregular = np.array([0, 1, 1, 0], np.int32)   # not grouped
    assert gqa_group(irregular, 4, 2) is None
    with pytest.raises(ValueError, match="gather path"):
        paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                   scale=1.0, kv_of_q=irregular)


# ---------------------------------------------------------------------------
# engine-level greedy token identity (pallas/blocked vs the xla gather path)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(params, cfg, prompts, backend, **kw):
    c = dataclasses.replace(cfg, attention_backend=backend)
    eng = Engine(params, c, **kw)
    rids = [eng.submit(p, max_new=6) for p in prompts]
    res = eng.run()
    return [res[r].tolist() for r in rids]


def test_engine_token_identical_across_backends(qwen):
    cfg, params = qwen
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 12, 9)]
    kw = dict(n_slots=2, page_size=4, n_pages=64)
    ref = _serve(params, cfg, prompts, "xla", **kw)
    for backend in ("auto", "pallas_interpret", "blocked"):
        assert _serve(params, cfg, prompts, backend, **kw) == ref, backend


def test_engine_token_identical_over_multi_block_walks(qwen):
    """Rows of 130 and 250 cached keys at 8-token pages take two and
    three compute blocks of 16 pages; greedy decode at f32 stays
    token-identical to the gather path."""
    cfg, params = qwen
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (130, 250, 9)]
    kw = dict(n_slots=2, page_size=8, n_pages=96)
    ref = _serve(params, cfg, prompts, "xla", **kw)
    assert _serve(params, cfg, prompts, "pallas_interpret", **kw) == ref


def test_engine_counts_head_batched_layers(qwen):
    """``paged_attn_head_batched_layers`` advances by the layer count a
    decode step where each kv head has one query row (MHA), whose tokens
    stay those of the gather path; the reduced GQA config (G = 2) keeps
    the per-head body and counts nothing."""
    cfg, params = qwen
    mha = dataclasses.replace(cfg, n_kv_heads=cfg.n_heads)
    mha_params = init_model(jax.random.PRNGKey(5), mha)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 12)]
    kw = dict(n_slots=2, page_size=4, n_pages=64)
    for c, p, per_step in ((mha, mha_params, mha.n_layers), (cfg, params, 0)):
        eng = Engine(p, dataclasses.replace(
            c, attention_backend="pallas_interpret"), **kw)
        calls = []
        step = eng._step
        eng._step = lambda *a: calls.append(1) or step(*a)
        rids = [eng.submit(x, max_new=6) for x in prompts]
        res = eng.run()
        assert calls
        assert eng.metrics["paged_attn_head_batched_layers"] == \
            per_step * len(calls)
        assert [res[r].tolist() for r in rids] == \
            _serve(p, c, prompts, "xla", **kw)


@pytest.mark.skipif(jax.default_backend() == "tpu",
                    reason="'pallas' is the Mosaic kernel on a TPU")
def test_pallas_backend_refuses_non_tpu(qwen):
    """'pallas' names the Mosaic kernel: off a TPU it raises instead of
    quietly interpreting — at the op and through the engine."""
    cfg, params = qwen
    rng = np.random.default_rng(0)
    q, pool_k, pool_v, pages, kv_map = _pool_case(rng, 2, 4, 2, 8, 4, 4)
    lens = jnp.asarray([3, 5], jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        paged_attn(q, pool_k, pool_v, pages, lens, layer=LAYER,
                   scale=1.0, kv_of_q=kv_map, backend="pallas")
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        _serve(params, cfg, [prompt], "pallas", n_slots=1, page_size=4,
               n_pages=16)


def test_engine_chunked_prefill_offsets_token_identical(qwen):
    """Chunked prefill + prefix cache leave decode starting at arbitrary
    non-page-aligned lens offsets; the fused path must agree there too."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, n).astype(np.int32)]) for n in (3, 7, 2)]
    kw = dict(n_slots=2, page_size=4, n_pages=64, prefill_chunk=8,
              prefix_cache=True)
    ref = _serve(params, cfg, prompts, "xla", **kw)
    assert _serve(params, cfg, prompts, "pallas_interpret", **kw) == ref


def test_engine_sliding_window_softcap_token_identical():
    """gemma2 reduced: alternating local/global layers + softcaps through
    the fused kernel path."""
    cfg = get_config("gemma2-27b").reduced()
    cfg = dataclasses.replace(cfg, sliding_window=16)
    params = init_model(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 40).astype(np.int32)]
    kw = dict(n_slots=1, page_size=8, n_pages=16)
    ref = _serve(params, cfg, prompts, "xla", **kw)
    for backend in ("auto", "pallas_interpret"):
        assert _serve(params, cfg, prompts, backend, **kw) == ref, backend
    dense = np.asarray(generate(params, cfg, jnp.asarray(prompts[0])[None],
                                max_new=6))[0]
    assert ref[0] == dense.tolist()


def test_mesh_paged_attn_parity():
    """Fused paged attention composes with --mesh tensor-parallel serving:
    kv-head-sharded pools, shard-local kernel (2 fake devices, subprocess
    so XLA_FLAGS doesn't leak)."""
    script = os.path.join(os.path.dirname(__file__),
                          "paged_attn_mesh_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_PAGED_ATTN_MESH_OK" in r.stdout


def test_mesh_pools_replicate_when_kv_heads_do_not_divide():
    """The lane-dense pools shard only in whole kv heads: 2 kv heads at
    model=4 stay replicated (no pool-sized gather, donation kept, tokens
    identical), while model=2 shards them (4 fake devices, subprocess)."""
    script = os.path.join(os.path.dirname(__file__),
                          "paged_pool_mesh4_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_PAGED_POOL_MESH4_OK" in r.stdout
