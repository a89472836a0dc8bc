"""Prefix caching + chunked prefill (DESIGN.md §7): refcount/COW
invariants of the allocator under churn, prefix-index hygiene, cache-hit
decode token-identical to the cold path, eviction preferring unreferenced
cached pages over preempting running requests, and composition with
tensor-parallel serving (subprocess, 2 fake devices)."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serve import (Engine, PageAllocator, PagedKVCache, PrefixIndex,
                         Scheduler, Request, generate, DECODING)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# allocator: refcounts, cached tier, COW invariants
# ---------------------------------------------------------------------------

def test_allocator_refcount_share_and_release():
    al = PageAllocator(8)                       # pages 1..7 usable
    a = al.alloc(3)
    assert all(al.refcount(p) == 1 for p in a)
    al.retain(a[0])                             # share with a second seq
    assert al.refcount(a[0]) == 2
    al.free(a)                                  # first owner drops all
    assert al.refcount(a[0]) == 1               # still held by the sharer
    assert al.n_free == 6                       # a[1], a[2] returned
    al.free([a[0]])
    assert al.n_free == 7
    with pytest.raises(ValueError):
        al.free([a[0]])                         # double free
    with pytest.raises(ValueError):
        al.retain(a[0])                         # retain of unheld page


def test_allocator_cached_tier_reuse_and_eviction():
    dropped = []
    al = PageAllocator(5, on_evict=dropped.append)   # 4 usable
    a = al.alloc(2)
    al.mark_cached(a[0])                        # "indexed" page
    al.free(a)
    assert al.n_free == 4                       # cached page still countable
    assert al.n_cached == 1
    al.retain(a[0])                             # revive from the cached tier
    assert al.refcount(a[0]) == 1 and al.n_cached == 0
    al.free([a[0]])
    assert al.n_cached == 1
    got = al.alloc(4)                           # forces LRU eviction of a[0]
    assert got is not None and a[0] in got
    assert dropped == [a[0]]                    # index was notified


def test_allocator_shared_pages_never_freed_while_referenced():
    """Churn: random alloc/retain/free; a page referenced by any holder
    must never be handed out to another alloc."""
    rng = np.random.default_rng(0)
    al = PageAllocator(17)                      # 16 usable
    held = []                                   # list of page-lists
    for _ in range(300):
        op = rng.integers(0, 3)
        if op == 0:
            got = al.alloc(int(rng.integers(1, 4)))
            if got is not None:
                held.append(got)
        elif op == 1 and held:
            src = held[int(rng.integers(len(held)))]
            p = src[int(rng.integers(len(src)))]
            al.retain(p)
            held.append([p])
        elif op == 2 and held:
            al.free(held.pop(int(rng.integers(len(held)))))
        # invariants: live refcounts equal the number of holders; free
        # pages are exactly the rest
        from collections import Counter
        refs = Counter(p for ps in held for p in ps)
        assert {p: al.refcount(p) for p in refs} == dict(refs)
        assert al.n_free == 16 - len(refs)
    for ps in held:
        al.free(ps)
    assert al.n_free == 16


def test_kv_copy_page_cow(qwen):
    cfg, _ = qwen
    kv = PagedKVCache(cfg, n_slots=1, n_pages=8, page_size=4,
                      max_seq_pages=4)
    kv.pools = jax.tree_util.tree_map(
        lambda a: a.at[:, 1].set(3.0), kv.pools)
    kv.copy_page(0, 3)          # warm the jitted copy (first call may alloc)
    ptrs = [a.unsafe_buffer_pointer()
            for st in kv.pools.values() for a in st.values()]
    kv.copy_page(1, 2)
    # COW is in-place: donated pool buffers, no full-pool reallocation
    assert [a.unsafe_buffer_pointer()
            for st in kv.pools.values() for a in st.values()] == ptrs
    for st in kv.pools.values():
        for a in st.values():
            np.testing.assert_array_equal(np.asarray(a[:, 2]),
                                          np.asarray(a[:, 1]))
            assert float(np.asarray(a[:, 3]).sum()) == 0.0  # others untouched


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_kv_layers_is_the_per_head_view_of_the_pools(qwen, kv_dtype):
    """``layers`` splits the lane-dense pools the steps carry into
    (L, n_pages, ps, n_kv, Dp) — head h is lanes [h·Dp, (h+1)·Dp) — and
    the head-major scale rows into per-token (L, n_pages, ps, n_kv); the
    next read of ``pools`` gives back the stored pools unchanged."""
    import dataclasses
    cfg, _ = qwen
    c = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    n_pages, ps, H, D = 8, 4, c.n_kv_p, c.head_dim_r
    kv = PagedKVCache(c, n_slots=1, n_pages=n_pages, page_size=ps,
                      max_seq_pages=4)
    rng = np.random.default_rng(0)
    kv.pools = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.integers(-100, 100, a.shape), a.dtype),
        kv.pools)
    stored = jax.tree_util.tree_map(np.asarray, kv.pools)
    nbytes = kv.pool_bytes()
    for name, st in kv.layers.items():
        L = stored[name]["pool_k"].shape[0]
        for k in ("pool_k", "pool_v"):
            view = np.asarray(st[k])
            assert view.shape == (L, n_pages, ps, H, D)
            for h in range(H):
                np.testing.assert_array_equal(
                    view[..., h, :], stored[name][k][..., h * D:(h + 1) * D])
        for k in ("scale_k", "scale_v"):
            assert (k in st) == (kv_dtype == "int8")
            if k in st:
                view = np.asarray(st[k])
                assert view.shape == (L, n_pages, ps, H)
                for h in range(H):
                    np.testing.assert_array_equal(
                        view[..., h],
                        stored[name][k][..., h * ps:(h + 1) * ps])
    assert kv.pool_bytes() == nbytes
    back = jax.tree_util.tree_map(np.asarray, kv.pools)
    jax.tree_util.tree_map(np.testing.assert_array_equal, back, stored)


def test_kv_copy_page_cow_quant_carries_scales(qwen):
    """COW over a quantized cache (DESIGN.md §11): copy_page must
    duplicate the int8 value rows AND the matching f32 scale rows in the
    same donated-buffer pass — a copied page that kept stale scales would
    dequantize to wrong K/V after the fork."""
    import dataclasses
    cfg, _ = qwen
    c = dataclasses.replace(cfg, kv_cache_dtype="int8")
    kv = PagedKVCache(c, n_slots=1, n_pages=8, page_size=4,
                      max_seq_pages=4)
    names = {k for st in kv.pools.values() for k in st}
    assert {"pool_k", "pool_v", "scale_k", "scale_v"} <= names
    kv.pools = jax.tree_util.tree_map(
        lambda a: a.at[:, 1].set(3 if a.dtype == jnp.int8 else 3.0),
        kv.pools)
    kv.copy_page(0, 3)          # warm the jitted copy (first call may alloc)
    ptrs = [a.unsafe_buffer_pointer()
            for st in kv.pools.values() for a in st.values()]
    kv.copy_page(1, 2)
    # COW is in-place across ALL leaves, scale pools included
    assert [a.unsafe_buffer_pointer()
            for st in kv.pools.values() for a in st.values()] == ptrs
    for st in kv.pools.values():
        for a in st.values():
            np.testing.assert_array_equal(np.asarray(a[:, 2]),
                                          np.asarray(a[:, 1]))
            assert float(np.abs(np.asarray(a[:, 3])
                                .astype(np.float32)).sum()) == 0.0


# ---------------------------------------------------------------------------
# prefix index
# ---------------------------------------------------------------------------

def test_prefix_index_match_leaves_a_token_to_prefill():
    al = PageAllocator(32)
    idx = PrefixIndex(al, page_size=4)
    toks = np.arange(16, dtype=np.int32)        # exactly 4 full pages
    pages = al.alloc(4)
    assert idx.insert(toks, pages) == 4
    al.free(pages)                              # all four park in the cache
    # a same-prompt match may reuse at most 3 pages: the last page must be
    # re-prefilled so the last-token logits exist
    got = idx.match(toks)
    assert got == pages[:3]
    assert all(al.refcount(p) == 1 for p in got)
    al.free(got)
    # longer continuation: all 4 pages reusable
    got = idx.match(np.arange(20, dtype=np.int32))
    assert got == pages
    al.free(got)


def test_prefix_index_chain_rejects_divergent_prefix():
    al = PageAllocator(32)
    idx = PrefixIndex(al, page_size=4)
    toks = np.arange(12, dtype=np.int32)
    pages = al.alloc(3)
    idx.insert(toks, pages)
    other = toks.copy()
    other[1] = 99                               # diverges inside page 0
    assert idx.match(other, 12) == []
    late = toks.copy()
    late[5] = 99                                # diverges inside page 1
    got = idx.match(late, 12)
    assert got == pages[:1]                     # only the intact page 0
    al.free(got)


def test_cached_tier_evicts_chain_tail_first():
    """A freed sequence parks its pages tail-first, so LRU eviction
    reclaims chain tails before heads — the surviving head prefix stays
    matchable instead of the whole chain dying with its head."""
    al = PageAllocator(8)                       # 7 usable
    idx = PrefixIndex(al, page_size=4)
    toks = np.arange(12, dtype=np.int32)
    pages = al.alloc(3)
    idx.insert(toks, pages)
    al.free(pages)                              # 3 cached, 4 free
    got = al.alloc(6)                           # evicts 2 of the 3 cached
    assert pages[2] in got and pages[1] in got  # tail + mid reclaimed
    assert pages[0] not in got                  # head survived
    m = idx.match(toks, 12)                     # head prefix still matches
    assert m == pages[:1]
    al.free(m)


def test_prefix_hit_stats_not_inflated_by_blocked_admissions(qwen):
    """A head-of-line request re-matched every step while blocked on pages
    must not inflate the reported hit counters: stats commit only when
    admission succeeds."""
    cfg, _ = qwen
    kv = PagedKVCache(cfg, n_slots=2, n_pages=5, page_size=4,
                      max_seq_pages=4)          # 4 usable pages
    sched = Scheduler(kv, prefix_cache=True)
    r1 = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=4)  # 3 pages
    r2 = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=4)  # 3 pages
    sched.submit(r1)
    sched.submit(r2)
    assert [r.rid for _, r in sched.admissions()] == [0]
    for _ in range(10):                         # r2 blocked for pages
        assert sched.admissions() == []
    assert sched.prefix.lookup_tokens == 8      # only r1's admission
    r1.state = DECODING
    sched.finish(r1, t=1.0)
    assert [r.rid for _, r in sched.admissions()] == [1]
    assert sched.prefix.lookup_tokens == 16     # + r2, exactly once


def test_prefix_index_dropped_entries_free_pages():
    al = PageAllocator(8)                       # 7 usable
    idx = PrefixIndex(al, page_size=4)
    toks = np.arange(8, dtype=np.int32)
    pages = al.alloc(2)
    idx.insert(toks, pages)
    al.free(pages)
    assert al.n_cached == 2 and len(idx) == 2
    got = al.alloc(7)                           # evicts both cached pages
    assert got is not None
    assert len(idx) == 0                        # index dropped its entries
    assert idx.match(toks, 8) == []


# ---------------------------------------------------------------------------
# engine: hit-path parity, eviction policy
# ---------------------------------------------------------------------------

def test_cache_hit_decode_token_identical_to_cold(qwen):
    cfg, params = qwen
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, n).astype(np.int32)]) for n in (3, 6, 4)]

    def serve(prefix_cache):
        eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=64,
                     prefix_cache=prefix_cache, prefill_chunk=8)
        outs = []
        for p in prompts:                       # sequential → later prompts
            rid = eng.submit(p, max_new=6)      # can hit the first's pages
            outs.append(eng.run()[rid].tolist())
        return outs, eng.stats()

    cold, st_cold = serve(False)
    warm, st_warm = serve(True)
    assert warm == cold
    assert st_cold["prefix_hit_tokens"] == 0
    assert st_warm["prefix_hit_tokens"] >= 2 * 20 // 4 * 4  # 2 hits × 5 pages
    assert st_warm["prefill_tokens"] < st_cold["prefill_tokens"]
    for p, out in zip(prompts, cold):           # both match dense generate
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=6))[0]
        assert out == ref.tolist()


def test_chunked_prefill_interleaves_with_decode(qwen):
    """While a long prompt prefills chunk-by-chunk, an already-running
    request keeps generating (no full-prefill freeze)."""
    cfg, params = qwen
    short, long = _prompts(cfg, (4, 33), seed=5)
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=64,
                 prefill_chunk=4)
    ra = eng.submit(short, max_new=12)
    eng.step()                                  # short prefills + 1st decode
    assert len(eng.requests[ra].out) >= 1
    rb = eng.submit(long, max_new=4)
    before = len(eng.requests[ra].out)
    eng.step()                                  # long runs ONE 4-token chunk
    assert eng.requests[rb].state == "prefilling"
    assert eng.requests[rb].n_cached == 4
    assert len(eng.requests[ra].out) == before + 1   # decode kept moving
    res = eng.run()
    for rid, p, mn in ((ra, short, 12), (rb, long, 4)):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=mn))[0]
        assert res[rid].tolist() == ref.tolist()


def test_eviction_prefers_unreferenced_cached_pages(qwen):
    """When pages run out, unreferenced prefix-cached pages are reclaimed
    (dropping index entries) BEFORE any running request is preempted."""
    cfg, params = qwen
    rng = np.random.default_rng(7)
    # fill the index: a finished request leaves its 2 full prompt pages
    # parked in the allocator's cached tier (5 usable pages, page_size 8)
    filler = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    eng = Engine(params, cfg, n_slots=2, page_size=8, n_pages=6,
                 reserve="optimistic", prefix_cache=True, prefill_chunk=8)
    eng.submit(filler, max_new=2)
    eng.run()
    assert eng.kv.alloc.n_cached == 2
    assert eng.stats()["prefix_pages_indexed"] == 2
    # two 7-token prompts (no full pages → index nothing themselves) that
    # each grow to 2 pages: 4 pages needed, only 3 truly free → one
    # cached page must be reclaimed, and nobody may be preempted
    pa, pb = _prompts(cfg, (7, 7), seed=8)
    ra = eng.submit(pa, max_new=9)
    rb = eng.submit(pb, max_new=9)
    res = eng.run()
    st = eng.stats()
    assert st["evictions"] == 0                 # nobody was preempted
    assert st["prefix_pages_indexed"] == 1      # one cached page reclaimed
    assert eng.kv.alloc.n_cached == 1
    for rid, p in ((ra, pa), (rb, pb)):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=9))[0]
        assert res[rid].tolist() == ref.tolist()


def test_mesh_prefix_cache_parity():
    """Prefix cache + chunked prefill compose with --mesh tensor-parallel
    serving (2 fake devices, subprocess so XLA_FLAGS doesn't leak)."""
    script = os.path.join(os.path.dirname(__file__),
                          "prefix_cache_mesh_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_PREFIX_MESH_OK" in r.stdout
