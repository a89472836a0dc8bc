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
from repro.kernels.paged_attention import paged_attn, gqa_group
from repro.models import init_model
from repro.nn.paged import gather_kv, paged_attn_decode
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
