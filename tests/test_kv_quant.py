"""Quantized paged KV cache (DESIGN.md §11): int8/int4 pools with
per-token per-head scale rows must (a) round-trip within the symmetric
quantization error bound, (b) give bit-identical attention between the
in-kernel dequant lowerings and the dequantized-gather reference across
ps/lens/GQA sweeps, (c) track the dense cache's logits closely, and
(d) preserve the serving invariants — chunked prefill + prefix-cache
reuse and speculative decoding both stay token-identical *within* a
kv-dtype.  Mesh composition runs in kv_quant_mesh_script.py (2 fake
devices, subprocess)."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attn
from repro.models import init_model, init_paged_cache
from repro.nn.paged import gather_kv_dequant, paged_attn_decode
from repro.quant.kvcache import (kv_mode_of, pack_int4, unpack_int4,
                                 quantize_kv, dequantize_kv, stored_layout)
from repro.serve import Engine


# ---------------------------------------------------------------------------
# quantize / pack round-trips
# ---------------------------------------------------------------------------

def test_pack_unpack_int4_roundtrip():
    rng = np.random.default_rng(0)
    q = rng.integers(-7, 8, size=(3, 5, 2, 16)).astype(np.int8)
    back = np.asarray(unpack_int4(pack_int4(jnp.asarray(q))))
    np.testing.assert_array_equal(back, q.astype(np.float32))


@pytest.mark.parametrize("mode,levels", [("int8", 127), ("int4", 7)])
def test_quantize_error_bound(mode, levels):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 3, 2, 32)) * 3.0, jnp.float32)
    q, s = quantize_kv(x, mode)
    back = dequantize_kv(q, s, mode)
    # symmetric round-to-nearest: |err| <= s/2 per element (s per row)
    bound = np.asarray(s)[..., None] * 0.5 + 1e-6
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err < bound).all(), (err - bound).max()
    assert np.asarray(s).min() >= 0.0


def test_quantize_all_zero_rows_stay_zero():
    x = jnp.zeros((2, 2, 2, 8), jnp.float32)
    for mode in ("int8", "int4"):
        q, s = quantize_kv(x, mode)
        np.testing.assert_array_equal(np.asarray(dequantize_kv(q, s, mode)),
                                      0.0)


# ---------------------------------------------------------------------------
# op parity: in-kernel dequant vs the dequantized-gather reference
# ---------------------------------------------------------------------------

N_LAYERS, LAYER = 3, 1               # stacked pools; the layer read


def _quant_pool_case(rng, B, Hq, Hkv, D, ps, P, mode):
    """Random dense stacked pools quantized row-wise into value + scale
    pools."""
    n_pages = 1 + B * P
    shape = (N_LAYERS, n_pages, ps, Hkv, D)
    dense_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    dense_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pool_k, scale_k = stored_layout(*quantize_kv(dense_k, mode))
    pool_v, scale_v = stored_layout(*quantize_kv(dense_v, mode))
    pages = np.zeros((B, P), np.int32)
    for b in range(B):
        pages[b] = 1 + b * P + np.arange(P)
    g = max(1, Hq // Hkv)
    kv_map = np.minimum(np.arange(Hq) // g, Hkv - 1).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    return q, pool_k, pool_v, scale_k, scale_v, jnp.asarray(pages), kv_map


def _quant_reference(q, pool_k, pool_v, scale_k, scale_v, pages, lens,
                     kv_map, *, scale, window, cap):
    S = q.shape[1]
    ck = gather_kv_dequant(pool_k, scale_k, LAYER, pages)
    cv = gather_kv_dequant(pool_v, scale_v, LAYER, pages)
    k_pos = jnp.arange(ck.shape[1])
    k_valid = k_pos[None, :] < (lens + S)[:, None]
    q_pos = lens[:, None] + jnp.arange(S)[None, :]
    return paged_attn_decode(q, ck, cv, kv_map, scale=scale, q_pos=q_pos,
                             k_pos=k_pos, k_valid=k_valid, window=window,
                             cap=cap)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("ps,Hq,Hkv,window,cap", [
    (4, 4, 2, None, None),       # GQA group 2
    (4, 4, 4, None, None),       # MHA identity map
    (8, 4, 1, None, None),       # MQA
    (4, 4, 2, 7, 30.0),          # sliding window + softcap
])
def test_op_matches_dequant_gather_reference(backend, mode, ps, Hq, Hkv,
                                             window, cap):
    """Both paths read the SAME quantized bytes, so the fused in-loop
    dequant must match the gathered dequant view to float tolerance."""
    rng = np.random.default_rng(hash((mode, ps, Hq, Hkv)) % 2 ** 32)
    B, D, P = 3, 16, 6
    q, pk, pv, sk, sv, pages, kv_map = _quant_pool_case(
        rng, B, Hq, Hkv, D, ps, P, mode)
    lens = jnp.asarray([0, ps + 1, 2 * ps][:B], jnp.int32)
    scale = 1.0 / np.sqrt(D)
    ref = _quant_reference(q, pk, pv, sk, sv, pages, lens, kv_map,
                           scale=scale, window=window, cap=cap)
    out = paged_attn(q, pk, pv, pages, lens, layer=LAYER, scale=scale,
                     window=window, cap=cap, kv_of_q=kv_map,
                     backend=backend, scale_k=sk, scale_v=sv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_op_lens_sweep_quant(backend, mode):
    rng = np.random.default_rng(13)
    ps, P = 4, 4
    q, pk, pv, sk, sv, pages, kv_map = _quant_pool_case(
        rng, 2, 4, 2, 8, ps, P, mode)
    for ln in (0, 1, ps - 1, ps, ps + 1, 2 * ps, P * ps - 1):
        lens = jnp.asarray([ln, max(0, ln - 1)], jnp.int32)
        ref = _quant_reference(q, pk, pv, sk, sv, pages, lens, kv_map,
                               scale=0.3, window=None, cap=None)
        out = paged_attn(q, pk, pv, pages, lens, layer=LAYER, scale=0.3,
                         kv_of_q=kv_map, backend=backend, scale_k=sk,
                         scale_v=sv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6, err_msg=f"lens {ln}")


@pytest.mark.parametrize("backend", ["blocked", "pallas_interpret"])
@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_op_reads_only_its_layers_scales(backend, mode):
    """Every other layer's scale rows hold NaN: the given layer still
    matches the reference, so the scales stream from its pages alone."""
    rng = np.random.default_rng(17)
    ps = 4
    q, pk, pv, sk, sv, pages, kv_map = _quant_pool_case(
        rng, 3, 4, 2, 8, ps, 5, mode)
    other = (jnp.arange(N_LAYERS) != LAYER)[:, None, None]
    sk = jnp.where(other, jnp.nan, sk)
    sv = jnp.where(other, jnp.nan, sv)
    lens = jnp.asarray([0, ps + 2, 3 * ps], jnp.int32)
    ref = _quant_reference(q, pk, pv, sk, sv, pages, lens, kv_map,
                           scale=0.3, window=None, cap=None)
    assert np.isfinite(np.asarray(ref)).all()
    out = paged_attn(q, pk, pv, pages, lens, layer=LAYER, scale=0.3,
                     kv_of_q=kv_map, backend=backend, scale_k=sk,
                     scale_v=sv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("mode,tol", [("int8", 0.02), ("int4", 0.30)])
def test_quant_attention_tracks_dense(mode, tol):
    """Logit-agreement bound vs the dense pools: quantizing K/V perturbs
    attention output by at most the quantization noise — int8 stays
    within ~2% relative, int4 within ~30%."""
    rng = np.random.default_rng(5)
    B, Hq, Hkv, D, ps, P = 3, 4, 2, 32, 4, 6
    n_pages = 1 + B * P
    shape = (1, n_pages, ps, Hkv, D)
    dense_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    dense_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pk, sk = stored_layout(*quantize_kv(dense_k, mode))
    pv, sv = stored_layout(*quantize_kv(dense_v, mode))
    dense_k = dense_k.reshape(1, n_pages, ps, Hkv * D)
    dense_v = dense_v.reshape(1, n_pages, ps, Hkv * D)
    pages = np.zeros((B, P), np.int32)
    for b in range(B):
        pages[b] = 1 + b * P + np.arange(P)
    pages = jnp.asarray(pages)
    kv_map = np.arange(Hq, dtype=np.int32) // 2
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    lens = jnp.asarray([5, 11, 23], jnp.int32)
    dense = paged_attn(q, dense_k, dense_v, pages, lens, layer=0, scale=0.2,
                       kv_of_q=kv_map, backend="blocked")
    quant = paged_attn(q, pk, pv, pages, lens, layer=0, scale=0.2,
                       kv_of_q=kv_map, backend="blocked", scale_k=sk,
                       scale_v=sv)
    err = np.abs(np.asarray(quant) - np.asarray(dense))
    rel = err.max() / (np.abs(np.asarray(dense)).max() + 1e-9)
    assert rel < tol, f"{mode} relative error {rel:.4f} >= {tol}"


# ---------------------------------------------------------------------------
# engine-level serving invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(params, cfg, prompts, kv_dtype, backend="blocked", max_new=6,
           **kw):
    c = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype,
                            attention_backend=backend)
    eng = Engine(params, c, **kw)
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    res = eng.run()
    return [res[r].tolist() for r in rids], eng


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_engine_backends_identical_within_dtype(qwen, kv_dtype):
    """All three lowerings read/write the same quantized bytes, so greedy
    serving is token-identical across backends within one kv-dtype."""
    cfg, params = qwen
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 14, 9)]
    kw = dict(n_slots=2, page_size=4, n_pages=64, prefill_chunk=8)
    ref, _ = _serve(params, cfg, prompts, kv_dtype, "xla", **kw)
    for backend in ("blocked", "pallas_interpret"):
        out, _ = _serve(params, cfg, prompts, kv_dtype, backend, **kw)
        assert out == ref, backend


def test_engine_quant_tracks_dense_tokens(qwen):
    """Token-level agreement with the bf16 cache on the smoke config —
    int8's quantization noise rarely flips a greedy argmax."""
    cfg, params = qwen
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (6, 12, 17, 9)]
    kw = dict(n_slots=2, page_size=4, n_pages=64, prefill_chunk=8)
    ref, _ = _serve(params, cfg, prompts, "bf16", **kw)
    out, eng = _serve(params, cfg, prompts, "int8", **kw)
    match = sum(int(a == b) for r, s in zip(out, ref)
                for a, b in zip(r, s))
    total = sum(len(r) for r in ref)
    assert match / total >= 0.8, f"int8 token agreement {match}/{total}"
    st = eng.stats()
    assert st["kv_cache_dtype"] == "int8"
    assert st["kv_bytes_per_token"] < 0.5 * (
        2 * 2 * cfg.n_kv_p * cfg.head_dim_r * 4)   # << dense f32 bytes


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_chunked_prefill_prefix_reuse_quant(qwen, kv_dtype):
    """Prefix-cache page reuse + chunked prefill over a quantized pool:
    reused quantized pages must reproduce the no-reuse output exactly
    (same bytes, same scales — incl. across the COW path)."""
    cfg, params = qwen
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, cfg.vocab_size, n)
                               .astype(np.int32)]) for n in (3, 5, 7)]
    kw = dict(n_slots=2, page_size=4, n_pages=64, prefill_chunk=8)
    ref, _ = _serve(params, cfg, prompts, kv_dtype, **kw)
    out, eng = _serve(params, cfg, prompts, kv_dtype,
                      prefix_cache=True, **kw)
    assert out == ref
    assert eng.stats()["prefix_hit_tokens"] > 0   # reuse actually happened


@pytest.mark.parametrize("kv_dtype", ["int8", "int4"])
def test_spec_decode_identity_quant(qwen, kv_dtype):
    """Speculative decoding with a quantized verifier cache: quantize-on-
    scatter is deterministic, so verify's overwrite of drafted positions
    reproduces non-spec bytes exactly → greedy output token-identical."""
    cfg, params = qwen
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 14, 9)]
    kw = dict(n_slots=2, page_size=4, n_pages=64, prefill_chunk=8,
              max_new=8)
    ref, _ = _serve(params, cfg, prompts, kv_dtype, **kw)
    out, eng = _serve(params, cfg, prompts, kv_dtype, spec_decode=2, **kw)
    assert out == ref
    assert eng.stats()["spec_rounds"] > 0


def test_engine_mem_accounting(qwen):
    """mem_bytes covers value pools + scale pools + page-table/lens
    buffers; kv_bytes_per_token reflects the narrow storage."""
    cfg, params = qwen
    kw = dict(n_slots=2, page_size=4, n_pages=32, prefill_chunk=8)
    engines = {}
    for kvd in ("bf16", "int8", "int4"):
        c = dataclasses.replace(cfg, kv_cache_dtype=kvd)
        engines[kvd] = Engine(params, c, **kw)
    b16 = engines["bf16"].kv
    i8, i4 = engines["int8"].kv, engines["int4"].kv
    # table/lens bytes included
    assert b16.mem_bytes() == b16.pool_bytes() + b16.ptab.nbytes \
        + b16.lens.nbytes
    # scale pools included: int8 pools alone are 1/4 the f32 pools, but
    # mem_bytes must exceed that by exactly the scale-pool bytes
    n_leaves = sum(1 for st in i8.layers.values() for k in st
                   if k.startswith("scale_"))
    assert n_leaves > 0
    scale_bytes = sum(a.size * a.dtype.itemsize
                      for st in i8.layers.values()
                      for k, a in st.items() if k.startswith("scale_"))
    assert i8.pool_bytes() == b16.pool_bytes() // 4 + scale_bytes
    # per-token bytes strictly ordered: int4 < int8 < dense
    assert i4.kv_bytes_per_token() < i8.kv_bytes_per_token() \
        < b16.kv_bytes_per_token()
    # capacity criterion at equal HBM: >= 2x pages per byte for int8
    assert b16.kv_bytes_per_token() / i8.kv_bytes_per_token() >= 2.0


def test_int4_requires_even_head_dim():
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              head_dim=33, kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="even"):
        init_paged_cache(cfg, 8, 4)


def test_unknown_kv_dtype_rejected():
    cfg = dataclasses.replace(get_config("qwen1.5-0.5b").reduced(),
                              kv_cache_dtype="fp8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        init_paged_cache(cfg, 8, 4)


def test_kv_mode_classifier():
    assert kv_mode_of(jnp.zeros((2,), jnp.int8)) == "int8"
    assert kv_mode_of(jnp.zeros((2,), jnp.uint8)) == "int4"
    assert kv_mode_of(jnp.zeros((2,), jnp.bfloat16)) == "bf16"
    assert kv_mode_of(jnp.zeros((2,), jnp.float32)) == "bf16"


# ---------------------------------------------------------------------------
# mesh composition (2 fake devices, subprocess so XLA_FLAGS doesn't leak)
# ---------------------------------------------------------------------------

def test_mesh_kv_quant_parity():
    """Quantized pools + scale rows shard over kv heads and serve
    token-identically to the single-device quantized engine."""
    script = os.path.join(os.path.dirname(__file__),
                          "kv_quant_mesh_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_KV_QUANT_MESH_OK" in r.stdout
