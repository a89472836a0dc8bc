"""Encoded-matmul micro-bench (CPU wall time is NOT the perf claim — TPU is
the target; this records the simulation cost + the decomposition's plane
count R, which sets the TPU FLOP multiplier of the functional simulation)."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.mac import EncodedMac, lut_matmul
from repro.kernels.ops import encoded_matmul
from repro.kernels.ref import encoded_matmul_ref
from .common import time_call


def run():
    mac = EncodedMac.default()
    prog = mac.program
    rng = np.random.default_rng(0)
    m = k = n = 256
    x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(mac.s_init)
    Wt, bias = prog.fold_weights(wq, s)

    f_x = jax.jit(lambda a: encoded_matmul(a, Wt, bias, prog.a_mono_bits,
                                           backend="xla"))
    f_ref = jax.jit(lambda a: encoded_matmul_ref(a, Wt, bias,
                                                 prog.a_mono_bits))
    f_lut = jax.jit(lambda a: lut_matmul(a, wq, mac.spec.lut()))
    f_fp = jax.jit(lambda a: a.astype(jnp.float32)
                   @ wq.astype(jnp.float32))
    res = {
        "planes_R": int(prog.n_a_planes),
        "b_planes_V": int(prog.n_b_planes),
        "m_bits": int(mac.spec.m_bits),
        "encoded_xla_us": time_call(f_x, x, n=5),
        "encoded_ref_us": time_call(f_ref, x, n=5),
        "lut_oracle_us": time_call(f_lut, x, n=3),
        "fp_matmul_us": time_call(f_fp, x, n=10),
    }
    # decode shapes: small m (a B=4 decode step) used to pad up to bm=128,
    # wasting 97% of the MXU rows — bm=None picks the bucket (8/32/128)
    # covering m.  Record adaptive vs fixed-128 Pallas wall time + the
    # padded-row waste each avoids.
    for mb in (4, 32):
        xs = jnp.asarray(rng.integers(-127, 128, (mb, k)), jnp.int8)
        f_ad = jax.jit(lambda a: encoded_matmul(
            a, Wt, bias, prog.a_mono_bits, backend="pallas_interpret"))
        f_128 = jax.jit(lambda a: encoded_matmul(
            a, Wt, bias, prog.a_mono_bits, backend="pallas_interpret",
            bm=128))
        from repro.kernels.ops import _pick_bm
        res[f"decode_m{mb}_bm_bucket"] = _pick_bm(mb)
        res[f"decode_m{mb}_adaptive_us"] = time_call(f_ad, xs, n=3)
        res[f"decode_m{mb}_bm128_us"] = time_call(f_128, xs, n=3)
        res[f"decode_m{mb}_row_util_adaptive"] = mb / _pick_bm(mb)
        res[f"decode_m{mb}_row_util_bm128"] = mb / 128
    res.update(_paged_attn_bench(rng))
    return res


def _paged_attn_bench(rng):
    """Fused paged-attention decode op (DESIGN.md §8) vs the gathered-view
    reference at one table width: the gather path's cost is pinned to the
    table width while the fused path follows ``lens`` (block skipping).
    The interpret-mode Pallas number is the simulation cost on CPU (the
    kernel targets Mosaic), recorded like the encoded interpret numbers —
    the ``blocked`` XLA lowering is what serves off-TPU."""
    from repro.kernels.paged_attention import paged_attn
    from repro.nn.paged import gather_kv, paged_attn_decode

    B, Hq, Hkv, D, ps, P = 4, 4, 2, 32, 16, 64       # 1024-token table
    # one layer of stacked lane-dense pools (L, n_pages, ps, Hkv·D)
    pool_k = jnp.asarray(rng.normal(size=(1, P + 1, ps, Hkv * D)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(1, P + 1, ps, Hkv * D)),
                         jnp.float32)
    pages = jnp.broadcast_to(jnp.arange(1, P + 1, dtype=jnp.int32)[None],
                             (B, P))
    kv_map = np.minimum(np.arange(Hq) // (Hq // Hkv), Hkv - 1)
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)

    def gather_ref(q, lens):
        ck = gather_kv(pool_k, 0, pages, Hkv)
        cv = gather_kv(pool_v, 0, pages, Hkv)
        k_pos = jnp.arange(ck.shape[1])
        return paged_attn_decode(q, ck, cv, kv_map, scale=scale,
                                 q_pos=lens[:, None], k_pos=k_pos,
                                 k_valid=k_pos[None] < (lens + 1)[:, None])

    f_gather = jax.jit(gather_ref)
    f_blk = jax.jit(lambda q, lens: paged_attn(
        q, pool_k, pool_v, pages, lens, layer=0, scale=scale,
        kv_of_q=kv_map, backend="blocked"))
    f_int = jax.jit(lambda q, lens: paged_attn(
        q, pool_k, pool_v, pages, lens, layer=0, scale=scale,
        kv_of_q=kv_map, backend="pallas_interpret"))
    out = {"paged_attn_table_tokens": P * ps}
    for name, ln in (("short", 40), ("long", 512)):
        lens = jnp.full((B,), ln, jnp.int32)
        out[f"paged_attn_{name}_gather_us"] = time_call(f_gather, q, lens,
                                                        n=10)
        out[f"paged_attn_{name}_blocked_us"] = time_call(f_blk, q, lens,
                                                         n=10)
        out[f"paged_attn_{name}_interpret_us"] = time_call(f_int, q, lens,
                                                           n=3)
    out.update(_paged_attn_dtype_axis(rng, B, Hq, Hkv, D, ps, P, kv_map,
                                      q, scale))
    return out


def _paged_attn_dtype_axis(rng, B, Hq, Hkv, D, ps, P, kv_map, q, scale):
    """Fused blocked decode step per KV-pool dtype (bf16/int8/int4,
    DESIGN.md §11) at lens=512, reporting the achieved pool bytes/s so
    the bandwidth-bound claim is measured, not asserted: the quantized
    pools stream 3.6–6.4x fewer bytes per cached token (value bytes +
    the f32 per-token scale rows); whether fewer bytes buys wall time
    depends on the host — on a 1-core CPU the step is bound by the f32
    attention matvec, on HBM-backed accelerators the pool read is the
    bottleneck the kernel targets.  Every slot reads its OWN page chain
    here (unlike the shared-chain rows above) so the streamed bytes are
    real, not cache-resident."""
    from repro.kernels.paged_attention import paged_attn
    from repro.quant.kvcache import quantize_kv, stored_layout

    ln = 512
    P_own = ln // ps
    n_pages = 1 + B * P_own
    dense_k = jnp.asarray(rng.normal(size=(1, n_pages, ps, Hkv, D)),
                          jnp.float32)
    dense_v = jnp.asarray(rng.normal(size=(1, n_pages, ps, Hkv, D)),
                          jnp.float32)
    pg = np.zeros((B, P_own), np.int32)
    for b in range(B):
        pg[b] = 1 + b * P_own + np.arange(P_own)
    pages = jnp.asarray(pg)
    lens = jnp.full((B,), ln, jnp.int32)
    out = {}
    for mode in ("bf16", "int8", "int4"):
        if mode == "bf16":
            pk, pv = stored_layout(dense_k), stored_layout(dense_v)
            sk = sv = None
        else:
            pk, sk = stored_layout(*quantize_kv(dense_k, mode))
            pv, sv = stored_layout(*quantize_kv(dense_v, mode))
        f = jax.jit(lambda q, lens, pk=pk, pv=pv, sk=sk, sv=sv: paged_attn(
            q, pk, pv, pages, lens, layer=0, scale=scale, kv_of_q=kv_map,
            backend="blocked", scale_k=sk, scale_v=sv))
        us = time_call(f, q, lens, n=10)
        bytes_per_step = B * ln * (
            2 * pk.shape[-1] * pk.dtype.itemsize
            + (2 * Hkv * 4 if sk is not None else 0))
        out[f"paged_attn_{mode}_us"] = us
        out[f"paged_attn_{mode}_pool_bytes"] = bytes_per_step
        out[f"paged_attn_{mode}_gb_per_s"] = bytes_per_step / (us / 1e6) \
            / 1e9
    return out


def csv_lines(res):
    return [
        f"kernel_encoded_xla,{res['encoded_xla_us']:.1f},R={res['planes_R']}",
        f"kernel_lut_oracle,{res['lut_oracle_us']:.1f},",
        f"kernel_fp_matmul,{res['fp_matmul_us']:.1f},",
        f"kernel_decode_m4_adaptive,{res['decode_m4_adaptive_us']:.1f},"
        f"bm={res['decode_m4_bm_bucket']}",
        f"kernel_decode_m4_bm128,{res['decode_m4_bm128_us']:.1f},bm=128",
        f"kernel_paged_attn_long_gather,"
        f"{res['paged_attn_long_gather_us']:.1f},"
        f"table={res['paged_attn_table_tokens']}",
        f"kernel_paged_attn_long_blocked,"
        f"{res['paged_attn_long_blocked_us']:.1f},lens=512",
        f"kernel_paged_attn_short_blocked,"
        f"{res['paged_attn_short_blocked_us']:.1f},lens=40",
    ] + [
        f"kernel_paged_attn_{m}_blocked,{res[f'paged_attn_{m}_us']:.1f},"
        f"{res[f'paged_attn_{m}_gb_per_s']:.3f}GB/s"
        for m in ("bf16", "int8", "int4")
    ]
