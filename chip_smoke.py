#!/usr/bin/env python3
"""Bring-up smoke test: the continuous-batching serving path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tensor-parallel path, 2x2 v5e host

Everything runs in this one process, through the library's own entry
points (``get_config``, ``init_model``, ``Engine``,
``prepare_encoded_serving``), on qwen1.5-0.5b at its published widths
(24 layers, d_model 1024, 16 heads of 64, d_ff 2816, vocab 151936) with
random weights from ``--seed``.

One chip:

  kernels  the fused paged-attention kernel (f32, bf16, int8 and int4 pools;
           decode Sq=1 and spec-verify Sq=5) and the encoded-MAC kernel
           against their XLA references, at the model's widths;
  dense    the full 24-layer model serves 8 greedy requests (prompts of
           4-24 tokens, 16 new tokens) through ``Engine`` twice: with the
           gather path (``attention_backend='xla'``) and with the fused
           Mosaic kernel (``'pallas'``).  Tokens must be identical;
  encoded  the paper's encoded MAC at the published widths, depth cut to
           ``ENCODED_LAYERS`` (see ``ENCODED_CUT``).  With searched
           encodings, every folded linear must agree between the Pallas
           kernel and the XLA fold on the same codes, and the 8 requests
           are served through ``Engine`` on the kernel.  With the
           bit-exact AND-plane circuit, one prefill forward per backend
           must agree on top-1 for >= 99% of positions and the two
           engines must emit identical tokens.

``--four-chips`` runs only the tensor-parallel path over a (data=1,
model=4) mesh, each half against its own one-chip run on device 0: the
dense model (forward top-1 agreement >= 99% at f32-rounding max|dlogit|;
served tokens identical except at a near-tie, see ``phase_tp_dense``),
and the exact-circuit encoded bundle with its folded tensors sharded
column/row-parallel (forward top-1 agreement >= 99%, identical served
tokens).

Each phase prints what it measured: wall time of a cold run (compiles
included) and of a warm rerun, peak device memory, tokens.  These are
one cold run on one machine, not a benchmark.  The last line of a passing
run is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
script exits non-zero without that line when JAX finds no TPU, when the
library is not beside it, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
N_REQUESTS, MAX_NEW = 8, 16
ENCODED_LAYERS = 2
ENCODED_CUT = (
    "n_layers 24 -> 2: prepare_encoded_serving stores the folded (U,k,n) "
    "weights as f32 (serve/encoded.py), ~12.6M weight elements per layer "
    "x U<=48 planes x 4 B = up to ~2.4 GB per layer, so 24 layers cannot "
    "fit 16 GB of HBM; 2 layers stay under ~5 GB")
BUNDLE_DIR = os.path.join(ROOT, ".serving_bundles")   # git-ignored
TOP1_MIN = 0.99
NEAR_TIE = 10      # see phase_tp_dense


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def peak_gb(jax):
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def prompts_from_seed(seed, vocab):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(4, 25))).astype(np.int32)
            for _ in range(N_REQUESTS)]


def serve(params, cfg, prompts, phase, mesh=None):
    """Serve ``prompts`` twice on one Engine: a cold round (compiles) and
    a warm one.  Returns the generated tokens of the cold round."""
    import numpy as np
    from repro.serve import Engine
    eng = Engine(params, cfg, n_slots=N_REQUESTS, page_size=16, n_pages=64,
                 max_seq_pages=4, mesh=mesh)
    rounds, walls = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
        res = eng.run()
        walls.append(time.perf_counter() - t0)
        rounds.append([np.asarray(res[r]).tolist() for r in rids])
    st = eng.stats()
    log(phase, f"cold {walls[0]:.2f}s (compiles included), warm "
               f"{walls[1]:.3f}s, jit_compiles={st['jit_compiles']}, "
               f"decode_tokens={st['decode_tokens']}")
    toks = rounds[0]
    if rounds[1] != toks:
        raise AssertionError(f"{phase}: warm rerun changed the tokens")
    if any(len(t) != MAX_NEW or not all(0 <= x < cfg.vocab_size for x in t)
           for t in toks):
        raise AssertionError(f"{phase}: malformed tokens {toks}")
    return toks


def forward_logits(params, cfg, tokens, mesh=None):
    """One prefill forward (no cache) → f32 logits, timed cold."""
    import jax
    from repro.models import apply_model
    from repro.parallel.sharding import param_specs, set_mesh
    fwd = jax.jit(lambda p, t: apply_model(p, cfg, t)[0])
    if mesh is not None:
        params = jax.device_put(params, param_specs(params, mesh))
    t0 = time.perf_counter()
    if mesh is None:
        out = fwd(params, tokens)
    else:
        with set_mesh(mesh):
            out = fwd(params, tokens)
    out = jax.block_until_ready(out)
    return out.astype("float32"), time.perf_counter() - t0


def compare_logits(phase, a, b, what, gate=True):
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise AssertionError(f"{phase}: non-finite logits")
    top1 = float((a.argmax(-1) == b.argmax(-1)).mean())
    delta = float(np.abs(a - b).max())
    log(phase, f"{what}: max|dlogit|={delta:.5g} top1_agreement={top1:.4f} "
               f"over {a.shape[0] * a.shape[1]} positions")
    if gate and top1 < TOP1_MIN:
        raise AssertionError(f"{phase}: top-1 agreement {top1:.4f} < "
                             f"{TOP1_MIN}")
    return delta


def padded_batch(prompts):
    import numpy as np
    import jax.numpy as jnp
    width = max(len(p) for p in prompts)
    out = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        out[i, :len(p)] = p
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(args):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels.ops import encoded_matmul
    from repro.kernels.paged_attention import paged_attn
    from repro.nn.paged import (gather_kv, gather_kv_dequant,
                                paged_attn_decode)
    from repro.quant.kvcache import quantize_kv, stored_layout
    cfg = get_config(ARCH)
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_r
    B, ps, P, L, layer = 8, 16, 4, 2, 1      # stacked pools; layer read
    n_pages = B * P + 1
    rng = np.random.default_rng(args.seed)
    shape = (L, n_pages, ps, Hkv, D)
    dense_k = jnp.asarray(rng.normal(size=shape), jnp.float32)
    dense_v = jnp.asarray(rng.normal(size=shape), jnp.float32)
    pages = jnp.asarray(1 + np.arange(B * P, dtype=np.int32).reshape(B, P))
    kv_map = np.arange(H, dtype=np.int32) // (H // Hkv)
    for pool in ("float32", "bfloat16", "int8", "int4"):
        if pool in ("int8", "int4"):
            pk, sk = stored_layout(*quantize_kv(dense_k, pool))
            pv, sv = stored_layout(*quantize_kv(dense_v, pool))
        else:
            pk = stored_layout(dense_k.astype(pool))
            pv = stored_layout(dense_v.astype(pool))
            sk = sv = None
        for sq in (1, 5):
            q = jnp.asarray(rng.normal(size=(B, sq, H, D)), jnp.float32)
            lens = jnp.asarray(rng.integers(0, P * ps - sq, B), jnp.int32)
            t0 = time.perf_counter()
            out = jax.block_until_ready(paged_attn(
                q, pk, pv, pages, lens, layer=layer, scale=D ** -0.5,
                kv_of_q=kv_map, backend="pallas", scale_k=sk, scale_v=sv))
            dt = time.perf_counter() - t0
            ck = gather_kv(pk, layer, pages, Hkv) if sk is None else \
                gather_kv_dequant(pk, sk, layer, pages)
            cv = gather_kv(pv, layer, pages, Hkv) if sv is None else \
                gather_kv_dequant(pv, sv, layer, pages)
            k_pos = jnp.arange(ck.shape[1])
            ref = paged_attn_decode(
                q, ck, cv, kv_map, scale=D ** -0.5,
                q_pos=lens[:, None] + jnp.arange(sq)[None],
                k_pos=k_pos, k_valid=k_pos[None] < (lens + sq)[:, None])
            err = float(jnp.abs(out - ref).max())
            log("kernels", f"paged_attn pool={pool} Sq={sq}: "
                           f"max|d|={err:.3g} (cold {dt:.2f}s)")
            if not err < 1e-4:
                raise AssertionError(f"paged_attn {pool} Sq={sq}: {err}")
    U, k, n = 48, cfg.d_model, cfg.d_ff
    monos = [sorted({u % 8, (u // 8 + u) % 8}) for u in range(U)]
    x = jnp.asarray(rng.integers(-127, 128, (8, k)), jnp.int8)
    w = jnp.asarray(rng.normal(size=(U, k, n)) * 0.01, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    t0 = time.perf_counter()
    out = jax.block_until_ready(encoded_matmul(x, w, bias, monos,
                                               backend="pallas"))
    dt = time.perf_counter() - t0
    ref = encoded_matmul(x, w, bias, monos, backend="xla")
    rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
    log("kernels", f"encoded_matmul m=8 {k}->{n} U={U}: rel max|d|="
                   f"{rel:.3g} (cold {dt:.2f}s)")
    if not rel < 1e-4:
        raise AssertionError(f"encoded_matmul relative error {rel}")


def phase_dense(args):
    import jax
    from repro.configs import get_config
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_params(cfg, args.seed)
    log("dense", f"{ARCH}: {cfg.n_layers}L d_model={cfg.d_model} "
                 f"{cfg.n_heads}x{cfg.head_dim_r} heads d_ff={cfg.d_ff} "
                 f"vocab={cfg.vocab_size}, {cfg.compute_dtype} "
                 f"(init {time.perf_counter() - t0:.1f}s)")
    prompts = prompts_from_seed(args.seed, cfg.vocab_size)
    out = {}
    for backend in ("xla", "pallas"):
        c = dataclasses.replace(cfg, attention_backend=backend)
        out[backend] = serve(params, c, prompts, f"dense/{backend}")
    log("dense", f"tokens (req0) {out['pallas'][0]}")
    same = sum(int(a == b) for a, b in zip(out["xla"], out["pallas"]))
    log("dense", f"gather path vs fused kernel: {same}/{N_REQUESTS} "
                 f"requests token-identical; peak HBM {peak_gb(jax):.2f} GiB")
    if same != N_REQUESTS:
        raise AssertionError("dense: xla and pallas tokens differ")


def init_params(cfg, seed):
    """Random weights from ``seed``, initialized in one compiled program
    (eager init compiles every op of 24 layers one by one)."""
    import jax
    from repro.models import init_model
    return jax.block_until_ready(
        jax.jit(init_model, static_argnums=1)(jax.random.PRNGKey(seed), cfg))


def encoded_bundle(args, encoding):
    """fp params at the published widths, depth cut → encoded bundle.

    ``searched``: the paper's task-specific encodings, found by a short
    search (the checks below hold the kernel to the XLA fold of the SAME
    bundle, whatever the encodings' accuracy).  ``exact``: the bit-exact
    AND-plane circuit (``launch/serve.py --encoding exact``), whose folded
    weights are integers, so every partial sum is exact in f32 and the
    kernel and the fold must agree bit for bit."""
    from repro.configs import get_config
    from repro.serve import prepare_encoded_serving
    cfg = dataclasses.replace(get_config(ARCH), n_layers=ENCODED_LAYERS)
    params = init_params(cfg, args.seed)
    overrides = None
    if encoding == "exact":
        from repro.core.circuits import exact_product_circuit
        from repro.core.encoding import EncodingSpec
        from repro.core.mac import EncodedMac
        circ, s = exact_product_circuit(cfg.mac.bits, cfg.mac.bits)
        mac = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
        overrides = {n: mac for n in ("wq", "wk", "wv", "wo", "wi", "wg")}
    t0 = time.perf_counter()
    params_enc, cfg_enc, info = prepare_encoded_serving(
        params, cfg, n_samples=32, refine=16, calib_batches=2,
        backend="pallas", cache_dir=BUNDLE_DIR, seed=args.seed,
        macs_override=overrides, verbose=False)
    folded = sum(v.nbytes for k, v in _flat(params_enc) if k.endswith("_fw"))
    fams = {n: (m.program.n_a_planes, round(info["families"][n], 1))
            for n, m in cfg_enc.mac.macs.items()}
    how = "loaded" if info["loaded"] else "built"
    log("encoded", f"{encoding} bundle {how} in "
                   f"{time.perf_counter() - t0:.1f}s: "
                   f"{info['n_folded']} folded linears, "
                   f"{folded / 2 ** 30:.2f} GiB folded f32; "
                   f"family: (U planes, product rmse) {fams}")
    return params_enc, cfg_enc


def _flat(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _flat(v, prefix + k + "/")
        else:
            yield prefix + k, v


def with_backend(cfg_enc, backend):
    return dataclasses.replace(
        cfg_enc, mac=dataclasses.replace(cfg_enc.mac, backend=backend),
        attention_backend="pallas" if backend == "pallas" else "xla")


def check_folded_linears(params_enc, cfg_enc, seed):
    """Every folded linear of the bundle through the Pallas kernel and the
    XLA fold on the same int8 codes, at a decode (m=8) and a prefill
    (m=168) block: they differ only in f32 accumulation order."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.ops import encoded_matmul
    rng = np.random.default_rng(seed)
    worst = 0.0
    for path, fw in _flat(params_enc):
        if not path.endswith("_fw"):
            continue
        node = params_enc
        for key in path.split("/")[:-1]:
            node = node[key]
        name = path.split("/")[-1][:-3]
        monos = cfg_enc.mac.mac_for(name).program.a_mono_tuples
        for layer in range(fw.shape[0]):
            w, b = fw[layer], node[name + "_fb"][layer]
            for m in (8, 168):
                x = jnp.asarray(rng.integers(-127, 128, (m, w.shape[1])),
                                jnp.int8)
                out = encoded_matmul(x, w, b, monos, backend="pallas")
                ref = encoded_matmul(x, w, b, monos, backend="xla")
                rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
                worst = max(worst, rel)
                if not rel < 1e-5:
                    raise AssertionError(f"{path}[{layer}] m={m}: kernel vs "
                                         f"fold relative error {rel:.3g}")
    log("encoded", f"every folded linear, kernel vs XLA fold on the same "
                   f"codes: worst relative max|d| {worst:.3g}")


def phase_encoded(args):
    import jax
    from repro.configs import get_config
    prompts = prompts_from_seed(args.seed, get_config(ARCH).vocab_size)
    tokens = padded_batch(prompts)
    log("encoded", f"cut: {ENCODED_CUT}")
    # searched encodings (the paper's MAC): kernel checked linear by
    # linear; the forward comparison is printed, not gated — these
    # encodings' large product error turns accumulation-order noise into
    # int8 code flips (the same forward disagrees as much on the CPU)
    params_enc, cfg_enc = encoded_bundle(args, "searched")
    check_folded_linears(params_enc, cfg_enc, args.seed)
    lg = {b: forward_logits(params_enc, with_backend(cfg_enc, b), tokens)[0]
          for b in ("pallas", "xla")}
    compare_logits("encoded", lg["pallas"], lg["xla"],
                   "searched: pallas kernel vs XLA fold (not gated)",
                   gate=False)
    toks = serve(params_enc, with_backend(cfg_enc, "pallas"), prompts,
                 "encoded/searched/pallas")
    log("encoded", f"searched tokens (req0) {toks[0]}")
    log("encoded", f"peak HBM {peak_gb(jax):.2f} GiB")
    del params_enc, lg
    # exact circuit: end-to-end agreement is gated
    params_enc, cfg_enc = encoded_bundle(args, "exact")
    lg = {}
    for b in ("pallas", "xla"):
        lg[b], dt = forward_logits(params_enc, with_backend(cfg_enc, b),
                                   tokens)
        log("encoded", f"exact: prefill forward backend={b} "
                       f"{tuple(tokens.shape)}: cold {dt:.2f}s")
    compare_logits("encoded", lg["pallas"], lg["xla"],
                   "exact: pallas kernel vs XLA fold")
    out = {b: serve(params_enc, with_backend(cfg_enc, b), prompts,
                    f"encoded/exact/{b}") for b in ("pallas", "xla")}
    same = sum(int(a == b) for a, b in zip(out["pallas"], out["xla"]))
    log("encoded", f"exact tokens (req0) {out['pallas'][0]}; pallas vs "
                   f"xla engine: {same}/{N_REQUESTS} requests identical")
    if same != N_REQUESTS:
        raise AssertionError("encoded: pallas and xla engine tokens differ")


def phase_tp_dense(args, mesh):
    """The row-parallel psums of the f32 model reorder its sums.  At the
    TPU's default precision an f32 matmul rounds its operands to bf16, so
    a reordered sum that crosses a bf16 rounding boundary moves the next
    matmul's operand by a whole bf16 step, and the mesh and one chip
    drift apart at bf16 level.  Both runs here therefore use full f32
    matmuls, which leaves f32 rounding of the reordered sums: the forward
    must agree on top-1 with a max|dlogit| that small, and a served token
    may differ only where the one-chip logits of the two candidates lie
    within ``NEAR_TIE`` times that max|dlogit| (a near-tie the rounding
    can flip).  A sharding fault moves logits by far more."""
    import jax
    with jax.default_matmul_precision("highest"):
        _tp_dense(args, mesh)


def _tp_dense(args, mesh):
    import numpy as np
    import jax.numpy as jnp
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(ARCH), attention_backend="pallas")
    params = init_params(cfg, args.seed)
    prompts = prompts_from_seed(args.seed, cfg.vocab_size)
    one, _ = forward_logits(params, cfg, padded_batch(prompts))
    tp, _ = forward_logits(params, cfg, padded_batch(prompts), mesh=mesh)
    delta = compare_logits("tp-dense", tp, one, "model=4 mesh vs one chip")
    scale = float(np.abs(np.asarray(one)).max())
    log("tp-dense", f"logit scale max|logit| = {scale:.4g}")
    if not delta <= 1e-3 * scale:
        raise AssertionError(f"tp-dense: max|dlogit| {delta:.3g} is not "
                             f"f32 rounding at logit scale {scale:.3g}")
    toks1 = serve(params, cfg, prompts, "tp-dense/1chip")
    toks4 = serve(params, cfg, prompts, "tp-dense/mesh", mesh=mesh)
    log("tp-dense", f"tokens (req0) {toks4[0]}")
    same = sum(int(a == b) for a, b in zip(toks1, toks4))
    log("tp-dense", f"model=4 mesh vs one chip: {same}/{N_REQUESTS} "
                    "requests token-identical")
    for r, (a, b) in enumerate(zip(toks1, toks4)):
        if a == b:
            continue
        i = next(j for j, (x, y) in enumerate(zip(a, b)) if x != y)
        seq = jnp.asarray(np.concatenate([prompts[r], a[:i]]), jnp.int32)
        lg = np.asarray(forward_logits(params, cfg, seq[None])[0][0, -1])
        margin = abs(float(lg[a[i]] - lg[b[i]]))
        log("tp-dense", f"request {r} first differs at new token {i}: one "
                        f"chip {a[i]}, mesh {b[i]}; their one-chip logit "
                        f"margin {margin:.3g} (near-tie bound "
                        f"{NEAR_TIE * delta:.3g})")
        if not margin <= NEAR_TIE * delta:
            raise AssertionError(f"tp-dense: request {r} differs at a "
                                 f"margin {margin:.3g}, not a near-tie")


def phase_tp_encoded(args, mesh):
    """Exact-circuit bundle (see ``encoded_bundle``): the row-parallel
    psums of integer partial sums are exact, so the mesh must agree with
    the one-chip run."""
    params_enc, cfg_enc = encoded_bundle(args, "exact")
    cfg = with_backend(cfg_enc, "pallas")
    prompts = prompts_from_seed(args.seed, cfg.vocab_size)
    tokens = padded_batch(prompts)
    one, dt1 = forward_logits(params_enc, cfg, tokens)
    tp, dt4 = forward_logits(params_enc, cfg, tokens, mesh=mesh)
    log("tp-encoded", f"prefill forward cold: one chip {dt1:.2f}s, "
                      f"model=4 mesh {dt4:.2f}s")
    compare_logits("tp-encoded", tp, one, "model=4 mesh vs one chip")
    toks1 = serve(params_enc, cfg, prompts, "tp-encoded/1chip")
    toks4 = serve(params_enc, cfg, prompts, "tp-encoded/mesh", mesh=mesh)
    same = sum(int(a == b) for a, b in zip(toks1, toks4))
    log("tp-encoded", f"tokens (req0) {toks4[0]}; model=4 mesh vs one "
                      f"chip: {same}/{N_REQUESTS} requests identical")
    if same != N_REQUESTS:
        raise AssertionError("tp-encoded: mesh and one-chip tokens differ")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: the repro package is not under {ROOT}/src",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.env import use_compile_cache
    cache = use_compile_cache()
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    devs = jax.devices()
    dev = devs[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache}", flush=True)

    if args.four_chips:
        if len(devs) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(devs)}", file=sys.stderr)
            return 1
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(1, 4, devices=devs[:4])
        phases = [("tp-dense", lambda: phase_tp_dense(args, mesh)),
                  ("tp-encoded", lambda: phase_tp_encoded(args, mesh))]
    else:
        phases = [("kernels", lambda: phase_kernels(args)),
                  ("dense", lambda: phase_dense(args)),
                  ("encoded", lambda: phase_encoded(args))]

    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            log(name, f"PASS in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            log(name, f"FAIL after {time.perf_counter() - t0:.1f}s")
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    count = 4 if args.four_chips else len(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
