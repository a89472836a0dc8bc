"""Serving launcher: batched requests against a (reduced) model, optionally
with the paper's encoded-MAC inference mode.

  # static batch (dense KV cache):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 8

  # continuous batching (paged KV cache + scheduler):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --continuous --slots 4 --page-size 16 --n-pages 256 --requests 16

  # + prefix caching and chunked prefill (DESIGN.md §7): shared prompt
  # prefixes are served from already-resident pages, long prompts prefill
  # in fixed chunks interleaved with decode:
  PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
      --prefix-cache --prefill-chunk 32

  # calibrated encoded-MAC serving (calibrate → search → fold → serve; the
  # fitted encodings + folded weights are cached under
  # src/repro/core/artifacts/serving/ so later starts are one load):
  PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
      --mac encoded

  # speculative decoding (DESIGN.md §10): draft 4 tokens/slot/round with
  # a lower-m-bits encoded drafter, verify in one batched dense forward
  # (greedy output token-identical to non-speculative serving):
  PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
      --spec-decode 4 --draft encoded --draft-m-bits 24

  # tensor-parallel encoded serving over the model axis (DESIGN.md §6;
  # folded bitplane tensors shard col/row-parallel, per-device bytes ÷ TP):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --reduced --continuous \
      --mac encoded --mesh 8

``--mac encoded`` routes every calibrated projection through
kernels/ops.encoded_matmul with per-projection-family encodings and
pre-folded (U, k, n) bitplane weights (DESIGN.md §3, docs/encoding.md).
``--mac int8`` keeps the fake-quant QAT simulation; ``--encoding exact``
swaps the searched encodings for the bit-exact AND-plane circuit (debug /
agreement demos).
"""
from __future__ import annotations

import argparse
import dataclasses
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mac", "--mac-mode", dest="mac", default="fp",
                    choices=["fp", "int8", "encoded"],
                    help="MAC mode (encoded = calibrated encoded-MAC "
                         "serving with pre-folded weights)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--mesh", default=None,
                    help="tensor-parallel serving (DESIGN.md §6): 'M' "
                         "shards the model axis over M devices, 'DxM' adds "
                         "a data axis (e.g. --mesh 8 or --mesh 2x4); "
                         "encoded folded tensors shard col/row-parallel")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=256)
    ap.add_argument("--reserve", default="conservative",
                    choices=["conservative", "optimistic"])
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix caching (DESIGN.md §7): reuse pool pages "
                         "holding full prompt pages already prefilled by "
                         "earlier requests; only the uncached suffix is "
                         "prefilled")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="prefill chunk size: prompts are prefilled in "
                         "fixed chunks interleaved with decode steps, so "
                         "long prompts never stall running slots")
    # observability (DESIGN.md §9) — continuous engine only
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request-lifecycle trace here: Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing), or JSONL when PATH ends in "
                         ".jsonl; tracing is off without this flag")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the full metrics-registry snapshot "
                         "(counters/gauges/histograms) as JSON")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="wrap the serving run in a jax.profiler trace "
                         "(TensorBoard/XPlane dump with per-op device "
                         "time and the engine's serve.* host spans on "
                         "the same clock)")
    ap.add_argument("--drift-every", type=int, default=0, metavar="N",
                    help="with --mac encoded: sample dense-vs-encoded "
                         "top-1 logit agreement online every N engine "
                         "steps and publish it as a gauge (0 = off)")
    ap.add_argument("--paged-attn", default="xla",
                    choices=["xla", "auto", "pallas"],
                    help="paged decode attention (DESIGN.md §8): 'xla' = "
                         "gathered-page-view reference; 'pallas' = fused "
                         "flash-decoding kernel reading K/V page-by-page "
                         "through the page table with per-row lens "
                         "early-exit, compiled by Mosaic (TPU only); "
                         "'auto' = that kernel on TPU, its blocked XLA "
                         "lowering elsewhere")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["bf16", "int8", "int4"],
                    help="paged KV-cache storage (DESIGN.md §11): 'bf16' "
                         "= dense pages in the compute dtype; 'int8'/"
                         "'int4' store pages quantized with per-token "
                         "per-head scale rows in side pools and "
                         "dequantize inside the paged-attention page "
                         "loop — 2-4x fewer pool bytes per token, so "
                         "more slots / longer contexts at equal HBM")
    # speculative decoding (DESIGN.md §10) — continuous engine only
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding: draft K tokens per slot "
                         "per round with the drafter, verify all K+1 "
                         "positions in one batched dense forward, commit "
                         "the longest agreeing prefix + bonus token "
                         "(greedy output token-identical to K=0); 0 = off")
    ap.add_argument("--draft", default="self",
                    choices=["self", "encoded"],
                    help="drafter for --spec-decode: 'self' = the "
                         "verifier's own params (speedup from dispatch "
                         "amortization alone), 'encoded' = a lower-m-bits "
                         "encoded bundle built by prepare_drafter (the "
                         "paper's accuracy knob as the draft model)")
    ap.add_argument("--draft-m-bits", type=int, default=24,
                    help="encoding width M for --draft encoded (coarser "
                         "than the verifier's --m-bits → cheaper drafts, "
                         "lower acceptance)")
    # encoded-serving knobs (ignored unless --mac encoded)
    ap.add_argument("--encoding", default="search",
                    choices=["search", "exact"],
                    help="search = task-specific per-family search (paper); "
                         "exact = bit-exact AND-plane circuit (debug)")
    ap.add_argument("--encoded-backend", default="auto",
                    choices=["auto", "xla", "pallas", "pallas_interpret"])
    ap.add_argument("--m-bits", type=int, default=48,
                    help="encoding output width M per family")
    ap.add_argument("--calib-samples", type=int, default=128,
                    help="random-search samples per family")
    ap.add_argument("--calib-refine", type=int, default=64,
                    help="anneal refinement iters per family")
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--force-calib", action="store_true",
                    help="rebuild the artifact bundle even if cached")
    ap.add_argument("--debug-nan", action="store_true",
                    help="raise on the first NaN any dispatch produces "
                         "(debug-only: forces per-op sync)")
    ap.add_argument("--sanitize", action="store_true",
                    help="attach the allocator shadow ledger (validates "
                         "every page transition + per-step conservation; "
                         "REPRO_SANITIZE=1 does the same)")
    args = ap.parse_args()

    from repro.launch.env import use_compile_cache
    use_compile_cache()
    if args.debug_nan:
        from repro.launch.env import set_debug_nan
        set_debug_nan(True)

    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core.layers import MacConfig
    from repro.models import init_model
    from repro.serve import Engine, ServeEngine, prepare_encoded_serving

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_test_mesh
        import re
        m = re.fullmatch(r"(?:(\d+)x)?(\d+)", args.mesh)
        if m is None:
            ap.error(f"--mesh {args.mesh!r}: expected 'M' or 'DxM' "
                     "(e.g. --mesh 8 or --mesh 2x4)")
        n_data, n_model = int(m.group(1) or 1), int(m.group(2))
        if n_data * n_model > jax.device_count():
            ap.error(f"--mesh {args.mesh} needs {n_data * n_model} devices, "
                     f"have {jax.device_count()} (hint: "
                     "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        mesh = make_test_mesh(n_data, n_model)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    elif args.mac == "encoded" and jax.default_backend() == "cpu":
        # folded bitplane weights are U× the dense weight bytes: at its
        # published size the model does not fit a CPU host's memory
        ap.error(f"--mac encoded serves {args.arch} at its published size "
                 "only on an accelerator; pass --reduced on the CPU")
    if args.mac == "int8":
        cfg = dataclasses.replace(cfg, mac=MacConfig(mode="int8"))
    if args.paged_attn != "xla":
        cfg = dataclasses.replace(cfg, attention_backend=args.paged_attn)
    if args.kv_dtype != "bf16":
        if not args.continuous:
            ap.error("--kv-dtype quantizes the PAGED cache; it requires "
                     "--continuous (the static engine's dense cache is "
                     "unaffected)")
        cfg = dataclasses.replace(cfg, kv_cache_dtype=args.kv_dtype)
    params = init_model(jax.random.PRNGKey(0), cfg)

    params_ref, cfg_ref = params, cfg   # dense reference for --drift-every
    if args.mac == "encoded":
        overrides = None
        if args.encoding == "exact":
            from repro.core.circuits import exact_product_circuit
            from repro.core.encoding import EncodingSpec
            from repro.core.mac import EncodedMac
            circ, s = exact_product_circuit(cfg.mac.bits, cfg.mac.bits)
            mac = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
            overrides = {n: mac for n in ("wq", "wk", "wv", "wo",
                                          "wi", "wg", "w")}
        t0 = time.time()
        params, cfg, info = prepare_encoded_serving(
            params, cfg, m_bits=args.m_bits, n_samples=args.calib_samples,
            refine=args.calib_refine, calib_batches=args.calib_batches,
            backend=args.encoded_backend, macs_override=overrides,
            force=args.force_calib)
        print(f"[encoded-serving] ready in {time.time() - t0:.1f}s "
              f"({'cache hit' if info['loaded'] else 'searched+folded'})")

    if args.spec_decode and not args.continuous:
        ap.error("--spec-decode requires --continuous (the draft/verify "
                 "rounds run against the paged KV cache)")
    draft_params = draft_cfg = None
    if args.spec_decode and args.draft == "encoded":
        from repro.serve import prepare_drafter
        verifier = (params, cfg) if args.mac == "encoded" else None
        t0 = time.time()
        draft_params, draft_cfg, dinfo = prepare_drafter(
            params_ref, cfg_ref, m_bits=args.draft_m_bits,
            verifier=verifier, n_samples=args.calib_samples,
            refine=args.calib_refine, calib_batches=args.calib_batches,
            backend=args.encoded_backend, force=args.force_calib)
        src = ("verifier artifacts" if dinfo.get("shared_with_verifier")
               else "searched+folded" if not dinfo.get("loaded")
               else "cache hit")
        print(f"[spec-decode] encoded drafter m_bits={args.draft_m_bits} "
              f"ready in {time.time() - t0:.1f}s ({src})")

    rng = np.random.default_rng(0)
    reqs = [rng.integers(0, cfg.vocab_size, rng.integers(4, 24))
            for _ in range(args.requests)]

    if args.continuous:
        from repro.obs import DriftMonitor
        from repro.serve.telemetry import ServeTelemetry
        drift = None
        if args.drift_every > 0:
            drift = DriftMonitor(params_ref, cfg_ref,
                                 every=args.drift_every)
        tel = ServeTelemetry(trace=bool(args.trace_out), drift=drift,
                             profile_dir=args.profile_dir)
        engine = Engine(params, cfg, n_slots=args.slots,
                        page_size=args.page_size, n_pages=args.n_pages,
                        reserve=args.reserve, mesh=mesh,
                        prefix_cache=args.prefix_cache,
                        prefill_chunk=args.prefill_chunk,
                        telemetry=tel, spec_decode=args.spec_decode,
                        draft_params=draft_params, draft_cfg=draft_cfg,
                        sanitize=args.sanitize or None)
        t0 = time.time()
        rids = [engine.submit(r, max_new=args.max_new) for r in reqs]
        outs = engine.run()
        dt = time.time() - t0
        st = engine.stats()
        total = st["decode_tokens"]
        print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
              f"({total / dt:.1f} tok/s, mac={args.mac}, "
              f"paged-attn={args.paged_attn}, continuous)")
        print(f"  occupancy={st['occupancy']:.2f} "
              f"evictions={st['evictions']} "
              f"jit_compiles={st['jit_compiles']} "
              f"p50={st['latency_p50_s']:.3f}s p99={st['latency_p99_s']:.3f}s "
              f"kv_pool={st['kv_pool_bytes'] / 1e6:.1f}MB")
        print(f"  kv: dtype={st['kv_cache_dtype']} "
              f"{st['kv_bytes_per_token']:.1f} B/token, "
              f"capacity={st['kv_capacity_tokens']} tokens")
        if args.prefix_cache:
            print(f"  prefix: hit_rate={st['prefix_hit_rate']:.2f} "
                  f"({st['prefix_hit_tokens']}/{st['prefix_lookup_tokens']} "
                  f"tokens, {st['prefix_pages_indexed']} pages indexed, "
                  f"{st['prefill_chunks']} prefill chunks of "
                  f"{st['prefill_chunk']})")
        if args.spec_decode:
            print(f"  spec: k={st['spec_decode_k']} "
                  f"acceptance={st['spec_acceptance_rate']:.3f} "
                  f"tokens/round={st['spec_tokens_per_round']:.2f} "
                  f"({st['spec_accepted_tokens']}/"
                  f"{st['spec_draft_tokens']} drafts accepted over "
                  f"{st['spec_rounds']} rounds, "
                  f"drafter={st['draft_mac_mode']})")
        if "ttft_p50_s" in st:
            print(f"  ttft_p50={st['ttft_p50_s']:.3f}s "
                  f"tpot_p50={st.get('tpot_p50_s', float('nan')):.4f}s "
                  f"step_p50={st['step_ms_p50']:.2f}ms")
        if drift is not None and drift.last is not None:
            print(f"  drift: top1_agreement={drift.last:.4f} "
                  f"abs_logit_delta={drift.last_delta:.4f}")
        jsonl = args.trace_out and args.trace_out.endswith(".jsonl")
        tel.write(trace_out=None if jsonl else args.trace_out,
                  trace_jsonl=args.trace_out if jsonl else None,
                  metrics_out=args.metrics_out)
        for p in (args.trace_out, args.metrics_out):
            if p:
                print(f"  wrote {p}")
        for i, rid in enumerate(rids[:3]):
            print(f"req{i}: {list(map(int, outs[rid][:10]))} ...")
        return

    engine = ServeEngine(params, cfg, batch_slots=args.slots, max_len=128,
                         mesh=mesh)
    t0 = time.time()
    outs = engine.run(reqs, max_new=args.max_new)
    dt = time.time() - t0
    total = sum(args.max_new for _ in reqs)
    print(f"served {len(reqs)} requests, {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, mac={args.mac}, static)")
    for i, o in enumerate(outs[:3]):
        print(f"req{i}: {list(map(int, o[:10]))} ...")


if __name__ == "__main__":
    main()
