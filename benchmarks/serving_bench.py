"""Serving benchmark: replay a Poisson arrival trace (mixed prompt lengths,
mixed max_new) against (a) the continuous-batching paged-KV ``Engine`` and
(b) the static-batch ``generate()`` baseline at an equal KV page budget.

Records aggregate tokens/s, p50/p99 request latency, occupancy, and checks
that paged greedy decode stays token-identical to the dense path.

``--trace shared-prefix`` replays a Poisson trace whose prompts share a
long common prefix (system-prompt traffic) through the engine with the
prefix cache + chunked prefill enabled vs the cold engine at an equal page
budget, reporting the prefix hit-rate, TTFT p50 for both paths, and the
greedy token-identity check (``--smoke`` shrinks it for CI):

  PYTHONPATH=src python benchmarks/serving_bench.py --trace shared-prefix

``--mac encoded`` (or ``run_encoded()``) adds the accuracy-vs-throughput
mode: the same trace replayed through the continuous engine with dense fp
matmuls and with the calibrated encoded-MAC path (pre-folded bitplane
weights, repro.serve.encoded) at an EQUAL page budget, reporting tokens/s,
p99 latency, and top-1 logit agreement vs the dense path in one command:

  PYTHONPATH=src python benchmarks/serving_bench.py --mac encoded

``--trace spec-decode`` (``run_spec_decode()``) benchmarks speculative
decoding (DESIGN.md §10): tokens/s and acceptance rate vs draft length k
for the self-drafter and a lower-m-bits encoded drafter, with greedy
token identity vs the non-speculative engine checked in every row:

  PYTHONPATH=src python benchmarks/serving_bench.py --trace spec-decode
"""
import argparse
import time

import numpy as np


N_REQ = 10
N_SLOTS = 4
PAGE_SIZE = 8
MAX_PROMPT = 24
ARRIVAL_RATE = 4.0          # requests/s (Poisson)
SEED = 0


def _trace(cfg, rng):
    """(prompt, max_new, arrival_s) triples with exponential gaps."""
    reqs = []
    t = 0.0
    for _ in range(N_REQ):
        plen = int(rng.integers(4, MAX_PROMPT + 1))
        max_new = int(rng.integers(8, 17))
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        t += rng.exponential(1.0 / ARRIVAL_RATE)
        reqs.append((prompt, max_new, t))
    return reqs


def _shared_prefix_trace(cfg, rng, n_req, prefix_len, suffix_max):
    """Poisson trace where every prompt opens with one shared prefix
    (system prompt / few-shot template) followed by a unique suffix."""
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    reqs = []
    t = 0.0
    for _ in range(n_req):
        slen = int(rng.integers(2, suffix_max + 1))
        suffix = rng.integers(0, cfg.vocab_size, slen).astype(np.int32)
        max_new = int(rng.integers(6, 13))
        t += rng.exponential(1.0 / ARRIVAL_RATE)
        reqs.append((np.concatenate([prefix, suffix]), max_new, t))
    return reqs


def _run_continuous(params, cfg, trace, n_pages, *, timed=True, **eng_kw):
    from repro.serve import Engine
    eng = Engine(params, cfg, n_slots=N_SLOTS, page_size=PAGE_SIZE,
                 n_pages=n_pages, **eng_kw)
    t0 = time.perf_counter()
    pending = list(trace)
    rids = []
    while pending or eng.busy:
        now = time.perf_counter() - t0
        while pending and (not timed or pending[0][2] <= now):
            prompt, max_new, _ = pending.pop(0)
            rids.append(eng.submit(prompt, max_new=max_new))
        if eng.busy:
            eng.step()
        elif pending:
            time.sleep(min(0.002, pending[0][2] - now))
    wall = time.perf_counter() - t0
    return eng, rids, wall


def _run_static(params, cfg, trace, *, timed=True):
    """Chunks of N_SLOTS in arrival order; a chunk starts only when its last
    member has arrived and the previous chunk finished (head-of-line), and
    decodes to the chunk max of max_new (slot waste)."""
    from repro.serve import generate
    import jax.numpy as jnp
    t0 = time.perf_counter()
    outs, lats = [], []
    for i in range(0, len(trace), N_SLOTS):
        chunk = trace[i:i + N_SLOTS]
        t_ready = max(t for _, _, t in chunk)
        if timed:
            while time.perf_counter() - t0 < t_ready:
                time.sleep(0.001)
        S = max(len(p) for p, _, _ in chunk)
        batch = np.zeros((len(chunk), S), np.int32)
        for j, (p, _, _) in enumerate(chunk):
            batch[j, S - len(p):] = p                       # left-pad
        mn = max(m for _, m, _ in chunk)
        toks = np.asarray(generate(params, cfg, jnp.asarray(batch),
                                   max_new=mn, max_len=S + mn + 8))
        t_done = time.perf_counter() - t0
        for j, (_, m, t_arr) in enumerate(chunk):
            outs.append(toks[j, :m])                        # truncate to own
            lats.append(t_done - t_arr)
    wall = time.perf_counter() - t0
    return outs, lats, wall


def _pct(xs, q):
    """q in [0, 1] — thin wrapper over the repo-wide percentile helper
    (repro.obs.stats, linear interpolation, matches numpy.percentile)."""
    from repro.obs import percentile
    return percentile(xs, 100.0 * q)


def run():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import generate

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(SEED)
    trace = _trace(cfg, rng)
    total_tokens = sum(m for _, m, _ in trace)

    # equal page budget: pool tokens == the static path's worst-case dense
    # cache tokens (N_SLOTS sequences of max_prompt + max_new + pad)
    budget_tokens = N_SLOTS * (MAX_PROMPT + 16 + 8)
    n_pages = budget_tokens // PAGE_SIZE + 1                # +1 scratch

    # warmup replays (absorb jit compiles for both paths)
    _run_continuous(params, cfg, trace, n_pages, timed=False)
    _run_static(params, cfg, trace, timed=False)

    eng, rids, wall_c = _run_continuous(params, cfg, trace, n_pages)
    st = eng.stats()
    res = eng.results()
    outs_s, lats_s, wall_s = _run_static(params, cfg, trace)

    # acceptance: paged greedy decode token-identical to the dense path
    identical = True
    for rid, (prompt, max_new, _) in zip(rids, trace):
        ref = np.asarray(generate(params, cfg, jnp.asarray(prompt)[None],
                                  max_new=max_new))[0]
        identical &= res[rid].tolist() == ref.tolist()

    cont_lat = [(r.t_finish - r.t_arrive) for r in eng.requests.values()]
    out = {
        "trace": {"n_requests": N_REQ, "arrival_rate_hz": ARRIVAL_RATE,
                  "total_tokens": total_tokens, "page_size": PAGE_SIZE,
                  "n_pages": n_pages, "n_slots": N_SLOTS},
        "continuous": {
            "tokens_per_s": total_tokens / wall_c,
            "wall_s": wall_c,
            "latency_p50_s": _pct(cont_lat, 0.50),
            "latency_p99_s": _pct(cont_lat, 0.99),
            "occupancy": st["occupancy"],
            "evictions": st["evictions"],
            "kv_pool_bytes": st["kv_pool_bytes"],
        },
        "static": {
            "tokens_per_s": total_tokens / wall_s,
            "wall_s": wall_s,
            "latency_p50_s": _pct(lats_s, 0.50),
            "latency_p99_s": _pct(lats_s, 0.99),
        },
        "speedup_tokens_per_s": wall_s / wall_c,
        "token_identical_to_dense": bool(identical),
    }
    return out


def csv_lines(res):
    c, s = res["continuous"], res["static"]
    return [
        f"serving_continuous_tok_s,0,{c['tokens_per_s']:.2f}",
        f"serving_static_tok_s,0,{s['tokens_per_s']:.2f}",
        f"serving_speedup,0,{res['speedup_tokens_per_s']:.3f}",
        f"serving_p99_continuous_s,0,{c['latency_p99_s']:.3f}",
        f"serving_p99_static_s,0,{s['latency_p99_s']:.3f}",
        f"serving_token_identical,0,{int(res['token_identical_to_dense'])}",
    ]


# ---------------------------------------------------------------------------
# prefix caching + chunked prefill: warm vs cold engine on shared prefixes
# ---------------------------------------------------------------------------

def run_prefix(smoke: bool = False, prefill_chunk: int = 8):
    """Shared-prefix Poisson trace through the prefix-cached engine (warm)
    vs the same engine without the cache (cold) at an equal page budget:
    prefix hit-rate, TTFT p50/p99, and greedy token identity."""
    import jax
    from repro.configs import get_config
    from repro.models import init_model

    n_req = 6 if smoke else 16
    prefix_len = 24 if smoke else 48
    suffix_max = 6 if smoke else 12

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(SEED)
    trace = _shared_prefix_trace(cfg, rng, n_req, prefix_len, suffix_max)
    total_tokens = sum(m for _, m, _ in trace)
    budget_tokens = N_SLOTS * (prefix_len + suffix_max + 16 + 8)
    n_pages = budget_tokens // PAGE_SIZE + 1                # +1 scratch
    warm_kw = dict(prefix_cache=True, prefill_chunk=prefill_chunk)
    cold_kw = dict(prefix_cache=False, prefill_chunk=prefill_chunk)

    # warmup replays (absorb jit compiles for both engines)
    _run_continuous(params, cfg, trace, n_pages, timed=False, **cold_kw)
    _run_continuous(params, cfg, trace, n_pages, timed=False, **warm_kw)

    eng_c, rids_c, wall_c = _run_continuous(params, cfg, trace, n_pages,
                                            **cold_kw)
    eng_w, rids_w, wall_w = _run_continuous(params, cfg, trace, n_pages,
                                            **warm_kw)
    st_c, st_w = eng_c.stats(), eng_w.stats()

    # greedy outputs must be token-identical with and without the cache
    res_c, res_w = eng_c.results(), eng_w.results()
    identical = all(res_w[rw].tolist() == res_c[rc].tolist()
                    for rw, rc in zip(rids_w, rids_c))

    def _ttft(eng):
        return sorted((r.t_first - r.t_arrive) for r in eng.requests.values()
                      if r.t_first is not None)

    ttft_c, ttft_w = _ttft(eng_c), _ttft(eng_w)
    return {
        "trace": {"n_requests": n_req, "arrival_rate_hz": ARRIVAL_RATE,
                  "prefix_len": prefix_len, "suffix_max": suffix_max,
                  "total_tokens": total_tokens, "page_size": PAGE_SIZE,
                  "n_pages": n_pages, "n_slots": N_SLOTS,
                  "prefill_chunk": prefill_chunk},
        "cold": {
            "tokens_per_s": total_tokens / wall_c,
            "wall_s": wall_c,
            "ttft_p50_s": _pct(ttft_c, 0.50),
            "ttft_p99_s": _pct(ttft_c, 0.99),
            "prefill_tokens": st_c["prefill_tokens"],
            "prefill_chunks": st_c["prefill_chunks"],
        },
        "warm": {
            "tokens_per_s": total_tokens / wall_w,
            "wall_s": wall_w,
            "ttft_p50_s": _pct(ttft_w, 0.50),
            "ttft_p99_s": _pct(ttft_w, 0.99),
            "prefill_tokens": st_w["prefill_tokens"],
            "prefill_chunks": st_w["prefill_chunks"],
            "prefix_hit_rate": st_w["prefix_hit_rate"],
            "prefix_hit_tokens": st_w["prefix_hit_tokens"],
            "prefix_pages_indexed": st_w["prefix_pages_indexed"],
        },
        "ttft_p50_speedup": (_pct(ttft_c, 0.50) / _pct(ttft_w, 0.50)
                             if ttft_w and _pct(ttft_w, 0.50) > 0
                             else float("nan")),
        "prefill_tokens_saved": st_c["prefill_tokens"]
        - st_w["prefill_tokens"],
        "token_identical_warm_vs_cold": bool(identical),
    }


def csv_lines_prefix(res):
    c, w = res["cold"], res["warm"]
    return [
        f"serving_prefix_hit_rate,0,{w['prefix_hit_rate']:.3f}",
        f"serving_ttft_p50_cold_s,0,{c['ttft_p50_s']:.4f}",
        f"serving_ttft_p50_warm_s,0,{w['ttft_p50_s']:.4f}",
        f"serving_ttft_p50_speedup,0,{res['ttft_p50_speedup']:.3f}",
        f"serving_prefill_tokens_saved,0,{res['prefill_tokens_saved']}",
        f"serving_prefix_token_identical,0,"
        f"{int(res['token_identical_warm_vs_cold'])}",
    ]


# ---------------------------------------------------------------------------
# paged-attention decode: fused page-walk kernel vs the gathered-view path
# ---------------------------------------------------------------------------

def run_paged_attn(smoke: bool = False):
    """Per-decode-step latency and tokens/s of the fused paged-attention
    path (``attention_backend='pallas'`` — the Pallas kernel on TPU, its
    blocked XLA lowering elsewhere; DESIGN.md §8) vs the gathered-view
    reference at one table width, for long-context rows (≥ 512 cached
    tokens) and short rows (block skipping: work follows ``lens``, not
    the table width).  Also replays a small real trace through the engine
    with both backends and checks greedy token identity."""
    import jax
    import jax.numpy as jnp
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_model, init_paged_cache
    from repro.serve.engine import make_paged_decode_step

    page_size = 16
    table_pages = 64 if smoke else 128       # 1024 / 2048-token table width
    n_slots = 4
    long_lens = 512                          # acceptance floor: ≥512 cached
    short_lens = 40
    n_iters = 10 if smoke else 30

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    layers = init_paged_cache(cfg, table_pages + 1, page_size)["layers"]
    key = jax.random.PRNGKey(1)
    layers = jax.tree_util.tree_map(
        lambda a: jax.random.normal(key, a.shape, a.dtype) * 0.1, layers)
    # every slot reads the same page chain — latency only depends on the
    # table geometry and lens, and the pool stays tiny
    pages = jnp.broadcast_to(
        jnp.arange(1, table_pages + 1, dtype=jnp.int32)[None],
        (n_slots, table_pages))
    toks = jnp.ones((n_slots, 1), jnp.int32)
    # "pallas" rows run the platform's fused lowering ("auto"): the
    # Mosaic kernel on TPU, its blocked XLA lowering elsewhere
    fused = {"xla": "xla", "pallas": "auto"}
    steps = {b: jax.jit(make_paged_decode_step(
        dataclasses.replace(cfg, attention_backend=fused[b])))
        for b in ("xla", "pallas")}

    def step_ms(backend, ln):
        step = steps[backend]
        lens = jnp.full((n_slots,), ln, jnp.int32)
        for _ in range(3):
            out = step(params, layers, toks, pages, lens)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(n_iters):
            out = step(params, layers, toks, pages, lens)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n_iters * 1e3

    rows = {}
    for name, ln in (("long", long_lens), ("short", short_lens)):
        xla_ms = step_ms("xla", ln)
        pal_ms = step_ms("pallas", ln)
        rows[name] = {
            "cached_tokens": ln,
            "xla_step_ms": xla_ms,
            "pallas_step_ms": pal_ms,
            "xla_tokens_per_s": n_slots / (xla_ms / 1e3),
            "pallas_tokens_per_s": n_slots / (pal_ms / 1e3),
            "pallas_speedup": xla_ms / pal_ms,
        }

    # greedy token identity through the real engine on a small trace
    rng = np.random.default_rng(SEED)
    trace = _trace(cfg, rng)[: 4 if smoke else N_REQ]
    n_pages = N_SLOTS * (MAX_PROMPT + 16 + 8) // PAGE_SIZE + 1

    def replay(backend):
        c = dataclasses.replace(cfg, attention_backend=fused[backend])
        eng, rids, _ = _run_continuous(params, c, trace, n_pages,
                                       timed=False)
        res = eng.results()
        return [res[r].tolist() for r in rids]

    identical = replay("pallas") == replay("xla")

    resolved = "pallas" if jax.default_backend() == "tpu" else "blocked"
    return {
        "setup": {"table_tokens": table_pages * page_size,
                  "page_size": page_size, "n_slots": n_slots,
                  "timing_iters": n_iters, "smoke": smoke,
                  "jax_backend": jax.default_backend(),
                  # 'pallas' = Mosaic kernel on TPU; elsewhere the blocked
                  # XLA lowering of the same page-walk algorithm runs
                  "pallas_resolves_to": resolved},
        "long": rows["long"],
        "short": rows["short"],
        # block-skip visibility: the fused path gets faster as lens
        # shrinks while the gather path stays pinned to the table width
        "pallas_short_vs_long_step": (rows["short"]["pallas_step_ms"]
                                      / rows["long"]["pallas_step_ms"]),
        "xla_short_vs_long_step": (rows["short"]["xla_step_ms"]
                                   / rows["long"]["xla_step_ms"]),
        "token_identical_pallas_vs_xla": bool(identical),
    }


def csv_lines_paged_attn(res):
    lo, sh = res["long"], res["short"]
    return [
        f"paged_attn_long_xla_step_ms,0,{lo['xla_step_ms']:.3f}",
        f"paged_attn_long_pallas_step_ms,0,{lo['pallas_step_ms']:.3f}",
        f"paged_attn_long_speedup,0,{lo['pallas_speedup']:.3f}",
        f"paged_attn_short_xla_step_ms,0,{sh['xla_step_ms']:.3f}",
        f"paged_attn_short_pallas_step_ms,0,{sh['pallas_step_ms']:.3f}",
        f"paged_attn_short_speedup,0,{sh['pallas_speedup']:.3f}",
        f"paged_attn_block_skip_ratio,0,"
        f"{res['pallas_short_vs_long_step']:.3f}",
        f"paged_attn_token_identical,0,"
        f"{int(res['token_identical_pallas_vs_xla'])}",
    ]


# ---------------------------------------------------------------------------
# telemetry: tracing overhead + Chrome-trace validity + span reconciliation
# ---------------------------------------------------------------------------

def run_telemetry(smoke: bool = False, trace_out=None, metrics_out=None):
    """Replay a pressure trace through the engine with the lifecycle
    tracer ON vs OFF (DESIGN.md §9): reports the tracing overhead
    fraction, validates the Chrome trace-event export in-process (every
    span well-formed; prefill/decode/evict spans present; per-request
    ``queued``/``prefill``/``decode`` phase durations sum exactly to the
    ``request`` span = the reported latency), and optionally writes the
    trace + metrics-registry snapshot to disk.

    The geometry (2 slots over a 7-page pool of 4-token pages, optimistic
    reservation, 10 new tokens per request) forces recompute preemption,
    so the trace provably contains ``evict`` instants."""
    import jax
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import Engine
    from repro.serve.telemetry import ServeTelemetry

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    n_slots, page_size, n_pages = 2, 4, 7
    plens = [5, 3, 6] if smoke else [5, 3, 6, 7, 4, 6, 5, 3]
    max_new = 10
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    total_tokens = max_new * len(prompts)

    def replay(tel):
        eng = Engine(params, cfg, n_slots=n_slots, page_size=page_size,
                     n_pages=n_pages, reserve="optimistic",
                     prefill_chunk=4, telemetry=tel)
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new=max_new) for p in prompts]
        eng.run()
        return eng, rids, time.perf_counter() - t0

    replay(None)                       # warmup (absorb jit compiles)
    reps = 3 if smoke else 5
    wall_off = min(replay(None)[2] for _ in range(reps))
    tel = ServeTelemetry(trace=True)
    eng, rids, _ = replay(tel)         # the validated + exported run
    walls_on = [replay(ServeTelemetry(trace=True))[2] for _ in range(reps)]
    wall_on = min(walls_on)

    # greedy outputs must not depend on whether the tracer is attached
    eng_off, rids_off, _ = replay(None)
    res_on, res_off = eng.results(), eng_off.results()
    identical = all(res_on[a].tolist() == res_off[b].tolist()
                    for a, b in zip(rids, rids_off))

    # ---- in-process trace validation ----
    events = tel.tracer.chrome_events()
    spans = [e for e in events if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    valid = all(e["dur"] >= 0 and e["ts"] >= 0
                and {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
                for e in spans)
    need = {"serve.step", "serve.prefill_chunk", "serve.decode", "evict",
            "request", "queued", "prefill", "decode", "admit",
            "first_token"}
    valid &= need <= names
    # reconciliation: per request, phase spans telescope to the latency
    phases = {}
    for e in spans:
        if e["name"] in ("queued", "prefill", "decode"):
            phases.setdefault(e["tid"], 0.0)
            phases[e["tid"]] += e["dur"]
    max_err_us = 0.0
    n_req_spans = 0
    for e in spans:
        if e["name"] == "request":
            n_req_spans += 1
            max_err_us = max(max_err_us,
                             abs(phases.get(e["tid"], 0.0) - e["dur"]))
    valid &= n_req_spans == len(prompts)
    valid &= max_err_us <= 2.0          # µs — float rounding only

    st = eng.stats()
    if trace_out:
        tel.tracer.write_chrome(trace_out)
    if metrics_out:
        tel.registry.write_json(metrics_out)

    counts = {n: sum(1 for e in events if e.get("name") == n)
              for n in sorted(names - {"process_name", "thread_name"})}
    return {
        "setup": {"n_requests": len(prompts), "n_slots": n_slots,
                  "page_size": page_size, "n_pages": n_pages,
                  "max_new": max_new, "reps": reps, "smoke": smoke},
        "untraced": {"wall_s": wall_off,
                     "tokens_per_s": total_tokens / wall_off},
        "traced": {"wall_s": wall_on,
                   "tokens_per_s": total_tokens / wall_on,
                   "n_events": len(events)},
        "overhead_frac": (wall_on - wall_off) / wall_off,
        "trace_valid": bool(valid),
        "reconcile_max_err_us": max_err_us,
        "span_counts": counts,
        "evictions": st["evictions"],
        "token_identical_traced_vs_untraced": bool(identical),
    }


def csv_lines_telemetry(res):
    t, u = res["traced"], res["untraced"]
    return [
        f"telemetry_untraced_tok_s,0,{u['tokens_per_s']:.2f}",
        f"telemetry_traced_tok_s,0,{t['tokens_per_s']:.2f}",
        f"telemetry_overhead_frac,0,{res['overhead_frac']:.4f}",
        f"telemetry_trace_valid,0,{int(res['trace_valid'])}",
        f"telemetry_reconcile_max_err_us,0,"
        f"{res['reconcile_max_err_us']:.3f}",
        f"telemetry_evictions,0,{res['evictions']}",
        f"telemetry_token_identical,0,"
        f"{int(res['token_identical_traced_vs_untraced'])}",
    ]


# ---------------------------------------------------------------------------
# accuracy-vs-throughput: dense fp vs calibrated encoded-MAC serving
# ---------------------------------------------------------------------------

def _engine_metrics(eng, rids, wall, total_tokens):
    lat = [(r.t_finish - r.t_arrive) for r in eng.requests.values()
           if r.t_finish is not None]
    st = eng.stats()
    return {
        "tokens_per_s": total_tokens / wall,
        "wall_s": wall,
        "latency_p50_s": _pct(lat, 0.50),
        "latency_p99_s": _pct(lat, 0.99),
        "occupancy": st["occupancy"],
        "mac_mode": st["mac_mode"],
    }


def _logit_agreement(params_d, cfg_d, params_e, cfg_e, prompts):
    """Top-1 argmax agreement + mean |Δlogit| between the dense fp forward
    and the encoded forward over full prompt prefills (all positions) —
    the same ``repro.obs.logit_agreement`` the engine's online
    ``DriftMonitor`` gauge samples, so offline and online numbers agree
    by construction."""
    from repro.obs import logit_agreement
    return logit_agreement(params_d, cfg_d, params_e, cfg_e, prompts)


def run_encoded(m_bits: int = 48, n_samples: int = 128, refine: int = 64):
    """Dense vs encoded continuous serving at an equal page budget."""
    import jax
    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import prepare_encoded_serving

    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    t0 = time.perf_counter()
    params_e, cfg_e, info = prepare_encoded_serving(
        params, cfg, m_bits=m_bits, n_samples=n_samples, refine=refine)
    prep_s = time.perf_counter() - t0

    rng = np.random.default_rng(SEED)
    trace = _trace(cfg, rng)
    total_tokens = sum(m for _, m, _ in trace)
    budget_tokens = N_SLOTS * (MAX_PROMPT + 16 + 8)
    n_pages = budget_tokens // PAGE_SIZE + 1

    # warmup replays (absorb jit compiles for both MAC paths)
    _run_continuous(params, cfg, trace, n_pages, timed=False)
    _run_continuous(params_e, cfg_e, trace, n_pages, timed=False)

    eng_d, rids_d, wall_d = _run_continuous(params, cfg, trace, n_pages)
    eng_e, rids_e, wall_e = _run_continuous(params_e, cfg_e, trace, n_pages)
    top1, dlogit = _logit_agreement(params, cfg, params_e, cfg_e,
                                    [p for p, _, _ in trace[:4]])

    # int8 ceiling: the bit-exact AND-plane encoding isolates the plain
    # quantization error from the searched encoding's approximation error
    from repro.core.circuits import exact_product_circuit
    from repro.core.encoding import EncodingSpec
    from repro.core.mac import EncodedMac
    circ, s = exact_product_circuit(cfg.mac.bits, cfg.mac.bits)
    exact = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
    params_x, cfg_x, _ = prepare_encoded_serving(
        params, cfg, macs_override={n: exact for n in info["families"]},
        verbose=False)
    top1_x, _ = _logit_agreement(params, cfg, params_x, cfg_x,
                                 [p for p, _, _ in trace[:4]])

    return {
        "trace": {"n_requests": N_REQ, "arrival_rate_hz": ARRIVAL_RATE,
                  "total_tokens": total_tokens, "page_size": PAGE_SIZE,
                  "n_pages": n_pages, "n_slots": N_SLOTS},
        "prepare_s": prep_s,
        "artifact": {"bundle_dir": info["bundle_dir"],
                     "loaded_from_cache": info["loaded"],
                     "family_rmse": info["families"]},
        "dense": _engine_metrics(eng_d, rids_d, wall_d, total_tokens),
        "encoded": _engine_metrics(eng_e, rids_e, wall_e, total_tokens),
        "top1_logit_agreement": top1,
        "top1_logit_agreement_int8_ceiling": top1_x,
        "mean_abs_logit_delta": dlogit,
        "encoded_vs_dense_tok_s": wall_d / wall_e,
    }


def csv_lines_encoded(res):
    d, e = res["dense"], res["encoded"]
    return [
        f"serving_dense_tok_s,0,{d['tokens_per_s']:.2f}",
        f"serving_encoded_tok_s,0,{e['tokens_per_s']:.2f}",
        f"serving_encoded_rel_tok_s,0,{res['encoded_vs_dense_tok_s']:.3f}",
        f"serving_p99_dense_s,0,{d['latency_p99_s']:.3f}",
        f"serving_p99_encoded_s,0,{e['latency_p99_s']:.3f}",
        f"serving_top1_logit_agreement,0,{res['top1_logit_agreement']:.3f}",
        f"serving_top1_agreement_int8_ceiling,0,"
        f"{res['top1_logit_agreement_int8_ceiling']:.3f}",
    ]


# ---------------------------------------------------------------------------
# quantized paged KV cache: capacity, equal-HBM decode throughput, agreement
# ---------------------------------------------------------------------------

def run_kv_quant(smoke: bool = False):
    """Quantized paged KV cache (``--kv-dtype``, DESIGN.md §11) across
    bf16/int8/int4, one JSON with the three gated claims:

    1. **capacity** — pool bytes per cached token (values + scale rows)
       and concurrent 544-token slots at an EQUAL page-pool HBM budget
       (int8 must fit ≥2x the bf16 slots).
    2. **equal_hbm_decode** — aggregate fused-decode tokens/s through
       the real engine when every dtype gets the SAME pool bytes and
       every request holds ≥512 cached tokens: the bf16 pool only
       admits ~1 request at a time, the quantized pools run all slots
       concurrently, so the capacity win converts into batched decode
       throughput (int8 must reach ≥1.3x bf16).  Decode time comes from
       the telemetry tracer's ``serve.decode`` spans, so prefill (equal
       work in every arm) does not dilute the ratio.
    3. **agreement** — per-position top-1 argmax agreement of a paged
       prefill over the quantized pool vs the dense pool (int8 ≥0.99 on
       this smoke config), plus greedy engine token identity vs the
       bf16 cache; a non-1.0 fraction IS the reported drift gap.

    The model is briefly TRAINED (a deterministic next-token chain it
    memorizes in ~500 steps) before any fidelity number is read: a
    random-init model's top-2 logit margins are vanishingly small, so
    its argmax flips under any perturbation and "agreement" measures
    seed luck, not cache fidelity.  On the memorized distribution the
    margins are real and both fidelity numbers are stable across prompt
    seeds.  Throughput arms reuse the same trained params (weights
    don't change step latency).

    The ``fused_step`` block is the honest kernel-level micro: one
    fused blocked decode step over a 512-token table per dtype with the
    pool's achieved bytes/s.  On a 1-core CPU host the step is bound by
    the f32 attention matvec (same work in every arm), so the kernel
    ratio hovers near 1x and int4's unpack costs extra compute — the
    bandwidth win needs real HBM (the ``jax_backend`` field records
    what ran); the equal-HBM engine numbers above are the CPU-visible
    form of the same byte savings."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_paged_cache
    from repro.quant.kvcache import quantize_kv
    from repro.kernels.paged_attention import paged_attn
    from repro.serve import Engine, PagedKVCache, ServeTelemetry
    from repro.serve.engine import make_paged_prefill
    from repro.train.trainer import init_train_state, make_train_step

    cfg = get_config("qwen1.5-0.5b").reduced()
    V = cfg.vocab_size

    def chain(start, n):
        """The memorization corpus: an order-1 deterministic token chain
        (next token is a fixed affine map of the current one)."""
        out = np.empty(n, np.int32)
        x = int(start) % V
        for i in range(n):
            out[i] = x
            x = (5 * x + 17) % V
        return out

    train_cfg = dataclasses.replace(cfg, learning_rate=3e-3)
    train_steps = 500
    state = init_train_state(jax.random.PRNGKey(0), train_cfg)
    tstep = jax.jit(make_train_step(train_cfg, total_steps=train_steps,
                                    warmup=50))
    trng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    for _ in range(train_steps):
        toks = np.stack([chain(s, 33) for s in trng.integers(0, V, 8)])
        state, tm = tstep(state, {"tokens": jnp.asarray(toks[:, :-1]),
                                  "labels": jnp.asarray(toks[:, 1:])})
    train_s = time.perf_counter() - t0
    final_loss = float(tm["loss"])
    params = state["params"]
    dtypes = ("bf16", "int8", "int4")
    ps, n_slots, plen, max_new = 16, 4, 512, 32   # ≥512 cached per request
    seq_tokens = plen + max_new
    seq_pages = seq_tokens // ps + 2
    n_iters = 10 if smoke else 30

    # ---- capacity: bytes/token and slots at equal HBM ----
    def bpt(kvd):
        c = dataclasses.replace(cfg, kv_cache_dtype=kvd)
        kv = PagedKVCache(c, n_slots=1, n_pages=4, page_size=ps,
                          max_seq_pages=4)
        return kv.kv_bytes_per_token()

    bytes_per_token = {d: bpt(d) for d in dtypes}
    budget = int(1.4 * seq_tokens * bytes_per_token["bf16"])

    def npages(kvd):
        return max(seq_pages + 1,
                   int(budget // (bytes_per_token[kvd] * ps)) + 1)

    capacity = {d: {
        "kv_bytes_per_token": bytes_per_token[d],
        "n_pages_at_budget": npages(d),
        "slots_at_equal_hbm": int(budget
                                  // (bytes_per_token[d] * seq_tokens)),
    } for d in dtypes}
    slot_ratio_int8 = (capacity["int8"]["slots_at_equal_hbm"]
                       / max(1, capacity["bf16"]["slots_at_equal_hbm"]))

    # ---- fused kernel micro: one blocked decode step per dtype ----
    def fused_step(kvd):
        rng = np.random.default_rng(1)
        B, Hkv, D, P = 4, cfg.n_kv_p, cfg.head_dim_r, plen // ps
        n_pages = 1 + B * P                     # distinct chain per slot
        dk = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv, D))
                         .astype(np.float32))
        dv = jnp.asarray(rng.normal(size=(n_pages, ps, Hkv, D))
                         .astype(np.float32))
        if kvd == "bf16":
            pk, pv, sk, sv = dk.astype(cfg.cdtype), dv.astype(cfg.cdtype), \
                None, None
        else:
            pk, sk = quantize_kv(dk, kvd)
            pv, sv = quantize_kv(dv, kvd)
        pg = np.zeros((B, P), np.int32)
        for b in range(B):
            pg[b] = 1 + b * P + np.arange(P)
        pages = jnp.asarray(pg)
        lens = jnp.full((B,), plen, jnp.int32)
        kv_map = np.minimum(np.arange(cfg.n_heads) // max(
            1, cfg.n_heads // Hkv), Hkv - 1).astype(np.int32)
        q = jnp.asarray(rng.normal(size=(B, 1, cfg.n_heads, D)),
                        jnp.float32)
        f = jax.jit(lambda q: paged_attn(
            q, pk, pv, pages, lens, scale=D ** -0.5, kv_of_q=kv_map,
            backend="blocked", scale_k=sk, scale_v=sv))
        us = time_call_local(f, q, n=n_iters)
        pool_bytes_read = B * plen * (
            pk.dtype.itemsize * 2 * Hkv * pk.shape[-1]
            + (8 * Hkv if sk is not None else 0))   # 2 f32 scale rows
        return {"step_us": us,
                "tokens_per_s": B / (us / 1e6),
                "pool_bytes_per_step": pool_bytes_read,
                "achieved_gb_per_s": pool_bytes_read / (us / 1e6) / 1e9}

    try:
        from .common import time_call as time_call_local
    except ImportError:
        from common import time_call as time_call_local
    fused = {d: fused_step(d) for d in dtypes}

    # ---- equal-HBM engine decode throughput (the ≥1.3x gate) ----
    rng = np.random.default_rng(SEED)
    prompts = [chain(rng.integers(0, V), plen) for _ in range(n_slots)]

    def engine_run(kvd):
        c = dataclasses.replace(cfg, kv_cache_dtype=kvd,
                                attention_backend="auto")
        tel = ServeTelemetry(trace=True)
        eng = Engine(params, c, n_slots=n_slots, page_size=ps,
                     n_pages=npages(kvd), max_seq_pages=seq_pages,
                     prefill_chunk=64, telemetry=tel)
        rids = [eng.submit(p, max_new=max_new) for p in prompts]
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        dec_us = sum(e["dur"] for e in tel.tracer.chrome_events()
                     if e.get("ph") == "X" and e["name"] == "serve.decode")
        st = eng.stats()
        res = eng.results()
        return {
            "tokens_per_s": total_new / wall,
            "decode_tokens_per_s": st["decode_tokens"] / (dec_us / 1e6),
            "wall_s": wall,
            "latency_p99_s": _pct(
                [(r.t_finish - r.t_arrive)
                 for r in eng.requests.values()], 0.99),
            "occupancy": st["occupancy"],
            "evictions": st["evictions"],
            "kv_bytes_per_token": st["kv_bytes_per_token"],
            "kv_capacity_tokens": st["kv_capacity_tokens"],
        }, [res[r].tolist() for r in rids]

    total_new = n_slots * max_new
    engine = {}
    outs = {}
    for d in dtypes:
        engine_run(d)                       # warmup (absorb jit compiles)
        engine[d], outs[d] = engine_run(d)
    decode_speedup_int8 = (engine["int8"]["decode_tokens_per_s"]
                           / engine["bf16"]["decode_tokens_per_s"])

    # greedy token identity vs the bf16 cache (drift gap if < 1.0)
    token_match = {}
    for d in ("int8", "int4"):
        n_match = sum(int(a == b) for r, s in zip(outs[d], outs["bf16"])
                      for a, b in zip(r, s))
        token_match[d] = n_match / total_new

    # ---- per-position top-1 agreement over a paged prefill ----
    # --mac encoded's methodology: short trace-sized prompts, argmax at
    # every prefill position (each position attends over the quantized
    # pages scattered by the earlier positions)
    n_prompts = 4 if smoke else 8
    P = 24 // ps + 1
    agree_prompts = [chain(rng.integers(0, V), int(rng.integers(8, 25)))
                     for _ in range(n_prompts)]

    def make_prefill_argmax(kvd):
        c = dataclasses.replace(cfg, kv_cache_dtype=kvd,
                                attention_backend="auto")
        fn = jax.jit(make_paged_prefill(c))
        pages = jnp.arange(1, P + 1, dtype=jnp.int32)[None]

        def run_one(prompt):
            layers = init_paged_cache(c, 1 + P, ps)["layers"]
            toks, _ = fn(params, layers, jnp.asarray(prompt)[None],
                         pages, jnp.zeros((1,), jnp.int32))
            return np.asarray(toks)[0]
        return run_one

    prefills = {d: make_prefill_argmax(d) for d in dtypes}
    dense_toks = [prefills["bf16"](p) for p in agree_prompts]
    agreement = {}
    for d in ("int8", "int4"):
        hits = total = 0
        for p, a in zip(agree_prompts, dense_toks):
            b = prefills[d](p)
            hits += int((a == b).sum())
            total += a.size
        agreement[d] = hits / total

    return {
        "setup": {"page_size": ps, "n_slots": n_slots,
                  "prompt_tokens": plen, "max_new": max_new,
                  "cached_tokens_floor": plen,
                  "equal_hbm_budget_bytes": budget,
                  "timing_iters": n_iters, "smoke": smoke,
                  "train_steps": train_steps, "train_s": train_s,
                  "train_final_loss": final_loss,
                  "compute_dtype": str(np.dtype(cfg.cdtype)),
                  "jax_backend": jax.default_backend()},
        "capacity": capacity,
        "slots_ratio_int8_vs_bf16": slot_ratio_int8,
        "fused_step": fused,
        "equal_hbm_decode": engine,
        "decode_speedup_int8_vs_bf16": decode_speedup_int8,
        "top1_logit_agreement": agreement,
        "token_match_vs_bf16": token_match,
    }


def csv_lines_kv_quant(res):
    lines = []
    for d in ("bf16", "int8", "int4"):
        c = res["capacity"][d]
        e = res["equal_hbm_decode"][d]
        f = res["fused_step"][d]
        lines += [
            f"kv_quant_{d}_bytes_per_token,0,{c['kv_bytes_per_token']:.1f}",
            f"kv_quant_{d}_slots_equal_hbm,0,{c['slots_at_equal_hbm']}",
            f"kv_quant_{d}_decode_tok_s,0,{e['decode_tokens_per_s']:.1f}",
            f"kv_quant_{d}_fused_step_us,{f['step_us']:.1f},"
            f"{f['achieved_gb_per_s']:.3f}GB/s",
        ]
    lines += [
        f"kv_quant_slots_ratio_int8,0,"
        f"{res['slots_ratio_int8_vs_bf16']:.2f}",
        f"kv_quant_decode_speedup_int8,0,"
        f"{res['decode_speedup_int8_vs_bf16']:.3f}",
        f"kv_quant_int8_top1_agreement,0,"
        f"{res['top1_logit_agreement']['int8']:.4f}",
        f"kv_quant_int4_top1_agreement,0,"
        f"{res['top1_logit_agreement']['int4']:.4f}",
        f"kv_quant_int8_token_match,0,"
        f"{res['token_match_vs_bf16']['int8']:.3f}",
    ]
    return lines


def run_spec_decode(smoke: bool = False):
    """Speculative decoding (DESIGN.md §10): replay the mixed trace
    through the continuous engine non-speculatively and with
    ``spec_decode=k`` for k ∈ {2, 4, 8} (self-draft: the verifier's own
    params as drafter, so the speedup isolates dispatch amortization —
    one draft dispatch + one verify dispatch per up-to-(k+1) tokens vs
    one dispatch per token), plus an encoded lower-m-bits drafter built
    by ``prepare_drafter`` (acceptance rate = the paper's accuracy knob).
    Greedy output must be token-identical to the baseline in every row.

    The drafter's top-1 agreement vs dense comes FREE from verification
    (``DriftMonitor.observe_agreement`` fed by the engine): no second
    dense forward is run for the drift number, unlike the ``--mac
    encoded`` bench's offline ``logit_agreement`` replay."""
    import jax
    import dataclasses
    from repro.configs import get_config
    from repro.models import init_model
    from repro.obs import DriftMonitor
    from repro.serve import ServeTelemetry, prepare_drafter

    # extra-tiny config: speculation amortizes per-step dispatch + host
    # scheduling, so the bench pins the dispatch-bound regime the
    # optimization targets (self-draft doubles per-token FLOPs — on a
    # compute-bound host the win is acceptance × drafter cheapness
    # instead, which the encoded rows cover)
    cfg = dataclasses.replace(
        get_config("qwen1.5-0.5b").reduced(), n_layers=1, d_model=64,
        d_ff=128, n_heads=2, n_kv_heads=1, head_dim=32, vocab_size=256)
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(SEED)
    # decode-heavy trace: speculation amortizes DECODE dispatches, so the
    # bench measures steady-state decode (short prompts, long max_new) —
    # prefill-heavy tails would dilute both paths equally and hide the
    # per-round win
    max_new = 32 if smoke else 48
    trace = []
    for _ in range(6 if smoke else N_REQ):
        plen = int(rng.integers(4, 13))
        trace.append((rng.integers(0, cfg.vocab_size, plen)
                      .astype(np.int32), max_new, 0.0))
    total_tokens = sum(m for _, m, _ in trace)
    n_pages = N_SLOTS * (13 + max_new + 8) // PAGE_SIZE + 2

    def replay(**kw):
        # warmup replay absorbs jit compiles, then best-of-3 timed
        # replays in throughput mode (all requests queued up front) —
        # min wall, the standard noise-robust estimator for a CI gate
        _run_continuous(params, cfg, trace, n_pages, timed=False, **kw)
        wall = float("inf")
        for _ in range(3):
            eng, rids, w = _run_continuous(params, cfg, trace, n_pages,
                                           timed=False, **kw)
            wall = min(wall, w)
        res = eng.results()
        return eng, [res[r].tolist() for r in rids], wall

    eng_b, ref, wall_b = replay()
    base = {"tokens_per_s": total_tokens / wall_b, "wall_s": wall_b,
            "decode_tokens": eng_b.stats()["decode_tokens"]}

    def spec_row(k, **kw):
        drift = DriftMonitor(params, cfg)
        tel = ServeTelemetry(drift=drift)
        eng, out, wall = replay(spec_decode=k, telemetry=tel, **kw)
        st = eng.stats()
        return {
            "k": k,
            "tokens_per_s": total_tokens / wall,
            "wall_s": wall,
            "speedup_vs_baseline": wall_b / wall,
            "acceptance_rate": st["spec_acceptance_rate"],
            "tokens_per_round": st["spec_tokens_per_round"],
            "rounds": st["spec_rounds"],
            "draft_mac_mode": st["draft_mac_mode"],
            # drift-for-free: draft-vs-dense top-1 agreement accumulated
            # from the verify logits, zero extra forwards
            "draft_top1_agreement": drift.last,
            "token_identical": out == ref,
        }

    self_rows = {f"k{k}": spec_row(k) for k in (2, 4, 8)}

    enc_rows = {}
    # bit-exact AND-plane drafter first: agreement = the int8 ceiling
    # (~0.75 acceptance), independent of search quality — the row the CI
    # smoke gate checks.  The searched lower-m rows trace the paper's
    # acceptance-vs-m_bits knob (smoke calibration is too coarse for
    # argmax agreement on this tiny config; full runs do better).
    from repro.core.circuits import exact_product_circuit
    from repro.core.encoding import EncodingSpec
    from repro.core.mac import EncodedMac
    circ, s = exact_product_circuit(cfg.mac.bits, cfg.mac.bits)
    exact = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
    dp, dc, _ = prepare_drafter(
        params, cfg, m_bits=cfg.mac.bits * 2,
        macs_override={n: exact for n in ("wq", "wk", "wv", "wo",
                                          "wi", "wg", "w")},
        verbose=False)
    row = spec_row(4, draft_params=dp, draft_cfg=dc)
    row["m_bits"] = "exact"
    enc_rows["exact"] = row
    for mb in ((40,) if smoke else (24, 40)):
        calib = dict(n_samples=16, refine=8) if smoke else \
            dict(n_samples=64, refine=32)
        dp, dc, dinfo = prepare_drafter(params, cfg, m_bits=mb, **calib)
        row = spec_row(4, draft_params=dp, draft_cfg=dc)
        row["m_bits"] = mb
        row["shared_with_verifier"] = dinfo.get("shared_with_verifier",
                                                False)
        enc_rows[f"m{mb}"] = row

    rows = list(self_rows.values()) + list(enc_rows.values())
    return {
        "setup": {"n_requests": len(trace), "total_tokens": total_tokens,
                  "page_size": PAGE_SIZE, "n_pages": n_pages,
                  "n_slots": N_SLOTS, "smoke": smoke,
                  "jax_backend": jax.default_backend()},
        "baseline": base,
        "self_draft": self_rows,
        "encoded_draft": enc_rows,
        "token_identical_all": all(r["token_identical"] for r in rows),
    }


def csv_lines_spec(res):
    lines = [f"spec_decode_baseline_tok_s,0,"
             f"{res['baseline']['tokens_per_s']:.2f}"]
    for key, r in res["self_draft"].items():
        lines += [
            f"spec_decode_self_{key}_tok_s,0,{r['tokens_per_s']:.2f}",
            f"spec_decode_self_{key}_speedup,0,"
            f"{r['speedup_vs_baseline']:.3f}",
            f"spec_decode_self_{key}_acceptance,0,"
            f"{r['acceptance_rate']:.3f}",
        ]
    for key, r in res["encoded_draft"].items():
        lines += [
            f"spec_decode_encoded_{key}_acceptance,0,"
            f"{r['acceptance_rate']:.3f}",
            f"spec_decode_encoded_{key}_speedup,0,"
            f"{r['speedup_vs_baseline']:.3f}",
        ]
    lines.append(f"spec_decode_token_identical,0,"
                 f"{int(res['token_identical_all'])}")
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mac", default="fp", choices=["fp", "encoded"],
                    help="fp = continuous-vs-static baseline bench; "
                         "encoded = dense-vs-encoded accuracy/throughput")
    ap.add_argument("--trace", default="mixed",
                    choices=["mixed", "shared-prefix", "paged-attn",
                             "telemetry", "spec-decode", "kv-quant"],
                    help="mixed = the continuous-vs-static trace; "
                         "shared-prefix = prefix-cache warm-vs-cold trace; "
                         "paged-attn = fused decode kernel vs gathered-"
                         "view path (per-step latency + tokens/s); "
                         "telemetry = tracing overhead + Chrome-trace "
                         "validity + span/latency reconciliation; "
                         "spec-decode = speculative decoding tokens/s + "
                         "acceptance vs k (self + encoded drafters); "
                         "kv-quant = bf16/int8/int4 KV pools: capacity, "
                         "equal-HBM decode tokens/s, logit agreement")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace variants (CI smoke jobs)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="--trace telemetry: write the Chrome trace-event "
                         "JSON here (only on a fresh run, i.e. with "
                         "--force or a cold artifact cache)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="--trace telemetry: write the metrics-registry "
                         "snapshot JSON here (fresh runs only, as above)")
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--m-bits", type=int, default=48)
    ap.add_argument("--calib-samples", type=int, default=128)
    ap.add_argument("--calib-refine", type=int, default=64)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    try:
        from .common import cached          # python -m benchmarks.serving_bench
    except ImportError:
        from common import cached           # python benchmarks/serving_bench.py
    if args.trace == "paged-attn":
        # one canonical artifact name: the CI smoke job and the full run
        # write the same file (the 'setup' block records which ran)
        res = cached("BENCH_paged_attn", lambda: run_paged_attn(args.smoke),
                     force=args.force)
        lines = csv_lines_paged_attn(res)
    elif args.trace == "telemetry":
        # one canonical artifact (the 'setup' block records smoke-ness);
        # trace/metrics exports happen inside the fresh run
        res = cached("BENCH_telemetry",
                     lambda: run_telemetry(args.smoke, args.trace_out,
                                           args.metrics_out),
                     force=args.force)
        lines = csv_lines_telemetry(res)
    elif args.trace == "kv-quant":
        # one canonical artifact (the 'setup' block records smoke-ness)
        res = cached("BENCH_kv_quant", lambda: run_kv_quant(args.smoke),
                     force=args.force)
        lines = csv_lines_kv_quant(res)
    elif args.trace == "spec-decode":
        # one canonical artifact (the 'setup' block records smoke-ness)
        res = cached("BENCH_spec_decode",
                     lambda: run_spec_decode(args.smoke),
                     force=args.force)
        lines = csv_lines_spec(res)
    elif args.trace == "shared-prefix":
        # key carries smoke-ness AND the chunk size so flag changes never
        # report another configuration's stale numbers
        name = (f"serving_bench_prefix{'_smoke' if args.smoke else ''}"
                f"_c{args.prefill_chunk}")
        res = cached(name,
                     lambda: run_prefix(args.smoke, args.prefill_chunk),
                     force=args.force)
        lines = csv_lines_prefix(res)
    elif args.mac == "encoded":
        # cache key carries the search hyperparameters so flag changes
        # never report another configuration's stale numbers
        name = (f"serving_bench_encoded_m{args.m_bits}"
                f"_s{args.calib_samples}_r{args.calib_refine}")
        res = cached(name,
                     lambda: run_encoded(args.m_bits, args.calib_samples,
                                         args.calib_refine),
                     force=args.force)
        lines = csv_lines_encoded(res)
    else:
        res = cached("serving_bench", run, force=args.force)
        lines = csv_lines(res)
    print("name,us_per_call,derived")
    for line in lines:
        print(line)


if __name__ == "__main__":
    main()
