"""Serving: prefill / decode step factories, the batched greedy loop, and
the continuous-batching ``Engine`` over the paged KV cache.

Two serving modes:

  * ``generate`` / ``ServeEngine`` — static batch, dense per-sequence KV
    cache sized to the worst case (the original path; kept as the
    benchmark baseline and for archs without paged-cache support).
  * ``Engine`` — continuous batching: a scheduler admits queued requests
    into a fixed number of slots under a page budget (vLLM-style paged
    KV, repro.serve.paged_cache), prefill and decode interleave, and
    finished slots are swapped for queued requests every step.  Decode is
    ONE jitted step for all slots regardless of per-request progress, so
    the encoded-MAC matmul path stays hot under ragged traffic.  For
    calibrated encoded inference (mac mode 'encoded_infer' — per-family
    encodings, pre-folded bitplane weights) build the params/cfg pair
    with repro.serve.encoded.prepare_encoded_serving first; the engine
    itself is MAC-mode agnostic.

``make_decode_step`` is the dense decode of ``generate`` and the static
engine: one new token against a KV cache of seq_len.  Caches are donated
(buffer-reuse) and sequence-sharded over the model axis (DESIGN.md §5).

Both engines take ``mesh=`` for tensor-parallel serving (DESIGN.md §6):
params are placed per the path-based sharding rules (folded encoded
tensors col/row-parallel over the model axis), paged pools split over kv
heads, and every jitted step traces/runs under the mesh.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.ledger import sanitize_enabled
from repro.core.macexec import check_drafter
from repro.nn.attention import paged_head_batched
from repro.models import (apply_model, init_cache, init_paged_cache,
                          supports_paged_cache)
from repro.obs import CompileTracker, percentile, profiler_trace
from repro.parallel.sharding import param_specs, set_mesh
from repro.parallel.statesharding import cache_specs
from .paged_cache import PagedKVCache, _copy_page_jit, pages_for
from .scheduler import (Scheduler, Request, QUEUED, PREFILLING, DECODING,
                        FINISHED)
from .spec import greedy_accept, make_spec_draft, make_spec_verify
from .telemetry import ServeTelemetry, TID_ENGINE, req_tid


def _shard_params(params, mesh):
    """Place params per the path-based rules (folded ``*_fw``/``*_fb``
    encoded-serving tensors included — DESIGN.md §6)."""
    return jax.device_put(params, param_specs(params, mesh))


def _mesh_scope(mesh):
    """Active-mesh scope for tracing/running jitted steps (model-code
    ``constrain`` and the shard-local encoded kernel read it); no-op when
    serving single-device."""
    return set_mesh(mesh) if mesh is not None else contextlib.nullcontext()


def make_prefill(cfg):
    def prefill(params, cache, tokens, **extras):
        logits, cache, _ = apply_model(params, cfg, tokens, cache=cache,
                                       **extras)
        return logits[:, -1:], cache
    return prefill


def make_decode_step(cfg):
    def decode_step(params, cache, tokens):
        logits, cache, _ = apply_model(params, cfg, tokens, cache=cache)
        return logits, cache
    return decode_step


def supports_ragged_mask(cfg) -> bool:
    """Whether the left-pad masking path (``pad_lens``) is exact for this
    arch: standard GQA attention over a dense cache.  MLA latents,
    recurrent state (ssm/xlstm/hybrid), and meta tokens ingest pads into
    state the attention mask cannot retroactively exclude — the same
    plain-GQA-cache predicate as ``supports_paged_cache``.  Flash-kernel
    prefill is excluded too: the masked path runs through ``mha``, whose
    accumulation order differs from the flash kernel a solo run would
    use, so bit-exact parity with per-request ``generate`` could not be
    guaranteed."""
    return supports_paged_cache(cfg) and not cfg.flash_attention


def generate(params, cfg, prompts: jnp.ndarray, max_new: int = 16,
             max_len: Optional[int] = None, extras: Optional[dict] = None,
             greedy: bool = True, key=None, eos_id: Optional[int] = None,
             pad_lens=None):
    """Batched generation loop (greedy or temperature-1 sampling).

    ``eos_id``: rows that emit it are frozen — subsequent positions repeat
    ``eos_id`` (so finished sequences stop contributing new tokens) and the
    loop exits early once every row has finished.  Output stays (B, ≤max_new).

    ``pad_lens`` (B,): per-row count of left-pad tokens for ragged batches.
    Pad keys are masked out of attention and positions are offset so every
    row computes exactly what it would alone (see ``supports_ragged_mask``).

    The loop never runs a wasted decode step: logits are only computed for
    tokens that will actually be appended, so a ``max_new``-token rollout
    costs one prefill plus ``max_new - 1`` decode steps.
    """
    B, S = prompts.shape
    max_len = max_len or (S + max_new + (cfg.meta_tokens or 0))
    cache = init_cache(cfg, B, max_len)
    if pad_lens is not None:
        pad_lens = jnp.asarray(pad_lens, jnp.int32).ravel()
        if not bool((pad_lens > 0).any()):
            pad_lens = None                  # uniform batch: keep fast path
        elif not supports_ragged_mask(cfg):
            raise ValueError(
                f"pad_lens: arch {cfg.arch!r} (family={cfg.family}, "
                f"mla={cfg.use_mla}, meta={cfg.meta_tokens}, "
                f"flash={cfg.flash_attention}) cannot mask left pads "
                "exactly; batch equal-length prompts instead")
        else:
            cache["pad"] = pad_lens
    # the cache is freshly built above and rebound to the return value, so
    # prefill donates it like the decode step does — without the donation
    # XLA keeps both copies live across the call (compiled-donation audit)
    prefill = jax.jit(make_prefill(cfg), donate_argnums=(1,))
    step = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    logits, cache = prefill(params, cache, prompts, **(extras or {}))
    out = []
    tok = jnp.argmax(logits[:, -1:, :cfg.vocab_size], -1).astype(jnp.int32)
    done = jnp.zeros((B, 1), bool)
    for i in range(max_new):
        if eos_id is not None:
            tok = jnp.where(done, jnp.int32(eos_id), tok)
            done = done | (tok == eos_id)
        out.append(tok)
        if i + 1 == max_new:                 # final token appended — the
            break                            # next logits would be unused
        if eos_id is not None and bool(done.all()):
            break
        logits, cache = step(params, cache, tok)
        lg = logits[:, -1:, :cfg.vocab_size]
        if greedy:
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, lg).astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# paged step factories
# ---------------------------------------------------------------------------

def make_paged_prefill(cfg):
    """Prefill one right-padded token chunk into its pages starting at
    offset ``lens`` (0 for a fresh slot; the cached-token count for later
    chunks of a chunked prefill or after a prefix-cache hit).  Returns
    per-position greedy tokens (the engine picks the last prompt
    position) + the updated pools."""
    def prefill(params, layers, tokens, pages, lens):
        cache = {"layers": layers, "pages": pages, "lens": lens}
        logits, nc, _ = apply_model(params, cfg, tokens, cache=cache)
        toks = jnp.argmax(logits[..., :cfg.vocab_size], -1).astype(jnp.int32)
        return toks, nc["layers"]
    return prefill


def make_paged_decode_step(cfg):
    """One token for every slot against the shared page pool (greedy).
    An expert model appends two numbers to the (n_slots,) tokens, summed
    over its expert layers: the routed (token, expert) assignments the
    step computed and the experts that got one, so the host reads both
    with the tokens in one transfer."""
    def step(params, layers, tokens, pages, lens):
        cache = {"layers": layers, "pages": pages, "lens": lens}
        logits, nc, _, *routed = apply_model(params, cfg, tokens,
                                             cache=cache,
                                             moe_stats=bool(cfg.n_experts))
        toks = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1
                          ).astype(jnp.int32)
        if routed:
            toks = jnp.concatenate([toks, routed[0].astype(jnp.int32)])
        return toks, nc["layers"]
    return step


@functools.lru_cache(maxsize=32)
def _jitted_paged_steps_cached(cfg, mesh):
    return (jax.jit(make_paged_prefill(cfg), donate_argnums=(1,)),
            jax.jit(make_paged_decode_step(cfg), donate_argnums=(1,)))


def _jitted_paged_steps(cfg, mesh):
    """Jitted (prefill, decode) pair memoized per (frozen, hashable) cfg
    and mesh: jax.jit caches on function identity, so without this every
    Engine wraps brand-new closures and re-traces/re-compiles — warmup
    engines could never absorb the compile cost for the engine being
    timed.  The mesh is part of the key because model-code ``constrain``
    and the shard-local encoded kernel read the active mesh at trace
    time — a no-mesh trace must never be reused under a mesh.  Configs
    with unhashable leaves (e.g. ``encoded_infer``'s per-family ``macs``
    dict) fall back to per-engine jit — the pre-memoization behavior."""
    try:
        return _jitted_paged_steps_cached(cfg, mesh)
    except TypeError:
        return (jax.jit(make_paged_prefill(cfg), donate_argnums=(1,)),
                jax.jit(make_paged_decode_step(cfg), donate_argnums=(1,)))


@functools.lru_cache(maxsize=32)
def _jitted_spec_steps_cached(draft_cfg, cfg, k, mesh):
    return (jax.jit(make_spec_draft(draft_cfg, k), donate_argnums=(1,)),
            jax.jit(make_spec_verify(cfg, k), donate_argnums=(1,)))


def _jitted_spec_steps(draft_cfg, cfg, k, mesh):
    """Jitted (draft, verify) pair for speculative decoding, memoized
    like ``_jitted_paged_steps`` (same warm-engine rationale; same
    unhashable-cfg fallback — 'encoded_infer' drafters carry a per-family
    ``macs`` dict)."""
    try:
        return _jitted_spec_steps_cached(draft_cfg, cfg, k, mesh)
    except TypeError:
        return (jax.jit(make_spec_draft(draft_cfg, k), donate_argnums=(1,)),
                jax.jit(make_spec_verify(cfg, k), donate_argnums=(1,)))


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------

class Engine:
    """Continuous-batching greedy serving engine over the paged KV cache.

    Static shapes throughout: decode compiles once for (n_slots, 1) tokens;
    prefill compiles ONCE for the fixed ``(1, prefill_chunk)`` chunk shape
    (padded right — padded writes land in the scratch page or are
    overwritten before they become readable).  Long prompts are prefilled
    one chunk per engine step, interleaved with decode steps for the other
    slots, so a long prefill never freezes every decoding slot (chunked
    prefill; DESIGN.md §7).

    ``prefix_cache=True`` enables vLLM-style prefix caching: full prompt
    pages are hash-indexed after prefill, and admission maps matching
    cached pages into a new request's page table (refcount-shared) so only
    the uncached suffix is prefilled.

    ``reserve='conservative'`` admits a request only when pages for
    prompt+max_new are free (no mid-flight exhaustion);
    ``reserve='optimistic'`` admits on prompt pages alone and grows
    page-by-page, reclaiming unreferenced cached pages and then evicting
    the youngest running request on exhaustion.

    ``spec_decode=k`` (k ≥ 1) turns on self-drafting speculative decoding
    (DESIGN.md §10): each decode round drafts k greedy tokens per slot
    with ``draft_params``/``draft_cfg`` (default: the serving params —
    pure multi-token lookahead) in ONE jitted dispatch, verifies all k+1
    positions in one batched forward through the same paged pools, and
    commits the longest agreeing prefix plus a bonus token.  Greedy
    output is token-identical to ``spec_decode=0`` for ANY drafter; the
    drafter only moves the acceptance rate.  Build a cheap drafter with
    ``repro.serve.encoded.prepare_drafter`` (lower-m-bits encoded path).
    """

    def __init__(self, params, cfg, *, n_slots: int = 4,
                 page_size: int = 16, n_pages: int = 128,
                 max_seq_pages: Optional[int] = None,
                 reserve: str = "conservative", mesh=None,
                 prefill_chunk: int = 32, prefix_cache: bool = False,
                 telemetry: Optional[ServeTelemetry] = None,
                 spec_decode: int = 0, draft_params=None, draft_cfg=None,
                 sanitize: Optional[bool] = None):
        if not supports_paged_cache(cfg):
            raise ValueError(
                f"{cfg.arch!r} cannot serve paged; use ServeEngine")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.params, self.cfg = params, cfg
        self.mesh = mesh
        self.prefill_chunk = prefill_chunk
        self.tel = telemetry if telemetry is not None else \
            ServeTelemetry.disabled()
        if max_seq_pages is None:
            # default: one sequence may hold up to half the pool
            max_seq_pages = max(4, (n_pages - 1) // 2)
        if sanitize is None:
            # opt-in shadow page ledger (DESIGN.md §12): env var so test
            # suites can sanitize every engine without touching call sites
            sanitize = sanitize_enabled()
        self.kv = PagedKVCache(cfg, n_slots, n_pages, page_size,
                               max_seq_pages, sanitize=sanitize)
        self.sched = Scheduler(self.kv, reserve=reserve,
                               prefix_cache=prefix_cache,
                               telemetry=self.tel)
        if mesh is not None:
            # tensor-parallel serving (DESIGN.md §6): params per the
            # path-based rules (folded encoded tensors shard col/row over
            # the model axis), page pools split over kv heads where their
            # count divides the model axis, else replicated; every jitted
            # step below runs under the mesh so model-code constraints and
            # the shard-local encoded kernel see it.
            self.params = _shard_params(params, mesh)
            self.kv.pools = jax.device_put(
                self.kv.pools,
                cache_specs(self.kv.pools, mesh, kv_heads=cfg.n_kv_p))
        self._prefill, self._step = _jitted_paged_steps(cfg, mesh)
        self.spec_k = int(spec_decode)
        if self.spec_k < 0:
            raise ValueError("spec_decode must be >= 0")
        if self.spec_k:
            self.draft_cfg = draft_cfg if draft_cfg is not None else cfg
            check_drafter(draft_params if draft_params is not None
                          else params, self.draft_cfg.mac.mode)
            # the drafter writes into the verifier's pools, so its paged
            # cache geometry must match exactly (layer pytree + shapes)
            want = jax.eval_shape(
                lambda: init_paged_cache(cfg, 2, page_size)["layers"])
            got = jax.eval_shape(
                lambda: init_paged_cache(self.draft_cfg, 2,
                                         page_size)["layers"])
            if (jax.tree_util.tree_structure(want)
                    != jax.tree_util.tree_structure(got)
                    or [(a.shape, a.dtype) for a in
                        jax.tree_util.tree_leaves(want)]
                    != [(a.shape, a.dtype) for a in
                        jax.tree_util.tree_leaves(got)]):
                raise ValueError(
                    "spec_decode drafter cache geometry mismatch: "
                    "draft_cfg must produce the same paged KV layout "
                    "(layers/kv-heads/head-dim/dtype) as the serving cfg")
            if draft_params is None:
                self.draft_params = self.params   # sharded copy if mesh
            else:
                self.draft_params = (_shard_params(draft_params, mesh)
                                     if mesh is not None else draft_params)
            self._draft, self._verify = _jitted_spec_steps(
                self.draft_cfg, cfg, self.spec_k, mesh)
        # compile accounting (DESIGN.md §13): deltas over the jitted
        # steps' cache sizes since THIS engine attached — shared warm
        # steps start at zero, so the counts are compiles this engine
        # caused (a leaked shape retracing decode shows up immediately)
        self.jit_tracker = CompileTracker()
        self.jit_tracker.track("prefill", self._prefill)
        self.jit_tracker.track("decode", self._step)
        if self.spec_k:
            self.jit_tracker.track("draft", self._draft)
            self.jit_tracker.track("verify", self._verify)
        self.jit_tracker.track("copy_page", _copy_page_jit)
        self.requests = {}
        self._next_rid = 0
        self.clock = 0                     # logical steps
        self._init_metrics()

    def _init_metrics(self) -> None:
        """Registry-backed engine bookkeeping (DESIGN.md §9): replaces
        the old raw ``self.metrics`` dict — that name survives as a
        read-only snapshot property for callers/tests."""
        reg = self.tel.registry
        self._mac = self.cfg.mac.mode
        self._c_steps = reg.counter("engine_steps", "engine loop ticks")
        self._c_decode = reg.counter("decode_tokens",
                                     "tokens produced by decode steps")
        self._c_prefill_tok = reg.counter("prefill_tokens",
                                          "prompt tokens ingested")
        self._c_prefills = reg.counter("prefills", "completed prefills")
        self._c_chunks = reg.counter("prefill_chunks",
                                     "prefill chunk dispatches")
        self._c_occ = reg.counter("occupancy_sum",
                                  "per-step busy-slot fraction, summed")
        self._c_stalls = reg.counter(
            "stalls", "decode steps a request sat page-starved")
        self._c_rejects = reg.counter("rejects",
                                      "requests rejected at submit")
        # expert models: routed work of the decode steps, summed over
        # expert layers (every slot's row is routed, idle ones included)
        self._c_moe_assign = reg.counter(
            "moe_assignments",
            "routed (token, expert) pairs computed by decode steps")
        self._c_moe_active = reg.counter(
            "moe_experts_active",
            "experts that got at least one token in a decode step, summed "
            "over steps and expert layers")
        # decode steps' layer calls that ran the paged kernel's
        # head-batched body: a static choice, the same every step
        self._c_head_batched = reg.counter(
            "paged_attn_head_batched_layers",
            "layer calls of decode steps through the paged-attention "
            "kernel's head-batched body (one query row a kv head)")
        self._head_batched_layers = (self.cfg.n_layers
                                     if paged_head_batched(self.cfg) else 0)
        self._c_jit = reg.counter(
            "jit_compiles",
            "XLA compilations of the jitted serving steps since this "
            "engine attached (labeled fn=prefill|decode|draft|verify|"
            "copy_page)")
        self._h_step = reg.histogram("step_ms", "engine step wall ms",
                                     buckets=(1, 2, 5, 10, 25, 50, 100,
                                              250, 500, 1000))
        self._g_pages_free = reg.gauge("pages_free",
                                       "strictly free pool pages")
        self._g_pages_cached = reg.gauge(
            "pages_cached", "ref-0 pages parked in the prefix LRU tier")
        self._g_pages_held = reg.gauge("pages_held",
                                       "pages referenced by sequences")
        self._g_queue = reg.gauge("queue_depth", "requests waiting")
        self._g_hit_win = reg.gauge(
            "prefix_windowed_hit_rate",
            "prefix-cache hit rate over recent admissions")
        # quantized-pool capacity gauges (DESIGN.md §11): bytes/token is
        # a property of the pool layout, capacity of the page budget —
        # both constant per engine, published so dashboards can compare
        # kv-dtype deployments at a glance
        self._g_kv_bpt = reg.gauge(
            "kv_bytes_per_token",
            "pool bytes per cached token across layers (values + scales)")
        self._g_kv_cap = reg.gauge(
            "kv_capacity_tokens",
            "token capacity of the allocatable page pool")
        self._g_kv_bpt.set(self.kv.kv_bytes_per_token())
        self._g_kv_cap.set((self.kv.n_pages - 1) * self.kv.page_size)
        # speculative decoding (DESIGN.md §10)
        self._c_spec_rounds = reg.counter(
            "spec_rounds", "speculative draft+verify rounds")
        self._c_spec_prop = reg.counter(
            "spec_draft_tokens", "draft tokens considered by verification")
        self._c_spec_acc = reg.counter(
            "spec_accepted_tokens", "draft tokens accepted by verification")
        self._g_spec_rate = reg.gauge(
            "spec_acceptance_rate",
            "accepted / considered draft tokens, cumulative")

    @property
    def metrics(self) -> dict:
        """Read-only snapshot with the historical key set (the engine
        itself increments registry metrics, not this dict)."""
        return {
            "steps": int(self._c_steps.total()),
            "decode_tokens": int(self._c_decode.total()),
            "prefill_tokens": int(self._c_prefill_tok.total()),
            "prefills": int(self._c_prefills.total()),
            "prefill_chunks": int(self._c_chunks.total()),
            "occupancy_sum": self._c_occ.total(),
            "moe_assignments": int(self._c_moe_assign.total()),
            "moe_experts_active": int(self._c_moe_active.total()),
            "paged_attn_head_batched_layers":
                int(self._c_head_batched.total()),
        }

    @property
    def _steps(self) -> int:
        return int(self._c_steps.total())

    def _mesh_ctx(self):
        return _mesh_scope(self.mesh)

    # ---- API ---------------------------------------------------------------

    def submit(self, prompt, max_new: int = 32,
               eos_id: Optional[int] = None) -> int:
        prompt = np.asarray(prompt, np.int32).ravel()
        total = int(prompt.shape[0]) + max_new
        tr = self.tel.tracer
        if total > self.kv.max_seq_tokens:
            # reject BEFORE registering: an admitted oversize request
            # would outgrow its fixed (max_seq_pages,)-row page table and
            # die mid-serve deep in PagedKVCache.set_pages — and a raise
            # after registration would leak a dead rid into self.requests
            self._c_rejects.inc()
            if tr.enabled:
                tr.instant("reject", tid=TID_ENGINE, cat="lifecycle",
                           args={"plen": int(prompt.shape[0]),
                                 "max_new": max_new,
                                 "limit": self.kv.max_seq_tokens})
            raise ValueError(
                f"request of {prompt.shape[0]} prompt + {max_new} new "
                f"tokens exceeds the {self.kv.max_seq_tokens}-token "
                f"per-sequence limit (max_seq_pages={self.kv.max_seq_pages}"
                f" × page_size={self.kv.page_size}); raise max_seq_pages "
                "or split the request")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new=max_new, eos_id=eos_id,
                      t_arrive=time.perf_counter())
        self.requests[rid] = req
        self.sched.submit(req)
        if tr.enabled:
            tr.thread(req_tid(rid), f"req {rid}")
            tr.instant("submit", tid=req_tid(rid), cat="lifecycle",
                       args={"rid": rid, "plen": int(prompt.shape[0]),
                             "max_new": max_new}, t_s=req.t_arrive)
        return rid

    @property
    def busy(self) -> bool:
        return self.sched.busy

    def run(self, max_steps: int = 100_000) -> dict:
        """Drive the loop until the queue and all slots drain.

        ``max_steps`` bounds THIS call *exactly*: at most ``max_steps``
        steps run before the livelock guard raises (the guard used to
        fire one step late), and the bound is per-call — ``engine_steps``
        is lifetime-cumulative, so a reused warm engine (the memoized-jit
        warmup flow) must not trip on its second trace.  With
        ``telemetry.profile_dir`` set, the whole drain runs under a
        ``jax.profiler`` trace."""
        start = self._steps
        with profiler_trace(self.tel.profile_dir):
            while self.busy:
                if self._steps - start >= max_steps:
                    raise RuntimeError(
                        f"engine did not drain within {max_steps} steps "
                        "(livelock?)")
                self.step()
        return self.results()

    def results(self) -> dict:
        # analysis: allow(host-sync): packs host-side int lists, no device
        # transfer — runs once per drain, not per step
        return {rid: np.asarray(r.out, np.int32)
                for rid, r in self.requests.items() if r.state == FINISHED}

    # ---- one scheduler tick ------------------------------------------------

    def step(self) -> None:
        """One scheduler tick, instrumented: the tick is the ``serve.step``
        span, with a child span at each layer boundary inside it
        (DESIGN.md §9); per-step wall time lands in the ``step_ms``
        histogram, and the allocator/queue gauges refresh after the
        work (``serve.gauges``)."""
        tr = self.tel.tracer
        with tr.span("serve.step", args={"step": self._steps + 1}) as span:
            t0 = time.perf_counter()
            try:
                n_chunks, n_active = self._step_impl()
                span.set_metadata(n_active=n_active, n_chunks=n_chunks)
            finally:
                self._h_step.observe((time.perf_counter() - t0) * 1e3,
                                     mac=self._mac)
                with tr.span("serve.gauges"):
                    self._update_gauges()
                    if self.kv.ledger is not None:
                        # sanitizer: per-step page conservation +
                        # shadow/real cross-check (DESIGN.md §12)
                        self.kv.ledger.verify()

    def _update_gauges(self) -> None:
        """Pool / queue / prefix gauges (free–held–cached page split,
        DESIGN.md §9)."""
        al = self.kv.alloc
        self._g_pages_free.set(al.n_free_strict)
        self._g_pages_cached.set(al.n_cached)
        self._g_pages_held.set(al.n_held)
        self._g_queue.set(len(self.sched.queue))
        if self.sched.prefix is not None:
            self._g_hit_win.set(self.sched.prefix.windowed_hit_rate)

    def _step_impl(self) -> tuple:
        """The tick's work; returns (prefill chunks run, rows decoded)."""
        self._c_steps.inc()
        self.clock += 1
        if self.tel.drift is not None and not self.spec_k:
            # under spec decoding drift comes free from verification
            # (observe_agreement in _spec_round) — no replay forwards
            self.tel.drift.maybe_sample(
                self._steps, self.params, self.cfg,
                [r.prompt for r in self.sched.slots if r is not None])
        # admit and run ONE prefill chunk per prefilling slot; a short
        # prefill that completes and finishes at EOS frees its slot and
        # pages, so keep admitting until no new slot fills (each request
        # still runs at most one chunk this step)
        chunked = set()
        while True:
            self._admit()
            todo = [r for r in self.sched.prefilling()
                    if r.rid not in chunked]
            if not todo:
                break
            for req in todo:
                chunked.add(req.rid)
                self._prefill_chunk(req)
        if self.spec_k:
            return len(chunked), self._spec_round(chunked)
        tr = self.tel.tracer
        with tr.span("serve.grow"):
            active = self._runnable()
        # occupancy counts every slot that did work this step: decoding
        # slots plus slots that ran a prefill chunk (a request that
        # finished its prefill and decodes in the same step counts once)
        worked = set(chunked) | {r.rid for r in active}
        self._c_occ.inc(len(worked) / self.kv.n_slots)
        if not active:
            if chunked or not self.sched.queue:
                return len(chunked), 0     # prefill progress / fully idle
            raise RuntimeError(
                "page pool too small for the queued request "
                f"(need {self.sched._pages_needed(self.sched.queue[0])}"
                f" pages, {self.kv.alloc.n_free} free)")
        with tr.span("serve.inputs"):
            tokens = np.zeros((self.kv.n_slots, 1), np.int32)
            # refresh lens for every slotted request (stalled ones
            # included, so their dummy write this step lands past their
            # pages → scratch; mid-prefill slots' dummy write lands at
            # their cursor and is overwritten by their next chunk before
            # it is ever read)
            for r in self.sched.slots:
                if r is not None:
                    self.kv.set_len(r.slot, r.n_cached)
            for req in active:
                tokens[req.slot, 0] = req.out[-1]
        with tr.span("serve.decode", args={"n_active": len(active)}):
            with tr.span("serve.dispatch"), self._mesh_ctx():
                # the host arrays go to the jitted step as they are: its
                # call puts them on the device, with less host time than
                # a jnp.asarray each.  The step is read back below before
                # the page table or lens change again.
                toks, self.kv.pools = self._step(
                    self.params, self.kv.pools, tokens, self.kv.ptab,
                    self.kv.lens)
            with tr.span("serve.wait"):
                # analysis: allow(host-sync): THE step boundary — decoded
                # tokens must reach the host for scheduling (eos/done/emit)
                toks = np.asarray(toks)
            if self.cfg.n_experts:
                n = self.kv.n_slots
                self._c_moe_assign.inc(int(toks[n]))
                self._c_moe_active.inc(int(toks[n + 1]))
            self._c_head_batched.inc(self._head_batched_layers)
        with tr.span("serve.commit"):
            now = time.perf_counter()
            self._c_decode.inc(len(active), mac=self._mac)
            for req in active:
                req.n_cached += 1
                req.out.append(int(toks[req.slot]))
                if req.done:
                    self.sched.finish(req, now)
                    self._trace_finish(req)
        return len(chunked), len(active)

    def _spec_round(self, chunked) -> int:
        """One speculative draft+verify round (DESIGN.md §10).

        Per active slot: draft k greedy tokens with the drafter (ONE
        jitted dispatch — the k steps are unrolled in the trace), verify
        all k+1 positions in one batched dense forward (which scatters
        dense K/V over the drafted positions BEFORE attending, so every
        committed cache position is dense-exact), then commit the longest
        agreeing prefix plus the verifier's bonus token.  Rollback of
        rejected tokens is pure host arithmetic on ``n_cached`` — the
        rejected positions sit past the device lens (masked on read) and
        are overwritten by the next round's scatter, and no pages move.

        Each slot's acceptance is capped at its ensured *write window* w:
        ``ensure_write_window`` guarantees w exclusively-owned positions,
        so verify logits past w-1 may have read scratch-page garbage and
        must not be trusted (a lucky argmax match there would commit a
        token whose KV was never written).  Slots that cannot even secure
        w = 1 stall exactly like the non-speculative path.  Non-active
        slotted requests get their device lens pushed to the end of their
        owned pages so the round's k+1 batched writes land in the scratch
        page — never in a page a peer might share.  Returns the rows
        that decoded."""
        k = self.spec_k
        tr = self.tel.tracer
        with tr.span("serve.grow"):
            active, wins = self._write_windows()
        worked = set(chunked) | {r.rid for r in active}
        self._c_occ.inc(len(worked) / self.kv.n_slots)
        if not active:
            if chunked or not self.sched.queue:
                return 0
            raise RuntimeError(
                "page pool too small for the queued request "
                f"(need {self.sched._pages_needed(self.sched.queue[0])}"
                f" pages, {self.kv.alloc.n_free} free)")
        with tr.span("serve.inputs"):
            act = {r.rid for r in active}
            for r in self.sched.slots:
                if r is None:
                    continue
                if r.rid in act:
                    self.kv.set_len(r.slot, r.n_cached)
                else:
                    self.kv.set_len(r.slot,
                                    len(r.pages) * self.kv.page_size)
            tokens = np.zeros((self.kv.n_slots, 1), np.int32)
            for req in active:
                tokens[req.slot, 0] = req.out[-1]
            pages_dev, lens_dev = self.kv.pages_dev(), self.kv.lens_dev()
            tok_dev = jnp.asarray(tokens)
        args = {"k": k, "n_active": len(active)}
        with tr.span("serve.draft", args=args), \
                tr.span("serve.dispatch"), self._mesh_ctx():
            d_toks, self.kv.pools = self._draft(
                self.draft_params, self.kv.pools, tok_dev,
                pages_dev, lens_dev)
        with tr.span("serve.verify", args=args):
            with tr.span("serve.dispatch"), self._mesh_ctx():
                # d_toks stays on device: verify concatenates it with the
                # round's input tokens inside the trace, so draft → verify
                # is two back-to-back dispatches with no host sync between
                v_toks, self.kv.pools = self._verify(
                    self.params, self.kv.pools, tok_dev, d_toks,
                    pages_dev, lens_dev)
            with tr.span("serve.wait"):
                # analysis: allow(host-sync): the round boundary —
                # accept/rollback is host arithmetic over both token sets
                d_np, v_np = np.asarray(d_toks), np.asarray(v_toks)
        with tr.span("serve.commit"):
            self._commit_round(active, wins, d_np, v_np)
        return len(active)

    def _write_windows(self):
        """Speculative page growth: the rows that secured a write window
        this round (oldest first) and each one's window."""
        k = self.spec_k
        tr = self.tel.tracer
        active, wins = [], {}
        for req in sorted(self.sched.active(),
                          key=lambda r: (r.t_arrive, r.rid)):
            if req.state != DECODING:
                continue                    # evicted mid-loop by a peer
            want = min(k + 1, req.max_new - len(req.out))
            if self.sched.ensure_write_window(req, want):
                wins[req.rid] = want
            elif want > 1 and self.sched.ensure_write_window(req, 1):
                wins[req.rid] = 1
            else:
                self._c_stalls.inc()
                if tr.enabled:
                    tr.instant("stall", tid=req_tid(req.rid),
                               cat="lifecycle", args={"rid": req.rid})
                continue
            active.append(req)
        active = [r for r in active if r.state == DECODING]  # late evicts
        return active, wins

    def _commit_round(self, active, wins, d_np, v_np) -> None:
        """Accept each row's longest agreeing draft prefix plus the
        verifier's bonus token, and update the speculation counters."""
        k = self.spec_k
        now = time.perf_counter()
        r_acc = r_cons = 0
        for req in active:
            cons = min(k, wins[req.rid] - 1)   # draft tokens we may trust
            d, v = d_np[req.slot], v_np[req.slot]
            n_acc = greedy_accept(d[:cons], v[:cons])
            emit = [int(x) for x in d[:n_acc]] + [int(v[n_acc])]
            emit = emit[:req.max_new - len(req.out)]
            if req.eos_id is not None:
                for j, t in enumerate(emit):
                    if t == req.eos_id:
                        emit = emit[:j + 1]
                        break
            req.out.extend(emit)
            req.n_cached += len(emit)
            self._c_decode.inc(len(emit), mac=self._mac)
            r_acc += n_acc
            r_cons += cons
            if req.done:
                self.sched.finish(req, now)
                self._trace_finish(req)
        self._c_spec_rounds.inc()
        self._c_spec_prop.inc(r_cons)
        self._c_spec_acc.inc(r_acc)
        prop, acc = self._c_spec_prop.total(), self._c_spec_acc.total()
        if prop:
            self._g_spec_rate.set(acc / prop)
        if self.tel.drift is not None:
            # drift for free: draft-vs-target top-1 agreement measured on
            # the verifier's dense logits — no replay forwards
            self.tel.drift.observe_agreement(r_acc, r_cons)

    def _admit(self) -> None:
        self.sched.admissions()

    def _runnable(self):
        """Decoding requests with a page for their next write, oldest first
        (growth may evict younger requests; a request that can neither grow
        nor evict stalls for this step)."""
        out = []
        tr = self.tel.tracer
        for req in sorted(self.sched.active(),
                          key=lambda r: (r.t_arrive, r.rid)):
            if req.state != DECODING:
                continue
            if self.sched.ensure_page(req):
                out.append(req)
            else:
                self._c_stalls.inc()
                if tr.enabled:
                    tr.instant("stall", tid=req_tid(req.rid),
                               cat="lifecycle", args={"rid": req.rid})
        return out

    def _prefill_chunk(self, req: Request) -> None:
        """Run one fixed-shape prefill chunk for a PREFILLING request,
        starting at its cursor (``n_cached`` — nonzero after a prefix-cache
        hit or for later chunks).  On the final chunk the request flips to
        DECODING; a fresh request takes its first token from the last
        prompt position, while a re-admitted evicted request keeps the
        tokens it already generated (``prefill_stream`` re-ingests them)
        and its original ``t_first``."""
        stream = req.prefill_stream()
        target = req.prefill_target
        start = req.n_cached
        C = self.prefill_chunk
        chunk = stream[start:start + C]
        n = int(chunk.shape[0])
        slot = req.slot
        tr = self.tel.tracer
        with tr.span("serve.prefill_chunk",
                     args={"rid": req.rid, "start": start, "n": n}):
            with tr.span("serve.inputs"):
                padded = np.zeros((1, C), np.int32)
                padded[0, :n] = chunk
                args = (jnp.asarray(padded),
                        self.kv.pages_dev()[slot:slot + 1],
                        jnp.asarray([start], jnp.int32))
            with tr.span("serve.dispatch"), self._mesh_ctx():
                toks, self.kv.pools = self._prefill(
                    self.params, self.kv.pools, *args)
            req.n_cached = start + n
            self.kv.set_len(slot, req.n_cached)
            self._c_chunks.inc(1, mac=self._mac)
            self._c_prefill_tok.inc(n, mac=self._mac)
            if req.n_cached < target:
                return                      # more chunks to go
            now = time.perf_counter()
            req.state = DECODING
            req.t_prefill_done = now
            self._c_prefills.inc()
            self.sched.note_prefilled(req)  # prompt pages → prefix index
            if not req.out:
                with tr.span("serve.wait"):
                    # analysis: allow(host-sync): first-token read at
                    # prefill completion — seeds the decode stream on host
                    first = int(np.asarray(toks)[0, req.plen - 1 - start])
                req.out = [first]
                if req.t_first is None:     # honest TTFT across evictions
                    req.t_first = now
                    if tr.enabled:
                        tr.instant("first_token", tid=req_tid(req.rid),
                                   cat="lifecycle", args={"rid": req.rid},
                                   t_s=now)
            if req.done:                    # eos on the very first token
                self.sched.finish(req, now)
                self._trace_finish(req)

    def _trace_finish(self, req: Request) -> None:
        """Emit the finished request's lifecycle phase spans on its own
        track.  ``queued`` [submit → admit], ``prefill`` [admit → prefill
        done], ``decode`` [prefill done → finish] are CONTIGUOUS by
        construction, so their durations sum to the request latency
        exactly — the reconciliation the telemetry bench asserts.  (After
        an eviction the timestamps are the final round's, so the
        ``queued`` span absorbs the earlier rounds; the sum invariant
        still holds.)"""
        tr = self.tel.tracer
        if not tr.enabled or req.t_finish is None:
            return
        tid = req_tid(req.rid)
        t_admit = req.t_admit if req.t_admit is not None else req.t_arrive
        t_pf = req.t_prefill_done if req.t_prefill_done is not None \
            else t_admit
        args = {"rid": req.rid, "n_out": len(req.out),
                "evictions": req.n_evictions}
        tr.complete("request", req.t_arrive, req.t_finish, tid=tid,
                    cat="lifecycle", args=args)
        tr.complete("queued", req.t_arrive, t_admit, tid=tid,
                    cat="phase")
        tr.complete("prefill", t_admit, t_pf, tid=tid, cat="phase")
        tr.complete("decode", t_pf, req.t_finish, tid=tid, cat="phase")

    # ---- reporting ---------------------------------------------------------

    def stats(self) -> dict:
        """Snapshot of the registry plus request-derived percentiles.

        Latency needs ``t_finish`` so it is over finished requests;
        TTFT is over EVERY request that has produced a first token —
        in-flight included (the old finished-only version silently
        dropped slow in-flight requests, biasing TTFT optimistic under
        load).  TPOT = time-per-output-token after the first,
        ``(t_finish - t_first) / (len(out) - 1)``, over finished
        requests with ≥ 2 tokens.  Percentiles interpolate via the
        shared ``repro.obs.percentile``."""
        reqs = list(self.requests.values())
        fin = [r for r in reqs if r.state == FINISHED]
        lat = [(r.t_finish - r.t_arrive) for r in fin
               if r.t_finish is not None]
        ttft = [(r.t_first - r.t_arrive) for r in reqs
                if r.t_first is not None]
        tpot = [(r.t_finish - r.t_first) / (len(r.out) - 1)
                for r in fin
                if r.t_finish is not None and r.t_first is not None
                and len(r.out) > 1]

        pfx = self.sched.prefix
        on = pfx is not None        # NOT truthiness — an empty index is falsy
        al = self.kv.alloc
        jit_total = self.jit_tracker.publish(self._c_jit)
        m = dict(self.metrics)
        m.update({
            "finished": len(fin),
            "rejects": int(self._c_rejects.total()),
            "stalls": int(self._c_stalls.total()),
            "evictions": self.sched.n_evictions,
            "cow_copies": self.sched.n_cow_copies,
            "prefix_cache": on,
            "prefix_hit_tokens": pfx.hit_tokens if on else 0,
            "prefix_lookup_tokens": pfx.lookup_tokens if on else 0,
            "prefix_hit_rate": pfx.hit_rate if on else 0.0,
            "prefix_windowed_hit_rate": pfx.windowed_hit_rate if on else 0.0,
            "prefix_pages_indexed": len(pfx) if on else 0,
            "prefill_chunk": self.prefill_chunk,
            "occupancy": (m["occupancy_sum"] / m["steps"]
                          if m["steps"] else 0.0),
            "latency_p50_s": percentile(lat, 50),
            "latency_p99_s": percentile(lat, 99),
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p99_s": percentile(ttft, 99),
            "tpot_p50_s": percentile(tpot, 50),
            "tpot_p99_s": percentile(tpot, 99),
            "step_ms_p50": self._h_step.percentile(50, mac=self._mac),
            "step_ms_p99": self._h_step.percentile(99, mac=self._mac),
            "pages_free": al.n_free_strict,
            "pages_cached": al.n_cached,
            "pages_held": al.n_held,
            "kv_pool_bytes": self.kv.mem_bytes(),
            "kv_bytes_per_token": self.kv.kv_bytes_per_token(),
            "kv_capacity_tokens": (self.kv.n_pages - 1) * self.kv.page_size,
            "kv_cache_dtype": self.cfg.kv_cache_dtype,
            "page_size": self.kv.page_size,
            "n_pages": self.kv.n_pages,
            "n_slots": self.kv.n_slots,
            "mac_mode": self.cfg.mac.mode,
            "jit_compiles": jit_total,
            "mesh": (dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
                     if self.mesh is not None else None),
        })
        if self.spec_k:
            rounds = int(self._c_spec_rounds.total())
            prop = int(self._c_spec_prop.total())
            acc = int(self._c_spec_acc.total())
            m.update({
                "spec_decode_k": self.spec_k,
                "spec_rounds": rounds,
                "spec_draft_tokens": prop,
                "spec_accepted_tokens": acc,
                "spec_acceptance_rate": acc / prop if prop else 0.0,
                "spec_tokens_per_round": (m["decode_tokens"] / rounds
                                          if rounds else 0.0),
                "draft_mac_mode": self.draft_cfg.mac.mode,
            })
        if self.tel.drift is not None and self.tel.drift.last is not None:
            m["encoded_drift_top1"] = self.tel.drift.last
        return m


# ---------------------------------------------------------------------------
# static-batch engine (baseline / non-paged archs)
# ---------------------------------------------------------------------------

class ServeEngine:
    """Static-batch serving engine: fixed-batch greedy decode.

    Requests are chunked into fixed batches, left-padded to the chunk's
    longest prompt, and each chunk runs ``generate`` to completion before
    the next starts.  It serves the architectures the continuous-batching
    ``Engine`` rejects (latent attention, recurrent state).
    """

    def __init__(self, params, cfg, batch_slots: int = 8,
                 max_len: int = 512, mesh=None):
        self.params, self.cfg = params, cfg
        self.mesh = mesh
        if mesh is not None:
            self.params = _shard_params(params, mesh)
        self.max_len = max_len
        self.batch_slots = batch_slots

    def run(self, requests: List[np.ndarray], max_new: int = 32,
            eos_id: Optional[int] = None) -> List[np.ndarray]:
        """Serve a list of prompt arrays; returns generated ids per request.

        Ragged prompts are left-padded to the chunk's longest; where the
        arch supports it (``supports_ragged_mask``) the pad slots are
        masked out of attention and positions offset per row, so each
        request decodes exactly as it would alone.  Archs whose state
        ingests pads (MLA, ssm/xlstm hybrids, meta tokens) keep the
        unmasked behavior — batch equal-length prompts for exactness."""
        results = []
        ragged_ok = supports_ragged_mask(self.cfg)
        with _mesh_scope(self.mesh):
            for i in range(0, len(requests), self.batch_slots):
                chunk = requests[i:i + self.batch_slots]
                S = max(len(r) for r in chunk)
                batch = np.zeros((len(chunk), S), np.int32)
                pad = np.zeros((len(chunk),), np.int32)
                for j, r in enumerate(chunk):
                    batch[j, S - len(r):] = r          # left-pad
                    pad[j] = S - len(r)
                toks = generate(self.params, self.cfg, jnp.asarray(batch),
                                max_new=max_new, max_len=S + max_new + 8 +
                                (self.cfg.meta_tokens or 0), eos_id=eos_id,
                                pad_lens=pad if ragged_ok else None)
                results.extend(np.asarray(toks))
        return results
