"""Paged-KV serving subsystem: allocator reuse/exhaustion, scheduler
admission & eviction, and paged greedy decode == dense generate()
token-for-token (incl. EOS handling)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import init_model, init_paged_cache, supports_paged_cache
from repro.serve import (Engine, ServeEngine, generate, PageAllocator,
                         PagedKVCache, Scheduler, Request, pages_for,
                         DECODING, FINISHED)


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
# allocator
# ---------------------------------------------------------------------------

def test_page_allocator_reuse_and_exhaustion():
    al = PageAllocator(8)                       # pages 1..7 usable
    assert al.n_free == 7
    a = al.alloc(3)
    b = al.alloc(4)
    assert a is not None and b is not None
    assert 0 not in a + b                       # page 0 is scratch
    assert al.alloc(1) is None                  # exhausted → all-or-nothing
    assert al.n_free == 0
    al.free(a)
    assert al.n_free == 3
    with pytest.raises(ValueError):
        al.free(a)                              # double free
    c = al.alloc(3)                             # freed pages are reused
    assert sorted(c) == sorted(a)
    assert pages_for(1, 4) == 1 and pages_for(9, 4) == 3


# ---------------------------------------------------------------------------
# scheduler (host-side only — no model)
# ---------------------------------------------------------------------------

def test_scheduler_admits_after_slot_frees(qwen):
    cfg, _ = qwen
    kv = PagedKVCache(cfg, n_slots=1, n_pages=32, page_size=4,
                      max_seq_pages=8)
    sched = Scheduler(kv)
    r1 = Request(rid=0, prompt=np.zeros(5, np.int32), max_new=4)
    r2 = Request(rid=1, prompt=np.zeros(3, np.int32), max_new=4)
    sched.submit(r1)
    sched.submit(r2)
    adm = sched.admissions()
    assert [r.rid for _, r in adm] == [0]       # one slot → r2 waits
    r1.state = DECODING
    assert sched.admissions() == []
    sched.finish(r1, t=1.0)
    assert r1.state == FINISHED
    assert kv.alloc.n_free == 31                # r1's pages were returned
    adm = sched.admissions()                    # the step after the slot
    assert [r.rid for _, r in adm] == [1]       # frees, r2 is admitted
    assert np.all(kv.ptab[0, :len(r2.pages)] == r2.pages)


def test_scheduler_blocks_on_page_budget(qwen):
    cfg, _ = qwen
    kv = PagedKVCache(cfg, n_slots=2, n_pages=5, page_size=4,
                      max_seq_pages=4)          # 4 usable pages
    sched = Scheduler(kv)                       # conservative reserve
    r1 = Request(rid=0, prompt=np.zeros(8, np.int32), max_new=4)   # 3 pages
    r2 = Request(rid=1, prompt=np.zeros(8, np.int32), max_new=4)   # 3 pages
    sched.submit(r1)
    sched.submit(r2)
    adm = sched.admissions()
    assert [r.rid for _, r in adm] == [0]       # free slot, but no pages
    r1.state = DECODING
    sched.finish(r1, t=1.0)
    assert [r.rid for _, r in sched.admissions()] == [1]


# ---------------------------------------------------------------------------
# paged decode vs dense path
# ---------------------------------------------------------------------------

def test_paged_greedy_matches_dense_generate(qwen):
    cfg, params = qwen
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=32)
    prompts = _prompts(cfg, (5, 12, 9))        # 3 reqs > 2 slots: queueing
    rids = [eng.submit(p, max_new=6) for p in prompts]
    res = eng.run()
    assert eng.stats()["finished"] == 3
    for rid, p in zip(rids, prompts):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=6))[0]
        assert res[rid].tolist() == ref.tolist(), f"req {rid} diverged"


def test_paged_matches_dense_with_sliding_window():
    """gemma2 reduced: alternating local/global layers, softcaps, post-norm;
    prompt long enough that the 64-token window actually masks."""
    import dataclasses
    cfg = get_config("gemma2-27b").reduced()
    cfg = dataclasses.replace(cfg, sliding_window=16)
    params = init_model(jax.random.PRNGKey(1), cfg)
    prompt = _prompts(cfg, (40,), seed=3)[0]
    eng = Engine(params, cfg, n_slots=1, page_size=8, n_pages=16)
    rid = eng.submit(prompt, max_new=5)
    res = eng.run()
    ref = np.asarray(generate(params, cfg, jnp.asarray(prompt)[None],
                              max_new=5))[0]
    assert res[rid].tolist() == ref.tolist()


def test_eviction_under_page_pressure(qwen):
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 3), seed=1)
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=7,
                 reserve="optimistic")          # 6 usable pages < 4+4 needed
    rids = [eng.submit(p, max_new=10) for p in prompts]
    res = eng.run()
    st = eng.stats()
    assert st["evictions"] >= 1                 # someone got preempted...
    assert st["finished"] == 2                  # ...yet everyone finished
    for rid, p in zip(rids, prompts):           # recompute kept greedy exact
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=10))[0]
        assert res[rid].tolist() == ref.tolist()
    # eviction preserves generated tokens (re-prefilled, not regenerated):
    # every output token was decoded exactly once despite the eviction
    assert st["decode_tokens"] == sum(len(res[r]) - 1 for r in rids)


def test_eviction_keeps_tokens_and_ttft(qwen):
    """Drive the engine step-by-step across an eviction: the victim's
    already-generated tokens survive (re-prefilled via prompt+out), and
    its t_first is not overwritten by the re-prefill (honest TTFT)."""
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 3), seed=1)
    eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=7,
                 reserve="optimistic")
    rids = [eng.submit(p, max_new=10) for p in prompts]
    evicted = None
    while evicted is None and eng.busy:
        eng.step()
        for r in eng.requests.values():
            if r.n_evictions > 0:
                evicted = r
    assert evicted is not None
    kept_out = list(evicted.out)
    kept_t_first = evicted.t_first
    assert kept_out, "victim had generated tokens before eviction"
    assert kept_t_first is not None
    eng.run()
    assert evicted.out[:len(kept_out)] == kept_out   # tokens survived
    assert evicted.t_first == kept_t_first           # TTFT not rewritten
    ref = np.asarray(generate(
        params, cfg, jnp.asarray(prompts[rids.index(evicted.rid)])[None],
        max_new=10))[0]
    assert evicted.out == ref.tolist()


def test_prefill_chunk_overflow_lands_in_scratch(qwen):
    """Prompt whose padded prefill chunk exceeds the per-sequence page
    table: the overflow writes must hit the scratch page, not wrap onto
    the last real page (which holds live prompt K/V)."""
    cfg, params = qwen
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=64,
                 max_seq_pages=5, prefill_chunk=32)   # 20-token cap < chunk
    p = _prompts(cfg, (18,), seed=6)[0]
    rid = eng.submit(p, max_new=2)
    res = eng.run()
    ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                              max_new=2))[0]
    assert res[rid].tolist() == ref.tolist()


def test_retry_admission_gets_pages_before_decode(qwen):
    """A request admitted on the starvation-retry path (slot freed by an
    EOS-at-prefill finish) must still get a page for its first decode
    write when its prompt exactly fills its pages (optimistic mode)."""
    cfg, params = qwen
    pa, pb = _prompts(cfg, (8, 8), seed=7)      # plen == 2 * page_size
    ref_a = np.asarray(generate(params, cfg, jnp.asarray(pa)[None],
                                max_new=4))[0]
    ref_b = np.asarray(generate(params, cfg, jnp.asarray(pb)[None],
                                max_new=4))[0]
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=4,
                 max_seq_pages=3, reserve="optimistic")   # 3 usable pages
    ra = eng.submit(pa, max_new=4, eos_id=int(ref_a[0]))  # dies at prefill
    rb = eng.submit(pb, max_new=4)
    res = eng.run()
    assert res[ra].tolist() == [int(ref_a[0])]
    assert res[rb].tolist() == ref_b.tolist()


def test_run_max_steps_counts_per_call(qwen):
    """``run(max_steps=...)`` bounds THIS call: a reused warm engine used
    to trip the livelock guard on its second run because the guard
    compared lifetime-cumulative metrics['steps']."""
    cfg, params = qwen
    p = _prompts(cfg, (6,), seed=12)[0]
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=32)
    eng.submit(p, max_new=5)
    eng.run(max_steps=50)
    steps_first = eng.metrics["steps"]
    assert steps_first > 0
    # a second run whose budget is below the cumulative count must pass
    assert steps_first < 50
    eng.submit(p, max_new=5)
    out = eng.run(max_steps=steps_first)        # would raise pre-fix
    assert len(out) == 2
    # a genuinely too-small budget still trips the guard
    eng.submit(p, max_new=5)
    with pytest.raises(RuntimeError, match="did not drain"):
        eng.run(max_steps=1)


def test_run_max_steps_bound_is_exact(qwen):
    """The livelock guard permits at most ``max_steps`` steps — the old
    ``>`` comparison let max_steps+1 through, so a workload needing
    exactly K steps passed a K-1 budget."""
    cfg, params = qwen
    p = _prompts(cfg, (6,), seed=12)[0]

    def fresh():
        eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=32)
        eng.submit(p, max_new=5)
        return eng

    eng = fresh()
    eng.run()
    k = eng.metrics["steps"]                    # steps this workload needs
    assert k > 1
    fresh().run(max_steps=k)                    # exact budget drains
    eng = fresh()
    with pytest.raises(RuntimeError, match="did not drain"):
        eng.run(max_steps=k - 1)                # one short must trip...
    assert eng.metrics["steps"] == k - 1        # ...after exactly k-1 steps


def test_submit_rejects_oversized_request(qwen):
    """plen + max_new must fit the fixed per-sequence page table: the
    boundary request is served, one token more is rejected at submit()
    (clear error naming the limit, nothing registered) — it used to be
    admitted and die mid-serve in PagedKVCache.set_pages."""
    cfg, params = qwen
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=32,
                 max_seq_pages=3)               # 12-token limit
    assert eng.kv.max_seq_tokens == 12
    p = _prompts(cfg, (8,), seed=13)[0]
    with pytest.raises(ValueError, match="12-token per-sequence limit"):
        eng.submit(p, max_new=5)                # 13 > 12
    assert eng.requests == {} and eng._next_rid == 0   # nothing leaked
    rid = eng.submit(p, max_new=4)              # 12 == 12: boundary serves
    res = eng.run()
    ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                              max_new=4))[0]
    assert res[rid].tolist() == ref.tolist()


def test_unsupported_arch_rejected():
    cfg = get_config("hymba-1.5b").reduced()    # ssm state + meta tokens
    assert not supports_paged_cache(cfg)
    with pytest.raises(ValueError):
        init_paged_cache(cfg, 8, 4)


# ---------------------------------------------------------------------------
# EOS handling
# ---------------------------------------------------------------------------

def test_generate_eos_freezes_finished_rows(qwen):
    cfg, params = qwen
    prompts = jnp.asarray(np.stack([p[:5] for p in _prompts(
        cfg, (5, 5), seed=2)]))
    ref = np.asarray(generate(params, cfg, prompts, max_new=6))
    eos = int(ref[0, 2])                        # row 0's 3rd token
    out = np.asarray(generate(params, cfg, prompts, max_new=6, eos_id=eos))
    assert out.shape[1] <= 6
    # row 0 froze at eos; everything after is eos padding
    row = out[0].tolist()
    assert row[:3] == ref[0, :3].tolist()
    assert all(t == eos for t in row[3:])
    # unaffected row matches the no-eos rollout (until any own eos)
    row1 = out[1].tolist()
    stop = row1.index(eos) + 1 if eos in row1 else len(row1)
    assert row1[:stop] == ref[1, :stop].tolist()


def test_engine_eos_stops_request(qwen):
    cfg, params = qwen
    p = _prompts(cfg, (7,), seed=4)[0]
    ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                              max_new=6))[0]
    eos = int(ref[2])
    eng = Engine(params, cfg, n_slots=1, page_size=4, n_pages=16)
    rid = eng.submit(p, max_new=6, eos_id=eos)
    out = eng.run()[rid]
    assert out.tolist() == ref[:3].tolist()     # stops AT the eos token


def test_serve_engine_baseline_still_works(qwen):
    cfg, params = qwen
    reqs = _prompts(cfg, (4, 9, 6), seed=5)
    outs = ServeEngine(params, cfg, batch_slots=2).run(reqs, max_new=4)
    assert len(outs) == 3
    assert all(o.shape == (4,) for o in outs)


# ---------------------------------------------------------------------------
# ragged left-padded batching + decode-step economy
# ---------------------------------------------------------------------------

def test_serve_engine_ragged_matches_per_request_generate(qwen):
    """Unequal-length prompts in ONE left-padded batch must decode exactly
    what each prompt decodes alone: pad keys are masked out of attention
    and positions are offset per row."""
    cfg, params = qwen
    reqs = _prompts(cfg, (4, 11, 7), seed=9)
    outs = ServeEngine(params, cfg, batch_slots=3).run(reqs, max_new=6)
    for o, p in zip(outs, reqs):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=6))[0]
        assert o.tolist() == ref.tolist(), (o.tolist(), ref.tolist())


def test_generate_pad_lens_matches_per_request(qwen):
    cfg, params = qwen
    pa, pb = _prompts(cfg, (5, 9), seed=10)
    S = 9
    batch = np.zeros((2, S), np.int32)
    batch[0, S - 5:] = pa
    batch[1] = pb
    out = np.asarray(generate(params, cfg, jnp.asarray(batch), max_new=5,
                              pad_lens=np.array([4, 0])))
    for row, p in zip(out, (pa, pb)):
        ref = np.asarray(generate(params, cfg, jnp.asarray(p)[None],
                                  max_new=5))[0]
        assert row.tolist() == ref.tolist()


def test_generate_pad_lens_rejected_for_stateful_archs():
    cfg = get_config("hymba-1.5b").reduced()    # meta tokens + ssm state
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = jnp.zeros((2, 6), jnp.int32)
    with pytest.raises(ValueError, match="pad_lens"):
        generate(params, cfg, prompts, max_new=2, pad_lens=np.array([2, 0]))


def test_generate_runs_no_wasted_decode_step(qwen, monkeypatch):
    """A max_new rollout costs exactly max_new - 1 decode steps: the old
    loop ran one extra step whose logits were discarded."""
    import repro.serve.engine as eng_mod
    cfg, params = qwen
    calls = {"n": 0}
    orig = eng_mod.make_decode_step

    def counting(cfg):
        inner = orig(cfg)

        def step(params, cache, tokens):
            calls["n"] += 1
            return inner(params, cache, tokens)
        return step

    monkeypatch.setattr(eng_mod, "make_decode_step", counting)
    monkeypatch.setattr(eng_mod.jax, "jit",
                        lambda f, **kw: f)      # eager → count real calls
    p = _prompts(cfg, (6,), seed=11)[0]
    out = generate(params, cfg, jnp.asarray(p)[None], max_new=4)
    assert out.shape == (1, 4)
    assert calls["n"] == 3


# ---------------------------------------------------------------------------
# configurations read from JSON; expert models
# ---------------------------------------------------------------------------

def test_config_from_json_hashes_and_memoizes_steps():
    """A configuration file gives lists; the frozen config keeps tuples,
    so it hashes and two engines share one pair of jitted steps."""
    import json
    from repro.configs.base import ModelConfig
    from repro.serve.engine import _jitted_paged_steps
    kw = json.loads(json.dumps(dict(
        n_layers=4, sliding_window=8, global_layers=[3], vocab_size=64)))
    a, b = ModelConfig(**kw), ModelConfig(**kw)
    assert a.global_layers == (3,) and hash(a) == hash(b)
    assert a.layer_windows == [8, 8, 8, None]
    assert _jitted_paged_steps(a, None) is _jitted_paged_steps(b, None)


def test_expert_engine_counts_routed_work():
    """Each decode step of an expert model reads its routed work with its
    tokens: every slot's row to top_k experts in every expert layer; a
    dense model counts nothing and its step returns the tokens alone."""
    import dataclasses
    cfg = dataclasses.replace(get_config("qwen3-moe-235b-a22b").reduced(),
                              attention_backend="auto")
    params = init_model(jax.random.PRNGKey(3), cfg)
    eng = Engine(params, cfg, n_slots=3, page_size=8, n_pages=32)
    calls = []
    step = eng._step
    eng._step = lambda *a: calls.append(step(*a)) or calls[-1]
    rid = eng.submit(_prompts(cfg, (9,))[0], max_new=5)
    eng.run()
    assert len(eng.results()[rid]) == 5
    m = eng.metrics
    assert calls and all(t.shape == (3 + 2,) for t, _ in calls)
    assert m["moe_assignments"] == \
        len(calls) * 3 * cfg.top_k * cfg.n_layers
    assert 0 < m["moe_experts_active"] <= \
        len(calls) * cfg.n_experts * cfg.n_layers

    dense_cfg = get_config("qwen1.5-0.5b").reduced()
    dense = Engine(init_model(jax.random.PRNGKey(0), dense_cfg), dense_cfg,
                   n_slots=3, page_size=8, n_pages=32)
    dense.submit(_prompts(dense_cfg, (9,))[0], max_new=3)
    dense.run()
    assert dense.metrics["moe_assignments"] == 0
    assert dense.metrics["moe_experts_active"] == 0
