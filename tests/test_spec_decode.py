"""Speculative decoding (DESIGN.md §10): distribution identity of the
rejection sampler (hypothesis + TV distance), greedy token identity of
the spec-decode engine vs dense ``generate()`` across prompt lengths /
EOS / max_new boundaries and drafters, composition with chunked prefill
+ prefix cache + eviction (rollback leaks no pages), drafter guards,
the exact-circuit encoded drafter getting drafts accepted, and the
2-fake-device mesh subprocess (slow)."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:      # bare container: fixed-seed fallback below
    HAVE_HYPOTHESIS = False


def _property(arg_sets):
    """``@given`` (derandomized) when hypothesis is installed, a fixed
    parametrize over representative cases otherwise — the statistical
    checks run either way."""
    names = list(arg_sets[0])

    def deco(fn):
        if HAVE_HYPOTHESIS:
            strat = {
                "seed": hst.integers(0, 2**31 - 1),
                "k": hst.integers(1, 3),
                "sharp": hst.floats(0.3, 3.0),
            }
            return settings(
                max_examples=10, deadline=None, derandomize=True,
                suppress_health_check=[HealthCheck.too_slow],
            )(given(**{n: strat[n] for n in names})(fn))
        cases = [c[names[0]] if len(names) == 1 else
                 tuple(c[n] for n in names) for c in arg_sets]
        return pytest.mark.parametrize(",".join(names), cases)(fn)

    return deco

from repro.configs import get_config
from repro.core.circuits import exact_product_circuit
from repro.core.encoding import EncodingSpec
from repro.core.mac import EncodedMac
from repro.core.macexec import check_drafter, count_prepared
from repro.models import init_model
from repro.obs import DriftMonitor
from repro.serve import (Engine, ServeTelemetry, generate, greedy_accept,
                         prepare_drafter, rejection_sample, req_tid)


# ---------------------------------------------------------------------------
# rejection sampling: distribution identity (hypothesis property)
# ---------------------------------------------------------------------------

def test_greedy_accept_prefix():
    assert greedy_accept([], []) == 0
    assert greedy_accept([3, 5, 7], [3, 5, 7]) == 3
    assert greedy_accept([3, 5, 7], [3, 9, 7]) == 1
    assert greedy_accept([4], [2]) == 0


def _softmax(x):
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


@_property([{"seed": 0, "k": 1, "sharp": 1.0},
            {"seed": 1, "k": 2, "sharp": 0.4},
            {"seed": 2, "k": 3, "sharp": 2.5}])
def test_rejection_sample_first_token_matches_target(seed, k, sharp):
    """The first emitted token's law is exactly ``target_probs[0]`` no
    matter how bad the drafter is (Leviathan identity) — checked as a
    total-variation bound on the empirical distribution."""
    V = 8
    rng = np.random.default_rng(seed)
    draft_p = _softmax(rng.normal(size=(k, V)) * sharp)
    target_p = _softmax(rng.normal(size=(k + 1, V)) * sharp)
    n = 4000
    counts = np.zeros(V)
    samp = np.random.default_rng(seed + 1)
    for _ in range(n):
        toks = [int(samp.choice(V, p=draft_p[i])) for i in range(k)]
        out, _ = rejection_sample(draft_p, target_p, toks, samp)
        counts[out[0]] += 1
    tv = 0.5 * np.abs(counts / n - target_p[0]).sum()
    assert tv < 0.06, (tv, counts / n, target_p[0])


@_property([{"seed": 0}, {"seed": 7}, {"seed": 42}])
def test_rejection_sample_bonus_token_matches_target(seed):
    """Conditioned on accepting all k drafts, the bonus token is an
    exact ancestral sample from ``target_probs[k]``."""
    V, k, n = 6, 2, 4000
    rng = np.random.default_rng(seed)
    p = _softmax(rng.normal(size=(k, V)))
    # identical draft/target at drafted positions → always accept k
    target_p = np.concatenate([p, _softmax(rng.normal(size=(1, V)))])
    samp = np.random.default_rng(seed + 1)
    counts = np.zeros(V)
    for _ in range(n):
        toks = [int(samp.choice(V, p=p[i])) for i in range(k)]
        out, n_acc = rejection_sample(p, target_p, toks, samp)
        assert n_acc == k and len(out) == k + 1
        counts[out[k]] += 1
    tv = 0.5 * np.abs(counts / n - target_p[k]).sum()
    assert tv < 0.06, tv


def test_rejection_sample_shapes_and_guards():
    rng = np.random.default_rng(0)
    draft_p = np.array([[0.5, 0.5, 0.0]])
    target_p = np.array([[0.0, 1.0, 0.0], [1.0, 0.0, 0.0]])
    # draft token 0 has target prob 0 → always rejected, resampled from
    # the residual (= token 1), emitting exactly one token
    out, n_acc = rejection_sample(draft_p, target_p, [0], rng)
    assert out == [1] and n_acc == 0
    # agreement → accept + bonus from target[k]
    out, n_acc = rejection_sample(draft_p, target_p, [1], rng)
    assert out == [1, 0] and n_acc == 1


# ---------------------------------------------------------------------------
# engine greedy identity vs dense generate
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _serve(params, cfg, prompts, *, backend="xla", spec=0, max_new=10,
           **kw):
    c = dataclasses.replace(cfg, attention_backend=backend)
    eng = Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                 spec_decode=spec, **kw)
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    res = eng.run()
    return [res[r].tolist() for r in rids], eng


def test_spec_greedy_token_identical_and_matches_dense(qwen):
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 12, 9, 3))
    ref, _ = _serve(params, cfg, prompts)
    dense = np.asarray(generate(params, cfg, jnp.asarray(prompts[0])[None],
                                max_new=10))[0].tolist()
    assert ref[0] == dense
    out, eng = _serve(params, cfg, prompts, spec=4)
    assert out == ref
    st = eng.stats()
    assert st["spec_acceptance_rate"] == pytest.approx(1.0)  # self-draft
    assert st["spec_rounds"] < st["decode_tokens"]  # actually speculated


def test_spec_identity_with_any_drafter(qwen):
    """Greedy identity holds for ANY drafter — a different-seed model
    disagrees at ~every token (acceptance ≈ 0) yet the emitted tokens
    are exactly the dense model's."""
    cfg, params = qwen
    drafter = init_model(jax.random.PRNGKey(1), cfg)
    prompts = _prompts(cfg, (5, 12, 9))
    ref, _ = _serve(params, cfg, prompts)
    out, eng = _serve(params, cfg, prompts, spec=3, draft_params=drafter)
    assert out == ref
    assert eng.stats()["spec_acceptance_rate"] < 0.5


def test_exact_circuit_encoded_drafter_gets_drafts_accepted(qwen, tmp_path):
    """The encoded drafter folded with the zero-RMSE AND-plane circuit
    differs from the verifier only by int8 quantization, so its drafts
    agree with the dense model's argmax far above chance; the emitted
    tokens stay exactly the non-speculative engine's."""
    cfg, params = qwen
    circ, s = exact_product_circuit(cfg.mac.bits, cfg.mac.bits)
    exact = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
    dp, dc, _ = prepare_drafter(
        params, cfg, m_bits=cfg.mac.bits * 2,
        macs_override={n: exact for n in ("wq", "wk", "wv", "wo", "wi",
                                          "wg", "w")},
        cache_dir=str(tmp_path), calib_batches=1, calib_batch_size=1,
        calib_seq=8, verbose=False)
    prompts = _prompts(cfg, (5, 12))
    ref, _ = _serve(params, cfg, prompts, max_new=12)
    out, eng = _serve(params, cfg, prompts, spec=4, max_new=12,
                      draft_params=dp, draft_cfg=dc)
    assert out == ref
    st = eng.stats()
    assert st["draft_mac_mode"] == "encoded_infer"
    assert st["spec_acceptance_rate"] > 0.2


def test_spec_max_new_and_eos_boundaries(qwen):
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 9))
    for mn in (1, 2, 4, 5):
        a, _ = _serve(params, cfg, prompts, max_new=mn)
        b, _ = _serve(params, cfg, prompts, spec=4, max_new=mn)
        assert a == b, mn
    # eos that actually fires mid-draft: take an emitted token as eos
    full, _ = _serve(params, cfg, prompts, max_new=10)
    eos = full[0][len(prompts[0]) + 4]

    def run_eos(spec):
        c = dataclasses.replace(cfg, attention_backend="xla")
        eng = Engine(params, c, n_slots=2, page_size=4, n_pages=64,
                     spec_decode=spec)
        rids = [eng.submit(p, max_new=10, eos_id=eos) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids]

    assert run_eos(4) == run_eos(0)


def test_spec_chunked_prefill_prefix_cache_identity(qwen):
    cfg, params = qwen
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, cfg.vocab_size, n).astype(np.int32)]) for n in (3, 7, 2)]
    kw = dict(prefill_chunk=8, prefix_cache=True)
    ref, _ = _serve(params, cfg, prompts, **kw)
    out, eng = _serve(params, cfg, prompts, spec=4, **kw)
    assert out == ref
    assert eng.stats()["prefix_hit_tokens"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["xla", "blocked", "pallas_interpret"])
@pytest.mark.parametrize("k", [1, 2, 4, 8])
def test_spec_backend_k_sweep(qwen, backend, k):
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 12, 9))
    ref, _ = _serve(params, cfg, prompts)
    out, _ = _serve(params, cfg, prompts, backend=backend, spec=k)
    assert out == ref, (backend, k)


# ---------------------------------------------------------------------------
# eviction / rollback stress: no leaks, no regenerated tokens
# ---------------------------------------------------------------------------

def test_spec_eviction_rollback_stress(qwen):
    """Pressure geometry (2 slots / 6×4-token pages / optimistic
    reserve) forces preemption mid-draft.  Rollback must leak no pages
    (allocator returns to its idle baseline), regenerate no tokens
    (token-identical to the non-speculative engine under the SAME
    pressure), and the telemetry phase spans must still telescope."""
    cfg, params = qwen
    prompts = _prompts(cfg, (5, 3, 6), seed=0)

    def run(spec):
        drift = DriftMonitor(params, cfg, every=4) if spec else None
        tel = ServeTelemetry(trace=True, drift=drift)
        eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=6,
                     reserve="optimistic", prefill_chunk=4, telemetry=tel,
                     spec_decode=spec)
        rids = [eng.submit(p, max_new=10) for p in prompts]
        res = eng.run()
        return [res[r].tolist() for r in rids], eng, tel, rids

    ref, eng0, _, _ = run(0)
    out, eng, tel, rids = run(4)
    assert out == ref                       # no regenerated/lost tokens
    st = eng.stats()
    assert st["evictions"] >= 1             # pressure actually preempted
    assert st["finished"] == 3
    # allocator back to idle baseline: nothing held, free+cached conserve
    al, al0 = eng.kv.alloc, eng0.kv.alloc
    assert al.n_held == 0
    assert al.n_free_strict + al.n_cached == al0.n_free_strict + al0.n_cached
    # drift gauge fed from verification for free (no replay forwards):
    # self-draft agreement is 1.0
    assert tel.drift.last == pytest.approx(1.0)
    assert tel.registry.gauge("encoded_drift_top1").value() == \
        pytest.approx(1.0)
    # phase spans still telescope to the request span under spec rounds
    spans = [e for e in tel.tracer.chrome_events() if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert {"serve.draft", "serve.verify", "request"} <= names
    for rid in rids:
        mine = {e["name"]: e for e in spans if e["tid"] == req_tid(rid)}
        total = sum(mine[n]["dur"] for n in ("queued", "prefill", "decode"))
        assert total == pytest.approx(mine["request"]["dur"], abs=2.0)


# ---------------------------------------------------------------------------
# drafter guards
# ---------------------------------------------------------------------------

def test_drafter_guards(qwen):
    cfg, params = qwen
    # a dense param tree has zero prepared encoded tables
    assert count_prepared(params, "encoded_infer") == 0
    assert count_prepared(params, "fp") == -1
    with pytest.raises(ValueError, match="drafter"):
        check_drafter(params, "encoded_infer")
    with pytest.raises(ValueError, match="spec_decode"):
        Engine(params, cfg, n_slots=2, page_size=4, n_pages=16,
               spec_decode=-1)
    # drafter cache geometry must match the verifier's pools
    bad = dataclasses.replace(cfg, n_layers=cfg.n_layers + 1)
    with pytest.raises(ValueError, match="geometry"):
        Engine(params, cfg, n_slots=2, page_size=4, n_pages=16,
               spec_decode=2, draft_params=params, draft_cfg=bad)


# ---------------------------------------------------------------------------
# 2-fake-device mesh composition (subprocess so XLA_FLAGS doesn't leak)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_mesh_spec_decode_parity():
    script = os.path.join(os.path.dirname(__file__),
                          "spec_decode_mesh_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_SPEC_DECODE_MESH_OK" in r.stdout
