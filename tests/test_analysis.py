"""repro.analysis (DESIGN.md §12): the tree is clean under every checker,
each seeded violation class is caught, suppressions round-trip (honored
with a reason, rejected without), and the allocator sanitizer validates a
real engine run while rejecting illegal transitions."""
import os
import subprocess
import sys

import numpy as np
import jax
import pytest

from repro.analysis.kernelcheck import (check_blocked_lowering,
                                        check_encoded_maps,
                                        check_paged_index_maps)
from repro.analysis.ledger import LedgerError, sanitize_enabled
from repro.analysis.lint import registered_rules, repo_root, run_lint
from repro.analysis.selftest import CASES, run_selftest
from repro.analysis.shardcheck import (check_cache_coverage,
                                       check_fold_roles,
                                       check_param_coverage)
from repro.configs import get_config
from repro.serve import Engine, PagedKVCache
from repro.models import init_model


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-0.5b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _tiny_kv(**kw):
    cfg = get_config("qwen1.5-0.5b").reduced()
    return PagedKVCache(cfg, n_slots=2, n_pages=8, page_size=8,
                        max_seq_pages=4, **kw)


# ---------------------------------------------------------------------------
# lint: clean tree, suppression round-trip, rule registry
# ---------------------------------------------------------------------------

def test_lint_tree_is_clean():
    assert run_lint(root=repo_root()) == []


def test_rule_registry_names_the_documented_rules():
    ids = set(registered_rules())
    assert {"host-sync-in-hot-path", "jit-in-loop", "f32-accum",
            "metric-docs-sync"} <= ids


def test_suppression_round_trip():
    from repro.analysis.selftest import (_lint_annotation_honored,
                                         _lint_blanket_rejected,
                                         _lint_hot_sync_caught)
    assert _lint_hot_sync_caught()       # unannotated sync → finding
    assert _lint_annotation_honored()    # reasoned allow() → suppressed
    assert _lint_blanket_rejected()      # reason-less allow() → finding


def test_metric_docs_sync_round_trip():
    from repro.analysis.selftest import _metric_docs_drift
    assert _metric_docs_drift()


# ---------------------------------------------------------------------------
# kernel bounds: real maps sound, seeded mutations caught
# ---------------------------------------------------------------------------

def test_real_index_maps_are_sound():
    for ps, sq in ((8, 1), (16, 5)):
        assert check_paged_index_maps(ps=ps, Sq=sq) == []


def test_off_by_one_index_map_is_caught():
    import functools
    import jax.numpy as jnp

    def bad(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps):
        p_eff = jnp.minimum(p + 1, (lens_s[b] + Sq - 1) // ps)
        return (layer_s[0], pages_s[b, p_eff], 0, 0)

    f = check_paged_index_maps(
        kv_map=functools.partial(bad, Sq=1, ps=8), ps=8, Sq=1)
    assert any("wrong page" in x.message for x in f)


def test_missing_lens_clamp_is_caught():
    f = check_paged_index_maps(
        kv_map=lambda b, p, pages, lens, win, layer: (layer[0], pages[b, p],
                                                      0, 0),
        ps=8, Sq=1)
    assert any("past-lens" in x.message for x in f)


def test_wrong_layer_index_map_is_caught():
    """A map that ignores the layer scalar reads layer 0's pages."""
    import functools
    from repro.kernels import paged_attention as pa

    def layer0(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps):
        _, page, _, _ = pa.paged_kv_block_map(b, p, pages_s, lens_s, win_s,
                                               layer_s, Sq=Sq, ps=ps)
        return (0, page, 0, 0)

    f = check_paged_index_maps(
        kv_map=functools.partial(layer0, Sq=1, ps=8), ps=8, Sq=1)
    assert any("kv map returned" in x.message for x in f)


def test_blocked_lowering_is_in_bounds():
    assert check_blocked_lowering(ps=8, Sq=1, mode="int8", bk=8) == []


def test_encoded_maps_and_seeded_overrun():
    assert check_encoded_maps(m=33, k=64, n=64) == []
    bad = check_encoded_maps(x_map=lambda i, j, kk: (i + 1, kk),
                             m=33, k=64, n=64)
    assert any("outside the padded extent" in x.message for x in bad)


# ---------------------------------------------------------------------------
# sharding coverage
# ---------------------------------------------------------------------------

def test_param_and_cache_coverage_clean():
    assert check_param_coverage("qwen1.5-0.5b") == []
    assert check_cache_coverage("qwen1.5-0.5b") == []
    assert check_fold_roles() == []


def test_unruled_large_leaf_is_caught():
    from repro.parallel.sharding import _RULES
    table = [(p, i) for p, i in _RULES if "embed/table" not in p]
    f = check_param_coverage("qwen1.5-0.5b", rules=table)
    assert any("embed/table" in x.message for x in f)


# ---------------------------------------------------------------------------
# allocator sanitizer
# ---------------------------------------------------------------------------

def test_ledger_double_free_rejected():
    kv = _tiny_kv(sanitize=True)
    pages = kv.alloc.alloc(2)
    kv.alloc.free(pages)
    with pytest.raises(LedgerError, match="free"):
        kv.alloc.free(pages)


def test_ledger_use_after_free_rejected():
    kv = _tiny_kv(sanitize=True)
    pages = kv.alloc.alloc(1)
    kv.alloc.free(pages)
    with pytest.raises(LedgerError):
        kv.set_pages(0, pages)


def test_ledger_copy_to_unowned_page_rejected():
    kv = _tiny_kv(sanitize=True)
    pages = kv.alloc.alloc(1)
    with pytest.raises(LedgerError):
        kv.copy_page(pages[0], pages[0] + 1)


def test_ledger_rejection_leaves_shadow_intact():
    kv = _tiny_kv(sanitize=True)
    a = kv.alloc.alloc(2)
    kv.alloc.free(a[:1])
    with pytest.raises(LedgerError):
        kv.alloc.free(a)                 # batch contains the freed page
    kv.alloc.free(a[1:])                 # still-held page frees cleanly
    kv.ledger.verify()


def test_sanitized_engine_run_token_identical(qwen):
    """A full sanitized serve (prefix cache on, eviction pressure) must
    assert conservation every step and change no tokens."""
    cfg, params = qwen
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 13)]

    def serve(sanitize):
        eng = Engine(params, cfg, n_slots=2, page_size=4, n_pages=16,
                     prefix_cache=True, prefill_chunk=8,
                     sanitize=sanitize)
        outs = []
        for p in prompts:
            rid = eng.submit(p, max_new=6)
            outs.append(eng.run()[rid].tolist())
        if sanitize:
            assert eng.kv.ledger is not None
            assert eng.kv.ledger.checks > 0
            eng.kv.ledger.verify()
        else:
            assert eng.kv.ledger is None
        return outs

    assert serve(True) == serve(False)


def test_sanitize_env_default(monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", "1")
    assert sanitize_enabled()
    kv = _tiny_kv()                      # explicit opt-in only: stays off
    assert kv.ledger is None
    monkeypatch.setenv("REPRO_SANITIZE", "0")
    assert not sanitize_enabled()


# ---------------------------------------------------------------------------
# self-test harness + CLI
# ---------------------------------------------------------------------------

def test_selftest_has_no_escapes():
    results = run_selftest()
    assert len(results) == len(CASES)
    escapes = [r for r in results if not r["caught"]]
    assert escapes == []


def test_analyze_cli_lint_exits_clean(tmp_path):
    out = tmp_path / "report.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo_root(), "scripts", "analyze.py"),
         "--lint", "--json", str(out)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert out.exists()


# ---------------------------------------------------------------------------
# compiled-artifact audit (DESIGN.md §13)
# ---------------------------------------------------------------------------

def test_hlo_parser_on_real_compiled_module():
    """The shared HLO-text parser reads aliasing, entry params, and
    large literal constants out of a module XLA actually compiled."""
    import jax.numpy as jnp
    from repro.analysis import hlo as H

    f = jax.jit(lambda a, b: (a + b, b * 2.0), donate_argnums=(0,))
    a = jnp.zeros((4, 8), jnp.float32)
    text = f.lower(a, a).compile().as_text()
    aliases = H.input_output_aliases(text)
    assert len(aliases) == 1 and aliases[0]["param"] == 0
    pshapes = H.entry_param_shapes(text)
    assert len(pshapes) == 2 and pshapes[0] == "f32[4,8]"
    assert H.count_ops(text).get("all-reduce", 0) == 0
    assert H.collective_instrs(text) == []

    w = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64)
    g = jax.jit(lambda x: x @ w)
    text = g.lower(jnp.zeros((1, 64), jnp.float32)).compile().as_text()
    consts = H.constants(text, min_bytes=4096)
    assert any(b >= 64 * 64 * 4 for _, b in consts), text[:2000]


def test_hloparse_shim_reexports_shared_parser():
    from repro.analysis import hlo as H
    from repro.launch import hloparse
    assert hloparse.collective_bytes is H.collective_bytes
    assert hloparse.count_ops is H.count_ops
    assert hloparse.input_output_aliases is H.input_output_aliases


def test_compiled_audit_single_cell_clean_and_reported():
    """Every executable of the primary arch lowers clean on one device:
    donation aliased with exact shapes, zero collectives, no captures;
    the per-exe report carries alias/memory numbers."""
    from repro.analysis.compiled import _executables, audit_cell
    cfg = get_config("qwen1.5-0.5b").reduced()
    f, cell = audit_cell("qwen1.5-0.5b", cfg, "bf16", None, "single",
                         exes=_executables(cfg, full=False))
    assert f == [], [str(x) for x in f]
    for name, rec in cell["executables"].items():
        assert rec["collectives"]["counts"] == {}, name
        assert rec["aliases"] >= rec["donated_leaves"] > 0, (name, rec)
        assert rec["memory"]["argument_size_in_bytes"] > 0, name


def test_compiled_audit_catches_dropped_donation_and_capture():
    from repro.analysis.selftest import (_compiled_captured_constant,
                                         _compiled_dropped_donation)
    assert _compiled_dropped_donation()
    assert _compiled_captured_constant()


def test_donation_site_sweep_flags_unwaivered_jit():
    from repro.analysis.compiled import (RULE_DONATION,
                                         check_donation_sites)
    assert check_donation_sites() == []          # the real tree is clean
    bad = {"src/repro/serve/engine.py":
           "import jax\nstep = jax.jit(lambda c: c)\n"}
    f = check_donation_sites(sources=bad)
    assert any(x.rule == RULE_DONATION for x in f)


def test_recompile_counts_are_exact():
    """Both smoke traces (plain + speculative) land on the pinned
    compile counts, include an eviction, and the report says so."""
    from repro.analysis.compiled import EXPECTED_COMPILES, check_recompile
    f, rep = check_recompile()
    assert f == [], [str(x) for x in f]
    for mode in ("plain", "spec"):
        for name, n in EXPECTED_COMPILES[mode].items():
            assert rep[mode]["compiles"][name] == n, (mode, rep)
        assert rep[mode]["trace"]["evictions"] >= 1, (mode, rep)
        assert rep[mode]["compiles"]["copy_page"] <= 1, (mode, rep)


def test_compiled_report_schema_serializable():
    import json as _json
    from repro.analysis.compiled import run_compiled
    f, rep = run_compiled(archs=["qwen1.5-0.5b"], dtypes=("bf16",),
                          meshes=("single",), encoded=False,
                          recompile=False)
    assert f == [], [str(x) for x in f]
    assert set(rep) == {"cells", "recompile", "skipped", "donation_sites"}
    cell = rep["cells"]["qwen1.5-0.5b/bf16/single"]
    assert cell["arch"] == "qwen1.5-0.5b" and cell["mac"] == "dense"
    assert set(cell["executables"])  # non-empty
    _json.dumps(rep)                 # the whole report is JSON-clean


def test_engine_stats_exports_jit_compiles(qwen):
    """The CompileTracker feeds the labeled ``jit_compiles`` counter: a
    cold engine serving one request compiles prefill + decode, exactly."""
    import dataclasses
    cfg, _ = qwen
    cfg2 = dataclasses.replace(cfg, rope_theta=cfg.rope_theta + 0.125)
    params = init_model(jax.random.PRNGKey(0), cfg2)
    eng = Engine(params, cfg2, n_slots=2, page_size=8, n_pages=16,
                 prefill_chunk=8)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new=4)
    eng.run()
    assert eng.stats()["jit_compiles"] == 2
    assert eng.jit_tracker.counts() == \
        {"prefill": 1, "decode": 1, "copy_page": 0}
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new=4)
    eng.run()                                    # warm: no new compiles
    assert eng.stats()["jit_compiles"] == 2


def test_compiled_audit_mesh():
    """model=2 cell: donation survives SPMD, collective counts match
    the pinned profile (2 fake devices, subprocess so XLA_FLAGS doesn't
    leak)."""
    script = os.path.join(os.path.dirname(__file__),
                          "compiled_audit_mesh_script.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=1200, env=env)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    assert "ALL_COMPILED_AUDIT_MESH_OK" in r.stdout
