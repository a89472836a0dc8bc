"""Seeded-mutation self-test: prove each checker catches the bug class it
exists for (DESIGN.md §12).

A static analyzer that silently stops finding anything is worse than no
analyzer.  Each case here plants one representative defect — an
off-by-one index map, a missing lens clamp, a deleted sharding rule, a
mistabled fold role, a double-free, a use-after-free, an unannotated
host sync, a blanket suppression, an undocumented metric, a dropped
``donate_argnums``, a weight baked into an executable as a constant, a
fold-role flip that plants a stray collective, a leaked decode shape
that forces a retrace — and asserts the corresponding checker reports
it.  A mutation that goes undetected
is an **escape**; ``scripts/analyze.py --self-test`` (and the CI
``static-analysis`` job) fails on any escape.

Mutations are injected, never written into the real tree: the kernel
cases pass mutated index maps into the parameterized checker cores, the
sharding cases pass doctored rule tables, the lint cases run on a
synthetic repo in a temp dir, and the ledger cases drive a real (tiny)
``PagedKVCache`` through illegal transitions.
"""
from __future__ import annotations

import functools
import os
import tempfile
import textwrap
from typing import Callable, List, Tuple

Case = Tuple[str, Callable[[], bool]]      # (name, returns True if caught)


def _kernel_off_by_one() -> bool:
    import jax.numpy as jnp
    from repro.analysis.kernelcheck import check_paged_index_maps
    from repro.kernels import paged_attention as pa

    def bad_map(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps):
        p_eff = jnp.minimum(p + 1, (lens_s[b] + Sq - 1) // ps)  # off by one
        return (layer_s[0], pages_s[b, p_eff], 0, 0)

    f = check_paged_index_maps(
        kv_map=functools.partial(bad_map, Sq=1, ps=8), ps=8, Sq=1,
        label="selftest")
    return any("wrong page" in x.message for x in f)


def _kernel_missing_clamp() -> bool:
    from repro.analysis.kernelcheck import check_paged_index_maps

    def bad_map(b, p, pages_s, lens_s, win_s, layer_s, *, Sq, ps):
        return (layer_s[0], pages_s[b, p], 0, 0)    # reads past lens

    f = check_paged_index_maps(
        kv_map=functools.partial(bad_map, Sq=1, ps=8), ps=8, Sq=1,
        label="selftest")
    return any("past-lens" in x.message for x in f)


def _encoded_overrun() -> bool:
    from repro.analysis.kernelcheck import check_encoded_maps

    def bad_x(i, j, kk):
        return (i + 1, kk)                          # runs past padded M

    f = check_encoded_maps(x_map=bad_x, m=33, k=64, n=64,
                           label="selftest")
    return any("outside the padded extent" in x.message for x in f)


def _shard_unruled_leaf() -> bool:
    from repro.analysis.shardcheck import check_param_coverage
    from repro.parallel.sharding import _RULES
    # delete the embedding rule: every arch has a large embed/table leaf
    table = [(p, i) for p, i in _RULES if "embed/table" not in p]
    f = check_param_coverage("qwen1.5-0.5b", rules=table)
    return any("embed/table" in x.message for x in f)


def _shard_fold_role_flip() -> bool:
    from repro.analysis.shardcheck import check_fold_roles
    from repro.parallel.sharding import _RULES
    # re-point the column-parallel fw rule at the row-parallel placement
    table = [(p, (None, "model", "fsdp"))
             if p == r"w(q|k|v|kv|qkv|i|g|in|up)_fw$" else (p, i)
             for p, i in _RULES]
    f = check_fold_roles(rules=table)
    return any("column-parallel" in x.message or "must ride" in x.message
               for x in f)


def _tiny_kv(sanitize=True):
    from repro.configs.registry import get_config
    from repro.serve.paged_cache import PagedKVCache
    cfg = get_config("qwen1.5-0.5b").reduced()
    return PagedKVCache(cfg, n_slots=2, n_pages=8, page_size=8,
                        max_seq_pages=4, sanitize=sanitize)


def _ledger_double_free() -> bool:
    from repro.analysis.ledger import LedgerError
    kv = _tiny_kv()
    pages = kv.alloc.alloc(2)
    kv.alloc.free(pages)
    try:
        kv.alloc.free(pages)                        # double free
    except LedgerError:
        return True
    return False


def _ledger_use_after_free() -> bool:
    from repro.analysis.ledger import LedgerError
    kv = _tiny_kv()
    pages = kv.alloc.alloc(1)
    kv.alloc.free(pages)
    try:
        kv.set_pages(0, pages)                      # stale page table
    except LedgerError:
        return True
    return False


def _ledger_foreign_copy() -> bool:
    from repro.analysis.ledger import LedgerError
    kv = _tiny_kv()
    pages = kv.alloc.alloc(1)
    try:
        kv.copy_page(pages[0], pages[0] + 1)        # COW into unowned dst
    except LedgerError:
        return True
    return False


_SYNTH_ENGINE = textwrap.dedent("""\
    import numpy as np

    class Engine:
        def run(self):
            while True:
                self.step()

        def step(self):
            toks = self._dispatch()
            {annot}
            out = np.asarray(toks)
            return out

        def _dispatch(self):
            return [1]
    """)


def _synth_repo(annot: str):
    from repro.analysis.lint import Repo
    tmp = tempfile.mkdtemp(prefix="analysis-selftest-")
    pkg = os.path.join(tmp, "src", "repro", "serve")
    os.makedirs(pkg)
    for d in (os.path.join(tmp, "src", "repro"), pkg):
        with open(os.path.join(d, "__init__.py"), "w"):
            pass
    with open(os.path.join(pkg, "engine.py"), "w") as f:
        f.write(_SYNTH_ENGINE.format(annot=annot))
    return Repo(tmp)


def _lint_hot_sync_caught() -> bool:
    from repro.analysis.lint import run_lint
    f = run_lint(repo=_synth_repo("pass"))
    return any(x.rule == "host-sync-in-hot-path" for x in f)


def _lint_annotation_honored() -> bool:
    from repro.analysis.lint import run_lint
    f = run_lint(repo=_synth_repo(
        "# analysis: allow(host-sync): step boundary, tokens must land"))
    return not any(x.rule == "host-sync-in-hot-path" for x in f)


def _lint_blanket_rejected() -> bool:
    from repro.analysis.lint import run_lint
    f = run_lint(repo=_synth_repo("# analysis: allow(host-sync)"))
    return (any(x.rule == "host-sync-in-hot-path" for x in f)
            and any(x.rule == "blanket-suppression" for x in f))


def _metric_docs_drift() -> bool:
    from repro.analysis.lint import Repo
    from repro.analysis.rules.metricdocs import check
    tmp = tempfile.mkdtemp(prefix="analysis-selftest-")
    pkg = os.path.join(tmp, "src", "repro")
    os.makedirs(os.path.join(tmp, "docs"))
    os.makedirs(pkg)
    with open(os.path.join(pkg, "__init__.py"), "w"):
        pass
    with open(os.path.join(pkg, "obs.py"), "w") as f:
        f.write("def bind(r):\n    r.counter('fresh_metric', 'help')\n")
    with open(os.path.join(tmp, "docs", "observability.md"), "w") as f:
        f.write("| metric | kind |\n|---|---|\n| `stale_metric` | counter |\n")
    f = check(Repo(tmp))
    return (any("fresh_metric" in x.message for x in f)
            and any("stale_metric" in x.message for x in f))


def _compiled_dropped_donation() -> bool:
    from repro.analysis.compiled import (RULE_DONATION, _executables,
                                         audit_cell)
    from repro.configs.registry import get_config
    cfg = get_config("qwen1.5-0.5b").reduced()
    exes = {"paged_decode": _executables(cfg, full=False)["paged_decode"]}
    # lower the decode step with donation stripped: the cache pools must
    # show up as un-aliased params in the compiled module
    f, _ = audit_cell("qwen1.5-0.5b", cfg, "bf16", None, "single",
                      exes=exes, donate_override=())
    return any(x.rule == RULE_DONATION for x in f)


def _compiled_captured_constant() -> bool:
    import jax
    import jax.numpy as jnp
    from repro.analysis.compiled import RULE_CAPTURE, check_capture
    w = jnp.zeros((512, 1024), jnp.float32)     # 2 MB closed-over weight

    def step(x):
        return x @ w

    f = check_capture(
        step, (jax.ShapeDtypeStruct((1, 512), jnp.float32),), "selftest")
    return any(x.rule == RULE_CAPTURE for x in f)


def _compiled_fold_flip_gather() -> bool:
    # needs a 2-device mesh, which means XLA_FLAGS before jax import —
    # run the mutation in a subprocess (SKIP counts as caught: the same
    # audit is exercised wherever a multi-device jax is available)
    import subprocess
    import sys
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "repro.analysis._selftest_mesh"],
        capture_output=True, text=True, env=env, timeout=1200)
    verdict = (p.stdout.strip().splitlines() or [""])[-1]
    return verdict in ("CAUGHT", "SKIP")


def _compiled_shape_leak() -> bool:
    from repro.analysis.compiled import RULE_RECOMPILE, check_recompile
    f, _ = check_recompile(inject_decode_shapes=((3, 1),))
    return any(x.rule == RULE_RECOMPILE for x in f)


CASES: List[Case] = [
    ("kernel/off-by-one-index-map", _kernel_off_by_one),
    ("kernel/missing-lens-clamp", _kernel_missing_clamp),
    ("kernel/encoded-grid-overrun", _encoded_overrun),
    ("shard/unruled-large-leaf", _shard_unruled_leaf),
    ("shard/fold-role-flip", _shard_fold_role_flip),
    ("ledger/double-free", _ledger_double_free),
    ("ledger/use-after-free", _ledger_use_after_free),
    ("ledger/copy-to-unowned-page", _ledger_foreign_copy),
    ("lint/hot-path-sync-detected", _lint_hot_sync_caught),
    ("lint/annotation-honored", _lint_annotation_honored),
    ("lint/blanket-suppression-rejected", _lint_blanket_rejected),
    ("lint/metric-docs-drift", _metric_docs_drift),
    ("compiled/dropped-donation", _compiled_dropped_donation),
    ("compiled/captured-weight-constant", _compiled_captured_constant),
    ("compiled/fold-role-flip-gather", _compiled_fold_flip_gather),
    ("compiled/shape-leak-retrace", _compiled_shape_leak),
]


def run_selftest() -> List[dict]:
    """Run every seeded mutation; return the list of case reports.  A
    case with ``caught == False`` is an escape (checker regression)."""
    out = []
    for name, fn in CASES:
        try:
            caught = bool(fn())
            err = None
        except Exception as e:          # checker crashed ≠ checker caught
            caught, err = False, f"{type(e).__name__}: {e}"
        out.append({"case": name, "caught": caught,
                    **({"error": err} if err else {})})
    return out
