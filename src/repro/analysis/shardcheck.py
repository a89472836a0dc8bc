"""Sharding-coverage checker (DESIGN.md §12).

``parallel/sharding.py`` places parameters by path-regex and
``parallel/statesharding.py`` places cache/pool leaves by terminal name —
both default to replication on a miss.  Replication is the *correct*
default for small leaves (norms, scales) but a silent memory/perf bug for
large ones: a forgotten rule for a new projection replicates gigabytes
per device without any runtime error.  This checker makes the default
loud:

  * **param coverage** — ``eval_shape`` every registry arch's full (paper
    scale) parameter tree and require an explicit ``_RULES`` entry —
    replicate rules included — for every leaf above a size threshold.
    ``rule_for_path`` distinguishes "explicitly replicated" from "no rule
    matched"; only the latter is a finding.
  * **pool coverage** — ``eval_shape`` the paged KV cache for every
    paged-servable arch × kv dtype (bf16/int8/int4) and require every
    leaf name in ``_CACHE_RULES``, pools sharded over the kv-head axis
    (index 3), and the quantized scale side pools riding the same
    kv-head axis as their pools; the dense decode cache gets the same
    name-coverage check.
  * **fold-role consistency** — the folded encoded-serving ``*_fw``
    bitplane rules in ``_RULES`` must agree with ``LINEAR_ROLES``:
    column-parallel linears shard the n dim of ``(U, k, n)``,
    row-parallel ones shard k with a replicated bias.  The two tables
    are maintained by hand; this pins them together.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from repro.analysis.lint import Finding

RULE = "shard-coverage"
LARGE_LEAF = 1_000_000           # elements; below this, replication is fine

SHARDING_REL = "src/repro/parallel/sharding.py"
STATESHARDING_REL = "src/repro/parallel/statesharding.py"


def _leaf_paths(tree):
    import jax
    from repro.parallel.sharding import _path_str
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(path), leaf) for path, leaf in flat]


def check_param_coverage(arch: str, rules=None) -> List[Finding]:
    """Every large param leaf of ``arch``'s full config must hit an
    explicit rule.  ``rules`` overrides ``_RULES`` for the self-test."""
    import jax
    from repro.configs.registry import get_config
    from repro.models import init_model
    from repro.parallel import sharding as sh

    def rule_for(path):
        table = sh._RULES if rules is None else rules
        for pat, items in table:
            if re.search(pat, path):
                return pat, items
        return None

    cfg = get_config(arch)
    params = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg))
    out: List[Finding] = []
    for pstr, leaf in _leaf_paths(params):
        if leaf.size < LARGE_LEAF:
            continue
        if rule_for(pstr) is None:
            out.append(Finding(
                RULE, SHARDING_REL, 0,
                f"{arch}: param '{pstr}' {tuple(leaf.shape)} "
                f"({leaf.size:,} elements) matches no _RULES entry — "
                "silently replicated on every device; add a placement "
                "or an explicit replicate rule"))
    return out


def check_cache_coverage(arch: str) -> List[Finding]:
    """Dense decode cache + paged pools (all kv dtypes): every leaf name
    ruled, pools and scale pools sharded over their head-major last
    axis."""
    import dataclasses
    import jax
    from repro.configs.registry import get_config
    from repro.models import (init_cache, init_paged_cache,
                              supports_paged_cache)
    from repro.parallel.sharding import AXIS_MODEL
    from repro.parallel.statesharding import _CACHE_RULES

    out: List[Finding] = []
    cfg = get_config(arch).reduced()

    def leaf_name(pstr):
        return pstr.rsplit("/", 1)[-1]

    dense = jax.eval_shape(lambda: init_cache(cfg, 2, 64))
    for pstr, leaf in _leaf_paths(dense):
        if leaf_name(pstr) not in _CACHE_RULES:
            out.append(Finding(
                RULE, STATESHARDING_REL, 0,
                f"{arch}: cache leaf '{pstr}' {tuple(leaf.shape)} has no "
                "_CACHE_RULES entry — replicated decode state"))
    if not supports_paged_cache(cfg):
        return out
    for dt in ("bf16", "int8", "int4"):
        if dt == "int4" and cfg.head_dim_r % 2:
            continue
        qcfg = dataclasses.replace(cfg, kv_cache_dtype=dt)
        paged = jax.eval_shape(lambda: init_paged_cache(qcfg, 8, 8))
        names = set()
        for pstr, leaf in _leaf_paths(paged):
            name = leaf_name(pstr)
            names.add(name)
            items = _CACHE_RULES.get(name)
            if items is None:
                out.append(Finding(
                    RULE, STATESHARDING_REL, 0,
                    f"{arch} kv_dtype={dt}: paged leaf '{pstr}' "
                    f"{tuple(leaf.shape)} has no _CACHE_RULES entry"))
                continue
            if name.startswith(("pool_", "scale_")):
                if len(items) != len(leaf.shape) or items[-1] != AXIS_MODEL:
                    out.append(Finding(
                        RULE, STATESHARDING_REL, 0,
                        f"'{name}' rule {items} does not shard the "
                        "merged kv-head axis (the last) over the model "
                        "axis"))
        if dt != "bf16" and not {"scale_k", "scale_v"} <= names:
            out.append(Finding(
                RULE, "src/repro/models/lm.py", 0,
                f"{arch} kv_dtype={dt}: quantized pool has no scale "
                "side pools to rule"))
    return out


# roles the fold rules must realize on (U, k, n) planes / (n,) biases
_FOLD_RE = re.compile(r"w\(?([a-z|]+)\)?_f([wb])\$$")


def check_fold_roles(rules=None) -> List[Finding]:
    """Pin the ``*_fw``/``*_fb`` placement rules to ``LINEAR_ROLES``."""
    from repro.parallel.sharding import (AXIS_MODEL, LINEAR_ROLES,
                                         _RULES, linear_role)
    out: List[Finding] = []
    table = _RULES if rules is None else rules
    for pat, items in table:
        m = _FOLD_RE.search(pat)
        if not m:
            continue
        names = [("w" + n if n not in ("w",) else n)
                 for n in m.group(1).split("|")]
        if "lm_head" in pat or "head" in pat:
            names = ["w"]
        kind = m.group(2)
        for name in names:
            role = linear_role(name)
            if role == "replicated":
                continue
            if kind == "w":
                want = (None, "fsdp", "model") if role == "column" \
                    else (None, "model", "fsdp")
                slot = 2 if role == "column" else 1
                if items is None or len(items) != 3 or \
                        items[slot] != "model":
                    out.append(Finding(
                        RULE, SHARDING_REL, 0,
                        f"fold rule '{pat}' places {items} but "
                        f"'{name}' is {role}-parallel — the "
                        f"{'n' if role == 'column' else 'k'} dim of "
                        f"(U, k, n) must ride the model axis "
                        f"(expected {want})"))
            else:
                want_b = ("model",) if role == "column" else None
                if items != want_b:
                    out.append(Finding(
                        RULE, SHARDING_REL, 0,
                        f"fold bias rule '{pat}' places {items} but "
                        f"'{name}' is {role}-parallel — expected "
                        f"{want_b} (row-parallel bias is added once "
                        "after the psum)"))
    if rules is None and not any(_FOLD_RE.search(p) for p, _ in table):
        out.append(Finding(
            RULE, SHARDING_REL, 0,
            "no *_fw fold rules found — encoded-serving bitplane "
            "tensors would be silently replicated"))
    # every roled linear name must be covered by some fold rule
    covered = set()
    for pat, _ in table:
        m = _FOLD_RE.search(pat)
        if m:
            covered |= {"w" + n for n in m.group(1).split("|")}
    for name, role in LINEAR_ROLES.items():
        if name == "w" or name.endswith("_b"):
            continue          # lm_head + low-rank ups have bespoke rules
        if name not in covered and rules is None:
            out.append(Finding(
                RULE, SHARDING_REL, 0,
                f"LINEAR_ROLES names '{name}' ({role}) but no *_fw fold "
                "rule covers it"))
    return out


def run_shardcheck() -> Tuple[List[Finding], Dict]:
    from repro.configs.registry import list_archs
    findings: List[Finding] = []
    archs = list_archs()
    for arch in archs:
        findings.extend(check_param_coverage(arch))
        findings.extend(check_cache_coverage(arch))
    findings.extend(check_fold_roles())
    coverage = {
        "archs": archs,
        "large_leaf_threshold": LARGE_LEAF,
        "kv_dtypes": ["bf16", "int8", "int4"],
    }
    return findings, coverage
