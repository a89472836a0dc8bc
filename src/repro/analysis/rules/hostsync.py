"""Rule ``host-sync-in-hot-path``: host synchronization reachable from the
engine's serving loop or inside jitted step functions.

Flagged operations: ``np.asarray`` / ``np.array``, ``jax.block_until_ready``,
``jax.device_get``, ``.item()``, ``float(...)`` — each forces a device→host
transfer (or, inside a jitted trace, a ``ConcretizationTypeError`` at best
and a silent constant-fold at worst).

Reachability is a static call-graph closure with two root classes:

  * **Engine hot roots** (``Engine.run`` / ``Engine.step``): edges follow
    bare-name calls, ``self.<method>`` calls, ``functools.partial``
    targets, and from-imported functions ACROSS modules (re-exports
    chased) — the serving loop's full host-side extent.
  * **Jit roots** (functions decorated ``@jax.jit`` /
    ``functools.partial(jax.jit, ...)`` or passed to ``jax.jit(...)``,
    including factory-call results): scanned with MODULE-LOCAL edges
    only.  Cross-module callees of a traced function run under the same
    trace, where a genuine host sync would already break tracing loudly —
    the local scan targets the quiet case: host ops sitting directly in
    the step function's own module.

Intentional syncs (the engine's step boundaries) carry
``# analysis: allow(host-sync): <reason>`` annotations.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.lint import (Finding, Module, Repo, call_name,
                                 from_imports, rule, top_level_functions)

RULE_ID = "host-sync-in-hot-path"

FLAGGED_DOTTED = {"np.asarray", "np.array", "jax.block_until_ready",
                  "jax.device_get"}

HOT_ROOTS = (("repro.serve.engine", "Engine.run"),
             ("repro.serve.engine", "Engine.step"))

FuncKey = Tuple[str, str]                   # (module name, qualname)


def _is_flagged(cn: Optional[str]) -> Optional[str]:
    if cn is None:
        return None
    if cn == "float":
        return "float(...)"
    if cn in FLAGGED_DOTTED:
        return cn
    if "." in cn and cn.rsplit(".", 1)[1] == "item":
        return ".item()"
    return None


class _Index:
    """Call-graph index over a parsed Repo."""

    def __init__(self, repo: Repo):
        self.repo = repo
        self.funcs: Dict[FuncKey, ast.AST] = {}
        self.imports: Dict[str, Dict[str, tuple]] = {}
        for name, mod in repo.modules.items():
            for qual, node in top_level_functions(mod.tree).items():
                self.funcs[(name, qual)] = node
            self.imports[name] = from_imports(mod.tree, name)

    def resolve_import(self, mod: str, name: str,
                       depth: int = 5) -> Optional[FuncKey]:
        """Chase ``from X import name`` (and re-exports) to a function."""
        if depth <= 0:
            return None
        if (mod, name) in self.funcs:
            return (mod, name)
        imp = self.imports.get(mod)
        if imp and name in imp:
            tmod, tname = imp[name]
            if tmod in self.repo.modules:
                return self.resolve_import(tmod, tname, depth - 1)
        return None

    def resolve_name(self, mod: str, name: str,
                     follow_imports: bool) -> Optional[FuncKey]:
        """A bare-name call inside ``mod``: module function first, then
        (optionally) a from-imported function."""
        if (mod, name) in self.funcs:
            return (mod, name)
        if follow_imports:
            imp = self.imports.get(mod, {})
            if name in imp:
                tmod, tname = imp[name]
                if tmod in self.repo.modules:
                    return self.resolve_import(tmod, tname)
        return None

    def edges(self, key: FuncKey, follow_imports: bool) -> List[FuncKey]:
        mod, qual = key
        node = self.funcs[key]
        cls = qual.split(".")[0] if "." in qual else None
        out: List[FuncKey] = []
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            fn = sub.func
            # functools.partial(f, ...) / partial(f, ...): edge to f
            cn = call_name(sub)
            if cn in ("functools.partial", "partial") and sub.args and \
                    isinstance(sub.args[0], ast.Name):
                tgt = self.resolve_name(mod, sub.args[0].id, follow_imports)
                if tgt:
                    out.append(tgt)
                continue
            if isinstance(fn, ast.Name):
                tgt = self.resolve_name(mod, fn.id, follow_imports)
                if tgt:
                    out.append(tgt)
            elif isinstance(fn, ast.Attribute) and \
                    isinstance(fn.value, ast.Name) and \
                    fn.value.id == "self" and cls is not None:
                tgt = (mod, f"{cls}.{fn.attr}")
                if tgt in self.funcs:
                    out.append(tgt)
        return out

    def closure(self, roots: List[FuncKey],
                follow_imports: bool) -> Set[FuncKey]:
        seen: Set[FuncKey] = set()
        work = [r for r in roots if r in self.funcs]
        while work:
            key = work.pop()
            if key in seen:
                continue
            seen.add(key)
            work.extend(self.edges(key, follow_imports))
        return seen


def _jit_roots(idx: _Index, mod_name: str, mod: Module) -> Set[FuncKey]:
    """Jit roots defined in (or discovered from) ``mod``: decorated
    functions plus anything passed to ``jax.jit(...)`` — bare names and
    factory-call results alike."""
    roots: Set[FuncKey] = set()
    funcs = top_level_functions(mod.tree)
    for qual, node in funcs.items():
        for dec in getattr(node, "decorator_list", ()):
            if _is_jax_jit(dec):
                roots.add((mod_name, qual))
            elif isinstance(dec, ast.Call):
                cn = call_name(dec)
                if _is_jax_jit(dec.func):
                    roots.add((mod_name, qual))
                elif cn in ("functools.partial", "partial") and dec.args \
                        and _is_jax_jit(dec.args[0]):
                    roots.add((mod_name, qual))
    for sub in ast.walk(mod.tree):
        if not (isinstance(sub, ast.Call) and _is_jax_jit(sub.func)
                and sub.args):
            continue
        arg = sub.args[0]
        if isinstance(arg, ast.Name):
            tgt = idx.resolve_name(mod_name, arg.id, follow_imports=True)
        elif isinstance(arg, ast.Call) and isinstance(arg.func, ast.Name):
            tgt = idx.resolve_name(mod_name, arg.func.id,
                                   follow_imports=True)
        else:
            tgt = None
        if tgt:
            roots.add(tgt)
    return roots


def _is_jax_jit(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "jit"
            and isinstance(node.value, ast.Name) and node.value.id == "jax")


def _scan(idx: _Index, key: FuncKey, context: str,
          seen_sites: Set[tuple]) -> List[Finding]:
    mod = idx.repo.modules[key[0]]
    node = idx.funcs[key]
    out = []
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        what = _is_flagged(call_name(sub))
        if what is None:
            continue
        site = (mod.rel, sub.lineno, sub.col_offset)
        if site in seen_sites:
            continue
        seen_sites.add(site)
        out.append(Finding(
            RULE_ID, mod.rel, sub.lineno,
            f"{what} in {key[1]} — {context}; annotate with "
            f"'# analysis: allow(host-sync): <reason>' if intentional"))
    return out


@rule(RULE_ID,
      "host sync (np.asarray/.item()/float()/block_until_ready) reachable "
      "from the engine serving loop or inside jitted step functions",
      allow="host-sync")
def check(repo: Repo) -> List[Finding]:
    idx = _Index(repo)
    findings: List[Finding] = []
    seen: Set[tuple] = set()
    hot = idx.closure(list(HOT_ROOTS), follow_imports=True)
    for key in sorted(hot):
        findings.extend(_scan(
            idx, key, "reachable from the Engine.run/step hot loop", seen))
    for mod_name in sorted(repo.modules):
        mod = repo.modules[mod_name]
        roots = _jit_roots(idx, mod_name, mod)
        reach = idx.closure(sorted(roots), follow_imports=False)
        for key in sorted(reach):
            findings.extend(_scan(
                idx, key, "inside a jitted function's trace scope", seen))
    return findings
