"""Compiled-executable audit (DESIGN.md §13).

The AST/index-map checkers (§12) prove invariants about the *source*;
this module proves the ones only the lowered artifacts can witness.  It
``.lower()``s every serving executable the engine jits — paged prefill
chunk, paged decode step, spec draft/verify, dense prefill/decode,
``copy_page`` — for every ``supports_paged_cache`` registry arch × kv
dtype (bf16/int8/int4) × mesh {single, model=2}, entirely from
``eval_shape``-abstract inputs (no weights materialize), and audits:

  * **donation** (``compiled-donation``) — every ``donate_argnums``
    buffer must appear in the compiled module's ``input_output_alias``
    header.  XLA drops donation silently (shape/layout mismatch, an
    unused output, a backend quirk) and the cost is invisible until the
    KV pools exist twice in HBM.  An AST sweep over the serving modules
    additionally demands ``donate_argnums`` (or a justified
    ``DONATION_WAIVERS`` entry) at every ``jax.jit`` call site.
  * **collectives** (``compiled-collectives``) — on the post-SPMD HLO of
    model=2 cells, per-op instruction counts must equal the pinned
    ``EXPECTED_COLLECTIVES`` table (one psum per row-parallel linear
    family, argmax-combine gathers, nothing else), no all-gather may
    reassemble a protected tensor (KV pool / scale side pool / folded
    ``fw`` bitplane — byte-size match against the full leaf), and
    single-device cells must contain no collectives at all.
  * **capture & purity** (``compiled-capture``) — the jaxpr must close
    over no array constant above 1MB (a weight baked into the
    executable), contain no host callbacks, and produce no f64 values;
    the compiled text must hold no >1MB ``constant`` instruction.
  * **recompiles** (``recompile-count``) — a deterministic smoke serving
    trace (chunked prefill + decode + spec round + eviction) must cost
    EXACTLY the expected number of XLA compilations per jitted step;
    a leaked shape that retraces the decode loop is a finding, not a
    silent 100× slowdown.

``memory_analysis()`` per cell lands in the JSON report
(``scripts/analyze.py --compiled`` → ``ANALYSIS_compiled.json``).
Mutation seams (``donate_override``, ``rules``, ``expected``,
``inject_decode_shapes``) let ``analysis/selftest.py`` plant each bug
class without touching the tree.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import re
from typing import Dict, List, Optional, Tuple

from repro.analysis.hlo import (collective_instrs, constants, count_ops,
                                input_output_aliases)
from repro.analysis.lint import Finding

RULE_DONATION = "compiled-donation"
RULE_COLLECTIVES = "compiled-collectives"
RULE_CAPTURE = "compiled-capture"
RULE_RECOMPILE = "recompile-count"

ENGINE_REL = "src/repro/serve/engine.py"
PRIMARY_ARCH = "qwen1.5-0.5b"          # gets the full executable set
LARGE_CONST_BYTES = 1 << 20
KV_DTYPES = ("bf16", "int8", "int4")
MESH_KINDS = ("single", "model2")

# abstract cell geometry (shapes only — values never materialize)
B = 2                                   # decode batch / slots
N_PAGES = 9
PAGE_SIZE = 8
SLOT_PAGES = 4
CHUNK = 16                              # prefill chunk length
DENSE_LEN = 48                          # dense-cache max_len
SPEC_K = 2

# ``jax.jit`` call sites in the serving modules that may legitimately
# skip ``donate_argnums``, keyed "<file>:<enclosing scope>" with the
# justification as the value.  Empty today: every serving jit donates
# its cache/pool argument (dense ``generate`` prefill included — its
# cache is freshly built and rebound to the return value).
DONATION_WAIVERS: Dict[str, str] = {}

_DONATION_SCAN = ("src/repro/serve/engine.py",
                  "src/repro/serve/paged_cache.py",
                  "src/repro/serve/spec.py")

# Pinned per-step collective profile of every model=2 executable
# (instruction counts in the post-SPMD HLO; identical across the paged
# registry archs — their reduced geometries share one shape set and the
# collective pattern is per linear *family*, not per size).  Keyed
# (executable, mac_kind).  The 2 all-gathers on decode-shaped steps are
# the (B, n_model)-element argmax combines of the vocab-sharded lm head;
# all-reduces are the row-parallel out-projection psums (attn + mlp,
# inside the layer while-loop, so the static count is per-family) plus
# the lm-head family.  A deviation — GSPMD inserting a gather where
# shardcheck proved a sharded placement — fails the cell.
EXPECTED_COLLECTIVES: Dict[Tuple[str, str], Dict[str, int]] = {
    ("paged_prefill", "dense"):  {"all-gather": 2, "all-reduce": 3},
    ("paged_decode",  "dense"):  {"all-gather": 2, "all-reduce": 3},
    # draft runs k=2 chained decode steps inside one executable: 2×
    ("spec_draft",    "dense"):  {"all-gather": 4, "all-reduce": 6},
    ("spec_verify",   "dense"):  {"all-gather": 2, "all-reduce": 3},
    ("copy_page",     "dense"):  {},
    # encoded MAC: the bitplane popcount path psums per plane family and
    # gathers the tiny per-step combine twice more than fp — still zero
    # fw/pool-sized transfers (the exact-size detector proves that part)
    ("paged_decode",  "encoded"): {"all-gather": 8, "all-reduce": 6},
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# fields of jax's compiled memory_analysis we report per cell
_MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
               "temp_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def _paged_geometries(archs=None, dtypes=KV_DTYPES):
    """(arch, reduced cfg with kv dtype, dt) for every paged-servable
    registry arch — the same sweep kernelcheck/shardcheck prove."""
    from repro.configs.registry import get_config, list_archs
    from repro.models import supports_paged_cache
    for arch in (archs or list_archs()):
        cfg0 = get_config(arch).reduced()
        if not supports_paged_cache(cfg0):
            continue
        for dt in dtypes:
            if dt == "int4" and cfg0.head_dim_r % 2:
                continue
            yield arch, dataclasses.replace(cfg0, kv_cache_dtype=dt), dt


def _make_mesh(kind):
    """None for single-device; a (1, n_model=2) test mesh otherwise —
    or the string 'skip' when the host exposes <2 devices (analyze.py
    forces 2 via XLA_FLAGS; a bare pytest process may not)."""
    if kind == "single":
        return None
    import jax
    if jax.device_count() < 2:
        return "skip"
    from repro.launch.mesh import make_test_mesh
    return make_test_mesh(1, 2)


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


def _executables(cfg, *, full: bool):
    """name → executable descriptor with engine-identical factory, the
    engine's donate_argnums, abstract args, and per-arg sharding roles
    ('params' | 'layers' | 'cache' | 'plain')."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_cache, init_model, init_paged_cache
    from repro.serve.engine import (make_decode_step, make_paged_decode_step,
                                    make_paged_prefill, make_prefill)

    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    layers = jax.eval_shape(
        lambda: init_paged_cache(cfg, N_PAGES, PAGE_SIZE))["layers"]
    i32 = jnp.int32
    exes = {
        "paged_prefill": dict(
            fn=make_paged_prefill(cfg), donate=(1,),
            args=(params, layers, _sds((1, CHUNK), i32),
                  _sds((1, SLOT_PAGES), i32), _sds((1,), i32)),
            roles=("params", "layers", "plain", "plain", "plain")),
        "paged_decode": dict(
            fn=make_paged_decode_step(cfg), donate=(1,),
            args=(params, layers, _sds((B, 1), i32),
                  _sds((B, SLOT_PAGES), i32), _sds((B,), i32)),
            roles=("params", "layers", "plain", "plain", "plain")),
    }
    if full:
        from repro.serve.paged_cache import _copy_page_jit
        from repro.serve.spec import make_spec_draft, make_spec_verify
        exes["spec_draft"] = dict(
            fn=make_spec_draft(cfg, SPEC_K), donate=(1,),
            args=(params, layers, _sds((B, 1), i32),
                  _sds((B, SLOT_PAGES), i32), _sds((B,), i32)),
            roles=("params", "layers", "plain", "plain", "plain"))
        exes["spec_verify"] = dict(
            fn=make_spec_verify(cfg, SPEC_K), donate=(1,),
            args=(params, layers, _sds((B, 1), i32), _sds((B, SPEC_K), i32),
                  _sds((B, SLOT_PAGES), i32), _sds((B,), i32)),
            roles=("params", "layers", "plain", "plain", "plain", "plain"))
        exes["copy_page"] = dict(
            fn=_copy_page_jit, prejit=True, donate=(0,),
            args=(layers, _sds((), i32), _sds((), i32)),
            roles=("layers", "plain", "plain"))
        if cfg.kv_cache_dtype == "bf16":
            # the dense baseline path (generate/ServeEngine) — single
            # mesh only, kv-dtype-independent (dense cache is unquantized)
            cache = jax.eval_shape(lambda: init_cache(cfg, B, DENSE_LEN))
            exes["dense_prefill"] = dict(
                fn=make_prefill(cfg), donate=(1,),
                args=(params, cache, _sds((B, CHUNK), i32)),
                roles=("params", "cache", "plain"), single_only=True)
            exes["dense_decode"] = dict(
                fn=make_decode_step(cfg), donate=(1,),
                args=(params, cache, _sds((B, 1), i32)),
                roles=("params", "cache", "plain"), single_only=True)
    return exes


def _shard_args(exe, mesh, rules=None, kv_heads=None):
    """Re-tag the abstract args with the engine's committed placements
    (param rules / cache rules with the config's kv-head count; scalars
    replicated)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.sharding import param_specs
    from repro.parallel.statesharding import cache_specs

    def tag(tree, specs):
        return jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
            tree, specs)

    out = []
    for arg, role in zip(exe["args"], exe["roles"]):
        if role == "params":
            out.append(tag(arg, param_specs(arg, mesh, rules=rules)))
        elif role in ("layers", "cache"):
            out.append(tag(arg, cache_specs(arg, mesh, kv_heads=kv_heads)))
        else:
            ndim = len(arg.shape)
            s = NamedSharding(mesh, P(*([None] * ndim)))
            out.append(jax.ShapeDtypeStruct(arg.shape, arg.dtype, sharding=s))
    return tuple(out)


def _lower(exe, mesh, *, rules=None, donate_override=None, kv_heads=None):
    import jax
    from repro.parallel.sharding import set_mesh
    donate = exe["donate"] if donate_override is None else donate_override
    jf = exe["fn"] if exe.get("prejit") else \
        jax.jit(exe["fn"], donate_argnums=donate)
    args = exe["args"] if mesh is None else \
        _shard_args(exe, mesh, rules, kv_heads)
    ctx = set_mesh(mesh) if mesh is not None else contextlib.nullcontext()
    with ctx:
        lowered = jf.lower(*args)
        compiled = lowered.compile()
    return donate, args, lowered, compiled


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------

_HLO_DT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
           "int8": "s8", "uint8": "u8", "int32": "s32", "int64": "s64",
           "bool": "pred", "int4": "s4", "uint4": "u4", "float64": "f64"}


def _leaf_hlo_shape(leaf) -> str:
    import numpy as np
    code = _HLO_DT.get(np.dtype(leaf.dtype).name, str(leaf.dtype))
    return f"{code}[{','.join(str(d) for d in leaf.shape)}]"


def _donated_leaves(args, donate):
    import jax
    out = []
    for i in donate:
        out.extend(jax.tree_util.tree_leaves(args[i]))
    return out


def check_donation(hlo: str, args, donate, label: str,
                   exact_shapes: bool = True,
                   roles=None) -> List[Finding]:
    """Every donated leaf must be aliased into an output.  With
    ``exact_shapes`` (single-device cells) the aliased parameters' shape
    multiset must equal the donated leaves'; mesh cells check the count
    (HLO parameter shapes there are per-device slices).  ``roles``
    additionally pins WHICH operands must be donated: any cache/pool
    argument outside ``donate`` means the jit site forgot its
    ``donate_argnums`` — the double-buffered pool is live twice."""
    import jax
    out_roles: List[Finding] = []
    if roles is not None:
        for i, r in enumerate(roles):
            if r in ("layers", "cache") and i not in donate:
                out_roles.append(Finding(
                    RULE_DONATION, ENGINE_REL, 0,
                    f"{label}: operand {i} ({r}) is the KV pool but is "
                    "not in donate_argnums — the executable keeps input "
                    "AND output pools live, doubling cache HBM"))
    leaves = _donated_leaves(args, donate)
    aliases = input_output_aliases(hlo)
    out: List[Finding] = out_roles
    if len(aliases) < len(leaves):
        out.append(Finding(
            RULE_DONATION, ENGINE_REL, 0,
            f"{label}: {len(leaves)} donated buffer leaf(s) but compiled "
            f"HLO aliases only {len(aliases)} — XLA dropped the donation; "
            "the un-aliased pools exist twice in device memory"))
        return out
    if exact_shapes and leaves:
        from repro.analysis.hlo import entry_param_shapes
        pshapes = entry_param_shapes(hlo)
        want = sorted(_leaf_hlo_shape(l) for l in leaves)
        got = sorted(pshapes[a["param"]] for a in aliases
                     if a["param"] < len(pshapes))
        if got != want:
            out.append(Finding(
                RULE_DONATION, ENGINE_REL, 0,
                f"{label}: aliased parameter shapes {got} != donated leaf "
                f"shapes {want} — donation landed on the wrong buffers"))
    return out


def _protected_sizes(exe) -> Dict[int, str]:
    """Full byte size → description of every tensor GSPMD must never
    reassemble: KV pool / scale side-pool leaves and folded ``*_fw``
    bitplane params."""
    import jax
    import numpy as np
    out: Dict[int, str] = {}

    def nbytes(l):
        n = 1
        for d in l.shape:
            n *= d
        return n * np.dtype(l.dtype).itemsize

    for arg, role in zip(exe["args"], exe["roles"]):
        if role in ("layers", "cache"):
            for path, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]:
                out[nbytes(leaf)] = f"pool leaf {jax.tree_util.keystr(path)}"
        elif role == "params":
            for path, leaf in jax.tree_util.tree_flatten_with_path(arg)[0]:
                if re.search(r"_fw'?\]$", jax.tree_util.keystr(path)):
                    out[nbytes(leaf)] = \
                        f"fw bitplane {jax.tree_util.keystr(path)}"
    return out


def check_collectives(hlo: str, exe, exe_name: str, mac_kind: str,
                      mesh, label: str,
                      expected=None) -> Tuple[List[Finding], dict]:
    """Single-device: no collectives at all.  model=2: per-op counts ==
    the pinned table, and no all-gather output as large as a protected
    (pool/scale/fw) tensor's full size."""
    instrs = collective_instrs(hlo)
    counts = {op: 0 for op in _COLL_OPS}
    for op, _, _ in instrs:
        counts[op] += 1
    obs = {"counts": {k: v for k, v in counts.items() if v},
           "wire_bytes": sum(sz for _, sz, _ in instrs)}
    out: List[Finding] = []
    if mesh is None:
        if instrs:
            out.append(Finding(
                RULE_COLLECTIVES, ENGINE_REL, 0,
                f"{label}: single-device executable contains collectives "
                f"{obs['counts']} — a sharding constraint leaked into the "
                "unsharded path"))
        return out, obs
    table = EXPECTED_COLLECTIVES if expected is None else expected
    want = table.get((exe_name, mac_kind))
    if want is not None:
        want_full = {op: want.get(op, 0) for op in _COLL_OPS}
        if counts != want_full:
            out.append(Finding(
                RULE_COLLECTIVES, ENGINE_REL, 0,
                f"{label}: model=2 collective counts "
                f"{ {k: v for k, v in counts.items() if v} } != pinned "
                f"{ {k: v for k, v in want_full.items() if v} } — GSPMD "
                "changed the step's communication pattern"))
    protected = _protected_sizes(exe)
    for op, size, _ in instrs:
        if op == "all-gather" and size in protected:
            out.append(Finding(
                RULE_COLLECTIVES, ENGINE_REL, 0,
                f"{label}: all-gather reassembles {protected[size]} "
                f"({size} bytes) — a sharded tensor is being replicated "
                "every step"))
    return out, obs


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _iter_eqns(sub)


def check_capture(fn, args, label: str,
                  big_bytes: int = LARGE_CONST_BYTES) -> List[Finding]:
    """Jaxpr-level purity: no >1MB closed-over array constant, no host
    callbacks, no f64 anywhere in the trace."""
    import jax
    import numpy as np
    closed = jax.make_jaxpr(fn)(*args)
    out: List[Finding] = []
    for c in closed.consts:
        if hasattr(c, "shape") and np.asarray(c).nbytes >= big_bytes:
            out.append(Finding(
                RULE_CAPTURE, ENGINE_REL, 0,
                f"{label}: closed-over constant {tuple(c.shape)} "
                f"({np.asarray(c).nbytes:,} bytes) baked into the "
                "executable — pass weights as arguments so they are "
                "shardable/donatable"))
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if "callback" in name:
            out.append(Finding(
                RULE_CAPTURE, ENGINE_REL, 0,
                f"{label}: host callback '{name}' inside a serving "
                "executable — blocks the device critical path"))
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64:
                out.append(Finding(
                    RULE_CAPTURE, ENGINE_REL, 0,
                    f"{label}: f64 value produced by '{name}' — doubles "
                    "bandwidth on every accelerator"))
                break
    return out


def check_hlo_constants(hlo: str, label: str,
                        big_bytes: int = LARGE_CONST_BYTES) -> List[Finding]:
    out = []
    for shape, nbytes in constants(hlo, min_bytes=big_bytes):
        out.append(Finding(
            RULE_CAPTURE, ENGINE_REL, 0,
            f"{label}: compiled executable embeds a {shape} constant "
            f"({nbytes:,} bytes)"))
    return out


def check_donation_sites(sources: Optional[Dict[str, str]] = None
                         ) -> List[Finding]:
    """AST sweep: every ``jax.jit(...)`` call in the serving modules
    must pass ``donate_argnums`` or carry a ``DONATION_WAIVERS`` entry
    keyed ``<file>:<enclosing def/class scope>``.  ``sources`` overrides
    file contents (self-test seam)."""
    import ast
    import os
    from repro.analysis.lint import repo_root
    out: List[Finding] = []
    root = repo_root()
    for rel in _DONATION_SCAN:
        if sources is not None and rel in sources:
            text = sources[rel]
        else:
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                continue
            with open(path, encoding="utf-8") as f:
                text = f.read()
        tree = ast.parse(text)
        scopes: List[Tuple[ast.AST, str]] = []
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                for child in ast.walk(node):
                    scopes.append((child, node.name))
        scope_of = {id(n): s for n, s in scopes}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fnode = node.func
            is_jit = (isinstance(fnode, ast.Attribute)
                      and fnode.attr == "jit"
                      and isinstance(fnode.value, ast.Name)
                      and fnode.value.id == "jax")
            # functools.partial(jax.jit, donate_argnums=...) sites
            is_partial_jit = (
                isinstance(fnode, ast.Attribute) and fnode.attr == "partial"
                and any(isinstance(a, ast.Attribute) and a.attr == "jit"
                        for a in node.args))
            if not (is_jit or is_partial_jit):
                continue
            has_donate = any(kw.arg == "donate_argnums"
                             for kw in node.keywords)
            key = f"{rel}:{scope_of.get(id(node), '<module>')}"
            if not has_donate and key not in DONATION_WAIVERS:
                out.append(Finding(
                    RULE_DONATION, rel, node.lineno,
                    f"jax.jit without donate_argnums in '{key}' — serving "
                    "steps must donate their cache/pool argument (or add "
                    "a justified DONATION_WAIVERS entry)"))
    return out


# ---------------------------------------------------------------------------
# per-cell audit
# ---------------------------------------------------------------------------

def audit_cell(arch: str, cfg, dt: str, mesh, mesh_kind: str, *,
               full: bool = False, mac_kind: str = "dense",
               exes=None, rules=None, donate_override=None,
               expected_collectives=None) -> Tuple[List[Finding], dict]:
    findings: List[Finding] = []
    cell: dict = {"arch": arch, "kv_dtype": dt, "mesh": mesh_kind,
                  "mac": mac_kind, "executables": {}}
    if exes is None:
        exes = _executables(cfg, full=full)
    for name, exe in exes.items():
        if mesh is not None and exe.get("single_only"):
            continue
        label = f"{arch}/{dt}/{mesh_kind}/{mac_kind}/{name}"
        donate, args, lowered, compiled = _lower(
            exe, mesh, rules=rules, donate_override=donate_override,
            kv_heads=cfg.n_kv_p)
        hlo = compiled.as_text()
        findings += check_donation(hlo, exe["args"], donate, label,
                                   exact_shapes=(mesh is None),
                                   roles=exe["roles"])
        f_coll, obs = check_collectives(hlo, exe, name, mac_kind, mesh,
                                        label, expected=expected_collectives)
        findings += f_coll
        if mesh is None:
            findings += check_capture(exe["fn"], exe["args"], label)
            findings += check_hlo_constants(hlo, label)
        mem = compiled.memory_analysis()
        rec = {"collectives": obs,
               "aliases": len(input_output_aliases(hlo)),
               "donated_leaves": len(_donated_leaves(exe["args"], donate))}
        if mem is not None:
            rec["memory"] = {k: int(getattr(mem, k, 0)) for k in _MEM_FIELDS}
        cell["executables"][name] = rec
    return findings, cell


def encoded_cell_cfg():
    """A calibration-free encoded-serving config + abstract params:
    the exact AND-plane product circuit folds the PRIMARY_ARCH reduced
    weights into real ``(U, k, n)`` bitplane tensors, then everything is
    stripped back to ShapeDtypeStructs for lowering."""
    import tempfile
    import jax
    from repro.configs.registry import get_config
    from repro.core.circuits import exact_product_circuit
    from repro.core.encoding import EncodingSpec
    from repro.core.layers import MacConfig
    from repro.core.mac import EncodedMac
    from repro.models import init_model
    from repro.serve import prepare_encoded_serving

    cfg0 = dataclasses.replace(get_config(PRIMARY_ARCH).reduced(),
                               mac=MacConfig(bits=8))
    params = init_model(jax.random.PRNGKey(0), cfg0)
    circ, s = exact_product_circuit(8, 8)
    exact = EncodedMac.from_spec(EncodingSpec(circ, s, 0.0))
    ov = {nm: exact for nm in ("wq", "wk", "wv", "wo", "wi", "wg")}
    with tempfile.TemporaryDirectory() as td:
        pe, ce, _ = prepare_encoded_serving(
            params, cfg0, macs_override=ov, cache_dir=td,
            calib_batches=1, calib_batch_size=1, calib_seq=8, verbose=False)
    pe_abs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pe)
    return ce, pe_abs


def _encoded_exes(ce, pe_abs):
    """Encoded decode-step descriptor (the hot executable of `--mac
    encoded` serving) with the abstract folded params swapped in."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_paged_cache
    from repro.serve.engine import make_paged_decode_step
    layers = jax.eval_shape(
        lambda: init_paged_cache(ce, N_PAGES, PAGE_SIZE))["layers"]
    i32 = jnp.int32
    return {"paged_decode": dict(
        fn=make_paged_decode_step(ce), donate=(1,),
        args=(pe_abs, layers, _sds((B, 1), i32),
              _sds((B, SLOT_PAGES), i32), _sds((B,), i32)),
        roles=("params", "layers", "plain", "plain", "plain"))}


def audit_encoded_cell(mesh, mesh_kind: str, *, cell_state=None,
                       rules=None, expected_collectives=None):
    """Audit the encoded decode step (folded fw bitplanes in flight).
    ``cell_state`` caches (cfg, abstract params) across mesh kinds."""
    if cell_state is None:
        cell_state = encoded_cell_cfg()
    ce, pe_abs = cell_state
    f, cell = audit_cell(PRIMARY_ARCH, ce, "bf16", mesh, mesh_kind,
                         mac_kind="encoded", exes=_encoded_exes(ce, pe_abs),
                         rules=rules,
                         expected_collectives=expected_collectives)
    return f, cell, cell_state


# ---------------------------------------------------------------------------
# recompile tracker over a deterministic smoke serving trace
# ---------------------------------------------------------------------------

# Exact XLA compilations each smoke trace must cost, per jitted step.
# One each: chunked prefill runs many chunks at ONE compiled shape, spec
# rounds reuse one draft + one verify executable, and eviction/rollback
# are host-side (no new trace).  Under spec decoding EVERY round goes
# through draft+verify, so the plain decode step never compiles (0 is
# asserted — a fallback dispatch sneaking in would be a silent double
# compile); the plain trace pins decode itself.
EXPECTED_COMPILES: Dict[str, Dict[str, int]] = {
    "plain": {"prefill": 1, "decode": 1},
    "spec": {"prefill": 1, "decode": 0, "draft": 1, "verify": 1},
}

_FRESH = itertools.count()


def run_smoke_trace(arch: str = PRIMARY_ARCH, *,
                    inject_decode_shapes=(), spec_k: int = SPEC_K):
    """Chunked prefill + decode + spec rounds + eviction on a tiny pool,
    returning (per-step compile counts, engine stats).  The config gets
    a unique (numerically irrelevant) rope_theta so the memoized jit
    pair is cold for every call — counts are absolute, not
    warmth-dependent.  ``inject_decode_shapes`` simulates a shape leak:
    each extra tokens-shape drives one off-trace decode dispatch."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.models import init_model
    from repro.serve import Engine

    cfg = get_config(arch).reduced()
    cfg = dataclasses.replace(
        cfg, rope_theta=cfg.rope_theta + 1e-4 * (1 + next(_FRESH)))
    params = init_model(jax.random.PRNGKey(0), cfg)
    # optimistic reserve + a pool two slots outgrow mid-decode → the
    # page-starved growth path runs (evictions land in the trace report)
    eng = Engine(params, cfg, n_slots=2, page_size=8, n_pages=7,
                 max_seq_pages=6, prefill_chunk=8, prefix_cache=True,
                 reserve="optimistic", spec_decode=spec_k)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab_size, 16).astype(np.int32)
    p0 = np.concatenate([shared, rng.integers(1, cfg.vocab_size, 4)
                         .astype(np.int32)])          # 20 toks → 3 chunks
    p1 = np.concatenate([shared, rng.integers(1, cfg.vocab_size, 3)
                         .astype(np.int32)])          # prefix-cache hit
    p2 = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    for p in (p0, p1, p2):
        eng.submit(p, max_new=12)
    eng.run()
    stats = eng.stats()
    for shape in inject_decode_shapes:
        # a leaked shape retraces the decode step; pools are deep-copied
        # so the live (donated) buffers stay valid
        layers = jax.tree.map(jnp.array, eng.kv.pools)
        eng._step(eng.params, layers,
                  jnp.zeros(shape, jnp.int32),
                  jnp.zeros((shape[0], eng.kv.max_seq_pages), jnp.int32),
                  jnp.zeros((shape[0],), jnp.int32))
    counts = eng.jit_tracker.counts()
    return counts, stats


def _check_trace(arch, mode, *, inject_decode_shapes, expected):
    spec_k = SPEC_K if mode == "spec" else 0
    counts, stats = run_smoke_trace(
        arch, inject_decode_shapes=inject_decode_shapes, spec_k=spec_k)
    want = EXPECTED_COMPILES[mode] if expected is None else expected
    out: List[Finding] = []
    for name, n in want.items():
        got = counts.get(name, 0)
        if got != n:
            out.append(Finding(
                RULE_RECOMPILE, ENGINE_REL, 0,
                f"{mode} smoke trace: '{name}' compiled {got}× (expected "
                f"exactly {n}) — "
                + ("a leaked shape is retracing the step"
                   if got > n else "the step never compiled; the trace "
                   "no longer exercises it")))
    if counts.get("copy_page", 0) > 1:
        out.append(Finding(
            RULE_RECOMPILE, ENGINE_REL, 0,
            f"{mode} smoke trace: copy_page compiled "
            f"{counts['copy_page']}× — COW page pairs must share one "
            "traced-scalar executable"))
    if stats.get("evictions", 0) < 1:
        out.append(Finding(
            RULE_RECOMPILE, ENGINE_REL, 0,
            f"{mode} smoke trace ran 0 evictions — the trace no longer "
            "exercises the page-starved growth path, so its compile "
            "counts prove nothing about eviction-driven retraces"))
    report = {"compiles": counts,
              "trace": {k: stats.get(k) for k in
                        ("prefill_chunks", "evictions", "cow_copies",
                         "spec_rounds", "decode_tokens", "finished",
                         "jit_compiles")}}
    return out, report


def check_recompile(arch: str = PRIMARY_ARCH, *, inject_decode_shapes=(),
                    expected=None) -> Tuple[List[Finding], dict]:
    """Two deterministic smoke traces — plain decode and speculative —
    each pinned to an EXACT per-step compile count.  ``expected``
    overrides the spec-trace table only (self-test seam)."""
    out: List[Finding] = []
    report: dict = {}
    f, report["plain"] = _check_trace(
        arch, "plain", inject_decode_shapes=(), expected=None)
    out += f
    f, report["spec"] = _check_trace(
        arch, "spec", inject_decode_shapes=inject_decode_shapes,
        expected=expected)
    out += f
    return out, report


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_compiled(archs=None, dtypes=KV_DTYPES, meshes=MESH_KINDS, *,
                 full_arch: str = PRIMARY_ARCH, encoded: bool = True,
                 recompile: bool = True) -> Tuple[List[Finding], dict]:
    """The full audit: donation-site sweep, every arch × kv dtype × mesh
    cell, the encoded cell, and the recompile smoke trace."""
    findings: List[Finding] = []
    report: dict = {"cells": {}, "recompile": {}, "skipped": [],
                    "donation_sites": 0}
    f = check_donation_sites()
    findings += f
    report["donation_sites"] = len(f)
    for arch, cfg, dt in _paged_geometries(archs, dtypes):
        for mk in meshes:
            mesh = _make_mesh(mk)
            if mesh == "skip":
                report["skipped"].append(f"{arch}/{dt}/{mk}: <2 devices")
                continue
            f, cell = audit_cell(arch, cfg, dt, mesh, mk,
                                 full=(arch == full_arch))
            findings += f
            report["cells"][f"{arch}/{dt}/{mk}"] = cell
    if encoded:
        state = None
        for mk in meshes:
            mesh = _make_mesh(mk)
            if mesh == "skip":
                report["skipped"].append(f"encoded/{mk}: <2 devices")
                continue
            f, cell, state = audit_encoded_cell(mesh, mk, cell_state=state)
            findings += f
            report["cells"][f"{PRIMARY_ARCH}/encoded/{mk}"] = cell
    if recompile:
        f, rep = check_recompile()
        findings += f
        report["recompile"] = rep
    return findings, report
