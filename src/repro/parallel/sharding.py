"""Sharding rules + mesh context.

Axes convention (production mesh, launch/mesh.py):
  single-pod: (data=16, model=16); multi-pod: (pod=2, data=16, model=16).
``pod`` is an outer data axis (batch + FSDP shard over ('pod','data')).

Param sharding is *path-based*: the flattened pytree path of every parameter
is matched against rules below.  Activations are annotated in model code via
``constrain`` which no-ops when no mesh is active (single-device tests).
"""
from __future__ import annotations

import re
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS_MODEL = "model"
AXIS_DATA = "data"
AXIS_POD = "pod"
AXIS_BATCH = (AXIS_POD, AXIS_DATA)     # logical batch = pod × data
AXIS_EXPERT = AXIS_MODEL               # experts sharded over the model axis

_ctx = threading.local()


class MeshCtx:
    """Activate a mesh for model-code sharding annotations."""

    def __init__(self, mesh: Optional[Mesh]):
        self.mesh = mesh

    def __enter__(self):
        _ctx.mesh = self.mesh
        return self.mesh

    def __exit__(self, *a):
        _ctx.mesh = None


def set_mesh(mesh: Optional[Mesh]) -> MeshCtx:
    return MeshCtx(mesh)


def get_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


def _filter_axes(mesh: Mesh, spec_items):
    """Drop axis names absent from the mesh (e.g. 'pod' on single-pod)."""
    names = set(mesh.axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    return P(*[keep(e) for e in spec_items])


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active, else identity."""
    mesh = get_mesh()
    if mesh is None:
        return x
    p = _filter_axes(mesh, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, p))


def batch_spec(mesh: Mesh, shape, batch_axis: int = 0) -> NamedSharding:
    """Inputs: batch over ('pod','data'), rest replicated.

    ``shape`` may be a tuple (divisibility-checked: batch=1 cells replicate)
    or an int ndim (assumed divisible)."""
    if isinstance(shape, int):
        ndim, dim0 = shape, None
    else:
        ndim, dim0 = len(shape), shape[batch_axis]
    items = [None] * ndim
    items[batch_axis] = AXIS_BATCH
    if dim0 is not None:
        names = [a for a in AXIS_BATCH if a in mesh.axis_names]
        total = int(np.prod([mesh.shape[a] for a in names])) if names else 1
        if total and dim0 % total != 0:
            items[batch_axis] = None
    return NamedSharding(mesh, _filter_axes(mesh, items))


# ---------------------------------------------------------------------------
# Per-linear tensor-parallel roles over the model axis (DESIGN.md §6).
#
#   'column'     — the OUTPUT dim is model-sharded (wq/wk/wv/wi/wg/lm_head):
#                  no collective; activations leave feature-sharded.
#   'row'        — the INPUT dim is model-sharded (wo/wout): every shard
#                  computes a partial accumulation that is psum-reduced.
#   'replicated' — everything else (low-rank downs, routers, small projs).
#
# The same table drives (a) the sharding rules for the folded encoded-serving
# bitplane tensors below and (b) the shard-local kernel dispatch in
# kernels/ops.encoded_matmul.
#
# Keyed by bare param name while the placement rules are path-keyed: sound
# because the only servable-folded linear named 'w' is the untied lm_head
# (column, matching its path rule) — other 'w' linears (mtp proj, routers)
# are never walked by the calibration capture, so their role is never
# consulted.  A kernel receiving a role that disagrees with placement stays
# correct regardless (shard_map reshards); only locality is lost.
# ---------------------------------------------------------------------------

LINEAR_ROLES: dict = {
    "wq": "column", "wk": "column", "wv": "column", "wkv": "column",
    "wqkv": "column", "wq_b": "column", "wk_b": "column", "wv_b": "column",
    "wi": "column", "wg": "column", "win": "column", "wup": "column",
    "w": "column",                        # lm_head / untied output head
    "wo": "row", "wout": "row",
}


def linear_role(name: str) -> str:
    """Tensor-parallel role of linear param ``name`` ('column' | 'row' |
    'replicated').  Advisory for placement: the kernel falls back to the
    unsharded path when the shapes don't divide the model axis."""
    return LINEAR_ROLES.get(name, "replicated")


# ---------------------------------------------------------------------------
# Parameter sharding rules (path regex → PartitionSpec items).
# Paths look like "layers/attn/wq", "layers/moe/experts_w1", "embed/table"…
# Rules are checked in order; first match wins.  ``F`` marks the dim that the
# FSDP axis additionally shards when cfg.fsdp is on (largest remaining dim).
# ---------------------------------------------------------------------------

_RULES: list[tuple[str, tuple]] = [
    # folded encoded-serving bitplane tensors ``*_fw (U, k, n)`` / ``*_fb
    # (n,)`` (DESIGN.md §6): the U plane dim is always replicated; column-
    # parallel projections shard n (mirroring the fp out-dim rule), row-
    # parallel ones shard k and keep the bias replicated — it is added once
    # after the psum of partial encoded accumulations.
    (r"w(q|k|v|kv|qkv|i|g|in|up)_fw$", (None, "fsdp", "model")),
    (r"w(q|k|v|kv|qkv|i|g|in|up)_fb$", ("model",)),
    (r"w(o|out)_fw$",        (None, "model", "fsdp")),
    (r"w(o|out)_fb$",        None),
    (r"(lm_head|head)/w_fw$", (None, "fsdp", "model")),
    (r"(lm_head|head)/w_fb$", ("model",)),
    (r"_(fw|fb)$",           None),    # un-roled folds: replicate
    (r"_(as|ws|s)$",         None),    # per-linear scales: replicate
    # embeddings / heads: shard vocab over model
    (r"embed/table$",        ("model", "fsdp")),
    (r"lm_head/w$",          ("fsdp", "model")),
    (r"mtp/.*head/w$",       ("fsdp", "model")),
    (r"mtp/proj/w$",         ("fsdp", "model")),  # (2d, d) combiner
    # read-every-step position table: deliberately replicated — sharding
    # it would trade 10s of MB/device for an all-gather per added slice
    (r"pos_table$",          None),
    # attention projections: in-dim × (heads*dim) — shard head dim over model
    (r"(attn|mla)/w(q|k|v|kv|qkv)(_b)?$", ("fsdp", "model")),
    (r"(attn|mla)/w(q_a|kv_a|kr)$",       ("fsdp", None)),   # low-rank down
    (r"(attn|mla)/w(q_b|k_b|v_b)$",       (None, "model")),  # low-rank up
    (r"(attn|mla)/wo$",      ("model", "fsdp")),
    # dense mlp: d × f sharded over model on f
    (r"mlp/w(i|g)$",         ("fsdp", "model")),
    (r"mlp/wo$",             ("model", "fsdp")),
    # MoE experts: experts over model (EP), dims unsharded (fsdp on d)
    (r"moe/experts_w(i|g)$", ("model", "fsdp", None)),
    (r"moe/experts_wo$",     ("model", None, "fsdp")),
    (r"moe/router/w$",       (None, None)),
    (r"moe/shared/w(i|g)$",  ("fsdp", "model")),
    (r"moe/shared/wo$",      ("model", "fsdp")),
    # ssm / xlstm projections
    (r"(ssm|mlstm|slstm)/w(in|i|g)$",  ("fsdp", "model")),
    (r"(ssm|mlstm|slstm)/w(out|o)$",   ("model", "fsdp")),
    (r"(ssm|mlstm|slstm)/",  None),    # small per-channel params: replicate
    # norms, biases, scalars: replicated
    (r"(norm|ln)",           None),
]


def rule_for_path(path: str, rules=None):
    """First matching ``(pattern, items)`` rule for ``path``, or ``None``
    when NO rule matches.  ``items is None`` means an explicit replicate
    rule — distinct from no rule at all, which also replicates but is the
    silent default ``analysis/shardcheck.py`` flags for large leaves.
    ``rules`` overrides ``_RULES`` (analysis seams only)."""
    for pat, items in (_RULES if rules is None else rules):
        if re.search(pat, path):
            return pat, items
    return None


def _spec_for_path(path: str, shape: tuple, fsdp: bool, rules=None) -> P:
    rule = rule_for_path(path, rules)
    if rule is None or rule[1] is None:
        return P()  # explicit replicate rule, or no-match default
    items = rule[1]
    out = []
    for i, e in enumerate(items[:len(shape)]):
        if e == "fsdp":
            out.append(AXIS_BATCH if fsdp else None)
        elif e == "model":
            out.append(AXIS_MODEL)
        else:
            out.append(None)
    # pad missing dims with None
    out += [None] * (len(shape) - len(out))
    return P(*out)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


# Stage containers whose params carry a leading stacked-layer dim — sharding
# rules apply to the per-layer shape, shifted right by one.
STACKED_STAGES = ("stack", "moe_stack", "dense_prefix", "xlstm", "enc",
                  "dec")


def param_specs(params, mesh: Mesh, fsdp: bool = False, rules=None):
    """PartitionSpec pytree (NamedShardings) mirroring ``params``.

    Dims whose size does not divide the assigned mesh axes fall back to
    replication on that dim (divisibility-safe by construction — configs pad
    vocab/heads, but e.g. tiny smoke models stay runnable on any mesh).
    ``rules`` overrides the ``_RULES`` table — the compiled-audit self-test
    (DESIGN.md §13) shards under a doctored table to plant stray gathers.
    """
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))

    def norm(e):
        if e is None:
            return None
        if isinstance(e, (tuple, list)):
            return tuple(a for a in e if a in mesh.axis_names)
        return e if e in mesh.axis_names else None

    def ok(dim_size, entry):
        entry = norm(entry)
        if entry is None:
            return True
        names = entry if isinstance(entry, tuple) else (entry,)
        total = int(np.prod([axis_size.get(a, 1) for a in names]))
        return dim_size % total == 0

    def one(path, leaf):
        pstr = _path_str(path)
        stacked = pstr.split("/", 1)[0] in STACKED_STAGES
        shape = leaf.shape[1:] if stacked and leaf.ndim >= 1 else leaf.shape
        spec = _spec_for_path(pstr, shape, fsdp, rules)
        items = list(spec)[:len(shape)] + [None] * (len(shape) - len(spec))
        if stacked:
            items = [None] + items          # layer-stack dim replicated
        items = [e if ok(leaf.shape[i], e) else None
                 for i, e in enumerate(items)]
        return NamedSharding(mesh, _filter_axes(mesh, items))

    return jax.tree_util.tree_map_with_path(one, params)
