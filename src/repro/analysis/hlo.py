"""Compiled (post-SPMD) HLO text parsing — the layer under the
compiled-executable audit (``analysis/compiled.py``, DESIGN.md §13).

cost_analysis() has no collective traffic — we sum tensor sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
instruction, with ring-algorithm wire factors from the replica-group size:

  all-gather        (n−1)/n · out_bytes
  all-reduce        2(n−1)/n · bytes
  reduce-scatter    (n−1) · out_bytes        (input = n·out streams through)
  all-to-all        (n−1)/n · bytes
  collective-permute  bytes

Shapes in compiled HLO are already per-device (partitioned), so sums are
per-device wire bytes.

Beyond traffic, the audit needs two more facts only the compiled text
states: ``input_output_alias`` (which donated parameters XLA actually
aliased into outputs — a dropped donation silently doubles KV HBM) and
``constant`` instructions (a weight captured by closure lowers to a
baked-in constant instead of a parameter).  Both parsers live here so
every consumer reads one grammar.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
    # sub-byte types round up to one byte per element
    "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

# module-header donation record:  { {out_idx}: (param, {path}, kind) }
_ALIAS_RE = re.compile(
    r"\{([0-9, ]*)\}:\s*\(\s*(\d+)\s*,\s*\{([0-9, ]*)\}\s*,?\s*"
    r"(may-alias|must-alias)?\s*\)")
_ALIAS_BLOCK_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*,\s*\w+=",
                             re.DOTALL)
_CONST_RE = re.compile(
    r"^\s*%?[\w.\-]+\s*=\s*(\w+\[[0-9,]*\])[^=]*\bconstant\(",
    re.MULTILINE)
_ENTRY_RE = re.compile(r"entry_computation_layout=\{\((.*?)\)->",
                       re.DOTALL)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """→ {op_name: wire_bytes_per_device}, plus '_total'."""
    out: dict = defaultdict(float)
    for op, size, n in collective_instrs(hlo_text):
        if op == "all-gather":
            wire = size * (n - 1) / n
        elif op == "all-reduce":
            wire = 2.0 * size * (n - 1) / n
        elif op == "reduce-scatter":
            wire = size * (n - 1)
        elif op == "all-to-all":
            wire = size * (n - 1) / n
        else:                        # collective-permute
            wire = float(size)
        out[op] += wire
    out["_total"] = sum(v for k, v in out.items() if not k.startswith("_"))
    return dict(out)


def collective_instrs(hlo_text: str) -> List[Tuple[str, int, int]]:
    """Every collective instruction as ``(op, out_bytes, group_size)``.

    ``out_bytes`` is the instruction's (full) result size — for an
    all-gather that is the gathered tensor, which is what the audit
    compares against pool/bitplane leaf sizes."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:        # started op already counted at -start
            continue
        shape_str = m.group(1) or m.group(2)
        op = m.group(3)
        size = _shape_bytes(shape_str)
        n = 1
        g = _GROUPS_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                n = int(gi.group(2))
        out.append((op, size, max(n, 2)))
    return out


def count_ops(hlo_text: str, names=("fusion", "all-gather", "all-reduce",
                                    "reduce-scatter", "all-to-all",
                                    "collective-permute", "while", "dot",
                                    "custom-call")) -> dict:
    counts = {}
    for n in names:
        counts[n] = len(re.findall(rf"\b{n}\(", hlo_text)) + \
            len(re.findall(rf"\b{n}-start\(", hlo_text))
    return counts


def input_output_aliases(hlo_text: str) -> List[dict]:
    """Donation records from the HLO module header.

    ``input_output_alias={ {1}: (1, {}, may-alias), ... }`` →
    ``[{"out": (1,), "param": 1, "path": (), "kind": "may-alias"}]``.
    An empty list means XLA aliased nothing — every donated buffer was
    silently copied."""
    header = hlo_text.split("\n", 1)[0]
    blk = _ALIAS_BLOCK_RE.search(header)
    if not blk:
        return []
    out = []
    for m in _ALIAS_RE.finditer(blk.group(1)):
        out.append({
            "out": tuple(int(x) for x in m.group(1).split(",") if x.strip()),
            "param": int(m.group(2)),
            "path": tuple(int(x) for x in m.group(3).split(",")
                          if x.strip()),
            "kind": m.group(4) or "may-alias",
        })
    return out


def entry_param_shapes(hlo_text: str) -> List[str]:
    """Flat entry-parameter shape strings (``'f32[2,9,8,1,32]'`` …) in
    parameter order, from ``entry_computation_layout``."""
    header = hlo_text.split("\n", 1)[0]
    m = _ENTRY_RE.search(header)
    if not m:
        return []
    return [f"{dt}[{dims}]" for dt, dims in _SHAPE_RE.findall(m.group(1))]


def constants(hlo_text: str, min_bytes: int = 0) -> List[Tuple[str, int]]:
    """``constant(...)`` instructions as ``(shape_str, bytes)``, largest
    first, filtered to ``bytes >= min_bytes``.  Big entries are weights
    baked into the executable instead of passed as arguments."""
    out = []
    for m in _CONST_RE.finditer(hlo_text):
        shape = m.group(1)
        b = _shape_bytes(shape)
        if b >= min_bytes:
            out.append((shape, b))
    return sorted(out, key=lambda t: -t[1])
