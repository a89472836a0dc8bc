"""Find a cell's files by name: BENCHMARK.json, the configuration, its
architecture module, the traffic mix and the per-layer metric readers."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of BENCHMARK.json with everything it names loaded."""
    name: str
    chips: int
    config: dict                 # bench/configs/<config>.json
    arch: ModuleType             # bench/arch/<architectures[0]>.py
    traffic: dict                # bench/traffic/<traffic>.json
    end_to_end: List[dict]       # metric entries this cell reports
    per_layer: List[dict]


def _applies(metric: dict, workload: str, reported: set) -> bool:
    """A metric with ``workloads`` is reported in those cells; one without
    in every cell that reports the end-to-end metric it moves (or, for an
    end-to-end metric, in every cell)."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reported


def load_cell(workload: str, root: str = ROOT,
              benchmark: Optional[dict] = None) -> Cell:
    bm = benchmark if benchmark is not None else \
        load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    cfgs = {c["name"]: c for c in bm["configs"]}
    config = load_json(os.path.join(root, cfgs[w["config"]]["file"]))
    traffic = load_json(traffic_path(w["traffic"], root))
    e2e = [m for m in bm["end_to_end"] if _applies(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _applies(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                arch=arch_module(config, root), traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def traffic_path(mix: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{mix}.json")


def _load(path: str, mod_name: str) -> ModuleType:
    """The module at ``path``, loaded by path (names may hold dots) and
    kept under ``mod_name``, so a second load of the same file returns
    the same module and its jitted functions compile once."""
    mod = sys.modules.get(mod_name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return mod


ARCH_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")
ARCH_NEEDS = ("shapes", "dims", "Reference")


def arch_module(c: dict, root: str = ROOT) -> ModuleType:
    """The architecture module of configuration ``c``:
    ``bench/arch/<c["architectures"][0]>.py`` under ``root``, with what
    ``bench/arch/__init__.py`` says it provides."""
    name = c["architectures"][0]
    rel = f"bench/arch/{name}.py"
    if not ARCH_NAME.match(name):
        raise ValueError(f"configuration {c['name']!r}: architecture "
                         f"{name!r} is no module name")
    path = os.path.join(root, *rel.split("/"))
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"configuration {c['name']!r} is of architecture {name!r}, "
            f"which has no module: add {rel} (bench/arch/__init__.py "
            "says what it provides)")
    mod = _load(path, "bench_arch_" + name)
    missing = [a for a in ARCH_NEEDS if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"{rel} lacks {', '.join(missing)}")
    if c.get("encoded") and not hasattr(mod, "calibrate"):
        raise ValueError(
            f"configuration {c['name']!r} has an encoded block, and {rel} "
            "has no calibrate for the encoded linears' activation scales")
    return mod


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    return _load(path, mod_name).read


def readers(metrics: List[dict], root: str = ROOT) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in metrics}
