"""Find a cell's files by name: BENCHMARK.json, the configuration, the
traffic mix and the per-layer metric readers."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
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
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def traffic_path(mix: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{mix}.json")


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``.  Names may hold dots
    (``prefill_mfu.batch``), so the file is loaded by path."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def readers(metrics: List[dict], root: str = ROOT) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], root) for m in metrics}
