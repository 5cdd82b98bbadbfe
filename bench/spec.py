"""Find a cell's pieces by name: its configuration, traffic mix, limits and
metric readers. Nothing here knows a cell; adding one is adding files."""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(bm: dict, name: str) -> dict:
    """Everything one run of cell `name` needs, as plain data."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bm["configs"]}
    conf = load_json(os.path.join(CHECKOUT, confs[w["config"]]["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", name + ".json"))
    return {"name": name, "chips": w["chips"], "conf": conf,
            "traffic": traffic, "limits": limits,
            "end_to_end": metrics_for(bm["end_to_end"], name),
            "per_layer": metrics_for(bm["per_layer"], name)}


def metrics_for(entries: List[dict], cell_name: str) -> List[dict]:
    """The metrics a cell reports: those that list it, or list no cells."""
    return [m for m in entries
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str) -> ModuleType:
    """`bench/metrics/<metric>.py`; its `read(run)` returns a number or
    None when the run holds nothing to read."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table["devices"][device_kind]
