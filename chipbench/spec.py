"""The benchmark's data, found by name: ``BENCHMARK.json`` at the root,
``configs/<name>.json``, ``traffic/<name>.json``,
``workloads/<name>.json`` and ``metrics/<name>.py``.  Adding a cell, a
mix or a metric adds files and entries; no code here names one."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"chipbench: no {kind[:-1]} named {name!r} "
                         f"({path.relative_to(ROOT)} is missing)")
    return json.loads(path.read_text())


def benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit("chipbench: BENCHMARK.json is missing")
    return json.loads(path.read_text())


def cell(name: str) -> dict:
    """The workload ``name`` with its config and traffic loaded."""
    w = _load("workloads", name)
    return dict(w, config=_load("configs", w["config"]),
                traffic=_load("traffic", w["traffic"]))


def per_layer_metrics(bench: dict, cell_name: str, reports: set) -> list:
    """The per-layer metric entries this cell reports: those listing it,
    and those without a list whose end-to-end metric the cell reports."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in reports:
            out.append(m)
    return out


def end_to_end_metrics(bench: dict, cell_name: str) -> list:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric_name: str):
    """The ``read(run)`` function of ``metrics/<metric_name>.py``."""
    path = HERE / "metrics" / f"{metric_name}.py"
    if not path.is_file():
        raise SystemExit(f"chipbench: no reader for metric {metric_name!r}"
                         f" ({path.relative_to(ROOT)} is missing)")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
