"""Finding a cell's files by the names in BENCHMARK.json.

A cell is a pair of names. ``configs/<config>.json`` (or the ``file`` the
entry gives), ``traffic/<traffic>.json`` and ``metrics/<metric>.json`` with
its reader are looked up by name, so a later PR adds a cell, a
configuration, a traffic mix or a per-layer metric with new files and new
entries and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict


class CellError(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


HERE = Path(__file__).resolve().parent


def load_cell(root: Path, workload: str, bench_dir: Path = HERE) -> dict:
    """bench_dir: where traffic/, metrics/, limits.json and peaks.json are
    looked up (the benchmark's own directory; a test points it elsewhere)."""
    bench = load_json(root / "BENCHMARK.json")
    here = Path(bench_dir)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise CellError(f"no workload {workload!r} in BENCHMARK.json (have: {names})")
    cfg_entry = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise CellError(f"workload {workload!r} names no known config")
    config = load_json(root / cfg_entry["file"])
    traffic_path = here / "traffic" / f"{entry['traffic']}.json"
    if not traffic_path.exists():
        raise CellError(f"no traffic file {traffic_path}")
    traffic = load_json(traffic_path)

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": entry["chips"],
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "limits": load_json(here / "limits.json"),
        "peaks": load_json(here / "peaks.json"),
        "root": str(root),
        "bench_dir": str(here),
    }


def load_reader(name: str, bench_dir: Path = HERE) -> Callable[[dict], object]:
    """The reader of per-layer metric `name`: ``metrics/<name>.json`` says
    which file beside it holds ``read(ctx)``."""
    mdir = Path(bench_dir) / "metrics"
    spec_path = mdir / f"{name}.json"
    if not spec_path.exists():
        raise CellError(f"per-layer metric {name!r} has no {spec_path.name}")
    meta = load_json(spec_path)
    path = mdir / meta.get("reader", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_per_layer(cell: dict, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell["per_layer"]:
        value = load_reader(m["name"], cell.get("bench_dir", HERE))(ctx)
        if value is None:
            continue  # nothing to read: the metric is left out, never 0
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
