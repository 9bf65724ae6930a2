"""Finding a cell's files by the names in BENCHMARK.json.

A cell is a pair of names. ``configs/<config>.json`` (or the ``file`` the
entry gives), ``traffic/<traffic>.json``, ``metrics/<metric>.json`` with
its reader and ``evaluators/<evaluator>.py`` (the name the configuration
gives under ``engine``) are looked up by name, so a later PR adds a cell, a
configuration, a traffic mix, a per-layer metric or an evaluator with new
files and new entries and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict


class CellError(Exception):
    pass


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


HERE = Path(__file__).resolve().parent

# what an evaluator's file provides (load_evaluator says what each is)
EVALUATOR_FUNCTIONS = ("load_weights", "evaluate", "program_params", "net_work")


def _load_module(name: str, path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
    if not isinstance(traffic.get("pool_seed"), int):
        # games made from --seed itself let the seed set the rate (PERF.md 2)
        raise CellError(f"{traffic_path} states no pool_seed")
    evaluator = config.get("engine", {}).get("evaluator")
    if evaluator is None:
        raise CellError(f"{cfg_entry['file']} names no evaluator under engine")

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "name": workload,
        "chips": entry["chips"],
        "config": config,
        "traffic": traffic,
        "evaluator": load_evaluator(evaluator, here),
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
    return _load_module("benchmark_metric_" + name, path).read


def load_evaluator(name: str, bench_dir: Path = HERE) -> ModuleType:
    """The evaluator a configuration names, ``evaluators/<name>.py``:

    * ``load_weights(engine_cfg, root)`` → {name: numpy array}, from the
      file ``engine.net`` names or made from a seed the configuration
      states (numpy's PCG64, so every machine gets the same bytes);
    * ``evaluate(weights, pos)`` → int: the plain reference eval of a
      ``rules.Pos`` in centipawns, numpy float32, nothing of the program,
      truncated and clamped as the search clamps;
    * ``program_params(weights)``: what ``TpuEngine(params=...)`` takes; the
      file's only import of the program;
    * ``net_work(net_shapes)`` → {"flops", "bytes"}: the net's share of one
      node's work by ``work_count``'s rules, from shape keys of its own."""
    path = Path(bench_dir) / "evaluators" / f"{name}.py"
    if not path.exists():
        raise CellError(f"no evaluator file {path}")
    module = _load_module("benchmark_evaluator_" + name, path)
    missing = [f for f in EVALUATOR_FUNCTIONS
               if not callable(getattr(module, f, None))]
    if missing:
        raise CellError(f"evaluator {name!r} lacks {', '.join(missing)}")
    return module


def read_per_layer(cell: dict, ctx: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell["per_layer"]:
        value = load_reader(m["name"], cell.get("bench_dir", HERE))(ctx)
        if value is None:
            continue  # nothing to read: the metric is left out, never 0
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
