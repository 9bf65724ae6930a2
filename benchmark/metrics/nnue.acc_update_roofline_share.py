import json
import re
from pathlib import Path

from benchmark import cells


def read(ctx):
    """The least time the chip could take for the slice's accumulator
    updates over the time the ops that make them took there."""
    meta = json.loads(Path(__file__).with_suffix(".json").read_text())
    occ, sl, tr = ctx["occupancy"], ctx.get("slice") or {}, ctx.get("trace") or {}
    cfg = ctx.get("config") or {}
    if not (meta["ops"] and occ.get("acc_updates") and occ.get("movegen_nodes")
            and occ.get("steps") and sl.get("steps")):
        return None  # no ops named, no counters, or no whole interval traced
    kept = tr.get("device_ops") or []
    found = [[s for name, s in kept if re.search(pattern, name)]
             for pattern in meta["ops"]]
    if not all(found):
        return None  # not among the ten that device_ops keeps (PERF.md 7.6)
    busy_s = sum(map(sum, found))
    evaluator = cells.load_evaluator(
        cfg["engine"]["evaluator"], Path(__file__).resolve().parents[1])
    expansions = sl["steps"] * occ["movegen_nodes"] / occ["steps"]
    least_s = (expansions * evaluator.acc_update_bytes(cfg["net_shapes"])
               / ctx["peak"]["bytes_per_s"])
    return 100.0 * least_s / busy_s if busy_s > 0 else None
