"""`scheduler.replay_shared_share`: how much of the submitted positions'
prefix moves `LaneScheduler._submit` did not validate again, read from
the program's `submit_prefix_moves` and `submit_moves_parsed` counters —
a value where the program keeps them, nothing (never 0) where it does
not: the parent commit, the stand-in engine."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells  # noqa: E402
import fake_engine  # noqa: E402

NAME = "scheduler.replay_shared_share"
BENCH = json.load(open(ROOT / "BENCHMARK.json"))


@pytest.mark.parametrize("parsed, prefix, share", [
    (210, 1_050, 80.0),  # chunks of six consecutive plies around ply 20
    (1_050, 1_050, 0.0),  # nothing shared: every position its own line
    (0, 40, 100.0),  # every position walked before (duplicates)
])
def test_reader_reads_the_counters(parsed, prefix, share):
    occ = {"submit_moves_parsed": parsed, "submit_prefix_moves": prefix,
           "positions_submitted": 60}
    value = cells.load_reader(NAME)({"occupancy": occ, "window_s": 50.0})
    assert value == pytest.approx(share)


def test_reader_reads_nothing_without_the_counters():
    read = cells.load_reader(NAME)
    parent = {"segments": 90, "steps": 36_000, "host_ms": 13_000.0,
              "submit_ms": 16_500.0, "positions_submitted": 110,
              "submit_replay_ms": 9_000.0}
    assert read({"occupancy": parent, "window_s": 50.0}) is None
    assert read({"occupancy": {}, "window_s": 50.0}) is None
    fake = dict(fake_engine.FakeAdapter(*fake_engine.cell_weights(ROOT)).counters())
    assert read({"occupancy": fake, "window_s": 50.0}) is None


def test_reader_reads_nothing_where_no_position_had_moves():
    occ = {"submit_moves_parsed": 0, "submit_prefix_moves": 0}
    assert cells.load_reader(NAME)({"occupancy": occ, "window_s": 50.0}) is None


def test_metric_is_declared_for_the_cell_it_reads():
    """Declared for `standard.trickle`: the other cells' declarations
    end in the metrics of their own mechanism, and stay so."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    meta = json.load(open(ROOT / "benchmark/metrics" / f"{NAME}.json"))
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == meta[key], key
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Engine / LaneScheduler boundary"
    assert entry["workloads"] == ["standard.trickle"]
    for cell in BENCH["workloads"]:
        names = [m["name"] for m in cells.load_cell(ROOT, cell["name"])["per_layer"]]
        assert (NAME in names) == (cell["name"] in entry["workloads"])


def test_program_keeps_the_counters():
    from fishnet_tpu.engine.tpu import TpuEngine

    totals = TpuEngine(refill=True, max_depth=2, tt_size_log2=0,
                       helper_lanes=1).occupancy_totals
    assert totals["submit_prefix_moves"] == 0
    assert totals["submit_moves_parsed"] == 0
