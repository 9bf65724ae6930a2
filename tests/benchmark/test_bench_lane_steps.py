"""Where the lockstep's lane-steps go: the five metrics that read the
program's lane-step, empty-dispatch and queue-wait counters
(`engine/tpu.py` LANE_STEP_COUNTERS, `position_queue_ms`,
`position_engine_ms`) — a value where the program keeps them, nothing
(never 0) where it does not: the parent commit, the stand-in engine."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells  # noqa: E402
import fake_engine  # noqa: E402

BENCH = json.load(open(ROOT / "BENCHMARK.json"))
NAMES = ["segment.searching_lane_share", "segment.parked_lane_share",
         "segment.primary_tail_share", "scheduler.empty_dispatch_share",
         "scheduler.queue_wait_share"]
LAYERS = {"segment": "Compiled segment",
          "scheduler": "Engine / LaneScheduler boundary"}

# a window of 200 segments of 64 lanes, 80,000 steps: 12 % of the
# lane-steps a primary's own search, 30 % a helper's, 1 % stray; 20,000
# steps ran after the segment's last primary had parked; 25 dispatches
# found every lane parked; positions waited 0.1 s of 0.8 s for a lane
OCC = {
    "segments": 200, "steps": 80_000, "lane_steps": 5_120_000,
    "lane_steps_assigned": 3_072_000,
    "lane_steps_primary_run": 614_400, "lane_steps_helper_run": 1_536_000,
    "lane_steps_stray_run": 51_200, "lane_steps_free_run": 0,
    "steps_after_last_primary": 20_000, "segments_empty": 25,
    "position_queue_ms": 6_000.0, "position_engine_ms": 48_000.0,
    "positions_finalized": 60,
}
WANT = {
    "segment.searching_lane_share": 43.0,
    "segment.parked_lane_share": 18.0,
    "segment.primary_tail_share": 25.0,
    "scheduler.empty_dispatch_share": 100.0 * 25 / 225,
    "scheduler.queue_wait_share": 12.5,
}
# the parent's occupancy_totals, as the harness takes its delta
PARENT = {
    "segments": 190, "steps": 80_000, "lane_steps": 5_000_000,
    "live_lane_steps": 745_000, "helper_lane_steps": 1_900_000,
    "idle_lane_steps": 2_355_000, "positions_done": 60,
    "host_ms": 3_000.0, "device_ms": 45_000.0,
}


def read(name, occ):
    return cells.load_reader(name)({"occupancy": occ, "window_s": 50.0})


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_counters(name):
    assert read(name, OCC) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_nothing_without_the_counters(name):
    assert read(name, PARENT) is None
    assert read(name, {}) is None
    fake = dict(fake_engine.FakeAdapter(*fake_engine.cell_weights(ROOT)).counters())
    assert read(name, fake) is None


@pytest.mark.parametrize("name, zero", [
    ("segment.searching_lane_share", {"lane_steps": 0}),
    ("segment.parked_lane_share", {"lane_steps": 0}),
    ("segment.primary_tail_share", {"steps": 0}),
    ("scheduler.empty_dispatch_share", {"segments": 0, "segments_empty": 0}),
    ("scheduler.queue_wait_share", {"position_engine_ms": 0.0,
                                    "position_queue_ms": 0.0}),
])
def test_reader_reads_nothing_over_a_zero(name, zero):
    assert read(name, dict(OCC, **zero)) is None


def test_shares_of_a_window_with_nothing_parked_or_empty():
    """0 is a reading where the counters are there: every assigned lane
    ran every step, no dispatch was empty, no position waited."""
    occ = dict(OCC, lane_steps_assigned=2_150_400,
               lane_steps_primary_run=614_400, lane_steps_helper_run=1_536_000,
               segments_empty=0, position_queue_ms=0.0)
    assert read("segment.parked_lane_share", occ) == 0.0
    assert read("scheduler.empty_dispatch_share", occ) == 0.0
    assert read("scheduler.queue_wait_share", occ) == 0.0


def test_searching_and_parked_shares_hold_a_lane_step_once():
    """A lane-step is searched or parked, never both: the two shares add
    to the assigned lane-steps and what stray and free lanes ran."""
    total = read("segment.searching_lane_share", OCC) + read(
        "segment.parked_lane_share", OCC)
    assert total == pytest.approx(100.0 * (
        OCC["lane_steps_assigned"] + OCC["lane_steps_stray_run"]
        + OCC["lane_steps_free_run"]) / OCC["lane_steps"])
    assert total <= 100.0


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_declared_for_standard_trickle_only(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    meta = json.load(open(ROOT / "benchmark/metrics" / f"{name}.json"))
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert entry[key] == meta[key], key
    assert entry["unit"] == "%" and entry["source"] == "program_counter"
    assert entry["moves"] == "positions_per_s"
    assert entry["layer"] == LAYERS[name.split(".")[0]]
    assert entry["workloads"] == ["standard.trickle"]
    for cell in BENCH["workloads"]:
        names = [m["name"] for m in cells.load_cell(ROOT, cell["name"])["per_layer"]]
        assert (name in names) == (cell["name"] == "standard.trickle")


def test_the_five_close_the_list():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-5:] == NAMES


SHARED = ["scheduler.live_lane_share", "scheduler.boundary_host_share",
          "scheduler.session_share", "scheduler.position_p95_s", "segment.step_us",
          "segment.nodes_per_s", "step.mfu_roofline_share", "device.idle_share",
          "device.session_idle_share", "device.hbm_peak_share",
          "scheduler.refill_host_share", "scheduler.lane_loop_host_share",
          "scheduler.gap_submit_share", "scheduler.gap_starved_share",
          "scheduler.submit_ms_per_position", "movegen.list_fill_share"]


@pytest.mark.parametrize("cell, own", [
    ("crazyhouse.trickle", ["movegen.drop_share"]),
    ("halfka3072.trickle", ["nnue.refresh_share", "nnue.rows_per_node",
                            "nnue.acc_update_roofline_share"]),
    ("halfka_dual.trickle", ["nnue.small_eval_share", "nnue.reeval_share",
                             "nnue.dual_acc_update_roofline_share"]),
])
def test_the_other_cells_declarations_are_unchanged(cell, own):
    names = [m["name"] for m in cells.load_cell(ROOT, cell)["per_layer"]]
    assert names == SHARED + own


def test_program_keeps_the_counters():
    from fishnet_tpu.engine.tpu import LANE_STEP_COUNTERS, TpuEngine

    totals = TpuEngine(refill=True, max_depth=2, tt_size_log2=0,
                       helper_lanes=1).occupancy_totals
    for name in LANE_STEP_COUNTERS + ("position_queue_ms", "position_engine_ms",
                                      "positions_finalized"):
        assert totals[name] == 0, name
    occ = {k: v for k, v in totals.items() if isinstance(v, (int, float))}
    for name in NAMES:
        assert read(name, occ) is None  # nothing ran: every denominator is 0
