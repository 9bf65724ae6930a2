"""Cells, configurations, traffic mixes and per-layer metrics are found by
name: each can be added by new files and new entries alone."""
import json
import shutil
from pathlib import Path

import pytest

from benchmark import cells, games, work_count

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.load(open(ROOT / "BENCHMARK.json"))


def test_benchmark_json_names_files_that_exist():
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).exists() and c["file"].startswith("benchmark/")
        assert len(c["source"]) <= 200
        assert json.load(open(ROOT / c["file"]))["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        cell = cells.load_cell(ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert callable(cells.load_reader(m["name"]))


def test_every_cell_has_the_same_node_budget():
    budgets = {json.dumps(cells.load_cell(ROOT, w["name"])["config"]["work"]["nodes"],
                          sort_keys=True) for w in BENCH["workloads"]}
    assert len(budgets) == 1


def test_new_cell_config_traffic_and_metric_by_new_files_only(tmp_path):
    """A later PR's addition: nothing that exists is edited."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    bdir = root / "benchmark"
    cfg = json.load(open(bdir / "configs/standard.json"))
    cfg.update(name="chess960", variant="chess960")
    (bdir / "configs/chess960.json").write_text(json.dumps(cfg))
    (bdir / "traffic/burst.json").write_text(json.dumps(
        {"name": "burst", "loop": "closed", "workers": 5, "games": 3,
         "preroll_min_s": 1, "preroll_quiet_s": 1, "preroll_max_s": 5,
         "warm_sessions": []}))
    (bdir / "metrics/scheduler.refills_per_segment.json").write_text(json.dumps(
        {"name": "scheduler.refills_per_segment", "unit": "1", "better": "lower",
         "source": "program_counter", "layer": "Engine / LaneScheduler boundary",
         "moves": "positions_per_s", "reader": "scheduler.refills_per_segment.py"}))
    (bdir / "metrics/scheduler.refills_per_segment.py").write_text(
        "def read(ctx):\n"
        "    occ = ctx['occupancy']\n"
        "    return occ['refills'] / occ['segments'] if occ.get('segments') else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "chess960", "source": "x", "reduced": [],
                             "file": "benchmark/configs/chess960.json", "why": "y"})
    bench["workloads"].append({"name": "chess960.burst", "config": "chess960",
                               "traffic": "burst", "chips": 1, "why": "z"})
    bench["per_layer"].append({"name": "scheduler.refills_per_segment", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "Engine / LaneScheduler boundary",
                               "moves": "positions_per_s",
                               "workloads": ["chess960.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell(root, "chess960.burst", bench_dir=bdir)
    assert cell["config"]["variant"] == "chess960"
    assert cell["traffic"]["workers"] == 5
    names = [m["name"] for m in cell["per_layer"]]
    assert "scheduler.refills_per_segment" in names
    got = cells.read_per_layer(cell, {"occupancy": {"refills": 30, "segments": 10}})
    assert got["scheduler.refills_per_segment"] == {"value": 3.0, "unit": "1"}
    # the new metric lists its cells, so an old cell is not asked for it
    old = cells.load_cell(root, "standard.trickle", bench_dir=bdir)
    assert "scheduler.refills_per_segment" not in [m["name"] for m in old["per_layer"]]
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def test_a_reader_that_finds_nothing_returns_nothing():
    cell = cells.load_cell(ROOT, "standard.trickle")
    got = cells.read_per_layer(cell, {"occupancy": {}, "window_s": 10.0})
    assert got == {}


def test_readers_on_known_counters():
    cell = cells.load_cell(ROOT, "standard.trickle")
    peak = cell["peaks"]["devices"]["TPU v5 lite"]
    ctx = {
        "occupancy": {"lane_steps": 1000, "live_lane_steps": 170,
                      "host_ms": 300.0, "device_ms": 700.0},
        "window_s": 10.0, "nodes": 50_000, "peak_bytes": peak["hbm_bytes"] // 4,
        "peak": peak, "slice": {"steps": 2000, "device_s": 0.7},
        "busy_s": 0.5,
        "per_node": {"flops": 6000.0, "bytes": 8190.0},
    }
    got = {k: v["value"] for k, v in cells.read_per_layer(cell, ctx).items()}
    assert got["scheduler.live_lane_share"] == pytest.approx(17.0)
    assert got["scheduler.boundary_host_share"] == pytest.approx(30.0)
    assert got["scheduler.session_share"] == pytest.approx(10.0)  # 1 s of 10
    assert got["segment.step_us"] == pytest.approx(350.0)
    assert got["segment.nodes_per_s"] == pytest.approx(5000.0)
    # a session ran 1 s of the 10 s; the device was busy 0.5 s of the window
    assert got["device.session_idle_share"] == pytest.approx(50.0)
    assert got["device.idle_share"] == pytest.approx(95.0)
    assert got["device.hbm_peak_share"] == pytest.approx(25.0)
    # 50,000 nodes x 8,190 B = 0.4095 GB at 819 GB/s = 0.5 ms of 0.5 s busy
    assert got["step.mfu_roofline_share"] == pytest.approx(0.1)
    assert ctx["notes"]["step.mfu_roofline_share.bound"] == "bytes"


def test_per_node_work_from_shapes():
    cfg = json.load(open(ROOT / "benchmark/configs/standard.json"))
    node = work_count.per_node(cfg["net_shapes"], cfg["max_moves"])
    # accumulator: 4 changes x 64 x 2 perspectives = 512 adds;
    # forward: 2 x (128x16 + 16x32 + 32) = 5184
    assert node["flops"] == 512 + 5184
    weights = 4 * (2 * 4 * 64 + 128 * 16 + 16 + 16 * 32 + 32 + 32 + 1)
    assert node["bytes"] == weights + 2 * 512 + 2 * 340 + 64 + 2 * 218 * 4 + 32
    # crazyhouse: 5 droppable pieces x 64 squares more in the move list
    wide = work_count.per_node(cfg["net_shapes"], cfg["max_moves"] + 5 * 64)
    assert wide["bytes"] - node["bytes"] == 2 * 320 * 4
    share, bound = work_count.roofline_share(
        1e6, 1.0, {"flops": 197e6, "bytes": 1.0},
        {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert share == pytest.approx(100.0) and bound == "flops"
    assert work_count.roofline_share(0, 1.0, node, {"flops_per_s": 1, "bytes_per_s": 1}) == (None, None)


def test_tiling_is_the_planners():
    chunks = games.tile(40)
    assert [len(c) for c in chunks] == [5, 6, 6, 6, 6, 6, 6, 6, 2]
    owed = [idx for c in chunks for idx, _n in c if idx is not None]
    assert sorted(owed) == list(range(41)) and owed[0] == 40
    overlaps = [n for c in chunks for idx, n in c if idx is None]
    assert len(overlaps) == 8
    for c in chunks[1:]:
        assert c[0][0] is None and c[0][1] == c[1][1] + 1
    assert max(len(c) for c in chunks) <= games.MAX_CHUNK_POSITIONS
