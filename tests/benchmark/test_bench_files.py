"""Cells, configurations, traffic mixes and per-layer metrics are found by
name: each can be added by new files and new entries alone."""
import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, games, loadgen, rules, work_count  # noqa: E402
import fake_engine  # noqa: E402

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
        {"name": "burst", "loop": "closed", "workers": 5, "games": 3, "pool_seed": 5,
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


# a second evaluator as a later PR would write it: one new file, weights
# from a seed (no file under weights/), shape keys of its own
MATERIAL = '''"""Material and piece-square sums from a seeded table (a toy)."""
import numpy as np

VALUE = (100, 320, 330, 500, 900, 0)


def load_weights(engine_cfg, root):
    rng = np.random.Generator(np.random.PCG64(engine_cfg["weights"]["seed"]))
    return {"psq": rng.integers(-40, 41, size=(13, 64))}


def evaluate(w, pos):
    total = 0
    for sq, code in enumerate(pos.board):
        if code:
            worth = VALUE[(code - 1) % 6] + int(w["psq"][code, sq])
            total += worth if code <= 6 else -worth
    for i, n in enumerate(pos.pockets):  # a feature set may read the variant's state
        total += (n if i < 5 else -n) * VALUE[i % 5]
    return total if pos.stm == 0 else -total


def program_params(weights):
    raise NotImplementedError("a toy: no program runs it")


def net_work(shapes):
    return {"flops": 2.0 * shapes["squares"], "bytes": 8.0 * shapes["squares"]}
'''


def test_new_evaluator_by_new_files_only(tmp_path):
    """A later PR's evaluator, configuration and cell: the stand-in engine
    answers with the new eval through `loadgen.run_cell`, `correct` is true,
    a planted +7 cp fails `d1_gap_cp`, and nothing that exists is edited."""
    root, before = fake_engine.tree_with_new_evaluator(
        tmp_path, ROOT, "material", MATERIAL, engine={"weights": {"seed": 34}},
        net_shapes={"squares": 64}, variant="crazyhouse", max_moves=538)
    bdir = root / "benchmark"
    assert not (bdir / "weights").exists()  # the weights are made, not read
    cell = fake_engine.toy_cell(root, "material.trickle", bench_dir=bdir)
    evaluator, cfg = cell["evaluator"], cell["config"]
    assert "net" not in cfg["engine"] and cfg["engine"]["evaluator"] == "material"
    weights = evaluator.load_weights(cfg["engine"], root)
    again = evaluator.load_weights(cfg["engine"], root)
    assert (weights["psq"] == again["psq"]).all() and weights["psq"].any()
    # the start position is symmetric in material; the table is not
    assert evaluator.evaluate(weights, rules.start("crazyhouse")) != 0

    def run(fault):
        return loadgen.run_cell(
            cell, seed=2147483659, seconds=1.2, trace=False,
            make_engine=lambda: fake_engine.FakeAdapter(weights, evaluator, fault),
            device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            t_start=time.monotonic(), rehearsal=None, control=None,
            say=lambda s: None, trace_dir=str(tmp_path / "trace"))

    sound = run(None)
    assert sound["correct"] is True, sound["checks"]
    assert sound["checks"]["d1_gap_cp"] == {"value": 0, "limit": 2}
    assert sound["window"]["answers"] > 0 and sound["failed"] == 0
    broken = run("score")
    assert broken["correct"] is False
    assert broken["checks"]["d1_gap_cp"] == {"value": 7, "limit": 2}
    # the net's work is counted from the evaluator's own shape keys
    node = work_count.per_node(evaluator.net_work(cfg["net_shapes"]), cfg["max_moves"])
    assert node == {"flops": 128.0,
                    "bytes": 512.0 + 2 * 340 + 64 + 2 * 538 * 4 + 32}
    # an old cell still finds its own evaluator in the same tree
    old = cells.load_cell(root, "standard.trickle", bench_dir=bdir)
    assert old["evaluator"].__name__ == "benchmark_evaluator_board768"
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


@pytest.mark.parametrize("engine,source,why", [
    ({"evaluator": "nowhere"}, None, "no evaluator file"),
    ({"evaluator": "half"}, "def evaluate(w, pos):\n    return 0\n",
     "lacks load_weights, program_params, net_work"),
    ({}, None, "names no evaluator"),
])
def test_a_cell_whose_evaluator_is_not_there_is_refused(tmp_path, engine, source, why):
    root, _before = fake_engine.tree_with_new_evaluator(
        tmp_path, ROOT, "half", source or "", engine={}, net_shapes={})
    bdir = root / "benchmark"
    if source is None:
        (bdir / "evaluators/half.py").unlink()
    cfg = json.load(open(bdir / "configs/half.json"))
    cfg["engine"] = engine
    (bdir / "configs/half.json").write_text(json.dumps(cfg))
    with pytest.raises(cells.CellError, match=why):
        cells.load_cell(root, "half.trickle", bench_dir=bdir)


def test_a_traffic_file_without_a_pool_is_refused(tmp_path):
    bdir = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    traffic = json.load(open(bdir / "traffic/trickle.json"))
    del traffic["pool_seed"]
    (bdir / "traffic/trickle.json").write_text(json.dumps(traffic))
    with pytest.raises(cells.CellError, match="states no pool_seed"):
        cells.load_cell(ROOT, "standard.trickle", bench_dir=bdir)


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_names_an_evaluator_that_is_there(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    name = json.load(open(ROOT / entry["file"]))["engine"]["evaluator"]
    assert (ROOT / "benchmark/evaluators" / f"{name}.py").exists()
    module = cells.load_evaluator(name)
    for fn in cells.EVALUATOR_FUNCTIONS:
        assert callable(getattr(module, fn)), fn


# recorded on the parent commit (790550d), before the evaluator became a
# file found by name: seed 1's games as `make_games` makes them, the reference
# eval of every position in them, and the node's work
PARENTS = {
    "standard.trickle": (
        "b0d4a75e2d16502a02a41d14a2dbb4e61340e119a39a88497f2ce591cd1fc293",
        "e74651d5c33279035ce35fa2b753b585eaa4d810cd770ba712747c7f84a51f28",
        {"flops": 5696.0, "bytes": 16156.0}),
    "crazyhouse.trickle": (
        "fb7386ae9879eb524b69eaef1745ab9971d964255fd83ff13814de26ab397e84",
        "2e31393c841ae83c54a2c47c4ea40a6c976d0326ea67fb79208dc6ee5dddd7d5",
        {"flops": 5696.0, "bytes": 18716.0}),
}


@pytest.mark.parametrize("workload", sorted(PARENTS))
def test_games_evals_and_per_node_are_the_parents(workload):
    games_sha, evals_sha, per_node = PARENTS[workload]
    cell = cells.load_cell(ROOT, workload)
    cfg, evaluator = cell["config"], cell["evaluator"]
    weights = evaluator.load_weights(cfg["engine"], ROOT)
    made = games.make_games(weights, evaluator, cfg["variant"], cell["traffic"]["games"],
                            cfg["assumed"]["plies_per_game"], 1)
    assert hashlib.sha256(json.dumps(made).encode()).hexdigest() == games_sha
    evals = []
    for moves in made:
        p = rules.start(cfg["variant"])
        evals.append(evaluator.evaluate(weights, p))
        for text in moves:
            p = rules.make(p, rules.parse_uci(p, text))
            evals.append(evaluator.evaluate(weights, p))
    assert len(evals) == 8 * 41
    assert hashlib.sha256(json.dumps(evals).encode()).hexdigest() == evals_sha
    node = work_count.per_node(evaluator.net_work(cfg["net_shapes"]), cfg["max_moves"])
    assert node == per_node


# the pool every --seed is dealt (traffic/trickle.json's pool_seed): the
# games PR 34's spreads and every later level were measured on
POOLS = {
    "standard.trickle": "d00a22e7ef5a34f909ff73a4e72bdf5ce7c2122678f16c1ebad43d4ae88b2a6c",
    "crazyhouse.trickle": "0d896ffaa66fb614096b2e312b39ce930a6686e3605d107e7d080eeab74a949a",
}


@pytest.mark.parametrize("workload", sorted(POOLS))
def test_the_pools_games_are_the_ones_measured(workload):
    cell = cells.load_cell(ROOT, workload)
    cfg, evaluator = cell["config"], cell["evaluator"]
    weights = evaluator.load_weights(cfg["engine"], ROOT)
    made = games.make_games(weights, evaluator, cfg["variant"], cell["traffic"]["games"],
                            cfg["assumed"]["plies_per_game"], cell["traffic"]["pool_seed"])
    assert len(made) == 8 and all(len(g) == 40 for g in made)
    assert hashlib.sha256(json.dumps(made).encode()).hexdigest() == POOLS[workload]


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
    net = cells.load_evaluator(cfg["engine"]["evaluator"]).net_work(cfg["net_shapes"])
    node = work_count.per_node(net, cfg["max_moves"])
    # accumulator: 4 changes x 64 x 2 perspectives = 512 adds;
    # forward: 2 x (128x16 + 16x32 + 32) = 5184
    assert node["flops"] == 512 + 5184
    weights = 4 * (2 * 4 * 64 + 128 * 16 + 16 + 16 * 32 + 32 + 32 + 1)
    assert node["bytes"] == weights + 2 * 512 + 2 * 340 + 64 + 2 * 218 * 4 + 32
    # crazyhouse: 5 droppable pieces x 64 squares more in the move list
    wide = work_count.per_node(net, cfg["max_moves"] + 5 * 64)
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
