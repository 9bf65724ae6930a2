"""`halfka3072.trickle`: the configuration at its published widths (loaded,
its work and bytes counted, never run here: a 277 MB table a test is too
dear), and its evaluator file, `benchmark/evaluators/halfka.py`, at L1 32
through `measure.program_engine_factory` and the real `TpuEngine` on
XLA:CPU at the rehearsal's sizes, named by a configuration in a temporary
tree; the three `nnue.*` readers."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, reference, rules, work_count  # noqa: E402
from test_bench_crazyhouse import rehearse  # noqa: E402

CELL = "halfka3072.trickle"
BENCH = json.load(open(ROOT / "BENCHMARK.json"))
NNUE_METRICS = ["nnue.refresh_share", "nnue.rows_per_node",
                "nnue.acc_update_roofline_share"]


def test_cell_is_declared_with_its_three_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "trickle"
    assert cell["config"]["variant"] == "standard" and cell["config"]["max_moves"] == 218
    assert [m["name"] for m in cell["per_layer"]][-3:] == NNUE_METRICS
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NNUE_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        spec = cells.load_json(ROOT / "benchmark/metrics" / f"{name}.json")
        assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")} \
            == {k: v for k, v in by_name[name].items() if k != "workloads"}
    for old in ("standard.trickle", "crazyhouse.trickle"):
        names = [m["name"] for m in cells.load_cell(ROOT, old)["per_layer"]]
        assert not set(names) & set(NNUE_METRICS)
    # the same work as standard's, under the same traffic
    std = cells.load_cell(ROOT, "standard.trickle")
    assert cell["config"]["work"] == std["config"]["work"]
    assert cell["traffic"] == std["traffic"]


def test_no_width_is_cut_and_the_work_is_counted_from_the_shapes():
    cfg = cells.load_cell(ROOT, CELL)["config"]
    shapes = cfg["net_shapes"]
    assert shapes == {"features": 22528, "l1": 3072, "psqt": 8, "stacks": 8,
                      "fc0": 16, "fc1_in": 30, "fc1": 32, "out": 1}
    assert cfg["engine"]["weights"]["l1"] == shapes["l1"]
    assert cfg["engine"]["dtype"] == "float32" and cfg["engine"]["evaluator"] == "halfka"
    assert cfg["published"]["nets"]["big"]["l1"] == 3072
    assert cfg["reduced"] == ["nodes.sf16", "nodes.classical", "nets.small"]
    assert not [k for k in cfg["reduced"] if "l1" in k or k.endswith(("_dim", "_rank"))]
    evaluator = cells.load_evaluator("halfka")
    net = evaluator.net_work(shapes)
    assert net == {"flops": 128015.0, "bytes": 348612.0}
    node = work_count.per_node(net, cfg["max_moves"])
    assert node["bytes"] == 348612 + (2 * work_count.BOARD_ROW_BYTES + 64) + 2 * 218 * 4 + 32
    assert evaluator.acc_update_bytes(shapes) == 8 * 3080 * 4 + 4 * 3080 * 4
    # the parameters and their bytes as float32, against what the file states
    n = (22528 * 3072 + 3072 + 22528 * 8
         + 8 * (16 * 3072 + 16 + 32 * 30 + 32 + 32 + 1))
    assert n == 69_790_856 and cfg["assumed"]["net_bytes"] == 4 * n
    # 64 lanes x (33 plies + the spare pair) x 2 rows of 3,080 float32
    assert cfg["assumed"]["acc_stack_bytes_64_lanes"] == 64 * 2 * 34 * 3080 * 4


def test_weights_are_drawn_once_a_process_and_by_the_seed():
    evaluator = cells.load_evaluator("halfka")
    spec = {"weights": {"seed": 11, "l1": 32}}
    first = evaluator.load_weights(spec, ROOT)
    assert evaluator.load_weights(spec, ROOT) is first  # kept, not drawn again
    other = evaluator.load_weights({"weights": {"seed": 12, "l1": 32}}, ROOT)
    assert not np.array_equal(other["ft_w"], first["ft_w"])
    again = evaluator.load_weights(spec, ROOT)
    assert again is not first and all(
        np.array_equal(again[k], first[k]) for k in first)
    assert {k: v.shape for k, v in first.items()} == {
        "ft_w": (22528, 32), "ft_b": (32,), "psqt_w": (22528, 8),
        "fc0_w": (8, 16, 32), "fc0_b": (8, 16), "fc1_w": (8, 32, 30),
        "fc1_b": (8, 32), "fc2_w": (8, 1, 32), "fc2_b": (8, 1)}
    assert all(v.dtype == np.float32 for v in first.values())
    # the scales: accumulators straddle the clip, a balanced position
    # scores within a few hundred centipawns
    p = rules.start("standard")
    assert abs(evaluator.evaluate(first, p)) < 500
    idx = [(0 * 11 + k) * 64 + sq for k, sq in ((0, 8), (3, 0), (10, 4), (9, 59))]
    acc = first["ft_b"] + first["ft_w"][idx].sum(axis=0)
    assert 0.0 < acc.mean() < 1.0


def test_the_fixture_and_the_real_file_hold_the_same_eval():
    """ROADMAP's debt line: `tests/benchmark/data/halfka_toy.py` is the
    same mathematics at a toy width; on the same weights the two agree."""
    real = cells.load_evaluator("halfka")
    toy = cells._load_module("halfka_toy", ROOT / "tests/benchmark/data/halfka_toy.py")
    w = real.load_weights({"weights": {"seed": 5, "l1": 32}}, ROOT)
    p = rules.start("standard")
    for text in ("e2e4", "e7e5", "e1e2", "e8e7", "e2d3"):
        p = rules.make(p, rules.parse_uci(p, text))
        assert real.evaluate(w, p) == toy.evaluate(w, p)
    assert real.net_work({"l1": 32, "psqt": 8, "fc0": 16, "fc1": 32}) \
        == toy.net_work({"l1": 32})


@pytest.mark.parametrize("occupancy,want", [
    # 1,000 pushes: 1,900 perspectives updated, 100 rebuilt; 40,000 rows
    # gathered for 500 expansions
    ({"acc_updates": 1900, "acc_refreshes": 100, "acc_rows": 40_000,
      "movegen_nodes": 500}, {"nnue.refresh_share": 5.0, "nnue.rows_per_node": 80.0}),
    # a program that refreshes every step and updates nothing
    ({"acc_updates": 0, "acc_refreshes": 640, "acc_rows": 40_960,
      "movegen_nodes": 64}, {"nnue.refresh_share": 100.0, "nnue.rows_per_node": 640.0}),
    # board768: updates by a contraction, gathers no row
    ({"acc_updates": 2000, "acc_refreshes": 0, "acc_rows": 0, "movegen_nodes": 900},
     {"nnue.refresh_share": 0.0}),
    # the parent's program keeps no such counters: left out, not 0
    ({"segments": 190, "steps": 80_000, "movegen_nodes": 900}, {}),
    # counters that did not move in the window
    ({"acc_updates": 0, "acc_refreshes": 0, "acc_rows": 0, "movegen_nodes": 0}, {}),
])
def test_counter_readers(occupancy, want):
    ctx = {"occupancy": occupancy}
    got = {name: cells.load_reader(name)(ctx) for name in NNUE_METRICS[:2]}
    assert {k: v for k, v in got.items() if v is not None} == pytest.approx(want)


def test_roofline_reader_reads_the_ops_its_file_names(tmp_path):
    """The least time for the slice's expansions over the seconds of the
    ops its `.json` names by pattern, at either session width; nothing
    where a pattern finds no op among the ten kept, where the program has
    no counters, or (in a copy of `metrics/`) where the file names none."""
    cfg = cells.load_cell(ROOT, CELL)["config"]
    bdir = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    name = NNUE_METRICS[2]
    spec = cells.load_json(bdir / "metrics" / f"{name}.json")
    assert len(spec["ops"]) == 2
    read = cells.load_reader(name, bdir)
    occ = {"acc_updates": 10, "movegen_nodes": 20_000, "steps": 1000}
    ctx = {"occupancy": occ, "slice": {"steps": 100}, "config": cfg,
           "peak": {"bytes_per_s": 819e9},
           "trace": {"device_ops": [["sort s32[64,2550]", 0.005],
                                    ["fusion f32[128,3072]", 0.001],
                                    ["fusion f32[64,68,3080]", 0.003]]}}
    # 100 steps x 20 expansions a step x 147,840 B at 819 GB/s over 4 ms
    assert read(ctx) == pytest.approx(100.0 * (2000 * 147_840 / 819e9) / 0.004)
    narrow = {"device_ops": [["fusion f32[16,68,3080]", 0.002],
                             ["fusion f32[32,3072]", 0.001],
                             ["fusion f32[64,3072]", 0.001]]}  # a refresh pass's
    assert read(dict(ctx, trace=narrow)) == pytest.approx(
        100.0 * (2000 * 147_840 / 819e9) / 0.004)
    assert read(dict(ctx, trace={"device_ops": ctx["trace"]["device_ops"][:2]})) is None
    assert read(dict(ctx, occupancy={"movegen_nodes": 20_000, "steps": 1000})) is None
    assert read(dict(ctx, slice=None)) is None
    assert read(dict(ctx, trace=None)) is None
    spec["ops"] = []
    (bdir / "metrics" / f"{name}.json").write_text(json.dumps(spec))
    assert cells.load_reader(name, bdir)(ctx) is None


def halfka_tree(tmp_path, l1):
    """A copy of the tree in which a configuration `halfka<l1>` names the
    real evaluator file at that width, with its cell; nothing else differs
    from `halfka3072`."""
    root = tmp_path / "repo"
    bdir = root / "benchmark"
    shutil.copytree(ROOT / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    name = f"halfka{l1}"
    cfg = cells.load_json(bdir / "configs/halfka3072.json")
    cfg["name"] = name
    cfg["engine"]["weights"] = {"seed": 20261005, "l1": l1}
    cfg["net_shapes"]["l1"] = l1
    (bdir / f"configs/{name}.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": name, "source": "x", "reduced": cfg["reduced"],
                             "file": f"benchmark/configs/{name}.json", "why": "y"})
    bench["workloads"].append({"name": f"{name}.trickle", "config": name,
                               "traffic": "trickle", "chips": 1, "why": "z"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, name


def test_real_evaluator_through_the_real_engine_at_rehearsal_size(tmp_path, monkeypatch):
    """(iv) of ISSUE 35: `correct` true with the exact checks 0 on the
    incremental path; false against another seed's weights; and false by
    `d1_gap_cp` against the reference's eval with the weights rounded to
    bfloat16, the nearest precision below the one the configuration
    states."""
    import ml_dtypes

    root, before, name = halfka_tree(tmp_path, 32)
    bdir = root / "benchmark"
    cell = cells.load_cell(root, f"{name}.trickle", bench_dir=bdir)
    evaluator, engine_cfg = cell["evaluator"], cell["config"]["engine"]
    assert evaluator.__file__ == str(bdir / "evaluators/halfka.py")
    assert (bdir / "evaluators/halfka.py").read_bytes() \
        == (ROOT / "benchmark/evaluators/halfka.py").read_bytes()
    weights = evaluator.load_weights(engine_cfg, root)
    params = evaluator.program_params(weights)
    assert type(params).__name__ == "StockfishNet" and params.l1 == 32
    result, lines, sampled = rehearse(cell, tmp_path, monkeypatch, seed=2035000117)
    assert result["correct"] is True, json.dumps([result["checks"], lines[-8:]])
    assert result["failed"] == 0 and result["window"]["answers"] > 0
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["d1_gap_cp"] == 0 and checks["d1_move_gap_cp"] == 0
    assert checks["bad_lines"] == 0 and checks["delivery"] == 0
    assert checks["programs_inside"] == 0
    limits = cell["limits"]["checks"]

    def held_to(other_weights):
        return reference.compare(
            reference.Reference(other_weights, evaluator), list(sampled),
            {"delivery": 0, "programs_inside": 0}, limits)

    wrong, held, _detail = held_to(
        dict(evaluator.load_weights({"weights": {"seed": 1, "l1": 32}}, root)))
    assert wrong is False and held["d1_gap_cp"]["value"] > limits["d1_gap_cp"]
    # the same answers against the net in bfloat16
    rounded = {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
               for k, v in evaluator.load_weights(engine_cfg, root).items()}
    lower, held, _detail = held_to(rounded)
    assert lower is False, held
    assert held["d1_gap_cp"]["value"] > limits["d1_gap_cp"]
    assert held["delivery"]["value"] == 0 and held["bad_lines"]["value"] == 0
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"


def test_a_program_without_the_incremental_path_is_refused_at_once(monkeypatch):
    """The configuration is the net on the incremental path: a program whose
    search would refresh both perspectives every lane-step (PR 34's, which
    has no `nnue.acc_scheme`; or one whose scheme is not this net's) does
    not run it, and `program_params` says so before anything is built."""
    from benchmark.evaluators import halfka
    from fishnet_tpu.models import nnue

    weights = halfka.load_weights({"weights": {"seed": 3, "l1": 32}}, ROOT)
    assert type(halfka.program_params(weights)).__name__ == "StockfishNet"
    monkeypatch.setattr(nnue, "acc_scheme", lambda params, variant="standard": None)
    with pytest.raises(RuntimeError, match="no incremental"):
        halfka.program_params(weights)
    monkeypatch.delattr(nnue, "acc_scheme")
    with pytest.raises(RuntimeError, match="no incremental"):
        halfka.program_params(weights)
