"""`halfka_dual.trickle`: both nets of the deployment at their published
widths (loaded, their work and bytes counted, never run here), and the
evaluator file, `benchmark/evaluators/halfka_dual.py`, at small widths
through `measure.program_engine_factory` and the real `TpuEngine` on
XLA:CPU at the rehearsal's sizes, named by a configuration in a temporary
tree; the three readers of its metrics."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, games, reference, rules, work_count  # noqa: E402
from test_bench_crazyhouse import rehearse  # noqa: E402

CELL = "halfka_dual.trickle"
BENCH = json.load(open(ROOT / "BENCHMARK.json"))
DUAL_METRICS = ["nnue.small_eval_share", "nnue.reeval_share",
                "nnue.dual_acc_update_roofline_share"]


def test_cell_is_declared_with_its_three_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "trickle"
    assert cell["config"]["variant"] == "standard" and cell["config"]["max_moves"] == 218
    assert [m["name"] for m in cell["per_layer"]][-3:] == DUAL_METRICS
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in DUAL_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        spec = cells.load_json(ROOT / "benchmark/metrics" / f"{name}.json")
        assert {k: spec[k] for k in ("name", "unit", "better", "source", "layer", "moves")} \
            == {k: v for k, v in by_name[name].items() if k != "workloads"}
    for old in ("standard.trickle", "crazyhouse.trickle", "halfka3072.trickle"):
        names = [m["name"] for m in cells.load_cell(ROOT, old)["per_layer"]]
        assert not set(names) & set(DUAL_METRICS)
    # the wide net's cell's work under the same traffic, byte for byte
    wide = cells.load_cell(ROOT, "halfka3072.trickle")
    assert cell["config"]["work"] == wide["config"]["work"]
    assert cell["traffic"] == wide["traffic"]


def test_no_width_is_cut_and_the_work_is_counted_from_the_shapes():
    cfg = cells.load_cell(ROOT, CELL)["config"]
    shapes = cfg["net_shapes"]
    assert shapes == {"features": 22528, "l1": 3072, "l1_small": 128, "psqt": 8,
                      "stacks": 8, "fc0": 16, "fc1_in": 30, "fc1": 32, "out": 1}
    weights = cfg["engine"]["weights"]
    assert (weights["big"]["l1"], weights["small"]["l1"]) == (3072, 128)
    assert cfg["published"]["nets"]["big"]["l1"] == 3072
    assert cfg["published"]["nets"]["small"]["l1"] == 128
    # the big net is halfka3072's, the same bytes
    wide = cells.load_cell(ROOT, "halfka3072.trickle")["config"]
    assert weights["big"] == wide["engine"]["weights"]
    assert weights["small"]["seed"] != weights["big"]["seed"]
    assert cfg["reduced"] == ["nodes.sf16", "nodes.classical"]
    assert cfg["engine"]["dtype"] == "float32" and cfg["engine"]["evaluator"] == "halfka_dual"
    evaluator = cells.load_evaluator("halfka_dual")
    halfka = cells.load_evaluator("halfka")
    big = halfka.net_work(wide["net_shapes"])
    small = halfka.net_work(dict(wide["net_shapes"], l1=128))
    net = evaluator.net_work(shapes)
    assert net == {k: big[k] + small[k] for k in ("flops", "bytes")}
    node = work_count.per_node(net, cfg["max_moves"])
    assert node["bytes"] > work_count.per_node(big, cfg["max_moves"])["bytes"]
    # 4 rows a perspective of 3,080 + 136 float32, both pairs read and written
    assert evaluator.acc_update_bytes(shapes) == 8 * 3216 * 4 + 4 * 3216 * 4 == 154_368
    n_small = (22528 * 128 + 128 + 22528 * 8
               + 8 * (16 * 128 + 16 + 32 * 30 + 32 + 32 + 1))
    assert cfg["assumed"]["small_net_bytes"] == 4 * n_small == 12_354_592
    assert cfg["assumed"]["net_bytes"] == wide["assumed"]["net_bytes"]
    # 64 lanes x (33 plies + the spare pair) x 2 rows of 3,216 float32,
    # padded to 3,328 = 26 x 128; the table the same
    assert cfg["assumed"]["acc_stack_bytes_64_lanes"] == 64 * 2 * 34 * 3328 * 4
    assert cfg["assumed"]["joined_table_bytes"] == 22528 * 3328 * 4
    for const in ("962", "236", "208", "781", "825", "1276", "2538"):
        assert const in cfg["assumed"]["rule"]


def test_both_nets_are_drawn_once_a_process_each_by_its_seed():
    evaluator = cells.load_evaluator("halfka_dual")
    halfka = cells.load_evaluator("halfka")
    spec = {"weights": {"big": {"seed": 11, "l1": 32}, "small": {"seed": 12, "l1": 16}}}
    first = evaluator.load_weights(spec, ROOT)
    assert evaluator.load_weights(spec, ROOT) is first  # kept, not drawn again
    one = halfka.load_weights({"weights": {"seed": 11, "l1": 32}}, ROOT)
    assert all(np.array_equal(first["big"][k], one[k]) for k in one)
    assert first["small"]["ft_w"].shape == (22528, 16)
    assert first["small"]["fc0_w"].shape == (8, 16, 16)


def test_the_rule_picks_the_net():
    """Balanced: the big net. A queen and more up: the small net, unless
    its value is under 236 cp, where the big net's stands."""
    evaluator = cells.load_evaluator("halfka_dual")
    halfka = evaluator.halfka
    w = evaluator.load_weights(
        {"weights": {"big": {"seed": 5, "l1": 32}, "small": {"seed": 6, "l1": 16}}}, ROOT)
    start = rules.start("standard")
    assert evaluator.material(start) == 0
    assert evaluator.evaluate(w, start) == halfka.evaluate(w["big"], start)
    board = list(start.board)
    board[59] = board[57] = 0  # black's queen and knight
    up = rules.Pos(board, 0, -1, start.castling, 0)
    assert evaluator.material(up) == 2538 + 781
    small = halfka.evaluate(w["small"], up)
    assert abs(small) >= 236 and evaluator.evaluate(w, up) == small
    # the same position with a small net that is unsure
    timid = dict(w, small=dict(w["small"], psqt_w=np.zeros_like(w["small"]["psqt_w"]),
                                fc2_w=np.zeros_like(w["small"]["fc2_w"]),
                                fc0_w=np.zeros_like(w["small"]["fc0_w"])))
    assert abs(halfka.evaluate(timid["small"], up)) < 236
    assert evaluator.evaluate(timid, up) == halfka.evaluate(w["big"], up)


@pytest.mark.parametrize("occupancy,want", [
    # 1,000 expansions: 400 answered by the small net, 50 handed back
    ({"eval_small": 400, "eval_reeval": 50, "eval_big": 550},
     {"nnue.small_eval_share": 40.0, "nnue.reeval_share": 100.0 * 50 / 450}),
    # the small net never chosen: no re-evaluation share to read
    ({"eval_small": 0, "eval_reeval": 0, "eval_big": 900},
     {"nnue.small_eval_share": 0.0}),
    # every small value kept
    ({"eval_small": 300, "eval_reeval": 0, "eval_big": 700},
     {"nnue.small_eval_share": 30.0, "nnue.reeval_share": 0.0}),
    # the parent's program keeps no such counters: left out, not 0
    ({"segments": 190, "steps": 80_000, "movegen_nodes": 900}, {}),
    # counters that did not move in the window
    ({"eval_small": 0, "eval_reeval": 0, "eval_big": 0}, {}),
])
def test_counter_readers(occupancy, want):
    ctx = {"occupancy": occupancy}
    got = {name: cells.load_reader(name)(ctx) for name in DUAL_METRICS[:2]}
    assert {k: v for k, v in got.items() if v is not None} == pytest.approx(want)


def test_roofline_reader_reads_the_ops_its_file_names(tmp_path):
    """The least time for the slice's expansions over the seconds of the
    ops its own `.json` names, at either session width; nothing where a
    pattern finds no op among the ten kept (the one-net program's 3,080-wide
    ops are not these), or where the program has no counters."""
    cfg = cells.load_cell(ROOT, CELL)["config"]
    name = DUAL_METRICS[2]
    spec = cells.load_json(ROOT / "benchmark/metrics" / f"{name}.json")
    assert len(spec["ops"]) == 2
    read = cells.load_reader(name)
    occ = {"acc_updates": 10, "movegen_nodes": 20_000, "steps": 1000}
    ctx = {"occupancy": occ, "slice": {"steps": 100}, "config": cfg,
           "peak": {"bytes_per_s": 819e9},
           "trace": {"device_ops": [["sort s32[64,2550]", 0.005],
                                    ["fusion f32[128,3328]", 0.001],
                                    ["fusion f32[64,68,3328]", 0.003]]}}
    # 100 steps x 20 expansions a step x 154,368 B at 819 GB/s over 4 ms
    want = 100.0 * (2000 * 154_368 / 819e9) / 0.004
    assert read(ctx) == pytest.approx(want)
    narrow = {"device_ops": [["fusion f32[16,68,3328]", 0.002],
                             ["fusion f32[32,3328]", 0.002]]}
    assert read(dict(ctx, trace=narrow)) == pytest.approx(want)
    one_net = {"device_ops": [["fusion f32[128,3072]", 0.001],
                              ["fusion f32[64,68,3080]", 0.003]]}
    assert read(dict(ctx, trace=one_net)) is None
    assert read(dict(ctx, occupancy={"movegen_nodes": 20_000, "steps": 1000})) is None
    assert read(dict(ctx, slice=None)) is None
    # a copy of the one-net reader's logic: the same number on the same ops
    wide = cells.load_reader("nnue.acc_update_roofline_share")
    wide_cfg = cells.load_cell(ROOT, "halfka3072.trickle")["config"]
    assert read(ctx) / wide(dict(ctx, config=wide_cfg, trace=one_net)) == \
        pytest.approx(154_368 / 147_840)


def test_a_program_without_the_pair_is_refused_at_once(monkeypatch):
    """A program with no type that holds both nets (one from before the
    pair), or whose search has no scheme for it, does not run the
    configuration, and `program_params` says so before anything is built."""
    from fishnet_tpu.models import nnue, nnue_import

    evaluator = cells.load_evaluator("halfka_dual")
    weights = evaluator.load_weights(
        {"weights": {"big": {"seed": 3, "l1": 32}, "small": {"seed": 4, "l1": 16}}}, ROOT)
    assert type(evaluator.program_params(weights)).__name__ == "DualNet"
    monkeypatch.setattr(nnue, "acc_scheme", lambda params, variant="standard": "halfka")
    with pytest.raises(RuntimeError, match="no incremental"):
        evaluator.program_params(weights)
    monkeypatch.undo()
    monkeypatch.delattr(nnue_import, "DualNet")
    with pytest.raises(RuntimeError, match="no pair"):
        evaluator.program_params(weights)


def dual_tree(tmp_path, l1, l1_small):
    """A copy of the tree in which a configuration names the real
    evaluator file at those widths, with its cell; nothing else differs
    from `halfka_dual`."""
    root = tmp_path / "repo"
    bdir = root / "benchmark"
    shutil.copytree(ROOT / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    name = f"halfka_dual{l1}"
    cfg = cells.load_json(bdir / "configs/halfka_dual.json")
    cfg["name"] = name
    cfg["engine"]["weights"] = {"big": {"seed": 20261015, "l1": l1},
                                "small": {"seed": 20261016, "l1": l1_small}}
    cfg["net_shapes"].update(l1=l1, l1_small=l1_small)
    (bdir / f"configs/{name}.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": name, "source": "x", "reduced": cfg["reduced"],
                             "file": f"benchmark/configs/{name}.json", "why": "y"})
    bench["workloads"].append({"name": f"{name}.trickle", "config": name,
                               "traffic": "trickle", "chips": 1, "why": "z"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, name


def d1_gap_over_pool(cell, weights, other):
    """Over every position of the games a rehearsal of the cell plays: the
    widest gap between the reference's depth-1 values under two sets of
    weights, and how many of the first set's evals the small net made."""
    rehearsal = cells.load_json(ROOT / "benchmark/rehearsal.json")
    traffic = dict(cell["traffic"], **rehearsal["traffic"])
    plies = rehearsal["assumed"]["plies_per_game"]
    evaluator = cell["evaluator"]
    pool = games.make_games(weights, evaluator, "standard", traffic["games"],
                            plies, traffic["pool_seed"])
    small = []

    def counting(w, p):
        if w is weights:
            small.append(abs(evaluator.material(p)) > evaluator.SMALLNET_MATERIAL)
        return evaluator.evaluate(w, p)

    by = type("Counting", (), {"evaluate": staticmethod(counting)})
    ref, low = (reference.Reference(w, by) for w in (weights, other))
    gaps = []
    for moves in pool:
        for k in range(len(moves) + 1):
            p = reference.replay("standard", moves[:k])
            want, got = ref.depth1(p), low.depth1(p)
            if want:
                gaps.append(abs(max(want.values()) - max(got.values())))
    return max(gaps), sum(small)


def test_real_evaluator_through_the_real_engine_at_rehearsal_size(tmp_path, monkeypatch):
    """`correct` true with the exact checks 0 on the incremental path with
    both nets; false against another seed's nets; and false by `d1_gap_cp`
    against the reference's eval with both nets' weights rounded to
    bfloat16, the nearest precision below the one the configuration
    states."""
    import ml_dtypes

    root, before, name = dual_tree(tmp_path, 32, 16)
    bdir = root / "benchmark"
    cell = cells.load_cell(root, f"{name}.trickle", bench_dir=bdir)
    evaluator, engine_cfg = cell["evaluator"], cell["config"]["engine"]
    assert evaluator.__file__ == str(bdir / "evaluators/halfka_dual.py")
    weights = evaluator.load_weights(engine_cfg, root)
    params = evaluator.program_params(weights)
    assert type(params).__name__ == "DualNet"
    assert (params.big.l1, params.small.l1) == (32, 16)
    result, lines, sampled = rehearse(cell, tmp_path, monkeypatch, seed=2038000119)
    assert result["correct"] is True, json.dumps([result["checks"], lines[-8:]])
    assert result["failed"] == 0 and result["window"]["answers"] > 0
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["d1_gap_cp"] == 0 and checks["d1_move_gap_cp"] == 0
    assert checks["bad_lines"] == 0 and checks["delivery"] == 0
    assert checks["programs_inside"] == 0
    limits = cell["limits"]["checks"]
    other = evaluator.load_weights(
        {"weights": {"big": {"seed": 1, "l1": 32}, "small": {"seed": 2, "l1": 16}}}, root)
    wrong, held, _detail = reference.compare(
        reference.Reference(dict(other), evaluator), list(sampled),
        {"delivery": 0, "programs_inside": 0}, limits)
    assert wrong is False and held["d1_gap_cp"]["value"] > limits["d1_gap_cp"]
    # both nets in bfloat16 fail `d1_gap_cp`, and the small net evaluates in
    # the searches compared. Held on every position of the rehearsal's
    # games, not on the window's sample: at these widths bf16 moves a
    # depth-1 value by a few cp, so whether a position that reads over the
    # limit is among the answers would depend on how many answers a 6 s
    # window holds, which is the CPU the test is given (the served depth-1
    # score is the float32 reference's on every answer, above)
    rounded = {net: {k: v.astype(ml_dtypes.bfloat16).astype(np.float32)
                     for k, v in w.items()}
               for net, w in weights.items()}
    gap, by_small = d1_gap_over_pool(cell, weights, rounded)
    assert gap > limits["d1_gap_cp"] and by_small > 0, (gap, by_small)
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
