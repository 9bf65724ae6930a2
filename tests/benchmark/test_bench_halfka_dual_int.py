"""`halfka_dual_int.trickle`: both nets of the deployment in the integers
their files hold, at their published widths (declared and counted, never
run here), and the evaluator file, `benchmark/evaluators/halfka_dual_int.py`,
at small widths: against the program's own integer reference, through
`measure.program_engine_factory` and the real `TpuEngine` on XLA:CPU at the
rehearsal's sizes, named by a configuration in a temporary tree; and a
program without the integer scheme refused before anything is built."""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, reference, rules  # noqa: E402
from test_bench_crazyhouse import rehearse  # noqa: E402
from test_bench_halfka_dual import d1_gap_over_pool  # noqa: E402

CELL = "halfka_dual_int.trickle"
BENCH = json.load(open(ROOT / "BENCHMARK.json"))
SMALL = {"weights": {"big": {"seed": 5, "l1": 32}, "small": {"seed": 6, "l1": 16}}}


def test_cell_is_declared_with_the_shared_metrics():
    """The cell reports the per-layer metrics every cell reports and no
    other cell's own."""
    cell = cells.load_cell(ROOT, CELL)
    assert cell["chips"] == 1 and cell["traffic"]["name"] == "trickle"
    assert cell["config"]["variant"] == "standard" and cell["config"]["max_moves"] == 218
    shared = [m["name"] for m in BENCH["per_layer"] if "workloads" not in m]
    assert [m["name"] for m in cell["per_layer"]] == shared
    # the float pair's cell: the same work under the same traffic
    float_cell = cells.load_cell(ROOT, "halfka_dual.trickle")
    assert cell["config"]["work"] == float_cell["config"]["work"]
    assert cell["traffic"] == float_cell["traffic"]
    assert [c["name"] for c in BENCH["configs"]][-1] == "halfka_dual_int"
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL


def test_no_width_is_cut_and_the_bytes_are_the_integers():
    cfg = cells.load_cell(ROOT, CELL)["config"]
    float_cfg = cells.load_cell(ROOT, "halfka_dual.trickle")["config"]
    shapes = cfg["net_shapes"]
    assert shapes == float_cfg["net_shapes"]
    assert shapes["l1"] == 3072 and shapes["l1_small"] == 128 and shapes["stacks"] == 8
    assert cfg["published"] == float_cfg["published"]
    assert cfg["reduced"] == ["nodes.sf16", "nodes.classical"]
    entry = next(c for c in BENCH["configs"] if c["name"] == "halfka_dual_int")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    engine, float_engine = cfg["engine"], float_cfg["engine"]
    assert engine["evaluator"] == "halfka_dual_int" and engine["dtype"] == "int16/int8"
    for key in ("weights", "max_depth", "tt_size_log2", "helper_lanes", "refill",
                "max_lanes"):
        assert engine[key] == float_engine[key], key
    evaluator = cells.load_evaluator("halfka_dual_int")
    # 8 rows and both pairs read and written, of 3,200 int16 + 16 int32
    assert evaluator.acc_update_bytes(shapes) == 12 * (3200 * 2 + 16 * 4) == 77_568
    assumed = cfg["assumed"]
    assert assumed["joined_table_bytes"] == 22528 * 6464 == 145_620_992
    assert assumed["acc_stack_bytes_64_lanes"] == 64 * 68 * 6464 == 28_131_328

    def stored(l1):
        return (22528 * l1 * 2 + l1 * 2 + 22528 * 8 * 4
                + 8 * (16 * l1 + 16 * 4 + 32 * 30 + 32 * 4 + 32 + 4))

    assert assumed["small_net_bytes"] == stored(128)
    assert assumed["net_bytes"] == stored(3072) + stored(128) == 146_056_000
    for const in ("127", "128", ">> 19", ">> 6", "9600", "8128", "/ 16"):
        assert const in assumed["ladder"], const
    assert assumed["rule"] == float_cfg["assumed"]["rule"]
    # the net's work at its stored widths: fewer bytes than the float pair's
    net = evaluator.net_work(shapes)
    float_net = cells.load_evaluator("halfka_dual").net_work(shapes)
    assert net["flops"] == float_net["flops"] and net["bytes"] < float_net["bytes"] / 2


def test_the_weights_are_halfka_duals_draw_quantized():
    evaluator = cells.load_evaluator("halfka_dual_int")
    halfka = cells.load_evaluator("halfka")
    w = evaluator.load_weights(SMALL, ROOT)
    assert evaluator.load_weights(SMALL, ROOT) is w  # kept, not drawn again
    assert evaluator.clipped == {"big": 0, "small": 0}
    drawn = halfka._draw(5, 32)
    big = w["big"]
    assert {k: v.dtype for k, v in big.items()} == {
        "ft_w": np.int16, "ft_b": np.int16, "psqt_w": np.int32,
        "fc0_w": np.int8, "fc0_b": np.int32, "fc1_w": np.int8, "fc1_b": np.int32,
        "fc2_w": np.int8, "fc2_b": np.int32}
    np.testing.assert_array_equal(big["ft_w"], np.rint(drawn["ft_w"].astype(np.float64) * 127))
    np.testing.assert_array_equal(big["psqt_w"], np.rint(drawn["psqt_w"].astype(np.float64) * 9600))
    # fc1's columns in the file's order: the squared 15 first
    np.testing.assert_array_equal(
        big["fc1_w"][..., :15], np.rint(drawn["fc1_w"][..., 15:].astype(np.float64) * 64))
    np.testing.assert_array_equal(
        big["fc1_w"][..., 15:], np.rint(drawn["fc1_w"][..., :15].astype(np.float64) * 64))
    assert w["small"]["ft_w"].shape == (22528, 16)


def test_the_benchmark_reference_is_the_programs_integer_reference():
    """Two plain references written apart (the benchmark's and
    nnue_import's) agree on every position of a random playout and on a
    lopsided position the small net is chosen for."""
    from fishnet_tpu.models import nnue_import as ni

    evaluator = cells.load_evaluator("halfka_dual_int")
    w = evaluator.load_weights(SMALL, ROOT)
    big, small = (ni.StockfishNetInt(**w[n]) for n in ("big", "small"))
    p = rules.start("standard")
    n = 0
    for mv in np.random.default_rng(3).integers(0, 1 << 30, 60):
        moves = rules.legal_moves(p)
        if not moves:
            break
        p = rules.make(p, moves[int(mv) % len(moves)])
        board = np.asarray(p.board, np.int32)
        want = ni.evaluate_dual_int_reference(big, small, board, p.stm)
        assert evaluator.evaluate(w, p) == max(-31000, min(31000, want))
        n += 1
    assert n >= 40
    start = rules.start("standard")
    board = list(start.board)
    board[59] = board[57] = board[58] = 0  # black's queen, knight and bishop
    up = rules.Pos(board, 0, -1, start.castling, 0)
    assert abs(evaluator.material(up)) > evaluator.SMALLNET_MATERIAL
    assert evaluator.evaluate(w, up) == ni.evaluate_dual_int_reference(
        big, small, np.asarray(up.board, np.int32), 0)


def test_the_reference_refuses_a_row_that_would_wrap():
    evaluator = cells.load_evaluator("halfka_dual_int")
    w = evaluator.load_weights(SMALL, ROOT)
    wide = dict(w, big=dict(w["big"], ft_w=np.full_like(w["big"]["ft_w"], 3000)))
    with pytest.raises(OverflowError, match="int16"):
        evaluator.evaluate(wide, rules.start("standard"))


def test_a_program_without_the_integer_scheme_is_refused_at_once(monkeypatch):
    """A program with no integer net types (the parent's), or whose search
    has no integer scheme for them, does not run the configuration, and
    `program_params` says so before anything is built."""
    from fishnet_tpu.models import nnue, nnue_import

    evaluator = cells.load_evaluator("halfka_dual_int")
    weights = evaluator.load_weights(SMALL, ROOT)
    assert type(evaluator.program_params(weights)).__name__ == "DualNetInt"
    monkeypatch.setattr(nnue, "acc_scheme", lambda params, variant="standard": "dual")
    with pytest.raises(RuntimeError, match="no integer scheme"):
        evaluator.program_params(weights)
    monkeypatch.undo()
    monkeypatch.delattr(nnue_import, "DualNetInt")
    with pytest.raises(RuntimeError, match="no integer nets"):
        evaluator.program_params(weights)


def int_tree(tmp_path, l1, l1_small):
    """A copy of the tree in which a configuration names the real
    evaluator file at those widths, with its cell; nothing else differs
    from `halfka_dual_int`."""
    root = tmp_path / "repo"
    bdir = root / "benchmark"
    shutil.copytree(ROOT / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    name = f"halfka_dual_int{l1}"
    cfg = cells.load_json(bdir / "configs/halfka_dual_int.json")
    cfg["name"] = name
    cfg["engine"]["weights"] = {"big": {"seed": 20261019, "l1": l1},
                                "small": {"seed": 20261020, "l1": l1_small}}
    cfg["net_shapes"].update(l1=l1, l1_small=l1_small)
    (bdir / f"configs/{name}.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": name, "source": "x", "reduced": cfg["reduced"],
                             "file": f"benchmark/configs/{name}.json", "why": "y"})
    bench["workloads"].append({"name": f"{name}.trickle", "config": name,
                               "traffic": "trickle", "chips": 1, "why": "z"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, name


def test_real_evaluator_through_the_real_engine_at_rehearsal_size(tmp_path, monkeypatch):
    """`correct` true with `d1_gap_cp` and `d1_move_gap_cp` 0 on the
    incremental integer path with both nets; false against another seed's
    nets; and the float arithmetic of the same integers (each divided by
    its storage scale, the float forward, as the float program computes a
    dequantized file) misses the reference's depth-1 values by more than
    `d1_gap_cp`'s limit."""
    root, before, name = int_tree(tmp_path, 32, 16)
    bdir = root / "benchmark"
    cell = cells.load_cell(root, f"{name}.trickle", bench_dir=bdir)
    evaluator, engine_cfg = cell["evaluator"], cell["config"]["engine"]
    assert evaluator.__file__ == str(bdir / "evaluators/halfka_dual_int.py")
    weights = evaluator.load_weights(engine_cfg, root)
    params = evaluator.program_params(weights)
    assert type(params).__name__ == "DualNetInt"
    assert (params.big.l1, params.small.l1) == (32, 16)
    result, lines, sampled = rehearse(cell, tmp_path, monkeypatch, seed=2043000119)
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
    # the same integers in the float program's arithmetic
    weights = evaluator.load_weights(engine_cfg, root)
    floats = {net: {key: (w[key] / scale).astype(np.float32)
                    for key, scale, _type in evaluator.STORAGE}
              for net, w in weights.items()}
    float_dual = cells.load_evaluator("halfka_dual", bdir)

    class Both:
        """The integer reference for the integers, the float one else."""
        SMALLNET_MATERIAL = evaluator.SMALLNET_MATERIAL
        material = staticmethod(evaluator.material)

        @staticmethod
        def evaluate(w, p):
            return (evaluator if w is weights else float_dual).evaluate(w, p)

    gap, by_small = d1_gap_over_pool(dict(cell, evaluator=Both), weights, floats)
    assert gap > limits["d1_gap_cp"] and by_small > 0, (gap, by_small)
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
