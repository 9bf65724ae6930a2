"""`crazyhouse.trickle`: the drop program through the real `TpuEngine`
against the benchmark's plain reference at the rehearsal's sizes, and the
two movegen metrics' readers; and, by the same road, a second params type
that comes as an evaluator file."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, loadgen, measure, reference  # noqa: E402
import fake_engine  # noqa: E402

CELL = "crazyhouse.trickle"


def test_cell_is_declared_with_its_two_metrics():
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    cell = cells.load_cell(ROOT, CELL)
    assert cell["config"]["variant"] == "crazyhouse"
    assert cell["config"]["max_moves"] == 538 and cell["chips"] == 1
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-2:] == ["movegen.list_fill_share", "movegen.drop_share"]
    # the drop share is the drop program's alone; the fill share is every cell's
    old = [m["name"] for m in cells.load_cell(ROOT, "standard.trickle")["per_layer"]]
    assert "movegen.list_fill_share" in old and "movegen.drop_share" not in old
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["movegen.drop_share"]["workloads"] == [CELL]
    assert "workloads" not in by_name["movegen.list_fill_share"]


@pytest.mark.parametrize("occupancy,config,want", [
    # 1,000 expansions listed 53,800 moves of 1,000 x 538 slots, 10,760 drops
    ({"movegen_nodes": 1000, "movegen_moves": 53_800, "movegen_drops": 10_760},
     {"max_moves": 538}, {"movegen.list_fill_share": 10.0, "movegen.drop_share": 20.0}),
    # a program that generates no drop: the fill share alone
    ({"movegen_nodes": 1000, "movegen_moves": 21_800, "movegen_drops": 0},
     {"max_moves": 218}, {"movegen.list_fill_share": 10.0}),
    # the parent's program keeps no such counters: both left out, not 0
    ({"segments": 190, "steps": 80_000}, {"max_moves": 538}, {}),
    # counters that did not move in the window
    ({"movegen_nodes": 0, "movegen_moves": 0, "movegen_drops": 0},
     {"max_moves": 538}, {}),
    # a context without the configuration (an older harness)
    ({"movegen_nodes": 10, "movegen_moves": 100, "movegen_drops": 1}, None,
     {"movegen.drop_share": 1.0}),
])
def test_readers(occupancy, config, want):
    ctx = {"occupancy": occupancy}
    if config is not None:
        ctx["config"] = config
    got = {name: cells.load_reader(name)(ctx)
           for name in ("movegen.list_fill_share", "movegen.drop_share")}
    assert {k: v for k, v in got.items() if v is not None} == pytest.approx(want)


def rehearse(cell, tmp_path, monkeypatch, seed):
    """What `run.py --rehearse-cpu` runs: the real engine on XLA:CPU at the
    rehearsal's sizes, the comparison that decides `correct`.
    → (result, the lines said, the answers that were compared)."""
    rehearsal = cells.load_json(ROOT / "benchmark/rehearsal.json")
    # what measure.prepare_environment sets for a rehearsal, for this test only
    for key, value in {**measure._REHEARSAL_ENV, **rehearsal["env"]}.items():
        monkeypatch.setenv(key, value)
    monkeypatch.delenv("FISHNET_TPU_DTYPE", raising=False)
    sampled = []
    compare = reference.compare

    def keep(ref, answers, counted, limits):
        sampled.extend(answers)
        return compare(ref, answers, counted, limits)

    monkeypatch.setattr(reference, "compare", keep)
    make_adapter = measure.program_engine_factory(cell, rehearsal)

    def one_chip():
        # tests/conftest.py gives XLA:CPU eight devices and the engine would
        # shard over them; the cell is a one-chip cell
        adapter = make_adapter()
        engine = adapter.engine
        if engine.mesh is not None:
            engine.mesh, engine.n_dev = None, 1
            engine.tt = engine._scratch_tt()
        return adapter

    lines = []
    result = loadgen.run_cell(
        cell, seed=seed, seconds=6.0, trace=False,
        make_engine=one_chip,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t_start=time.monotonic(), rehearsal=rehearsal, control=None,
        say=lines.append, trace_dir=str(tmp_path / "trace"))
    return result, lines, sampled


def test_program_against_the_reference_at_rehearsal_size(tmp_path, monkeypatch):
    """The drop program, seeded games, the committed net."""
    # a seed whose eight-ply games see a capture: pockets at the roots
    result, lines, sampled = rehearse(
        cells.load_cell(ROOT, CELL), tmp_path, monkeypatch, seed=2147483659)
    assert result["correct"] is True, json.dumps([result["checks"], lines[-8:]])
    assert result["failed"] == 0 and result["window"]["answers"] > 0
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["d1_gap_cp"] == 0 and checks["bad_lines"] == 0
    assert checks["delivery"] == 0 and checks["programs_inside"] == 0
    assert result["metrics"] == {}  # a rehearsal prints no metric
    roots = [reference.replay("crazyhouse", a["moves"]) for a in sampled]
    assert any(any(p.pockets) for p in roots)
    served = [mv for a in sampled for line in a["pvs"].values() for mv in line]
    assert any("@" in mv for mv in served)
    # and every drop served was from a pocket that held the piece
    for a, p in zip(sampled, roots):
        for line in a["pvs"].values():
            assert reference.walk_line(p, line) is not None, (a["id"], line)


def test_second_params_type_by_new_files_through_the_real_engine(tmp_path, monkeypatch):
    """A fixture, not a configuration: the king-relative wide format at a toy
    width (`data/halfka_toy.py`: plain numpy eval, weights from a seed,
    `program_params` returning the `StockfishNet` that `TpuEngine` runs on
    its full-refresh path) added to a copy of the tree as one evaluator
    file, one configuration and one cell, through
    `measure.program_engine_factory` and the rehearsal's sizes."""
    source = (ROOT / "tests/benchmark/data/halfka_toy.py").read_text()
    root, before = fake_engine.tree_with_new_evaluator(
        tmp_path, ROOT, "halfka_toy", source,
        engine={"weights": {"seed": 20261004, "l1": 32}},
        net_shapes={"features": 22528, "l1": 32})
    bdir = root / "benchmark"
    cell = cells.load_cell(root, "halfka_toy.trickle", bench_dir=bdir)
    evaluator = cell["evaluator"]
    weights = evaluator.load_weights(cell["config"]["engine"], root)
    assert weights["ft_w"].shape == (22528, 32) and not (bdir / "weights").exists()
    params = evaluator.program_params(weights)
    assert type(params).__name__ == "StockfishNet" and params.l1 == 32
    result, lines, sampled = rehearse(cell, tmp_path, monkeypatch, seed=2034000113)
    assert result["correct"] is True, json.dumps([result["checks"], lines[-8:]])
    assert result["failed"] == 0 and result["window"]["answers"] > 0
    checks = {k: v["value"] for k, v in result["checks"].items()}
    assert checks["d1_gap_cp"] == 0 and checks["d1_move_gap_cp"] == 0
    assert checks["bad_lines"] == 0 and checks["delivery"] == 0
    # the answers were held to the new evaluator, not to board768: the two
    # disagree on what the roots are worth
    old = cells.load_cell(ROOT, "standard.trickle")
    old_w = old["evaluator"].load_weights(old["config"]["engine"], ROOT)
    roots = [reference.replay("standard", a["moves"]) for a in sampled]
    assert any(evaluator.evaluate(weights, p) != old["evaluator"].evaluate(old_w, p)
               for p in roots)
    # and the comparison can fail here: the same answers against the same
    # format with another seed's weights
    other = evaluator.load_weights({"weights": {"seed": 1, "l1": 32}}, root)
    wrong, held, _detail = reference.compare(
        reference.Reference(other, evaluator), list(sampled),
        {"delivery": 0, "programs_inside": 0}, cell["limits"]["checks"])
    assert wrong is False
    assert held["d1_gap_cp"]["value"] > held["d1_gap_cp"]["limit"]
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
