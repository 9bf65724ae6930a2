"""`crazyhouse.trickle`: the drop program through the real `TpuEngine`
against the benchmark's plain reference at the rehearsal's sizes, and the
two movegen metrics' readers."""
import json
import time
from pathlib import Path

import pytest

from benchmark import cells, loadgen, measure, reference

ROOT = Path(__file__).resolve().parents[2]
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


def test_program_against_the_reference_at_rehearsal_size(tmp_path, monkeypatch):
    """What `run.py --rehearse-cpu` runs: the real engine on XLA:CPU,
    seeded weights, the comparison that decides `correct`."""
    rehearsal = cells.load_json(ROOT / "benchmark/rehearsal.json")
    cell = cells.load_cell(ROOT, CELL)
    # what measure.prepare_environment sets for a rehearsal, for this test only
    for key, value in {**measure._REHEARSAL_ENV, **rehearsal["env"]}.items():
        monkeypatch.setenv(key, value)
    monkeypatch.delenv("FISHNET_TPU_DTYPE", raising=False)
    sampled = []
    compare = reference.compare

    def keep(weights, answers, counted, limits):
        sampled.extend(answers)
        return compare(weights, answers, counted, limits)

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
    # a seed whose eight-ply games see a capture: pockets at the roots
    result = loadgen.run_cell(
        cell, seed=2147483659, seconds=6.0, trace=False,
        make_engine=one_chip,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        t_start=time.monotonic(), rehearsal=rehearsal, control=None,
        say=lines.append, trace_dir=str(tmp_path / "trace"))
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
