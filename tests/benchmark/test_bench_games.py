"""Games from the seed: legal, of full length, different from seed to seed,
and worth the same node budget whatever the seed drew."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import games, reference, rules  # noqa: E402
import fake_engine  # noqa: E402

WEIGHTS, EVALUATOR = fake_engine.cell_weights(ROOT)


def perft(p, depth):
    if depth == 0:
        return 1
    return sum(perft(rules.make(p, m), depth - 1) for m in rules.legal_moves(p))


def test_rules_perft_from_the_start():
    assert perft(rules.start(), 1) == 20
    assert perft(rules.start(), 2) == 400
    assert perft(rules.start(), 3) == 8902
    # crazyhouse from the start has empty pockets: the same tree
    assert perft(rules.start("crazyhouse"), 2) == 400


@pytest.mark.parametrize("variant", ["standard", "crazyhouse"])
def test_two_seeds_two_games_one_budget(variant):
    a = games.make_games(WEIGHTS, EVALUATOR, variant, 2, 12, seed=2147483659)
    b = games.make_games(WEIGHTS, EVALUATOR, variant, 2, 12, seed=987654321)
    again = games.make_games(WEIGHTS, EVALUATOR, variant, 2, 12, seed=2147483659)
    assert a == again and a != b and a[0] != a[1]
    for moves in a + b:
        assert len(moves) == 12
        end = reference.replay(variant, moves)
        assert end is not None and rules.legal_moves(end)
        seen = set()
        p = rules.start(variant)
        for text in moves:
            seen.add(p.key())
            p = rules.make(p, rules.parse_uci(p, text))
            assert p.key() not in seen
    # the budget is the configuration's, the same for every position of
    # every seed: chunks made from either seed carry the same node limit
    from benchmark import cells, loadgen

    cell = cells.load_cell(ROOT, "standard.trickle")
    cell["config"] = dict(cell["config"], variant=variant)
    budgets = set()
    for seed, gl in ((2147483659, a), (987654321, b)):
        lp = loadgen.ClosedLoop(fake_engine.FakeAdapter(WEIGHTS, EVALUATOR), cell,
                                cell["traffic"], gl, seed)
        for _ in range(6):
            chunk, _rec = lp.next_chunk()
            budgets.add((chunk["nodes"]["sf16"], chunk["nodes"]["classical"]))
    assert budgets == {(1024, 1024)}


def test_crazyhouse_drops_and_pockets():
    p = reference.replay("crazyhouse", ["e2e4", "d7d5", "e4d5", "d8d5"])
    assert p.pockets[0] == 1 and p.pockets[5] == 1  # a pawn each
    drops = [rules.uci(m) for m in rules.legal_moves(p) if m & rules.DROP]
    assert "P@e4" in drops and not any(d.endswith(("1", "8")) for d in drops)
    q = rules.make(p, rules.parse_uci(p, "P@e4"))
    assert q.pockets[0] == 0 and q.board[28] == 1


def test_castling_is_accepted_in_both_spellings():
    p = reference.replay("standard", ["e2e4", "e7e5", "g1f3", "g8f6", "f1c4", "f8c5"])
    assert rules.parse_uci(p, "e1g1") == rules.parse_uci(p, "e1h1") is not None
    q = rules.make(p, rules.parse_uci(p, "e1h1"))
    assert q.board[6] == 6 and q.board[5] == 4 and q.castling[:2] == [-1, -1]


def test_reference_depth1_is_the_best_quiescent_child():
    p = reference.replay("standard", ["e2e4", "d7d5"])
    ref = reference.Reference(WEIGHTS, EVALUATOR)
    values = ref.depth1(p)
    assert len(values) == len(rules.legal_moves(p))
    take = rules.parse_uci(p, "e4d5")
    # after exd5 black recaptures in quiescence: the value is not the
    # static eval of the position a pawn up
    static_up = -EVALUATOR.evaluate(WEIGHTS, rules.make(p, take))
    assert values[take] < static_up


@pytest.mark.parametrize("seed", [0, 1, 2064000410, 2147483659])
def test_deal_is_a_permutation_drawn_from_the_seed(seed):
    order = games.deal(8, seed)
    assert sorted(order) == list(range(8)) and order == games.deal(8, seed)
    assert games.deal(0, seed) == []
    others = [games.deal(8, seed + k) for k in range(1, 6)]
    assert any(o != order for o in others)


@pytest.mark.parametrize("workload", ["standard.trickle", "crazyhouse.trickle"])
def test_every_seed_is_dealt_the_traffic_files_pool(workload, tmp_path, monkeypatch):
    """What the refusal of PR 34's first check asked for: the same games for
    every --seed, in another order."""
    import time

    from benchmark import loadgen

    seen = {}

    class Spy(loadgen.ClosedLoop):
        def __init__(self, adapter, cell, traffic, game_list, seed):
            seen[seed] = [tuple(g) for g in game_list]
            super().__init__(adapter, cell, traffic, game_list, seed)

    class NoTrace:
        pass

    monkeypatch.setattr(loadgen, "ClosedLoop", Spy)

    def run(cell, seed):
        return loadgen.run_cell(
            cell, seed=seed, seconds=0.3, trace=False,
            make_engine=lambda: fake_engine.FakeAdapter(WEIGHTS, EVALUATOR),
            device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            t_start=time.monotonic(), rehearsal=None, control=None,
            say=lambda _m: None, trace_dir=str(tmp_path / "trace"),
            tracer_factory=NoTrace)

    cell = fake_engine.toy_cell(ROOT, workload)
    cell["traffic"]["games"] = 4
    assert isinstance(cell["traffic"]["pool_seed"], int)
    seeds = [3, 2147483659, 2200000001, 77]
    for seed in seeds:
        assert run(cell, seed)["correct"] is True
    pool = games.make_games(WEIGHTS, EVALUATOR, cell["config"]["variant"], 4, 6,
                            cell["traffic"]["pool_seed"])
    for seed in seeds:
        assert sorted(seen[seed]) == sorted(tuple(g) for g in pool)
        assert seen[seed] == [tuple(pool[g]) for g in games.deal(4, seed)]
    assert len({tuple(seen[s]) for s in seeds}) > 1
