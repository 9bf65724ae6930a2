"""A chunk's games are replayed once (engine/tpu.py::_ChunkReplay).

`LaneScheduler._plan_jobs` walks each line of play of a chunk once and
hashes each line's history tail once; `_go_multiple_locked` takes the same
replay. What comes out must be what the per-position replay gave: every
position replayed from its root FEN, every move validated by
`Position.parse_uci`, every tail hashed on its own. That replay is kept
here, with the per-lane history filter it used, as the reference.
"""
import random
import time

import numpy as np
import pytest

from benchmark import games
from fishnet_tpu.chess.position import IllegalMoveError
from fishnet_tpu.chess.variants import from_fen, position_class
from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.tpu import (
    DEVICE_VARIANTS, MAX_PLY, TpuEngine, _ChunkEntry, _ChunkReplay,
    _position_keys,
)
from fishnet_tpu.ops.board import Board, position_fields
from fishnet_tpu.ops.search import HIST_HM_SENTINEL, MAX_HIST

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
STARTS = {
    "standard": START,
    "chess960": "bqnbrkrn/pppppppp/8/8/8/8/PPPPPPPP/BQNBRKRN w KQkq - 0 1",
    "crazyhouse": position_class("crazyhouse").starting_fen(),
    "racingKings": position_class("racingKings").starting_fen(),
    "antichess": START,
    "atomic": START,
}
DEADLINE = 1_000.0


def reference_history(hist_lists, B, variant, keep_last=0):
    """The per-lane history arrays as the engine built them before the
    shared walk: keys hashed over the flat tails, then the >=2 filter."""
    hh = np.zeros((B, MAX_HIST, 2), np.uint32)
    hm = np.full((B, MAX_HIST), HIST_HM_SENTINEL, np.int32)
    flat, lanes, ks = [], [], []
    for lane, hist in enumerate(hist_lists):
        tail = hist[-MAX_HIST:]
        flat.extend(tail)
        lanes.extend([lane] * len(tail))
        ks.extend(range(MAX_HIST - len(tail), MAX_HIST))
    if flat:
        hh[lanes, ks, 0], hh[lanes, ks, 1] = _position_keys(flat, variant)
        hm[lanes, ks] = [p.halfmove for p in flat]
        for lane in range(B):
            filled = hm[lane] != HIST_HM_SENTINEL
            pairs = [tuple(hh[lane, k]) for k in range(MAX_HIST)]
            for k in range(MAX_HIST - keep_last):
                if filled[k] and pairs.count(pairs[k]) < 2:
                    hm[lane, k] = HIST_HM_SENTINEL
                    hh[lane, k] = 0
    return hh, hm


def reference_replay(wp, variant):
    pos = from_fen(wp.root_fen, variant)
    game = []
    for uci in wp.moves:
        game.append(pos)
        pos = pos.push(pos.parse_uci(uci))
    return pos, game


def playout(variant, plies, rng):
    """UCI moves of a random legal playout, ending early where the game
    ends (its last position is then terminal)."""
    pos = from_fen(STARTS[variant], variant)
    moves = []
    while len(moves) < plies and pos.outcome() is None:
        move = rng.choice(pos.legal_moves())
        moves.append(move.uci())
        pos = pos.push(move)
    return moves


def work():
    return AnalysisWork(
        id="walk01", nodes=NodeLimit(sf16=1024, classical=1024),
        timeout_s=30.0, depth=None, multipv=None)


def make_chunk(variant, specs):
    """specs: (position_index, root_fen, moves) each."""
    w = work()
    return Chunk(
        work=w, deadline=DEADLINE, variant=variant, flavor=EngineFlavor.TPU,
        positions=[WorkPosition(work=w, position_index=idx, url=None,
                                skip=False, root_fen=fen, moves=list(mv))
                   for idx, fen, mv in specs])


def tiled_chunks(variant, moves):
    return [make_chunk(variant, [(idx, STARTS[variant], moves[:played])
                                 for idx, played in spec])
            for spec in games.tile(len(moves))]


@pytest.fixture(scope="module")
def engine():
    eng = TpuEngine(refill=True, max_depth=3, tt_size_log2=0, helper_lanes=1)
    eng.mesh = None
    eng.n_dev = 1
    return eng


def plain_response(resp):
    return (resp.position_index, resp.url, resp.scores.matrix,
            resp.pvs.matrix, resp.best_move, resp.depth, resp.nodes,
            resp.time_s, resp.nps)


def reference_plan(engine, chunk):
    """(jobs as dicts, terminal deliveries in order) of the per-position
    replay."""
    variant = DEVICE_VARIANTS.get(chunk.variant, "standard")
    jobs, delivered = [], []
    for wp in chunk.positions:
        pos, game = reference_replay(wp, chunk.variant)
        if pos.outcome() is not None:
            delivered.append(plain_response(
                engine._terminal_response(chunk, wp, pos, 0.001)))
            continue
        hh, hm = reference_history([game], 1, variant)
        jobs.append({
            "index": wp.position_index, "fen": pos.to_fen(),
            "board": position_fields(pos), "hh": hh[0], "hm": hm[0],
            "variant": variant,
            "target": min(engine.max_depth, MAX_PLY - 1),
            "budget": chunk.work.nodes.get(chunk.flavor.eval_flavor()),
            "deadline": chunk.deadline - 0.25,
        })
    return jobs, delivered


def plan(engine, chunk):
    """(jobs as dicts, terminal deliveries in order, moves parsed)."""
    delivered = []
    engine.on_response = lambda wp, resp: delivered.append(
        plain_response(resp))
    try:
        entry = _ChunkEntry(chunk, time.monotonic())
        replay = _ChunkReplay(chunk.positions, chunk.variant)
        spent = {"replay": 0.0, "history": 0.0, "ttwarm": 0.0}
        jobs = engine._scheduler._plan_jobs(entry, replay, None, "", spent)
    finally:
        engine.on_response = None
    return [{
        "index": j.wp.position_index, "fen": j.pos.to_fen(),
        "board": j.board, "hh": j.hh, "hm": j.hm, "variant": j.variant,
        "target": j.target_depth, "budget": j.remaining,
        "deadline": j.deadline,
    } for j in jobs], delivered, replay.parsed


def assert_same_plan(got, want):
    assert [j["index"] for j in got] == [j["index"] for j in want]
    for g, w in zip(got, want):
        for key in ("fen", "variant", "target", "budget", "deadline"):
            assert g[key] == w[key], (g["index"], key)
        for key in ("hh", "hm"):
            assert g[key].dtype == w[key].dtype, (g["index"], key)
            assert g[key].shape == w[key].shape, (g["index"], key)
            np.testing.assert_array_equal(g[key], w[key], err_msg=key)
        for name in Board._fields:
            a, b = getattr(g["board"], name), getattr(w["board"], name)
            assert np.asarray(a).dtype == np.asarray(b).dtype, name
            np.testing.assert_array_equal(a, b, err_msg=name)


VARIANTS = ["standard", "chess960", "crazyhouse", "racingKings",
            "antichess", "atomic"]


@pytest.mark.parametrize("variant", VARIANTS)
def test_tiled_chunks_plan_as_the_per_position_replay(engine, variant):
    """Chunks cut by the planner's tiling from seeded playouts: the
    same jobs, keys and terminal deliveries, and each chunk's moves
    validated once, to its deepest position."""
    rng = random.Random(f"walk:{variant}")
    for _game in range(3):
        moves = playout(variant, 40, rng)
        for chunk in tiled_chunks(variant, moves):
            got, delivered, parsed = plan(engine, chunk)
            want, want_delivered = reference_plan(engine, chunk)
            assert_same_plan(got, want)
            assert delivered == want_delivered
            assert parsed == max(len(wp.moves) for wp in chunk.positions)


@pytest.mark.parametrize("variant", VARIANTS)
def test_replay_gives_each_position_its_board_and_game(variant):
    """What `_go_multiple_locked` takes: each position's board and the
    boards before it, oldest first, in chunk order ascending and
    descending."""
    rng = random.Random(f"games:{variant}")
    moves = playout(variant, 24, rng)
    for chunk in tiled_chunks(variant, moves):
        for positions in (chunk.positions, chunk.positions[::-1]):
            replay = _ChunkReplay(positions, chunk.variant)
            for i, wp in enumerate(positions):
                pos, game = reference_replay(wp, chunk.variant)
                got, legal = replay.position(i)
                assert got.to_fen() == pos.to_fen()
                assert [p.to_fen() for p in replay.game(i)] == [
                    p.to_fen() for p in game]
                if legal is not None:
                    assert legal == pos.legal_moves()
                    assert pos.outcome(legal) == pos.outcome()


def test_terminal_positions_inside_a_chunk(engine):
    """A mated position is answered at once, in the chunk's order,
    wherever it stands in the chunk."""
    moves = ["f2f3", "e7e5", "g2g4", "d8h4"]
    for order in (1, -1):
        specs = [(i, START, moves[:i]) for i in range(len(moves) + 1)]
        chunk = make_chunk("standard", specs[::order])
        got, delivered, parsed = plan(engine, chunk)
        want, want_delivered = reference_plan(engine, chunk)
        assert_same_plan(got, want)
        assert delivered == want_delivered and len(delivered) == 1
        assert delivered[0][0] == 4
        assert parsed == 4


def test_unrelated_positions_are_each_their_own_line(engine):
    """A `serve`-style chunk: other root FENs, nothing shared, so every
    position's moves are validated as before."""
    specs = [
        (0, START, ["e2e4", "e7e5"]),
        (1, "r1bqkbnr/pppp1ppp/2n5/4p3/4P3/5N2/PPPP1PPP/RNBQKB1R w KQkq - 2 3",
         ["f1b5", "a7a6"]),
        (2, "8/8/8/4k3/8/8/4K3/4R3 w - - 0 1", ["e1a1"]),
        (3, START, ["d2d4"]),
        (4, "7k/5Q2/6K1/8/8/8/8/8 b - - 0 1", []),  # stalemate
    ]
    chunk = make_chunk("standard", specs)
    got, delivered, parsed = plan(engine, chunk)
    want, want_delivered = reference_plan(engine, chunk)
    assert_same_plan(got, want)
    assert delivered == want_delivered and len(delivered) == 1
    assert parsed == sum(len(mv) for _i, _f, mv in specs)


def test_diverging_lines_from_one_root(engine):
    """Two lines from one root part after a shared move: a prefix of
    both walks with one of them, and each line is walked once."""
    a = ["e2e4", "e7e5", "g1f3", "b8c6", "f1b5"]
    b = ["e2e4", "c7c5", "g1f3", "d7d6"]
    specs = [(0, START, a[:1]), (1, START, b), (2, START, a), (3, START, b[:2]),
             (4, START, a[:3]), (5, START, [])]
    chunk = make_chunk("standard", specs)
    got, delivered, parsed = plan(engine, chunk)
    want, want_delivered = reference_plan(engine, chunk)
    assert_same_plan(got, want)
    assert delivered == want_delivered == []
    assert parsed == len(a) + len(b)


def test_repetitions_in_a_shared_tail_are_planted_as_before(engine):
    """Knights out and back: the tails hold repeated boards, so the >=2
    filter keeps entries, and positions deeper than MAX_HIST plies see
    a tail that starts inside the line."""
    shuffle = ["g1f3", "g8f6", "f3g1", "f6g8"] * 5 + ["e2e4", "e7e5"]
    specs = [(i, START, shuffle[:n]) for i, n in
             enumerate((22, 21, 20, 19, 9, 3))]
    chunk = make_chunk("standard", specs)
    got, _delivered, parsed = plan(engine, chunk)
    want, _want = reference_plan(engine, chunk)
    assert_same_plan(got, want)
    assert any((j["hm"] != HIST_HM_SENTINEL).any() for j in got)
    assert parsed == 22


@pytest.mark.parametrize("where", ["deepest_first", "deepest_last"])
def test_an_illegal_move_raises_as_before(engine, where):
    """The same IllegalMoveError, with the same message, as the first
    position in chunk order whose own moves hold the illegal one."""
    moves = ["e2e4", "e7e5", "e1e3", "b8c6"]  # the king cannot jump
    specs = [(i, START, moves[:i]) for i in range(len(moves) + 1)]
    if where == "deepest_first":
        specs = specs[::-1]
    chunk = make_chunk("standard", specs)
    with pytest.raises(IllegalMoveError) as want:
        reference_plan(engine, chunk)
    with pytest.raises(IllegalMoveError) as got:
        plan(engine, chunk)
    assert str(got.value) == str(want.value)
    with pytest.raises(IllegalMoveError):
        engine._scheduler._submit(chunk)


def submitted(engine, chunk):
    tot = engine.occupancy_totals
    before = {k: tot[k] for k in ("submit_prefix_moves",
                                  "submit_moves_parsed",
                                  "positions_submitted")}
    engine._scheduler._submit(chunk)
    with engine._scheduler._q_lock:
        engine._scheduler._pending.clear()  # never driven here
    return {k: tot[k] - v for k, v in before.items()}


def test_counters_tie_out_on_a_tiled_chunk(engine):
    """A chunk of consecutive plies: the walk validates the deepest
    position's moves, the positions hold the sum of theirs."""
    moves = playout("standard", 40, random.Random("tie"))
    for chunk in tiled_chunks("standard", moves):
        plies = [len(wp.moves) for wp in chunk.positions]
        got = submitted(engine, chunk)
        assert got["positions_submitted"] == len(plies)
        assert got["submit_moves_parsed"] == max(plies)
        assert got["submit_prefix_moves"] == sum(plies)


def test_counters_tie_out_on_unrelated_positions(engine):
    specs = [(0, START, ["e2e4", "e7e5", "g1f3"]),
             (1, START, ["d2d4", "d7d5"]),
             (2, "8/8/8/4k3/8/8/4K3/4R3 w - - 0 1", ["e1a1"])]
    got = submitted(engine, make_chunk("standard", specs))
    assert got["submit_moves_parsed"] == got["submit_prefix_moves"] == 6


def test_history_arrays_keep_their_contract():
    """`_history_arrays` (move jobs, multi-PV, the serial path) shares
    the row filter with the walk: the same arrays as before, several
    lanes, empty tails and keep_last included."""
    shuffle = ["g1f3", "g8f6", "f3g1", "f6g8"] * 5
    _pos, game = reference_replay(
        WorkPosition(work=work(), position_index=0, url=None, skip=False,
                     root_fen=START, moves=shuffle), "standard")
    hists = [game, game[:3], [], game[:17]]
    for keep_last in (0, 1):
        got = TpuEngine._history_arrays(hists, 6, "standard", keep_last)
        want = reference_history(hists, 6, "standard", keep_last)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
