"""Twofold repetition draws along the search path.

Stockfish scores repetitions as draws (observable through the reference's
UCI score stream, src/stockfish.rs:361-464); the device search implements
the same path-stack rule, and the host oracle implements it independently
in Python. Sparse reversible endgames at depth 5 hit repetitions by the
thousands — exact score AND node-count equality proves the device rule
matches the oracle's, and the instrumented rep_hits counter proves the
rule actually fired (rather than the positions never repeating).
"""
import jax
import numpy as np
import pytest

from fishnet_tpu.chess import Position
from fishnet_tpu.models import nnue
from fishnet_tpu.ops.board import from_position, stack_boards
from fishnet_tpu.ops.oracle import oracle_search
from fishnet_tpu.ops.search import search_batch_jit

# reversible-shuffle endgames: kings (+rooks) with nothing irreversible
# nearby, so depth-5 trees revisit earlier path positions constantly
FENS = [
    "7k/8/8/8/8/8/8/K7 w - - 0 1",
    "7k/8/8/8/8/8/8/KR6 w - - 0 1",
    "1r5k/8/8/8/8/8/8/K7 b - - 0 1",
    "1r5k/8/8/8/8/8/8/KR6 w - - 0 1",
]
DEPTH = 5
MAX_PLY = 7
BUDGET = 300_000


@pytest.fixture(scope="module")
def params():
    return nnue.init_params(
        jax.random.PRNGKey(0), l1=32, h1=8, h2=8, feature_set="board768"
    )


def test_repetition_draws_match_oracle(params):
    roots = stack_boards([from_position(Position.from_fen(f)) for f in FENS])
    out = search_batch_jit(
        params, roots, DEPTH, BUDGET, max_ply=MAX_PLY
    )
    out = {k: np.asarray(v) for k, v in out.items() if k != "tt"}
    total_reps = 0
    for i, fen in enumerate(FENS):
        exp = oracle_search(
            params, from_position(Position.from_fen(fen)), DEPTH, BUDGET, MAX_PLY
        )
        assert int(out["score"][i]) == exp["score"], fen
        assert int(out["nodes"][i]) == exp["nodes"], fen
        total_reps += exp["rep_hits"]
    # the scenario must actually exercise the rule. The pruning stack
    # keeps shaving these shuffle trees: thousands of hits unpruned,
    # ~99 after NMP/LMR (round 4), 13 after the measured aspiration-delta
    # narrowing to (15,120) (round 6) — the score/node parity asserts
    # above are the contract; this floor only proves the rule still fires
    assert total_reps > 5, f"only {total_reps} repetition hits"


def _shuffle_game(n_plies):
    """King shuffle from a K-vs-K start; returns (prefix list, root)."""
    pos = Position.from_fen("k7/8/8/8/8/8/8/K7 w - - 0 1")
    prefix = []
    for uci in ["a1b1", "a8b8", "b1a1", "b8a8"] * 2:
        if len(prefix) == n_plies:
            break
        prefix.append(pos)
        pos = pos.push(pos.parse_uci(uci))
    return prefix, pos


def _oracle_history(game):
    """Game prefix → oracle history quadruples, via the same doubled-
    position filter the engine applies for the device."""
    from fishnet_tpu.engine.tpu import TpuEngine
    from fishnet_tpu.ops.search import HIST_HM_SENTINEL, MAX_HIST

    hh, hm = TpuEngine._history_arrays([game], 1)
    return (hh, hm), [
        (int(hh[0, k, 0]), int(hh[0, k, 1]), int(hm[0, k]), MAX_HIST - k)
        for k in range(MAX_HIST)
        if hm[0, k] != HIST_HM_SENTINEL
    ]


def test_game_history_repetition_draws(params):
    """After 8 shuffle plies every pre-root placement occurred twice, so
    (Stockfish Position::is_draw: 'repeats twice before or at the root')
    the root and each child read as immediate draws; device == oracle
    exactly, and the game history is what makes it a draw."""
    game, pos = _shuffle_game(8)
    root = from_position(pos)
    (hh, hm), triples = _oracle_history(game)
    assert triples, "8-ply shuffle must yield doubled positions"

    roots = stack_boards([root] * len(FENS))
    B = len(FENS)
    out = search_batch_jit(
        params, roots, DEPTH, BUDGET, max_ply=MAX_PLY,
        hist=(np.repeat(hh, B, axis=0), np.repeat(hm, B, axis=0)),
    )
    exp = oracle_search(params, root, DEPTH, BUDGET, MAX_PLY, history=triples)
    assert exp["rep_hits"] > 0
    assert int(np.asarray(out["score"])[0]) == exp["score"] == 0
    assert int(np.asarray(out["nodes"])[0]) == exp["nodes"]

    # without history the same position searches normally (no draw leaf
    # at the root)
    plain = search_batch_jit(params, roots, DEPTH, BUDGET, max_ply=MAX_PLY)
    assert int(np.asarray(plain["nodes"])[0]) > int(np.asarray(out["nodes"])[0])


def test_single_game_occurrence_is_not_a_draw(params):
    """4 shuffle plies: the root repeats the start position once — by the
    reference rule (distance > ply) that is NOT a draw, so the doubled-
    position filter must plant nothing and results must equal plain
    search."""
    game, pos = _shuffle_game(4)
    root = from_position(pos)
    (hh, hm), triples = _oracle_history(game)
    assert not triples, "singly-occurring positions must be filtered out"

    roots = stack_boards([root] * len(FENS))
    B = len(FENS)
    out = search_batch_jit(
        params, roots, DEPTH, BUDGET, max_ply=MAX_PLY,
        hist=(np.repeat(hh, B, axis=0), np.repeat(hm, B, axis=0)),
    )
    plain = search_batch_jit(params, roots, DEPTH, BUDGET, max_ply=MAX_PLY)
    assert int(np.asarray(out["score"])[0]) == int(np.asarray(plain["score"])[0])
    assert int(np.asarray(out["nodes"])[0]) == int(np.asarray(plain["nodes"])[0])


def test_repetition_not_confused_by_irreversible_moves(params):
    """A pawn move between two visually identical placements breaks the
    reversible chain — a position 'repeated' across a pawn move is NOT a
    repetition (the halfmove-continuity condition)."""
    fen = "7k/8/8/8/8/P7/8/K7 w - - 0 1"
    root = from_position(Position.from_fen(fen))
    roots = stack_boards([root] * len(FENS))
    out = search_batch_jit(params, roots, DEPTH, BUDGET, max_ply=MAX_PLY)
    exp = oracle_search(params, root, DEPTH, BUDGET, MAX_PLY)
    assert int(np.asarray(out["score"])[0]) == exp["score"]
    assert int(np.asarray(out["nodes"])[0]) == exp["nodes"]


def _device_history(hist_lists, B, variant="standard", keep_last=0):
    """`TpuEngine._history_arrays`' contract spelt out with the device's
    own `tt.hash_board`, one position at a time: the keys the in-search
    scan will compare against."""
    from fishnet_tpu.ops import tt
    from fishnet_tpu.ops.search import HIST_HM_SENTINEL, MAX_HIST

    hh = np.zeros((B, MAX_HIST, 2), np.uint32)
    hm = np.full((B, MAX_HIST), HIST_HM_SENTINEL, np.int32)
    for lane, hist in enumerate(hist_lists):
        tail = hist[-MAX_HIST:]
        keys = []
        for p in tail:
            b = from_position(p)
            h1, h2 = tt.hash_board(b.board, b.stm, b.ep, b.castling, b.extra, variant)
            keys.append((int(h1), int(h2)))
        for j, (p, key) in enumerate(zip(tail, keys)):
            if keys.count(key) >= 2 or j >= len(tail) - keep_last:
                k = MAX_HIST - len(tail) + j
                hh[lane, k] = key
                hm[lane, k] = p.halfmove
    return hh, hm


def _long_shuffle():
    """Two pawn moves, then 22 plies of knight shuffling: a tail longer
    than MAX_HIST whose halfmove clocks do not start at 0."""
    pos = Position.initial()
    game = []
    for uci in ["e2e4", "e7e5"] + ["g1f3", "g8f6", "f3g1", "f6g8",
                                   "b1c3", "b8c6", "c3b1", "c6b8"] * 3:
        game.append(pos)
        pos = pos.push(pos.parse_uci(uci))
    return game[:-2]


def _crazyhouse_game():
    from fishnet_tpu.chess.variants import from_fen

    pos = from_fen("r3k2r/8/8/8/8/8/8/R3K2Q~[PPpn] w KQkq - 0 1", "crazyhouse")
    game = []
    for uci in ["P@e4", "N@f6", "h1h8", "f6g8", "h8h1", "g8f6", "h1h8",
                "f6g8", "h8h1", "p@d5"]:
        game.append(pos)
        pos = pos.push(pos.parse_uci(uci))
    return game + [pos]


@pytest.mark.parametrize("keep_last", [0, 1])
@pytest.mark.parametrize("case", ["shuffle4", "shuffle8", "long", "crazyhouse",
                                  "lanes", "empty"])
def test_history_arrays_are_built_on_the_host(monkeypatch, case, keep_last):
    """No device call in `_history_arrays` (it runs inside
    LaneScheduler._submit, where a fetch waits behind the running
    segment): no transfer either way, and exactly the arrays the
    device's own hash gives."""
    from fishnet_tpu.engine.tpu import TpuEngine
    from fishnet_tpu.ops import tt
    from fishnet_tpu.ops.search import MAX_HIST

    variant, B = "standard", 1
    if case == "crazyhouse":
        hists, variant = [_crazyhouse_game()], "crazyhouse"
    elif case == "lanes":
        hists, B = [_shuffle_game(8)[0], [], _long_shuffle(), _shuffle_game(3)[0]], 6
    elif case == "empty":
        hists = [[]]
    else:
        hists = [{"shuffle4": _shuffle_game(4)[0], "shuffle8": _shuffle_game(8)[0],
                  "long": _long_shuffle()}[case]]
    if case == "long":
        assert len(hists[0]) > MAX_HIST
    want = _device_history(hists, B, variant, keep_last)

    def no_device_hash(*_a, **_k):
        raise AssertionError("history keys hashed on the device")

    monkeypatch.setattr(tt, "hash_board", no_device_hash)
    with jax.transfer_guard("disallow"):
        hh, hm = TpuEngine._history_arrays(hists, B, variant, keep_last)
        shared = TpuEngine._history_arrays_shared(hists[0], 3, variant, keep_last)
    assert hh.dtype == np.uint32 and hm.dtype == np.int32
    assert np.array_equal(hh, want[0]) and np.array_equal(hm, want[1])
    for lane in range(3):
        assert np.array_equal(shared[0][lane], want[0][0])
        assert np.array_equal(shared[1][lane], want[1][0])
    if case in ("shuffle8", "long", "crazyhouse", "lanes"):
        assert (want[0] != 0).any(), "the case plants nothing"
