"""Device-side crazyhouse + threeCheck vs the host variant rules.

The reference analyses variants with Fairy-Stockfish (src/stockfish.rs:
245-260 sets UCI_Variant); the device implements them as statically
compiled program variants. Property tests: move SETS and make_move state
(incl. pockets, promoted bits, check counters) must match the host
library over random playouts; searches must match the host oracle
exactly; a variant chunk must flow through TpuEngine end to end.
"""
import asyncio
import random
import time

import jax
import numpy as np
import pytest

from fishnet_tpu.chess import Move
from fishnet_tpu.chess.variants import from_fen, position_class
from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.models import nnue
from fishnet_tpu.ops import tables as T
from fishnet_tpu.ops.board import from_position, stack_boards
from fishnet_tpu.ops.movegen import DROP_FLAG, generate_moves
from fishnet_tpu.ops.board import make_move
from fishnet_tpu.ops.oracle import oracle_search
from fishnet_tpu.ops.search import search_batch_jit

_PROMO_MAP = {
    1: T.PROMO_N, 2: T.PROMO_B, 3: T.PROMO_R, 4: T.PROMO_Q,
    5: T.PROMO_K,  # antichess promotes to king (host piece type 5)
}


def encode_host_move(m: Move) -> int:
    if m.drop is not None:
        return DROP_FLAG | (m.drop << 12) | (m.to_sq << 6) | m.to_sq
    promo = _PROMO_MAP[m.promotion] if m.promotion is not None else 0
    return m.from_sq | (m.to_sq << 6) | (promo << 12)


@pytest.fixture(scope="module")
def params():
    return nnue.init_params(
        jax.random.PRNGKey(0), l1=32, h1=8, h2=8, feature_set="board768"
    )


ALL_VARIANTS = [
    "crazyhouse", "threeCheck", "antichess", "atomic", "horde",
    "kingOfTheHill", "racingKings",
]


@pytest.fixture(scope="module", params=ALL_VARIANTS)
def variant(request):
    return request.param


@pytest.fixture(scope="module")
def kernels(variant):
    gen = jax.jit(lambda b: generate_moves(b, variant))
    mk = jax.jit(lambda b, m: make_move(b, m, variant))
    return gen, mk


def _boards_equal(b1, b2) -> bool:
    return (
        np.array_equal(np.asarray(b1.board), np.asarray(b2.board))
        and int(b1.stm) == int(b2.stm)
        and int(b1.ep) == int(b2.ep)
        and sorted(np.asarray(b1.castling).tolist())
        == sorted(np.asarray(b2.castling).tolist())
        and int(b1.halfmove) == int(b2.halfmove)
        and np.array_equal(np.asarray(b1.extra), np.asarray(b2.extra))
    )


def test_playouts_match_host(variant, kernels):
    gen, mk = kernels
    rng = random.Random(42)
    for game in range(6):
        pos = position_class(variant).from_fen(
            position_class(variant).starting_fen()
        )
        for ply in range(40):
            legal = pos.legal_moves()
            if not legal or pos.outcome() is not None:
                break
            if variant == "antichess":
                # the device folds capture compulsion into generation
                # (no check concept, so legal == compulsion-filtered)
                host_set = {encode_host_move(m) for m in legal}
            else:
                host_set = {
                    encode_host_move(m) for m in pos.generate_pseudo_legal()
                }
            b = from_position(pos)
            moves, count, _ = gen(b)
            dev_set = set(np.asarray(moves)[: int(count)].tolist())
            assert dev_set == host_set, (
                f"{variant} move set mismatch\nfen={pos.to_fen()}\n"
                f"host-only={sorted(host_set - dev_set)}\n"
                f"device-only={sorted(dev_set - host_set)}"
            )
            move = rng.choice(legal)
            child = pos.push(move)
            dev_child = mk(b, encode_host_move(move))
            assert _boards_equal(dev_child, from_position(child)), (
                f"{variant} make_move mismatch: {move.uci()}\n"
                f"fen={pos.to_fen()} → {child.to_fen()}"
            )
            pos = child


def _variant_fens(variant, n, seed=11):
    rng = random.Random(seed)
    fens = []
    while len(fens) < n:
        pos = position_class(variant).from_fen(
            position_class(variant).starting_fen()
        )
        for _ in range(rng.randrange(4, 40)):
            legal = pos.legal_moves()
            if not legal or pos.outcome() is not None:
                break
            pos = pos.push(rng.choice(legal))
        if pos.outcome() is None and pos.legal_moves():
            fens.append(pos.to_fen())
    return fens


def _oracle_check(params, variant, depth, n_fens=8):
    fens = _variant_fens(variant, n_fens)
    roots = stack_boards([from_position(from_fen(f, variant)) for f in fens])
    out = search_batch_jit(
        params, roots, depth, 100_000, max_ply=4, variant=variant
    )
    out = {k: np.asarray(v) for k, v in out.items() if k != "tt"}
    for i, fen in enumerate(fens):
        exp = oracle_search(
            params, from_position(from_fen(fen, variant)), depth, 100_000, 4,
            variant=variant,
        )
        assert int(out["score"][i]) == exp["score"], (variant, fen, depth)
        assert int(out["nodes"][i]) == exp["nodes"], (variant, fen, depth)


def test_search_matches_oracle_depth1(params, variant):
    _oracle_check(params, variant, 1)


@pytest.mark.slow
def test_search_matches_oracle_depth2(params, variant):
    _oracle_check(params, variant, 2)


def test_three_check_win_is_mate_scored(params):
    """2 checks given + a check available: delivering the 3rd check ends
    the game — the search must find a forced win."""
    from fishnet_tpu.ops.search import MATE

    # white Qd2+Ke1 vs black Ke8; white has given 2 checks already and
    # has checks at will (e.g. Qd8+) — any check is the 3rd
    fen = "4k3/8/8/8/8/8/3Q4/4K3 w - - +2+0 0 1"
    root = from_position(from_fen(fen, "threeCheck"))
    roots = stack_boards([root] * 8)
    out = search_batch_jit(
        params, roots, 2, 100_000, max_ply=4, variant="threeCheck"
    )
    score = int(np.asarray(out["score"])[0])
    assert score >= MATE - 10, f"expected 3check win, got {score}"


def _spot_score(params, fen, variant, depth=2, lanes=8):
    root = from_position(from_fen(fen, variant))
    roots = stack_boards([root] * lanes)
    out = search_batch_jit(
        params, roots, depth, 100_000, max_ply=4, variant=variant
    )
    return int(np.asarray(out["score"])[0])


def test_atomic_exploding_the_king_wins(params):
    from fishnet_tpu.ops.search import MATE

    # Qxd8 explodes the knight; the blast removes the adjacent king
    score = _spot_score(params, "3nk3/8/8/8/8/8/8/3QK3 w - - 0 1", "atomic")
    assert score >= MATE - 10, score


def test_atomic_explosion_reaches_a1(params):
    """Regression: the blast zone must cover square a1 (a clipped -1 pad
    in KING_TARGETS once overwrote a1's membership), so a non-pawn on a1
    dies when a capture lands next to it."""
    pos = from_fen("4k3/8/8/8/8/8/1r6/nR2K3 w - - 0 1", "atomic")
    mv = next(m for m in pos.legal_moves() if m.uci() == "b1b2")
    child = pos.push(mv)
    dev = jax.jit(lambda b, m: make_move(b, m, "atomic"))(
        from_position(pos), encode_host_move(mv)
    )
    assert _boards_equal(dev, from_position(child))
    assert int(np.asarray(dev.board)[0]) == 0  # the a1 knight exploded


def test_koth_reaching_the_hill_wins(params):
    from fishnet_tpu.ops.search import MATE

    # Kd3-d4 steps onto the hill
    score = _spot_score(params, "7k/8/8/8/8/3K4/8/8 w - - 0 1", "kingOfTheHill")
    assert score >= MATE - 10, score


def test_racing_kings_goal_with_failed_rejoinder_wins(params):
    from fishnet_tpu.ops.search import MATE

    # Kg7-g8 reaches the goal; the black king on a1 cannot answer in one
    score = _spot_score(params, "8/6K1/8/8/8/8/8/k7 w - - 0 1", "racingKings")
    assert score >= MATE - 10, score


def test_racing_kings_rejoinder_draws(params):
    # white already on the goal, black to move one step below: Ka8
    # equalizes (draw); every other reply loses — so black scores 0
    score = _spot_score(params, "6K1/k7/8/8/8/8/8/8 b - - 0 1", "racingKings")
    assert score == 0, score


def test_horde_destroying_the_horde_wins(params):
    from fishnet_tpu.ops.search import MATE

    # black queen takes white's last pawn → horde destroyed
    score = _spot_score(params, "4k3/8/8/8/8/8/q6P/8 b - - 0 1", "horde")
    assert score >= MATE - 10, score


def test_antichess_capture_compulsion(params):
    # white pawn e4 can capture d5: ONLY captures may be generated
    pos = from_fen(
        "rnbqkbnr/ppp1pppp/8/3p4/4P3/8/PPPP1PPP/RNBQKBNR w - - 0 2",
        "antichess",
    )
    moves, count, _ = jax.jit(
        lambda b: generate_moves(b, "antichess")
    )(from_position(pos))
    dev = set(np.asarray(moves)[: int(count)].tolist())
    assert dev == {encode_host_move(m) for m in pos.legal_moves()}
    assert len(dev) == 1  # exd5 is the only legal move


def test_antichess_running_out_of_pieces_wins(params):
    from fishnet_tpu.ops.search import MATE

    # white's lone pawn must capture (compulsion) and is then taken:
    # white runs out of pieces and WINS
    score = _spot_score(
        params, "8/8/8/8/2q5/3q4/2P5/8 w - - 0 1", "antichess", depth=3
    )
    assert score >= MATE - 10, score


def test_decode_uci_handles_king_promotion():
    from fishnet_tpu.engine.tpu import _decode_uci
    from fishnet_tpu.ops import tables as T

    # e7e8k (antichess): promo code 5 must decode, not IndexError
    m = 52 | (60 << 6) | (T.PROMO_K << 12)
    assert _decode_uci(m) == "e7e8k"


@pytest.mark.parametrize("refill", [False, True])
def test_variant_chunk_through_engine(variant, refill):
    """Every variant program through both dispatch paths: chunk-serial
    (`_analyse_single`) and the LaneScheduler."""
    from fishnet_tpu.engine.tpu import TpuEngine

    engine = TpuEngine(max_depth=2, refill=refill)
    work = AnalysisWork(
        id="varjob01",
        nodes=NodeLimit(sf16=500_000, classical=500_000),
        timeout_s=30.0,
        depth=2,
    )
    start_fen = position_class(variant).starting_fen()
    positions = [
        WorkPosition(
            work=work, position_index=i, url=None, skip=False,
            root_fen=start_fen, moves=[],
        )
        for i in range(2)
    ]
    chunk = Chunk(
        work=work, deadline=time.monotonic() + 300, variant=variant,
        flavor=EngineFlavor.TPU, positions=positions,
    )
    responses = asyncio.run(engine.go_multiple(chunk))
    assert len(responses) == 2
    for res in responses:
        assert res.depth == 2
        assert res.nodes > 0
        assert res.best_move is not None
        # the engine's move must be legal under the variant rules
        pos = from_fen(start_fen, variant)
        pos.push(pos.parse_uci(res.best_move))
