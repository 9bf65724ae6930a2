"""Property tests: device movegen/make_move vs the perft-validated host
rules library over random playouts.

The device generator is pseudo-legal with legality-checked castling — which
is exactly what the host's generate_pseudo_legal + _castling_moves produce,
so the move *sets* must match square-for-square.
"""
import functools
import random
import re

import jax
import numpy as np
import pytest

from fishnet_tpu.chess import Move, Position
from fishnet_tpu.chess.position import Chess960Position
from fishnet_tpu.ops import tables as T
from fishnet_tpu.ops.board import Board, from_position, in_check, make_move
from fishnet_tpu.ops.movegen import generate_moves

_PROMO_MAP = {1: T.PROMO_N, 2: T.PROMO_B, 3: T.PROMO_R, 4: T.PROMO_Q}


def encode_host_move(m: Move) -> int:
    promo = _PROMO_MAP[m.promotion] if m.promotion is not None else 0
    return m.from_sq | (m.to_sq << 6) | (promo << 12)


def host_pseudo_set(pos: Position):
    return {encode_host_move(m) for m in pos.generate_pseudo_legal()}


@pytest.fixture(scope="module")
def kernels():
    return jax.jit(generate_moves), jax.jit(make_move), jax.jit(in_check)


def device_move_set(gen, pos: Position):
    moves, count, _noisy = gen(from_position(pos))
    return set(np.asarray(moves)[: int(count)].tolist())


def boards_equal(b1: Board, b2: Board) -> bool:
    return (
        np.array_equal(np.asarray(b1.board), np.asarray(b2.board))
        and int(b1.stm) == int(b2.stm)
        and int(b1.ep) == int(b2.ep)
        and sorted(np.asarray(b1.castling).tolist())
        == sorted(np.asarray(b2.castling).tolist())
        and int(b1.halfmove) == int(b2.halfmove)
    )


def _playout_check(kernels, pos: Position, plies: int, rng: random.Random):
    gen, mk, chk = kernels
    for ply in range(plies):
        legal = pos.legal_moves()
        if not legal:
            break
        host_set = host_pseudo_set(pos)
        dev_set = device_move_set(gen, pos)
        assert dev_set == host_set, (
            f"move set mismatch at ply {ply}\nfen={pos.to_fen()}\n"
            f"host-only={sorted(host_set - dev_set)}\n"
            f"device-only={sorted(dev_set - host_set)}"
        )
        assert bool(chk(from_position(pos))) == pos.is_check()
        move = rng.choice(legal)
        child = pos.push(move)
        dev_child = mk(from_position(pos), encode_host_move(move))
        assert boards_equal(dev_child, from_position(child)), (
            f"make_move mismatch at ply {ply}: {move.uci()}\n"
            f"fen={pos.to_fen()} → {child.to_fen()}"
        )
        pos = child


def test_random_playouts_standard(kernels):
    rng = random.Random(42)
    for game in range(6):
        _playout_check(kernels, Position.initial(), 60, rng)


def test_playouts_tactical_fens(kernels):
    rng = random.Random(7)
    fens = [
        # kiwipete: castling + pins + promos nearby
        "r3k2r/p1ppqpb1/bn2pnp1/3PN3/1p2P3/2N2Q1p/PPPBBPPP/R3K2R w KQkq - 0 1",
        # CPW pos 4: promotions and underpromotions
        "r3k2r/Pppp1ppp/1b3nbN/nP6/BBP1P3/q4N2/Pp1P2PP/R2Q1RK1 w kq - 0 1",
        # en-passant rich
        "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
    ]
    for fen in fens:
        for _ in range(3):
            _playout_check(kernels, Position.from_fen(fen), 40, rng)


def test_playouts_chess960(kernels):
    rng = random.Random(3)
    fens = [
        "bqnb1rkr/pp3ppp/3ppn2/2p5/5P2/P2P4/NPP1P1PP/BQ1BNRKR w HFhf - 2 9",
        "b1q1rrkb/pppppppp/3nn3/8/P7/1PPP4/4PPPP/BQNNRKRB w GE - 1 9",
    ]
    for fen in fens:
        for _ in range(3):
            _playout_check(kernels, Chess960Position.from_fen(fen), 40, rng)


def test_castling_move_application(kernels):
    _, mk, _ = kernels
    pos = Position.from_fen("r3k2r/8/8/8/8/8/8/R3K2R w KQkq - 0 1")
    child = pos.push_uci("e1h1")
    dev = mk(from_position(pos), encode_host_move(pos.parse_uci("e1h1")))
    assert boards_equal(dev, from_position(child))
    child_q = pos.push_uci("e1a1")
    dev_q = mk(from_position(pos), encode_host_move(pos.parse_uci("e1a1")))
    assert boards_equal(dev_q, from_position(child_q))


def test_history_ordering_uses_correct_slot_both_colors():
    """Pins the _hist_idx_tables mirror (ops/movegen.py): a history bump
    on one specific quiet move's from|to slot must pull exactly THAT move
    to the front of the quiet tail, for white and for black. A misaligned
    static index table would credit a different candidate slot."""
    import jax.numpy as jnp

    cases = [
        # (fen, uci of a late quiet move expected to jump the quiet tail)
        ("rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1", "h2h3"),
        ("rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR b KQkq - 0 1", "h7h6"),
    ]
    gen = jax.jit(
        lambda b, h: generate_moves(b, killers=jnp.asarray([-1, -1]), hist=h)
    )
    for fen, uci in cases:
        pos = Position.from_fen(fen)
        mv = encode_host_move(pos.parse_uci(uci))
        # two INDEPENDENT buffers: jnp.asarray of a numpy array can be
        # zero-copy on CPU and dispatch is async, so mutating the base
        # buffer in place raced the base computation (seen under full
        # suite load: the base run read the already-bumped table)
        hist0 = np.zeros(4096, np.int32)
        hist1 = np.zeros(4096, np.int32)
        hist1[mv & 4095] = 1 << 16
        base_moves, count, noisy = gen(from_position(pos), jnp.asarray(hist0))
        moves, count, noisy = gen(from_position(pos), jnp.asarray(hist1))
        moves = np.asarray(moves)[: int(count)].tolist()
        quiet_tail = moves[int(noisy):]
        # castling (key 900) sorts before history-bumped quiets (911+),
        # so the bumped move must lead the quiet tail modulo castling
        assert mv in quiet_tail
        assert quiet_tail.index(mv) <= 1, (uci, quiet_tail[:4])
        # and without the bump the move must NOT already be first
        base_tail = np.asarray(base_moves)[: int(count)].tolist()[int(noisy):]
        assert base_tail.index(mv) > 1


def test_hist_index_tables_match_candidates():
    """Exhaustive pin of the _static_moves / _hist_idx_tables mirror: for
    every variant table shape and both colors, the static move table must
    equal the candidate VALUE, and the from|to index table `cand & 4095`,
    for EVERY candidate slot the traced assembly produces (castling slots
    excepted — they hold 0 in the tables; generate_moves takes their
    values from the board, and they are never history-adjusted because
    their ordering key is 900)."""
    from fishnet_tpu.chess.variants import from_fen as v_from_fen
    from fishnet_tpu.ops.movegen import (
        _candidate_space,
        _hist_idx_tables,
        _static_moves,
    )

    fens = {
        0: "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        1: "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR b KQkq - 0 1",
    }
    # the three distinct table shapes: standard (4 promos), antichess
    # (5 promos incl. king), crazyhouse (+ drop section)
    for variant in ("standard", "antichess", "crazyhouse"):
        space = jax.jit(lambda b: _candidate_space(b, variant))
        for color in (0, 1):
            pos = (
                Position.from_fen(fens[color]) if variant == "standard"
                else v_from_fen(fens[color], variant)
            )
            _, flat_moves, _, _ = space(from_position(pos))
            cands = np.asarray(flat_moves)
            table = np.asarray(_static_moves(variant)[color])
            assert cands.shape == table.shape, variant
            # locate the 2 castling slots: fixed offset before the drops
            n = cands.shape[0]
            drops = 5 * 64 if variant == "crazyhouse" else 0
            castle_lo = n - drops - 2
            mism = np.nonzero(cands != table)[0]
            allowed = {castle_lo, castle_lo + 1}
            assert set(mism.tolist()) <= allowed, (
                variant, color, mism[:10], cands[mism[:10]], table[mism[:10]]
            )
            assert np.array_equal(
                _hist_idx_tables(variant)[color], table & 4095)


def test_history_ordering_crazyhouse_drop_slot():
    """Same mirror pin for the drop section of the crazyhouse tables."""
    import jax.numpy as jnp

    from fishnet_tpu.chess.variants import from_fen as v_from_fen
    from fishnet_tpu.ops.movegen import DROP_FLAG

    fen = "rnb1kbnr/ppp1pppp/8/3p4/3P4/8/PPPqPPPP/RNBQKBNR[Nn] w KQkq - 0 4"
    pos = v_from_fen(fen, "crazyhouse")
    gen = jax.jit(
        lambda b, h: generate_moves(
            b, "crazyhouse", killers=jnp.asarray([-1, -1]), hist=h
        )
    )
    to_sq = 16  # a3: drop N@a3
    drop_mv = DROP_FLAG | (1 << 12) | (to_sq << 6) | to_sq
    hist = np.zeros(4096, np.int32)
    hist[((to_sq << 6) | to_sq) & 4095] = 1 << 16
    moves, count, noisy = gen(from_position(pos), jnp.asarray(hist))
    moves = np.asarray(moves)[: int(count)].tolist()
    assert drop_mv in moves
    # drops normally order at 1100 (after board quiets); the bumped drop
    # lands at 1011..1110 - 99 → ahead of every un-bumped drop
    drops = [m for m in moves if m & DROP_FLAG]
    assert drops[0] == drop_mv


# ------------------------------------------------------------------------
# The ordering sort sorts only the slots that can hold a move (PR 33):
# `_live_slots(variant)` drops the candidate slots that are False on every
# board the rules admit — table padding, pawn moves from a back rank, and,
# where a side has at most one king, every row of the king section but
# the king's square's, which `generate_moves` takes by a one-hot compare —
# and `generate_moves` packs and sorts what is left. Proofs: (a) the table
# holds every slot whose static factor is true, (b) no board reaches a
# slot outside it or a king row but its king's, (c) `generate_moves` is
# the parent's, bit for bit, (d) the pruned history tables are the full
# ones taken through the table, (e) the FEN validators refuse every root
# that would hold a move in a dropped slot.

# every statically compiled program (engine/tpu.py DEVICE_VARIANTS' values)
PROGRAMS = [
    "standard", "threeCheck", "kingOfTheHill", "racingKings", "atomic",
    "horde", "antichess", "crazyhouse",
]
ROYAL = [v for v in PROGRAMS if v != "antichess"]
LIVE_WIDTH = dict.fromkeys(PROGRAMS, 2046) | {
    "horde": 2076, "antichess": 2482, "crazyhouse": 2350}
_LANE = Board(0, 0, 0, 0, 0, 0)
_KING_LO = 64 * 8 * 7 + 64 * 8  # the king section, after sliders, knights
_PAWN_LO = _KING_LO + 64 * 8  # the pawn section: (64, [push1 push2 capL capR])
# what seeded playouts from the start rarely reach
_EXTRA_FENS = {
    "standard": [  # chess960 castling (the standard program serves it)
        ("bqnb1rkr/pp3ppp/3ppn2/2p5/5P2/P2P4/NPP1P1PP/BQ1BNRKR w HFhf - 2 9",
         "chess960"),
        ("b1q1rrkb/pppppppp/3nn3/8/P7/1PPP4/4PPPP/BQNNRKRB b GE - 1 9",
         "chess960"),
        ("r3k2r/Pppp1ppp/1b3nbN/nP6/BBP1P3/q4N2/Pp1P2PP/R2Q1RK1 w kq - 0 1",
         "standard"),
    ],
    "horde": [  # white pawns on rank 0 (they double-push from there)
        ("rnbqkbnr/pppppppp/8/8/8/8/8/PPPPPPPP w kq - 0 1", "horde"),
        ("rnbqkbnr/pppppppp/8/1PP2PP1/PPPPPPPP/PPPPPPPP/PPPPPPPP/PPPPPPPP"
         " b kq - 0 1", "horde"),
    ],
    "antichess": [  # promoted kings, promotions to king at hand
        ("8/1PK3P1/8/2K5/5k2/8/1pk3p1/8 w - - 0 1", "antichess"),
        ("8/1PK3P1/8/2K5/5k2/8/1pk3p1/8 b - - 0 1", "antichess"),
    ],
    "crazyhouse": [  # every piece type in both pockets, promotions at once
        ("6k1/PPPP4/8/8/8/8/pppp4/6K1[QRBNPqrbnp] w - - 0 1", "crazyhouse"),
        ("k7/4PPPP/8/8/8/8/4pppp/K7[NNPPnnpp] b - - 0 1", "crazyhouse"),
        ("r3k2r/1PP3P1/8/8/8/8/1pp3p1/R3K2R[QRqr] w KQkq - 0 1",
         "crazyhouse"),
        ("4k3/8/8/8/8/8/8/4K3[QRBNPqrbnp] w - - 0 1", "crazyhouse"),
        ("4k3/8/8/8/8/8/8/4K3[QRBNPqrbnp] b - - 0 1", "crazyhouse"),
    ],
}


@functools.lru_cache(maxsize=None)
def _playout_boards(variant: str) -> Board:
    """Batched numpy Board of every position of 8 seeded 60-ply playouts
    of `variant` (both colors to move) and of 24-ply playouts from
    _EXTRA_FENS."""
    from fishnet_tpu.chess.variants import from_fen as v_from_fen
    from fishnet_tpu.chess.variants import position_class
    from fishnet_tpu.ops.board import position_fields, stack_fields

    rng = random.Random(33)
    cls = position_class(variant)
    starts = [(cls.from_fen(cls.starting_fen()), 60)] * 8
    for fen, rules in _EXTRA_FENS.get(variant, []):
        pos = (Chess960Position.from_fen(fen) if rules == "chess960"
               else v_from_fen(fen, rules))
        starts += [(pos, 24)] * 3
    rows = []
    for pos, plies in starts:
        for _ in range(plies):
            rows.append(position_fields(pos))
            legal = pos.legal_moves()
            if not legal or pos.outcome() is not None:
                break
            pos = pos.push(rng.choice(legal))
    return stack_fields(rows)


def _static_factor(variant: str) -> np.ndarray:
    """The static factor of `valid`, slot by slot, written out from
    `_candidate_space`'s sections and the rules every root is held to:
    what `_live_slots` may not drop. A pawn of either color stands on
    ranks 1-6 (horde's white pawns on rank 0 too), pushes once unless it
    stands on its pre-promotion rank (that push is a promotion slot),
    pushes twice from its start rank; a royal king's section holds one
    live row, taken apart from the slots, so none of it is here."""
    from fishnet_tpu.ops.movegen import _CAPS

    ranks = np.arange(64) >> 3
    stands = [(ranks >= 1) & (ranks <= 6)] * 2  # per color: white, black
    starts = [ranks == 1, ranks == 6]
    if variant == "horde":
        stands[0] = stands[0] | (ranks == 0)
        starts[0] = starts[0] | (ranks == 0)
    quiet = [stands[0] & (ranks != 6), stands[1] & (ranks != 1)]
    secs = [
        np.asarray(T.RAYS) >= 0,  # rvalid
        np.asarray(T.KNIGHT_TARGETS) >= 0,  # tvalid
        (np.asarray(T.KING_TARGETS) >= 0) & (variant not in ROYAL),
        np.stack([quiet[0] | quiet[1],
                  (stands[0] & starts[0]) | (stands[1] & starts[1])]
                 + [((_CAPS[0][:, k] >= 0) & quiet[0])  # cvalid
                    | ((_CAPS[1][:, k] >= 0) & quiet[1]) for k in (0, 1)],
                 axis=1),
        np.ones((8, 3, 5 if variant == "antichess" else 4), bool),
        np.ones(2, bool),
    ]
    if variant == "crazyhouse":
        secs.append(np.stack(
            [(ranks != 0) & (ranks != 7)] + [np.ones(64, bool)] * 4))
    return np.concatenate([x.reshape(-1) for x in secs])


def parent_generate_moves(b, variant="standard", killers=None, hist=None):
    """`generate_moves` as the parent commit (0cff71d) had it: refinements,
    pack and sort over the WHOLE candidate space."""
    import jax.numpy as jnp

    from fishnet_tpu.ops.movegen import (
        _candidate_space,
        _hist_idx_tables,
        max_moves_for,
    )

    white, flat_moves, flat_valid, flat_keys = _candidate_space(b, variant)
    if hist is not None:
        hw, hb = _hist_idx_tables(variant)
        hval = jnp.where(white, hist[hw], hist[hb])
        hbonus = jnp.clip(hval >> 5, 0, 99)
        flat_keys = jnp.where(flat_keys == 1000, 1010 - hbonus, flat_keys)
        flat_keys = jnp.where(flat_keys == 1100, 1110 - hbonus, flat_keys)
    if killers is not None:
        is_k = (flat_moves == killers[0]) | (flat_moves == killers[1])
        flat_keys = jnp.where(is_k & (flat_keys >= 900), 901, flat_keys)
    cap = max_moves_for(variant)
    packed = jnp.where(
        flat_valid, (flat_keys << 16) | flat_moves,
        jnp.int32(jnp.iinfo(jnp.int32).max),
    )
    packed = jax.lax.sort(packed, dimension=0, is_stable=False)
    top = jax.lax.slice_in_dim(packed, 0, cap)
    moves = jnp.where(
        top != jnp.iinfo(jnp.int32).max, top & 0xFFFF, jnp.int32(-1)
    )
    count = jnp.minimum(jnp.sum(flat_valid), cap).astype(jnp.int32)
    noisy = jnp.minimum(
        jnp.sum(flat_valid & (flat_keys < 900)), cap
    ).astype(jnp.int32)
    return moves, count, noisy


@functools.lru_cache(maxsize=None)
def _gen_pair(variant: str, ordering: str):
    """(`generate_moves`, `parent_generate_moves`) vmapped over lanes and
    jitted once a program and ordering, so that tests of one shape share
    a compile. "killers_hist" takes (boards, killers, hist)."""
    if ordering == "plain":
        new = jax.vmap(lambda b: generate_moves(b, variant), in_axes=(_LANE,))
        old = jax.vmap(
            lambda b: parent_generate_moves(b, variant), in_axes=(_LANE,))
    else:
        new = jax.vmap(
            lambda b, k, h: generate_moves(b, variant, killers=k, hist=h),
            in_axes=(_LANE, 0, 0))
        old = jax.vmap(
            lambda b, k, h: parent_generate_moves(
                b, variant, killers=k, hist=h),
            in_axes=(_LANE, 0, 0))
    return jax.jit(new), jax.jit(old)


def _ordering_state(listed: np.ndarray):
    """Killers taken from each board's own list (a capture and late quiets
    among them, -1 where the list is short) and a dense history."""
    import jax.numpy as jnp

    n = listed.shape[0]
    nrng = np.random.default_rng(33)
    cols = nrng.integers(0, 40, (n, 2))
    killers = jnp.asarray(np.take_along_axis(listed, cols, axis=1))
    hist = jnp.asarray(
        nrng.integers(0, 1 << 13, (n, 4096)) * (nrng.random((n, 4096)) < 0.5),
        jnp.int32)
    return killers, hist


def _assert_parents(variant: str, ordering: str, boards: Board):
    """`generate_moves` on `boards` is the parent's, bit for bit on moves
    (the whole list, padding included), count and noisy; → the parent's."""
    new, old = _gen_pair(variant, ordering)
    args = ()
    if ordering == "killers_hist":
        args = _ordering_state(np.asarray(_gen_pair(variant, "plain")[1](boards)[0]))
    got = new(boards, *args)
    want = old(boards, *args)
    for name, g, w in zip(("moves", "count", "noisy"), got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), (variant, name)
    return want


@pytest.mark.parametrize("variant", PROGRAMS)
def test_live_slots_by_construction(variant):
    from fishnet_tpu.ops.movegen import _live_slots, _live_tables, _static_moves

    live = _live_slots(variant)
    factor = _static_factor(variant)
    assert factor.shape == _static_moves(variant)[0].shape
    assert live.dtype == np.int32
    assert np.all(np.diff(live) > 0), "sorted and unique"
    assert 0 <= live[0] and live[-1] < factor.shape[0]
    assert set(np.flatnonzero(factor).tolist()) <= set(live.tolist())
    # what is packed and sorted: the slots, and a royal king's one row
    assert live.shape[0] + 8 * (variant in ROYAL) == LIVE_WIDTH[variant]
    assert _live_tables(variant).width == LIVE_WIDTH[variant]


@pytest.mark.parametrize("variant", PROGRAMS)
def test_no_board_reaches_a_slot_outside_live_slots(variant):
    from fishnet_tpu.ops.movegen import _candidate_space, _live_slots

    boards = _playout_boards(variant)
    space = jax.jit(jax.vmap(
        lambda b: _candidate_space(b, variant)[2], in_axes=(_LANE,)))
    valid = np.asarray(space(boards))
    seen = valid.any(axis=0)
    assert boards.board.shape[0] >= 300
    assert {0, 1} == set(np.asarray(boards.stm).tolist())
    dead = np.ones(seen.shape[0], bool)
    dead[_live_slots(variant)] = False
    if variant in ROYAL:
        # of the king section, only the row of the mover's king holds a move
        rows = valid[:, _KING_LO:_PAWN_LO].reshape(-1, 64, 8).any(axis=2)
        stm = np.asarray(boards.stm)[:, None]
        at_king = np.asarray(boards.board) == np.where(stm == 0, T.W_KING, T.B_KING)
        assert at_king.sum(axis=1).max() == 1
        assert not np.any(rows & ~at_king)
        assert rows.any()
        dead[_KING_LO:_PAWN_LO] = False
    assert not np.any(seen & dead), np.flatnonzero(seen & dead)[:10]
    # the playouts do exercise the space: most live slots held a move
    assert seen.sum() > 0.3 * _live_slots(variant).shape[0]


@pytest.mark.parametrize("ordering", ["plain", "killers_hist"])
@pytest.mark.parametrize("variant", PROGRAMS)
def test_generate_moves_is_the_parents(variant, ordering):
    """Bit for bit on moves (the whole list, padding included), count and
    noisy, both colors, with and without the quiet-ordering state."""
    want = _assert_parents(variant, ordering, _playout_boards(variant))
    assert int(np.asarray(want[1]).max()) > 30


def _lane_rows(boards, stm) -> Board:
    """Batched numpy Board of (n, 64) piece arrays, `stm` to move, no
    castling rights, no en-passant square, empty side-state."""
    from fishnet_tpu.ops.board import EXTRA_W

    n = boards.shape[0]
    return Board(
        boards.astype(np.int32), np.full(n, stm, np.int32),
        np.full(n, -1, np.int32), np.full((n, 4), -1, np.int32),
        np.zeros(n, np.int32), np.zeros((n, EXTRA_W), np.int32))


@pytest.mark.parametrize("color", [0, 1], ids=["white", "black"])
@pytest.mark.parametrize("variant", ["standard", "atomic"])
def test_king_row_is_the_whole_king_section(variant, color):
    """The mover's king alone on each of the 64 squares, then ringed by
    enemy queens on every other target (captures beside quiet moves): the
    one row taken by the king's square lists what the whole section
    listed, with and without the quiet-ordering state (the row's history
    lookup)."""
    king, queen = ((T.W_KING, T.B_QUEEN), (T.B_KING, T.W_QUEEN))[color]
    targets = np.asarray(T.KING_TARGETS)
    rows = np.zeros((128, 64), np.int32)
    for sq in range(64):
        rows[2 * sq, sq] = rows[2 * sq + 1, sq] = king
        ring = targets[sq][::2]
        rows[2 * sq + 1, ring[ring >= 0]] = queen
    boards = _lane_rows(rows, color)
    want = _assert_parents(variant, "plain", boards)
    _assert_parents(variant, "killers_hist", boards)
    # a lone king lists each of its targets
    assert np.array_equal(np.asarray(want[1])[0::2], (targets >= 0).sum(axis=1))


@pytest.mark.parametrize("variant", ROYAL)
def test_generate_moves_without_the_movers_king(variant):
    """The playouts with the mover's king lifted off the board (a search
    that captured it under king-capture pruning): the king's row is all
    invalid, and the list is still the parent's."""
    boards = _playout_boards(variant)
    stm = np.asarray(boards.stm)[:, None]
    board = np.asarray(boards.board)
    kingless = boards._replace(board=np.where(
        board == np.where(stm == 0, T.W_KING, T.B_KING), 0, board))
    from fishnet_tpu.ops.movegen import DROP_FLAG

    want = _assert_parents(variant, "plain", kingless)
    moves = np.asarray(want[0])
    from_sq = np.where(moves >= 0, moves & 63, 0)
    pieces = np.take_along_axis(board, from_sq, axis=1)
    on_board = (moves >= 0) & (moves < DROP_FLAG)  # a drop's from is its to
    assert not np.any(on_board & (pieces == np.where(stm == 0, T.W_KING, T.B_KING)))
    assert int(np.asarray(want[1]).max()) > 20


@pytest.mark.parametrize("fen,listed,absent", [
    # every white pawn on rank 0: single and double pushes from there
    ("rnbqkbnr/pppppppp/8/8/8/8/8/PPPPPPPP w kq - 0 1",
     ["a1a2", "a1a3", "h1h2", "h1h3"], []),
    # black knights on rank 1: captures from rank 0, blocked pushes
    ("rnbqkbnr/pppppppp/8/8/8/8/1n1n1n1n/PPPPPPPP w kq - 0 1",
     ["a1b2", "c1b2", "c1d2", "g1h2", "a1a2", "a1a3", "g1g3"], ["b1b2"]),
    # a double push from rank 0 blocked on its second square
    ("rnbqkbnr/pppppppp/8/8/8/n7/8/PPPPPPPP w kq - 0 1",
     ["a1a2", "b1b2", "b1b3"], ["a1a3"]),
])
def test_horde_pawns_on_rank_0(fen, listed, absent):
    """Horde keeps the pawn slots of rank 0: its white pawns stand there
    and push once, twice, or capture from it, as the host lists them."""
    from fishnet_tpu.chess.variants import from_fen as v_from_fen
    from fishnet_tpu.ops.board import position_fields, stack_fields

    pos = v_from_fen(fen, "horde")
    want = _assert_parents(
        "horde", "plain", stack_fields([position_fields(pos)] * 2))
    dev = set(np.asarray(want[0])[0, : int(want[1][0])].tolist())
    assert dev == host_pseudo_set(pos)
    enc = {u: encode_host_move(Move.parse_uci(u)) for u in listed + absent}
    assert {enc[u] for u in listed} <= dev
    assert not {enc[u] for u in absent} & dev


# a root the validators take, per program, with a1 and a8 empty; and what
# each case adds to it: a second king, a pawn on a back rank
_GATE_BASE = dict.fromkeys(PROGRAMS, "4k3/8/8/8/8/8/8/4K3 w - - 0 1") | {
    "horde": "4k3/8/8/8/8/8/4P3/8 w - - 0 1"}
_GATE_CASES = {
    "white_king": ("K", 28), "black_king": ("k", 36),
    "white_pawn_rank0": ("P", 0), "white_pawn_rank7": ("P", 56),
    "black_pawn_rank0": ("p", 0), "black_pawn_rank7": ("p", 56),
}


def _with_piece(fen: str, piece: str, sq: int) -> str:
    """`fen` with `piece` put on the empty square `sq`."""
    rows = fen.split()[0].split("/")
    rank, file = 7 - (sq >> 3), sq & 7
    cells = "".join("." * int(c) if c.isdigit() else c for c in rows[rank])
    assert cells[file] == "."
    cells = cells[:file] + piece + cells[file + 1:]
    rows[rank] = re.sub(r"\.+", lambda m: str(len(m.group())), cells)
    return " ".join(["/".join(rows)] + fen.split()[1:])


@pytest.mark.parametrize("case", list(_GATE_CASES))
@pytest.mark.parametrize("variant", PROGRAMS)
def test_the_validators_refuse_what_the_table_drops(variant, case):
    """Every root that would put a move in a slot the table drops is
    refused by the FEN validator of that program: a second king where the
    king is royal (one row is taken), a pawn on rank 0 or 7 (the pawn
    slots of those ranks are gone), but horde's white pawns on rank 0."""
    from fishnet_tpu.chess import InvalidFenError
    from fishnet_tpu.chess.variants import from_fen as v_from_fen
    from fishnet_tpu.ops.movegen import _live_tables

    v_from_fen(_GATE_BASE[variant], variant)  # the base is taken
    piece, sq = _GATE_CASES[case]
    fen = _with_piece(_GATE_BASE[variant], piece, sq)
    live = _live_tables(variant)
    if piece in "Kk":
        dropped = live.king_lo is not None  # one row of the section is taken
    else:
        dropped = _PAWN_LO + 4 * sq not in set(live.slots.tolist())  # push1
    try:
        v_from_fen(fen, variant)
        refused = False
    except InvalidFenError:
        refused = True
    assert refused or not dropped, fen
    # the rules this table rests on, and the two programs that differ
    assert dropped == (variant != "antichess" if piece in "Kk"
                       else not (variant == "horde" and sq == 0))
    if variant == "antichess" and piece in "Kk":
        assert not refused
    if variant == "horde" and case == "white_pawn_rank0":
        assert not refused


@pytest.mark.parametrize("variant", PROGRAMS)
def test_live_tables_are_the_full_tables_through_live_slots(variant):
    from fishnet_tpu.ops.movegen import (
        _KING_MOVES,
        _hist_idx_tables,
        _live_slots,
        _live_tables,
        _static_moves,
    )

    live = _live_tables(variant)
    assert np.array_equal(live.slots, _live_slots(variant))
    for c in (0, 1):
        assert np.array_equal(
            live.hist[c], _hist_idx_tables(variant)[c][live.slots])
        assert np.array_equal(
            live.moves[c], _static_moves(variant)[c][live.slots])
    # the two castling slots, whose move values come from the board
    assert live.slots[live.castle_at] == live.castle_lo
    assert live.slots[live.castle_at + 1] == live.castle_lo + 1
    assert _static_moves(variant)[0][live.castle_lo] == 0
    # a royal king's section: none of it among the slots, its row of
    # move values the full tables' (either color) at the king's square
    if variant in ROYAL:
        assert live.king_lo == _KING_LO
        assert not np.any((live.slots >= _KING_LO) & (live.slots < _PAWN_LO))
        for c in (0, 1):
            assert np.array_equal(
                _static_moves(variant)[c][_KING_LO:_PAWN_LO],
                _KING_MOVES.reshape(-1))
    else:
        assert live.king_lo is None
    assert live.width == live.slots.shape[0] + 8 * (variant in ROYAL)
