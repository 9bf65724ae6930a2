"""Transposition table: packing, hashing, probe/store, search integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import random

from fishnet_tpu.chess import Position
from fishnet_tpu.chess.variants import from_fen, position_class
from fishnet_tpu.models import nnue
from fishnet_tpu.ops import tt
from fishnet_tpu.ops.board import from_position, position_fields, stack_boards
from fishnet_tpu.ops.search import MATE, search_batch_jit


@pytest.fixture(scope="module")
def params():
    return nnue.init_params(
        jax.random.PRNGKey(0), l1=32, h1=8, h2=8, feature_set="board768"
    )


def test_meta_roundtrip():
    for score, depth, flag in ((0, 0, 0), (123, 7, 1), (-30000, 255, 2), (30000, 1, 0)):
        meta = int(tt.pack_meta(jnp.int32(score), jnp.int32(depth), jnp.int32(flag)))
        s, d, f = (int(x) for x in tt.unpack_meta(jnp.int32(meta)))
        assert (s, d, f) == (score, depth, flag)


def test_hash_distinguishes_positions():
    fens = [
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR b KQkq - 0 1",  # stm
        "rnbqkbnr/pppppppp/8/8/4P3/8/PPPP1PPP/RNBQKBNR w KQkq - 0 1",
        "rnbqkbnr/pppppppp/8/8/4P3/8/PPPP1PPP/RNBQKBNR w Qkq - 0 1",  # castling
        "rnbqkbnr/pp1ppppp/8/2p5/4P3/8/PPPP1PPP/RNBQKBNR w KQkq c6 0 2",
        "rnbqkbnr/pp1ppppp/8/2p5/4P3/8/PPPP1PPP/RNBQKBNR w KQkq - 0 2",  # ep
    ]
    hashes = set()
    for f in fens:
        b = from_position(Position.from_fen(f))
        h1, h2 = tt.hash_board(b.board, b.stm, b.ep, b.castling)
        hashes.add((int(h1), int(h2)))
    assert len(hashes) == len(fens)


def test_hash_ignores_halfmove():
    a = from_position(Position.from_fen("8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1"))
    b = from_position(Position.from_fen("8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 30 1"))
    assert tuple(map(int, tt.hash_board(a.board, a.stm, a.ep, a.castling))) == tuple(
        map(int, tt.hash_board(b.board, b.stm, b.ep, b.castling))
    )


# castling rights on both wings and pawns a step from promoting (the h
# file's promotes onto bit 31 of crazyhouse's high promoted word), so a
# short random playout loses some rights and keeps others, promotes,
# and in crazyhouse fills pockets
_NEAR_PROMOTION = "r3k2r/1P5P/8/8/8/8/1p5p/R3K2R w KQkq - 0 1"
_HOST_HASH_EXTRA_FENS = {
    "standard": [_NEAR_PROMOTION],
    "threeCheck": [_NEAR_PROMOTION + " +1+2"],
    "crazyhouse": [_NEAR_PROMOTION.replace(" w ", "[QRBNPqrbnp] w "),
                   "r3k2r/8/8/8/8/8/8/R3K2Q~[PPPPPPPPPPPPPPPPPPpp] b Qkq - 0 1"],
    "atomic": [_NEAR_PROMOTION],
    "kingOfTheHill": [_NEAR_PROMOTION],
}


def _host_hash_playouts(variant, n_games=6, plies=60):
    """Seeded legal playouts from the variant's start (double pawn
    pushes: en-passant squares) and from its extra FENs."""
    cls = position_class(variant)
    rng = random.Random(0x27 + tt._VARIANT_ID[variant])
    out = []
    for fen in [cls.starting_fen()] * n_games + _HOST_HASH_EXTRA_FENS.get(variant, []) * 3:
        pos = from_fen(fen, variant)
        for _ in range(plies):
            out.append(pos)
            moves = list(pos.legal_moves())
            if not moves or pos.outcome() is not None:
                break
            pos = pos.push(rng.choice(moves))
    return out


@pytest.mark.parametrize("variant", sorted(tt._VARIANT_ID))
def test_host_hash_is_hash_board(variant):
    """The engine hashes game histories on the host
    (engine/tpu.py::_history_arrays); the in-search repetition scan
    compares those keys with keys the device computes itself, so the two
    must agree bit for bit, in every variant's extras."""
    assert np.array_equal(tt.Z1_HOST, np.asarray(tt.Z1))
    assert np.array_equal(tt.Z2_HOST, np.asarray(tt.Z2))
    positions = _host_hash_playouts(variant)
    rows = [position_fields(p) for p in positions]
    assert all(isinstance(x, (np.ndarray, np.generic)) for b in rows for x in b)
    f = {k: np.stack([getattr(b, k) for b in rows]) for k in rows[0]._fields}
    # the playouts reach what the hash folds in
    assert (f["stm"] == 0).any() and (f["stm"] == 1).any()
    assert (f["ep"] >= 0).any() or variant == "racingKings"  # it has no pawns
    if getattr(positions[0], "has_castling", True) and variant != "horde":
        rights = (f["castling"] >= 0).sum(axis=1)
        assert (rights == 4).any() and (rights == 0).any()
        assert ((rights > 0) & (rights < 4)).any()
    if variant == "threeCheck":
        assert {0, 1, 2} <= set(f["extra"][:, :2].ravel().tolist())
    if variant == "crazyhouse":
        assert (f["extra"][:, :10] > 0).any(axis=0).all(), "a pocket slot never filled"
        assert (f["extra"][:, :10] > 16).any(), "no pocket beyond the hashed 16"
        assert (f["extra"][:, 10] != 0).any() and (f["extra"][:, 11] < 0).any()
    else:
        assert len({tuple(b.board) for b in rows}) > 100
    h1, h2 = tt.hash_boards_host(
        f["board"], f["stm"], f["ep"], f["castling"], f["extra"], variant)
    assert h1.dtype == h2.dtype == np.uint32 and h1.shape == (len(rows),)
    dev = stack_boards([from_position(p) for p in positions])
    d1, d2 = jax.jit(jax.vmap(
        lambda b, s, e, c, x: tt.hash_board(b, s, e, c, x, variant)
    ))(dev.board, dev.stm, dev.ep, dev.castling, dev.extra)
    assert np.array_equal(h1, np.asarray(d1))
    assert np.array_equal(h2, np.asarray(d2))
    # and unbatched, as ops/search.py calls it for a lane's root
    b = from_position(positions[-1])
    s1, s2 = tt.hash_board(b.board, b.stm, b.ep, b.castling, b.extra, variant)
    assert (int(s1), int(s2)) == (int(h1[-1]), int(h2[-1]))


def test_store_probe_roundtrip():
    t = tt.make_table(8)
    h1 = jnp.asarray([7, 300], jnp.uint32)
    h2 = jnp.asarray([11, 13], jnp.uint32)
    t = tt.store(
        t, h1, h2,
        score=jnp.asarray([150, -90], jnp.int32),
        depth=jnp.asarray([3, 2], jnp.int32),
        flag=jnp.asarray([tt.FLAG_EXACT, tt.FLAG_LOWER], jnp.int32),
        move=jnp.asarray([4242, 17], jnp.int32),
        mask=jnp.asarray([True, True]),
    )
    usable, score, move, omove = tt.probe(
        t, h1, h2,
        depth_left=jnp.asarray([3, 2], jnp.int32),
        alpha=jnp.asarray([-100, -100], jnp.int32),
        beta=jnp.asarray([200, -95], jnp.int32),
    )
    assert bool(usable[0]) and int(score[0]) == 150 and int(move[0]) == 4242
    # lower bound -90 >= beta -95 → cutoff usable
    assert bool(usable[1]) and int(score[1]) == -90
    # deeper requirement → miss, but ordering move still available
    usable2, _, _, omove2 = tt.probe(
        t, h1, h2,
        depth_left=jnp.asarray([4, 3], jnp.int32),
        alpha=jnp.asarray([-100, -100], jnp.int32),
        beta=jnp.asarray([200, -95], jnp.int32),
    )
    assert not bool(usable2[0]) and int(omove2[0]) == 4242
    # wrong verification key reads as a miss (torn-write defence)
    usable3, _, _, om3 = tt.probe(
        t, h1, h2 + jnp.uint32(1),
        depth_left=jnp.asarray([0, 0], jnp.int32),
        alpha=jnp.asarray([-100, -100], jnp.int32),
        beta=jnp.asarray([200, 200], jnp.int32),
    )
    assert not bool(usable3[0]) and int(om3[0]) == -1


def _store1(t, depth, score=100, move=42, gen=None, prefer_deep=False,
            h1=5, h2=9):
    """Single-slot store helper for the replacement-policy tests."""
    return tt.store(
        t, jnp.asarray([h1], jnp.uint32), jnp.asarray([h2], jnp.uint32),
        score=jnp.asarray([score], jnp.int32),
        depth=jnp.asarray([depth], jnp.int32),
        flag=jnp.asarray([tt.FLAG_EXACT], jnp.int32),
        move=jnp.asarray([move], jnp.int32),
        mask=jnp.asarray([True]),
        prefer_deep=prefer_deep, gen=gen,
    )


def _row(t, h1=5):
    return np.asarray(t.data[h1 & (t.size - 1)])


def test_prefer_deep_keeps_same_generation_deeper_entry():
    """Helper-lane store policy: within one generation a shallower store
    must not evict a deeper entry (the Lazy-SMP helpers' flood of
    low-depth writes would otherwise wash out the primary's deep path)."""
    t = _store1(tt.make_table(8), depth=5, move=111, gen=3, prefer_deep=True)
    deep = _row(t)
    # shallower same-generation store: dropped
    t2 = _store1(t, depth=2, score=-7, move=222, gen=3, prefer_deep=True)
    np.testing.assert_array_equal(_row(t2), deep)
    # equal-depth same-generation store: replaces (only STRICTLY deeper
    # entries are protected — newer information at the same depth wins)
    t3 = _store1(t, depth=5, score=-40, move=333, gen=3, prefer_deep=True)
    assert int(_row(t3)[2]) == 333


def test_prefer_deep_other_generation_always_replaceable():
    """Entries from any other generation — older chunks' helper stores
    and gen-0 plain stores alike — lose their depth protection, so the
    policy self-heals across chunks without a sweep."""
    t = _store1(tt.make_table(8), depth=7, move=111, gen=3, prefer_deep=True)
    # next chunk's generation: a depth-1 store evicts the old depth-7
    t2 = _store1(t, depth=1, move=222, gen=4, prefer_deep=True)
    assert int(_row(t2)[2]) == 222 and int(_row(t2)[3]) == 4
    # plain always-replace store (gen word 0) ignores the policy entirely
    t3 = _store1(t, depth=0, move=333)
    assert int(_row(t3)[2]) == 333 and int(_row(t3)[3]) == 0
    # and a later prefer_deep store replaces the gen-0 row at any depth
    t4 = _store1(t3, depth=1, move=444, gen=5, prefer_deep=True)
    assert int(_row(t4)[2]) == 444


def test_prefer_deep_gen_none_matches_plain_store():
    """store(..., gen=None) writes bit-identical rows to the pre-helper
    plain store — the K=1 engine path must stay byte-for-byte the same."""
    plain = _store1(tt.make_table(8), depth=3)
    helper_off = _store1(tt.make_table(8), depth=3, prefer_deep=False,
                         gen=None)
    np.testing.assert_array_equal(
        np.asarray(plain.data), np.asarray(helper_off.data)
    )


def test_store_mask_and_mate_filter():
    t = tt.make_table(8)
    t2 = tt.store(
        t,
        jnp.asarray([1, 2], jnp.uint32), jnp.asarray([1, 2], jnp.uint32),
        score=jnp.asarray([100, MATE - 3], jnp.int32),
        depth=jnp.asarray([1, 1], jnp.int32),
        flag=jnp.zeros(2, jnp.int32),
        move=jnp.zeros(2, jnp.int32),
        mask=jnp.asarray([False, True]),
    )
    # lane 0 masked out; lane 1 mate-range filtered: table unchanged
    assert (np.asarray(t2.meta) == np.asarray(t.meta)).all()


B = 16  # shared padded lane shape — one compile for the whole file


def search(params, fens, depth, tt_table, budget=200_000):
    boards = [from_position(Position.from_fen(f)) for f in fens]
    roots = stack_boards(boards + [boards[0]] * (B - len(boards)))
    out = search_batch_jit(
        params, roots, depth, budget, max_ply=4, tt=tt_table
    )
    return {
        k: (v if k == "tt"
            else np.asarray(v)[: len(fens)] if np.ndim(v)
            else np.asarray(v))
        for k, v in out.items()
    }


def test_search_with_tt_matches_plain(params):
    """Same scores with and without the table on these pinned inputs
    (exact-depth probes keep cutoff values true same-depth bounds; see
    ops/tt.py probe for the pruning-era determinism caveat). Node counts
    may grow a LITTLE with the table since round 4: a bound cutoff
    shifts alpha, which flips LMR re-search decisions (reduced score
    vs alpha), occasionally re-searching more than the cutoff saved —
    bounded here; the real cross-lane savings are asserted by
    test_tt_shares_work_across_game_plies."""
    fens = [
        "6k1/5ppp/8/8/8/8/8/4R2K w - - 0 1",  # mate in 1
        "r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3",
        "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
        "6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1",
    ]
    plain = search(params, fens, 3, None)
    with_tt = search(params, fens, 3, tt.make_table(16))
    np.testing.assert_array_equal(plain["score"], with_tt["score"])
    assert with_tt["nodes"].sum() <= 1.3 * plain["nodes"].sum()
    assert int(with_tt["score"][0]) == MATE - 1


def test_tt_shares_work_across_game_plies(params):
    """The real fishnet batch shape: one game's consecutive plies as
    lanes. Neighboring plies' subtrees overlap heavily and the lanes run
    out of phase (different tree shapes), so cross-lane TT hits must cut
    total nodes versus the same batch without a table.

    (Identical lanes would NOT share: lockstep sync means every lane
    reaches a node before any lane has stored it.)"""
    game = ["e2e4", "e7e5", "g1f3", "b8c6", "f1c4", "g8f6"]
    pos = Position.initial()
    fens = [pos.to_fen()]
    for uci in game:
        pos = pos.push_uci(uci)
        fens.append(pos.to_fen())
    plain = search(params, fens, 3, None)
    shared = search(params, fens, 3, tt.make_table(18))
    np.testing.assert_array_equal(plain["score"], shared["score"])
    total_plain = int(plain["nodes"].sum())
    total_shared = int(shared["nodes"].sum())
    # shallow (d3) trees transpose little across plies — require soundness
    # and no pathological growth here; the big win is measured by
    # test_tt_persists_across_searches (ID-style reuse, ~2x fewer nodes).
    # A few % of slack: the stored TT move jumps the killer/history order,
    # which at fixed shallow depth occasionally costs a handful of nodes.
    assert total_shared <= total_plain * 1.05, (
        f"TT made the search worse: {total_shared} vs {total_plain}"
    )


def test_tt_persists_across_searches(params):
    """Carrying the table into a repeat search makes it much cheaper."""
    fen = "r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3"
    t = tt.make_table(18)
    first = search(params, [fen], 3, t)
    second = search(params, [fen], 3, first["tt"])
    assert int(second["score"][0]) == int(first["score"][0])
    assert int(second["nodes"][0]) < int(first["nodes"][0]) // 2


def test_tt_hit_cannot_override_fifty_move_draw(params):
    """A stored score (hash excludes the halfmove counter) must not
    override a forced fifty-move draw at probe time."""
    root_fen = "7k/8/8/8/8/8/8/K7 b - - 99 50"
    plain = search(params, [root_fen], 1, None)
    assert int(plain["score"][0]) == 0  # all children are halfmove-100 draws

    # poison the table: every child placement gets an EXACT deep entry
    t = tt.make_table(16)
    pos = Position.from_fen(root_fen)
    for mv in pos.legal_moves():
        child = from_position(pos.push(mv))
        h1, h2 = tt.hash_board(child.board, child.stm, child.ep, child.castling)
        t = tt.store(
            t, h1[None], h2[None],
            score=jnp.asarray([-500], jnp.int32),
            depth=jnp.asarray([5], jnp.int32),
            flag=jnp.asarray([tt.FLAG_EXACT], jnp.int32),
            move=jnp.asarray([-1], jnp.int32),
            mask=jnp.asarray([True]),
        )
    poisoned = search(params, [root_fen], 1, t)
    assert int(poisoned["score"][0]) == 0, "TT hit overrode the fifty-move draw"


def test_tt_stores_leaf_evals(params):
    """Static leaf evals (the most numerous node type) must land in the
    table as depth-0 EXACT entries despite folding into their parents
    within a single lockstep step."""
    out = search(
        params,
        ["r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3"],
        2, tt.make_table(18),
    )
    meta = np.asarray(out["tt"].meta)
    depths = [(int(m) >> 2) & 0xFF for m in meta[meta != 0]]
    assert depths, "empty table after a search"
    assert 0 in depths, f"no depth-0 (leaf) entries; histogram: {np.unique(depths)}"
