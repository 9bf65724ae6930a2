"""The king-relative wide net (an imported `StockfishNet`) on the
incremental path: the accumulator and PSQT pair that rides down the search
stack equals a full refresh of the board it stands for, `forward` from it
equals the plain reference, and a `TpuEngine` search on it is the search
of the full-refresh path — on one device and on the 8-device mesh.

Seeded weights at L1 32 (the 3,072-wide table is 277 MB: the benchmark's
cell runs it on the chip, tests/benchmark/test_bench_halfka.py counts it).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.chess.position import Position
from fishnet_tpu.models import nnue, nnue_import as ni
from fishnet_tpu.ops import search as S
from fishnet_tpu.ops.board import from_position, make_move, move_piece_changes

from test_device_board import encode_host_move

L1 = 32
START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
KIWIPETE = "r3k2r/p1ppqpb1/bn2pnp1/3PN3/1p2P3/2N2Q1p/PPPBBPPP/R3K2R w KQkq - 0 1"
PLAYOUTS = [
    # (fen, seed): both castlings on both sides, an en-passant capture on
    # offer, pawns about to promote, kings with a pawn each that cross the board
    (START, 1), (START, 2), (KIWIPETE, 3), (KIWIPETE, 4), (KIWIPETE, 5),
    ("rnbqkbnr/ppp1p1pp/8/3pPp2/8/8/PPPP1PPP/RNBQKBNR w KQkq f6 0 3", 6),
    ("4k3/1P4P1/8/8/8/8/1p4p1/4K3 w - - 0 1", 7),
    ("8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1", 8),
    ("8/p7/3k4/8/8/4K3/7P/8 w - - 0 1", 9),
]
# float32 rounding of the sums: a row holds about 33 terms of 0.1-0.5, one
# add rounds by at most 2^-24 of the partial sum (under 4: 2.4e-7), and a
# playout carries a row through up to 40 moves of 8 adds without a refresh:
# 320 x 2.4e-7 = 7.7e-5 if every rounding went one way
ACC_ATOL = 1e-4


@pytest.fixture(scope="module")
def net():
    rng = np.random.Generator(np.random.PCG64(35))

    def normal(shape, scale, shift=0.0):
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale)
                + np.float32(shift))

    return ni.StockfishNet(
        ft_w=normal((ni.NUM_FEATURES, L1), 0.08), ft_b=normal((L1,), 0.1, 0.5),
        psqt_w=normal((ni.NUM_FEATURES, ni.NUM_PSQT_BUCKETS), 0.3),
        fc0_w=normal((ni.NUM_STACKS, ni.FC0_OUT, L1), 0.7 / np.sqrt(L1)),
        fc0_b=normal((ni.NUM_STACKS, ni.FC0_OUT), 0.1),
        fc1_w=normal((ni.NUM_STACKS, ni.FC1_OUT, ni.FC1_IN), 0.18),
        fc1_b=normal((ni.NUM_STACKS, ni.FC1_OUT), 0.1),
        fc2_w=normal((ni.NUM_STACKS, 1, ni.FC1_OUT), 0.05),
        fc2_b=normal((ni.NUM_STACKS, 1), 0.02),
    )


@pytest.fixture(scope="module")
def kernels(net):
    dev = net.as_device()

    def push(pair, board, move):
        """What one step of the search does to a lane's pair."""
        child, stale = ni.acc_update_pair(
            dev, pair, board.board, *move_piece_changes(board, move))
        after = make_move(board, move)
        fresh = ni.acc_refresh_pair(dev, after.board)
        return jnp.where(stale[:, None], fresh, child), stale, after

    return {
        "push": jax.jit(push),
        "refresh": jax.jit(lambda board64: ni.acc_refresh_pair(dev, board64)),
        "forward": jax.jit(lambda pair, stm, board64: ni.forward_sf_from_acc(
            dev, pair, stm, nnue.output_bucket(board64))),
    }


def classify(pos: Position, move) -> set:
    """What of the list in the module's docstring this move exercises."""
    kinds = set()
    board = np.asarray(from_position(pos).board)
    code, target = int(board[move.from_sq]), int(board[move.to_sq])
    is_king = (code - 1) % 6 == 5
    if pos.is_castling_move(move):
        kinds.add("castle_k" if move.to_sq > move.from_sq else "castle_q")
    elif target:
        kinds.add("capture")
    if (code - 1) % 6 == 0 and not target and (move.from_sq & 7) != (move.to_sq & 7):
        kinds.add("en_passant")
    if move.promotion is not None:
        kinds.add("promotion")
    if is_king and not pos.is_castling_move(move):
        kinds.add("king")
        if ((move.from_sq & 7) > 3) != ((move.to_sq & 7) > 3):
            kinds.add("king_across_mirror")
        if (move.from_sq >> 3) != (move.to_sq >> 3):
            kinds.add("king_across_buckets")
    return kinds


def playout(fen: str, seed: int, plies: int = 40):
    """Seeded legal moves; a castling, en-passant capture or promotion is
    taken most of the times it is on offer, a king's move a third of them:
    → [(position before, move, its kinds)]."""
    rng = random.Random(seed)
    pos = Position.from_fen(fen)
    out = []
    for _ in range(plies):
        legal = pos.legal_moves()
        if not legal or pos.outcome() is not None:
            break
        special = [m for m in legal if classify(pos, m)
                   & {"castle_k", "castle_q", "en_passant", "promotion"}]
        kings = [m for m in legal if "king" in classify(pos, m)]
        if special and rng.random() < 0.7:
            move = rng.choice(special)
        elif kings and rng.random() < 0.33:
            move = rng.choice(kings)
        else:
            move = rng.choice(legal)
        out.append((pos, move, classify(pos, move)))
        pos = pos.push(move)
    return out


@pytest.mark.parametrize("fen,seed", PLAYOUTS)
def test_pair_down_a_playout_equals_a_refresh_of_every_board(net, kernels, fen, seed):
    """(i) and (ii): after every move both rows equal a refresh of the
    resulting board to float32 rounding of the sum, a row is rebuilt
    exactly when a king of its colour moved, and `forward` from the
    carried pair is the plain reference's eval of the board."""
    moves = playout(fen, seed)
    assert len(moves) >= 12
    board = from_position(moves[0][0])
    pair = kernels["refresh"](board.board)
    for pos, move, kinds in moves:
        pair, stale, board = kernels["push"](pair, board, jnp.int32(encode_host_move(move)))
        after = pos.push(move)
        want_board = np.asarray(from_position(after).board)
        assert np.array_equal(np.asarray(board.board), want_board)
        mover = int(from_position(pos).stm)
        king_moved = bool(kinds & {"king", "castle_k", "castle_q"})
        assert np.asarray(stale).tolist() == [
            king_moved and mover == 0, king_moved and mover == 1], (move.uci(), kinds)
        fresh = np.asarray(kernels["refresh"](board.board))
        np.testing.assert_allclose(np.asarray(pair), fresh, rtol=0, atol=ACC_ATOL,
                                   err_msg=f"{move.uci()} in {pos.to_fen()}")
        got = float(kernels["forward"](pair, board.stm, board.board))
        want = ni.evaluate_sf_reference(net, want_board, int(board.stm))
        # the reference sums in float64; 600 cp a unit of output
        assert got == pytest.approx(want, abs=0.05), (move.uci(), pos.to_fen())


def test_the_playouts_meet_every_kind_of_move():
    seen = set()
    for fen, seed in PLAYOUTS:
        for _pos, _move, kinds in playout(fen, seed):
            seen |= kinds
    assert seen >= {"capture", "en_passant", "promotion", "castle_k", "castle_q",
                    "king", "king_across_mirror", "king_across_buckets"}, seen


def test_a_null_move_leaves_the_pair_bit_for_bit(net, kernels):
    """The search zeroes a null move's slots (ops/search.py): no row is
    added, nothing is stale."""
    dev = net.as_device()
    board = from_position(Position.from_fen(KIWIPETE))
    pair = kernels["refresh"](board.board)
    zero = jnp.zeros(4, jnp.int32)
    child, stale = jax.jit(lambda p, b, sq: ni.acc_update_pair(dev, p, b, zero, sq, zero))(
        pair, board.board, jnp.array([4, 6, 6, 5], jnp.int32))
    assert np.array_equal(np.asarray(child), np.asarray(pair))
    assert not np.asarray(stale).any()


@pytest.mark.parametrize("n_stale", [0, 1, 5, 16])
def test_stale_rows_are_rebuilt_across_lanes_in_as_many_passes_as_it_takes(net, n_stale):
    """`_refresh_stale` at 16 lanes holds two slots a pass: from none stale
    (no pass, nothing gathered) to every other pair."""
    dev = net.as_device()
    B, R = 16, 10
    fens = [KIWIPETE, START, PLAYOUTS[6][0], PLAYOUTS[7][0]]
    boards = jnp.stack([from_position(Position.from_fen(fens[i % 4])).board
                        for i in range(B)])
    rng = np.random.default_rng(n_stale)
    stale = np.zeros(2 * B, bool)
    stale[rng.choice(2 * B, n_stale, replace=False)] = True
    stale = stale.reshape(B, 2)
    row0 = 2 * rng.integers(0, R // 2, B).astype(np.int32)
    acc0 = rng.standard_normal((B, R, L1 + 8)).astype(np.float32)
    acc, rows = jax.jit(
        lambda a, s, r, b: S._refresh_stale(dev, a, s, r, b, "standard")
    )(jnp.asarray(acc0), jnp.asarray(stale), jnp.asarray(row0), boards)
    acc = np.asarray(acc)
    fresh = np.asarray(jax.vmap(lambda b: ni.acc_refresh_pair(dev, b))(boards))
    touched = np.zeros((B, R), bool)
    for lane, persp in zip(*np.nonzero(stale)):
        touched[lane, row0[lane] + persp] = True
        # the same 33 terms summed in another order
        np.testing.assert_allclose(acc[lane, row0[lane] + persp],
                                   fresh[lane, persp], rtol=0, atol=1e-5)
    assert np.array_equal(acc[~touched], acc0[~touched])  # bit for bit
    passes = -(-n_stale // 2)
    assert int(rows) == passes * 2 * ni.REFRESH_ROWS


# ----------------------------------------------------- through TpuEngine


def _chunk(n_positions=5):
    from test_refill import analysis_work, make_chunk

    # a game in which both sides castle and kings walk: the positions'
    # searches move kings at most plies
    game = ["e2e4", "e7e5", "g1f3", "g8f6", "f1c4", "f8c5", "e1g1", "e8g8",
            "g1h1", "g8h8"]
    chunk = make_chunk(analysis_work(depth=3), n_positions=n_positions,
                       moves=game[4:4 + n_positions])
    # from the fifth ply on: the castlings are one or two moves away
    for wp in chunk.positions:
        wp.moves = game[:4] + wp.moves
    return chunk


def _flat(resps):
    return [(r.position_index, r.best_move, r.depth, r.nodes,
             r.scores.matrix, r.pvs.matrix) for r in resps]


def test_engine_search_on_the_incremental_path_is_the_full_refresh_search(net, monkeypatch):
    """(iii): scores, PVs and node counts, position by position; and the
    counters say which path ran."""
    from test_refill import make_refill_engine, run

    inc = make_refill_engine(params=net.as_device())
    got = run(inc, _chunk())
    occ = inc.occupancy_totals
    assert occ["acc_updates"] > 0 and occ["movegen_nodes"] > 0
    # a refresh is the exception: well under one a node, and never both
    # perspectives of one push
    assert 0 < occ["acc_refreshes"] < 0.5 * occ["movegen_nodes"]
    assert occ["acc_refreshes"] < occ["acc_updates"]
    assert occ["acc_rows"] >= 8 * occ["lane_steps"]

    # the same search with every step refreshing from the board: the
    # programs are traced anew under the patched scheme, and once more
    # after it for whoever runs next in this process
    monkeypatch.setattr(nnue, "acc_scheme", lambda params, variant="standard": None)
    jax.clear_caches()
    try:
        full = make_refill_engine(params=net.as_device())
        want = run(full, _chunk())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert full.occupancy_totals["acc_updates"] == 0
    assert full.occupancy_totals["acc_refreshes"] == 2 * full.occupancy_totals["lane_steps"]
    assert _flat(got) == _flat(want)
    assert all(r.nodes > 0 and r.best_move for r in got)


def test_segment_spans_and_the_trace_report_carry_the_three_counts(net):
    """`segment` spans carry the counters interval by interval, and
    `tools/trace_report.py` prints their sums under its segment line."""
    from fishnet_tpu.obs import trace as obs_trace
    from test_refill import make_refill_engine, run
    from tools import trace_report

    engine = make_refill_engine(params=net.as_device())
    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=20_000))
    try:
        run(engine, _chunk(3))
        events = rec.snapshot()
    finally:
        obs_trace.uninstall()
    report = trace_report.summarize(events)
    counts = report["segments"]["counts"]
    for name in S.ACC_COUNTERS:
        assert counts[name] == engine.occupancy_totals[name] > 0
    text = trace_report.render_text(report)
    assert all(f"{name} {counts[name]}" in text for name in S.ACC_COUNTERS)


def test_mesh_runs_a_chunk_with_these_params(net):
    """(v): the 8-device CPU mesh, the rules of parallel/partition.py for
    the imported net's tensors; uncoupled lanes, so the searches are the
    single device's."""
    from test_mesh_refill import make_mesh_engine
    from test_refill import make_refill_engine, run

    mesh = make_mesh_engine(refill=True, params=net.as_device())
    got = run(mesh, _chunk(4))
    one = make_refill_engine(params=net.as_device())
    want = run(one, _chunk(4))
    assert _flat(got) == _flat(want)
    for name in S.ACC_COUNTERS[:2]:  # a mesh sums its shards'
        assert mesh.occupancy_totals[name] == one.occupancy_totals[name]


def test_partition_rules_cover_the_imported_net():
    from fishnet_tpu.parallel import partition
    from jax.sharding import PartitionSpec as P

    counts = partition.validate_rules()
    assert all(n > 0 for n in counts.values())
    assert partition.search_param_spec() == P()
    specs = partition.match_partition_rules(
        partition.imported_param_proto(), partition.PARAM_RULES)
    assert set(specs) == set(ni._ARRAY_FIELDS)


def test_cast_params_takes_either_type(net):
    small = nnue.init_params(jax.random.PRNGKey(0), l1=8, feature_set="board768")
    for params in (small, net.as_device()):
        cast = nnue.cast_params(params, jnp.bfloat16)
        assert type(cast) is type(params)
        assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(cast))
    assert nnue.acc_scheme(small) == "board768"
    assert nnue.acc_scheme(net) == "halfka" and nnue.acc_scheme(net, "atomic") is None
    kar = nnue.init_params(jax.random.PRNGKey(0), l1=8)
    assert nnue.acc_scheme(kar) is None
