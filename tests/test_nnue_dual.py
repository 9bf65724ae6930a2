"""Both nets of a Stockfish deployment (`nnue_import.DualNet`) on the
incremental path: the program's eval takes Stockfish's choice between the
small and the big net exactly as `evaluate_dual_reference` does, on every
branch; the joined row that rides down the search stack equals a refresh
of both nets from the board; the eval counters account for every node
expansion; a `TpuEngine` search on the pair is the full-refresh search, on
one device and on the 8-device mesh; and the pair loads from two `.nnue`
files on the normal path, with a cache identity of its own.

Seeded weights at small widths (big L1 64, small L1 16): the published
22,528 x 3,072 and x 128 nets are the benchmark's `halfka_dual` cell,
which tests/benchmark/test_bench_halfka_dual.py counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.chess.position import Position
from fishnet_tpu.models import nnue, nnue_import as ni
from fishnet_tpu.ops import search as S
from fishnet_tpu.ops.board import from_position, make_move, move_piece_changes

from test_device_board import encode_host_move
from test_nnue_halfka_incremental import ACC_ATOL, PLAYOUTS, playout

L1_BIG, L1_SMALL = 64, 16
START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
# white eight pawns up: Stockfish's material rule hands the position to
# the small net, whose value lies near its re-evaluation threshold there
PAWNS_UP = "r3k3/8/8/8/8/8/PPPPPPPP/R3K3 w Q - 0 1"
# a piece's worth by feature kind (own P N B R Q, theirs, a king) in the
# net's units of 1/600: what a trained net's PSQT columns hold
WORTH = np.array((126, 781, 825, 1276, 2538, -126, -781, -825, -1276, -2538, 0),
                 np.float32) / 600.0


def draw_net(seed: int, l1: int, psqt_scale: float) -> ni.StockfishNet:
    rng = np.random.Generator(np.random.PCG64(seed))

    def normal(shape, scale, shift=0.0):
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale)
                + np.float32(shift))

    worth = np.tile(np.repeat(WORTH, 64), 32)[:, None]
    return ni.StockfishNet(
        ft_w=normal((ni.NUM_FEATURES, l1), 0.08), ft_b=normal((l1,), 0.1, 0.5),
        psqt_w=psqt_scale * worth * normal((ni.NUM_FEATURES, 8), 0.03, 1.0)
        + normal((ni.NUM_FEATURES, 8), 0.01),
        fc0_w=normal((8, ni.FC0_OUT, l1), 0.7 / np.sqrt(l1)),
        fc0_b=normal((8, ni.FC0_OUT), 0.1),
        fc1_w=normal((8, ni.FC1_OUT, ni.FC1_IN), 1.0 / np.sqrt(ni.FC1_IN)),
        fc1_b=normal((8, ni.FC1_OUT), 0.1),
        fc2_w=normal((8, 1, ni.FC1_OUT), 0.3 / np.sqrt(ni.FC1_OUT)),
        fc2_b=normal((8, 1), 0.02),
    )


@pytest.fixture(scope="module")
def nets():
    # the small net's PSQT at a tenth of a piece's worth puts its value
    # on either side of 236 cp in the positions the material rule gives it
    return draw_net(38, L1_BIG, 1.0), draw_net(3800, L1_SMALL, 0.1)


@pytest.fixture(scope="module")
def dual(nets):
    return ni.DualNet.from_nets(*nets).as_device()


@pytest.fixture(scope="module")
def program_eval(dual):
    """The program's eval of a batch of boards: both nets' rows from the
    board, then the rule → (values, (n, 3) which branch)."""
    def one(board64, stm):
        pair = ni.acc_refresh_pair(dual, board64)
        return ni.forward_dual_from_acc(
            dual, pair, stm, nnue.output_bucket(board64), board64)

    return jax.jit(jax.vmap(one))


def branch(nets, board64, stm) -> int:
    """0 the small net's value kept, 1 handed back to the big net, 2 the
    big net chosen: from the reference's own pieces."""
    material = int(ni._MATERIAL_BY_CODE[board64].sum())
    if abs(material) <= ni.SMALLNET_MATERIAL:
        return 2
    v_small = int(ni.evaluate_sf_reference(nets[1], board64, stm))
    return 0 if abs(v_small) >= ni.SMALLNET_REEVAL else 1


def thinned_boards(n: int, seed: int):
    """Start positions with pieces taken off each side at its own rate:
    from balanced to a queen and more up; kings stay."""
    rng = np.random.default_rng(seed)
    start = np.asarray(from_position(Position.from_fen(START)).board)
    out = []
    for _ in range(n):
        board = start.copy()
        rates = rng.uniform(0.0, 0.7, 2)
        for sq in np.nonzero((board > 0) & (board % 6 != 0))[0]:
            if rng.random() < rates[int(board[sq] > 6)]:
                board[sq] = 0
        out.append(board)
    return np.stack(out).astype(np.int32), rng.integers(0, 2, n).astype(np.int32)


def test_material_is_stockfish_simple_eval():
    board = np.asarray(from_position(Position.from_fen(PAWNS_UP)).board)
    assert int(ni.material_imbalance(jnp.asarray(board))) == 8 * 208
    start = np.asarray(from_position(Position.from_fen(START)).board)
    assert int(ni.material_imbalance(jnp.asarray(start))) == 0
    no_black_queen = start.copy()
    no_black_queen[59] = 0
    assert int(ni.material_imbalance(jnp.asarray(no_black_queen))) == 2538


def test_program_takes_the_reference_branch_and_value(nets, program_eval):
    """Balanced boards go to the big net, lopsided ones to the small net,
    which keeps them where its value is 236 cp or more and hands them back
    otherwise; the integer value is the reference's on every board."""
    boards, stms = thinned_boards(240, 38)
    values, picks = map(np.asarray, program_eval(jnp.asarray(boards), jnp.asarray(stms)))
    seen = [0, 0, 0]
    for board, stm, value, pick in zip(boards, stms, values, picks):
        want = branch(nets, board, int(stm))
        seen[want] += 1
        assert pick.tolist() == [int(want == k) for k in range(3)]
        assert int(value) == ni.evaluate_dual_reference(*nets, board, int(stm))
    assert min(seen) >= 10, seen


@pytest.mark.parametrize("fen,seed", PLAYOUTS)
def test_both_nets_rows_down_a_playout_equal_a_refresh(nets, dual, fen, seed):
    """After every move of a line with captures, castlings, en passant,
    promotions and king moves, the joined row of each perspective equals
    a refresh of both nets from the board, and the eval from it is the
    reference's."""
    @jax.jit
    def push(pair, board, move):
        child, stale = ni.acc_update_pair(
            dual, pair, board.board, *move_piece_changes(board, move))
        after = make_move(board, move)
        fresh = ni.acc_refresh_pair(dual, after.board)
        return jnp.where(stale[:, None], fresh, child), after

    refresh = jax.jit(lambda b: ni.acc_refresh_pair(dual, b))
    forward = jax.jit(lambda pair, stm, b: ni.forward_dual_from_acc(
        dual, pair, stm, nnue.output_bucket(b), b)[0])
    moves = playout(fen, seed)
    board = from_position(moves[0][0])
    pair = refresh(board.board)
    width = L1_BIG + 8 + L1_SMALL + 8
    # both nets' columns, then zeros up to a multiple of 128
    assert pair.shape == (2, 128) and not np.asarray(pair)[:, width:].any()
    for pos, move, _kinds in moves:
        pair, board = push(pair, board, jnp.int32(encode_host_move(move)))
        fresh = np.asarray(refresh(board.board))
        np.testing.assert_allclose(np.asarray(pair), fresh, rtol=0, atol=ACC_ATOL,
                                   err_msg=f"{move.uci()} in {pos.to_fen()}")
        b64 = np.asarray(board.board)
        # each net's half of the row is that net's own refresh
        for net, cols in ((nets[0], slice(0, L1_BIG + 8)),
                          (nets[1], slice(L1_BIG + 8, width))):
            np.testing.assert_allclose(
                fresh[:, cols], np.asarray(ni.acc_refresh_pair(net.as_device(), b64)),
                rtol=0, atol=1e-5)
        assert int(forward(pair, board.stm, board.board)) == \
            ni.evaluate_dual_reference(*nets, b64, int(board.stm))


def test_stale_rows_are_rebuilt_at_the_joined_width(dual):
    """`_refresh_stale` rebuilds both nets' rows of a stale perspective."""
    B, R, W = 16, 6, dual.ft.shape[1]
    boards = jnp.stack([from_position(Position.from_fen(
        PAWNS_UP if i % 2 else START)).board for i in range(B)])
    stale = np.zeros((B, 2), bool)
    stale[[1, 4, 9], [0, 1, 1]] = True
    row0 = np.full(B, 2, np.int32)
    acc0 = np.random.default_rng(0).standard_normal((B, R, W)).astype(np.float32)
    acc, rows = jax.jit(lambda a, s, r, b: S._refresh_stale(dual, a, s, r, b, "standard"))(
        jnp.asarray(acc0), jnp.asarray(stale), jnp.asarray(row0), boards)
    fresh = np.asarray(jax.vmap(lambda b: ni.acc_refresh_pair(dual, b))(boards))
    acc = np.asarray(acc)
    for lane, persp in zip(*np.nonzero(stale)):
        np.testing.assert_allclose(acc[lane, 2 + persp], fresh[lane, persp],
                                   rtol=0, atol=1e-5)
    assert int(rows) == 2 * 2 * ni.REFRESH_ROWS  # 3 pairs, 2 slots a pass


def test_schemes_and_cast(nets, dual):
    assert nnue.acc_scheme(dual) == "dual" and nnue.acc_scheme(dual, "atomic") is None
    assert nnue.acc_scheme(nets[0]) == "halfka"
    cast = nnue.cast_params(dual, jnp.bfloat16)
    assert type(cast) is ni.DualNet
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(cast))
    # the full-refresh eval (atomic's path, nnue.evaluate) takes the rule too
    board = np.asarray(from_position(Position.from_fen(PAWNS_UP)).board)
    assert int(jax.jit(lambda b: nnue.evaluate(dual, b, jnp.int32(0)))(board)) \
        == ni.evaluate_dual_reference(*nets, board, 0)


# ----------------------------------------------------- through TpuEngine


def _chunks():
    from test_refill import analysis_work, make_chunk

    out = []
    for root, game in ((PAWNS_UP, ["a2a4", "a8a7", "a4a5", "a7a6"]),
                       (START, ["e2e4", "d7d5", "e4d5", "d8d5"])):
        chunk = make_chunk(analysis_work(depth=3), n_positions=4, moves=game)
        for wp in chunk.positions:
            wp.root_fen = root
        out.append(chunk)
    return out


def _flat(resps):
    return [(r.position_index, r.best_move, r.depth, r.nodes,
             r.scores.matrix, r.pvs.matrix) for r in resps]


def test_engine_counts_every_expansion_and_is_the_full_refresh_search(dual, monkeypatch):
    """The three eval counters sum to the node expansions, each branch is
    taken, and the search on the incremental path is the search of the
    full-refresh path, position by position."""
    from test_refill import make_refill_engine, run

    inc = make_refill_engine(params=dual)
    got = [run(inc, chunk) for chunk in _chunks()]
    occ = inc.occupancy_totals
    counts = [occ[name] for name in S.EVAL_COUNTERS]
    assert sum(counts) == occ["movegen_nodes"] > 0
    assert all(n > 0 for n in counts), dict(zip(S.EVAL_COUNTERS, counts))
    assert occ["acc_updates"] > 0 and occ["acc_refreshes"] > 0

    monkeypatch.setattr(nnue, "acc_scheme", lambda params, variant="standard": None)
    jax.clear_caches()
    try:
        full = make_refill_engine(params=dual)
        want = [run(full, chunk) for chunk in _chunks()]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert [_flat(r) for r in got] == [_flat(r) for r in want]
    # the full-refresh program chooses no differently, and counts nothing
    assert all(full.occupancy_totals[name] == 0 for name in S.EVAL_COUNTERS)


def test_other_schemes_count_no_eval(nets):
    """board768 and the one-net program leave the eval counters at 0."""
    from test_refill import make_refill_engine, run

    one = make_refill_engine(params=nets[0].as_device())
    run(one, _chunks()[0])
    assert one.occupancy_totals["movegen_nodes"] > 0
    assert all(one.occupancy_totals[name] == 0 for name in S.EVAL_COUNTERS)


def test_segment_spans_and_the_trace_report_carry_the_eval_counts(dual):
    from fishnet_tpu.obs import trace as obs_trace
    from test_refill import make_refill_engine, run
    from tools import trace_report

    engine = make_refill_engine(params=dual)
    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=20_000))
    try:
        run(engine, _chunks()[0])
        events = rec.snapshot()
    finally:
        obs_trace.uninstall()
    report = trace_report.summarize(events)
    counts = report["segments"]["counts"]
    for name in S.EVAL_COUNTERS:
        assert counts[name] == engine.occupancy_totals[name]
    text = trace_report.render_text(report)
    assert all(f"{name} {counts[name]}" in text for name in S.EVAL_COUNTERS)


def test_mesh_runs_a_chunk_with_both_nets(dual):
    """The 8-device CPU mesh under parallel/partition.py's rules for the
    pair's tensors: the single device's searches and counts."""
    from test_mesh_refill import make_mesh_engine
    from test_refill import make_refill_engine, run

    chunk = _chunks()[0]
    mesh = make_mesh_engine(refill=True, params=dual)
    got = run(mesh, chunk)
    one = make_refill_engine(params=dual)
    want = run(one, _chunks()[0])
    assert _flat(got) == _flat(want)
    for name in S.EVAL_COUNTERS:
        assert mesh.occupancy_totals[name] == one.occupancy_totals[name]


def test_partition_rules_cover_both_nets():
    from fishnet_tpu.parallel import partition
    from jax.sharding import PartitionSpec as P

    specs = partition.match_partition_rules(
        partition.dual_param_proto(), partition.PARAM_RULES)
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == 2 + 2 * 6 and all(s == P() for s in leaves)
    assert partition.search_param_spec() == P()


# ------------------------------------------------------ the normal path


def quantized(l1: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    nf = ni.NUM_FEATURES
    return {
        "ft_b": rng.integers(-500, 500, l1).astype(np.int16),
        "ft_w": rng.integers(-20, 21, (nf, l1)).astype(np.int16),
        "psqt": rng.integers(-2000, 2000, (nf, 8)).astype(np.int32),
        "fc0_b": rng.integers(-8000, 8000, (8, ni.FC0_OUT)).astype(np.int32),
        "fc0_w": rng.integers(-127, 128, (8, ni.FC0_OUT, l1)).astype(np.int8),
        "fc1_b": rng.integers(-8000, 8000, (8, ni.FC1_OUT)).astype(np.int32),
        "fc1_w": rng.integers(-127, 128, (8, ni.FC1_OUT, ni.FC1_IN)).astype(np.int8),
        "fc2_b": rng.integers(-8000, 8000, (8, 1)).astype(np.int32),
        "fc2_w": rng.integers(-127, 128, (8, 1, ni.FC1_OUT)).astype(np.int8),
    }


def test_engine_loads_both_files_and_the_cache_keys_on_both(tmp_path):
    """`TpuEngine(weights_path=<big>.nnue, weights_small_path=<small>.nnue)`
    holds the pair (what `--weights-small` and `tpu_weights_small` pass
    on); the analysis cache's identity changes with the small file alone."""
    from fishnet_tpu.cache.keys import engine_identity
    from fishnet_tpu.engine.supervisor import default_host_cmd
    from fishnet_tpu.engine.tpu import TpuEngine

    big, small, other = (tmp_path / f"{n}.nnue" for n in ("big", "small", "other"))
    ni.write_nnue(big, quantized(128, 1))
    ni.write_nnue(small, quantized(64, 2))
    ni.write_nnue(other, quantized(64, 3))
    engine = TpuEngine(weights_path=str(big), weights_small_path=str(small),
                       tt_size_log2=0)
    assert isinstance(engine.params, ni.DualNet)
    # 128 + 8 + 64 + 8 columns of the two nets, padded to a multiple of 128
    assert engine.params.ft.shape == (ni.NUM_FEATURES, 256)
    assert engine.params.big.l1 == 128 and engine.params.small.l1 == 64
    want = ni.DualNet.from_nets(ni.load_nnue(big), ni.load_nnue(small))
    assert np.array_equal(np.asarray(engine.params.ft), want.ft)
    ident = engine_identity(engine)
    engine.weights_small_path = str(other)
    assert engine_identity(engine) != ident
    # an engine of one net keeps the fingerprint it had before the pair
    engine.weights_small_path = None
    one_net = type("TpuEngine", (), {"weights_path": str(big),
                                     "max_depth": engine.max_depth})()
    assert engine_identity(engine) == engine_identity(one_net) != ident
    with pytest.raises(ValueError, match="small net"):
        TpuEngine(weights_small_path=str(small), tt_size_log2=0)
    cmd = default_host_cmd(weights=str(big), weights_small=str(small))
    assert cmd[cmd.index("--weights-small") + 1] == str(small)
