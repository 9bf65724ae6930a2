"""A `.nnue` file's nets in the integers the file holds
(`nnue_import.StockfishNetInt`, `DualNetInt`, nnue.acc_scheme "int"): the
program's int16 rows and int8 ladder equal the plain numpy int64
reference (`evaluate_int_reference`, `evaluate_dual_int_reference`)
exactly — after a refresh, down playouts of incremental updates, on every
branch of Stockfish's choice between the nets, and where a division
truncates a negative value; `load_nnue` keeps the file's integers and
`dequantize` gives the float32 net the file loaded as before; the counters
count, the mesh runs a chunk, and the normal path (`TpuEngine`, the engine
host) takes the files with no new setting.

Seeded nets at small widths (big L1 64, small L1 16), quantized from
tests/test_nnue_dual.py's draw, and once at the published widths (3,072
and 128) for a handful of positions.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fishnet_tpu.chess.position import Position
from fishnet_tpu.models import nnue, nnue_import as ni
from fishnet_tpu.ops import search as S
from fishnet_tpu.ops.board import from_position, make_move, move_piece_changes

from test_device_board import encode_host_move
from test_nnue_dual import (
    L1_BIG, L1_SMALL, PAWNS_UP, START, _chunks, _flat, draw_net, quantized,
    thinned_boards,
)
from test_nnue_halfka_incremental import PLAYOUTS, playout

HIDDEN = ni.FC0_OUT - 1  # 15


def quantize(net: ni.StockfishNet) -> ni.StockfishNetInt:
    """The integers a file would hold for a float net of this repo's
    convention: each array at its storage scale, rounded, clamped to its
    type, and fc1's input columns put into the file's order (the squared
    15 first)."""
    def q(a, scale, dtype):
        info = np.iinfo(dtype)
        return np.clip(np.rint(np.asarray(a, np.float64) * scale),
                       info.min, info.max).astype(dtype)

    fc1 = np.concatenate([net.fc1_w[..., HIDDEN:], net.fc1_w[..., :HIDDEN]], axis=-1)
    out_w = ni.NNUE2SCORE * ni.OUTPUT_SCALE
    return ni.StockfishNetInt(
        ft_w=q(net.ft_w, ni.QA, np.int16), ft_b=q(net.ft_b, ni.QA, np.int16),
        psqt_w=q(net.psqt_w, out_w, np.int32),
        fc0_w=q(net.fc0_w, ni.QB, np.int8), fc0_b=q(net.fc0_b, ni.QA * ni.QB, np.int32),
        fc1_w=q(fc1, ni.QB, np.int8), fc1_b=q(net.fc1_b, ni.QA * ni.QB, np.int32),
        fc2_w=q(net.fc2_w, out_w / ni.QA, np.int8), fc2_b=q(net.fc2_b, out_w, np.int32),
    )


@pytest.fixture(scope="module")
def nets():
    # test_nnue_dual's pair, in the integers of their files
    return (quantize(draw_net(38, L1_BIG, 1.0)),
            quantize(draw_net(3800, L1_SMALL, 0.1)))


@pytest.fixture(scope="module")
def dual(nets):
    return ni.DualNetInt.from_nets(*nets).as_device()


def reference(nets, board64, stm) -> int:
    return ni.evaluate_dual_int_reference(*nets, np.asarray(board64), int(stm))


def branch(nets, board64, stm) -> int:
    """0 the small net's value kept, 1 handed back to the big net, 2 the
    big net chosen: from the reference's own pieces."""
    material = int(ni._MATERIAL_BY_CODE[board64].sum())
    if abs(material) <= ni.SMALLNET_MATERIAL:
        return 2
    v_small = ni.evaluate_int_reference(nets[1], board64, int(stm))
    return 0 if abs(v_small) >= ni.SMALLNET_REEVAL else 1


def test_schemes_types_and_widths(nets, dual):
    assert nnue.acc_scheme(dual) == "int" and nnue.acc_scheme(nets[0]) == "int"
    assert nnue.acc_scheme(dual, "atomic") is None
    assert "int" in nnue.ROW_SCHEMES
    assert nnue.acc_dtype(dual) == jnp.int16
    assert nnue.arithmetic(dual) == "int" and nnue.arithmetic(
        ni.dequantize(nets[0])) == "float32"
    # both nets' int16 columns, padded to a multiple of 128; PSQT beside
    assert dual.ft.shape == (ni.NUM_FEATURES, 128) and dual.ft.dtype == jnp.int16
    assert dual.psqt.shape == (ni.NUM_FEATURES, 16) and dual.psqt.dtype == jnp.int32
    assert dual.big.fc0_w.dtype == jnp.int8 and dual.small.fc2_b.dtype == jnp.int32
    assert not np.asarray(dual.ft)[:, L1_BIG + L1_SMALL:].any()
    with pytest.raises(ValueError, match="integer net"):
        nnue.cast_params(dual, jnp.bfloat16)


def test_program_takes_the_reference_branch_and_value(nets, dual):
    """Every board's value is the reference's integer, and its branch of
    the rule the reference's; each branch is taken."""
    def one(board64, stm):
        pair = ni.acc_refresh_pair(dual, board64)
        return ni.forward_int(dual, pair, stm, nnue.output_bucket(board64), board64)

    boards, stms = thinned_boards(240, 38)
    values, picks = map(np.asarray, jax.jit(jax.vmap(one))(
        jnp.asarray(boards), jnp.asarray(stms)))
    seen = [0, 0, 0]
    for board, stm, value, pick in zip(boards, stms, values, picks):
        want = branch(nets, board, stm)
        seen[want] += 1
        assert pick.tolist() == [int(want == k) for k in range(3)]
        assert int(value) == reference(nets, board, stm)
    assert min(seen) >= 10, seen


def test_one_integer_net_equals_its_reference(nets):
    """A StockfishNetInt alone (a --weights .nnue with no small net)."""
    big = nets[0].as_device()
    boards, stms = thinned_boards(60, 7)
    values = np.asarray(jax.jit(jax.vmap(
        lambda b, s: ni.evaluate_sf(big, b, s)))(jnp.asarray(boards), jnp.asarray(stms)))
    want = [ni.evaluate_int_reference(nets[0], b, int(s)) for b, s in zip(boards, stms)]
    assert values.tolist() == want


@pytest.mark.parametrize("fen,seed", PLAYOUTS)
def test_integer_rows_down_a_playout_equal_a_refresh(nets, dual, fen, seed):
    """After every move of a line with captures, castlings, en passant,
    promotions and king moves, both stacks of the pair (int16 rows, int32
    PSQT sums) equal a refresh from the board bit for bit, and the eval
    from them is the reference's."""
    @jax.jit
    def push(pair, board, move):
        child, stale = ni.acc_update_pair(
            dual, pair, board.board, *move_piece_changes(board, move))
        after = make_move(board, move)
        fresh = ni.acc_refresh_pair(dual, after.board)
        return jax.tree.map(
            lambda c, f: jnp.where(stale[:, None], f, c), child, fresh), stale, after

    refresh = jax.jit(lambda b: ni.acc_refresh_pair(dual, b))
    forward = jax.jit(lambda pair, stm, b: ni.forward_int(
        dual, pair, stm, nnue.output_bucket(b), b)[0])
    moves = playout(fen, seed)
    board = from_position(moves[0][0])
    pair = refresh(board.board)
    assert pair[0].dtype == jnp.int16 and pair[1].dtype == jnp.int32
    for pos, move, _kinds in moves:
        pair, _stale, board = push(pair, board, jnp.int32(encode_host_move(move)))
        fresh = refresh(board.board)
        for got, want in zip(pair, fresh):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=f"{move.uci()} in {pos.to_fen()}")
        assert int(forward(pair, board.stm, board.board)) == \
            reference(nets, board.board, board.stm)
    assert len(moves) >= 12


def test_stale_integer_rows_are_rebuilt(dual):
    """`_refresh_stale` rebuilds both stacks of a stale perspective."""
    B, R = 16, 6
    boards = jnp.stack([from_position(Position.from_fen(
        PAWNS_UP if i % 2 else START)).board for i in range(B)])
    stale = np.zeros((B, 2), bool)
    stale[[1, 4, 9], [0, 1, 1]] = True
    row0 = np.full(B, 2, np.int32)
    rng = np.random.default_rng(0)
    acc0 = (jnp.asarray(rng.integers(-99, 99, (B, R, dual.ft.shape[1])), jnp.int16),
            jnp.asarray(rng.integers(-99, 99, (B, R, 16)), jnp.int32))
    acc, rows = jax.jit(lambda a, s, r, b: S._refresh_stale(dual, a, s, r, b, "standard"))(
        acc0, jnp.asarray(stale), jnp.asarray(row0), boards)
    fresh = jax.vmap(lambda b: ni.acc_refresh_pair(dual, b))(boards)
    for got, want, before in zip(acc, fresh, acc0):
        got, want, before = map(np.asarray, (got, want, before))
        assert got.dtype == before.dtype
        for lane in range(B):
            for persp in (0, 1):
                expect = want[lane, persp] if stale[lane, persp] else before[lane, 2 + persp]
                np.testing.assert_array_equal(got[lane, 2 + persp], expect)
    assert int(rows) == 2 * 2 * ni.REFRESH_ROWS


def _crafted_net() -> ni.StockfishNetInt:
    """A net whose every value is negative and odd where Stockfish
    divides: PSQT -3 for each own pawn (so -3 / 2), a skip output of
    -1,000 (x 9,600 / 8,128 = -1,181.1), and one hidden output of -3,000,
    whose square (17 after >> 19) feeds fc1 while its clipped value is 0.
    No feature weight: every accumulator holds its bias."""
    l1 = 4
    zeros = lambda shape, dt: np.zeros(shape, dt)  # noqa: E731
    psqt = zeros((ni.NUM_FEATURES, 8), np.int32)
    kinds = (np.arange(ni.NUM_FEATURES) // 64) % 11
    psqt[kinds == 0] = -3  # an own pawn, every king bucket and square
    fc0_b = zeros((8, ni.FC0_OUT), np.int32)
    fc0_b[:, 0] = -3000
    fc0_b[:, HIDDEN] = -1000
    fc1_w = zeros((8, ni.FC1_OUT, ni.FC1_IN), np.int8)
    fc1_w[:, 0, 0] = 127  # the square of hidden output 0
    fc2_w = zeros((8, 1, ni.FC1_OUT), np.int8)
    fc2_w[:, 0, 0] = -1
    return ni.StockfishNetInt(
        ft_w=zeros((ni.NUM_FEATURES, l1), np.int16),
        ft_b=np.full(l1, 50, np.int16), psqt_w=psqt,
        fc0_w=zeros((8, ni.FC0_OUT, l1), np.int8), fc0_b=fc0_b,
        fc1_w=fc1_w, fc1_b=zeros((8, ni.FC1_OUT), np.int32),
        fc2_w=fc2_w, fc2_b=np.full((8, 1), -5, np.int32),
    )


def test_negative_values_truncate_as_cpp_does():
    """psqt -3 / 2 = -1, fc1 127 x 17 = 2,159 >> 6 = 33, fc2 -33 - 5 =
    -38, skip -1,000 x 9,600 / 8,128 = -1,181, (-1 - 38 - 1,181) / 16 =
    -76: each division toward zero (flooring would give -2, -1,182 and
    -77), and the square taken of the unclipped hidden output."""
    net = _crafted_net()
    # white to move with one white pawn and no black one
    board = np.asarray(from_position(Position.from_fen(
        "4k3/8/8/8/8/8/4P3/4K3 w - - 0 1")).board)
    assert ni.evaluate_int_reference(net, board, 0) == -76
    got = jax.jit(lambda b: ni.evaluate_sf(net.as_device(), b, jnp.int32(0)))(board)
    assert int(got) == -76
    # black to move: its own pawns are none, the white pawn theirs: +3 / 2
    assert ni.evaluate_int_reference(net, board, 1) == (1 - 38 - 1181) // 16 + 1


def test_reference_refuses_a_draw_that_would_wrap():
    net = _crafted_net()
    wide = ni.StockfishNetInt(**{**{f: getattr(net, f) for f in ni._ARRAY_FIELDS},
                                 "ft_w": np.full_like(net.ft_w, 2000)})
    board = np.asarray(from_position(Position.from_fen(START)).board)
    with pytest.raises(OverflowError, match="int16"):
        ni.evaluate_int_reference(wide, board, 0)


def test_the_file_keeps_its_integers_and_dequantizes_as_before(tmp_path):
    """`load_nnue` returns the written integers unchanged, in the file's
    types; `dequantize` is the float32 net the same file loaded as
    before: each array over its storage scale, in float64, rounded once
    to float32."""
    q = quantized(64, 11)
    path = tmp_path / "net.nnue"
    ni.write_nnue(path, q)
    net = ni.load_nnue(path)
    assert isinstance(net, ni.StockfishNetInt)
    types = {"ft_w": np.int16, "ft_b": np.int16, "psqt_w": np.int32,
             "fc0_w": np.int8, "fc1_w": np.int8, "fc2_w": np.int8,
             "fc0_b": np.int32, "fc1_b": np.int32, "fc2_b": np.int32}
    for field, dtype in types.items():
        got = getattr(net, field)
        assert got.dtype == dtype, field
        np.testing.assert_array_equal(got, q["psqt" if field == "psqt_w" else field])
    flt = ni.dequantize(net)
    assert isinstance(flt, ni.StockfishNet)
    out = ni.NNUE2SCORE * ni.OUTPUT_SCALE
    want = {"ft_w": q["ft_w"] / ni.QA, "ft_b": q["ft_b"] / ni.QA,
            "psqt_w": q["psqt"] / out, "fc0_w": q["fc0_w"] / ni.QB,
            "fc0_b": q["fc0_b"] / (ni.QA * ni.QB), "fc1_w": q["fc1_w"] / ni.QB,
            "fc1_b": q["fc1_b"] / (ni.QA * ni.QB),
            "fc2_w": q["fc2_w"] / (out / ni.QA), "fc2_b": q["fc2_b"] / out}
    for field, value in want.items():
        got = getattr(flt, field)
        assert got.dtype == np.float32, field
        np.testing.assert_array_equal(got, value.astype(np.float32), err_msg=field)
    assert (flt.version, flt.net_hash, flt.description) == \
        (net.version, net.net_hash, net.description)


def test_the_float_forward_tells_the_arithmetics_apart(nets):
    """The float forward of the dequantized nets is another arithmetic:
    on most boards of the seeded draw it misses the integer reference by
    more than the benchmark's depth-1 limit of 2 cp."""
    floats = [ni.dequantize(n) for n in nets]
    boards, stms = thinned_boards(40, 5)
    far = sum(
        abs(ni.evaluate_dual_reference(*floats, b, int(s))
            - ni.evaluate_dual_int_reference(*nets, b, int(s))) > 2
        for b, s in zip(boards, stms))
    assert far >= 30, far


def test_published_widths_equal_the_reference():
    """L1 3,072 and 128, the widths fishnet ships: a handful of boards,
    refresh and ladder against the reference."""
    big = quantize(draw_net(3072, 3072, 1.0))
    small = quantize(draw_net(128, 128, 0.1))
    dual = ni.DualNetInt.from_nets(big, small)
    assert dual.ft.shape == (ni.NUM_FEATURES, 3200)  # 25 x 128: no padding
    dual = dual.as_device()
    boards, stms = thinned_boards(6, 3072)
    values = np.asarray(jax.jit(jax.vmap(lambda b, s: ni.evaluate_sf(dual, b, s)))(
        jnp.asarray(boards), jnp.asarray(stms)))
    want = [ni.evaluate_dual_int_reference(big, small, b, int(s))
            for b, s in zip(boards, stms)]
    assert values.tolist() == want


# ----------------------------------------------------- through TpuEngine


def test_engine_counts_every_expansion_and_is_the_full_refresh_search(dual, monkeypatch):
    """Under "int" the three eval counters sum to the node expansions,
    and the incremental search is the full-refresh search (every node's
    rows from the board) position by position."""
    from test_refill import make_refill_engine, run

    inc = make_refill_engine(params=dual)
    got = [run(inc, chunk) for chunk in _chunks()]
    occ = inc.occupancy_totals
    counts = [occ[name] for name in S.EVAL_COUNTERS]
    assert sum(counts) == occ["movegen_nodes"] > 0
    assert counts[0] > 0 and counts[2] > 0, dict(zip(S.EVAL_COUNTERS, counts))
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


def test_mesh_runs_a_chunk_with_the_integer_nets(dual):
    """The 8-device CPU mesh under parallel/partition.py's rules for the
    int16 table, its PSQT table and the int8 stacks: the single device's
    searches and counts."""
    from test_mesh_refill import make_mesh_engine
    from test_refill import make_refill_engine, run

    mesh = make_mesh_engine(refill=True, params=dual)
    got = run(mesh, _chunks()[0])
    one = make_refill_engine(params=dual)
    want = run(one, _chunks()[0])
    assert _flat(got) == _flat(want)
    for name in S.EVAL_COUNTERS:
        assert mesh.occupancy_totals[name] == one.occupancy_totals[name]


def test_partition_rules_cover_the_integer_nets():
    from fishnet_tpu.parallel import partition
    from jax.sharding import PartitionSpec as P

    specs = partition.match_partition_rules(
        partition.dual_int_param_proto(), partition.PARAM_RULES)
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == 3 + 2 * 6 and all(s == P() for s in leaves)
    assert "params_dual_int" in partition.search_proto()
    assert partition.search_param_spec() == P()


def test_the_host_runs_the_files_in_integers_and_says_so(tmp_path, dual):
    """The engine host's own `_build_engine` (what --weights / --weights-small
    reach from the client, the fleet and serve) holds the pair in
    integers with no setting; the cache identity and the session span
    name the arithmetic, and FISHNET_TPU_DTYPE=bf16 refuses the files."""
    from fishnet_tpu.cache.keys import engine_identity
    from fishnet_tpu.engine import host
    from fishnet_tpu.engine.tpu import TpuEngine
    from fishnet_tpu.obs import trace as obs_trace
    from test_refill import make_refill_engine, run

    big, small = tmp_path / "big.nnue", tmp_path / "small.nnue"
    ni.write_nnue(big, quantized(128, 21))
    ni.write_nnue(small, quantized(64, 22))
    args = argparse.Namespace(
        backend="tpu", weights=str(big), weights_small=str(small), depth=2,
        helpers=1, refill=1, mesh_refill=0, skip_warmup=True)
    # (the host's own table: the conftest mesh's 8 shards of 2^21 rows)
    engine = host._build_engine(args, print)
    assert isinstance(engine.params, ni.DualNetInt)
    assert engine.arithmetic == "int"
    want = ni.DualNetInt.from_nets(ni.load_nnue(big), ni.load_nnue(small))
    assert np.array_equal(np.asarray(engine.params.ft), want.ft)
    floats = type("TpuEngine", (), {"weights_path": str(big),
                                    "weights_small_path": str(small),
                                    "max_depth": engine.max_depth,
                                    "arithmetic": "float32"})()
    assert engine_identity(engine) != engine_identity(floats)

    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=20_000))
    try:
        run(make_refill_engine(params=dual), _chunks()[0])
        events = rec.snapshot()
    finally:
        obs_trace.uninstall()
    sessions = [e for e in events if e.get("name") == "session"]
    assert sessions and all(e["args"]["arithmetic"] == "int" for e in sessions)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FISHNET_TPU_DTYPE", "bf16")
        with pytest.raises(ValueError, match="integer net"):
            TpuEngine(weights_path=str(big), tt_size_log2=0)
