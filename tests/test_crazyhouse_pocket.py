"""The crazyhouse pocket under the lane vmap, and the movegen counters.

PR 30 replaced the pocket's traced-index reads and writes (a
`dynamic_slice` at `us * 5`, `extra.at[slot].add`, `extra.at[word].set`)
by forms that stay vectorised when `vmap` batches the index: a lowering
repair, not a change of rules. So the new forms must give the parent's
arrays bit for bit (the parent's forms are kept below), the host rules'
positions, and a jaxpr with no batched-index op on `extra`. The same PR
counts movegen work on the device (`ops/search.py` MOVEGEN_COUNTERS):
those must tie out against what the oracle sees.
"""
import random

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np
import pytest

from fishnet_tpu.chess.variants import from_fen, position_class
from fishnet_tpu.models import nnue
from fishnet_tpu.ops import search as S
from fishnet_tpu.ops.board import (
    EXTRA_POCKET,
    EXTRA_PROMOTED,
    Board,
    from_position,
    make_move,
    piece_color,
    piece_type,
    stack_boards,
)
from fishnet_tpu.ops.movegen import DROP_FLAG, _candidate_space, generate_moves
from fishnet_tpu.ops.oracle import oracle_search

from test_device_variants import encode_host_move

ZH = "crazyhouse"
LANES = 64
_AXES = Board(0, 0, 0, 0, 0, 0)
# promotions at once, every piece type in both pockets: promoted bits on
# both words of the bitboard, captures of promoted pieces, drops of all
PROMO_FENS = [
    "6k1/PPPP4/8/8/8/8/pppp4/6K1[QRBNPqrbnp] w - - 0 1",
    "k7/4PPPP/8/8/8/8/4pppp/K7[NNPPnnpp] b - - 0 1",
    "r3k2r/1PP3P1/8/8/8/8/1pp3p1/R3K2R[QRqr] w KQkq - 0 1",
]


# ------------------------------------------------- the parent's forms

def parent_pocket(extra, us):
    return jax.lax.dynamic_slice(extra, (us * 5,), (5,))


def parent_extra(b: Board, move):
    """`make_move`'s crazyhouse block as the parent commit had it, on the
    quantities `make_move` derives above it."""
    frm, to, promo = move & 63, (move >> 6) & 63, (move >> 12) & 7
    is_drop = ((move >> 15) & 1) == 1
    board, us = b.board, b.stm
    piece, target = board[frm], board[to]
    is_pawn = (piece_type(piece) == 0) & ~is_drop
    is_king = (piece_type(piece) == 5) & ~is_drop
    is_castle = is_king & (piece_color(target) == us) & (piece_type(target) == 3)
    is_ep = is_pawn & (to == b.ep) & (target == 0) & ((to & 7) != (frm & 7))
    ep_victim_c = jnp.clip(jnp.where(us == 0, to - 8, to + 8), 0, 63)
    capture = (piece_color(target) == 1 - us) | is_ep

    def get_bit(e, sq):
        return (e[EXTRA_PROMOTED + sq // 32] >> (sq % 32)) & 1

    def with_bit(e, sq, val):
        w = EXTRA_PROMOTED + sq // 32
        bit = jnp.int32(1) << (sq % 32)
        return e.at[w].set(jnp.where(val == 1, e[w] | bit, e[w] & ~bit))

    extra = b.extra
    was_promoted_mover = get_bit(extra, frm) & jnp.where(is_drop, 0, 1)
    cap_sq = jnp.where(is_ep, ep_victim_c, to)
    victim_code = jnp.where(is_ep, board[ep_victim_c], target)
    real_capture = capture & ~is_castle & ~is_drop
    cap_promoted = get_bit(extra, cap_sq) & jnp.where(real_capture, 1, 0)
    cap_type = jnp.where(
        cap_promoted == 1, 0, jnp.maximum(piece_type(victim_code), 0))
    pocket_slot = EXTRA_POCKET + us * 5 + jnp.clip(cap_type, 0, 4)
    extra = extra.at[pocket_slot].add(jnp.where(real_capture, 1, 0))
    drop_slot = EXTRA_POCKET + us * 5 + jnp.clip(promo, 0, 4)
    extra = extra.at[drop_slot].add(jnp.where(is_drop, -1, 0))
    extra = with_bit(extra, frm, jnp.int32(0))
    extra = with_bit(
        extra, cap_sq, jnp.where(real_capture, 0, get_bit(extra, cap_sq)))
    dest_promoted = jnp.where(
        is_drop, 0, jnp.where(promo > 0, 1, was_promoted_mover))
    return with_bit(extra, to, dest_promoted)


# ------------------------------------------------------ seeded positions

def _playout(rng, fen, plies):
    """Positions of one random playout that likes captures, promotions
    and drops (so pockets fill and promoted pieces move and die)."""
    pos = from_fen(fen, ZH)
    out = []
    for _ in range(plies):
        legal = pos.legal_moves()
        if not legal or pos.outcome() is not None:
            break
        out.append(pos)
        loud = [m for m in legal if m.drop is not None or m.promotion is not None
                or pos.piece_at(m.to_sq) is not None]
        pos = pos.push(rng.choice(loud if loud and rng.random() < 0.6 else legal))
    return out


@pytest.fixture(scope="module")
def positions():
    rng = random.Random(30)
    start = position_class(ZH).starting_fen()
    pool = []
    for fen in [start] * 6 + PROMO_FENS * 2:
        pool += _playout(rng, fen, 60)
    with_pocket = [p for p in pool if any(any(side) for side in p.pockets)]
    promoted = [p for p in with_pocket if p.promoted]
    assert len(promoted) >= 40, len(promoted)
    rng.shuffle(with_pocket)
    picked = (promoted + [p for p in with_pocket if not p.promoted])[:4 * LANES]
    assert len(picked) == 4 * LANES
    return picked


def _batches(positions):
    for i in range(0, len(positions), LANES):
        chunk = positions[i:i + LANES]
        yield chunk, stack_boards([from_position(p) for p in chunk])


# ------------------------------------------------------------------ tests

def test_vmapped_movegen_matches_host_rules_and_parent_pocket(positions):
    gen = jax.jit(jax.vmap(lambda b: generate_moves(b, ZH), in_axes=(_AXES,)))
    one = jax.jit(lambda b: generate_moves(b, ZH))
    old_pocket = jax.jit(jax.vmap(parent_pocket))
    drops_seen = 0
    for chunk, boards in _batches(positions):
        moves, count, noisy = map(np.asarray, gen(boards))
        pockets = np.asarray(old_pocket(boards.extra, boards.stm))
        for i, pos in enumerate(chunk):
            n = int(count[i])
            host = {encode_host_move(m) for m in pos.generate_pseudo_legal()}
            assert set(moves[i, :n].tolist()) == host and len(host) == n, pos.to_fen()
            assert (moves[i, n:] == -1).all()
            assert pockets[i].tolist() == list(pos.pockets[pos.turn])
            drops_seen += sum(1 for m in moves[i, :n] if m & DROP_FLAG)
        # the batched program and the single-lane one: the same arrays
        for i in (0, LANES // 2, LANES - 1):
            m1, c1, n1 = one(from_position(chunk[i]))
            assert np.array_equal(np.asarray(m1), moves[i])
            assert (int(c1), int(n1)) == (int(count[i]), int(noisy[i]))
    assert drops_seen > 1000


def test_vmapped_make_move_matches_host_rules_and_parent_forms(positions):
    mk = jax.jit(jax.vmap(lambda b, m: make_move(b, m, ZH), in_axes=(_AXES, 0)))
    old = jax.jit(jax.vmap(parent_extra, in_axes=(_AXES, 0)))
    rng = random.Random(31)
    kinds = {"drop": 0, "capture_of_promoted": 0, "promotion": 0, "moved_promoted": 0}
    for chunk, boards in _batches(positions):
        for _round in range(3):
            picks = []
            for pos in chunk:
                legal = pos.legal_moves()
                loud = [m for m in legal if m.drop is not None
                        or m.promotion is not None
                        or pos.piece_at(m.to_sq) is not None
                        or (pos.promoted >> m.from_sq) & 1]
                picks.append(rng.choice(loud or legal))
            enc = jnp.asarray([encode_host_move(m) for m in picks], jnp.int32)
            child = mk(boards, enc)
            extra = np.asarray(child.extra)
            assert np.array_equal(extra, np.asarray(old(boards, enc)))
            for i, (pos, mv) in enumerate(zip(chunk, picks)):
                want = from_position(pos.push(mv))
                assert np.array_equal(extra[i], np.asarray(want.extra)), (
                    pos.to_fen(), mv.uci())
                assert np.array_equal(np.asarray(child.board[i]),
                                      np.asarray(want.board))
                kinds["drop"] += mv.drop is not None
                kinds["promotion"] += mv.promotion is not None
                if mv.drop is None:
                    kinds["moved_promoted"] += (pos.promoted >> mv.from_sq) & 1
                    kinds["capture_of_promoted"] += (
                        pos.piece_at(mv.to_sq) is not None
                        and (pos.promoted >> mv.to_sq) & 1)
    assert all(n >= 5 for n in kinds.values()), kinds


# ---- the lowering: no batched-index op on what comes from `extra`

# indexed primitives → which operands hold data (the array, the updates)
INDEXED = {"gather": (0,), "dynamic_slice": (0,), "dynamic_update_slice": (0, 1),
           "scatter": (0, 2), "scatter-add": (0, 2), "scatter_add": (0, 2)}


def _indexed_ops_fed_by(jaxpr, tainted):
    """Names of the indexed ops of `jaxpr` (sub-jaxprs included) whose
    operand or update derives from the `tainted` input variables."""
    tainted = set(tainted)
    found = []
    for eqn in jaxpr.eqns:
        fed = [not isinstance(v, Literal) and v in tainted
               for v in eqn.invars]
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is not None:
            inner = getattr(inner, "jaxpr", inner)
            sub, outs = _indexed_ops_fed_by(
                inner, [iv for iv, f in zip(inner.invars, fed) if f])
            found += sub
            tainted.update(ov for ov, f in zip(eqn.outvars, outs) if f)
            continue
        if any(fed[k] for k in INDEXED.get(eqn.primitive.name, ())):
            found.append(eqn.primitive.name)
        if any(fed):
            tainted.update(eqn.outvars)
    return found, [v in tainted for v in jaxpr.outvars]


def _ops_on_extra(fn, *args):
    closed = jax.make_jaxpr(fn)(*args)
    flat, _ = jax.tree_util.tree_flatten(args)
    extra_at = next(i for i, a in enumerate(flat) if a.shape[-1] == 12)
    found, _ = _indexed_ops_fed_by(
        closed.jaxpr, [closed.jaxpr.invars[extra_at]])
    return found


def test_no_batched_index_op_on_the_pocket(positions):
    boards = stack_boards([from_position(p) for p in positions[:LANES]])
    moves = jnp.zeros(LANES, jnp.int32)
    space = jax.vmap(lambda b: _candidate_space(b, ZH), in_axes=(_AXES,))
    mk = jax.vmap(lambda b, m: make_move(b, m, ZH), in_axes=(_AXES, 0))
    assert _ops_on_extra(space, boards) == []
    assert _ops_on_extra(mk, boards, moves) == []
    # the walker does see the parent's forms
    assert "gather" in _ops_on_extra(
        jax.vmap(lambda b: parent_pocket(b.extra, b.stm), in_axes=(_AXES,)), boards)
    old = _ops_on_extra(jax.vmap(parent_extra, in_axes=(_AXES, 0)), boards, moves)
    assert "gather" in old and any(n.startswith("scatter") for n in old), old


# ---- the counters tie out

@pytest.fixture(scope="module")
def params():
    return nnue.init_params(
        jax.random.PRNGKey(0), l1=32, h1=8, h2=8, feature_set="board768")


def _segments(params, roots, depth, variant, seg=16):
    """Drive `_run_segment_jit` as the scheduler does, in short segments:
    → (movegen counters summed over segments, final state)."""
    B = roots.stm.shape[0]
    state = S._init_state_jit(
        params, roots, jnp.full((B,), depth, jnp.int32),
        jnp.full((B,), 100_000, jnp.int32), 4, variant)
    total = dict.fromkeys(S.MOVEGEN_COUNTERS, 0)
    segments = 0
    while True:
        state, _tt, n, summ = S._run_segment_jit(
            params, state, None, seg, variant, False)
        summ = np.asarray(summ)
        assert summ.shape == (B + S.SUM_TAIL, S.SUM_W) and summ[B, S.SUM_DONE] == int(n)
        for k, v in S.movegen_counts(summ[B]).items():
            total[k] += v
        segments += 1
        if int(n) < seg:
            return total, state, segments


FULL_POCKET = "r1bqk2r/ppp2ppp/2n2n2/3pp3/3PP3/2N2N2/PPP2PPP/R1BQK2R[QRBNPqrbnp] w KQkq - 0 6"


@pytest.mark.parametrize("variant,fens", [
    ("standard", ["rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
                  "r1bqkbnr/pppp1ppp/2n5/4p3/4P3/5N2/PPPP1PPP/RNBQKB1R w KQkq - 2 3"]),
    (ZH, [FULL_POCKET,
          "rnbqkbnr/ppp1pppp/8/8/8/8/PPPP1PPP/RNBQKBNR[Pp] w KQkq - 0 3"]),
])
def test_movegen_counters_tie_out_with_the_oracle(params, variant, fens):
    roots = stack_boards([from_position(from_fen(f, variant)) for f in fens] * 4)
    total, state, segments = _segments(params, roots, 1, variant)
    assert segments > 1  # the counters are summed across boundaries
    want = dict.fromkeys(S.MOVEGEN_COUNTERS, 0)
    for i, fen in enumerate(fens):
        exp = oracle_search(
            params, from_position(from_fen(fen, variant)), 1, 100_000, 4,
            variant=variant)
        for k, v in zip(S.MOVEGEN_COUNTERS, exp["movegen"]):
            want[k] += 4 * v
        # and the search is the one the oracle made, counters or no
        res = S.extract_results(state, 0)
        assert int(res["score"][i]) == exp["score"]
        assert int(res["nodes"][i]) == exp["nodes"]
    assert total == want
    assert total["movegen_nodes"] > 0
    assert total["movegen_moves"] <= total["movegen_nodes"] * S.max_moves_for(variant)
    if variant == ZH:
        assert 0 < total["movegen_drops"] < total["movegen_moves"]
    else:
        assert total["movegen_drops"] == 0
    # results are the ones `search_batch` gives, which reads no counter
    out = S.search_batch(params, roots, 1, 100_000, max_ply=4, variant=variant)
    res = S.extract_results(state, 0)
    for k in ("score", "nodes", "move"):
        assert np.array_equal(np.asarray(out[k]), np.asarray(res[k]))


def test_counters_wrap_as_unsigned_and_sum_over_shards():
    row = np.array([[7, -1, 5, 0], [9, 3, -(2 ** 31), 2]], np.int32)
    assert S.movegen_counts(row[0]) == {
        "movegen_nodes": 2 ** 32 - 1, "movegen_moves": 5, "movegen_drops": 0}
    assert S.movegen_counts(row) == {
        "movegen_nodes": 2 ** 32 + 2, "movegen_moves": 2 ** 31 + 5,
        "movegen_drops": 2}


def test_engine_totals_carry_the_counters_on_one_device_and_on_a_mesh():
    """Through `LaneScheduler`: the same chunk on one device and on the
    8-device mesh (uncoupled lanes, so the same searches) reads the same
    counters — a mesh sums its shards' — and the `segment` spans carry
    them interval by interval."""
    from fishnet_tpu.obs import trace as obs_trace

    from test_mesh_refill import make_mesh_engine
    from test_refill import analysis_work, make_chunk, make_refill_engine, run

    def counters(engine):
        return {k: engine.occupancy_totals[k] for k in S.MOVEGEN_COUNTERS}

    one = make_refill_engine()
    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=20_000))
    try:
        resp = run(one, make_chunk(analysis_work(depth=3), n_positions=4))
        spans = [e for e in rec.snapshot() if e["name"] == "segment"]
    finally:
        obs_trace.uninstall()
    got = counters(one)
    assert 0 < got["movegen_nodes"] <= sum(r.nodes for r in resp)
    assert got["movegen_moves"] > got["movegen_nodes"] and got["movegen_drops"] == 0
    for name in S.MOVEGEN_COUNTERS:
        assert sum(e["args"]["counts"].get(name, 0) for e in spans) == got[name]
    mesh = make_mesh_engine(refill=True)
    run(mesh, make_chunk(analysis_work(depth=3), n_positions=4))
    assert counters(mesh) == got
