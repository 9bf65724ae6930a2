"""Continuous lane refill (round 7): scheduler and search_stream tests.

Three contracts from the round-7 change (engine/tpu.py LaneScheduler,
ops/search.py refill_lanes/search_stream):

1. Refill OFF is bit-identical to the chunk-serial engine — same routing,
   same scores, same node counts. The refill path must be a pure opt-in.
2. Refill ON produces the SAME per-position results as refill off when
   nothing couples the lanes (no TT, no helpers): resplicing a DONE lane
   mid-flight must not perturb live lanes.
3. Every submitted position gets exactly one response, even when several
   chunks share the engine concurrently through the combining driver.

Engines here say refill=True or refill=False explicitly. This file pins the SINGLE-DEVICE scheduler semantics:
refill engines force engine.mesh = None, which is exactly what a
single-device production host looks like (conftest's 8 virtual CPU
devices would otherwise give every engine a mesh — the sharded
scheduler path has its own suite, tests/test_mesh_refill.py).
"""
import asyncio
import threading
import time

import numpy as np
import pytest

from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.tpu import COMPILE_SITES, TpuEngine
from fishnet_tpu.obs import trace as obs_trace
from fishnet_tpu.utils import syncstats

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
GAME = ["e2e4", "c7c5", "g1f3", "d7d6"]


def analysis_work(depth=3):
    return AnalysisWork(
        id="refill01",
        nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
        timeout_s=30.0,
        depth=depth,
        multipv=None,
    )


def make_chunk(work, n_positions=3, moves=GAME):
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=START, moves=moves[:i])
        for i in range(n_positions)
    ]
    return Chunk(work=work, deadline=time.monotonic() + 120,
                 variant="standard", flavor=EngineFlavor.TPU,
                 positions=positions)


def run(engine, chunk):
    return asyncio.run(engine.go_multiple(chunk))


def make_refill_engine(**kw):
    """Refill-on engine in the single-device configuration this suite
    pins (mesh=None), no helper coupling unless asked."""
    kw.setdefault("max_depth", 3)
    kw.setdefault("tt_size_log2", 0)
    kw.setdefault("helper_lanes", 1)
    engine = TpuEngine(refill=True, **kw)
    engine.mesh = None  # single-device semantics (mesh suite is separate)
    engine.n_dev = 1
    return engine


def test_refill_defaults_to_registry():
    """refill=None defers to FISHNET_TPU_REFILL (the registry default is
    on, and the suite does not pin it); an explicit constructor argument
    wins over the registry."""
    from fishnet_tpu.utils import settings

    assert settings.lookup("FISHNET_TPU_REFILL").default == "1"
    assert TpuEngine(max_depth=2, tt_size_log2=0).refill is True
    assert TpuEngine(max_depth=2, tt_size_log2=0, refill=False).refill is False


def _stub_search(engine):
    """Routing tests need the dispatch path, not a real search — stub
    the device program (same pattern as test_tpu_engine.py)."""

    def fake_search(roots, depth_arr, budget_arr, deadline=None, **kw):
        B = len(depth_arr)
        return {
            "done": np.ones(B, bool),
            "score": np.full(B, 20, np.int32),
            "move": np.full(B, 12 | (28 << 6), np.int32),  # e2e4
            "pv": np.full((B, 4), -1, np.int32),
            "pv_len": np.zeros(B, np.int32),
            "nodes": np.ones(B, np.int32),
        }

    engine._search = fake_search


def test_refill_off_never_touches_scheduler():
    """The refill-off engine must route every chunk through the serial
    path: a poisoned scheduler proves the routing never reaches it."""
    engine = TpuEngine(max_depth=2, tt_size_log2=0, refill=False)
    _stub_search(engine)

    def boom(chunk):
        raise AssertionError("scheduler engaged with refill disabled")

    engine._scheduler.run_chunk = boom
    responses = run(engine, make_chunk(analysis_work(depth=2)))
    assert len(responses) == 3
    assert all(r.best_move for r in responses)


def test_mesh_refill_optout_falls_back_to_serial():
    """FISHNET_TPU_MESH_REFILL=0 (mesh_refill=False) pins a MESHED
    engine back to strict chunk-serial dispatch even with refill on —
    the scheduler must never engage. (With mesh_refill on, the meshed
    scheduler path is covered by tests/test_mesh_refill.py.)"""
    engine = TpuEngine(max_depth=2, tt_size_log2=0, helper_lanes=1,
                       refill=True, mesh_refill=False)
    assert engine.mesh is not None  # conftest provides 8 virtual devices
    _stub_search(engine)

    def boom(chunk):
        raise AssertionError("scheduler engaged with mesh refill opted out")

    engine._scheduler.run_chunk = boom
    responses = run(engine, make_chunk(analysis_work(depth=2)))
    assert len(responses) == 3


def test_refill_on_matches_refill_off():
    """Uncoupled lanes (no TT, no helpers): the scheduler must reproduce
    the chunk-serial engine's results exactly — scores, PVs, node counts,
    per-depth matrices. This is the refill-off bit-identity guarantee
    from the other side: resplicing DONE lanes never perturbs live ones."""
    serial = TpuEngine(max_depth=3, tt_size_log2=0, helper_lanes=1,
                       refill=False)
    serial.mesh = None
    serial.n_dev = 1
    refill = make_refill_engine()
    chunk = make_chunk(analysis_work(depth=3), n_positions=4)
    want = run(serial, chunk)
    got = run(refill, make_chunk(analysis_work(depth=3), n_positions=4))
    assert refill.occupancy_totals["positions_done"] == 4
    assert refill.occupancy_totals["refills"] >= 4
    for w, g in zip(want, got):
        assert g.position_index == w.position_index
        assert g.best_move == w.best_move
        assert g.depth == w.depth
        assert g.nodes == w.nodes
        assert g.scores.matrix == w.scores.matrix
        assert g.pvs.matrix == w.pvs.matrix


def test_concurrent_chunks_exactly_once():
    """Two chunks submitted from two threads share one driver session;
    every position of both chunks gets exactly one response, in order."""
    engine = make_refill_engine(max_depth=2)
    chunks = [
        make_chunk(analysis_work(depth=2), n_positions=3, moves=GAME),
        make_chunk(analysis_work(depth=2), n_positions=3,
                   moves=["d2d4", "g8f6", "c2c4"]),
    ]
    results = [None, None]
    errors = []

    def go(i):
        try:
            results[i] = run(engine, chunks[i])
        except Exception as e:  # pragma: no cover - failure detail
            errors.append(e)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors
    for i, responses in enumerate(results):
        assert responses is not None and len(responses) == 3
        assert [r.position_index for r in responses] == [0, 1, 2]
        assert all(r.best_move for r in responses)
    assert engine.occupancy_totals["positions_done"] == 6


GAP_PARTS = ("gap_submit_ms", "gap_lock_ms", "gap_handoff_ms",
             "gap_starved_ms")


def test_gap_between_sessions_is_the_callers_when_nothing_is_submitted():
    """Two chunks with a pause between them: the pause is nobody's work
    (`gap_starved_ms`), the second chunk's replay and history hash are
    the engine's (`gap_submit_ms`), the parts make up the gap, and gaps
    plus sessions make up the time from the first session's start to
    the last one's end."""
    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=16384))
    try:
        engine = make_refill_engine(max_depth=2)
        run(engine, make_chunk(analysis_work(depth=2)))
        assert engine.occupancy_totals["gap_ms"] == 0.0  # nothing before
        time.sleep(0.25)
        run(engine, make_chunk(analysis_work(depth=2)))
    finally:
        obs_trace.uninstall()
    tot = engine.occupancy_totals
    assert tot["sessions"] >= 2
    assert tot["gap_starved_ms"] >= 250.0
    assert tot["gap_submit_ms"] > 0.0
    assert sum(tot[k] for k in GAP_PARTS) == pytest.approx(tot["gap_ms"])
    sessions = [e for e in rec.snapshot() if e["name"] == "session"]
    assert len(sessions) == tot["sessions"]
    elapsed_ms = (max(e["ts"] + e["dur"] for e in sessions)
                  - min(e["ts"] for e in sessions)) / 1000.0
    assert tot["gap_ms"] + tot["session_ms"] == pytest.approx(
        elapsed_ms, rel=0.01)


def test_gap_counts_overlapping_submits_once():
    """Two threads inside _submit at once after a session has ended: the
    time either was submitting is `gap_submit_ms`, counted once — the
    parts still make up the gap."""
    engine = make_refill_engine(max_depth=2)
    run(engine, make_chunk(analysis_work(depth=2)))
    chunks = [
        make_chunk(analysis_work(depth=2), n_positions=3, moves=GAME),
        make_chunk(analysis_work(depth=2), n_positions=3,
                   moves=["d2d4", "g8f6", "c2c4"]),
    ]
    threads = [threading.Thread(target=run, args=(engine, c)) for c in chunks]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    tot = engine.occupancy_totals
    assert tot["positions_done"] == 9 and tot["chunks_submitted"] == 3
    assert 0.0 < tot["gap_submit_ms"] <= tot["gap_ms"]
    assert sum(tot[k] for k in GAP_PARTS) == pytest.approx(tot["gap_ms"])


def test_compile_inside_submit_is_counted_there():
    """_submit builds no program (the history keys are hashed on the
    host), and a program that is built inside one of its steps is
    counted against that step on the engine the thread serves, and
    nowhere else."""
    import jax

    from fishnet_tpu.chess import Position
    from fishnet_tpu.ops.board import from_position

    engine = make_refill_engine(max_depth=2)
    work = analysis_work(depth=2)
    # nine plies of history: a length no other test of this file submits
    moves = ["g1f3", "g8f6", "f3g1", "f6g8", "b1c3", "b8c6", "c3b1",
             "c6b8", "e2e4"]
    chunk = Chunk(work=work, deadline=time.monotonic() + 120,
                  variant="standard", flavor=EngineFlavor.TPU,
                  positions=[WorkPosition(
                      work=work, position_index=0, url=None, skip=False,
                      root_fen=START, moves=moves)])
    # the job's own from_position puts numpy scalars on the device through
    # a one-off program per process, which an engine's warm-up has met
    from_position(Position.from_fen(START))
    tot = engine.occupancy_totals
    with syncstats.serving(tot):
        engine._scheduler._submit(chunk)
    for site in COMPILE_SITES:
        assert tot[f"compiles_{site}"] == 0, site
    assert tot["compile_ms"] == 0.0
    assert tot["submit_history_ms"] > 0.0
    assert tot["positions_submitted"] == 1
    # the listener's labelling: a program first met under the step's label
    with syncstats.serving(tot):
        with syncstats.step("submit_history"):
            jax.jit(lambda x: (x * 27 + len(moves)).sum())(
                np.arange(27)).block_until_ready()
    assert tot["compiles_submit_history"] >= 1
    assert tot["compile_ms"] > 0.0
    for site in COMPILE_SITES:
        if site != "submit_history":
            assert tot[f"compiles_{site}"] == 0, site
    # outside the block this thread serves no engine: counted nowhere
    assert syncstats.where() == (None, "other")


def test_occupancy_accounting():
    """Per-segment occupancy rows carry the lane breakdown the bench and
    tools/occupancy_report.py consume; totals tie out against the log."""
    engine = make_refill_engine(max_depth=2)
    run(engine, make_chunk(analysis_work(depth=2)))
    log = engine.occupancy_log
    assert log, "no occupancy rows recorded"
    for row in log:
        assert row["live"] + row["helpers"] + row["idle"] == row["width"]
        assert row["steps"] > 0
    totals = engine.occupancy_totals
    assert totals["segments"] == len(log)
    assert totals["refills"] == sum(r["refilled"] for r in log)
    assert totals["lane_steps"] == (
        totals["live_lane_steps"] + totals["helper_lane_steps"]
        + totals["idle_lane_steps"])


def test_search_stream_matches_batch():
    """Ops-level: streaming N positions through a narrower width yields
    the same per-position results as one full-width batch (no TT)."""
    import jax

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import from_position, stack_boards

    params = nnue.init_params(jax.random.PRNGKey(0), l1=64,
                              feature_set="board768")
    pos = Position.from_fen(START)
    boards, p = [], pos
    for uci in [None] + GAME[:5]:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    roots = stack_boards(boards)
    n = len(boards)
    depth = np.full(n, 2, np.int32)
    budget = np.full(n, 50_000, np.int32)
    batch = S.search_batch_resumable(params, roots, depth, budget,
                                     max_ply=6, segment_steps=200)
    stream = S.search_stream(params, roots, depth, budget, max_ply=6,
                             width=4, segment_steps=200)
    assert bool(np.asarray(stream["done"]).all())
    assert stream["refills"] >= n - 4
    for key in ("score", "move", "nodes", "pv_len"):
        np.testing.assert_array_equal(
            np.asarray(stream[key]), np.asarray(batch[key]), err_msg=key)
    np.testing.assert_array_equal(
        np.asarray(stream["pv"]), np.asarray(batch["pv"]))


@pytest.mark.slow
def test_refill_never_corrupts_live_lanes():
    """Mixed-depth stream with a shared TT: each finished position must
    match its single-position oracle search run against the same TT
    snapshot discipline — i.e. refilled neighbors never corrupt a live
    lane's accumulator or history state. TT stores only ever tighten
    move ordering, so node counts may differ; the depth-complete SCORE
    of a finished position must match a fresh solo search's score within
    the window the TT can shift it — here we pin exact equality by
    streaming with tt=None, where no sharing channel exists at all, and
    assert oracle equality position by position at unequal depths."""
    import jax

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import from_position, stack_boards

    params = nnue.init_params(jax.random.PRNGKey(7), l1=64,
                              feature_set="board768")
    pos = Position.from_fen(START)
    boards, p = [], pos
    for uci in [None] + GAME:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    roots = stack_boards(boards)
    n = len(boards)
    # staggered depths: lanes finish at different segments, forcing
    # refills to land next to still-live lanes at every boundary
    depth = np.asarray([1, 3, 2, 1, 3], np.int32)[:n]
    budget = np.full(n, 200_000, np.int32)
    stream = S.search_stream(params, roots, depth, budget, max_ply=6,
                             width=2, segment_steps=150)
    assert bool(np.asarray(stream["done"]).all())
    for i in range(n):
        solo = S.search_batch_resumable(
            params, stack_boards([boards[i]]),
            np.asarray([depth[i]]), np.asarray([budget[i]]),
            max_ply=6, segment_steps=150)
        assert int(np.asarray(stream["score"])[i]) == int(
            np.asarray(solo["score"])[0]), f"position {i} score diverged"
        assert int(np.asarray(stream["nodes"])[i]) == int(
            np.asarray(solo["nodes"])[0]), f"position {i} nodes diverged"


# ------------------------------------------ the one-shape splice (PR 31)
#
# A boundary's admissions are staged on the host and spliced by one
# program per state width and variant (ops/search.py _splice_lanes_jit),
# whatever their number. The reference below is the splice as the
# parent commit ran it: rows gathered to the width on the device, a
# fresh _init_state_jit, a masked merge.

SPLICE_PLY = 5
# promotions at hand and both pockets full from the first ply on
ZH_FENS = [
    "6k1/PPPP4/8/8/8/8/pppp4/6K1[QRBNPqrbnp] w - - 0 1",
    "r3k2r/1PP3P1/8/8/8/8/1pp3p1/R3K2R[QRqr] w KQkq - 0 1",
]


@pytest.fixture(scope="module")
def splice_params():
    import jax

    from fishnet_tpu.models import nnue

    return nnue.init_params(jax.random.PRNGKey(31), l1=64,
                            feature_set="board768")


def _splice_positions(variant, n):
    """n positions of seeded playouts that like drops and promotions."""
    import random

    from fishnet_tpu.chess.variants import from_fen, position_class

    rng = random.Random(31)
    starts = (ZH_FENS if variant == "crazyhouse"
              else [position_class(variant).starting_fen()])
    out = []
    while len(out) < n:
        pos = from_fen(starts[len(out) % len(starts)], variant)
        for _ in range(24):
            legal = pos.legal_moves()
            if not legal or pos.outcome() is not None:
                break
            loud = [m for m in legal
                    if m.drop is not None or m.promotion is not None]
            pos = pos.push(
                rng.choice(loud if loud and rng.random() < 0.5 else legal))
            out.append(pos)
    return out[:n]


def _running_state(params, width, variant, filler, seed):
    """A width-lane state with every field random, as host arrays: a
    lane that is not refilled has to come through bit for bit, and a
    refilled one owes nothing to what was there."""
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import stack_fields

    base = S._init_state_jit(
        params, stack_fields([filler] * width), np.zeros(width, np.int32),
        np.zeros(width, np.int32), SPLICE_PLY, variant)
    rng = np.random.default_rng(seed)
    return type(base)(*[
        rng.integers(-2**20, 2**20, a.shape).astype(a.dtype) for a in base])


def _admissions(rng, n, hist):
    """Per-lane operands of n admissions, helpers among them (jitter
    and a window on some rows), with or without history rows."""
    from fishnet_tpu.ops import search as S

    kw = dict(
        root_alpha=rng.integers(-900, 0, n).astype(np.int32),
        root_beta=rng.integers(1, 900, n).astype(np.int32),
        order_jitter=(rng.integers(0, 4, n)
                      * rng.integers(1, 60000, n)).astype(np.int32),
        group=rng.integers(0, 64, n).astype(np.int32),
    )
    if hist:
        kw["hist_hash"] = rng.integers(
            0, 2**32, (n, S.MAX_HIST, 2), dtype=np.uint32)
        kw["hist_halfmove"] = rng.integers(
            0, 60, (n, S.MAX_HIST)).astype(np.int32)
    return (rng.integers(1, 5, n).astype(np.int32),
            rng.integers(1, 10**6, n).astype(np.int32), kw)


def _parent_refill(params, state, roots, lane_idx, depth, budget, variant,
                   hist_hash=None, hist_halfmove=None, root_alpha=None,
                   root_beta=None, order_jitter=None, group=None):
    """`refill_lanes` of the parent commit (`_refill_fresh` +
    `_merge_lanes`), on host copies of `state`; → fields as numpy."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.ops import search as S

    B, n = state.lane.shape[0], len(lane_idx)
    take = np.zeros(B, np.int64)
    take[lane_idx] = np.arange(n)
    mask = np.zeros(B, bool)
    mask[lane_idx] = True
    tk = jnp.asarray(take)

    def expand(x, fill, dtype, tail=()):
        if x is None:
            x = np.full((n,) + tail, fill, dtype)
        return jnp.asarray(np.asarray(x))[tk]

    fresh = S._init_state_jit(
        params, jax.tree.map(lambda a: jnp.asarray(a)[tk], roots),
        expand(depth, 0, np.int32), expand(budget, 0, np.int32),
        state.bt.shape[1] - 1, variant,
        hist_hash=expand(hist_hash, 0, np.uint32, (S.MAX_HIST, 2)),
        hist_halfmove=expand(
            hist_halfmove, S.HIST_HM_SENTINEL, np.int32, (S.MAX_HIST,)),
        root_alpha=expand(root_alpha, -S.INF, np.int32),
        root_beta=expand(root_beta, S.INF, np.int32),
        order_jitter=expand(order_jitter, 0, np.int32),
        group=expand(group, 0, np.int32),
    )
    return type(state)(*[
        np.where(mask.reshape((B,) + (1,) * (old.ndim - 1)),
                 np.asarray(new), old)
        for old, new in zip(state, fresh)])


def _assert_states_equal(got, want):
    for name, g, w in zip(type(want)._fields, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


@pytest.mark.parametrize("hist", [False, True], ids=["nohist", "hist"])
@pytest.mark.parametrize("variant", ["standard", "crazyhouse"])
@pytest.mark.parametrize("width", [16, 64])
@pytest.mark.parametrize("count", [1, 7, "B"])
def test_splice_equals_parent_refill(splice_params, count, width, variant,
                                     hist):
    """The host-padded splice gives, field by field of SearchState, what
    the parent's gather + init + merge gave: scattered lanes, helpers'
    jitter and windows, pockets and promoted bits, history or none."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import (from_position, position_fields,
                                       stack_boards, stack_fields)

    n = width if count == "B" else count
    rng = np.random.default_rng(width * 131 + n)
    positions = _splice_positions(variant, n)
    rows = [position_fields(p) for p in positions]
    if variant == "crazyhouse" and n > 1:
        extra = np.stack([r.extra for r in rows])
        assert (extra[:, :10] > 0).any() and (extra[:, 10:] != 0).any()
    lane_idx = rng.permutation(width)[:n]
    depth, budget, kw = _admissions(rng, n, hist)
    host_state = _running_state(splice_params, width, variant, rows[0], n)
    want = _parent_refill(
        splice_params, host_state,
        stack_boards([from_position(p) for p in positions]), lane_idx,
        depth, budget, variant, **kw)
    got = S.refill_lanes(
        splice_params, jax.tree.map(jnp.asarray, host_state),
        stack_fields(rows), lane_idx, depth, budget, variant=variant, **kw)
    _assert_states_equal(got, want)
    # lanes that were not refilled: the running state's own rows
    keep = np.setdiff1d(np.arange(width), lane_idx)
    for g, old in zip(got, host_state):
        np.testing.assert_array_equal(np.asarray(g)[keep], old[keep])


@pytest.mark.parametrize("variant", ["standard", "crazyhouse"])
def test_device_rows_take_the_same_splice(splice_params, variant,
                                            monkeypatch):
    """Operands that already live on the device (search_stream's roots,
    the benchmark's warm-up board) are widened there and meet the same
    program with the same result as host rows."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import (from_position, position_fields,
                                       stack_boards, stack_fields)

    width, n = 16, 5
    rng = np.random.default_rng(5)
    positions = _splice_positions(variant, n)
    rows = [position_fields(p) for p in positions]
    lane_idx = rng.permutation(width)[:n]
    depth, budget, kw = _admissions(rng, n, True)
    host_state = _running_state(splice_params, width, variant, rows[0], 5)
    from_host = S.refill_lanes(
        splice_params, jax.tree.map(jnp.asarray, host_state),
        stack_fields(rows), lane_idx, depth, budget, variant=variant, **kw)
    # the program's body runs when it is traced, and only then
    traced = []
    merge = S._merge_lanes
    monkeypatch.setattr(
        S, "_merge_lanes", lambda *a: traced.append(1) or merge(*a))
    from_device = S.refill_lanes(
        splice_params, jax.tree.map(jnp.asarray, host_state),
        stack_boards([from_position(p) for p in positions]), lane_idx,
        jnp.asarray(depth), budget, variant=variant,
        **{k: jnp.asarray(v) for k, v in kw.items()})
    assert not traced
    _assert_states_equal(from_device, from_host)


def test_one_splice_program_whatever_the_count(splice_params):
    """After one splice at a width, splices of five other counts build
    or load no program: `compiles_refill` stays 0 on the engine the
    thread serves, and the jitted entry holds one executable per width
    and variant."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import position_fields, stack_fields

    make_refill_engine(max_depth=2)  # installs the compile listener
    entry = S._splice_lanes_jit.jit
    tot = {f"compiles_{site}": 0 for site in COMPILE_SITES}
    tot["compile_ms"] = 0.0
    built = []
    # widths no other test of this file splices at
    for width, variant in ((24, "standard"), (24, "crazyhouse"),
                           (40, "standard")):
        rows = [position_fields(p)
                for p in _splice_positions(variant, width)]
        rng = np.random.default_rng(width)
        state = jax.tree.map(jnp.asarray, _running_state(
            splice_params, width, variant, rows[0], width))

        def splice(state, n):
            depth, budget, kw = _admissions(rng, n, n % 2 == 0)
            return S.refill_lanes(
                splice_params, state, stack_fields(rows[:n]),
                rng.permutation(width)[:n], depth, budget,
                variant=variant, **kw)

        size = entry._cache_size()
        state = splice(state, 3)
        built.append(entry._cache_size() - size)
        with syncstats.serving(tot), syncstats.step("refill"):
            for n in (1, 2, 7, 11, width):
                state = splice(state, n)
            jax.block_until_ready(state)
        assert entry._cache_size() == size + 1, (width, variant)
    assert built == [1, 1, 1]
    for site in COMPILE_SITES:
        assert tot[f"compiles_{site}"] == 0, site
    assert tot["compile_ms"] == 0.0


def test_submit_stages_host_rows_and_makes_no_device_call():
    """A job's root is host arrays (`position_fields`), so `_submit`
    puts nothing on the device and runs nothing there: it passes under
    a guard that refuses every host-to-device transfer, explicit ones
    too, and builds no program."""
    import jax

    engine = make_refill_engine(max_depth=2)
    tot = engine.occupancy_totals
    chunk = make_chunk(analysis_work(depth=2), n_positions=4)
    with syncstats.serving(tot):
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            entry = engine._scheduler._submit(chunk)
    jobs = list(engine._scheduler._pending)
    assert len(jobs) == entry.n_open == 4
    for job in jobs:
        assert type(job.board).__name__ == "Board"
        for x in tuple(job.board) + (job.hh, job.hm):
            assert isinstance(x, (np.ndarray, np.generic)), type(x)
            assert not isinstance(x, jax.Array)
    for site in COMPILE_SITES:
        assert tot[f"compiles_{site}"] == 0, site
    # the guard itself bites: the device form of the same root raises
    from fishnet_tpu.ops.board import from_position

    with pytest.raises(Exception, match="[Dd]isallowed"):
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            from_position(jobs[0].pos)
    # and the queued jobs still run to their answers
    engine._scheduler._pending.clear()
    responses = run(engine, make_chunk(analysis_work(depth=2), 4))
    assert [r.position_index for r in responses] == [0, 1, 2, 3]


def test_refill_splices_tie_out():
    """`refill_splices` counts the boundaries that spliced and `refills`
    the lanes they spliced: both tie out with the log's `refilled`, and
    with the `lanes` argument of the `phase.refill` spans."""
    rec = obs_trace.install(obs_trace.TraceRecorder(capacity=16384))
    try:
        engine = make_refill_engine(max_depth=3)
        run(engine, make_chunk(analysis_work(depth=3), n_positions=4))
        run(engine, make_chunk(analysis_work(depth=2), n_positions=2))
    finally:
        obs_trace.uninstall()
    tot, log = engine.occupancy_totals, engine.occupancy_log
    assert tot["refill_splices"] == sum(1 for r in log if r["refilled"])
    assert tot["refills"] == sum(r["refilled"] for r in log)
    assert 0 < tot["refill_splices"] < tot["refills"]
    lanes = [e["args"]["lanes"] for e in rec.snapshot()
             if e["name"] == "phase.refill"]
    assert sum(lanes) == tot["refills"]
    assert sum(1 for n in lanes if n) == tot["refill_splices"]
    # tools/trace_report.py prints the phase by the same three numbers
    from tools import trace_report

    refill = trace_report.summarize(rec.snapshot())["refill"]
    assert refill["splices"] == tot["refill_splices"]
    assert refill["lanes"] == tot["refills"]
    assert refill["ms_per_splice"] * refill["splices"] == pytest.approx(
        tot["phase_refill_ms"], rel=0.01)
    assert tot["compiles_refill"] <= 1  # the first splice's own program


@pytest.mark.parametrize("variant", ["standard", "crazyhouse"])
def test_sharded_splice_matches_single_device(splice_params, variant):
    """The shard_map'd splice on the 8-device CPU mesh (each device
    rebuilds and merges its own two lanes) is bit-identical to the
    single-device one, and its result stays sharded by lane."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import position_fields, stack_fields
    from fishnet_tpu.parallel.mesh import (make_mesh, refill_lanes_sharded,
                                           shard_batch)

    mesh = make_mesh()
    assert mesh.devices.size == 8  # conftest's virtual devices
    width, n = 16, 7
    rng = np.random.default_rng(8)
    rows = [position_fields(p) for p in _splice_positions(variant, n)]
    lane_idx = rng.permutation(width)[:n]
    assert len(set(lane_idx // 2)) > 3  # several shards take part
    depth, budget, kw = _admissions(rng, n, True)
    host_state = _running_state(splice_params, width, variant, rows[0], 8)
    single = S.refill_lanes(
        splice_params, jax.tree.map(jnp.asarray, host_state),
        stack_fields(rows), lane_idx, depth, budget, variant=variant, **kw)
    sharded = refill_lanes_sharded(
        mesh, splice_params, shard_batch(mesh, host_state),
        stack_fields(rows), lane_idx, depth, budget, variant=variant, **kw)
    _assert_states_equal(sharded, single)
    assert len(sharded.lane.sharding.device_set) == 8
