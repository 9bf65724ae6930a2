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

conftest.py sets FISHNET_TPU_REFILL=0, so engines here opt in explicitly
with refill=True. This file pins the SINGLE-DEVICE scheduler semantics:
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
    """refill=None defers to FISHNET_TPU_REFILL, which conftest pins to 0;
    an explicit constructor argument wins over the registry."""
    assert TpuEngine(max_depth=2, tt_size_log2=0).refill is False
    assert TpuEngine(max_depth=2, tt_size_log2=0, refill=True).refill is True


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
