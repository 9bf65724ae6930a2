"""Asynchronous segment pipeline (round 8) tests.

Four contracts from the pipeline change (ops/search.py packed boundary
summary + buffer donation, engine/tpu.py double-buffered LaneScheduler,
utils/syncstats.py):

1. Pipeline ON is bit-identical to the round-7 synchronous loop at both
   the ops level (search_stream) and the engine level (LaneScheduler):
   overlap and speculation must never change a result, only its timing.
2. Every submitted position gets exactly one PositionResponse even when
   boundaries are processed one segment behind the device (speculative
   dispatch) — no drops, no duplicates.
3. Buffer donation is real: the state handed to _run_segment_jit is dead
   after the call, and the jits always rebind to outputs (a use of the
   donated input is a bug this suite must catch before XLA does).
4. The pipelined boundary is cheap: one packed-summary transfer on a
   no-finish boundary at the stream level, and >= 5x fewer transfers
   than the synchronous loop at the engine level (ISSUE acceptance).
5. The host timeline of a session, in both loops: the phase counters
   decompose every boundary interval (they sum to host_ms + device_ms,
   wait is device_ms), sessions are host + device + set-up + tail, and
   with a recorder on each `phase.*` span was emitted where its work
   ran — inside its segment and session, overlapping no other — while
   the submit path leaves async pairs only. Results are bit-identical
   with the recorder on and off.

conftest.py pins REFILL=0/HELPERS=1; engine tests opt in via refill=True
exactly like tests/test_refill.py (mesh=None single-device scheduler).
"""
import asyncio
import os
import time

import numpy as np
import pytest

from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.tpu import PHASES, TpuEngine
from fishnet_tpu.obs import trace as obs_trace

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
GAME = ["e2e4", "c7c5", "g1f3", "d7d6", "d2d4"]


# ------------------------------------------------------------ ops level


def _stream_inputs(n=6, depth=2):
    import jax

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops.board import from_position, stack_boards

    params = nnue.init_params(jax.random.PRNGKey(0), l1=64,
                              feature_set="board768")
    boards, p = [], Position.from_fen(START)
    for uci in [None] + GAME:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    boards = boards[:n]
    roots = stack_boards(boards)
    depth_arr = np.full(n, depth, np.int32)
    budget = np.full(n, 200_000, np.int32)
    return params, roots, depth_arr, budget


@pytest.fixture(scope="module")
def stream_pair():
    """One search_stream run per mode over the same inputs; several
    tests assert against the pair (XLA:CPU runs are the slow part)."""
    from fishnet_tpu.ops import search as S

    params, roots, depth_arr, budget = _stream_inputs()
    out = {}
    for pipeline in (False, True):
        out[pipeline] = S.search_stream(
            params, roots, depth_arr, budget, max_ply=6, width=4,
            segment_steps=200, pipeline=pipeline)
    return out


def test_stream_bit_identity(stream_pair):
    """Same scores, moves, PVs and node counts with the pipeline on and
    off: speculation and summary-only boundaries are pure scheduling."""
    legacy, piped = stream_pair[False], stream_pair[True]
    assert bool(np.asarray(legacy["done"]).all())
    assert bool(np.asarray(piped["done"]).all())
    for key in ("score", "move", "nodes", "pv_len", "pv", "done"):
        np.testing.assert_array_equal(
            np.asarray(legacy[key]), np.asarray(piped[key]), err_msg=key)


def test_stream_pipelined_boundary_is_one_transfer(stream_pair):
    """A no-finish boundary in pipelined mode fetches exactly the packed
    summary — one transfer (the final boundary additionally drains
    results; refill boundaries pull the finished lanes' rows)."""
    occ = stream_pair[True]["occupancy"]
    assert occ, "no boundaries recorded"
    nofin = [o for o in occ[:-1] if o["refilled"] == 0]
    assert nofin, "shape produced no quiet boundaries; shrink the segment"
    assert all(o["transfers"] == 1 for o in nofin)
    # and the synchronous loop pays more at the same boundaries
    legacy_nofin = [o for o in stream_pair[False]["occupancy"][:-1]
                    if o["refilled"] == 0]
    assert min(o["transfers"] for o in legacy_nofin) >= 2


def test_stream_segment_auto_controller(monkeypatch):
    """segment_steps=None + FISHNET_TPU_SEGMENT=auto engages the
    measured-feedback controller and still finishes every position."""
    from fishnet_tpu.ops import search as S

    monkeypatch.setenv("FISHNET_TPU_SEGMENT", "auto")
    monkeypatch.setenv("FISHNET_TPU_SEGMENT_MIN", "64")
    monkeypatch.setenv("FISHNET_TPU_SEGMENT_MAX", "1024")
    params, roots, depth_arr, budget = _stream_inputs(n=4)
    out = S.search_stream(params, roots, depth_arr, budget, max_ply=6,
                          width=4, segment_steps=None, pipeline=True)
    assert bool(np.asarray(out["done"]).all())


def test_no_use_after_donate():
    """_run_segment_jit donates the state (and table): the input handles
    are dead after the call and any later use must raise, which pins the
    'always rebind to the outputs' discipline the engine relies on."""
    import jax

    from fishnet_tpu.ops import search as S

    params, roots, depth_arr, budget = _stream_inputs(n=4)
    state = S._init_state_jit(params, roots, depth_arr, budget, 6,
                              "standard")
    out_state, _, n, _summ = S._run_segment_jit(
        params, state, None, 50, "standard", False)
    jax.block_until_ready(out_state.lane)
    assert state.lane.is_deleted(), (
        "donated input still live: donate_argnums lost on _run_segment_jit")
    with pytest.raises(RuntimeError):
        np.asarray(state.lane)
    # the returned state is the live handle and remains usable
    assert np.asarray(out_state.lane).shape[0] == 4
    assert int(np.asarray(n)) > 0


# --------------------------------------------------------- engine level


def analysis_work(depth=3):
    return AnalysisWork(id="pipe01",
                        nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
                        timeout_s=30.0, depth=depth, multipv=None)


def make_chunk(work, n_positions=4):
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=START, moves=GAME[:i])
        for i in range(n_positions)
    ]
    return Chunk(work=work, deadline=time.monotonic() + 120,
                 variant="standard", flavor=EngineFlavor.TPU,
                 positions=positions)


def make_refill_engine(**kw):
    kw.setdefault("max_depth", 3)
    kw.setdefault("tt_size_log2", 0)
    kw.setdefault("helper_lanes", 1)
    engine = TpuEngine(refill=True, **kw)
    engine.mesh = None  # single-device semantics (mesh suite is separate)
    engine.n_dev = 1
    return engine


@pytest.fixture(scope="module")
def engine_pair():
    """One LaneScheduler chunk per pipeline mode at a small segment (many
    boundaries, so the speculative path actually engages), and the same
    chunk again with a recorder on: out["traced", mode] holds
    (responses, the ring's events, totals)."""
    saved = {k: os.environ.get(k)
             for k in ("FISHNET_TPU_PIPELINE", "FISHNET_TPU_SEGMENT")}
    out = {}
    try:
        os.environ["FISHNET_TPU_SEGMENT"] = "200"
        for mode in ("0", "1"):
            os.environ["FISHNET_TPU_PIPELINE"] = mode
            eng = make_refill_engine()
            resp = asyncio.run(eng.go_multiple(
                make_chunk(analysis_work(depth=3), n_positions=4)))
            out[mode] = (resp, list(eng.occupancy_log),
                         dict(eng.occupancy_totals))
            rec = obs_trace.install(obs_trace.TraceRecorder(capacity=65536))
            try:
                eng = make_refill_engine()
                resp = asyncio.run(eng.go_multiple(
                    make_chunk(analysis_work(depth=3), n_positions=4)))
            finally:
                obs_trace.uninstall()
            out["traced", mode] = (resp, rec.snapshot(),
                                   dict(eng.occupancy_totals))
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def _flat(resps):
    return [(r.position_index, r.best_move, r.depth, r.nodes,
             r.scores.matrix, r.pvs.matrix) for r in resps]


def test_engine_exactly_once_under_speculation(engine_pair):
    """Every position answers exactly once even when the host stages
    admissions one segment behind the speculatively-dispatched device."""
    for mode in ("0", "1"):
        resp, _log, totals = engine_pair[mode]
        assert sorted(r.position_index for r in resp) == [0, 1, 2, 3]
        assert all(r.best_move for r in resp)
        assert totals["positions_done"] == 4


def test_engine_bit_identity(engine_pair):
    """Scheduler results are identical with the pipeline on and off:
    same best moves, scores, depths, node counts and PVs."""
    assert _flat(engine_pair["0"][0]) == _flat(engine_pair["1"][0])


def test_engine_boundary_transfer_reduction(engine_pair):
    """ISSUE acceptance: >= 5x fewer host transfers per no-finish
    boundary. The synchronous loop fetches the step count, the DONE mask
    and the six extract_results arrays every boundary; the pipelined
    loop fetches one packed summary."""
    quiet = {}
    for mode in ("0", "1"):
        log = engine_pair[mode][1]
        nofin = [r["transfers"] for r in log if r["refilled"] == 0]
        assert nofin, f"mode {mode}: no quiet boundaries recorded"
        # rows where a lane parked for re-admission also count
        # refilled == 0 (the admission lands in the NEXT row) but pay a
        # PV pull; the steady-state no-finish cost is the row minimum
        quiet[mode] = min(nofin)
    assert quiet["0"] >= 5 * quiet["1"], quiet
    # even the engine's most expensive pipelined boundary (summary + PV
    # pull) undercuts the synchronous loop's cheapest one
    assert max(r["transfers"] for r in engine_pair["1"][1]) < quiet["0"]
    # occupancy rows carry the host/device split for both modes
    for mode in ("0", "1"):
        row = engine_pair[mode][1][0]
        for key in ("transfers", "host_ms", "device_ms"):
            assert key in row


# ------------------------------------------------- the host's timeline


@pytest.mark.parametrize("mode", ["0", "1"])
def test_engine_phase_counters_tie_out(engine_pair, mode):
    """Every boundary interval is decomposed, none of it dropped: the
    phase totals sum to host_ms + device_ms, the wait phase IS
    device_ms, and a session is its boundaries plus set-up and tail."""
    for totals in (engine_pair[mode][2], engine_pair["traced", mode][2]):
        in_boundaries = totals["host_ms"] + totals["device_ms"]
        assert in_boundaries > 0
        assert sum(totals[f"phase_{p}_ms"] for p in PHASES) == pytest.approx(
            in_boundaries, rel=0.01)
        assert totals["phase_wait_ms"] == totals["device_ms"]
        for p in ("admit", "refill", "dispatch", "lanes", "account"):
            assert totals[f"phase_{p}_ms"] > 0, p
        assert totals["sessions"] >= 1
        assert totals["session_setup_ms"] > 0 and totals["session_tail_ms"] > 0
        assert totals["session_ms"] == pytest.approx(
            in_boundaries + totals["session_setup_ms"]
            + totals["session_tail_ms"], rel=0.01)
        assert totals["chunks_submitted"] == 1
        assert totals["positions_submitted"] == 4
        assert 0 < (totals["submit_replay_ms"] + totals["submit_history_ms"]
                    ) <= totals["submit_ms"]


@pytest.mark.parametrize("mode", ["0", "1"])
def test_engine_phase_spans_are_where_the_work_ran(engine_pair, mode):
    events = engine_pair["traced", mode][1]
    names = {e["name"] for e in events}
    assert "segment.device" not in names and "segment.host" not in names
    assert "segment.dispatch" not in names  # it is phase.dispatch now

    def inside(e, outer):
        return (outer["ts"] <= e["ts"] + 1e-3 and
                e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3)

    spans = [e for e in events if e["ph"] == "X"]
    phases = [e for e in spans if e["name"].startswith("phase.")]
    segments = [e for e in spans if e["name"] == "segment"]
    sessions = [e for e in spans if e["name"] == "session"]
    assert phases and segments and sessions
    assert {e["name"] for e in phases} >= {
        "phase.admit", "phase.refill", "phase.dispatch", "phase.lanes",
        "phase.account"}
    for s in sessions:
        assert s["args"]["width"] >= 4 and s["args"]["pending"] == 4
        assert s["args"]["segments"] >= 1 and s["args"]["steps"] > 0
    assert sum(s["args"]["positions"] for s in sessions) == 4
    for e in phases:
        mine = [s for s in sessions if inside(e, s)]
        assert len(mine) == 1, e
        # after the session's last boundary (its tail) no interval is
        # open: everything earlier lies in exactly one segment span
        last = max(g["ts"] + g["dur"] for g in segments if inside(g, mine[0]))
        if e["ts"] + 1e-3 < last:
            assert sum(inside(e, g) for g in segments) == 1, e
    # every scheduler span with a duration lies inside a session
    for e in spans:
        if e["name"] == "fetch" or e["name"] == "segment":
            assert any(inside(e, s) for s in sessions), e
    # self times: no two phase spans of one thread overlap
    by_tid = {}
    for e in phases:
        by_tid.setdefault(e["tid"], []).append(e)
    for track in by_tid.values():
        track.sort(key=lambda e: e["ts"])
        for a, b in zip(track, track[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-3, (a, b)
    dispatches = [e for e in phases if e["name"] == "phase.dispatch"]
    assert all(e["args"]["steps"] == 200 for e in dispatches)
    if mode == "1":
        assert any(e["args"]["speculative"] for e in dispatches)
    # the submit path runs while no session does: async pairs, no span
    submits = [e for e in events if e["name"].startswith("submit")]
    assert submits and all(e["ph"] in ("b", "e") for e in submits)
    for name in ("submit", "submit.replay", "submit.history"):
        ends = [e["ph"] for e in submits if e["name"] == name]
        assert ends.count("b") == ends.count("e") > 0, name
    assert len({e["id"] for e in submits}) == 1
    first_session = min(s["ts"] for s in sessions)
    assert all(e["ts"] <= first_session for e in submits)
    # and the report's cross-check holds on a real session
    from tools import trace_report

    report = trace_report.summarize(events)
    assert trace_report.crosscheck(report, tolerance=0.01) == []
    assert len(report["sessions"]) == len(sessions)


@pytest.mark.parametrize("mode", ["0", "1"])
def test_engine_bit_identity_recorder_on_off(engine_pair, mode):
    """Tracing is bookkeeping: the new phase, session and submit sites
    change no result."""
    assert _flat(engine_pair[mode][0]) == _flat(engine_pair["traced", mode][0])
