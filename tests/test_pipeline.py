"""Asynchronous segment boundaries: the one loop of search_stream and
of the engine's LaneScheduler (ops/search.py packed boundary summary +
buffer donation, engine/tpu.py _Session, utils/syncstats.py).

1. Every submitted position gets exactly one PositionResponse even when
   boundaries are processed one segment behind the device (speculative
   dispatch) — no drops, no duplicates. That the results are the right
   ones is held against independent paths elsewhere: the chunk-serial
   engine (tests/test_refill.py::test_refill_on_matches_refill_off),
   search_batch (test_search_stream_matches_batch), the benchmark's
   plain reference (tests/benchmark/).
2. Buffer donation is real: the state handed to _run_segment_jit is dead
   after the call, and the jits always rebind to outputs (a use of the
   donated input is a bug this suite must catch before XLA does).
3. The boundary is cheap: one packed-summary transfer on a quiet
   boundary, at the stream level and in the scheduler, whose dearest
   boundary adds the PV block and its lengths and nothing else.
4. The host timeline of a session: the phase counters decompose every
   boundary interval (they sum to host_ms + device_ms, wait is
   device_ms), sessions are host + device + set-up + tail, and with a
   recorder on each `phase.*` span was emitted where its work ran —
   inside its segment and session, overlapping no other — while the
   submit path leaves async pairs only. Results are bit-identical with
   the recorder on and off.

Engine tests build the single-device scheduler (mesh=None) with no
helper coupling, exactly like tests/test_refill.py.
"""
import asyncio
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


def test_stream_pipelined_boundary_is_one_transfer():
    """A no-finish boundary fetches exactly the packed summary — one
    transfer (the final boundary additionally drains results; refill
    boundaries pull the finished lanes' rows)."""
    from fishnet_tpu.ops import search as S

    params, roots, depth_arr, budget = _stream_inputs()
    out = S.search_stream(params, roots, depth_arr, budget, max_ply=6,
                          width=4, segment_steps=200)
    assert bool(np.asarray(out["done"]).all())
    occ = out["occupancy"]
    assert occ, "no boundaries recorded"
    nofin = [o for o in occ[:-1] if o["refilled"] == 0]
    assert nofin, "shape produced no quiet boundaries; shrink the segment"
    assert all(o["transfers"] == 1 for o in nofin)


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
    """One LaneScheduler chunk at a small segment (many boundaries, so
    the speculative path actually engages): out["plain"] holds
    (responses, occupancy log, totals), and the same chunk again with a
    recorder on: out["traced"] holds (responses, the ring's events,
    totals)."""
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("FISHNET_TPU_SEGMENT", "200")
        eng = make_refill_engine()
        resp = asyncio.run(eng.go_multiple(
            make_chunk(analysis_work(depth=3), n_positions=4)))
        out["plain"] = (resp, list(eng.occupancy_log),
                        dict(eng.occupancy_totals))
        rec = obs_trace.install(obs_trace.TraceRecorder(capacity=65536))
        try:
            eng = make_refill_engine()
            resp = asyncio.run(eng.go_multiple(
                make_chunk(analysis_work(depth=3), n_positions=4)))
        finally:
            obs_trace.uninstall()
        out["traced"] = (resp, rec.snapshot(), dict(eng.occupancy_totals))
    return out


def _flat(resps):
    return [(r.position_index, r.best_move, r.depth, r.nodes,
             r.scores.matrix, r.pvs.matrix) for r in resps]


def test_engine_exactly_once_under_speculation(engine_pair):
    """Every position answers exactly once even when the host stages
    admissions one segment behind the speculatively-dispatched device."""
    resp, _log, totals = engine_pair["plain"]
    assert sorted(r.position_index for r in resp) == [0, 1, 2, 3]
    assert all(r.best_move for r in resp)
    assert totals["positions_done"] == 4


def test_engine_boundary_transfer_reduction(engine_pair):
    """A quiet boundary of the scheduler fetches exactly one array, the
    packed summary; its dearest boundary adds the PV block and its
    lengths (flush_pv) and nothing else: three. A loop that brought the
    step count, the DONE mask and the six result arrays home paid eight
    at its cheapest."""
    log = engine_pair["plain"][1]
    nofin = [r["transfers"] for r in log if r["refilled"] == 0]
    assert nofin, "no quiet boundaries recorded"
    # rows where a lane parked for re-admission also count refilled == 0
    # (the admission lands in the NEXT row) but pay a PV pull; the
    # steady-state no-finish cost is the row minimum
    assert min(nofin) == 1
    assert max(r["transfers"] for r in log) == 3
    assert {r["transfers"] for r in log} == {1, 3}
    # occupancy rows carry the host/device split
    for key in ("transfers", "host_ms", "device_ms"):
        assert key in log[0]


# ------------------------------------------------- the host's timeline


def test_engine_phase_counters_tie_out(engine_pair):
    """Every boundary interval is decomposed, none of it dropped: the
    phase totals sum to host_ms + device_ms, the wait phase IS
    device_ms, and a session is its boundaries plus set-up and tail."""
    for totals in (engine_pair["plain"][2], engine_pair["traced"][2]):
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


def test_engine_phase_spans_are_where_the_work_ran(engine_pair):
    events = engine_pair["traced"][1]
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


def test_engine_bit_identity_recorder_on_off(engine_pair):
    """Tracing is bookkeeping: the new phase, session and submit sites
    change no result."""
    assert _flat(engine_pair["plain"][0]) == _flat(engine_pair["traced"][0])
