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
5. The segment's stop rule: a segment with watched lanes ends when the
   last of them parks, and leaves the others untouched, so a watched
   segment and the next one are one longer segment, bit for bit; with
   no lane watched it is the plain segment.

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


def _parking_lanes():
    """Six lanes that park at different steps: depths 1-2, budgets from
    a few dozen nodes up."""
    from fishnet_tpu.ops import search as S

    params, roots, _depth, _budget = _stream_inputs(n=6)
    depth_arr = np.array([1, 2, 1, 2, 2, 1], np.int32)
    budget = np.array([40, 400, 5, 60, 10_000, 200], np.int32)

    def fresh():
        return S._init_state_jit(params, roots, depth_arr, budget, 6,
                                 "standard")
    return params, fresh


def test_run_column_counts_each_lanes_steps_not_done():
    """The summary's run column (SUM_RUN) of one segment equals, lane by
    lane, the steps before which the lane was not DONE, taken by
    stepping the same lanes one step a segment; and a lane once DONE
    stays DONE inside the segment, which the count of steps a segment
    runs after its last primary parks rests on."""
    from fishnet_tpu.ops import search as S

    params, fresh = _parking_lanes()
    state, _tt, n, summ = S._run_segment_jit(
        params, fresh(), None, 10_000, "standard", False)
    n, summ = int(np.asarray(n)), np.asarray(summ)
    B = summ.shape[0] - S.SUM_TAIL
    assert summ.shape == (B + S.SUM_TAIL, S.SUM_W) and summ[B, S.SUM_DONE] == n
    run = summ[:B, S.SUM_RUN]
    assert summ[:B, S.SUM_DONE].all() and run.max() == n
    assert len(set(run.tolist())) > 2  # the lanes parked at different steps
    assert (summ[B:, S.SUM_RUN] == 0).all()  # the tail rows hold none

    state = fresh()
    counted = np.zeros(B, np.int64)
    done_before = np.zeros(B, bool)
    for _ in range(n):
        done = np.asarray(state.lane)[:, S.LN_MODE] == S.MODE_DONE
        assert (done | ~done_before).all(), "a DONE lane left DONE"
        done_before = done
        counted += ~done
        state, _tt, one, step_summ = S._run_segment_jit(
            params, state, None, 1, "standard", False)
        step_summ = np.asarray(step_summ)
        assert int(np.asarray(one)) == 1
        np.testing.assert_array_equal(step_summ[:B, S.SUM_RUN], ~done)
    assert (np.asarray(state.lane)[:, S.LN_MODE] == S.MODE_DONE).all()
    np.testing.assert_array_equal(counted, run)


def _segment(params, state, table, steps, watch=None):
    """One segment of `_run_segment_jit` → (state, table, steps, the
    summary as numpy); `watch` passed only where given."""
    from fishnet_tpu.ops import search as S

    extra = () if watch is None else (False, 0, watch)
    state, table, n, summ = S._run_segment_jit(
        params, state, table, steps, "standard", False, *extra)
    return state, table, int(np.asarray(n)), np.asarray(summ)


def _same_arrays(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _table(with_table):
    from fishnet_tpu.ops import tt

    return tt.make_table(8) if with_table else None


@pytest.mark.parametrize("watched", [[0, 2, 5], [3], [0, 1, 2, 3, 5]],
                         ids=["three", "one", "all-but-one"])
def test_watched_segment_stops_when_its_last_watched_lane_parks(watched):
    """With watch set on some lanes the segment runs exactly as many
    steps as its longest-running watched lane: every watched lane is
    DONE, and a lane that is not watched is left running."""
    from fishnet_tpu.ops import search as S

    params, fresh = _parking_lanes()
    watch = np.zeros(6, bool)
    watch[watched] = True
    _state, _tt, n, summ = _segment(params, fresh(), None, 10_000, watch)
    B = summ.shape[0] - S.SUM_TAIL
    run, done = summ[:B, S.SUM_RUN], summ[:B, S.SUM_DONE].astype(bool)
    assert summ[B, S.SUM_DONE] == n == run[watch].max() > 0
    assert done[watch].all()
    assert not done[~watch].all()
    assert (run[~watch & ~done] == n).all()


@pytest.mark.parametrize("with_table", [False, True],
                         ids=["no-table", "table"])
def test_watched_then_plain_segment_is_one_longer_segment(with_table):
    """A segment stopped by its watched lanes, followed with no splice
    between by a plain one, computes bit for bit what one plain segment
    of the summed length computes: state, table, the run column and
    the counters, summed over the two."""
    from fishnet_tpu.ops import search as S

    params, fresh = _parking_lanes()
    watch = np.array([1, 0, 1, 0, 0, 1], bool)
    state, table, n1, s1 = _segment(
        params, fresh(), _table(with_table), 10_000, watch)
    state, table, n2, s2 = _segment(params, state, table, 7)
    B = s1.shape[0] - S.SUM_TAIL
    assert 0 < n1 and n2 == 7
    assert not s2[:B, S.SUM_DONE].all()  # the cut falls inside the run
    one_state, one_table, n, s = _segment(
        params, fresh(), _table(with_table), n1 + n2)
    assert n == n1 + n2
    _same_arrays(state, one_state)
    _same_arrays(table, one_table)
    np.testing.assert_array_equal(s2[:B, :S.SUM_RUN], s[:B, :S.SUM_RUN])
    np.testing.assert_array_equal(
        s1[:B, S.SUM_RUN] + s2[:B, S.SUM_RUN], s[:B, S.SUM_RUN])
    np.testing.assert_array_equal(
        s1[B:, S.SUM_COUNTS] + s2[B:, S.SUM_COUNTS], s[B:, S.SUM_COUNTS])


@pytest.mark.parametrize("with_table", [False, True],
                         ids=["no-table", "table"])
def test_unwatched_segment_is_the_plain_one(with_table):
    """A watch mask that marks no lane gives the plain segment, bit for
    bit: state, table and summary."""
    params, fresh = _parking_lanes()
    for steps in (60, 10_000):
        state, table, n, summ = _segment(
            params, fresh(), _table(with_table), steps, np.zeros(6, bool))
        p_state, p_table, p_n, p_summ = _segment(
            params, fresh(), _table(with_table), steps)
        assert n == p_n > 0
        np.testing.assert_array_equal(summ, p_summ)
        _same_arrays(state, p_state)
        _same_arrays(table, p_table)


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
