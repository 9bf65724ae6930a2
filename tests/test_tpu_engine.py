"""TPU engine tests: chunk in, protocol-complete responses out."""
import asyncio
import time

import pytest

from fishnet_tpu.chess import Position
from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import (
    AnalysisWork,
    EngineFlavor,
    MoveWork,
    NodeLimit,
    SkillLevel,
)
from fishnet_tpu.engine.tpu import TpuEngine

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
GAME = ["e2e4", "c7c5", "g1f3", "d7d6"]


@pytest.fixture(scope="module")
def engine():
    return TpuEngine(max_depth=3)


@pytest.fixture(scope="module")
def engines(engine):
    """refill → engine. The module's engine sends single-pv analysis
    through the LaneScheduler (the registry default, as deployed); the
    other one takes the chunk-serial path (`_analyse_single`)."""
    assert engine.refill is True
    return {True: engine, False: TpuEngine(max_depth=3, refill=False)}


def make_chunk(work, n_positions=3, moves=GAME, variant="standard"):
    positions = [
        WorkPosition(
            work=work, position_index=i, url=None, skip=False,
            root_fen=START, moves=moves[:i],
        )
        for i in range(n_positions)
    ]
    return Chunk(
        work=work, deadline=time.monotonic() + 120, variant=variant,
        flavor=EngineFlavor.TPU, positions=positions,
    )


def analysis_work(depth=3, multipv=None):
    return AnalysisWork(
        id="tpujob01",
        nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
        timeout_s=30.0,
        depth=depth,
        multipv=multipv,
    )


def run(engine, chunk):
    return asyncio.run(engine.go_multiple(chunk))


@pytest.mark.parametrize("refill", [False, True])
def test_analysis_chunk(engines, refill):
    engine = engines[refill]
    responses = run(engine, make_chunk(analysis_work(depth=3)))
    assert len(responses) == 3
    for i, res in enumerate(responses):
        assert res.position_index == i
        assert res.depth == 3
        assert res.nodes > 0
        best_score = res.scores.best()
        assert best_score is not None and best_score.kind in ("cp", "mate")
        # per-depth rows populated for depths 1..3
        assert res.scores.matrix[0][1] is not None
        assert res.scores.matrix[0][3] is not None
        # pv must be a legal line from the position
        pos = Position.from_fen(START)
        for uci in GAME[:i]:
            pos = pos.push(pos.parse_uci(uci))
        pv = res.pvs.best()
        assert pv, "empty pv"
        for uci in pv:
            pos = pos.push(pos.parse_uci(uci))
        assert res.best_move == pv[0]


def test_multipv_lane_ceiling_splits_dispatches():
    """docs/tpu-hang.md round 5: ~1024 lanes is the v5e ceiling. With a
    tiny ceiling, a multipv chunk whose root moves exceed it must be
    split into sequential dispatch groups — with a warning — and still
    produce complete responses for every position. The device program is
    stubbed: the partitioning is host-side logic and must be testable
    without a dispatch."""
    import numpy as np

    class WarnCatcher:
        def __init__(self):
            self.messages = []

        def warn(self, msg):
            self.messages.append(msg)

    sparse = "4k3/8/8/8/8/8/4P3/4K3 w - - 0 1"  # 6 legal moves
    logger = WarnCatcher()
    engine = TpuEngine(max_depth=2, max_lanes=16, logger=logger)
    dispatches = []

    def fake_search(roots, depth_arr, budget_arr, deadline=None, **kw):
        B = len(depth_arr)
        dispatches.append(B)
        return {
            "done": np.ones(B, bool),
            "score": np.full(B, 20, np.int32),
            "pv": np.full((B, 4), -1, np.int32),
            "pv_len": np.zeros(B, np.int32),
            "nodes": np.ones(B, np.int32),
        }

    engine._search = fake_search
    work = analysis_work(depth=1, multipv=2)
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=sparse, moves=[])
        for i in range(3)  # 18 lanes total: 16-lane ceiling forces a split
    ]
    chunk = Chunk(
        work=work, deadline=time.monotonic() + 120, variant="standard",
        flavor=EngineFlavor.TPU, positions=positions,
    )
    responses = run(engine, chunk)
    assert len(responses) == 3
    for res in responses:
        assert res.depth == 1
        assert res.best_move is not None
        assert res.scores.best() is not None
        assert len(res.scores.matrix) == 2  # multipv rows intact
    # two dispatch groups (12 + 6 lanes), one depth iteration each
    assert len(dispatches) == 2
    assert any("lanes" in m and "splitting" in m for m in logger.messages)


def test_multipv_chunk(engine):
    responses = run(engine, make_chunk(analysis_work(depth=2, multipv=3), n_positions=2))
    for res in responses:
        assert len(res.scores.matrix) == 3  # three ranked rows
        # rank 1 must be >= rank 2 >= rank 3 at the final depth
        def val(rank):
            s = res.scores.matrix[rank][-1]
            return (1000000 - s.value) if s.kind == "mate" and s.value > 0 else (
                -1000000 - s.value if s.kind == "mate" else s.value
            )
        assert val(0) >= val(1) >= val(2)


@pytest.mark.parametrize("refill", [False, True])
def test_terminal_position(engines, refill):
    engine = engines[refill]
    # fool's mate final position: mate 0 at depth 0
    moves = ["f2f3", "e7e5", "g2g4", "d8h4"]
    work = analysis_work(depth=3)
    positions = [
        WorkPosition(work=work, position_index=0, url=None, skip=False,
                     root_fen=START, moves=moves)
    ]
    chunk = Chunk(work=work, deadline=time.monotonic() + 60,
                  variant="standard", flavor=EngineFlavor.TPU, positions=positions)
    (res,) = run(engine, chunk)
    assert res.depth == 0
    assert res.scores.best().kind == "mate" and res.scores.best().value == 0
    assert res.best_move is None


@pytest.mark.parametrize("refill", [False, True])
def test_mate_in_one_found(engines, refill):
    engine = engines[refill]
    work = analysis_work(depth=2)
    positions = [
        WorkPosition(work=work, position_index=0, url=None, skip=False,
                     root_fen="6k1/5ppp/8/8/8/8/8/4R2K w - - 0 1", moves=[])
    ]
    chunk = Chunk(work=work, deadline=time.monotonic() + 60,
                  variant="standard", flavor=EngineFlavor.TPU, positions=positions)
    (res,) = run(engine, chunk)
    assert res.best_move == "e1e8"
    assert res.scores.best().kind == "mate" and res.scores.best().value == 1


def test_time_apportionment():
    """Per-position time is the chunk's shared wall-clock split by node
    share (round-3 advisor flag: a uniform elapsed/len split misstates
    per-position nps on lichess's display). Sums to the chunk elapsed;
    implied nps is uniform across positions of one dispatch."""
    times = TpuEngine._apportion_time(2.0, [100, 300, 0])
    assert times == [0.5, 1.5, 0.0]
    assert abs(sum(times) - 2.0) < 1e-9
    # degenerate: no nodes anywhere → uniform split, still sums
    assert TpuEngine._apportion_time(1.2, [0, 0]) == [0.6, 0.6]


def test_skill_pick_weakens():
    """skill_pick at full strength always takes the top move; at low
    skill it samples weaker near-best moves (the engine's lichess skill
    analog — validated at game level by tools/strength_ab.py --skill)."""
    import random

    from fishnet_tpu.engine.tpu import skill_pick

    ranked = [(50, 0), (40, 1), (-20, 2), (-500, 3)]
    assert skill_pick(ranked, 20, random.Random(1)) == (50, 0)
    picks = {
        skill_pick(ranked, -9, random.Random(s))[1] for s in range(200)
    }
    assert len(picks) > 1, "low skill never deviated from the top move"
    # the hopeless move stays outside the 3×weakness acceptance window
    assert 3 not in picks


def test_move_job(engine):
    work = MoveWork(id="tpumv001", level=SkillLevel(8))
    positions = [
        WorkPosition(work=work, position_index=0, url=None, skip=False,
                     root_fen=START, moves=["e2e4", "e7e5"])
    ]
    chunk = Chunk(work=work, deadline=time.monotonic() + 60,
                  variant="standard", flavor=EngineFlavor.TPU, positions=positions)
    (res,) = run(engine, chunk)
    pos = Position.from_fen(START).push_uci("e2e4").push_uci("e7e5")
    legal = {m.uci() for m in pos.legal_moves()}
    assert res.best_move in legal
