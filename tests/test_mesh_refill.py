"""Sharded scheduler (mesh parity) tests on the 8-device virtual CPU mesh.

The mesh-parity change (parallel/mesh.py sharded segment/refill/merge
callables, ops/search.py search_stream(mesh=...), engine/tpu.py
shard-aware LaneScheduler) promises that multi-chip hosts get the same
occupancy stack single-device hosts got in rounds 7-8, without changing
a single result. Contracts pinned here:

1. Shard-local refill is bit-identical to chunk-serial dispatch and to
   the single-device stream: resplicing lanes per shard is pure
   scheduling, never search behavior.
2. Under a mesh a no-finish boundary still costs exactly one host
   transfer (the stacked per-shard summary is one fetch).
3. The sharded segment donates its operands like the single-device jit:
   inputs are dead after the call, callers must rebind to outputs.
4. Every position answers exactly once even when lanes finish on
   different shards at different boundaries (staggered depths), and the
   engine's padding handles position counts that don't divide over the
   mesh (B % ndev edge cases ride through _pad).
5. On a staggered-depth workload, refill keeps mean live-lane occupancy
   strictly above the chunk-serial mesh path (the point of the change).

conftest.py forces 8 virtual CPU devices (the `mesh` marker documents
the requirement); engines here say refill=True or False and keep the mesh
conftest provides.
"""
import asyncio
import threading
import time

import numpy as np
import pytest

from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.tpu import TpuEngine

# `slow` keeps the ~2 min of sharded compiles out of the quick tier's
# wall-clock budget; CI runs the module in its own step (-m mesh with
# addopts overridden), and `pytest -m mesh` runs it locally.
pytestmark = [pytest.mark.mesh, pytest.mark.slow]

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
# 11 plies of a Najdorf: START + prefixes give 12 distinct positions
GAME = ["e2e4", "c7c5", "g1f3", "d7d6", "d2d4", "c5d4", "f3d4", "g8f6",
        "b1c3", "a7a6", "f1e2"]
N_POS = 12
WIDTH = 8
# staggered depths: lanes park at different boundaries on different
# shards, so refill decisions and shard-local merges actually interleave
DEPTHS = np.asarray([1, 3, 1, 2, 3, 1, 2, 1, 3, 1, 2, 1], np.int32)


def _inputs():
    import jax

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops.board import from_position, stack_boards

    params = nnue.init_params(jax.random.PRNGKey(3), l1=64,
                              feature_set="board768")
    boards, p = [], Position.from_fen(START)
    for uci in [None] + GAME:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    assert len(boards) == N_POS
    return params, stack_boards(boards)


# ------------------------------------------------------------ ops level


@pytest.fixture(scope="module")
def mesh_streams():
    """One set of search_stream runs over the same staggered workload:
    single-device baseline, the sharded stream, and the
    chunk-serial mesh baseline (same width, each chunk fits, so no
    refill ever fires). Several tests assert against the set — the
    XLA:CPU runs are the slow part, the asserts are free."""
    import jax

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.parallel.mesh import make_mesh

    params, roots = _inputs()
    budget = np.full(N_POS, 200_000, np.int32)
    mesh = make_mesh()
    kw = dict(max_ply=6, width=WIDTH, segment_steps=150)
    out = {
        "base": S.search_stream(params, roots, DEPTHS, budget, **kw),
        "mesh_piped": S.search_stream(params, roots, DEPTHS, budget,
                                      mesh=mesh, **kw),
    }
    serial = {"occupancy": [], "score": [], "move": [], "nodes": [],
              "pv_len": [], "pv": []}
    for lo in range(0, N_POS, WIDTH):
        hi = min(lo + WIDTH, N_POS)
        sub = jax.tree.map(lambda a: a[lo:hi], roots)
        r = S.search_stream(params, sub, DEPTHS[lo:hi], budget[lo:hi],
                            mesh=mesh, **kw)
        assert r["refills"] == 0, "chunk-serial baseline must never refill"
        serial["occupancy"].extend(r["occupancy"])
        for key in ("score", "move", "nodes", "pv_len", "pv"):
            serial[key].append(np.asarray(r[key]))
    for key in ("score", "move", "nodes", "pv_len", "pv"):
        serial[key] = np.concatenate(serial[key])
    out["serial"] = serial
    return out


def test_stream_mesh_matches_single_device(mesh_streams):
    """Sharded dispatch is bit-identical to the single-device stream:
    same scores, moves, PVs and node counts position by position."""
    base, sharded = mesh_streams["base"], mesh_streams["mesh_piped"]
    assert bool(np.asarray(base["done"]).all())
    assert bool(np.asarray(sharded["done"]).all())
    for key in ("score", "move", "nodes", "pv_len", "pv", "done"):
        np.testing.assert_array_equal(
            np.asarray(base[key]), np.asarray(sharded[key]), err_msg=key)


def test_stream_mesh_refill_matches_chunk_serial(mesh_streams):
    """ISSUE acceptance: shard-local refill reproduces the chunk-serial
    mesh path exactly — refill is scheduling, not search."""
    refill, serial = mesh_streams["mesh_piped"], mesh_streams["serial"]
    assert refill["refills"] >= N_POS - WIDTH
    for key in ("score", "move", "nodes", "pv_len", "pv"):
        np.testing.assert_array_equal(
            np.asarray(refill[key]), serial[key], err_msg=key)


def test_stream_mesh_occupancy_shard_columns(mesh_streams):
    """Mesh occupancy rows carry per-shard live/refilled/steps lists (one
    entry per device) consistent with the scalar columns."""
    occ = mesh_streams["mesh_piped"]["occupancy"]
    assert occ, "no boundaries recorded"
    for row in occ:
        for key in ("shard_live", "shard_refilled", "shard_steps"):
            assert len(row[key]) == 8, key
        assert sum(row["shard_live"]) == row["live"]
        assert sum(row["shard_refilled"]) == row["refilled"]
        assert max(row["shard_steps"]) == row["steps"]
    # the single-device run must NOT grow shard columns
    assert "shard_live" not in mesh_streams["base"]["occupancy"][0]


def test_stream_mesh_pipelined_boundary_is_one_transfer(mesh_streams):
    """ISSUE acceptance: a no-finish boundary under a mesh is ONE host
    transfer — the stacked (ndev, local+1, 4) summary
    comes back as a single fetch, not one per shard."""
    occ = mesh_streams["mesh_piped"]["occupancy"]
    nofin = [o for o in occ[:-1] if o["refilled"] == 0]
    assert nofin, "shape produced no quiet boundaries; shrink the segment"
    assert all(o["transfers"] == 1 for o in nofin)


def _mean_live_occupancy(rows):
    """Steps-weighted mean fraction of lanes live across boundaries."""
    lane_steps = sum(r["live"] * r["steps"] for r in rows)
    total = sum(WIDTH * r["steps"] for r in rows)
    return lane_steps / total


def test_stream_mesh_refill_occupancy_beats_serial(mesh_streams):
    """ISSUE acceptance: on the staggered-depth workload, mean live-lane
    occupancy with shard-local refill is strictly higher than the
    chunk-serial mesh path at the same width — idle lanes get respliced
    instead of spinning until the deepest lane in the chunk finishes."""
    refill = _mean_live_occupancy(mesh_streams["mesh_piped"]["occupancy"])
    serial = _mean_live_occupancy(mesh_streams["serial"]["occupancy"])
    assert refill > serial, (refill, serial)


def test_no_use_after_donate_sharded():
    """run_segment_sharded donates state (and TT) exactly like the
    single-device _run_segment_jit: the sharded input handles are dead
    after the call and any later use must raise — pins the 'always
    rebind to outputs' discipline the scheduler relies on under a mesh."""
    import jax

    from fishnet_tpu.ops import search as S
    from fishnet_tpu.parallel.mesh import (
        make_mesh,
        run_segment_sharded,
        shard_batch,
    )

    params, roots = _inputs()
    mesh = make_mesh()
    sub = jax.tree.map(lambda a: a[:WIDTH], roots)
    state = S._init_state_jit(
        params, sub, DEPTHS[:WIDTH].copy(),
        np.full(WIDTH, 200_000, np.int32), 6, "standard")
    state = shard_batch(mesh, state)
    out_state, _tt, n, _summ = run_segment_sharded(
        mesh, params, state, None, 50)
    jax.block_until_ready(out_state.lane)
    assert state.lane.is_deleted(), (
        "donated sharded input still live: donate_argnums lost on the "
        "shard_map'd segment callable")
    with pytest.raises(RuntimeError):
        np.asarray(state.lane)
    # the returned state is the live handle and remains usable
    assert np.asarray(out_state.lane).shape[0] == WIDTH
    assert int(np.asarray(n).max()) > 0


# --------------------------------------------------------- engine level


def analysis_work(depth=3):
    return AnalysisWork(id="mesh01",
                        nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
                        timeout_s=30.0, depth=depth, multipv=None)


def make_chunk(work, n_positions=4, moves=GAME):
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


def make_mesh_engine(refill=True, **kw):
    """Engine that KEEPS conftest's 8-device mesh (unlike the refill and
    pipeline suites, which pin mesh=None for single-device semantics).
    refill=True engages the shard-aware scheduler (FISHNET_TPU_MESH_REFILL
    defaults on); refill=False is the chunk-serial sharded baseline."""
    kw.setdefault("max_depth", 3)
    kw.setdefault("tt_size_log2", 0)
    kw.setdefault("helper_lanes", 1)
    engine = TpuEngine(refill=refill, **kw)
    assert engine.mesh is not None, "conftest should provide 8 devices"
    assert engine.n_dev == 8
    return engine


def _flat(resps):
    return [(r.position_index, r.best_move, r.depth, r.nodes,
             r.scores.matrix, r.pvs.matrix) for r in resps]


@pytest.fixture(scope="module")
def mesh_engine_pair():
    """One chunk through the shard-aware scheduler and one through the
    chunk-serial sharded path, same positions (uncoupled lanes: no TT,
    no helpers)."""
    out = {}
    for mode, refill in (("serial", False), ("refill", True)):
        eng = make_mesh_engine(refill=refill)
        resp = run(eng, make_chunk(analysis_work(depth=3), n_positions=4))
        out[mode] = (resp, list(eng.occupancy_log),
                     dict(eng.occupancy_totals))
    return out


def test_engine_mesh_refill_matches_serial(mesh_engine_pair):
    """The shard-aware scheduler reproduces the chunk-serial sharded
    engine exactly — scores, PVs, node counts, per-depth matrices."""
    serial, refill = mesh_engine_pair["serial"][0], mesh_engine_pair["refill"][0]
    assert _flat(serial) == _flat(refill)


def test_engine_mesh_exactly_once(mesh_engine_pair):
    """Every position answers exactly once through the sharded scheduler,
    and the totals tie out."""
    resp, _log, totals = mesh_engine_pair["refill"]
    assert sorted(r.position_index for r in resp) == [0, 1, 2, 3]
    assert all(r.best_move for r in resp)
    assert totals["positions_done"] == 4


def test_engine_mesh_occupancy_shard_columns(mesh_engine_pair):
    """Scheduler occupancy rows under a mesh carry the per-shard columns
    the bench and occupancy report consume, and admissions balance over
    shards (most-free-shard policy: the first 4 primaries land on 4
    DIFFERENT shards, never stacked on one)."""
    log = mesh_engine_pair["refill"][1]
    assert log, "no occupancy rows recorded"
    for row in log:
        for key in ("shard_live", "shard_refilled", "shard_steps"):
            assert len(row[key]) == 8, key
        assert sum(row["shard_refilled"]) == row["refilled"]
    first = log[0]
    assert sum(1 for x in first["shard_refilled"] if x > 0) == 4
    # the serial path records no scheduler rows at all
    assert mesh_engine_pair["serial"][1] == []


@pytest.mark.parametrize("n_positions", [3, 10])
def test_engine_mesh_pad_edge_cases(n_positions):
    """Position counts that don't divide over 8 shards ride through the
    engine's _pad (3 -> width 8, 10 -> width 16): exactly-once delivery
    and bit-identity with the chunk-serial sharded path both hold."""
    serial = make_mesh_engine(refill=False, max_depth=2)
    want = run(serial, make_chunk(analysis_work(depth=2), n_positions))
    engine = make_mesh_engine(max_depth=2)
    got = run(engine, make_chunk(analysis_work(depth=2), n_positions))
    assert sorted(r.position_index for r in got) == list(range(n_positions))
    assert engine.occupancy_totals["positions_done"] == n_positions
    assert _flat(want) == _flat(got)


def test_engine_mesh_concurrent_chunks_exactly_once():
    """Two chunks at DIFFERENT depths share one driver session: lanes
    finish on different shards at different boundaries, refills land
    mid-flight, and both chunks still answer exactly once, in order."""
    engine = make_mesh_engine(max_depth=3)
    chunks = [
        make_chunk(analysis_work(depth=2), n_positions=3, moves=GAME),
        make_chunk(analysis_work(depth=3), n_positions=3,
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
        t.join(timeout=180)
    assert not errors
    for responses in results:
        assert responses is not None and len(responses) == 3
        assert [r.position_index for r in responses] == [0, 1, 2]
        assert all(r.best_move for r in responses)
    assert engine.occupancy_totals["positions_done"] == 6
