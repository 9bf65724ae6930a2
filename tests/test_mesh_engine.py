"""The production TpuEngine must actually use the device mesh.

VERDICT r1 #3: the engine previously never constructed a mesh — on a
v5e-8 it would use 1/8 of the machine. Under the test conftest jax
exposes 8 virtual CPU devices, so these assertions prove the sharded
path (parallel/mesh.py run_segment_sharded) is the engine's real code
path, not a demo.
"""
import asyncio
import time

import jax
import numpy as np
import pytest

from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.tpu import TpuEngine

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"


def test_engine_uses_the_full_mesh():
    assert len(jax.devices()) == 8, "conftest should provide 8 virtual devices"
    engine = TpuEngine(max_depth=2)
    assert engine.mesh is not None
    assert engine.n_dev == 8
    # the TT is sharded per device
    assert engine.tt.check.shape[0] == 8
    # lane padding stays divisible over the devices
    for n in (1, 3, 16, 65, 200):
        assert engine._pad(n) % 8 == 0


def test_go_multiple_on_8_device_mesh():
    engine = TpuEngine(max_depth=2)
    work = AnalysisWork(
        id="meshjob1",
        nodes=NodeLimit(sf16=500_000, classical=500_000),
        timeout_s=60.0,
        depth=2,
    )
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=START, moves=["e2e4"][:i])
        for i in range(2)
    ]
    chunk = Chunk(work=work, deadline=time.monotonic() + 300,
                  variant="standard", flavor=EngineFlavor.TPU,
                  positions=positions)
    responses = asyncio.run(engine.go_multiple(chunk))
    assert len(responses) == 2
    for res in responses:
        assert res.depth == 2 and res.nodes > 0
    # the sharded TT carried stores back from the run
    assert int(np.asarray(engine.tt.meta != 0).sum()) > 0


def test_sharded_watched_segment_is_the_flat_one_per_shard():
    """The watched-lane stop under the mesh: each shard applies it to
    its own lanes, so shard by shard the sharded segment (with its
    table shard) is the flat segment of that shard's lanes and mask, bit
    for bit; a shard with no watched lane keeps the plain rule."""
    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops import tt as tt_mod
    from fishnet_tpu.ops.board import from_position, stack_boards
    from fishnet_tpu.parallel.mesh import (
        make_mesh, make_sharded_table, run_segment_sharded, shard_batch)

    mesh = make_mesh()
    ndev, local = mesh.devices.size, 2
    width = ndev * local
    params = nnue.init_params(jax.random.PRNGKey(5), l1=64,
                              feature_set="board768")
    boards, p = [], Position.from_fen(START)
    for uci in ["e2e4", "c7c5", "g1f3", "d7d6", "d2d4", "c5d4", "f3d4",
                "g8f6", "b1c3", "a7a6", "f1e2", "e7e5", "d4b3", "f8e7",
                "e1g1"]:
        boards.append(from_position(p))
        p = p.push(p.parse_uci(uci))
    boards.append(from_position(p))
    roots = stack_boards(boards)
    # per shard a short lane and a long one; shards 6 and 7 watch none
    depth = np.tile(np.array([1, 2], np.int32), ndev)
    budget = np.tile(np.array([40, 10_000], np.int32), ndev)
    watch = np.zeros(width, bool)
    watch[0:12:2] = True
    state = S._init_state_jit(params, roots, depth, budget, 6, "standard")
    shards = [jax.tree.map(lambda a, s=s: a[s * local:(s + 1) * local],
                           state) for s in range(ndev)]
    out, table, n, summ = run_segment_sharded(
        mesh, params, shard_batch(mesh, state), make_sharded_table(mesh, 6),
        10_000, watch=watch)
    n, summ = np.asarray(n), np.asarray(summ)
    stopped = 0
    for s in range(ndev):
        lanes = slice(s * local, (s + 1) * local)
        f_state, f_table, f_n, f_summ = S._run_segment_jit(
            params, shards[s], tt_mod.make_table(6), 10_000, "standard",
            False, False, 0, watch[lanes])
        assert int(n[s]) == int(np.asarray(f_n)), s
        np.testing.assert_array_equal(summ[s], np.asarray(f_summ))
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(f_state)):
            np.testing.assert_array_equal(np.asarray(a)[lanes], np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(table.data)[s], np.asarray(f_table.data))
        done = summ[s, :local, S.SUM_DONE].astype(bool)
        if watch[lanes].any():
            stopped += int(not done.all())
        else:
            assert done.all(), s  # the plain rule: every lane parked
    assert stopped > 0


def test_helpers_land_on_their_primary_shard(monkeypatch):
    """Under the mesh each shard's segment stops at its own watched
    (primary) lanes, so a position's helper lanes are placed on its
    primary's shard while that shard has a lane free: a shard of
    helpers alone would run on until they park, and hold the boundary."""
    from fishnet_tpu.engine.tpu import _Session

    placed = []
    admit_helper = _Session.admit_helper

    def record(self, job, lane, h):
        s = job.lane // self.local
        room = any(
            self.lane_job[i] is None and self.lane_owner[i] is None
            for i in range(s * self.local, (s + 1) * self.local))
        placed.append((s, lane // self.local, room))
        return admit_helper(self, job, lane, h)

    monkeypatch.setattr(_Session, "admit_helper", record)
    engine = TpuEngine(max_depth=2, helper_lanes=2)
    assert engine.mesh is not None and engine.tt is not None
    work = AnalysisWork(
        id="meshhelp", nodes=NodeLimit(sf16=500_000, classical=500_000),
        timeout_s=60.0, depth=2,
    )
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=START, moves=["e2e4", "e7e5", "g1f3"][:i])
        for i in range(3)
    ]
    chunk = Chunk(work=work, deadline=time.monotonic() + 300,
                  variant="standard", flavor=EngineFlavor.TPU,
                  positions=positions)
    responses = asyncio.run(engine.go_multiple(chunk))
    assert [r.depth for r in responses] == [2, 2, 2]
    assert placed
    for primary_shard, helper_shard, room in placed:
        assert helper_shard == primary_shard or not room
