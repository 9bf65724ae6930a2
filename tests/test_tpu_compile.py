"""The main path's search programs compile for a TPU v5e — no chip needed.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). Nothing runs, so
these say nothing about results or speed; they say the production-width
programs still get through the chip's compiler and fit its 16 GB, which
every XLA:CPU test is blind to (tests/conftest.py shrinks the engine to
MAX_PLY=8 for the suite — every shape here is passed explicitly so the
toy is never what compiles).

Rules these tests live by: the topology is described inside a fixture —
never at import, never in conftest.py, never autouse — because only one
process may load libtpu and every xdist worker imports every test file;
the compiles run in this process; and the persistent compile cache is
off around them (an entry written for a described chip cannot be read
back without one, and the next run would warn about it).
"""
import hashlib
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from fishnet_tpu.assets import load_default_params
from fishnet_tpu.chess.position import Position
from fishnet_tpu.ops import movegen
from fishnet_tpu.ops import search as S
from fishnet_tpu.ops import tt as tt_mod
from fishnet_tpu.ops.board import Board, from_position, stack_boards
from fishnet_tpu.parallel import mesh as mesh_mod
from fishnet_tpu.parallel import partition

# production width (engine/tpu.py): MAX_PLY default, the widest warmup
# bucket, the lane ceiling, the TT the engine allocates
MAX_PLY = 32
BUCKET = 256
MAX_LANES = 1024
TT_LOG2 = 21
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def params():
    p = load_default_params("board768")
    assert p is not None, "shipped board768 net missing"
    return p


def _on(tree, sharding):
    """Shapes of `tree`, each placed by `sharding` (one sharding, or a
    pytree of them matching `tree`)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, sharding,
        )
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _roots(lanes: int):
    return stack_boards([from_position(Position.initial())] * lanes)


def _init_args(lanes: int):
    return _roots(lanes), jnp.ones(lanes, jnp.int32), jnp.full(
        lanes, 64, jnp.int32)


def _state_shape(params, lanes: int, variant: str = "standard"):
    roots, depth, budget = _init_args(lanes)
    return jax.eval_shape(
        lambda p, r, d, b: S.init_state(p, r, d, b, MAX_PLY, variant),
        params, roots, depth, budget,
    )


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, mem
    return used


def _compile_segment(params, sharding, lanes: int, variant: str,
                     deep_tt: bool, prefer_deep: bool):
    state = _state_shape(params, lanes, variant)
    ttab = jax.eval_shape(lambda: tt_mod.make_table(TT_LOG2))
    fn = jax.jit(
        S._run_segment, static_argnames=("variant", "deep_tt", "prefer_deep")
    )
    return fn.lower(
        _on(params, sharding), _on(state, sharding), _on(ttab, sharding),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding),
        variant=variant, deep_tt=deep_tt, prefer_deep=prefer_deep,
        tt_gen=jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=sharding),
    ).compile()


def test_init_state_compiles_at_production_width(one_chip, params):
    fn = jax.jit(S.init_state, static_argnames=("max_ply", "variant"))
    roots, depth, budget = _init_args(BUCKET)
    compiled = fn.lower(
        _on(params, one_chip), _on(roots, one_chip), _on(depth, one_chip),
        _on(budget, one_chip), max_ply=MAX_PLY, variant="standard",
    ).compile()
    _fits(compiled)


@pytest.mark.parametrize("lanes,variant,deep_tt,prefer_deep", [
    # what warmup compiles for analysis chunks with helper lanes on
    (BUCKET, "standard", False, True),
    # the single-dispatch lane ceiling (FISHNET_TPU_MAX_LANES)
    (MAX_LANES, "standard", False, True),
    # the variant with the widest candidate space (drops)
    (BUCKET, "crazyhouse", False, True),
], ids=["standard-256", "standard-1024", "crazyhouse-256"])
def test_run_segment_compiles(one_chip, params, lanes, variant, deep_tt,
                              prefer_deep):
    compiled = _compile_segment(
        params, one_chip, lanes, variant, deep_tt, prefer_deep)
    _fits(compiled)


@pytest.mark.parametrize("variant,widest", [
    ("standard", 2600), ("crazyhouse", 2900),
], ids=["standard", "crazyhouse"])
def test_segment_sorts_only_live_slots(one_chip, params, variant, widest):
    """The shape as the counter (PR 33): the 64-lane segment the trickle
    cells run holds exactly ONE sort, and what it sorts is the live slots
    of the candidate space (ops/movegen.py _live_slots: 2,550 / 2,854),
    not the whole space (4,962 / 5,282). The op's name in a trace,
    `sort s32[64,<width>]`, is what the ledger's `breakdown.device_ops`
    shows."""
    text = _compile_segment(params, one_chip, 64, variant, False, True).as_text()
    sorts = re.findall(r"= (\w+)\[([\d,]+)\]\S* sort\(.*?dimensions=\{(\d+)\}", text)
    assert len(sorts) == 1, sorts
    dtype, dims, axis = sorts[0]
    dims = [int(d) for d in dims.split(",")]
    assert dtype == "s32" and sorted(dims)[0] == 64, sorts
    assert dims[int(axis)] == len(movegen._live_slots(variant)) <= widest


@pytest.fixture(scope="module")
def wide_net():
    """The shapes of the benchmark's `halfka3072` net (no array: a
    3,072-wide table is 277 MB)."""
    from fishnet_tpu.models import nnue_import as ni

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    l1 = 3072
    return ni.StockfishNet(
        ft_w=f32(ni.NUM_FEATURES, l1), ft_b=f32(l1),
        psqt_w=f32(ni.NUM_FEATURES, ni.NUM_PSQT_BUCKETS),
        fc0_w=f32(ni.NUM_STACKS, ni.FC0_OUT, l1), fc0_b=f32(ni.NUM_STACKS, ni.FC0_OUT),
        fc1_w=f32(ni.NUM_STACKS, ni.FC1_OUT, ni.FC1_IN),
        fc1_b=f32(ni.NUM_STACKS, ni.FC1_OUT),
        fc2_w=f32(ni.NUM_STACKS, 1, ni.FC1_OUT), fc2_b=f32(ni.NUM_STACKS, 1),
    )


@pytest.mark.parametrize("lanes", [64, MAX_LANES])
def test_wide_net_segment_compiles(one_chip, wide_net, lanes):
    """The king-relative 3,072-wide net on the incremental path at the
    widths `halfka3072.trickle` runs and at the lane ceiling: the
    accumulator stack rides in the state — 2 x (33 plies + the spare pair)
    rows of 3,080 float32 a lane — the step gathers whole rows of the
    22,528 x 3,072 table, and the ordering sort is the one every net
    shares."""
    compiled = _compile_segment(wide_net, one_chip, lanes, "standard", False, True)
    used = _fits(compiled)
    acc = lanes * 2 * (MAX_PLY + 2) * 3080 * 4
    assert used > acc + 22528 * 3072 * 4
    text = compiled.as_text()
    assert f"f32[{lanes},{2 * (MAX_PLY + 2)},3080]" in text
    sorts = re.findall(r"= s32\[([\d,]+)\]\S* sort\(", text)
    assert len(sorts) == 1 and str(len(movegen._live_slots("standard"))) in sorts[0]


def test_wide_net_splice_compiles(one_chip, wide_net):
    """The refill's program for the wide net at 64 lanes: every admitted
    lane's root rebuilt from its board (32 rows a perspective)."""
    lanes = 64
    state = _state_shape(wide_net, lanes)
    roots, depth, budget = _init_args(lanes)
    hist = jnp.zeros((lanes, S.MAX_HIST, 2), jnp.uint32)
    col = jnp.zeros(lanes, jnp.int32)
    fn = jax.jit(S._splice_lanes, static_argnames=("variant",),
                 donate_argnums=(1,))
    compiled = fn.lower(
        _on(wide_net, one_chip), _on(state, one_chip),
        *_on((roots, depth, budget, hist, hist[:, :, 0].astype(jnp.int32),
              col, col, col, col, col != 0), one_chip),
        variant="standard",
    ).compile()
    _fits(compiled)


def test_merge_lanes_compiles(one_chip, params):
    state = _on(_state_shape(params, BUCKET), one_chip)
    mask = jax.ShapeDtypeStruct((BUCKET,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(S._merge_lanes).lower(state, state, mask).compile()
    _fits(compiled)


@pytest.mark.parametrize("variant", ["standard", "crazyhouse"])
def test_splice_lanes_compiles(one_chip, params, variant):
    """The refill splice (init_state at the state's width + the masked
    merge, one program, the running state donated) at the width of the
    `.backlog` sessions, for the chip."""
    state = _state_shape(params, BUCKET, variant)
    roots, depth, budget = _init_args(BUCKET)
    hist = jnp.zeros((BUCKET, S.MAX_HIST, 2), jnp.uint32)
    lanes = jnp.zeros(BUCKET, jnp.int32)
    fn = jax.jit(S._splice_lanes, static_argnames=("variant",),
                 donate_argnums=(1,))
    compiled = fn.lower(
        _on(params, one_chip), _on(state, one_chip),
        *_on((roots, depth, budget, hist, hist[:, :, 0].astype(jnp.int32),
              lanes, lanes, lanes, lanes, lanes != 0), one_chip),
        variant=variant,
    ).compile()
    _fits(compiled)


def test_sharded_segment_compiles_on_four_chips(topo, params):
    """The shard_map'd segment of parallel/mesh.py on a 4-device mesh,
    placed by the partition-rule registry's own specs."""
    mesh = Mesh(np.array(topo.devices), ("dp",))
    assert mesh.devices.size == 4
    in_specs, _ = partition.segment_specs(True, "dp")

    def named(specs):
        return jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )

    p_sh, st_sh, tt_sh, steps_sh, gen_sh = (named(s) for s in in_specs)
    lanes = BUCKET * 4  # one production bucket per chip
    state = _state_shape(params, lanes)
    ttab = jax.eval_shape(
        lambda: tt_mod.TTable(
            data=jnp.zeros((4, 1 << TT_LOG2, 4), jnp.int32)))
    fn = mesh_mod._segment_callable(
        mesh, "dp", True, "standard", False, True)
    compiled = fn.lower(
        _on(params, p_sh), _on(state, st_sh), _on(ttab, tt_sh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=steps_sh),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=gen_sh),
    ).compile()
    # per-device bytes: each chip holds its lanes and its TT shard
    _fits(compiled)
    assert "all-reduce" not in compiled.as_text(), (
        "the sharded segment is meant to run with no collectives")


# ---------------------------------------------------------------------------
# Not a compile for the chip: the CPU bit-identity case of PR 33. The sort
# lost its never-valid slots; the tree searched must be the parent's, node
# for node, in every variant program.

# sha256[:16] over done, move, nodes, pv, pv_len, score, steps of the search
# below, recorded on the parent commit (0cff71d) on this sandbox's CPU
PARENT_TREE = {
    "antichess": "3b11793f950ca883", "atomic": "9f679f7a55bd9a83",
    "crazyhouse": "1b627dd443080a3c", "horde": "8df043a18c4b088a",
    "kingOfTheHill": "0b1fb6a83203f0de", "racingKings": "a0118e1b40c422cd",
    "standard": "c25290edc4211971", "threeCheck": "4a999f0a82919548",
}

NODES = 400  # a root: the widest roots stop on it, the rest on depth 2


def _seeded_roots(variant: str, n: int = 16) -> Board:
    """`n` boards spread over test_device_board's seeded playouts (both
    colors, the special positions at the end among them)."""
    from test_device_board import _playout_boards

    boards = _playout_boards(variant)
    pick = np.linspace(3, boards.board.shape[0] - 1, n).astype(int)
    return Board(*[field[pick] for field in boards])


def _tree(variant: str) -> dict:
    from fishnet_tpu.models import nnue

    net = nnue.init_params(
        jax.random.PRNGKey(0), l1=32, h1=8, h2=8, feature_set="board768")
    out = S.search_batch(
        net, _seeded_roots(variant), 2, NODES, max_ply=4,
        tt=tt_mod.make_table(12), variant=variant)
    return {k: np.asarray(v) for k, v in sorted(out.items()) if k != "tt"}


def _digest(tree: dict) -> str:
    h = hashlib.sha256()
    for k, v in tree.items():
        h.update(k.encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("variant", sorted(PARENT_TREE))
def test_search_tree_is_the_parents(variant, monkeypatch):
    """A depth-2 search of 16 seeded roots through `search_batch` (table,
    killers and history on) gives the parent's scores, PVs, best moves,
    node and step counts: against the parent's `generate_moves` run here
    (exact, whatever this CPU's float arithmetic), and against the digest
    recorded on the parent commit where this CPU reproduces it."""
    from test_device_board import parent_generate_moves

    tree = _tree(variant)
    assert int(tree["nodes"].sum()) > 500
    def parent_run_segment(params, state, ttab, segment_steps,
                           variant="standard", deep_tt=False,
                           prefer_deep=False, tt_gen=0):
        # a function of its own, so that jit traces it anew and does not
        # hand back the program it holds for S._run_segment
        return S._run_segment(params, state, ttab, segment_steps, variant,
                              deep_tt, prefer_deep, tt_gen)

    with monkeypatch.context() as m:
        m.setattr(S, "generate_moves", parent_generate_moves)
        m.setattr(S, "_run_segment_jit", jax.jit(
            parent_run_segment,
            static_argnames=("variant", "deep_tt", "prefer_deep"),
            donate_argnums=(1, 2)))
        parent = _tree(variant)
    for k in parent:
        assert np.array_equal(tree[k], parent[k]), (variant, k)
    if _digest(parent) != PARENT_TREE[variant]:
        warnings.warn(
            f"{variant}: the parent's form gives {_digest(parent)} here, "
            f"{PARENT_TREE[variant]} where it was recorded: another CPU's "
            "float arithmetic; the comparison above still held")
    else:
        assert _digest(tree) == PARENT_TREE[variant]
