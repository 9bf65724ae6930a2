"""Partition-rule registry contracts (fishnet_tpu/parallel/partition.py).

The registry is the ONE place sharding layout lives: these tests pin
(1) total coverage — every leaf of the real search-side pytrees is won
by exactly one rule, and every rule fires (no dead regexes); (2) the
loud-failure contract — an unregistered field raises UnmatchedLeafError
naming the path, instead of sailing through under a default layout;
(3) literal equivalence — the derived segment/merge specs are exactly
the hand-built P-literals parallel/mesh.py used before the registry, so
the refactor cannot have moved a single element; (4) axis renaming and
the batch/replicated helpers behind shard_batch/replicate.

The sharded-vs-serial bit-identity of actual RESULTS under the
registry-derived specs is pinned by tests/test_mesh_refill.py (the
`mesh` marker suite) — here we pin the specs themselves, which needs no
device work and stays in the fast tier.
"""
from __future__ import annotations

import jax
import pytest
from jax.sharding import PartitionSpec as P

from fishnet_tpu.parallel import partition as PT

# ---------------------------------------------------------------- coverage


def test_every_search_leaf_matched_by_exactly_one_rule():
    proto = PT.search_proto()
    for path, leaf in PT.iter_paths(proto):
        hits = PT.matching_rules(path, PT.SEARCH_RULES)
        assert len(hits) == 1, (
            f"leaf {path!r} matched by {len(hits)} rules — the registry "
            "must name exactly one layout per leaf"
        )


def test_validate_rules_counts_cover_the_whole_prototype():
    proto = PT.search_proto()
    counts = PT.validate_rules(proto)
    assert sum(counts.values()) == len(PT.iter_paths(proto))
    # the layout in one screen: 9 state fields, 1 TT shard array,
    # 8 NNUE tensors and the 9 of an imported net (its ft_w and ft_b under
    # the first rule too), 5 boundary values
    assert counts[PT.STATE_RULES[0][0]] == 9
    assert counts[PT.TT_RULES[0][0]] == 1
    assert counts[PT.PARAM_RULES[0][0]] == 8 + 2
    assert counts[PT.PARAM_RULES[1][0]] == 7


def test_param_rules_tp_cover_params_exactly():
    counts = PT.validate_rules(PT.param_proto(), PT.PARAM_RULES_TP)
    assert counts[r"(^|/)ft_w$"] == 1
    assert counts[r"(^|/)ft_b$"] == 1
    assert sum(counts.values()) == 8


def test_dead_rule_raises():
    with pytest.raises(ValueError, match="never fire"):
        PT.validate_rules(
            PT.param_proto(),
            PT.PARAM_RULES + ((r"(^|/)renamed_field$", P("dp")),),
        )


# ------------------------------------------------------------ loud failure


def test_unregistered_leaf_fails_loudly_with_path_named():
    tree = {"state": PT.state_proto(), "mystery_field": "mystery_field"}
    with pytest.raises(PT.UnmatchedLeafError) as ei:
        PT.match_partition_rules(tree)
    assert "mystery_field" in str(ei.value)
    assert "partition.py" in str(ei.value)  # says where to register


def test_scalar_leaves_short_circuit_to_replicated():
    import numpy as np

    tree = {"no_rule_matches_me": np.int32(7)}
    specs = PT.match_partition_rules(tree)
    assert specs["no_rule_matches_me"] == P()


# -------------------------------------------------- literal equivalence
#
# Pre-registry, parallel/mesh.py hand-built these exact specs:
#   segment: in  (P(), P(axis), P(axis)|P(), P(), P(axis))
#            out (P(axis), P(axis)|P(), P(axis), P(axis, None, None))
#   merge:   in  (P(axis), P(axis), P(axis)) → out P(axis)
# The registry derives per-leaf trees; every leaf must equal the literal
# that used to broadcast over its subtree.


def _leaves(spec_tree):
    return jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("axis", ["dp", "x"])
@pytest.mark.parametrize("has_tt", [True, False])
def test_segment_specs_equal_old_hand_built_literals(axis, has_tt):
    in_specs, out_specs = PT.segment_specs(has_tt, axis)
    p_params, p_state, p_tt, p_steps, p_gen = in_specs
    assert all(s == P() for s in _leaves(p_params))
    assert all(s == P(axis) for s in _leaves(p_state))
    assert all(s == (P(axis) if has_tt else P()) for s in _leaves(p_tt))
    assert p_steps == P()
    assert p_gen == P(axis)
    o_state, o_tt, o_steps, o_summ = out_specs
    assert all(s == P(axis) for s in _leaves(o_state))
    assert all(s == (P(axis) if has_tt else P()) for s in _leaves(o_tt))
    assert o_steps == P(axis)
    assert o_summ == P(axis, None, None)


@pytest.mark.parametrize("axis", ["dp", "x"])
def test_splice_specs_shard_every_per_lane_operand(axis):
    """(params, state, roots, depth, node_budget, hist_hash,
    hist_halfmove, root_alpha, root_beta, order_jitter, group, mask):
    weights replicated, everything with a lane dim sharded over it."""
    in_specs, out_specs = PT.splice_specs(axis)
    p_params, st, *lanes = in_specs
    assert all(s == P() for s in _leaves(p_params))
    assert all(s == P(axis) for s in _leaves(st))
    assert lanes == [P(axis)] * 10
    assert all(s == P(axis) for s in _leaves(out_specs))


def test_training_param_specs_shard_feature_transform_over_tp():
    specs = PT.param_specs(tp=True)
    assert specs.ft_w == P(None, "tp")
    assert specs.ft_b == P("tp")
    assert specs.l1_w == P()
    assert specs.out_b == P()


# ------------------------------------------------------------- helpers


def test_rename_axes_substitutes_only_named_axes():
    assert PT.rename_axes(P("dp", None, "tp"), {"dp": "x"}) \
        == P("x", None, "tp")
    assert PT.rename_axes(P(), {"dp": "x"}) == P()


def test_batch_and_replicated_specs():
    assert PT.batch_spec(1) == P("dp")
    assert PT.batch_spec(3) == P("dp", None, None)
    assert PT.batch_spec(1, "x") == P("x")
    assert PT.batch_spec(0) == P("dp")  # scalar floor: rank >= 1
    assert PT.replicated_spec() == P()


def test_default_topology_names_the_fingerprint_fields():
    topo = PT.default_topology()
    assert set(topo) == {"mesh_shape", "mesh_axes", "process_count"}
    assert topo["mesh_axes"] == "dp"
    # conftest forces 8 virtual CPU devices for every test process
    assert topo["mesh_shape"] == "8"
    assert topo["process_count"] == 1


# --------------------------------------------------- sharded bit-identity


@pytest.mark.mesh
@pytest.mark.slow
def test_registry_derived_sharding_bit_identical_to_serial():
    """ISSUE acceptance: the registry-derived specs produce bit-for-bit
    the results of the plain single-device search on the forced-8-device
    mesh (scores, moves, nodes) — the full-size stream parity lives in
    tests/test_mesh_refill.py; this is the minimal direct pin."""
    import numpy as np

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops.board import from_position, stack_boards
    from fishnet_tpu.ops.search import search_batch_resumable
    from fishnet_tpu.parallel.mesh import make_mesh, sharded_search

    params = nnue.init_params(jax.random.PRNGKey(0), l1=32,
                              feature_set="board768")
    start = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    game = ["e2e4", "c7c5", "g1f3", "d7d6", "d2d4", "c5d4", "f3d4"]
    boards, p = [], Position.from_fen(start)
    for uci in [None] + game:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    roots = stack_boards(boards)
    depth = np.full(8, 2, np.int32)
    budget = np.full(8, 4_000, np.int32)
    serial = search_batch_resumable(params, roots, depth, budget,
                                    max_ply=6)
    sharded = sharded_search(params, roots, depth, budget, max_ply=6,
                             mesh=make_mesh(8))
    for key in ("score", "move", "nodes"):
        np.testing.assert_array_equal(
            np.asarray(serial[key]), np.asarray(sharded[key]), err_msg=key)
