"""Partition-rule registry: regex-keyed sharding rules for every pytree
the sharded engine moves across a mesh.

Before this module, every shard_map'd callable in parallel/mesh.py carried
its own hand-built ``P("dp")`` literals — three copies of the same layout
decision, none of them checkable against the real search-state pytree, and
all of them single-host by construction. The registry inverts that: ONE
table of ``(path-regex, PartitionSpec)`` rules describes how the engine's
pytrees shard, `match_partition_rules` turns any pytree into a sharding
tree (loudly failing on unmatched leaves), and mesh.py derives every
in/out spec from it — so a single-host shard_map, a forced-multi-device
CPU mesh and a multi-host `jax.distributed` mesh are one data-driven code
path that differs only in the Mesh object (parallel/distributed.py builds
the multi-host one).

Layout, in one screen:

  * per-lane search state (SearchState: bt/nt/lane/hist_hash/
    hist_halfmove/moves/hist/pv/acc) — leading dim is the lane axis,
    sharded over ``dp``; trailing dims replicated (an integer net's acc
    is a pair of stacks, rows and PSQT sums, both under acc's spec).
  * NNUE weights (NnueParams, StockfishNet, DualNet and the integer
    StockfishNetInt, DualNetInt) — replicated on every chip (`PARAM_RULES`),
    or tensor-sharded over an optional ``tp`` axis for the
    feature-transform width (`PARAM_RULES_TP`, the training layout).
  * transposition table (TTable.data, (ndev, N, 4)) — leading shard dim
    over ``dp``: each device hashes into its private shard.
  * boundary plumbing — per-lane ``tt_gen``, the segment's ``watch``
    mask and the splice ``mask`` shard with the lanes; the traced
    ``segment_steps`` scalar is replicated; per-shard ``steps`` and the
    packed boundary ``summary`` come back sharded over ``dp``.

fishnet-lint's `mesh-unregistered-spec` rule (lint/mesh_rules.py) pins
spec construction to this module + mesh.py, so a new sharded callable
cannot quietly fork the layout.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# One rule: ('/'-joined pytree-path regex matched with re.search,
# PartitionSpec). First matching rule wins; order is specific → generic.
Rule = Tuple[str, P]


class UnmatchedLeafError(ValueError):
    """A pytree leaf reached the mesh boundary with no partition rule.

    Raised by match_partition_rules so an unregistered field fails at
    spec-derivation time with the offending paths named, instead of
    sailing through under some default layout and corrupting results
    (or deadlocking a multi-host mesh) at dispatch time."""


# --------------------------------------------------------------- registry

# per-lane search state: every SearchState field carries the lane batch
# as its leading dim, so all of them shard over dp and nothing else
STATE_RULES: Tuple[Rule, ...] = (
    (r"(^|/)(bt|nt|lane|hist_hash|hist_halfmove|moves|hist|pv|acc)$",
     P("dp")),
)

# transposition table: (ndev, N, 4) with the leading shard dim over dp —
# each device owns one private shard (parallel/mesh.make_sharded_table)
TT_RULES: Tuple[Rule, ...] = (
    (r"(^|/)data$", P("dp")),
)

# NNUE weights, search layout: replicated into every chip's HBM — the
# eval stack is tiny and the lanes are embarrassingly parallel
PARAM_RULES: Tuple[Rule, ...] = (
    (r"(^|/)(ft_w|ft_b|l1_w|l1_b|l2_w|l2_b|out_w|out_b)$", P()),
    # an imported net (models/nnue_import.StockfishNet): its feature
    # table is gathered by row, lane by lane, so every chip holds it whole
    (r"(^|/)(psqt_w|fc0_w|fc0_b|fc1_w|fc1_b|fc2_w|fc2_b)$", P()),
    # both nets of a deployment (nnue_import.DualNet): one table of both
    # nets' rows, gathered by row as the imported net's is; in integers
    # (DualNetInt) the int16 table and its int32 PSQT side table, and
    # int8 stacks under the names above
    (r"(^|/)(ft|psqt)$", P()),
)

# NNUE weights, training layout: the gather-heavy feature transform
# splits its output width over tp; the small layer stack is replicated
# (models/train.py derives its param shardings from these)
PARAM_RULES_TP: Tuple[Rule, ...] = (
    (r"(^|/)ft_w$", P(None, "tp")),
    (r"(^|/)ft_b$", P("tp")),
    (r"(^|/)(l1_w|l1_b|l2_w|l2_b|out_w|out_b)$", P()),
)

# boundary plumbing of the segment/merge callables
AUX_RULES: Tuple[Rule, ...] = (
    (r"(^|/)tt_gen$", P("dp")),        # per-lane TT generation tags
    (r"(^|/)watch$", P("dp")),         # (B,) lanes whose parking ends a segment
    (r"(^|/)segment_steps$", P()),     # traced replicated scalar
    (r"(^|/)mask$", P("dp")),          # (B,) refill splice mask
    (r"(^|/)steps$", P("dp")),         # (ndev,) per-shard step counts
    (r"(^|/)summary$", P("dp", None, None)),  # stacked boundary summary
)

# the full search-side registry, in match order
SEARCH_RULES: Tuple[Rule, ...] = (
    STATE_RULES + TT_RULES + PARAM_RULES + AUX_RULES
)


# ------------------------------------------------------------ pytree paths


def iter_paths(tree: Any, prefix: str = "") -> List[Tuple[str, Any]]:
    """('/'-joined path, leaf) pairs in jax flatten order.

    NamedTuples contribute field names, dicts their (sorted) keys,
    sequences their indices; None subtrees are empty, matching the jax
    pytree convention — so the path list zips exactly against
    jax.tree_util.tree_flatten's leaves for the trees this engine moves
    (all NamedTuples/dicts/tuples of arrays)."""
    out: List[Tuple[str, Any]] = []

    def walk(node: Any, path: str) -> None:
        if node is None:
            return
        if hasattr(node, "_fields"):  # NamedTuple: field names
            for name, child in zip(node._fields, node):
                walk(child, f"{path}/{name}" if path else name)
        elif isinstance(node, dict):
            for name in sorted(node):
                walk(node[name], f"{path}/{name}" if path else str(name))
        elif isinstance(node, (list, tuple)):
            for i, child in enumerate(node):
                walk(child, f"{path}/{i}" if path else str(i))
        else:
            out.append((path, node))

    walk(tree, prefix)
    return out


def matching_rules(path: str,
                   rules: Sequence[Rule] = SEARCH_RULES) -> List[int]:
    """Indices of every rule whose regex matches this path (re.search)."""
    return [i for i, (pat, _) in enumerate(rules) if re.search(pat, path)]


def rename_axes(spec: P, axis_map: Dict[str, str]) -> P:
    """A PartitionSpec with mesh-axis names substituted — the registry
    speaks canonical 'dp'/'tp'; callables built over a differently-named
    axis rename at derivation time."""

    def sub(part):
        if part is None:
            return None
        if isinstance(part, (tuple, list)):
            return tuple(sub(p) for p in part)
        return axis_map.get(part, part)

    return P(*(sub(part) for part in spec))


# --------------------------------------------------------------- matching


def match_partition_rules(tree: Any, rules: Optional[Sequence[Rule]] = None,
                          *, prefix: str = "",
                          axis_map: Optional[Dict[str, str]] = None) -> Any:
    """A pytree of PartitionSpecs, same structure as `tree`.

    Each leaf takes the FIRST rule whose regex matches its '/'-joined
    path (0-d array leaves short-circuit to replicated `P()` — a scalar
    has no axis to shard). Leaves no rule matches raise
    UnmatchedLeafError naming every offender at once: an unregistered
    field is a layout decision nobody made, and the mesh boundary is
    where it must fail."""
    rules = SEARCH_RULES if rules is None else tuple(rules)
    paths = iter_paths(tree, prefix)
    specs: List[P] = []
    unmatched: List[str] = []
    for path, leaf in paths:
        if getattr(leaf, "ndim", None) == 0:
            specs.append(P())
            continue
        hit = matching_rules(path, rules)
        if hit:
            specs.append(rules[hit[0]][1])
        else:
            unmatched.append(path)
    if unmatched:
        raise UnmatchedLeafError(
            "no partition rule matches pytree leaf(s): "
            + ", ".join(repr(p) for p in unmatched)
            + " — register a (regex, PartitionSpec) rule in "
            "parallel/partition.py before moving this tree across a mesh"
        )
    treedef = jax.tree_util.tree_structure(tree)
    if treedef.num_leaves != len(specs):
        raise ValueError(
            f"path walk found {len(specs)} leaves but jax flattens "
            f"{treedef.num_leaves} — tree contains a custom pytree node "
            "iter_paths does not understand"
        )
    if axis_map:
        specs = [rename_axes(s, axis_map) for s in specs]
    return jax.tree_util.tree_unflatten(treedef, specs)


def validate_rules(tree: Any = None,
                   rules: Optional[Sequence[Rule]] = None,
                   *, prefix: str = "") -> Dict[str, int]:
    """Check every rule fires at least once on the real pytree.

    Returns {rule regex: first-match count}. A rule that never wins a
    leaf is dead weight — usually a renamed field or a shadowing earlier
    rule — and raises ValueError naming it. Unmatched leaves raise
    UnmatchedLeafError exactly as match_partition_rules would."""
    rules = SEARCH_RULES if rules is None else tuple(rules)
    if tree is None:
        tree = search_proto()
    counts = {pat: 0 for pat, _ in rules}
    unmatched: List[str] = []
    for path, leaf in iter_paths(tree, prefix):
        if getattr(leaf, "ndim", None) == 0:
            continue
        hit = matching_rules(path, rules)
        if hit:
            counts[rules[hit[0]][0]] += 1
        else:
            unmatched.append(path)
    if unmatched:
        raise UnmatchedLeafError(
            "no partition rule matches pytree leaf(s): "
            + ", ".join(repr(p) for p in unmatched)
        )
    dead = [pat for pat, n in counts.items() if n == 0]
    if dead:
        raise ValueError(
            "partition rule(s) never fire on the real pytree: "
            + ", ".join(repr(p) for p in dead)
            + " — stale regex or shadowed by an earlier rule"
        )
    return counts


# -------------------------------------------------------------- prototypes
#
# Spec derivation happens when a callable is BUILT (lru-cached per mesh
# config), before any real array exists — so the registry matches against
# prototype trees whose leaves are their own path strings. Field renames
# in the real NamedTuples flow into the prototypes automatically.


def state_proto():
    """A SearchState whose leaves are field-name strings."""
    from ..ops.search import SearchState

    return SearchState(*SearchState._fields)


def tt_proto():
    """A TTable whose leaves are field-name strings."""
    from ..ops.tt import TTable

    return TTable(*TTable._fields)


def param_proto():
    """An NnueParams whose leaves are field-name strings."""
    from ..models.nnue import NnueParams

    return NnueParams(*NnueParams._fields)


def imported_param_proto() -> Dict[str, str]:
    """The array fields of an imported net (nnue_import.StockfishNet), as
    a dict of field-name strings."""
    from ..models.nnue_import import _ARRAY_FIELDS

    return {name: name for name in _ARRAY_FIELDS}


def dual_param_proto() -> Dict[str, Any]:
    """The array fields of both nets of a deployment
    (nnue_import.DualNet), as a tree of field-name strings."""
    from ..models.nnue_import import _STACK_FIELDS

    stacks = {name: name for name in _STACK_FIELDS}
    return {"ft": "ft", "ft_b": "ft_b", "big": dict(stacks),
            "small": dict(stacks)}


def dual_int_param_proto() -> Dict[str, Any]:
    """The array fields of both integer nets of a deployment
    (nnue_import.DualNetInt), as a tree of field-name strings."""
    return dict(dual_param_proto(), psqt="psqt")


def search_proto() -> Dict[str, Any]:
    """Everything that crosses the mesh boundary, as one prototype tree —
    the default subject of validate_rules()."""
    return {
        "params": param_proto(),
        "params_imported": imported_param_proto(),
        "params_dual": dual_param_proto(),
        "params_dual_int": dual_int_param_proto(),
        "state": state_proto(),
        "tt": tt_proto(),
        "tt_gen": "tt_gen",
        "watch": "watch",
        "segment_steps": "segment_steps",
        "mask": "mask",
        "steps": "steps",
        "summary": "summary",
    }


# ---------------------------------------------------------- derived specs


def _axis_map(axis: str) -> Optional[Dict[str, str]]:
    return None if axis == "dp" else {"dp": axis}


def state_specs(axis: str = "dp"):
    """SearchState-shaped tree of PartitionSpecs (lanes over `axis`)."""
    return match_partition_rules(state_proto(), axis_map=_axis_map(axis))


def tt_specs(axis: str = "dp"):
    """TTable-shaped tree of PartitionSpecs (shard dim over `axis`)."""
    return match_partition_rules(tt_proto(), axis_map=_axis_map(axis))


def param_specs(tp: bool = False):
    """NnueParams-shaped spec tree: replicated (search) or ft-width
    tensor-sharded over tp (training)."""
    rules = PARAM_RULES_TP if tp else PARAM_RULES
    return match_partition_rules(param_proto(), rules)


def search_param_spec() -> P:
    """The search layout of the weights as one spec for the whole
    subtree, whichever params type the engine runs (a shard_map spec may
    be a prefix of its argument's tree): every PARAM_RULES leaf says
    replicated, which is checked here, so the callables need no
    prototype of the type."""
    for proto in (param_proto(), imported_param_proto(), dual_param_proto(),
                  dual_int_param_proto()):
        leaves = jax.tree_util.tree_leaves(
            match_partition_rules(proto, PARAM_RULES),
            is_leaf=lambda x: isinstance(x, P))
        assert all(spec == P() for spec in leaves), leaves
    return P()


def spec_for(name: str, axis: str = "dp") -> P:
    """The registry's spec for one named boundary value (tt_gen, mask,
    segment_steps, steps, summary)."""
    tree = match_partition_rules({name: name}, axis_map=_axis_map(axis))
    return tree[name]


def segment_specs(has_tt: bool, axis: str = "dp"):
    """(in_specs, out_specs) of the shard_map'd search segment — the
    registry-derived replacement for mesh.py's old hand-built literals.

    Argument order mirrors parallel.mesh._segment_callable's seg():
    (params, state, ttab, segment_steps, tt_gen, watch) →
    (state, ttab, steps, summary). A ttab-less build replicates the None
    placeholder."""
    tt = tt_specs(axis) if has_tt else P()
    in_specs = (
        search_param_spec(),
        state_specs(axis),
        tt,
        spec_for("segment_steps", axis),
        spec_for("tt_gen", axis),
        spec_for("watch", axis),
    )
    out_specs = (
        state_specs(axis),
        tt,
        spec_for("steps", axis),
        spec_for("summary", axis),
    )
    return in_specs, out_specs


def splice_specs(axis: str = "dp"):
    """(in_specs, out_specs) of the shard_map'd refill splice:
    (params, state, roots, depth, node_budget, hist_hash, hist_halfmove,
    root_alpha, root_beta, order_jitter, group, mask) → state. The
    weights are replicated; the state and every per-lane operand (the
    root Board as one subtree) shard their leading, lane dim."""
    st = state_specs(axis)
    lanes = spec_for("mask", axis)
    return (search_param_spec(), st) + (lanes,) * 10, st


def batch_spec(ndim: int, axis: str = "dp") -> P:
    """Leading-dim-sharded spec for a rank-`ndim` batched array — the
    placement rule behind mesh.shard_batch."""
    return P(axis, *([None] * (max(ndim, 1) - 1)))


def replicated_spec() -> P:
    return P()


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    """The one NamedSharding constructor the rest of the tree uses —
    keeps sharding objects flowing out of the registry (and keeps
    lint/mesh_rules.py's allow-list to this module + mesh.py)."""
    return NamedSharding(mesh, spec)


def default_topology() -> Dict[str, Any]:
    """The mesh topology this process would build: shape, axis names,
    process count — folded into the AOT store fingerprint (aot/keys.py)
    so a bundle packed on one topology is rejected-with-named-diff on
    another instead of deserializing garbage."""
    try:
        n_dev = len(jax.devices())
    except Exception:
        n_dev = 0
    try:
        n_proc = jax.process_count()
    except Exception:
        n_proc = 1
    return {
        "mesh_shape": str(n_dev),
        "mesh_axes": "dp",
        "process_count": n_proc,
    }
