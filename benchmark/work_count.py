"""What one search node has to compute and move, from shapes alone.

Algorithmic counts for one node of the search as the configuration defines
it, whatever program implements it: they never read an HLO or a trace, so a
later PR that removes an operation (the big sort, say) leaves them as they
are and can only raise the share of the roofline it reaches.

Per node:
* The net's share, which the configuration's evaluator counts from its own
  ``net_shapes`` (``evaluators/<name>.py::net_work``) by these rules. A
  move changes at most 4 piece placements; each adds or subtracts one
  accumulator-wide feature row per perspective. The forward pass is the
  dense layers of the one output bucket or stack used (2 FLOPs per
  multiply-add). Bytes: the changed feature rows and that bucket's layer
  weights read, the accumulators read and written.
* What every evaluator shares, counted here. Bytes: the board row read and
  the child's written, the node's scalars, the move list written and read
  once, one table probe and one table store.
"""
from __future__ import annotations

from typing import Dict

BOARD_ROW_BYTES = (64 + 1 + 1 + 4 + 1 + 12 + 2) * 4  # board, stm, ep, castling, clock, variant state, path hash
NODE_ROW_BYTES = 16 * 4
TT_ENTRY_BYTES = 16
MOVE_BYTES = 4


def per_node(net: Dict[str, float], max_moves: int) -> Dict[str, float]:
    """net: the evaluator's ``net_work`` of the configuration's shapes.
    max_moves: the move list's width for the variant (218 is the chess
    maximum; crazyhouse adds 5*64 drops)."""
    board_bytes = 2 * BOARD_ROW_BYTES + NODE_ROW_BYTES
    move_bytes = 2 * max_moves * MOVE_BYTES
    tt_bytes = 2 * TT_ENTRY_BYTES
    return {
        "flops": float(net["flops"]),
        "bytes": float(net["bytes"] + board_bytes + move_bytes + tt_bytes),
    }


def roofline_share(nodes: float, busy_s: float, node: Dict[str, float],
                   peak: Dict[str, float]):
    """→ (share in %, which bound): the least time the chip could take for
    `nodes` nodes over the time its ops took."""
    if not nodes or not busy_s or busy_s <= 0:
        return None, None
    t_flops = nodes * node["flops"] / peak["flops_per_s"]
    t_bytes = nodes * node["bytes"] / peak["bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / busy_s, bound
