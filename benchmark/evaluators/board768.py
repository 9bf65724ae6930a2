"""Plain NNUE (board768) evaluation in numpy float32: the reference's eval.

Follows the published layout of the net the cells run (768 piece-square
features per perspective -> L1 accumulator pair -> clipped ReLU -> two small
dense layers per output bucket -> one centipawn number, side to move's
view). No incremental update, no batching, nothing of the program: the
weights come from the benchmark's own copy of the net file.

The evaluator a configuration names as ``engine.evaluator`` = "board768"
(``cells.load_evaluator``); only ``program_params`` imports the program.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

import numpy as np

OUTPUT_SCALE = 600.0
OUTPUT_BUCKETS = 8
FIELDS = ("ft_w", "ft_b", "l1_w", "l1_b", "l2_w", "l2_b", "out_w", "out_b")
SCORE_CLAMP = 31000  # MATE - 1000: a static eval never reads as a mate
MAX_PIECE_CHANGES = 4  # mover off, mover on, captured off, rook/ep victim


def load_weights(engine_cfg: dict, root) -> Dict[str, np.ndarray]:
    with np.load(Path(root) / engine_cfg["net"], allow_pickle=False) as z:
        return {f: np.asarray(z[f], np.float32) for f in FIELDS}


def evaluate(w: Dict[str, np.ndarray], pos) -> int:
    """Static eval of a ``rules.Pos`` (its 64-code board and side to move)
    in centipawns, truncated to an int and clamped as the search clamps it."""
    board, stm = pos.board, pos.stm
    accs = []
    occupied = [(sq, c) for sq, c in enumerate(board) if c]
    for persp in (0, 1):
        idx = []
        for sq, code in occupied:
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = pt if col == persp else 6 + pt
            idx.append(kind * 64 + (sq ^ (56 if persp else 0)))
        accs.append(w["ft_b"] + w["ft_w"][idx].sum(axis=0, dtype=np.float32))
    own, opp = (accs[0], accs[1]) if stm == 0 else (accs[1], accs[0])
    x = np.concatenate([np.clip(own, 0.0, 1.0), np.clip(opp, 0.0, 1.0)])
    b = min(max((len(occupied) - 1) // 4, 0), OUTPUT_BUCKETS - 1)
    h = np.clip(x @ w["l1_w"][b] + w["l1_b"][b], 0.0, 1.0)
    h = np.clip(h @ w["l2_w"][b] + w["l2_b"][b], 0.0, 1.0)
    out = np.float32(h @ w["out_w"][b] + w["out_b"][b]) * np.float32(OUTPUT_SCALE)
    return max(-SCORE_CLAMP, min(SCORE_CLAMP, int(out)))


def program_params(weights: Dict[str, np.ndarray]):
    """What ``TpuEngine(params=...)`` takes for these weights."""
    import jax.numpy as jnp

    from fishnet_tpu.models import nnue

    return nnue.NnueParams(**{f: jnp.asarray(weights[f]) for f in FIELDS})


def net_work(shapes: Dict[str, int]) -> Dict[str, float]:
    """The net's share of one node's work, by ``work_count``'s rules, from
    l1, h1, h2: a move changes at most 4 piece placements, each one L1-wide
    row per perspective; 2*L1 -> H1 -> H2 -> 1 dense layers of one bucket."""
    l1, h1, h2 = shapes["l1"], shapes["h1"], shapes["h2"]
    acc_flops = 2 * MAX_PIECE_CHANGES * l1  # two perspectives, one add each
    fwd_flops = 2 * (2 * l1 * h1 + h1 * h2 + h2)
    weight_bytes = 4 * (2 * MAX_PIECE_CHANGES * l1
                        + 2 * l1 * h1 + h1 + h1 * h2 + h2 + h2 + 1)
    acc_bytes = 2 * (2 * l1 * 4)  # pair read, pair written
    return {"flops": float(acc_flops + fwd_flops),
            "bytes": float(weight_bytes + acc_bytes)}
