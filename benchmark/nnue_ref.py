"""Plain NNUE (board768) evaluation in numpy float32: the reference's eval.

Follows the published layout of the net the cells run (768 piece-square
features per perspective -> L1 accumulator pair -> clipped ReLU -> two small
dense layers per output bucket -> one centipawn number, side to move's
view). No incremental update, no batching, nothing of the program: the
weights come from the benchmark's own copy of the net file.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

import numpy as np

OUTPUT_SCALE = 600.0
OUTPUT_BUCKETS = 8
FIELDS = ("ft_w", "ft_b", "l1_w", "l1_b", "l2_w", "l2_b", "out_w", "out_b")
SCORE_CLAMP = 31000  # MATE - 1000: a static eval never reads as a mate


def load_weights(path) -> Dict[str, np.ndarray]:
    with np.load(Path(path), allow_pickle=False) as z:
        return {f: np.asarray(z[f], np.float32) for f in FIELDS}


def evaluate(w: Dict[str, np.ndarray], board, stm: int) -> int:
    """Static eval of a 64-code board in centipawns, truncated to an int
    and clamped as the search clamps it."""
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
