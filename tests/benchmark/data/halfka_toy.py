"""A fixture, not a configuration: an evaluator file for the king-relative
wide format (HalfKAv2_hm features, SFNNv5 layer stack) at a toy width, which
`test_bench_crazyhouse.py` copies into a temporary tree's `evaluators/` to
prove that a second params type goes through the harness by new files
alone. The `model_config` PR that adds upstream's net brings its own file.

The format, from its published description: 22,528 features a perspective
(32 king buckets after mirroring the king onto files a-d x 11 piece kinds,
the two kings sharing a plane, x 64 squares) -> an L1-wide accumulator and
8 PSQT columns a perspective -> clipped to [0, 1], the two halves of each
accumulator multiplied pairwise -> one of eight layer stacks by piece
count: L1 -> 16 (15 hidden + 1 skip), 30 -> 32 (the 15 clipped and their
squares), 32 -> 1 -> (output + skip + PSQT difference / 2) x 600 centipawns.

Plain numpy float32, no incremental update, no batching; weights from the
seed the configuration states, so no file under `weights/`.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KING_BUCKETS, PIECE_KINDS, SQUARES = 32, 11, 64
FEATURES = KING_BUCKETS * PIECE_KINDS * SQUARES  # 22,528
STACKS = 8  # layer stacks, and PSQT columns
FC0_OUT, FC1_IN, FC1_OUT = 16, 30, 32
OUTPUT_SCALE = 600.0
SCORE_CLAMP = 31000  # MATE - 1000: a static eval never reads as a mate
MAX_PIECE_CHANGES = 4  # mover off, mover on, captured off, rook/ep victim


def load_weights(engine_cfg: dict, root) -> Dict[str, np.ndarray]:
    """Made from ``engine.weights`` = {seed, l1} with numpy's PCG64, so the
    program and the reference get the same bytes on any machine."""
    spec = engine_cfg["weights"]
    l1 = spec["l1"]
    rng = np.random.Generator(np.random.PCG64(spec["seed"]))

    def normal(shape, scale, shift=0.0):
        return (rng.standard_normal(shape, dtype=np.float32) * np.float32(scale)
                + np.float32(shift))

    return {
        "ft_w": normal((FEATURES, l1), 0.25),
        "ft_b": normal((l1,), 0.25, 0.5),
        "psqt_w": normal((FEATURES, STACKS), 0.02),
        "fc0_w": normal((STACKS, FC0_OUT, l1), 1.0 / np.sqrt(l1)),
        "fc0_b": normal((STACKS, FC0_OUT), 0.1),
        "fc1_w": normal((STACKS, FC1_OUT, FC1_IN), 1.0 / np.sqrt(FC1_IN)),
        "fc1_b": normal((STACKS, FC1_OUT), 0.1),
        "fc2_w": normal((STACKS, 1, FC1_OUT), 0.3 / np.sqrt(FC1_OUT)),
        "fc2_b": normal((STACKS, 1), 0.02),
    }


def evaluate(w: Dict[str, np.ndarray], pos) -> int:
    """Static eval of a ``rules.Pos`` in centipawns from the side to move's
    view, truncated to an int and clamped as the search clamps it."""
    board, stm = pos.board, pos.stm
    occupied = [(sq, c) for sq, c in enumerate(board) if c]
    accs, psqts = [], []
    for persp in (0, 1):
        flip = 56 if persp else 0
        ksq = board.index(12 if persp else 6) ^ flip
        mirror = 7 if (ksq & 7) > 3 else 0
        ksq ^= mirror
        bucket = (ksq >> 3) * 4 + (ksq & 7)
        idx = []
        for sq, code in occupied:
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = 10 if pt == 5 else (pt if col == persp else 5 + pt)
            idx.append((bucket * PIECE_KINDS + kind) * SQUARES + (sq ^ flip ^ mirror))
        accs.append(w["ft_b"] + w["ft_w"][idx].sum(axis=0, dtype=np.float32))
        psqts.append(w["psqt_w"][idx].sum(axis=0, dtype=np.float32))
    own, opp = (0, 1) if stm == 0 else (1, 0)
    half = w["ft_b"].shape[0] // 2

    def pairwise(acc):
        c = np.clip(acc, 0.0, 1.0)
        return c[:half] * c[half:]

    x = np.concatenate([pairwise(accs[own]), pairwise(accs[opp])])
    b = min(max((len(occupied) - 1) // 4, 0), STACKS - 1)
    h0 = w["fc0_w"][b] @ x + w["fc0_b"][b]
    skip = h0[FC0_OUT - 1]
    h = np.clip(h0[:FC0_OUT - 1], 0.0, 1.0)
    h1 = np.clip(w["fc1_w"][b] @ np.concatenate([h, h * h]) + w["fc1_b"][b], 0.0, 1.0)
    out = (w["fc2_w"][b] @ h1)[0] + w["fc2_b"][b][0]
    psqt = (psqts[own][b] - psqts[opp][b]) / np.float32(2.0)
    value = np.float32(out + skip + psqt) * np.float32(OUTPUT_SCALE)
    return max(-SCORE_CLAMP, min(SCORE_CLAMP, int(value)))


def program_params(weights: Dict[str, np.ndarray]):
    """The imported-net type `TpuEngine` runs on its full-refresh path."""
    from fishnet_tpu.models.nnue_import import StockfishNet

    return StockfishNet(**weights).as_device()


def net_work(shapes: Dict[str, int]) -> Dict[str, float]:
    """By ``work_count``'s rules, from ``l1`` alone: a move changes at most 4
    placements, each one row of L1 + 8 PSQT columns a perspective; the
    pairwise product, then L1 -> 16, 30 -> 32, 32 -> 1 of one stack. A king
    move's refresh of its perspective is not counted (the least a node
    needs is the incremental update)."""
    l1 = shapes["l1"]
    row = l1 + STACKS
    acc_flops = 2 * MAX_PIECE_CHANGES * row
    fwd_flops = l1 + 2 * (l1 * FC0_OUT + FC1_IN * FC1_OUT + FC1_OUT) + (FC0_OUT - 1)
    weight_bytes = 4 * (2 * MAX_PIECE_CHANGES * row + l1 * FC0_OUT + FC0_OUT
                        + FC1_IN * FC1_OUT + FC1_OUT + FC1_OUT + 1)
    acc_bytes = 2 * (2 * row * 4)  # pair read, pair written
    return {"flops": float(acc_flops + fwd_flops),
            "bytes": float(weight_bytes + acc_bytes)}
