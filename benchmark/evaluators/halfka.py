"""Plain evaluation of the king-relative wide net (HalfKAv2_hm features,
SFNNv5 layer stacks) in numpy float32: the reference's eval for the
configurations that name ``engine.evaluator`` = "halfka".

The net, from its published description (lichess-org/fishnet build.rs:8-9
embeds nn-1c0000000000.nnue; the layout is nnue-pytorch's / Stockfish's):

* for each perspective p: the king's square oriented (ranks flipped for
  black, files mirrored onto a-d) gives bucket k of 32; every piece, both
  kings too, gives feature ``(k * 11 + kind) * 64 + oriented square``, kind
  0-4 own P N B R Q, 5-9 theirs, 10 either king: 22,528 rows;
  ``acc_p = ft_b + sum ft_w[f]`` (L1 wide), ``psqt_p = sum psqt_w[f]`` (8);
* ``x = [pair(acc_own), pair(acc_opp)]``,
  ``pair(a) = clip(a[:L1/2], 0, 1) * clip(a[L1/2:], 0, 1)`` (L1 values);
* stack b = (pieces - 1) // 4 of eight: ``h0 = fc0_w[b] x + fc0_b[b]`` (16,
  the last is the skip), ``h = clip(h0[:15], 0, 1)``,
  ``h1 = clip(fc1_w[b] [h, h*h] + fc1_b[b], 0, 1)`` (30 -> 32),
  ``out = fc2_w[b] h1 + fc2_b[b]`` (32 -> 1);
* ``cp = (out + skip + (psqt_own[b] - psqt_opp[b]) / 2) * 600``, truncated
  and clamped as the search clamps.

No incremental update, no batching, nothing of the program. The weights are
made from the seed the configuration states (``engine.weights`` = {seed,
l1}; the published file is not in the repository), once a process.
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
# a piece's worth by feature kind (own P N B R Q, theirs, a king) in the
# net's own units of 1/600: Stockfish's classical piece values, with which
# the trainer starts the PSQT columns and near which a trained net keeps
# them — large terms that cancel in a balanced position
PIECE_WORTH = (126, 781, 825, 1276, 2538, -126, -781, -825, -1276, -2538, 0)

_made: Dict[tuple, Dict[str, np.ndarray]] = {}  # the last weights drawn


def load_weights(engine_cfg: dict, root) -> Dict[str, np.ndarray]:
    """Made from ``engine.weights`` = {seed, l1} with numpy's PCG64, so the
    program and the reference get the same bytes on any machine, and kept:
    the harness asks twice a run, and at L1 3,072 the table is 277 MB.

    The scales: an accumulator is 0.5 + about 32 rows of 0.08, so three
    quarters of them lie inside the clip's 0 and 1 and an eighth beyond
    either end; the stacks' outputs and the skip come to 100-150 cp each;
    the PSQT columns hold a piece's worth (PIECE_WORTH, +-3 % by bucket,
    square and column), so a balanced position scores within a few
    hundred centipawns out of terms of up to 2,538 — which is what lets a
    lower precision of the weights show in the score (bfloat16 keeps 8
    bits: 32 such terms a perspective come out about 4 cp off)."""
    spec = engine_cfg["weights"]
    key = (int(spec["seed"]), int(spec["l1"]))
    if key not in _made:
        _made.clear()
        _made[key] = _draw(*key)
    return _made[key]


def _draw(seed: int, l1: int) -> Dict[str, np.ndarray]:
    rng = np.random.Generator(np.random.PCG64(seed))

    def normal(shape, scale, shift=0.0):
        out = rng.standard_normal(shape, dtype=np.float32)
        out *= np.float32(scale)
        out += np.float32(shift)
        return out

    worth = np.repeat(
        np.asarray(PIECE_WORTH, np.float32) / np.float32(OUTPUT_SCALE), SQUARES)
    worth = np.tile(worth, KING_BUCKETS)[:, None]  # (FEATURES, 1) by kind
    return {
        "ft_w": normal((FEATURES, l1), 0.08),
        "ft_b": normal((l1,), 0.1, 0.5),
        "psqt_w": worth * normal((FEATURES, STACKS), 0.03, 1.0)
                  + normal((FEATURES, STACKS), 0.01),
        "fc0_w": normal((STACKS, FC0_OUT, l1), 0.7 / np.sqrt(l1)),
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
    """What ``TpuEngine(params=...)`` takes for these weights: the type
    ``load_nnue`` returns for a published file.

    The configuration is this net *on the incremental path*: the pair
    carried down the search stack, a move's <= 4 rows a perspective, a
    king's move a refresh. A program whose search has no such scheme for
    these params would rebuild both perspectives from the board in every
    lane-step (128 rows of L1 where a node needs 8): another deployment,
    at half the rate, under this one's name. It does not run the
    configuration, so it is told so here and fails at once."""
    from fishnet_tpu.models import nnue
    from fishnet_tpu.models.nnue_import import StockfishNet

    params = StockfishNet(**weights)
    scheme = getattr(nnue, "acc_scheme", None)
    if scheme is None or scheme(params) != "halfka":
        raise RuntimeError(
            "evaluator halfka: this program's search carries no incremental "
            "accumulator for a StockfishNet (fishnet_tpu.models.nnue."
            "acc_scheme); its full refresh every lane-step is not the "
            "configuration")
    return params.as_device()


def net_work(shapes: Dict[str, int]) -> Dict[str, float]:
    """By ``work_count``'s rules, from the configuration's ``net_shapes``: a
    move changes at most 4 placements, each one row of L1 + the PSQT columns
    a perspective; the pairwise product, then one stack's L1 -> fc0, 2 *
    (fc0 - 1) -> fc1, fc1 -> 1. A king move's refresh of its perspective is
    not counted: the least a node needs is the incremental update, whatever
    the program does."""
    l1, fc0, fc1 = shapes["l1"], shapes["fc0"], shapes["fc1"]
    fc1_in = 2 * (fc0 - 1)
    row = l1 + shapes["psqt"]
    acc_flops = 2 * MAX_PIECE_CHANGES * row  # two perspectives, one add each
    fwd_flops = l1 + 2 * (l1 * fc0 + fc1_in * fc1 + fc1) + (fc0 - 1)
    weight_bytes = 4 * (2 * MAX_PIECE_CHANGES * row + l1 * fc0 + fc0
                        + fc1_in * fc1 + fc1 + fc1 + 1)
    return {"flops": float(acc_flops + fwd_flops),
            "bytes": float(weight_bytes + acc_update_pair_bytes(shapes))}


def acc_update_pair_bytes(shapes: Dict[str, int]) -> int:
    """The accumulator pair of one node, read and written (float32)."""
    return 2 * (2 * (shapes["l1"] + shapes["psqt"]) * 4)


def acc_update_bytes(shapes: Dict[str, int]) -> int:
    """The least the row-gather-and-add of one node expansion moves: 4 rows
    a perspective of L1 + PSQT columns, and the pair read and written. What
    ``metrics/nnue.acc_update_roofline_share`` holds the kernel to."""
    row = shapes["l1"] + shapes["psqt"]
    return 4 * 2 * MAX_PIECE_CHANGES * row + acc_update_pair_bytes(shapes)
