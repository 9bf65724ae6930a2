"""NNUE training: supervised regression on (position, score) pairs.

The reference consumes externally-trained Stockfish nets; this framework
can train its own. The step shards over a 2-D ("dp", "tp") mesh: batch over
dp, the feature-transform width (L1) over tp — the gather-heavy FT is the
bulk of the FLOPs, and splitting its output dim keeps each chip's HBM
traffic local until the (tiny) layer stack, where an all_gather over tp
assembles the accumulator. Gradients psum over dp. XLA inserts both
collectives from the shardings; nothing is hand-written.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from ..ops.board import piece_color, piece_type  # noqa: F401 (re-export context)
from ..parallel import partition as _partition
from . import nnue


def batched_forward(params: nnue.NnueParams, boards: jnp.ndarray,
                    stms: jnp.ndarray) -> jnp.ndarray:
    """(B, 64) boards, (B,) stms → (B,) centipawn scores.

    Plain XLA: the eval stack is a few small matmuls + clipped ReLUs that
    XLA fuses on its own. A hand-written Pallas fusion of this stack
    lived here for rounds 2-3 but never reached hardware and only ever
    ran interpreted in training — retired per the round-3 verdict ("measure on hardware or
    delete"); see git history (ops/pallas_nnue.py) to resurrect it if a
    measured win ever justifies it."""
    return jax.vmap(nnue.evaluate, in_axes=(None, 0, 0))(params, boards, stms)


def loss_fn(params, boards, stms, targets):
    pred = batched_forward(params, boards, stms)
    # scale to pawns so the loss is O(1)
    return jnp.mean(((pred - targets) / 100.0) ** 2)


def make_train_step(optimizer):
    @jax.jit
    def train_step(params, opt_state, boards, stms, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, boards, stms, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def param_shardings(mesh: Mesh) -> nnue.NnueParams:
    """TP over the feature-transform width; the small stack is
    replicated. Derived from the partition-rule registry
    (parallel/partition.py PARAM_RULES_TP) — the training layout and the
    search engine's replicated layout live in ONE table."""
    return jax.tree_util.tree_map(
        lambda spec: _partition.named_sharding(mesh, spec),
        _partition.param_specs(tp=True),
    )


def make_sharded_train_step(mesh: Mesh, optimizer):
    """Training step with dp×tp shardings; collectives inserted by XLA."""
    p_shard = param_shardings(mesh)
    batch_shard = _partition.named_sharding(
        mesh, _partition.batch_spec(1))
    board_shard = _partition.named_sharding(
        mesh, _partition.batch_spec(2))

    @partial(
        jax.jit,
        in_shardings=(p_shard, None, board_shard, batch_shard, batch_shard),
        out_shardings=(p_shard, None, None),
    )
    def train_step(params, opt_state, boards, stms, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, boards, stms, targets)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# --------------------------------------------------- training data synthesis


def material_mobility_target(pos) -> float:
    """Cheap supervised target: material + mobility in centipawns, from the
    side to move's perspective (mirrors engine/pyengine.py's evaluation)."""
    from ..chess.types import BISHOP, KNIGHT, PAWN, QUEEN, ROOK

    vals = {PAWN: 100, KNIGHT: 300, BISHOP: 315, ROOK: 500, QUEEN: 900}
    us = pos.turn
    score = 0
    for ptype, val in vals.items():
        score += val * (
            bin(pos.bbs[us][ptype]).count("1")
            - bin(pos.bbs[us ^ 1][ptype]).count("1")
        )
    score += 2 * len(pos.legal_moves())
    return float(score)


def random_position_dataset(n: int, seed: int = 0, max_plies: int = 60):
    """Generate positions by random playouts with material targets."""
    import random as _random

    from ..chess import Position
    from ..ops.board import board_array

    rng = _random.Random(seed)
    boards = np.zeros((n, 64), np.int32)
    stms = np.zeros((n,), np.int32)
    targets = np.zeros((n,), np.float32)
    pos = Position.initial()
    plies = 0
    for i in range(n):
        legal = pos.legal_moves()
        if not legal or plies > max_plies or pos.outcome() is not None:
            pos = Position.initial()
            plies = 0
            legal = pos.legal_moves()
        pos = pos.push(rng.choice(legal))
        plies += 1
        boards[i] = board_array(pos)  # numpy: no per-position device put
        stms[i] = int(pos.turn)
        targets[i] = material_mobility_target(pos)
    return boards, stms, targets


def train_material_net(
    l1: int = 64,
    steps: int = 200,
    batch: int = 256,
    seed: int = 0,
    dataset: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None,
    lr: float = 1e-3,
    feature_set: str = "board768",
):
    """Train a small net against the material+mobility oracle. Returns
    (params, final_loss). Gives the TPU engine sane (if modest) play
    without external weights."""
    params = nnue.init_params(jax.random.PRNGKey(seed), l1=l1, feature_set=feature_set)
    optimizer = optax.adam(lr)
    opt_state = optimizer.init(params)
    step = make_train_step(optimizer)
    if dataset is None:
        dataset = random_position_dataset(batch * 8, seed=seed)
    boards, stms, targets = dataset
    n = boards.shape[0]
    rng = np.random.default_rng(seed)
    loss = None
    for i in range(steps):
        idx = rng.integers(0, n, size=batch)
        params, opt_state, loss = step(
            params, opt_state,
            jnp.asarray(boards[idx]), jnp.asarray(stms[idx]),
            jnp.asarray(targets[idx]),
        )
    return params, float(loss)


# ------------------------------------------ classical target + diverse data
#
# The packaged board768 net is distilled from a classical handcrafted
# evaluation (material + piece-square + mobility), the same bootstrap real
# NNUE lineages used before self-play data existed. The r1 net trained on
# random-playout positions only — near-balanced material throughout — so it
# extrapolated garbage on imbalanced/sparse positions (a bare
# queen-vs-king board eval'd ~0). The dataset below mixes playouts with
# synthetic random-material positions precisely to pin the material axis.

_PST_PAWN = np.array([
    0, 0, 0, 0, 0, 0, 0, 0,
    5, 10, 10, -20, -20, 10, 10, 5,
    5, -5, -10, 0, 0, -10, -5, 5,
    0, 0, 0, 20, 20, 0, 0, 0,
    5, 5, 10, 25, 25, 10, 5, 5,
    10, 10, 20, 30, 30, 20, 10, 10,
    50, 50, 50, 50, 50, 50, 50, 50,
    0, 0, 0, 0, 0, 0, 0, 0,
], np.int32)
_PST_KNIGHT = np.array([
    -50, -40, -30, -30, -30, -30, -40, -50,
    -40, -20, 0, 5, 5, 0, -20, -40,
    -30, 5, 10, 15, 15, 10, 5, -30,
    -30, 0, 15, 20, 20, 15, 0, -30,
    -30, 5, 15, 20, 20, 15, 5, -30,
    -30, 0, 10, 15, 15, 10, 0, -30,
    -40, -20, 0, 0, 0, 0, -20, -40,
    -50, -40, -30, -30, -30, -30, -40, -50,
], np.int32)
_PST_BISHOP = np.array([
    -20, -10, -10, -10, -10, -10, -10, -20,
    -10, 5, 0, 0, 0, 0, 5, -10,
    -10, 10, 10, 10, 10, 10, 10, -10,
    -10, 0, 10, 10, 10, 10, 0, -10,
    -10, 5, 5, 10, 10, 5, 5, -10,
    -10, 0, 5, 10, 10, 5, 0, -10,
    -10, 0, 0, 0, 0, 0, 0, -10,
    -20, -10, -10, -10, -10, -10, -10, -20,
], np.int32)
_PST_ROOK = np.array([
    0, 0, 0, 5, 5, 0, 0, 0,
    -5, 0, 0, 0, 0, 0, 0, -5,
    -5, 0, 0, 0, 0, 0, 0, -5,
    -5, 0, 0, 0, 0, 0, 0, -5,
    -5, 0, 0, 0, 0, 0, 0, -5,
    -5, 0, 0, 0, 0, 0, 0, -5,
    5, 10, 10, 10, 10, 10, 10, 5,
    0, 0, 0, 0, 0, 0, 0, 0,
], np.int32)
_PST_QUEEN = np.array([
    -20, -10, -10, -5, -5, -10, -10, -20,
    -10, 0, 5, 0, 0, 0, 0, -10,
    -10, 5, 5, 5, 5, 5, 0, -10,
    0, 0, 5, 5, 5, 5, 0, -5,
    -5, 0, 5, 5, 5, 5, 0, -5,
    -10, 0, 5, 5, 5, 5, 0, -10,
    -10, 0, 0, 0, 0, 0, 0, -10,
    -20, -10, -10, -5, -5, -10, -10, -20,
], np.int32)
_PST_KING = np.array([
    20, 30, 10, 0, 0, 10, 30, 20,
    20, 20, 0, 0, 0, 0, 20, 20,
    -10, -20, -20, -20, -20, -20, -20, -10,
    -20, -30, -30, -40, -40, -30, -30, -20,
    -30, -40, -40, -50, -50, -40, -40, -30,
    -30, -40, -40, -50, -50, -40, -40, -30,
    -30, -40, -40, -50, -50, -40, -40, -30,
    -30, -40, -40, -50, -50, -40, -40, -30,
], np.int32)
_PSTS = [_PST_PAWN, _PST_KNIGHT, _PST_BISHOP, _PST_ROOK, _PST_QUEEN, _PST_KING]
_PIECE_VALUES = [100, 300, 315, 500, 900, 0]


def classical_eval_target(pos) -> float:
    """Material + piece-square + mobility in cp from the side to move."""
    from ..chess.types import scan

    score = 0
    for color in (0, 1):
        sign = 1 if color == pos.turn else -1
        for ptype in range(6):
            for sq in scan(pos.bbs[color][ptype]):
                o_sq = sq if color == 0 else sq ^ 56
                score += sign * (_PIECE_VALUES[ptype] + int(_PSTS[ptype][o_sq]))
    score += 2 * len(pos.legal_moves())
    return float(np.clip(score, -3000, 3000))


def _random_material_position(rng) -> Optional[object]:
    """A synthetic legal-ish position with random (often lopsided)
    material — the axis random playouts never cover."""
    from ..chess import Position

    board = [""] * 64
    squares = list(range(64))
    rng.shuffle(squares)
    it = iter(squares)
    wk, bk = next(it), next(it)
    while max(abs((wk & 7) - (bk & 7)), abs((wk >> 3) - (bk >> 3))) <= 1:
        bk = next(it)
    board[wk], board[bk] = "K", "k"
    for color, syms in ((0, "PNBRQ"), (1, "pnbrq")):
        counts = [
            rng.randint(0, 8), rng.randint(0, 2), rng.randint(0, 2),
            rng.randint(0, 2), rng.randint(0, 1),
        ]
        for ptype, cnt in enumerate(counts):
            for _ in range(cnt):
                sq = next(it, None)
                if sq is None:
                    break
                if syms[ptype] in "Pp" and (sq < 8 or sq >= 56):
                    continue
                board[sq] = syms[ptype]
    rows = []
    for rank in range(7, -1, -1):
        row, empty = "", 0
        for f in range(8):
            c = board[rank * 8 + f]
            if c:
                row += (str(empty) if empty else "") + c
                empty = 0
            else:
                empty += 1
        rows.append(row + (str(empty) if empty else ""))
    fen = "/".join(rows) + (" w - - 0 1" if rng.random() < 0.5 else " b - - 0 1")
    try:
        return Position.from_fen(fen)
    except Exception:
        return None


def diverse_position_dataset(n: int, seed: int = 0):
    """50% random-playout positions (structure), 50% synthetic
    random-material positions (material axis); classical targets."""
    import random as _random

    from ..chess import Position
    from ..ops.board import board_array

    rng = _random.Random(seed)
    boards = np.zeros((n, 64), np.int32)
    stms = np.zeros((n,), np.int32)
    targets = np.zeros((n,), np.float32)
    pos = Position.initial()
    plies = 0
    i = 0
    while i < n:
        if i % 2 == 0:
            legal = pos.legal_moves()
            if not legal or plies > 80 or pos.outcome() is not None:
                pos = Position.initial()
                plies = 0
                legal = pos.legal_moves()
            pos = pos.push(rng.choice(legal))
            plies += 1
            sample = pos
        else:
            sample = _random_material_position(rng)
            if sample is None or sample.outcome() is not None:
                continue
        # numpy end to end: per-position jnp conversion costs a device
        # put (a host-device round trip each) — at 200k positions the
        # round-5 run spent 30+ min "generating" before the fix
        boards[i] = board_array(sample)
        stms[i] = int(sample.turn)
        targets[i] = classical_eval_target(sample)
        i += 1
    return boards, stms, targets
