"""Host-side model of the device search, for exact-equality testing.

The reference's search correctness is carried by Stockfish itself; the
lockstep device search (ops/search.py) needs an oracle instead. This is a
plain recursive negamax that mirrors the device state machine EXACTLY —
same pseudo-legal movegen and move order, same king-capture refutation,
same capture-only quiescence with stand-pat floor, same fifty-move /
repetition / budget / stack-full leaf rules, same mate/stalemate values,
and the same NNUE evaluation path (incremental board768 accumulators or
full refresh) — so `search_batch` results can be asserted bit-identical
at small depth.

It deliberately calls the device ops (fused into two jitted calls per
node, dispatched from the recursion) rather than re-implementing them in
numpy: float summation order then matches the device program exactly,
keeping int-cast evals bit-stable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models import nnue
from . import tt as tt_mod
from .board import (
    TERM_DRAW,
    TERM_LOSS,
    TERM_NONE,
    TERM_WIN,
    Board,
    make_move,
    move_piece_changes,
    node_rules,
)
from .movegen import generate_moves
from .search import DRAW, ILLEGAL, INF, MATE, NULL_R, _PRUNING


@functools.lru_cache(maxsize=8)
def _jitted(b768: bool, variant: str):
    """Two fused device calls per oracle node (single-core dispatch cost
    dominates the oracle's runtime, so everything per-node is batched into
    `classify`, and per-child into `child`)."""

    def classify(params, b: Board, acc, killers, hist):
        us = b.stm
        illegal, checked, term_kind = node_rules(b, variant)
        if b768 and variant != "atomic":
            val = jnp.int32(
                nnue.forward_from_acc(params, acc, us, nnue.output_bucket(b.board))
            )
        else:
            # atomic explosions exceed the 4-slot incremental scheme —
            # full refresh, same as the device step
            val = jnp.int32(nnue.evaluate(params, b.board, us))
        moves, count, noisy = generate_moves(
            b, variant, killers=killers, hist=hist
        )
        h1, h2 = tt_mod.hash_board(b.board, us, b.ep, b.castling, b.extra, variant)
        return illegal, checked, val, moves, count, noisy, h1, h2, term_kind

    def child(params, b: Board, acc, move):
        nb = make_move(b, move, variant)
        if b768 and variant != "atomic":
            codes, sqs, signs = move_piece_changes(b, move, variant)
            nacc = nnue.apply_acc_updates_768(params, acc, codes, sqs, signs)
        else:
            nacc = acc
        return nb, nacc

    return {
        "classify": jax.jit(classify),
        "child": jax.jit(child),
        "acc_root": jax.jit(nnue.accumulators_768),
    }


class _Oracle:
    def __init__(self, params, depth: int, node_budget: int, max_ply: int,
                 variant: str = "standard", history=None):
        self.p = params
        self.depth = depth
        self.budget = node_budget
        self.max_ply = max_ply
        self.variant = variant
        self.nodes = 0
        self.rep_hits = 0  # repetition-draw leaves seen (test instrumentation)
        # node expansions, the moves their lists hold, the drops among
        # them: what the device counts as ops/search.py MOVEGEN_COUNTERS
        self.movegen = [0, 0, 0]
        self.b768 = nnue.is_board768(params)
        self.ops = _jitted(self.b768, variant)
        # [(h1, h2, halfmove, virtual_ply)]: pre-root game history at
        # virtual ply -distance (mirrors ops/search.py hist_hash slots),
        # then entered in-search path nodes at their real plies.
        # history: [(h1, h2, halfmove, distance)] with distance >= 1
        # plies before the root — pre-filtered to doubled positions the
        # same way the engine seeds the device (see engine/tpu.py
        # _history_arrays).
        self.path = [
            (h1, h2, hm, -dist) for h1, h2, hm, dist in (history or [])
        ]
        # quiet-move ordering state, mirroring the device lane's exactly
        # (ops/search.py killer/history update on fail-high)
        self.killers = np.full((max_ply + 2, 2), -1, np.int32)
        self.hist = np.zeros(4096, np.int32)

    def search(self, b: Board, acc, ply: int, alpha: int, beta: int,
               depth_left: int | None = None,
               from_null: bool = False) -> int:
        """depth_left: per-node remaining depth (root: self.depth); None
        derives the pre-reduction value — kept for the depth==ply-derived
        callers in older tests. from_null: this node was reached by a
        null move (mirrors the device's parent null_st == 2)."""
        if depth_left is None:
            depth_left = self.depth - ply
        ops = self.ops
        (illegal, checked, val, moves, count, noisy, h1, h2,
         term_kind) = ops["classify"](
            self.p, b, acc,
            jnp.asarray(self.killers[min(ply, self.max_ply)]),
            jnp.asarray(self.hist),
        )
        if ply > 0 and bool(illegal):
            return ILLEGAL
        over_budget = self.nodes >= self.budget
        self.nodes += 1
        halfmove = int(b.halfmove)
        fifty = halfmove >= 100
        # twofold repetition along the path (mirrors ops/search.py):
        # equal hash through an unbroken reversible chain
        hh = (int(h1), int(h2))
        repet = any(
            (halfmove - ph) == (ply - vp) and (a, c) == hh
            for a, c, ph, vp in self.path
        )
        self.rep_hits += int(repet)
        in_qs = depth_left <= 0
        stack_full = ply >= self.max_ply

        static_val = max(min(int(val), MATE - 1000), -(MATE - 1000))
        leaf_val = DRAW if (fifty or repet) else static_val
        kind = int(term_kind)
        vterm = kind != TERM_NONE
        if vterm:
            leaf_val = {
                TERM_LOSS: -(MATE - ply),
                TERM_WIN: MATE - ply,
                TERM_DRAW: DRAW,
            }[kind]
        count, noisy = int(count), int(noisy)
        # futility pruning (mirrors ops/search.py bit for bit): frontier
        # node with static eval a margin below alpha expands only the
        # noisy prefix with the static eval as fail-soft floor
        futile = False
        if _PRUNING and not in_qs and not bool(checked) and ply > 0:
            f_margin = 150 if depth_left == 1 else 300
            futile = (
                depth_left <= 2
                and static_val + f_margin <= alpha
                and alpha > -(MATE - 1000)
                and alpha < MATE - 1000
            )
        qs_like = in_qs or futile
        is_leaf = (
            fifty or repet or vterm or over_budget or stack_full
            or (qs_like and noisy == 0)
        )
        if in_qs and leaf_val >= beta:  # stand-pat beta cutoff
            is_leaf = True
        if is_leaf:
            return leaf_val

        n = noisy if qs_like else count
        moves = np.asarray(moves)
        listed = moves[:count]
        self.movegen[0] += 1
        self.movegen[1] += count
        self.movegen[2] += int(np.sum((listed >> 15) & 1))
        if qs_like:
            best = leaf_val  # stand-pat floors best and alpha
            alpha = max(alpha, leaf_val)
        else:
            best = -INF
        searched = 0
        cut = False
        best_move = -1
        board_np = np.asarray(b.board)
        # null-move eligibility, mirroring ops/search.py's nmp_ok bit for
        # bit (antichess excluded there: captures are forced, so passing
        # proves nothing)
        nmp_ok = False
        if _PRUNING and self.variant != "antichess" and not in_qs:
            base = int(b.stm) * 6
            nonpawn = bool(
                ((board_np >= base + 2) & (board_np <= base + 5)).any()
            )
            nmp_ok = (
                depth_left >= 3
                and not bool(checked)
                and not from_null
                and ply > 0
                and static_val >= beta
                and beta < MATE - 1000
                and beta > -(MATE - 1000)
                and nonpawn
            )
        self.path.append((hh[0], hh[1], halfmove, ply))
        try:
            if nmp_ok and not alpha >= beta:
                # same position, opponent to move, ep cleared, halfmove
                # clock reset (breaks repetition chains across the null),
                # searched in the zero-width (beta-1, beta) window at
                # reduced depth — exactly the device's null child
                r = NULL_R + (1 if depth_left >= 7 else 0)
                nb = Board(
                    board=b.board, stm=jnp.int32(1 - int(b.stm)),
                    ep=jnp.int32(-1), castling=b.castling,
                    halfmove=jnp.int32(0), extra=b.extra,
                )
                nv = self.search(
                    nb, acc, ply + 1, -beta, 1 - beta,
                    max(depth_left - 1 - r, 0), from_null=True,
                )
                if nv != ILLEGAL and -nv >= beta and -nv < MATE - 1000:
                    return -nv
            for i in range(n):
                if alpha >= beta:
                    cut = True
                    break
                mv = int(moves[i])
                # late-move reduction, mirroring the device's lmr_ok
                red = 0
                if _PRUNING and not in_qs:
                    mto = (mv >> 6) & 63
                    quiet = ((mv >> 15) & 1) == 1 or (
                        int(board_np[mto]) == 0 and ((mv >> 12) & 7) == 0
                    )
                    if (depth_left >= 3 and i >= 3 and quiet
                            and not bool(checked)):
                        red = 2 if i >= 8 else 1
                cb, cacc = ops["child"](self.p, b, acc, jnp.int32(mv))
                v = self.search(
                    cb, cacc, ply + 1, -beta, -alpha,
                    max(depth_left - 1 - red, 0),
                )
                if v == ILLEGAL:
                    continue
                if red > 0 and -v > alpha:
                    # reduced score beat alpha: re-search at full depth
                    # (the device's RETURN-phase research re-push)
                    v = self.search(
                        cb, cacc, ply + 1, -beta, -alpha,
                        max(depth_left - 1, 0),
                    )
                    if v == ILLEGAL:
                        continue
                searched += 1
                if -v > best:
                    best = -v
                    best_move = mv
                alpha = max(alpha, best)
            # killer/history credit on fail-high, mirroring the device's
            # TRYMOVE update bit for bit (which also fires when the
            # cutoff move happened to be the last one generated)
            if alpha >= beta and best_move >= 0:
                cause = best_move
                cto = (cause >> 6) & 63
                quiet = ((cause >> 15) & 1) == 1 or (
                    int(board_np[cto]) == 0 and ((cause >> 12) & 7) == 0
                )
                if quiet:
                    kp = min(ply, self.max_ply)
                    k0 = int(self.killers[kp, 0])
                    if cause != k0:
                        self.killers[kp] = (cause, k0)
                    dl = max(depth_left, 0)
                    w = min(dl * dl + 1, 1024)
                    idx = cause & 4095
                    self.hist[idx] = min(int(self.hist[idx]) + w, 1 << 20)
        finally:
            self.path.pop()
        # best == -INF mirrors the device's no_legal guard: a futile node
        # whose noisy children were all illegal still carries its static
        # floor in `best` and must return it, not a phantom mate/stalemate
        if searched == 0 and not in_qs and not cut and best == -INF:
            if self.variant == "antichess":
                # the side with no moves (stalemated / out of pieces) WINS
                return MATE - ply
            return -(MATE - ply) if bool(checked) else DRAW
        return best


def oracle_search(params, root: Board, depth: int, node_budget: int,
                  max_ply: int, variant: str = "standard",
                  history=None) -> dict:
    """Search one root exactly like one device lane; → {score, nodes,
    rep_hits, movegen}.

    root: single-lane Board. Matches ops.search.search_batch semantics for
    the same (depth, node_budget, max_ply, variant); scores must agree
    exactly. history: optional [(h1, h2, halfmove, distance)] doubled
    positions from the reversible game tail, distance = plies before the
    root (mirrors the device's hist_hash/hist_halfmove seeding; see
    engine/tpu.py _history_arrays for the Stockfish draw-rule rationale).
    """
    o = _Oracle(params, depth, node_budget, max_ply, variant, history)
    if o.b768:
        acc = o.ops["acc_root"](params, root.board)
    else:
        acc = jnp.zeros((2, params.ft_w.shape[1]), params.ft_w.dtype)
    score = o.search(root, acc, 0, -INF, INF)
    return {"score": score, "nodes": o.nodes, "rep_hits": o.rep_hits,
            "movegen": tuple(o.movegen)}
