"""Lockstep batched alpha-beta search.

The reference's "search layer" is Stockfish's recursive C++ alpha-beta run
in one process per core (reference: §2 of SURVEY.md; fishnet drives it via
`go nodes N` per position, src/stockfish.rs:290-350). On TPU the recursion
becomes an explicit per-lane DFS stack advanced in lockstep by a single
jitted `lax.while_loop` step over B independent lanes:

- copy-make: child boards are written to a (B, MAX_PLY, ...) stack, so
  there is no unmake logic on device;
- pseudo-legal movegen + king-capture refutation: a mover that leaves the
  king en prise is refuted at the child (ILLEGAL sentinel), which keeps
  pin/evasion logic out of the kernel;
- one state machine step = phase ENTER (classify node: illegal/leaf/expand
  with movegen) → phase RETURN (fold a finished child into its parent) →
  phase TRYMOVE (pick next move or finish the node). Phase order is chosen
  so a leaf child costs a single step;
- per-lane node budgets and depth limits; lanes park in DONE and are
  masked out (divergence tax: a step costs the same while any lane runs).

State layout (round-5 redesign): the round-5 device profile
(docs/profile-r5.md) showed the step's cost dominated by per-op overhead —
~380 compiled ops and a ~330 us/step fixed scheduling gap — rather than
compute. The ~30 small per-node arrays are therefore PACKED into three
tables so each phase issues ONE fused row write instead of ~a dozen:

  bt   (B, P+1, BT_W)  board rows: board(64), stm, ep, castling(4),
                       halfmove, extra(12), path-hash words (int32 bits)
  nt   (B, P+1, NT_W)  per-node search scalars: move cursor, window,
                       null/LMR state, pv length, remaining depth,
                       in-check flag, killer slots
  lane (B, LN_W)       per-lane scalars: ply, mode, return value/depth,
                       leaf-store mark, node counter, budget, root
                       window/result, LMR re-search flag

MultiPV and iterative deepening are driven from the host (engine/tpu.py):
lanes are cheap, so multipv lanes are just more lanes.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Buffer donation below is best-effort by design: XLA:CPU declines to
# alias through the select ops _merge_lanes lowers to, and jax then
# warns once per compile. The donation still holds wherever the backend
# CAN alias (the big _run_segment tables, TPU merges), so the warning is
# pure noise here — silence exactly it, nothing broader.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

from ..aot import registry as _aot_registry
from ..models import nnue, nnue_import
from ..utils import sanitize as _sanitize
from ..utils import settings
from .board import (
    TERM_LOSS,
    TERM_NONE,
    TERM_WIN,
    Board,
    make_move,
    move_piece_changes,
    node_rules,
)
from .movegen import DROP_FLAG, MAX_MOVES, generate_moves, max_moves_for
from . import tt as _tt_mod

INF = 32500
MATE = 32000
ILLEGAL = 99999  # sentinel: the move leading to this node was illegal
DRAW = 0

MODE_ENTER = 0
MODE_RETURN = 1
MODE_TRYMOVE = 2
MODE_DONE = 3

# packed boundary summary (int32, shape (B+3, 4)): everything the host
# needs to decide a segment boundary — done bitmap plus per-lane
# nodes/score/best-move — in ONE small transfer instead of the full
# extract_results set; row B holds the segment's step count and, in
# columns SUM_COUNTS, its movegen counters (node expansions, the moves
# their lists hold, the drops among those), row B+1 in the same columns
# its accumulator counters (perspective updates made, perspectives
# rebuilt from the board, feature rows gathered), row B+2 which net
# answered the node expansions' evals (the "dual" scheme's: the small
# net alone, the small net handed back to the big one, the big net
# chosen; 0 under every other scheme); `movegen_counts`, `acc_counts`
# and `eval_counts` read them. PV rows are pulled separately, and only
# for lanes that actually finished.
SUM_DONE, SUM_NODES, SUM_SCORE, SUM_MOVE = range(4)
SUM_W = 4
SUM_TAIL = 3  # the rows behind the lanes'
SUM_COUNTS = slice(1, 4)
MOVEGEN_COUNTERS = ("movegen_nodes", "movegen_moves", "movegen_drops")
ACC_COUNTERS = ("acc_updates", "acc_refreshes", "acc_rows")
EVAL_COUNTERS = ("eval_small", "eval_reeval", "eval_big")
SEGMENT_COUNTERS = MOVEGEN_COUNTERS + ACC_COUNTERS + EVAL_COUNTERS


def _tail_counts(rows: np.ndarray, names) -> dict:
    """One tail row of the summary ((SUM_W,), or (n_shard, SUM_W) under a
    mesh: summed over shards) under `names`. The device counts in int32
    and may wrap past 2^31 on the longest segments at full width; read as
    uint32 a count holds to 2^32."""
    rows = np.asarray(rows, np.int32).reshape(-1, SUM_W)
    sums = rows[:, SUM_COUNTS].view(np.uint32).astype(np.int64).sum(axis=0)
    return dict(zip(names, map(int, sums)))


def movegen_counts(last_rows: np.ndarray) -> dict:
    """The movegen counters of one segment from the summary's row B."""
    return _tail_counts(last_rows, MOVEGEN_COUNTERS)


def acc_counts(last_rows: np.ndarray) -> dict:
    """The accumulator counters of one segment from the summary's row B+1."""
    return _tail_counts(last_rows, ACC_COUNTERS)


def eval_counts(last_rows: np.ndarray) -> dict:
    """The eval counters of one segment from the summary's row B+2."""
    return _tail_counts(last_rows, EVAL_COUNTERS)

# game-history repetition seeding: hashes of up to MAX_HIST reversible
# game positions before each lane's root (the reference feeds Stockfish
# the full `position fen ... moves ...` history, so repetitions against
# already-played positions score as draws — src/stockfish.rs:298-306).
# Slot MAX_HIST-1 is the root's parent; unused slots carry the sentinel
# halfmove, which can never satisfy the reversible-chain condition.
MAX_HIST = 16
HIST_HM_SENTINEL = -32000

# ---------------------------------------------------------- packed layouts
# nt fields (one int32 row per node)
(NT_COUNT, NT_MIDX, NT_SEARCHED, NT_ALPHA, NT_ALPHA0, NT_BETA, NT_BEST,
 NT_BMOVE, NT_NULL, NT_LASTRED, NT_PVLEN, NT_DL, NT_INCHECK, NT_K0,
 NT_K1) = range(15)
NT_W = 16
# bt fields (one int32 row per node's board)
BT_BOARD = 0
BT_STM = 64
BT_EP = 65
BT_CAST = 66
BT_HM = 70
BT_EXTRA = 71
BT_PH1 = 83  # path-hash words, uint32 stored as int32 bits
BT_PH2 = 84
BT_W = 96
# lane fields
(LN_PLY, LN_MODE, LN_RET, LN_RETD, LN_SMARK, LN_SVAL, LN_NODES, LN_DLIM,
 LN_BUDGET, LN_RSCORE, LN_RMOVE, LN_RALPHA, LN_RBETA, LN_RESEARCH) = range(14)
# lane-group metadata (Lazy-SMP helper lanes, engine/tpu.py): the lane's
# ordering-jitter seed (0 = primary / unperturbed) and its group id (the
# original lane index of the primary whose root it replicates). Carried
# for debugging/extraction; the jitter's effect is baked into the
# initial history table by init_state.
LN_JITTER = 14
LN_GROUP = 15
LN_W = 16

# nt fields ENTER initializes on node expansion vs on every entry: a
# single full-row write reproduces the per-field masks because the row
# vector keeps the old value wherever the mask is off (see _step_lane)
_FM_EXPAND = np.zeros(NT_W, bool)
_FM_EXPAND[[NT_COUNT, NT_MIDX, NT_SEARCHED, NT_ALPHA, NT_ALPHA0, NT_BETA,
            NT_BEST, NT_BMOVE, NT_NULL, NT_LASTRED]] = True
_FM_ENTER = np.zeros(NT_W, bool)
_FM_ENTER[[NT_PVLEN, NT_INCHECK]] = True

# FISHNET_TPU_SELECT_UPDATES: implement every per-lane dynamic row write
# as a one-hot masked select (=1, the DEFAULT since round 5) instead of a
# dynamic-update-slice scatter (=0). Select is the workaround for the
# device fault bisected in docs/tpu-hang.md (B>=16 lanes with max_ply>=4
# hung or killed the TPU worker — suspected miscompiled scatter at
# multi-sublane lane counts) AND, since the round-5 packed-table layout,
# dramatically faster: scatter lowers the packed row writes to a
# serialized form costing 25 ms/step at B=256 vs select's 1.15 ms
# (docs/profile-r5.md). The two modes are bit-identical
# (tests/test_search.py proves it on CPU).
_SELECT_UPDATES = settings.get_bool("FISHNET_TPU_SELECT_UPDATES")

# FISHNET_TPU_NO_PRUNING=1: disable null-move pruning, late-move
# reductions AND futility pruning (debug/A-B lever; the oracle mirrors
# whatever mode is active). All three cut the tree the reference's
# engine cuts it with (Stockfish's search.cpp nullMove/LMR/futility are
# the biggest reducers behind its depth-22 budgets — reference
# src/api.rs:275-281 sends depth 22 move jobs unreachable by plain
# alpha-beta; futility itself lives at the ENTER phase below):
# - null move: at a non-PV-critical node whose static eval already
#   beats beta, give the opponent a free move at reduced depth; if the
#   score STILL comes back >= beta, the node fails high without
#   expanding a single real child.
# - LMR: late, quiet, unchecked moves search at reduced depth first and
#   only re-search at full depth when the reduced result beats alpha.
# ("" and "0" both leave pruning ON — same parse as SELECT_UPDATES, so
# exporting the var as 0 never silently flips the search mode)
_PRUNING = not settings.get_bool("FISHNET_TPU_NO_PRUNING")
NULL_R = 2  # base null-move depth reduction (+1 at depth_left >= 7)


def _is_quiet(move: jnp.ndarray, board_row: jnp.ndarray) -> jnp.ndarray:
    """Non-capture, non-promotion move (drops count as quiet; en passant
    reads as quiet, which only costs ordering). Shared by the killer/
    history credit and the LMR reduction test so the two paths can never
    disagree on what 'quiet' means; move must be >= 0 (masked upstream)."""
    to = jnp.clip((move >> 6) & 63, 0, 63)
    return (((move >> 15) & 1) == 1) | (
        (board_row[to] == 0) & (((move >> 12) & 7) == 0)
    )


def _row_set(arr: jnp.ndarray, idx, row, mask) -> jnp.ndarray:
    """arr (R, ...) ← row at position idx where mask (all unbatched;
    vmapped over lanes). Scatter or one-hot select per _SELECT_UPDATES."""
    if not _SELECT_UPDATES:
        return arr.at[idx].set(jnp.where(mask, row, arr[idx]))
    sel = (jnp.arange(arr.shape[0], dtype=jnp.int32) == idx) & mask
    sel = sel.reshape((arr.shape[0],) + (1,) * (arr.ndim - 1))
    return jnp.where(sel, row, arr)


def _field_set(tab: jnp.ndarray, row_idx, field: int, val, mask) -> jnp.ndarray:
    """tab (R, W): tab[row_idx, field] ← val where mask, as one fused
    2-D one-hot select (no row read needed)."""
    oh_r = (jnp.arange(tab.shape[0], dtype=jnp.int32) == row_idx) & mask
    oh_f = jnp.arange(tab.shape[1], dtype=jnp.int32) == field
    return jnp.where(oh_r[:, None] & oh_f[None, :], val, tab)


def _board_from_row(row: jnp.ndarray) -> Board:
    return Board(
        board=row[BT_BOARD:BT_BOARD + 64],
        stm=row[BT_STM],
        ep=row[BT_EP],
        castling=row[BT_CAST:BT_CAST + 4],
        halfmove=row[BT_HM],
        extra=row[BT_EXTRA:BT_EXTRA + 12],
    )


def _row_from_board(b: Board, ph1=None, ph2=None) -> jnp.ndarray:
    z = jnp.zeros((1,), jnp.int32)
    ph1 = z if ph1 is None else jnp.asarray(ph1, jnp.int32)[None]
    ph2 = z if ph2 is None else jnp.asarray(ph2, jnp.int32)[None]
    return jnp.concatenate([
        b.board.astype(jnp.int32),
        jnp.asarray(b.stm, jnp.int32)[None],
        jnp.asarray(b.ep, jnp.int32)[None],
        b.castling.astype(jnp.int32),
        jnp.asarray(b.halfmove, jnp.int32)[None],
        b.extra.astype(jnp.int32),
        ph1, ph2,
        jnp.zeros((BT_W - BT_PH2 - 1,), jnp.int32),
    ])


class SearchState(NamedTuple):
    bt: jnp.ndarray  # (B, P+1, BT_W) int32 board rows
    nt: jnp.ndarray  # (B, P+1, NT_W) int32 per-node scalars
    lane: jnp.ndarray  # (B, LN_W) int32 per-lane scalars
    hist_hash: jnp.ndarray  # (B, MAX_HIST, 2) uint32 pre-root game hashes
    hist_halfmove: jnp.ndarray  # (B, MAX_HIST) their halfmove counters
    moves: jnp.ndarray  # (B, P, MAX_MOVES) int32
    hist: jnp.ndarray  # (B, 4096) from|to-indexed history counters
    pv: jnp.ndarray  # (B, P, P) int32
    # incremental NNUE accumulators, by nnue.acc_scheme: (B, P+1, 2, L1),
    # or for "halfka" (B, 2·(P+2), L1+8) — row 2·ply + perspective, the 8
    # PSQT sums behind the L1 values (nnue_import.acc_*), a spare pair last;
    # for "dual" the same with both nets' values and sums a row
    acc: jnp.ndarray


def init_state(params: nnue.NnueParams, roots: Board, depth: jnp.ndarray,
               node_budget: jnp.ndarray, max_ply: int,
               variant: str = "standard",
               hist_hash=None, hist_halfmove=None,
               root_alpha=None, root_beta=None,
               order_jitter=None, group=None) -> SearchState:
    """roots: batched Board (B leading dim); depth/node_budget: (B,).

    hist_hash (B, MAX_HIST, 2) / hist_halfmove (B, MAX_HIST): optional
    reversible game-history tail per lane (see MAX_HIST above); None
    seeds the sentinel (no pre-root repetitions possible).
    root_alpha/root_beta (B,): optional aspiration window at the root
    (host-side iterative deepening re-searches on fail-low/high).
    order_jitter (B,): optional per-lane move-ordering perturbation seed
    for Lazy-SMP helper lanes. A lane with jitter j > 0 starts with
    small pseudo-random history counters (hash-mixed from j), so its
    quiet-move ordering breaks ties differently from every other lane of
    its group — the lanes then explore the shared tree in different
    orders and feed each other TT entries. Jitter 0 seeds exact zeros:
    a jitter-0 lane is bit-identical to one searched without the
    argument. group (B,): opaque per-lane group tag (stored, unused by
    the search itself)."""
    B = roots.stm.shape[0]
    P = max_ply
    scheme = nnue.acc_scheme(params, variant)
    # acc stays f32 even under bf16-quantized weights (nnue.cast_params):
    # incremental adds accumulate rounding error down the stack otherwise.
    # int8-quantized nets use int32 accumulators — integer adds are exact.
    adt = nnue.acc_dtype(params)
    if scheme in nnue.ROW_SCHEMES:
        # the root's two rows, rebuilt from its board (a refill comes
        # through here too: _splice_lanes)
        root_acc = jax.vmap(
            lambda b: nnue_import.acc_refresh_pair(
                params, b, nnue_import.refresh_rows(variant))
        )(roots.board)
        # ... and one pair past the deepest ply's, which a lane that
        # pushes no child writes to (_step_lane), so that every lane's
        # write is in bounds
        acc = jnp.zeros((B, 2 * (P + 2), root_acc.shape[-1]), adt)
        acc = acc.at[:, :2].set(root_acc)
    else:
        # (a full-refresh program carries a stack it never reads)
        table = (params.ft if isinstance(params, nnue_import.DualNet)
                 else params.ft_w)
        l1 = table.shape[1]
        if scheme == "board768":
            root_acc = jax.vmap(nnue.accumulators_768, in_axes=(None, 0))(
                params, roots.board
            )
        else:
            root_acc = jnp.zeros((B, 2, l1), table.dtype)
        acc = jnp.zeros((B, P + 1, 2, l1), adt)
        acc = acc.at[:, 0].set(root_acc.astype(adt))

    bt = jnp.zeros((B, P + 1, BT_W), jnp.int32)
    bt = bt.at[:, :, BT_EP].set(-1)
    bt = bt.at[:, :, BT_CAST:BT_CAST + 4].set(-1)
    root_rows = jax.vmap(_row_from_board)(roots)
    bt = bt.at[:, 0].set(root_rows)

    nt = jnp.zeros((B, P + 1, NT_W), jnp.int32)
    nt = nt.at[:, :, NT_ALPHA].set(-INF)
    nt = nt.at[:, :, NT_ALPHA0].set(-INF)
    nt = nt.at[:, :, NT_BETA].set(INF)
    nt = nt.at[:, :, NT_BEST].set(-INF)
    nt = nt.at[:, :, NT_BMOVE].set(-1)
    nt = nt.at[:, :, NT_K0].set(-1)
    nt = nt.at[:, :, NT_K1].set(-1)
    nt = nt.at[:, 0, NT_DL].set(depth.astype(jnp.int32))

    lane = jnp.zeros((B, LN_W), jnp.int32)
    lane = lane.at[:, LN_DLIM].set(depth.astype(jnp.int32))
    lane = lane.at[:, LN_BUDGET].set(node_budget.astype(jnp.int32))
    lane = lane.at[:, LN_RSCORE].set(-INF)
    lane = lane.at[:, LN_RMOVE].set(-1)
    lane = lane.at[:, LN_RALPHA].set(
        jnp.full((B,), -INF, jnp.int32) if root_alpha is None
        else jnp.asarray(root_alpha, jnp.int32)
    )
    lane = lane.at[:, LN_RBETA].set(
        jnp.full((B,), INF, jnp.int32) if root_beta is None
        else jnp.asarray(root_beta, jnp.int32)
    )
    if order_jitter is not None:
        lane = lane.at[:, LN_JITTER].set(jnp.asarray(order_jitter, jnp.int32))
    if group is not None:
        lane = lane.at[:, LN_GROUP].set(jnp.asarray(group, jnp.int32))

    hist0 = jnp.zeros((B, 4096), jnp.int32)
    if order_jitter is not None:
        # jittered lanes start from small (0..255) pseudo-random history
        # counters instead of zeros, and exactly zero where jitter == 0.
        # The range matters: move ordering reads hist >> 5 (movegen.py
        # hbonus), so seeds below 32 would be invisible — 0..255 yields
        # ordering bonuses of 0..7 key units, enough to reorder the
        # equal-history quiet tail, while sustained real cutoffs (dl²+1
        # credit each, growing to 2^20) still dominate within a few
        # fail-highs
        j = jnp.asarray(order_jitter, jnp.int32).astype(jnp.uint32)
        idx = jnp.arange(4096, dtype=jnp.uint32)
        mix = (j[:, None] * jnp.uint32(2654435761)) ^ (
            idx[None, :] * jnp.uint32(2246822519)
        )
        mix = mix ^ (mix >> 15)
        hist0 = jnp.where(
            (j > 0)[:, None], (mix & jnp.uint32(255)).astype(jnp.int32), hist0
        )

    if hist_hash is None:
        hist_hash = jnp.zeros((B, MAX_HIST, 2), jnp.uint32)
    if hist_halfmove is None:
        hist_halfmove = jnp.full((B, MAX_HIST), HIST_HM_SENTINEL, jnp.int32)
    return SearchState(
        bt=bt, nt=nt, lane=lane,
        hist_hash=jnp.asarray(hist_hash, jnp.uint32),
        hist_halfmove=jnp.asarray(hist_halfmove, jnp.int32),
        moves=jnp.full((B, P, max_moves_for(variant)), -1, jnp.int32),
        hist=hist0,
        pv=jnp.full((B, P, P), -1, jnp.int32),
        acc=acc,
    )


def _step_lane(params: nnue.NnueParams, s: SearchState,
               tt_hit=None, tt_score=None, tt_move=None,
               variant: str = "standard"):
    """One state-machine step for a single lane (vmapped over B):
    → (the lane's next SearchState, its StepAux of the step).

    The three phases keep their row state in registers: ENTER composes
    the entered node's nt/bt rows, RETURN composes the parent's, and
    TRYMOVE selects whichever row it acts on from those — so the whole
    step issues four nt row writes, two bt row writes and one write each
    to moves/pv/acc/hist, instead of ~30 per-array scatters (round-5
    profile: per-op overhead dominated the step).

    tt_hit/tt_score: a usable transposition-table cutoff for this lane's
    current ENTER node (probed outside the vmap against the shared table);
    tt_move: stored best move for ordering (-1 when none). None → no TT.

    The `jax.named_scope("step.*")` blocks are names only (each op's
    metadata; the compiled program is the same instructions): they cut
    the step at its own seams so a profile can say what a fusion is for
    (tools/profile_step.py prints device time by scope).
    """
    lane = s.lane
    ply0 = lane[LN_PLY]
    mode0 = lane[LN_MODE]
    nodes = lane[LN_NODES]
    parent0 = jnp.maximum(ply0 - 1, 0)
    P1 = s.bt.shape[0]  # P+1 rows
    ntr0 = s.nt[ply0]
    ntp0 = s.nt[parent0]
    btr0 = s.bt[ply0]
    btp0 = s.bt[parent0]
    moves_p_row = s.moves[jnp.minimum(parent0, s.moves.shape[0] - 1)]

    # ---------------------------------------------------------- phase ENTER
    enter = mode0 == MODE_ENTER
    with jax.named_scope("step.rules"):
        b = _board_from_row(btr0)
        us = b.stm
        # legality of the move that led here + check state + variant-rule
        # game end, all per the statically compiled variant (board.node_rules)
        illegal_raw, we_are_checked, term_kind = node_rules(b, variant)
        parent_illegal = (ply0 > 0) & illegal_raw
        depth_left = ntr0[NT_DL]
        # this node was reached by a null move: its window is the parent's
        # null-window (beta-1, beta) seen from this side — and it must not
        # null-move again (two passes in a row search the parent's position)
        parent_null = (ply0 > 0) & (ntp0[NT_NULL] == 2)
        over_budget = nodes >= lane[LN_BUDGET]
        fifty = b.halfmove >= 100

        # twofold repetition along the search path (reference behavior is
        # Stockfish's draw scoring, observable through src/stockfish.rs score
        # output): hash the position on entry, scan ancestors for an equal
        # hash reachable through an unbroken reversible-move chain
        # (halfmove[ply]-halfmove[k] == ply-k). Path-dependent by nature, so
        # repetition draws are never TT-stored and never TT-overridden; the
        # residual graph-history interaction is the same approximation every
        # real engine ships. (_tt_mod is imported at module top: importing it
        # lazily inside this jit-traced function once leaked its module-level
        # Zobrist tables as tracers — see round-2 verdict.)
        h1, h2 = _tt_mod.hash_board(
            b.board, us, b.ep, b.castling, b.extra, variant
        )
        h1i = jax.lax.bitcast_convert_type(h1, jnp.int32)
        h2i = jax.lax.bitcast_convert_type(h2, jnp.int32)
        ks = jnp.arange(P1, dtype=jnp.int32)
        chain_ok = (b.halfmove - s.bt[:, BT_HM]) == (ply0 - ks)
        repet_path = jnp.any(
            (ks < ply0)
            & chain_ok
            & (s.bt[:, BT_PH1] == h1i)
            & (s.bt[:, BT_PH2] == h2i)
        )
        # ... and against the pre-root game history: slot k sits at virtual
        # ply k - MAX_HIST, so the unbroken-reversible-chain condition is
        # halfmove distance == ply distance with that offset
        hk = jnp.arange(s.hist_halfmove.shape[0], dtype=jnp.int32)
        hist_chain = (b.halfmove - s.hist_halfmove) == (
            ply0 + (s.hist_halfmove.shape[0] - hk)
        )
        repet_hist = jnp.any(
            hist_chain & (s.hist_hash[:, 0] == h1) & (s.hist_hash[:, 1] == h2)
        )
        repet = enter & (repet_path | repet_hist)
    # window inherited from the parent (negamax flip); a null child runs
    # the parent's zero-width null-window (beta-1, beta) instead
    entry_alpha = jnp.where(ply0 == 0, lane[LN_RALPHA], -ntp0[NT_BETA])
    entry_beta = jnp.where(
        ply0 == 0, lane[LN_RBETA],
        jnp.where(parent_null, 1 - ntp0[NT_BETA], -ntp0[NT_ALPHA]),
    )
    # quiescence: past the nominal depth, keep expanding CAPTURES until
    # the position is quiet (gen_noisy == 0), the stack is full, or the
    # budget runs out — the standard horizon-effect fix, with stand-pat
    # as the floor (see the expand section below)
    in_qs = depth_left <= 0
    stack_full = ply0 >= s.moves.shape[0]  # no moves row / child slot left

    with jax.named_scope("step.eval"):
        # leaf value: NNUE eval (or draw for 50-move). Under an incremental
        # scheme (nnue.acc_scheme: board768, or an imported king-relative
        # net) the accumulator came down the stack and only the layer stack
        # runs here; our own king-relative NnueParams pay a full refresh per
        # step — as does atomic, whose explosions exceed the 4-slot
        # incremental update scheme (move_piece_changes). The oracle
        # (ops/oracle.py) mirrors board768's scheme and refreshes otherwise.
        scheme = nnue.acc_scheme(params, variant)
        # "dual": which net answered, counted below for node expansions
        eval_pick = jnp.zeros((3,), jnp.int32)
        if scheme == "board768":
            leaf_val = jnp.int32(
                nnue.forward_from_acc(params, s.acc[ply0], us, nnue.output_bucket(b.board))
            )
        elif scheme == "halfka":
            leaf_val = jnp.int32(nnue_import.forward_sf_from_acc(
                params, jax.lax.dynamic_slice_in_dim(s.acc, 2 * ply0, 2),
                us, nnue.output_bucket(b.board),
            ))
        elif scheme == "dual":
            leaf_val, eval_pick = nnue_import.forward_dual_from_acc(
                params, jax.lax.dynamic_slice_in_dim(s.acc, 2 * ply0, 2),
                us, nnue.output_bucket(b.board), b.board,
            )
        else:
            leaf_val = jnp.int32(nnue.evaluate(params, b.board, us))
        leaf_val = jnp.clip(leaf_val, -MATE + 1000, MATE - 1000)
        static_val = leaf_val  # pre-draw-override eval (null-move eligibility)
        leaf_val = jnp.where(fifty | repet, DRAW, leaf_val)

        # variant-rule game end (3 checks, exploded king, hill, goal rank,
        # horde destroyed) ends the node at once — takes precedence over
        # draws; mate-range (or rule-draw) values are never TT-stored
        vterm = term_kind != TERM_NONE
        leaf_val = jnp.where(
            vterm,
            jnp.where(
                term_kind == TERM_LOSS, -(MATE - ply0),
                jnp.where(term_kind == TERM_WIN, MATE - ply0, DRAW),
            ),
            leaf_val,
        )

    with jax.named_scope("step.movegen"):
        gen_moves, gen_count, gen_noisy = generate_moves(
            b, variant,
            killers=jnp.stack([ntr0[NT_K0], ntr0[NT_K1]]),
            hist=s.hist,
        )
    with jax.named_scope("step.enter"):
        # futility pruning: at a frontier node (depth_left 1-2, not in check,
        # non-mate window) whose static eval sits a margin below alpha, quiet
        # moves cannot realistically raise alpha — expand only the noisy
        # prefix, exactly the QS mechanics with the static eval as the
        # fail-soft floor (static < alpha, so the floor never raises alpha).
        # The same speculative unsoundness every real engine ships: skipped
        # quiets are treated as searched-and-failed-low.
        if _PRUNING:
            f_margin = jnp.where(depth_left == 1, 150, 300)
            futile = (
                ~in_qs
                & (depth_left <= 2)
                & ~we_are_checked
                & (ply0 > 0)
                & (static_val + f_margin <= entry_alpha)
                & (entry_alpha > -(MATE - 1000))
                & (entry_alpha < MATE - 1000)
            )
        else:
            futile = jnp.bool_(False)
        qs_like = in_qs | futile  # expands noisy prefix only, static floor
        is_leaf = (
            fifty | repet | vterm | over_budget | stack_full
            | (qs_like & (gen_noisy == 0))
        )
        # stand-pat beta cutoff: in QS the static eval is already >= beta —
        # the opponent wouldn't enter this line; fail high immediately
        stand_pat_cut = in_qs & (leaf_val >= entry_beta)
        is_leaf |= stand_pat_cut

        # TT cutoff: treat as a leaf return with the stored score (never at
        # the root — the root must produce a move; never on fifty-move or
        # repetition draws — the hash excludes the halfmove counter and the
        # path, so a stored score must not override a forced draw)
        use_tt = (
            (tt_hit & (ply0 > 0) & ~fifty & ~repet & ~vterm)
            if tt_hit is not None
            else jnp.bool_(False)
        )
        to_return = parent_illegal | is_leaf | use_tt
        expand = enter & ~to_return
        # what _run_segment sums over lanes and steps (MOVEGEN_COUNTERS):
        # this step expanded a node, the moves its list holds, and the
        # drops among them (only the crazyhouse program has the flag)
        if variant == "crazyhouse":
            # a listed move fits 16 bits and an empty slot is -1, so the
            # drop flag (bit 15) is set exactly where the slot >= DROP_FLAG
            gen_drops = jnp.sum(gen_moves >= DROP_FLAG).astype(jnp.int32)
        else:
            gen_drops = jnp.int32(0)
        movegen = expand.astype(jnp.int32) * jnp.stack(
            [jnp.int32(1), gen_count, gen_drops])
        # (EVAL_COUNTERS) and which net's eval the expanded node took
        eval_counts = expand.astype(jnp.int32) * eval_pick
        # mark fresh static-eval leaves for the runner's depth-0 TT store.
        # Quiet positions only: a quiet static eval IS the node's QS value,
        # while a noisy leaf (budget/stack cutoff) stored as depth-0 EXACT
        # would later short-circuit a real QS expansion of the same position.
        # (fifty/repetition draws excluded: they don't transpose; variant
        # terminals excluded: their ply-relative mate-range values must
        # never be TT-stored)
        leaf_store = (
            enter & is_leaf & ~parent_illegal & ~use_tt & ~fifty & ~repet
            & ~vterm & (gen_noisy == 0)
        )
        store_mark = leaf_store
        store_val = jnp.where(leaf_store, leaf_val, 0)

        # order the stored TT move first (classic biggest ordering win); not
        # in QS, where the swap could pull a quiet move into the noisy prefix
        if tt_move is not None:
            tm_at = jnp.argmax(gen_moves == tt_move)
            tm_present = (tt_move >= 0) & (gen_moves[tm_at] == tt_move) & ~qs_like
            m0 = gen_moves[0]
            # dynamic-index swap routed through _row_set so the
            # SELECT_UPDATES experiment covers this scatter too (the index-0
            # write below is static — not a dynamic-update-slice)
            gen_moves = _row_set(gen_moves, tm_at, m0, tm_present)
            gen_moves = gen_moves.at[0].set(
                jnp.where(tm_present, tt_move, gen_moves[0])
            )

        # stand-pat: in QS the node may decline every capture and keep the
        # static eval, so it floors both best and alpha (futile nodes reuse
        # the same floor; their static sits below alpha by construction, so
        # only `best` actually moves — the fail-soft return value)
        # null-move eligibility (Stockfish search.cpp nullMove conditions,
        # minus the zugzwang verification search): interior node, depth to
        # spare, not in check, not already inside a null subtree, static
        # eval >= beta, non-mate window, and side to move still has a piece
        # (pawn/king-only positions are where the null observation fails)
        if _PRUNING and variant != "antichess":
            # antichess excluded: captures are FORCED there, so passing is
            # not "at least as bad as the best move" — the null observation
            # that justifies the cutoff simply doesn't hold
            us_base = us * 6
            nonpawn = jnp.any(
                (b.board >= us_base + 2) & (b.board <= us_base + 5)
            )
            nmp_ok = (
                ~in_qs
                & (depth_left >= 3)
                & ~we_are_checked
                & ~parent_null
                & (ply0 > 0)
                & (static_val >= entry_beta)
                & (entry_beta < MATE - 1000)
                & (entry_beta > -(MATE - 1000))
                & nonpawn
            )
            null_v = jnp.where(nmp_ok, 1, 0)
        else:
            null_v = jnp.int32(0)

        # the entered node's nt row, composed once: fields in _FM_EXPAND take
        # their expansion value under `expand`, _FM_ENTER fields under
        # `enter`, everything else keeps its old value — so one full-row
        # write under `enter` reproduces the per-field write masks exactly
        nv = jnp.stack([
            jnp.where(qs_like, gen_noisy, gen_count),            # NT_COUNT
            jnp.int32(0),                                        # NT_MIDX
            jnp.int32(0),                                        # NT_SEARCHED
            jnp.where(qs_like, jnp.maximum(entry_alpha, leaf_val),
                      entry_alpha),                              # NT_ALPHA
            entry_alpha,                                         # NT_ALPHA0
            entry_beta,                                          # NT_BETA
            jnp.where(qs_like, leaf_val, -INF),                  # NT_BEST
            jnp.int32(-1),                                       # NT_BMOVE
            null_v,                                              # NT_NULL
            jnp.int32(0),                                        # NT_LASTRED
            jnp.int32(0),                                        # NT_PVLEN
            ntr0[NT_DL],                                         # NT_DL
            we_are_checked.astype(jnp.int32),                    # NT_INCHECK
            ntr0[NT_K0],                                         # NT_K0
            ntr0[NT_K1],                                         # NT_K1
            jnp.int32(0),
        ])
        sel = (jnp.asarray(_FM_EXPAND) & expand) | (jnp.asarray(_FM_ENTER) & enter)
        ntE = jnp.where(sel, nv, ntr0)
        nt_new = _row_set(s.nt, ply0, ntE, enter)

        btE = btr0.at[BT_PH1].set(h1i).at[BT_PH2].set(h2i)
        bt_new = _row_set(s.bt, ply0, btE, enter)
        moves_new = _row_set(
            s.moves, jnp.minimum(ply0, s.moves.shape[0] - 1), gen_moves, expand
        )

        ret = jnp.where(
            enter & to_return,
            jnp.where(
                parent_illegal,
                ILLEGAL,
                jnp.where(use_tt, tt_score, leaf_val) if tt_score is not None
                else leaf_val,
            ),
            lane[LN_RET],
        )
        # ret_depth: 0 for static leaves, -1 for TT-sourced values (already in
        # the table — don't re-store them)
        ret_depth = jnp.where(
            enter & to_return, jnp.where(use_tt, -1, 0), lane[LN_RETD]
        )
        nodes = nodes + jnp.where(enter & ~parent_illegal, 1, 0)
        mode = jnp.where(
            enter, jnp.where(to_return, MODE_RETURN, MODE_TRYMOVE), mode0
        )

    # --------------------------------------------------------- phase RETURN
    # the node at ply0 finished with value `ret` (from its stm's view);
    # it folds into parent0
    with jax.named_scope("step.return"):
        ret_m = mode == MODE_RETURN
        at_root = ply0 == 0
        was_illegal = ret == ILLEGAL
        v = -ret
        tried = moves_p_row[jnp.maximum(ntp0[NT_MIDX] - 1, 0)]
        # the child that just returned was the parent's null move: score it
        # against beta only — a fail-high ends the parent (unproven-mate
        # guard: never cut on a mate-range null score), a fail-low is simply
        # discarded. Either way it folds into nothing: no best_move, no pv,
        # no searched credit.
        is_null_ret = ret_m & ~at_root & (ntp0[NT_NULL] == 2)
        null_cut = (
            is_null_ret & ~was_illegal & (v >= ntp0[NT_BETA]) & (v < MATE - 1000)
        )
        # LMR re-search: the last child was depth-reduced and its reduced
        # score beat alpha — discard the fold and re-push it at full depth
        need_rs = (
            ret_m & ~at_root & ~was_illegal & ~is_null_ret
            & (ntp0[NT_LASTRED] > 0) & (v > ntp0[NT_ALPHA])
        )
        better = (
            ret_m & (~at_root) & (~was_illegal) & (v > ntp0[NT_BEST])
            & ~is_null_ret & ~need_rs
        )
        fold = ret_m & ~at_root

        best_p = jnp.where(better | null_cut, v, ntp0[NT_BEST])
        bmove_p = jnp.where(better, tried, ntp0[NT_BMOVE])
        alpha_p = jnp.where(
            fold, jnp.maximum(ntp0[NT_ALPHA], best_p), ntp0[NT_ALPHA]
        )
        searched_p = ntp0[NT_SEARCHED] + jnp.where(
            fold & ~was_illegal & ~is_null_ret & ~need_rs, 1, 0
        )
        null_p = jnp.where(is_null_ret, 0, ntp0[NT_NULL])
        # pv[parent] = tried + pv[ply]; pv_len[ply] is the post-ENTER value
        # (a leaf that entered this same step zeroed it)
        pvlen_child = ntE[NT_PVLEN]
        pvlen_p = jnp.where(
            better, jnp.minimum(pvlen_child + 1, s.pv.shape[-1]), ntp0[NT_PVLEN]
        )
        ntP = ntp0
        for f_ix, f_val in ((NT_BEST, best_p), (NT_BMOVE, bmove_p),
                            (NT_ALPHA, alpha_p), (NT_SEARCHED, searched_p),
                            (NT_NULL, null_p), (NT_PVLEN, pvlen_p)):
            ntP = ntP.at[f_ix].set(f_val)
        nt_new = _row_set(nt_new, parent0, ntP, fold)

        new_pv_row = jnp.concatenate([tried[None], s.pv[ply0][:-1]])
        pv_new = _row_set(s.pv, parent0, new_pv_row, better)
        research = jnp.where(ret_m, need_rs, lane[LN_RESEARCH] != 0)
        # root: record and park (ret, not best[0] — ret carries the
        # mate/stalemate value when the root had no legal moves)
        root_score = jnp.where(ret_m & at_root, ret, lane[LN_RSCORE])
        root_move = jnp.where(ret_m & at_root, ntp0[NT_BMOVE], lane[LN_RMOVE])
        ply1 = jnp.where(fold, parent0, ply0)
        mode = jnp.where(
            ret_m, jnp.where(at_root, MODE_DONE, MODE_TRYMOVE), mode
        )

    # -------------------------------------------------------- phase TRYMOVE
    # note: the node budget is enforced in ENTER (children degrade to leaf
    # evals), not here — finishing a node early with searched==0 would
    # return -INF garbage to the parent
    with jax.named_scope("step.trymove"):
        try_m = mode == MODE_TRYMOVE
        # the row TRYMOVE acts on: the freshly-expanded node (ENTER cascade)
        # or the freshly-folded parent (RETURN cascade) — both in registers
        came_from_enter = enter & expand
        nt1 = jnp.where(came_from_enter, ntE, ntP)
        moves_row1 = jnp.where(came_from_enter, gen_moves, moves_p_row)
        bt1 = jnp.where(came_from_enter, btE, btp0)
        parent_b = _board_from_row(bt1)
        exhausted = nt1[NT_MIDX] >= nt1[NT_COUNT]
        cutoff = nt1[NT_ALPHA] >= nt1[NT_BETA]
        # a pending null move is tried BEFORE the first real move; an LMR
        # re-push (research, set by RETURN this same step) re-enters the
        # previous move at full depth and overrides finish — exhausted may
        # already be true when the reduced move was the last one
        re_push = try_m & research
        do_null = try_m & ~re_push & (nt1[NT_NULL] == 1) & ~cutoff
        finish = (exhausted | cutoff) & ~do_null & ~re_push
        advance = try_m & ~finish
        normal_adv = advance & ~re_push & ~do_null
        dl_node = nt1[NT_DL]

        # killer/history credit on fail-high: the quiet move that raised
        # alpha >= beta becomes killer slot 0 for this ply and earns a
        # depth²-weighted history bump (captures already order by MVV-LVA;
        # en-passant reads as quiet here, which only costs ordering)
        cause = nt1[NT_BMOVE]
        c_quiet = (cause >= 0) & _is_quiet(cause, bt1[BT_BOARD:BT_BOARD + 64])
        k_upd = try_m & cutoff & c_quiet
        k_new = k_upd & (cause != nt1[NT_K0])
        k0_v = jnp.where(k_new, cause, nt1[NT_K0])
        k1_v = jnp.where(k_new, nt1[NT_K0], nt1[NT_K1])
        h_idx = jnp.clip(cause, 0) & 4095
        dl = jnp.maximum(dl_node, 0)
        h_w = jnp.minimum(dl * dl + 1, 1024)
        hist_new = _row_set(
            s.hist, h_idx, jnp.minimum(s.hist[h_idx] + h_w, 1 << 20), k_upd
        )

        # finished node value: best, or mate/stalemate when no legal child.
        # QS nodes only tried captures — no legal capture is NOT mate; their
        # stand-pat floor in `best` already covers the quiet alternatives.
        node_in_qs = dl_node <= 0
        # best == -INF guards the count==0 + null-cutoff corner: a null-move
        # fail-high set best without any legal child being searched, and the
        # node must return that score, not a phantom mate/stalemate
        no_legal = (nt1[NT_SEARCHED] == 0) & ~node_in_qs & (nt1[NT_BEST] == -INF)
        if variant == "antichess":
            # losing chess: the side with no moves left (stalemated or out of
            # pieces) WINS (host: AntichessPosition._variant_outcome)
            mate_val = MATE - ply1
        else:
            mate_val = jnp.where(nt1[NT_INCHECK] != 0, -(MATE - ply1), DRAW)
        fin_val = jnp.where(no_legal & exhausted, mate_val, nt1[NT_BEST])

        m_ix = jnp.where(
            re_push,
            jnp.maximum(nt1[NT_MIDX] - 1, 0),
            jnp.minimum(nt1[NT_MIDX], moves_row1.shape[0] - 1),
        )
    with jax.named_scope("step.make_move"):
        move = moves_row1[m_ix]
        child = make_move(parent_b, jnp.maximum(move, 0), variant)
        # late-move reduction: late, quiet, unchecked moves of a deep-enough
        # node search 1 ply shallower (2 from move 8); RETURN re-pushes at
        # full depth when the reduced score beats alpha
        if _PRUNING:
            m_quiet = _is_quiet(jnp.maximum(move, 0), bt1[BT_BOARD:BT_BOARD + 64])
            lmr_ok = (
                (dl_node >= 3) & (nt1[NT_MIDX] >= 3) & m_quiet
                & (nt1[NT_INCHECK] == 0) & ~node_in_qs
            )
            red = jnp.where(
                lmr_ok, jnp.where(nt1[NT_MIDX] >= 8, 2, 1), 0
            )
            red = jnp.where(re_push | do_null, 0, red)
            # the null child: same position, opponent to move, no ep, and a
            # reset halfmove clock — which deliberately breaks the reversible
            # repetition chain across the null (Stockfish's pliesFromNull)
            child = Board(
                board=jnp.where(do_null, parent_b.board, child.board),
                stm=jnp.where(do_null, 1 - parent_b.stm, child.stm),
                ep=jnp.where(do_null, -1, child.ep),
                castling=jnp.where(do_null, parent_b.castling, child.castling),
                halfmove=jnp.where(do_null, 0, child.halfmove),
                extra=jnp.where(do_null, parent_b.extra, child.extra),
            )
            null_r = NULL_R + jnp.where(dl_node >= 7, 1, 0)
            child_dl = jnp.maximum(
                jnp.where(do_null, dl_node - 1 - null_r, dl_node - 1 - red), 0
            )
        else:
            red = jnp.int32(0)
            child_dl = jnp.maximum(dl_node - 1, 0)
        nply = jnp.minimum(ply1 + 1, P1 - 1)

    with jax.named_scope("step.push"):
        # TRYMOVE's own-row write (midx/null/lastred/killers), then the
        # child-push writes: depth_left of the pushed row (a single-field
        # 2-D one-hot — the row's other fields belong to the OLD node there
        # and are rewritten when the child expands), its board row, and its
        # incremental accumulator
        nt1w = nt1
        for f_ix, f_val in (
            (NT_MIDX, jnp.where(normal_adv, nt1[NT_MIDX] + 1, nt1[NT_MIDX])),
            (NT_NULL, jnp.where(do_null, 2, nt1[NT_NULL])),
            (NT_LASTRED, jnp.where(advance, red, nt1[NT_LASTRED])),
            (NT_K0, k0_v), (NT_K1, k1_v),
        ):
            nt1w = nt1w.at[f_ix].set(f_val)
        nt_new = _row_set(nt_new, ply1, nt1w, try_m)
        nt_new = _field_set(nt_new, nply, NT_DL, child_dl, advance)
        research = jnp.where(try_m, jnp.bool_(False), research)

        bt_new = _row_set(bt_new, nply, _row_from_board(child), advance)
    with jax.named_scope("step.acc_update"):
        stale = None  # "halfka": the child's perspectives still to rebuild
        if scheme is not None:
            codes, sqs, signs = move_piece_changes(
                parent_b, jnp.maximum(move, 0), variant
            )
            if _PRUNING:
                # a null move changes no pieces: zeroed slots make the
                # incremental update an exact no-op (code 0 → no-op)
                codes = jnp.where(do_null, 0, codes)
                signs = jnp.where(do_null, 0, signs)
        if scheme == "board768":
            child_acc = nnue.apply_acc_updates_768(params, s.acc[ply1], codes, sqs, signs)
            acc_new = _row_set(s.acc, nply, child_acc, advance)
            acc_counts = advance.astype(jnp.int32) * jnp.array([2, 0, 0], jnp.int32)
        elif scheme in nnue.ROW_SCHEMES:
            child_acc, stale = nnue_import.acc_update_pair(
                params, jax.lax.dynamic_slice_in_dim(s.acc, 2 * ply1, 2),
                parent_b.board, codes, sqs, signs,
            )
            stale &= advance
            # the pair goes to rows 2·nply, 2·nply + 1 in place, a
            # scatter of whole rows: a one-hot select (_row_set) would
            # rewrite the whole stack, 2·(P+1) rows of L1 + 8 a lane a
            # step, and a window of two rows a lane (dynamic_update_slice,
            # or lax.scatter with a (2, L1 + 8) window) becomes a loop
            # over the lanes on the chip (612-615 against 409 µs a step;
            # PERF.md §6, PR 35). A lane that pushes no child writes the
            # spare pair behind the stack.
            acc_new = s.acc.at[
                jnp.where(advance, 2 * nply, 2 * P1)
                + jnp.arange(2, dtype=jnp.int32)
            ].set(child_acc, mode="promise_in_bounds", unique_indices=True,
                  indices_are_sorted=True)
            n_stale = jnp.sum(stale).astype(jnp.int32)
            acc_counts = jnp.stack([
                2 * advance.astype(jnp.int32) - n_stale, n_stale,
                jnp.int32(2 * codes.shape[0]),
            ])
        else:
            acc_new = s.acc
            # every step rebuilt both perspectives from all 64 squares
            acc_counts = jnp.array([0, 2, 2 * 64], jnp.int32)

    with jax.named_scope("step.switch"):
        ret = jnp.where(try_m & finish, fin_val, ret)
        ret_depth = jnp.where(try_m & finish, dl_node, ret_depth)
        mode = jnp.where(
            try_m, jnp.where(finish, MODE_RETURN, MODE_ENTER), mode
        )
        ply_f = jnp.where(advance, nply, ply1)

        lane_new = jnp.stack([
            ply_f, mode, ret, ret_depth,
            store_mark.astype(jnp.int32), store_val,
            nodes, lane[LN_DLIM], lane[LN_BUDGET],
            root_score, root_move, lane[LN_RALPHA], lane[LN_RBETA],
            research.astype(jnp.int32),
            lane[LN_JITTER], lane[LN_GROUP],
        ])

    return SearchState(
        bt=bt_new, nt=nt_new, lane=lane_new,
        hist_hash=s.hist_hash, hist_halfmove=s.hist_halfmove,
        moves=moves_new, hist=hist_new, pv=pv_new, acc=acc_new,
    ), StepAux(
        counts=jnp.stack([movegen, acc_counts, eval_counts]),
        stale=None if stale is None else (stale, 2 * nply, child.board),
    )


class StepAux(NamedTuple):
    """What a lane's step hands _run_segment beside its next state."""
    # (SUM_TAIL, 3): MOVEGEN_COUNTERS, ACC_COUNTERS, EVAL_COUNTERS
    counts: jnp.ndarray
    # nnue.ROW_SCHEMES only, else None: ((2,) bool perspectives of the pushed
    # child whose row a king's move made stale, the child's first acc
    # row, its (64,) board) — rebuilt across lanes by _refresh_stale
    stale: Optional[tuple]


def _refresh_stale(params, acc: jnp.ndarray, stale: jnp.ndarray,
                   row0: jnp.ndarray, boards: jnp.ndarray, variant: str):
    """Rebuild from the board the accumulator rows a king's move made
    stale: → (acc, feature rows gathered).

    acc (B, R, W); stale (B, 2) bool; row0 (B,) the pushed child's first
    row; boards (B, 64) the children's. In a lockstep step some lane
    moves a king nearly every time and nearly every lane does not, so
    the (lane, perspective) pairs that need a rebuild are compacted into
    a few slots and rebuilt there, in as many passes as it takes —
    nothing is gathered for a lane that needs nothing, and a step in
    which no king moved pays the count alone."""
    B = acc.shape[0]
    n_slots = max(2, B // 8)
    n_rows = nnue_import.refresh_rows(variant)
    flat = stale.reshape(-1)  # pair 2·lane + perspective
    rank = jnp.cumsum(flat) - 1
    total = jnp.sum(flat).astype(jnp.int32)
    pairs = jnp.arange(2 * B, dtype=jnp.int32)
    slots = jnp.arange(n_slots, dtype=jnp.int32)

    def one_pass(carry):
        acc, done = carry
        hit = flat[None, :] & (rank[None, :] == done + slots[:, None])
        pair = jnp.sum(jnp.where(hit, pairs[None, :], 0), axis=1)
        lane, persp = pair >> 1, pair & 1
        rows = jax.vmap(
            lambda b, p: nnue_import.acc_refresh_row(params, b, p, n_rows)
        )(boards[lane], persp)
        # a slot past the last stale pair writes past the end: dropped
        dest = jnp.where(jnp.any(hit, axis=1), row0[lane] + persp, acc.shape[1])
        acc = acc.at[lane, dest].set(rows.astype(acc.dtype), mode="drop")
        return acc, done + n_slots

    with jax.named_scope("step.acc_refresh"):
        acc, done = jax.lax.while_loop(
            lambda c: c[1] < total, one_pass, (acc, jnp.int32(0))
        )
    return acc, (done // n_slots) * (n_slots * n_rows)


def make_search_step(params: nnue.NnueParams, variant: str = "standard"):
    lane_axes = SearchState(
        *[0 for _ in SearchState._fields]
    )
    return jax.vmap(
        lambda s: _step_lane(params, s, variant=variant), in_axes=(lane_axes,)
    )


def make_search_step_tt(params: nnue.NnueParams, variant: str = "standard"):
    lane_axes = SearchState(
        *[0 for _ in SearchState._fields]
    )
    return jax.vmap(
        lambda s, h, sc, m: _step_lane(params, s, h, sc, m, variant=variant),
        in_axes=(lane_axes, 0, 0, 0),
    )


def _gather_ply(arr: jnp.ndarray, ply: jnp.ndarray) -> jnp.ndarray:
    """arr (B, P, ...) → per-lane row at each lane's ply, shape (B, ...)."""
    return jax.vmap(lambda a, p: a[p])(arr, ply)


# ------------------------------------------------- segmented (resumable) run
#
# A deep search can take hundreds of thousands of lockstep steps. Running
# them as ONE device program is fragile (a multi-minute XLA program can
# trip device/runtime watchdogs, and cannot be interrupted when the chunk
# deadline passes — reference fishnet races `go_multiple` against the
# deadline and kills the engine process, src/main.rs:307-338). The
# TPU-native equivalent of that kill switch: run the while_loop in bounded
# segments and let the HOST decide between segments whether to continue,
# stop on deadline, or abandon. State lives on device throughout; the only
# per-segment host traffic is one scalar (steps executed).


def _run_segment(params: nnue.NnueParams, state: SearchState,
                 ttab, segment_steps: int, variant: str = "standard",
                 deep_tt: bool = False, prefer_deep: bool = False,
                 tt_gen=0):
    """Advance all lanes ≤ segment_steps. ttab: shared tt.TTable or None.
    deep_tt (STATIC): accept deeper LOWER/UPPER TT entries as cutoffs
    (move-job strength mode — see ops/tt.py probe).
    prefer_deep (STATIC) + tt_gen (traced): helper-lane dispatches store
    under the depth-preferred generation-aware replacement policy
    (ops/tt.py store) so helper writes don't evict primary-path entries.

    The TT lives OUTSIDE the vmap: each iteration first stores every lane
    parked in RETURN (its finished node's value), then probes every lane
    in ENTER against the just-updated table, and feeds the probe results
    into the vmapped step. Stores from one lane are visible to every
    other lane in the same iteration — the cross-lane sharing that makes
    one HBM table worth more than B private ones."""
    gen_i = jnp.asarray(tt_gen, jnp.int32)

    def account(s, aux, counts):
        """The step's counters into the carry and, where a king's move
        left accumulator rows stale, those rows rebuilt."""
        counts = counts + jnp.sum(aux.counts, axis=0)
        if aux.stale is not None:
            acc, rows = _refresh_stale(params, s.acc, *aux.stale, variant)
            s = s._replace(acc=acc)
            counts = counts.at[1, 2].add(rows)
        return s, counts

    if ttab is None:
        step = make_search_step(params, variant)

        def body(carry):
            s, t, i, counts = carry
            s, aux = step(s)
            s, counts = account(s, aux, counts)
            return s, t, i + 1, counts
    else:
        step = make_search_step_tt(params, variant)

        def body(carry):
            s, t, i, counts = carry
            lane = s.lane
            ply = lane[:, LN_PLY]
            btrow = _gather_ply(s.bt, ply)  # one row gather serves all
            h1, h2 = jax.vmap(
                lambda r: _tt_mod.hash_board(
                    r[BT_BOARD:BT_BOARD + 64], r[BT_STM], r[BT_EP],
                    r[BT_CAST:BT_CAST + 4], r[BT_EXTRA:BT_EXTRA + 12],
                    variant,
                )
            )(btrow)

            # ---- store lanes whose INTERIOR node just finished. (Leaf
            # returns fold into the parent within one step — the ENTER→
            # RETURN cascade — so a lane parked in RETURN here always
            # carries ret_depth >= 1, except TT-sourced values at -1.)
            ret_m = lane[:, LN_MODE] == MODE_RETURN
            store_mask = (
                ret_m
                & (lane[:, LN_RET] != ILLEGAL)
                & (lane[:, LN_RETD] >= 1)  # -1: value came from the TT
                # after budget exhaustion subtrees are degraded — their
                # values are shallow despite the nominal depth label
                & (lane[:, LN_NODES] < lane[:, LN_BUDGET])
            )
            ntrow = _gather_ply(s.nt, ply)
            flag = jnp.where(
                lane[:, LN_RET] >= ntrow[:, NT_BETA],
                _tt_mod.FLAG_LOWER,
                jnp.where(
                    lane[:, LN_RET] <= ntrow[:, NT_ALPHA0],
                    _tt_mod.FLAG_UPPER, _tt_mod.FLAG_EXACT,
                ),
            )
            with jax.named_scope("step.tt_store"):
                t = _tt_mod.store(
                    t, h1, h2, lane[:, LN_RET],
                    jnp.maximum(lane[:, LN_RETD], 0), flag,
                    ntrow[:, NT_BMOVE], store_mask,
                    prefer_deep=prefer_deep, gen=gen_i,
                )

            # ---- probe lanes about to enter a node (mode == ENTER);
            # the probe window must match the window ENTER will give the
            # node — incl. the zero-width null window for null children,
            # or stored LOWER bounds inside [1-beta_p, -alpha_p) would
            # miss valid null-search fail-high cutoffs
            enter = lane[:, LN_MODE] == MODE_ENTER
            parent = jnp.maximum(ply - 1, 0)
            ntprow = _gather_ply(s.nt, parent)
            pnull = (ply > 0) & (ntprow[:, NT_NULL] == 2)
            a_w = jnp.where(
                ply == 0, lane[:, LN_RALPHA], -ntprow[:, NT_BETA]
            )
            b_w = jnp.where(
                ply == 0, lane[:, LN_RBETA],
                jnp.where(
                    pnull, 1 - ntprow[:, NT_BETA], -ntprow[:, NT_ALPHA]
                ),
            )
            with jax.named_scope("step.tt_probe"):
                usable, score, _mv, order_mv = _tt_mod.probe(
                    t, h1, h2, ntrow[:, NT_DL], a_w, b_w,
                    deep_bounds=deep_tt,
                )
            usable &= enter
            order_mv = jnp.where(enter, order_mv, -1)
            s, aux = step(s, usable, score, order_mv)
            s, counts = account(s, aux, counts)

            # ---- store leaves the step just evaluated (depth-0 EXACT).
            # Their hash is the PRE-step hash: a marking lane was in ENTER
            # at this ply, exactly the position h1/h2 were computed for.
            sval = s.lane[:, LN_SVAL]
            with jax.named_scope("step.tt_store"):
                t = _tt_mod.store(
                    t, h1, h2, sval, jnp.zeros_like(sval),
                    jnp.full_like(sval, _tt_mod.FLAG_EXACT),
                    jnp.full_like(sval, -1), s.lane[:, LN_SMARK] != 0,
                    prefer_deep=prefer_deep, gen=gen_i,
                )
            return s, t, i + 1, counts

    def cond(carry):
        s, t, i, counts = carry
        return (i < segment_steps) & jnp.any(s.lane[:, LN_MODE] != MODE_DONE)

    state, ttab, n, counts = jax.lax.while_loop(
        cond, body,
        (state, ttab, jnp.int32(0), jnp.zeros((SUM_TAIL, 3), jnp.int32)),
    )
    lane = state.lane
    summary = jnp.concatenate([
        jnp.stack([
            (lane[:, LN_MODE] == MODE_DONE).astype(jnp.int32),
            lane[:, LN_NODES],
            lane[:, LN_RSCORE],
            lane[:, LN_RMOVE],
        ], axis=1),
        jnp.concatenate([
            jnp.zeros((SUM_TAIL, 1), jnp.int32).at[0, 0].set(n), counts,
        ], axis=1),
    ], axis=0)
    return state, ttab, n, summary


# segment_steps is TRACED (an int32 operand of the while cond), not
# static: callers pass different lengths (FISHNET_TPU_SEGMENT, a test's
# own, search_batch's max_steps) to one program. state and ttab are
# DONATED — chained segments alias the multi-MB tables in place instead of
# copying them, so a caller must treat the arguments it passed as
# consumed and continue from the returned state/ttab only.
_run_segment_jit = _aot_registry.wrap(
    "run_segment",
    jax.jit(
        _run_segment,
        static_argnames=("variant", "deep_tt", "prefer_deep"),
        donate_argnums=(1, 2),
    ),
    _run_segment,
    static_names=("variant", "deep_tt", "prefer_deep"),
)
# the big tables are OUTPUTS of init_state; its only device-state-shaped
# inputs are the history rows, donated so a caller's device rows are not
# copied (a refill does not come through here: _splice_lanes_jit below)
_init_state_jit = _aot_registry.wrap(
    "init_state",
    jax.jit(
        init_state, static_argnames=("max_ply", "variant"),
        donate_argnames=("hist_hash", "hist_halfmove"),
    ),
    init_state,
    static_names=("max_ply", "variant"),
)


def extract_results(state: SearchState, steps) -> dict:
    return {
        "score": state.lane[:, LN_RSCORE],
        "move": state.lane[:, LN_RMOVE],
        "pv": state.pv[:, 0],
        "pv_len": state.nt[:, 0, NT_PVLEN],
        "nodes": state.lane[:, LN_NODES],
        "done": state.lane[:, LN_MODE] == MODE_DONE,
        "steps": steps,
    }


# ------------------------------------------------- continuous lane refill
#
# A lockstep step costs the same however many lanes are live, so a DONE
# lane is pure waste until the batch narrows or the chunk drains — the
# static-batching tax. The iteration-level scheduling fix from LLM
# serving (continuous batching: when one sequence finishes, splice the
# next request into its slot without relaunching the batch) maps
# one-to-one onto lanes: at a segment boundary the host reinitializes
# exactly the DONE lanes it wants to reuse — board rows, NNUE
# accumulators, lane scalars, move/pv/history tables — while live
# lanes' state is untouched bit-for-bit, and the SAME _run_segment_jit
# program keeps running (refill changes array values, never shapes, so
# there is no recompile). Per-lane TT generation tags stay host-side:
# the caller passes a (B,) tt_gen array into _run_segment_jit, which
# ops/tt.py broadcasts elementwise, so a refilled lane's stores carry
# its own fresh generation without any tt.py change.
#
# The splice is ONE program per state width and variant
# (_splice_lanes_jit): init_state at the state's own width, then the
# masked per-lane select of _merge_lanes, the running state donated.
# Its per-lane operands — the six board fields, depth, budget, history
# rows, window, jitter, group — and the (B,) mask are always B rows,
# however many lanes a boundary refills: _refill_inputs widens the n
# admitted rows on the host (numpy; row i of a lane that is not
# refilled holds the first admitted row, which the mask discards), so
# no shape anywhere in a refill follows n and a session builds the
# program once. Operands that already live on the device (a caller's
# carried rows) are widened there by a gather instead.

def _merge_lanes(state: SearchState, fresh: SearchState,
                 mask: jnp.ndarray) -> SearchState:
    """Per-lane select between two same-shape states: lanes where mask
    (B,) is True take `fresh`, the rest keep `state` — one fused masked
    select per state field, no scatter."""
    def pick(old, new):
        m = mask.reshape((old.shape[0],) + (1,) * (old.ndim - 1))
        return jnp.where(m, new, old)

    with jax.named_scope("refill.merge"):
        return jax.tree.map(pick, state, fresh)


def _splice_lanes(params: nnue.NnueParams, state: SearchState,
                  roots: Board, depth, node_budget, hist_hash,
                  hist_halfmove, root_alpha, root_beta, order_jitter,
                  group, mask, variant: str = "standard") -> SearchState:
    """The refill splice: a fresh init_state of `roots` at the width
    and stack depth of `state`, taken where mask (B,) is True; every
    other lane keeps `state` bit for bit. All per-lane operands have
    B rows (see _refill_inputs)."""
    with jax.named_scope("refill.fresh"):
        fresh = init_state(
            params, roots, depth, node_budget, state.bt.shape[1] - 1,
            variant, hist_hash=hist_hash, hist_halfmove=hist_halfmove,
            root_alpha=root_alpha, root_beta=root_beta,
            order_jitter=order_jitter, group=group,
        )
    return _merge_lanes(state, fresh, mask)


# the running state is donated: its tables are overwritten in place
# where the mask selects, and the fresh state never leaves the program —
# a refill boundary allocates nothing big
_splice_lanes_jit = _aot_registry.wrap(
    "splice_lanes",
    jax.jit(_splice_lanes, static_argnames=("variant",),
            donate_argnums=(1,)),
    _splice_lanes,
    static_names=("variant",),
)

# FISHNET_TPU_SANITIZE: poison donated inputs after dispatch so a
# use-after-donate raises on CPU too (XLA:CPU only warns and leaves the
# handles readable). guard_donation returns each jit UNCHANGED when the
# flag is off — the default path pays nothing. docs/sanitizer.md.
_run_segment_jit = _sanitize.guard_donation(
    "ops/search.py::_run_segment_jit", _run_segment_jit, argnums=(1, 2))
_init_state_jit = _sanitize.guard_donation(
    "ops/search.py::_init_state_jit", _init_state_jit,
    argnames=("hist_hash", "hist_halfmove"))
_splice_lanes_jit = _sanitize.guard_donation(
    "ops/search.py::_splice_lanes_jit", _splice_lanes_jit, argnums=(1,))


def _refill_inputs(state: SearchState, new_roots: Board, lane_idx, depth,
                   node_budget, *, hist_hash=None, hist_halfmove=None,
                   root_alpha=None, root_beta=None, order_jitter=None,
                   group=None):
    """The per-lane operands of _splice_lanes for n admitted rows, each
    widened to the state's B lanes, and the (B,) splice mask last:
    (roots, depth, node_budget, hist_hash, hist_halfmove, root_alpha,
    root_beta, order_jitter, group, mask), or None when lane_idx is
    empty.

    Shared by the single-device `refill_lanes` and the sharded
    parallel.mesh.refill_lanes_sharded. A host operand (numpy, a
    sequence) is widened on the host and stays numpy, so the splice
    program receives it as it is; one that is a jax.Array is gathered
    on the device — np.asarray there would block the host and
    round-trip the rows through it. Lane lane_idx[i] gets row i; a lane
    that is not refilled gets row 0, which the mask discards. None
    takes the init_state default, so every call shares one trace."""
    B = state.lane.shape[0]
    lane_idx = np.asarray(lane_idx, np.int64).reshape(-1)
    n = int(lane_idx.shape[0])
    if n == 0:
        return None
    take = np.zeros(B, np.int64)
    take[lane_idx] = np.arange(n)
    mask = np.zeros(B, bool)
    mask[lane_idx] = True
    tk = None  # `take` on the device, put there by the first operand to need it

    def widen(x, dtype, fill=0, tail=()):
        nonlocal tk
        if x is None:
            return np.full((B,) + tail, fill, dtype)
        if isinstance(x, jax.Array):
            if tk is None:
                tk = jnp.asarray(take)
            return jnp.take(x, tk, axis=0)
        return np.asarray(x, dtype)[take]

    return (
        jax.tree.map(lambda a: widen(a, np.int32), new_roots),
        widen(depth, np.int32), widen(node_budget, np.int32),
        widen(hist_hash, np.uint32, 0, (MAX_HIST, 2)),
        widen(hist_halfmove, np.int32, HIST_HM_SENTINEL, (MAX_HIST,)),
        widen(root_alpha, np.int32, -INF), widen(root_beta, np.int32, INF),
        widen(order_jitter, np.int32), widen(group, np.int32),
        mask,
    )


def refill_lanes(params: nnue.NnueParams, state: SearchState, new_roots: Board,
                 lane_idx, depth, node_budget, *, variant: str = "standard",
                 hist_hash=None, hist_halfmove=None,
                 root_alpha=None, root_beta=None,
                 order_jitter=None, group=None) -> SearchState:
    """Splice fresh root positions into selected lanes of a running state.

    new_roots: batched Board with n rows; lane_idx: host sequence of n
    distinct lane indices to reinitialize; depth/node_budget (n,) and the
    optional per-lane arrays follow init_state semantics. One program
    per state width and variant runs whatever n is (_splice_lanes_jit);
    `state` is donated — rebind to the return value.

    Lanes not in lane_idx keep their exact pre-call state — including
    mid-segment stack contents, accumulators and history — so live
    searches are unaffected. The caller is responsible for only
    refilling DONE lanes and for bumping those lanes' TT generation
    tags before the next _run_segment_jit dispatch. For a mesh-sharded
    state use parallel.mesh.refill_lanes_sharded (same contract, the
    splice routed through shard_map)."""
    operands = _refill_inputs(
        state, new_roots, lane_idx, depth, node_budget,
        hist_hash=hist_hash, hist_halfmove=hist_halfmove,
        root_alpha=root_alpha, root_beta=root_beta,
        order_jitter=order_jitter, group=group,
    )
    if operands is None:
        return state
    return _splice_lanes_jit(params, state, *operands, variant=variant)


def search_stream(
    params: nnue.NnueParams,
    roots: Board,
    depth,
    node_budget,
    max_ply: int,
    width: int,
    segment_steps: int | None = None,
    max_steps: int = 50_000_000,
    deadline: float | None = None,
    tt=None,
    mesh=None,
    variant: str = "standard",
    hist=None,
    prefer_deep_store: bool = False,
    tt_gen_start: int = 1,
    sync_stats=None,
):
    """Stream N root positions through a fixed `width`-lane program.

    The occupancy-driven counterpart of `search_batch_resumable`: instead
    of narrowing as lanes finish, the host refills DONE lanes with queued
    positions at every segment boundary, keeping the compiled step at
    full width until the queue drains. The engine-level LaneScheduler
    adds helper lanes, aspiration windows and per-position deadlines on
    top of the same primitives.

    mesh: optional jax.sharding.Mesh — lanes shard over its devices
    (width must divide evenly) and segment/refill/merge route through
    the shard_map'd callables in parallel.mesh: each device advances and
    resplices ITS lanes locally, the host sees one stacked
    (ndev, width/ndev + 1, 4) boundary summary per dispatch, and the
    sharded jits donate state+TT exactly like the single-device path.
    With a mesh, tt must carry a leading (ndev,) shard dim
    (parallel.mesh.make_sharded_table) or be None, and each occupancy
    row gains shard_live / shard_refilled / shard_steps lists (one entry
    per shard).

    Segment boundaries are asynchronous: the host fetches ONE packed
    summary per boundary, pulls PV rows only for lanes that actually
    finished, and, when the refill queue is empty (no boundary decision
    pending), dispatches the next segment speculatively before blocking
    on the current one, so host bookkeeping overlaps device compute.
    sync_stats: optional utils.syncstats.SyncStats to account transfers
    into. segment_steps None reads FISHNET_TPU_SEGMENT.

    Returns per-position (N,) results keyed as extract_results, plus:
      occupancy: list of per-segment dicts {segment, steps, live, idle,
                 refilled, queue, transfers, elements, host_ms,
                 device_ms} — live counts lanes still searching at the
                 boundary, refilled the lanes spliced this boundary,
                 idle = width - live - refilled; the last four come from
                 utils.syncstats (transfer count and the host/device
                 wall-clock split of the boundary interval).
      refills:   total refill events (lanes spliced) across the run.
    Positions not finished by deadline/max_steps report done=False.
    """
    import time as _time

    from ..utils.syncstats import SyncStats

    stats = sync_stats if sync_stats is not None else SyncStats()
    if segment_steps is None:
        segment_steps = settings.get_int("FISHNET_TPU_SEGMENT")
    N = int(roots.stm.shape[0])
    P = max_ply
    depth = np.broadcast_to(np.asarray(depth, np.int32), (N,)).copy()
    node_budget = np.broadcast_to(
        np.asarray(node_budget, np.int32), (N,)
    ).copy()
    hist_hash, hist_halfmove = hist if hist is not None else (None, None)
    if hist_hash is not None:
        hist_hash = np.asarray(hist_hash)
        hist_halfmove = np.asarray(hist_halfmove)

    def gather_roots(pos_idx):
        ix = jnp.asarray(np.asarray(pos_idx, np.int64))
        return jax.tree.map(lambda a: jnp.asarray(a)[ix], roots)

    def hist_rows(pos_idx):
        if hist_hash is None:
            return None, None
        return hist_hash[pos_idx], hist_halfmove[pos_idx]

    # initial admission: positions 0..k-1 into lanes 0..k-1; surplus
    # lanes start with budget 0 so they park in DONE within two steps
    lane_pos = np.full(width, -1, np.int64)
    k = min(width, N)
    lane_pos[:k] = np.arange(k)
    queue = list(range(k, N))
    take0 = np.where(lane_pos >= 0, lane_pos, 0)
    assigned0 = lane_pos >= 0
    hh0, hm0 = hist_rows(take0)
    state = _init_state_jit(
        params, gather_roots(take0),
        jnp.asarray(np.where(assigned0, depth[take0], 0).astype(np.int32)),
        jnp.asarray(
            np.where(assigned0, node_budget[take0], 0).astype(np.int32)
        ),
        max_ply, variant,
        hist_hash=jnp.asarray(
            hh0 if hh0 is not None
            else np.zeros((width, MAX_HIST, 2), np.uint32)
        ),
        hist_halfmove=jnp.asarray(
            hm0 if hm0 is not None
            else np.full((width, MAX_HIST), HIST_HM_SENTINEL, np.int32)
        ),
        root_alpha=jnp.full((width,), -INF, jnp.int32),
        root_beta=jnp.full((width,), INF, jnp.int32),
        order_jitter=jnp.zeros((width,), jnp.int32),
        group=jnp.zeros((width,), jnp.int32),
    )
    ndev = local = 1
    multiproc = False
    if mesh is not None:
        from ..parallel import distributed as _dist
        from ..parallel.mesh import (
            refill_lanes_sharded,
            run_segment_sharded,
            shard_batch,
        )

        ndev = mesh.devices.size
        if width % ndev != 0:
            raise ValueError(
                f"stream width {width} must divide over {ndev} devices")
        local = width // ndev
        multiproc = _dist.spans_processes(mesh)
        if multiproc:
            # multi-host stream: every participating process drives this
            # same loop with identical inputs (SPMD discipline); its host
            # fetches are addressable-shard aware (parallel/distributed.py)
            params = _dist.replicate_tree(mesh, params)
        # place the fresh state sharded BEFORE the first dispatch: the
        # sharded segment donates its operands, and donation only takes
        # when the input already carries the program's sharding
        state = shard_batch(mesh, state)
    gen = np.zeros(width, np.int32)
    next_gen = int(tt_gen_start)
    gen[assigned0] = np.arange(next_gen, next_gen + k, dtype=np.int32)
    next_gen += k

    out = {
        "score": np.zeros(N, np.int32),
        "move": np.full(N, -1, np.int32),
        "pv": np.full((N, P), -1, np.int32),
        "pv_len": np.zeros(N, np.int32),
        "nodes": np.zeros(N, np.int32),
    }
    done_out = np.zeros(N, bool)
    occupancy: list[dict] = []
    refills_total = 0
    total = 0
    seg_i = 0

    if mesh is not None:
        def dispatch(st, table, seg_n):
            return run_segment_sharded(
                mesh, params, st, table, seg_n, variant=variant,
                prefer_deep=prefer_deep_store, tt_gen=jnp.asarray(gen),
            )
    else:
        def dispatch(st, table, seg_n):
            return _run_segment_jit(
                params, st, table, seg_n, variant, False,
                prefer_deep_store, jnp.asarray(gen),
            )

    def canon_summ(raw):
        """Boundary summary → ((width, 4) lane rows, step count,
        per-shard step list). Single-device summaries are (width+1, 4);
        sharded ones come back stacked (ndev, local+1, 4) and the step
        count is the max over shards (devices park independently)."""
        if mesh is None:
            return raw[:width], int(raw[width, SUM_DONE]), None
        lanes = raw[:, :local, :].reshape(width, SUM_W)
        shard_steps = [int(x) for x in raw[:, local, SUM_DONE]]
        return lanes, max(shard_steps), shard_steps

    def do_refill(st, free, n_ref):
        nonlocal next_gen, refills_total
        take_pos = np.asarray(queue[:n_ref], np.int64)
        del queue[:n_ref]
        sel = free[:n_ref]
        lane_pos[sel] = take_pos
        gen[sel] = (
            np.arange(next_gen, next_gen + n_ref) & 0x3FFFFFFF
        ).astype(np.int32)
        next_gen += n_ref
        hh, hm = hist_rows(take_pos)
        refills_total += n_ref
        if mesh is not None:
            return refill_lanes_sharded(
                mesh, params, st, gather_roots(take_pos), sel,
                depth[take_pos], node_budget[take_pos], variant=variant,
                hist_hash=hh, hist_halfmove=hm,
            )
        return refill_lanes(
            params, st, gather_roots(take_pos), sel,
            depth[take_pos], node_budget[take_pos], variant=variant,
            hist_hash=hh, hist_halfmove=hm,
        )

    def shard_row(free, n_ref, shard_steps):
        """Per-shard occupancy columns (mesh runs only): live lanes,
        lanes respliced this boundary, device step counts. lane_pos is
        sampled pre-refill (do_refill mutates it), so `free` carries the
        boundary's free-lane snapshot."""
        if mesh is None:
            return None
        busy = lane_pos >= 0
        busy[free] = False
        sel = np.asarray(free[:n_ref], np.int64)
        return {
            "shard_live": [
                int(busy[s * local:(s + 1) * local].sum())
                for s in range(ndev)
            ],
            "shard_refilled": np.bincount(
                sel // local, minlength=ndev).astype(int).tolist(),
            "shard_steps": shard_steps,
        }

    def pull_pv(st, lanes, pos):
        """Materialize PV rows for finished lanes only: two small
        device-side gathers instead of the full (B, P) table. On a
        multi-host mesh each process gathers the rows its addressable
        shards own and the host exchange fills in the rest, so every
        process assembles identical results."""
        if multiproc:
            from ..parallel import distributed as _dist

            out["pv"][pos] = _dist.gather_rows(
                mesh, st.pv, lanes, stats, "pv",
                pick=lambda a: a[:, 0], tail=(P,), dtype=np.int32)
            out["pv_len"][pos] = _dist.gather_rows(
                mesh, st.nt, lanes, stats, "pv_len",
                pick=lambda a: a[:, 0, NT_PVLEN], tail=(),
                dtype=np.int32)
            return
        rows = jnp.asarray(np.asarray(lanes, np.int64))
        out["pv"][pos] = stats.fetch(
            jnp.take(st.pv[:, 0], rows, axis=0), "pv")
        out["pv_len"][pos] = stats.fetch(
            jnp.take(st.nt[:, 0, NT_PVLEN], rows, axis=0), "pv_len")

    def pull_summ(p_summ):
        """One boundary summary fetch; addressable-shard aware when the
        mesh spans processes (ONE local fetch + host exchange)."""
        if multiproc:
            from ..parallel import distributed as _dist

            return _dist.fetch_summary(mesh, p_summ, stats, "summary")
        return stats.fetch(p_summ, "summary")

    def record(n, live, n_ref, shard=None):
        nonlocal seg_i
        seg_i += 1
        snap = stats.boundary()
        row = {
            "segment": seg_i, "steps": int(n), "live": live,
            "refilled": int(n_ref),
            "idle": width - live - int(n_ref), "queue": len(queue),
            **snap,
        }
        if shard is not None:
            row.update(shard)
        occupancy.append(row)

    final_tt = tt
    # one in-flight segment at all times; while it runs, the host
    # processes the PREVIOUS boundary from its packed summary, and when
    # no refill decision is pending the NEXT segment is dispatched
    # speculatively (chained on the in-flight segment's output futures)
    # before blocking on the summary
    pend = None
    prev_live = k > 0
    pv_pending: list[tuple[int, int]] = []  # deferred (lane, pos)
    if total < max_steps and (
            deadline is None or _time.monotonic() < deadline):
        pend = dispatch(state, tt, segment_steps)
    while pend is not None:
        p_state, p_tt, _p_n, p_summ = pend
        nxt = None
        if (prev_live and not queue
                and total + segment_steps < max_steps
                and (deadline is None or _time.monotonic() < deadline)):
            # the queue is empty, so this exact segment would be
            # dispatched after the boundary anyway; issuing it now
            # donates p_state/p_tt in place and keeps the device busy
            # across the host's boundary work
            nxt = dispatch(p_state, p_tt, segment_steps)
        summ, n, shard_steps = canon_summ(pull_summ(p_summ))
        total += n
        lane_done = summ[:, SUM_DONE].astype(bool)
        fin = np.nonzero(lane_done & (lane_pos >= 0))[0]
        if fin.size:
            pos = lane_pos[fin]
            out["score"][pos] = summ[fin, SUM_SCORE]
            out["move"][pos] = summ[fin, SUM_MOVE]
            out["nodes"][pos] = summ[fin, SUM_NODES]
            done_out[pos] = True
            if nxt is None:
                pull_pv(p_state, fin, pos)
            else:
                # p_state was donated into the speculative dispatch;
                # DONE lanes stay frozen (and the empty queue means
                # they are never respliced), so their PV rows are
                # pulled from a later resolved state
                pv_pending.extend(zip(fin.tolist(), pos.tolist()))
            lane_pos[fin] = -1
        if pv_pending and nxt is None:
            lanes = np.asarray([ln for ln, _ in pv_pending], np.int64)
            pos = np.asarray([p for _, p in pv_pending], np.int64)
            pull_pv(p_state, lanes, pos)
            pv_pending.clear()
        live = int((lane_pos >= 0).sum())
        free = np.nonzero(lane_pos < 0)[0]
        n_ref = min(len(free), len(queue))
        cur_state = p_state
        if (n_ref and nxt is None
                and (deadline is None or _time.monotonic() < deadline)):
            cur_state = do_refill(cur_state, free, n_ref)
        else:
            n_ref = 0
        record(n, live, n_ref, shard_row(free, n_ref, shard_steps))
        if nxt is not None:
            pend = nxt
            prev_live = live > 0
            continue
        stop = (
            (live == 0 and n_ref == 0 and not queue)
            or total >= max_steps
            or (deadline is not None
                and _time.monotonic() >= deadline)
        )
        if stop:
            final_tt = p_tt
            pend = None
        else:
            pend = dispatch(cur_state, p_tt, segment_steps)
            prev_live = live > 0 or n_ref > 0

    return {
        "score": jnp.asarray(out["score"]),
        "move": jnp.asarray(out["move"]),
        "pv": jnp.asarray(out["pv"]),
        "pv_len": jnp.asarray(out["pv_len"]),
        "nodes": jnp.asarray(out["nodes"]),
        "done": jnp.asarray(done_out),
        "steps": jnp.int32(total),
        "occupancy": occupancy,
        "refills": refills_total,
        "tt": final_tt,
    }


def search_batch_resumable(
    params: nnue.NnueParams,
    roots: Board,
    depth,
    node_budget,
    max_ply: int,
    segment_steps: int | None = None,
    max_steps: int = 4_000_000,
    deadline: float | None = None,
    tt=None,
    mesh=None,
    variant: str = "standard",
    hist=None,
    window=None,
    deep_tt: bool = False,
    narrow: bool = True,
    order_jitter=None,
    group=None,
    required=None,
    prefer_deep_store: bool = False,
    tt_gen: int = 0,
):
    """Like `search_batch`, but dispatched in bounded segments.

    order_jitter/group (B,): Lazy-SMP lane-group metadata — see
    init_state. required (B,) bool: the lanes whose completion the
    caller actually needs (the PRIMARY lanes of helper groups). Once
    every required lane is DONE the host stops dispatching segments and
    abandons the rest mid-flight — helper lanes exist only to feed the
    shared TT, and a lockstep step costs the same however few lanes run,
    so finishing them would pay pure wall-clock for entries nobody will
    read. None means every lane is required (the pre-helper behavior).
    prefer_deep_store + tt_gen: store policy for helper dispatches
    (ops/tt.py store).

    window: optional (root_alpha (B,), root_beta (B,)) aspiration window;
    a root whose true value falls outside reports a bound (fail-low /
    fail-high) — the caller re-searches with a wider window.

    deep_tt: accept deeper LOWER/UPPER TT entries as cutoffs (move-job
    strength mode; analysis keeps deterministic exact-depth probes).

    deadline: absolute time.monotonic() stamp; between segments the host
    stops early when passed. Lanes not DONE at stop report done=False and
    their root_score/move must be ignored by the caller.

    tt: optional shared ops.tt.TTable; the updated table is returned as
    results["tt"] so callers can carry it across searches (the engine
    keeps one per process, like Stockfish's persistent hash).

    mesh: optional jax.sharding.Mesh — lanes shard over its devices and
    each device advances its shard independently (parallel.mesh). With a
    mesh, tt must carry a leading (ndev,) shard dim
    (parallel.mesh.make_sharded_table) or be None.

    narrow: at segment boundaries, retire DONE lanes and continue the
    live ones in a half-width program (repeatedly, power-of-two buckets,
    floor FISHNET_TPU_NARROW_FLOOR, default 64). A lockstep step costs the same whether 1 or B lanes are
    live, so the finish-tail otherwise dominates batch wall-clock (the
    round-5 bench measured 105 knps batch-completion vs 258 knps
    steady-state at B=1024 from exactly this). Off under a mesh (shards
    must keep their static width). With tt=None results are identical —
    narrowing relocates lanes, it never changes any lane's search. With a
    shared TT they are identical up to scatter write order: narrowing
    permutes lane order, and simultaneous stores to one TT slot keep an
    order-dependent winner — the same already-documented tolerance every
    TT-on search has (ops/tt.py: a lost/torn entry only costs a
    re-search, never a wrong score).
    """
    import time as _time

    # segment length and narrowing floor are registry-backed so deployments
    # can trade host-check latency against dispatch overhead without code
    # edits; the defaults reproduce the historical hardcoded values exactly.
    if segment_steps is None:
        segment_steps = settings.get_int("FISHNET_TPU_SEGMENT")
    narrow_floor = settings.get_int("FISHNET_TPU_NARROW_FLOOR")

    B = roots.stm.shape[0]
    depth = jnp.broadcast_to(jnp.asarray(depth, jnp.int32), (B,))
    node_budget = jnp.broadcast_to(jnp.asarray(node_budget, jnp.int32), (B,))
    hist_hash, hist_halfmove = hist if hist is not None else (None, None)
    root_alpha, root_beta = window if window is not None else (None, None)
    state = _init_state_jit(
        params, roots, depth, node_budget, max_ply, variant,
        hist_hash=hist_hash, hist_halfmove=hist_halfmove,
        root_alpha=root_alpha, root_beta=root_beta,
        order_jitter=order_jitter, group=group,
    )
    if mesh is not None:
        from ..parallel.mesh import run_segment_sharded, shard_batch

        # place the fresh state sharded BEFORE the first dispatch: the
        # sharded segment donates its operands, and donation only takes
        # when the input already carries the program's sharding
        state = shard_batch(mesh, state)

        def dispatch(state, tt):
            state, tt, n, _summ = run_segment_sharded(
                mesh, params, state, tt, segment_steps, variant=variant,
                deep_tt=deep_tt, prefer_deep=prefer_deep_store,
                tt_gen=tt_gen,
            )
            # devices stop independently; continue while ANY used the
            # full segment (i.e. may still have live lanes)
            return state, tt, int(np.max(np.asarray(n)))
    else:
        def dispatch(state, tt):
            state, tt, n, _summ = _run_segment_jit(
                params, state, tt, segment_steps, variant, deep_tt,
                prefer_deep_store, jnp.int32(tt_gen),
            )
            return state, tt, int(n)

    # retired-lane result buffers (original lane indexing); `orig` maps
    # current state rows → original lanes, `valid` marks rows that still
    # OWN their original lane (padding rows after a narrow do not)
    flushed: dict[str, np.ndarray] | None = None
    orig = np.arange(B)
    valid = np.ones(B, bool)
    req = None if required is None else np.asarray(required, bool).copy()

    def _flush(res: dict, mask: np.ndarray) -> None:
        nonlocal flushed
        if flushed is None:
            flushed = {
                k: np.zeros((B,) + np.asarray(v).shape[1:],
                            np.asarray(v).dtype)
                for k, v in res.items() if k != "steps"
            }
        for k, buf in flushed.items():
            buf[orig[mask]] = np.asarray(res[k])[mask]

    total = 0
    while total < max_steps:
        if deadline is not None and _time.monotonic() >= deadline:
            break  # don't dispatch (or cold-compile) a segment we'd discard
        state, tt, n = dispatch(state, tt)
        total += n  # sync point: segment finished on device
        if n < segment_steps:
            break  # every lane parked in DONE
        if req is not None:
            done_now = np.asarray(state.lane[:, LN_MODE] == MODE_DONE)
            if not np.any(req & valid & ~done_now):
                break  # all required lanes finished; abandon the helpers
        if deadline is not None and _time.monotonic() >= deadline:
            break
        cur = state.lane.shape[0]
        if narrow and mesh is None and cur > narrow_floor:
            done = np.asarray(state.lane[:, LN_MODE] == MODE_DONE)
            live = int((~done & valid).sum())
            # target width: smallest power of two >= live, floor
            # FISHNET_TPU_NARROW_FLOOR (default 64) — always a power of
            # two even when the caller's width is not (the engine pads
            # >256-lane batches to multiples of 256), so narrowed
            # programs land on the handful of pow2 shapes the compile
            # cache / engine warmup already know
            new_b = narrow_floor
            while new_b < live:
                new_b *= 2
            if new_b < cur:
                _flush(extract_results(state, jnp.int32(total)),
                       done & valid)
                keep = np.nonzero(~done & valid)[0]
                # pad with retired rows: they are DONE, so they park
                # inertly; their `valid` goes False so the final merge
                # never double-reports their original lane
                pad = np.nonzero(done)[0][: new_b - len(keep)]
                order = np.concatenate([keep, pad])
                state = jax.tree.map(lambda a: a[jnp.asarray(order)], state)
                orig = orig[order]
                if req is not None:
                    req = req[order]
                valid = np.concatenate(
                    [np.ones(len(keep), bool), np.zeros(len(pad), bool)]
                )

    out = extract_results(state, jnp.int32(total))
    if flushed is not None:
        final = {k: np.asarray(v) for k, v in out.items() if k != "steps"}
        for k, buf in flushed.items():
            buf[orig[valid]] = final[k][valid]
        out = {k: jnp.asarray(v) for k, v in flushed.items()}
        out["steps"] = jnp.int32(total)
    out["tt"] = tt
    return out


def search_batch(params: nnue.NnueParams, roots: Board, depth, node_budget,
                 max_ply: int, max_steps: int = 2_000_000, tt=None,
                 variant: str = "standard", hist=None):
    """Run fixed-depth alpha-beta + capture quiescence on B roots in
    lockstep.

    Requires max_ply > max(depth): past the nominal depth the search
    keeps expanding captures (quiescence with stand-pat) until quiet or
    until the max_ply stack runs out, so max_ply - depth is the QS
    headroom. Returns a dict of (B,)-shaped results; scores are
    centipawn ints from the root side to move's perspective; ±(MATE-n)
    encodes mate in n plies. tt: optional shared ops.tt.TTable.

    Thin wrapper over `search_batch_resumable` (one compile surface —
    tests and production share the same `_run_segment_jit` programs; a
    second whole-search jit used to double every suite's compile cost).
    """
    return search_batch_resumable(
        params, roots, depth, node_budget, max_ply=max_ply,
        segment_steps=min(max_steps, settings.get_int("FISHNET_TPU_SEGMENT")),
        max_steps=max_steps, tt=tt, variant=variant, hist=hist,
    )


# alias kept for callers that used the jitted entry point; the segment
# dispatch inside is jitted, so a separate outer jit adds nothing
search_batch_jit = search_batch
