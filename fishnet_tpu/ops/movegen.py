"""Batched pseudo-legal move generation.

Strategy (TPU-first, no data-dependent shapes): enumerate a fixed candidate
space — (64 sq × 8 dirs × 7 steps) slider slots, (64×8) knight and king
slots, (64×4) pawn slots, (8×3×4) promotion slots (promotions only
originate from the 8 pre-promotion-rank squares), 2 castling slots — as
masks, then compact valid candidates into a fixed (MAX_MOVES,) ORDERED move
list with one single-array sort of packed (ordering_key << 16 | move)
values (see generate_moves for the packing invariants). The space that is
sorted is not the space that is enumerated: about half the slots are table
padding (a ray past the edge, a knight target off the board), False on
every board, and a constant per-variant index table (_live_slots) takes
only the slots that can hold a move — 2,550 of 4,962; 2,854 of 5,282 with
crazyhouse's drops — to the ordering refinements, the pack and the sort.
The sort's cost on the TPU steps at powers of two of its width (PERF.md
§5), so the table is what takes it below 4,096. Legality is *not*
fully resolved here: the search uses king-capture pruning (an illegal mover
is refuted one ply later when its king is captured), so only castling does
attack checks. This keeps the kernel free of pin/evasion logic; the host
library remains the legality oracle for tests.

Single-lane function; `vmap` over lanes gives the batch.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from . import tables as T
from .board import (
    EXTRA_POCKET,
    Board,
    attack_map,
    exclusive_cumsum_small,
    king_square,
    piece_color,
    piece_type,
)

# static per-color pawn-target tables. Indexing `board[dynamic_idx]` with a
# data-dependent index array lowers to a serialized kCustom gather on TPU
# (the round-5 device profile measured ~0.5 us per gathered element — five
# such gathers cost ~370 us of the 1.6 ms step). Indexing with a CONSTANT
# table compiles to vectorized code, so every pawn target is gathered per
# color through a constant table and the two results are selected by stm.
_SQ = np.arange(64, dtype=np.int32)
_TO1 = np.stack([np.clip(_SQ + 8, 0, 63), np.clip(_SQ - 8, 0, 63)])  # (2,64)
_TO2 = np.stack([np.clip(_SQ + 16, 0, 63), np.clip(_SQ - 16, 0, 63)])
_CAPS = np.asarray(T.PAWN_CAPTURES)  # (2, 64, 2), -1 padded
_CSQ = np.clip(_CAPS, 0, 63)
# promotion origin squares per color: white promotes from rank 6
# (48..55), black from rank 1 (8..15). Restricting the promo candidate
# section to these 8 rows shrinks the packed sort's input by 768-~96
# slots (round-5 profile: the sort dominates the step) without losing
# any candidate — promo_ok was identically False off these rows.
_PROMO_FROM = np.stack(
    [np.arange(48, 56, dtype=np.int32), np.arange(8, 16, dtype=np.int32)]
)  # (2, 8)

MAX_MOVES = T.MAX_MOVES
# crazyhouse adds up to 5 droppable types × ≤64 empty squares on top of
# ordinary board moves; its program compiles with a wider move list.
# 5*64 + MAX_MOVES is a PROVEN bound (drops can never exceed 5 types ×
# empty squares; board moves are bounded by MAX_MOVES): the compaction
# silently drops overflow beyond the cap, so an unproven cap would be a
# correctness hole — extra width only costs padding in the crazyhouse
# program
MAX_MOVES_ZH = 5 * 64 + MAX_MOVES
DROP_FLAG = 1 << 15  # move encoding: drops are DROP_FLAG | pt<<12 | to<<6 | to


def max_moves_for(variant: str) -> int:
    return MAX_MOVES_ZH if variant == "crazyhouse" else MAX_MOVES


def _promo_list(variant: str) -> list:
    """Promotion pieces, in slot order (antichess also promotes to king)."""
    promos = [T.PROMO_N, T.PROMO_B, T.PROMO_R, T.PROMO_Q]
    return promos + [T.PROMO_K] if variant == "antichess" else promos


@functools.lru_cache(maxsize=None)
def _static_moves(variant: str):
    """Per-color (n_candidates,) tables of the candidate move VALUES of
    every slot of `_candidate_space`, as numpy constants.

    Candidate values are static per side to move — every section below
    mirrors `_candidate_space`'s candidate assembly (same tables, same
    order) — except the two castling slots, which hold 0 here
    (tests/test_device_board.py test_hist_index_tables_match_candidates pins
    the mirror)."""
    rsq = np.clip(np.asarray(T.RAYS), 0, None)
    sl = (_SQ[:, None, None] | (rsq << 6)).reshape(-1)
    kn = (_SQ[:, None] | (np.clip(np.asarray(T.KNIGHT_TARGETS), 0, None) << 6)).reshape(-1)
    kg = (_SQ[:, None] | (np.clip(np.asarray(T.KING_TARGETS), 0, None) << 6)).reshape(-1)
    promos = np.asarray(_promo_list(variant), np.int32)
    out = []
    for c in (0, 1):
        pawn_tos = np.stack(
            [_TO1[c], _TO2[c], _CSQ[c][:, 0], _CSQ[c][:, 1]], axis=1
        )
        pw = (_SQ[:, None] | (pawn_tos << 6)).reshape(-1)
        pf = _PROMO_FROM[c]
        promo_tos = np.stack(
            [_TO1[c][pf], _CSQ[c][pf, 0], _CSQ[c][pf, 1]], axis=1
        )
        pr = (
            (pf[:, None] | (promo_tos << 6))[:, :, None]
            | (promos[None, None, :] << 12)
        ).reshape(-1)
        secs = [sl, kn, kg, pw, pr, np.zeros(2, np.int32)]
        if variant == "crazyhouse":
            pt = np.arange(5, dtype=np.int32)
            secs.append(
                (DROP_FLAG | (pt[:, None] << 12) | ((_SQ << 6) | _SQ)[None, :])
                .reshape(-1)
            )
        out.append(np.concatenate(secs).astype(np.int32))
    return out[0], out[1]


@functools.lru_cache(maxsize=None)
def _hist_idx_tables(variant: str):
    """Per-color (n_candidates,) tables of `cand & 4095` (the from|to
    history index) for every candidate slot, as numpy constants.

    The two castling slots hold 0; castling keys are 900, and the history
    bonus only applies at keys 1000/1100, so those slots never read their
    (meaningless) history value. Constant index tables let the per-step
    history lookup compile to a vectorized static gather instead of the
    serialized dynamic-gather fusion the round-5 device profile flagged."""
    mw, mb = _static_moves(variant)
    return mw & 4095, mb & 4095


@functools.lru_cache(maxsize=None)
def _live_slots(variant: str) -> np.ndarray:
    """The flat indices, in section order, of the candidate slots whose
    STATIC factor of `valid` is not identically False: the slots that can
    hold a move on some board. Built from the very tables the sections of
    `_candidate_space` AND into `valid` (`rvalid`, `tvalid`, `cvalid`, the
    pawn-drop ranks), so a slot left out is False on every board of this
    variant. Push columns, promos and castling stay whole (horde has white
    pawns on rank 0; a section with no static factor has nothing to drop).
    2,550 of 4,962 slots (2,574 of 4,986 in antichess, 2,854 of 5,282 in
    crazyhouse): what `generate_moves` packs and sorts."""
    caps_on = (_CAPS[0] >= 0) | (_CAPS[1] >= 0)  # (64, 2): either color's
    secs = [
        np.asarray(T.RAYS) >= 0,
        np.asarray(T.KNIGHT_TARGETS) >= 0,
        np.asarray(T.KING_TARGETS) >= 0,
        np.concatenate([np.ones((64, 2), bool), caps_on], axis=1),
        np.ones(8 * 3 * len(_promo_list(variant)), bool),
        np.ones(2, bool),
    ]
    if variant == "crazyhouse":
        ranks = _SQ >> 3
        drops = np.ones((5, 64), bool)
        drops[0] = (ranks != 0) & (ranks != 7)  # pawn_ok_sq
        secs.append(drops)
    live = np.flatnonzero(np.concatenate([x.reshape(-1) for x in secs]))
    return live.astype(np.int32)


class _LiveTables(NamedTuple):
    """What `generate_moves` needs of `_live_slots`, per variant."""
    slots: np.ndarray  # _live_slots(variant)
    castle_lo: int  # the first castling slot in the candidate space ...
    castle_at: int  # ... and among the live slots
    moves: tuple  # per color: _static_moves(variant)[c][slots]
    hist: tuple  # per color: _hist_idx_tables(variant)[c][slots]


@functools.lru_cache(maxsize=None)
def _live_tables(variant: str) -> _LiveTables:
    slots = _live_slots(variant)
    moves = _static_moves(variant)
    castle_lo = moves[0].shape[0] - 2 - (5 * 64 if variant == "crazyhouse" else 0)
    castle_at = int(np.searchsorted(slots, castle_lo))
    assert slots[castle_at] == castle_lo and slots[castle_at + 1] == castle_lo + 1
    return _LiveTables(
        slots, castle_lo, castle_at,
        tuple(m[slots] for m in moves),
        tuple(h[slots] for h in _hist_idx_tables(variant)),
    )


def _capture_key(victim_type: jnp.ndarray, attacker_type: jnp.ndarray,
                 is_capture: jnp.ndarray, promo: jnp.ndarray) -> jnp.ndarray:
    """MVV-LVA ordering key (smaller = searched first): queen promos, then
    captures by victim desc / attacker asc, then quiets."""
    mvv_lva = (5 - victim_type) * 8 + attacker_type
    key = jnp.where(is_capture, 100 + mvv_lva, 1000)
    key = jnp.where(promo == T.PROMO_Q, key - 90, key)
    return key.astype(jnp.int32)


def generate_moves(b: Board, variant: str = "standard",
                   killers=None, hist=None):
    """→ (moves (max_moves_for(variant),) sorted by ordering key, count (),
    noisy ()).

    noisy = how many leading moves are captures / queen promotions (they
    sort first) — the quiescence search expands only those.
    Moves are encoded from | to<<6 | promo<<12; castling is king-takes-rook.
    `variant` is STATIC (compiled per variant): threeCheck generates like
    standard; crazyhouse appends pocket drops (quiet, after board quiets).

    killers (2,) int32 / hist (4096,) int32: optional quiet-move ordering
    state (killer slots for this node's ply; from|to-indexed history
    counters). They reorder only the quiet tail (keys >= 900), so the
    noisy prefix the quiescence search expands is unaffected.
    """
    white, flat_moves, flat_valid, flat_keys = _candidate_space(b, variant)

    # from here on only the slots that can hold a move on some board
    # (_live_slots: about half the space is table padding, False on every
    # board): one constant-index gather takes valid and key through the
    # table together (keys are >= 10, so -1 marks an invalid slot), and the
    # move values of the live slots are constants per side to move, the
    # two castling slots excepted. The history lookup, the killer compare,
    # the pack and the sort below all run at the live width.
    live = _live_tables(variant)
    keys = jnp.where(flat_valid, flat_keys, -1)[live.slots]
    valid = keys >= 0
    cands = jax.lax.dynamic_update_slice_in_dim(
        jnp.where(white, live.moves[0], live.moves[1]),
        jax.lax.slice_in_dim(flat_moves, live.castle_lo, live.castle_lo + 2),
        live.castle_at, axis=0,
    )

    # quiet-move ordering refinements: history first (quiets 1000 →
    # 911..1010, drops 1100 → 1011..1110 by counter magnitude), then
    # killers jump the whole quiet tail to 901
    if hist is not None:
        # candidate from|to indices are static per color (castling slots
        # excepted — their key is 900, never history-adjusted), so the
        # lookup is a constant-index gather per color + a stm select
        hval = jnp.where(white, hist[live.hist[0]], hist[live.hist[1]])
        hbonus = jnp.clip(hval >> 5, 0, 99)
        keys = jnp.where(keys == 1000, 1010 - hbonus, keys)
        keys = jnp.where(keys == 1100, 1110 - hbonus, keys)
    if killers is not None:
        # candidates are never -1, so an empty killer slot (-1) matches
        # nothing; invalid candidates are masked out at the pack below
        is_k = (cands == killers[0]) | (cands == killers[1])
        keys = jnp.where(is_k & (keys >= 900), 901, keys)

    # compaction + ordering in ONE single-array sort: pack (key << 16) |
    # move — key < 2048 and move <= 0xFFFF, so valid packs stay positive
    # and below the invalid sentinel — sort ascending, keep the first cap
    # entries. Replaces round 4's 3-array compaction sort + stable
    # ordering sort (the round-5 device profile: 350 us + the argsort
    # gather). Valid packs are distinct (moves are), so the first cap
    # entries are decided by the SET of valid packs alone: sorting the
    # live slots gives, bit for bit, what sorting the whole space gave.
    # Ties within a key break by move encoding (the previous
    # two-stage form broke them by candidate position): any deterministic
    # order is a valid move ordering, and the host oracle calls this same
    # function, so device/oracle equality is unaffected.
    cap = max_moves_for(variant)
    with jax.named_scope("step.order"):
        packed = jnp.where(
            valid, (keys << 16) | cands,
            jnp.int32(jnp.iinfo(jnp.int32).max),
        )
        packed = jax.lax.sort(packed, dimension=0, is_stable=False)
        top = jax.lax.slice_in_dim(packed, 0, cap)
    moves = jnp.where(
        top != jnp.iinfo(jnp.int32).max, top & 0xFFFF, jnp.int32(-1)
    )
    count = jnp.minimum(jnp.sum(valid), cap).astype(jnp.int32)
    # captures 100..739, queen promos down to 10; castling 900, quiets 1000
    noisy = jnp.minimum(
        jnp.sum(valid & (keys < 900)), cap
    ).astype(jnp.int32)
    return moves, count, noisy


def _candidate_space(b: Board, variant: str = "standard"):
    """The fixed candidate space for one lane: → (white (), flat_moves,
    flat_valid, flat_keys — each (n_candidates,)).

    Section order (mirrored by _hist_idx_tables; pinned by
    tests/test_device_board.py test_hist_index_tables_match_candidates):
    sliders (64,8,7), knights (64,8), king (64,8), pawns (64,4), promos
    (8,3,n_promo), castling (2,), then crazyhouse drops (5,64)."""
    board = b.board
    us = b.stm
    them = 1 - us
    colors = piece_color(board)  # (64,)
    types = piece_type(board)  # (64,)
    own = colors == us
    occ = board > 0
    sq_idx = jnp.arange(64, dtype=jnp.int32)

    all_moves = []
    all_valid = []
    all_keys = []
    all_iscap = []  # per-candidate capture flags (antichess compulsion)

    # ---------------------------------------------------------------- sliders
    rays = jnp.asarray(T.RAYS)  # (64, 8, 7)
    rvalid = rays >= 0
    rsq = jnp.clip(rays, 0)
    rpiece = board[rsq]  # (64, 8, 7)
    rocc = (rpiece > 0) & rvalid
    before = exclusive_cumsum_small(rocc.astype(jnp.int32), axis=2)
    reachable = rvalid & (before == 0)
    target_own = piece_color(rpiece) == us
    target_enemy = piece_color(rpiece) == them
    slides = jnp.asarray(T.SLIDER_MASK).T[board]  # (64, 8): our piece slides dir?
    valid = (
        own[:, None, None]
        & slides[:, :, None]
        & reachable
        & ~(target_own & rocc)
    )
    cands = sq_idx[:, None, None] | (rsq << 6)
    keys = _capture_key(
        jnp.maximum(piece_type(rpiece), 0), types[:, None, None],
        target_enemy & rocc, jnp.zeros_like(rpiece),
    )
    all_moves.append(cands)
    all_valid.append(valid)
    all_keys.append(keys)
    all_iscap.append(target_enemy & rocc)

    # ---------------------------------------------------------- knights, king
    for table, ptype_want in ((T.KNIGHT_TARGETS, 1), (T.KING_TARGETS, 5)):
        tg = jnp.asarray(table)  # (64, 8)
        tvalid = tg >= 0
        tsq = jnp.clip(tg, 0)
        tpiece = board[tsq]
        valid = (
            own[:, None]
            & (types == ptype_want)[:, None]
            & tvalid
            & ~(piece_color(tpiece) == us)
        )
        if variant == "atomic" and ptype_want == 5:
            # atomic kings never capture (the capture would explode them)
            valid &= ~(piece_color(tpiece) == them)
        cands = sq_idx[:, None] | (tsq << 6)
        keys = _capture_key(
            jnp.maximum(piece_type(tpiece), 0),
            jnp.full_like(tpiece, ptype_want),
            piece_color(tpiece) == them,
            jnp.zeros_like(tpiece),
        )
        all_moves.append(cands)
        all_valid.append(valid)
        all_keys.append(keys)
        all_iscap.append(piece_color(tpiece) == them)

    # ------------------------------------------------------------------ pawns
    white = us == 0
    our_pawn = own & (types == 0)
    ranks = sq_idx >> 3
    start_rank = jnp.where(white, 1, 6)
    pre_promo = ranks == jnp.where(white, 6, 1)

    # every target square/piece via constant-table gathers selected by stm
    # (see _TO1/_CAPS above for why not board[dynamic_idx])
    to1 = jnp.where(white, jnp.asarray(_TO1[0]), jnp.asarray(_TO1[1]))
    b_to1 = jnp.where(white, board[_TO1[0]], board[_TO1[1]])
    to1_ok = our_pawn & (b_to1 == 0)
    to2 = jnp.where(white, jnp.asarray(_TO2[0]), jnp.asarray(_TO2[1]))
    b_to2 = jnp.where(white, board[_TO2[0]], board[_TO2[1]])
    dbl_rank = ranks == start_rank
    if variant == "horde":
        # horde pawns on the back rank may also double-push
        dbl_rank |= white & (ranks == 0)
    to2_ok = to1_ok & dbl_rank & (b_to2 == 0)

    caps = jnp.where(white, jnp.asarray(_CAPS[0]), jnp.asarray(_CAPS[1]))
    cvalid = caps >= 0
    csq = jnp.where(white, jnp.asarray(_CSQ[0]), jnp.asarray(_CSQ[1]))
    cpiece = jnp.where(white, board[_CSQ[0]], board[_CSQ[1]])
    cap_ok = (
        our_pawn[:, None]
        & cvalid
        & ((piece_color(cpiece) == them) | (csq == b.ep))
    )

    # non-promotion pawn moves: [push1, push2, capL, capR]
    pawn_tos = jnp.stack([to1, to2, csq[:, 0], csq[:, 1]], axis=1)  # (64,4)
    b_pawn_tos = jnp.stack(
        [b_to1, b_to2, cpiece[:, 0], cpiece[:, 1]], axis=1
    )  # board[pawn_tos] assembled from the constant-table gathers
    pawn_ok = jnp.stack(
        [to1_ok & ~pre_promo, to2_ok, cap_ok[:, 0] & ~pre_promo[:],
         cap_ok[:, 1] & ~pre_promo[:]], axis=1,
    )
    cands = sq_idx[:, None] | (pawn_tos << 6)
    vict = jnp.maximum(piece_type(b_pawn_tos), 0)
    is_cap = jnp.stack(
        [jnp.zeros(64, bool), jnp.zeros(64, bool), cap_ok[:, 0], cap_ok[:, 1]],
        axis=1,
    )
    keys = _capture_key(vict, jnp.zeros_like(vict), is_cap, jnp.zeros_like(vict))
    all_moves.append(cands)
    all_valid.append(pawn_ok)
    all_keys.append(keys)
    all_iscap.append(is_cap)

    # promotions: [push, capL, capR] × 4 promo pieces (5 in antichess,
    # which allows promotion to king). Only the 8 pre-promotion-rank
    # squares can promote, so the section gathers those rows through the
    # _PROMO_FROM constant table (static per color → vectorized gather,
    # same trick as _TO1/_CAPS) and the pre_promo factor — identically
    # True on the selected rows — drops out. 768 → 8*3*n_promo sort slots.
    def sel8(a):
        return jnp.where(white, a[_PROMO_FROM[0]], a[_PROMO_FROM[1]])

    promo_from = sel8(sq_idx)  # (8,)
    to1_8, b_to1_8, to1_ok_8 = sel8(to1), sel8(b_to1), sel8(to1_ok)
    csq_8, cpiece_8, cap_ok_8 = sel8(csq), sel8(cpiece), sel8(cap_ok)
    promo_tos = jnp.stack([to1_8, csq_8[:, 0], csq_8[:, 1]], axis=1)  # (8, 3)
    b_promo_tos = jnp.stack([b_to1_8, cpiece_8[:, 0], cpiece_8[:, 1]], axis=1)
    promo_ok_base = jnp.stack(
        [to1_ok_8, cap_ok_8[:, 0], cap_ok_8[:, 1]], axis=1
    )
    promo_list = _promo_list(variant)
    promos = jnp.asarray(promo_list, dtype=jnp.int32)
    cands = (
        promo_from[:, None, None]
        | (promo_tos[:, :, None] << 6)
        | (promos[None, None, :] << 12)
    )
    valid = promo_ok_base[:, :, None] & jnp.ones((1, 1, len(promo_list)), bool)
    vict = jnp.maximum(piece_type(b_promo_tos), 0)[:, :, None]
    is_cap = jnp.stack([jnp.zeros(8, bool), cap_ok_8[:, 0], cap_ok_8[:, 1]], axis=1)
    keys = _capture_key(
        jnp.broadcast_to(vict, cands.shape),
        jnp.zeros_like(cands),
        jnp.broadcast_to(is_cap[:, :, None], cands.shape),
        jnp.broadcast_to(promos[None, None, :], cands.shape),
    )
    all_moves.append(cands)
    all_valid.append(valid)
    all_keys.append(keys)
    all_iscap.append(jnp.broadcast_to(is_cap[:, :, None], cands.shape))

    # --------------------------------------------------------------- castling
    ksq = king_square(board, us)
    ksq_c = jnp.maximum(ksq, 0)
    rook_slots = jnp.take(b.castling, jnp.arange(2, dtype=jnp.int32) + us * 2)  # [kingside, queenside]

    def castle_ok(slot):
        rsq = rook_slots[slot]
        has = (rsq >= 0) & (ksq >= 0)
        rsq_c = jnp.clip(rsq, 0, 63)
        rank_base = jnp.where(us == 0, 0, 56)
        kingside = slot == 0
        k_dest = rank_base + jnp.where(kingside, 6, 2)
        r_dest = rank_base + jnp.where(kingside, 5, 3)
        # all squares the king or rook crosses (inclusive spans), minus the
        # two moving pieces, must be empty
        lo_k = jnp.minimum(ksq_c, k_dest)
        hi_k = jnp.maximum(ksq_c, k_dest)
        lo_r = jnp.minimum(rsq_c, r_dest)
        hi_r = jnp.maximum(rsq_c, r_dest)
        span = ((sq_idx >= lo_k) & (sq_idx <= hi_k)) | (
            (sq_idx >= lo_r) & (sq_idx <= hi_r)
        )
        span = span & (sq_idx != ksq_c) & (sq_idx != rsq_c)
        empty_ok = ~jnp.any(span & occ)
        # king path (origin..dest inclusive, ≤7 contiguous squares on the
        # back rank) must not be attacked, tested with king and castling
        # rook lifted off the board — via the whole-board attack map with
        # those two squares skipped for slider blocking (bit-identical to
        # the old per-square is_attacked on the lifted board; see
        # board.attack_map's profile note for why)
        att = attack_map(board, them, skip_own1=ksq_c, skip_own2=rsq_c)
        kpath = (sq_idx >= lo_k) & (sq_idx <= hi_k)
        safe = ~jnp.any(att & kpath)
        return has & empty_ok & safe, sq_idx[0] * 0 + (ksq_c | (rsq_c << 6))

    ok0, mv0 = castle_ok(jnp.int32(0))
    ok1, mv1 = castle_ok(jnp.int32(1))
    all_moves.append(jnp.stack([mv0, mv1]))
    all_valid.append(jnp.stack([ok0, ok1]))
    all_keys.append(jnp.full((2,), 900, dtype=jnp.int32))
    all_iscap.append(jnp.zeros(2, bool))

    # ------------------------------------------------------ crazyhouse drops
    if variant == "crazyhouse":
        with jax.named_scope("step.drops"):
            # the mover's P N B R Q counts: two static slices and a select
            # on `us`. A dynamic_slice at us * 5 has a batched start index
            # under the lane vmap and becomes a gather the TPU runs lane
            # by lane inside every step
            pocket = jnp.where(
                us == 0,
                b.extra[EXTRA_POCKET:EXTRA_POCKET + 5],
                b.extra[EXTRA_POCKET + 5:EXTRA_POCKET + 10],
            )  # (5,)
            empty = board == 0  # (64,)
            pt = jnp.arange(5, dtype=jnp.int32)
            ranks8 = sq_idx >> 3
            pawn_ok_sq = (ranks8 != 0) & (ranks8 != 7)
            valid = (
                (pocket > 0)[:, None]
                & empty[None, :]
                & jnp.where(pt[:, None] == 0, pawn_ok_sq[None, :], True)
            )  # (5, 64)
            cands = DROP_FLAG | (pt[:, None] << 12) | (sq_idx[None, :] << 6) | sq_idx[None, :]
        all_moves.append(cands)
        all_valid.append(valid)
        # drops search after ordinary quiet moves
        all_keys.append(jnp.full((5, 64), 1100, dtype=jnp.int32))
        all_iscap.append(jnp.zeros((5, 64), bool))

    flat_moves = jnp.concatenate([m.reshape(-1) for m in all_moves])
    flat_valid = jnp.concatenate([v.reshape(-1) for v in all_valid])
    flat_keys = jnp.concatenate([k.reshape(-1) for k in all_keys])
    if variant == "antichess":
        # capture compulsion: when any capture exists, ONLY captures are
        # legal (en-passant counts — cap_ok folded it into is_cap above)
        flat_iscap = jnp.concatenate([c.reshape(-1) for c in all_iscap])
        any_cap = jnp.any(flat_valid & flat_iscap)
        flat_valid &= jnp.where(any_cap, flat_iscap, True)
    return white, flat_moves, flat_valid, flat_keys


v_generate_moves = jax.vmap(generate_moves, in_axes=(Board(0, 0, 0, 0, 0, 0),))
