"""Device board representation and move making.

The engine-process boundary of the reference (UCI pipes into Stockfish,
reference: src/stockfish.rs:124-143) becomes a host→device dispatch here:
positions live as SoA tensors and moves are applied by scatter, `vmap`-able
over the batch/lane dimension.

Board tensor layout (one lane):
  board:    (64,) int32, piece codes (tables.py: 0 empty, 1-6 white, 7-12 black)
  stm:      ()   int32, 0 white / 1 black
  ep:       ()   int32, en-passant target square or -1
  castling: (4,) int32, rook squares with castling rights, -1 if gone;
            order [white-kingside, white-queenside, black-kingside,
            black-queenside] (chess960-ready: stores actual rook squares)
  halfmove: ()   int32
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..chess.position import Position
from ..chess.types import scan
from . import tables as T


class Board(NamedTuple):
    board: jnp.ndarray  # (..., 64) int32
    stm: jnp.ndarray  # (...,) int32
    ep: jnp.ndarray  # (...,) int32
    castling: jnp.ndarray  # (..., 4) int32
    halfmove: jnp.ndarray  # (...,) int32
    # variant side-state, zeros for standard chess (EXTRA_* layout below):
    # [0:2]   threeCheck: checks delivered by white, black
    # [0:10]  crazyhouse: pocket counts [white P N B R Q, black P N B R Q]
    # [10:12] crazyhouse: promoted-piece bitboard (low word, high word)
    extra: jnp.ndarray  # (..., 12) int32


EXTRA_W = 12
EXTRA_CHECKS = 0  # +color
EXTRA_POCKET = 0  # +color*5 + ptype
EXTRA_PROMOTED = 10  # +word


def board_array(pos: Position) -> np.ndarray:
    """Host Position → (64,) numpy piece-code array (no device traffic —
    dataset builders iterate millions of positions and a per-position
    device put is a host-device round trip each)."""
    board = np.zeros(64, dtype=np.int32)
    for color in (0, 1):
        for ptype in range(6):
            for sq in scan(pos.bbs[color][ptype]):
                board[sq] = 1 + ptype + 6 * color
    return board


def position_fields(pos: Position) -> Board:
    """Host Position → single-lane Board of numpy fields: everything
    `from_position` computes, before its device puts (the engine hashes
    game histories from these on the host)."""
    board = board_array(pos)
    castling = np.full(4, -1, dtype=np.int32)
    # variants without castling (antichess, racingKings) never carry
    # rights on device — the host parses-but-ignores any FEN rights
    # (Position.has_castling), and the device movegen would otherwise
    # generate castle moves from them
    if getattr(pos, "has_castling", True):
        for color in (0, 1):
            ksq = pos.king_sq(color)
            back = 0xFF if color == 0 else 0xFF << 56
            rights = pos.castling & back
            for rsq in scan(rights):
                if ksq is None:
                    continue
                side = 0 if rsq > ksq else 1
                castling[color * 2 + side] = rsq
    extra = np.zeros(EXTRA_W, dtype=np.int32)
    if getattr(pos, "variant", "standard") == "threeCheck":
        for color in (0, 1):
            extra[EXTRA_CHECKS + color] = pos.checks_given[color]
    elif getattr(pos, "variant", "standard") == "crazyhouse":
        for color in (0, 1):
            for ptype in range(5):
                extra[EXTRA_POCKET + color * 5 + ptype] = pos.pockets[color][ptype]
        for w in (0, 1):
            word = (pos.promoted >> (32 * w)) & 0xFFFFFFFF
            extra[EXTRA_PROMOTED + w] = word - (1 << 32) if word >= 1 << 31 else word
    return Board(
        board=board,
        stm=np.int32(pos.turn),
        ep=np.int32(pos.ep_square if pos.ep_square is not None else -1),
        castling=castling,
        halfmove=np.int32(pos.halfmove),
        extra=extra,
    )


def from_position(pos: Position) -> Board:
    """Host Position → single-lane Board on the device."""
    return Board(*map(jnp.asarray, position_fields(pos)))


def stack_boards(boards) -> Board:
    """List of single-lane Boards → batched Board."""
    return Board(*[jnp.stack([getattr(b, f) for b in boards]) for f in Board._fields])


def stack_fields(rows) -> Board:
    """List of `position_fields` rows → batched Board of numpy fields:
    `stack_boards` for boards that stay on the host, no device call."""
    return Board(*[np.stack([getattr(b, f) for b in rows]) for f in Board._fields])


def piece_color(code: jnp.ndarray) -> jnp.ndarray:
    """0 white, 1 black, -1 empty."""
    return jnp.where(code == 0, -1, jnp.where(code <= 6, 0, 1))


def piece_type(code: jnp.ndarray) -> jnp.ndarray:
    """0..5 = P N B R Q K, -1 empty."""
    return jnp.where(code == 0, -1, (code - 1) % 6)


def exclusive_cumsum_small(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Exclusive integer cumsum along a SMALL static axis via log2(n)
    shift-adds (Hillis-Steele). Bit-identical to
    `jnp.cumsum(x, axis) - x` for integer inputs; exists because XLA:TPU
    lowers cumsum to reduce-window, which the round-4 device profile
    showed dominating `is_attacked`/movegen at these tiny axis lengths."""
    n = x.shape[axis]
    acc = x
    shift = 1
    while shift < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (shift, 0)
        sliced = jax.lax.slice_in_dim(acc, 0, n - shift, axis=axis)
        acc = acc + jnp.pad(sliced, pad)
        shift *= 2
    return acc - x


# RAY_DIRS order: E, N, NE, NW, W, S, SW, SE → orthogonal dirs 0,1,4,5
_ORTHO_DIR = np.array([True, True, False, False, True, True, False, False])


def attack_map(board64: jnp.ndarray, by_color: jnp.ndarray,
               skip_own1=None, skip_own2=None) -> jnp.ndarray:
    """(64,) bool: every square attacked by `by_color`, in one pass.

    skip_own1/skip_own2 (optional square indices) are treated as EMPTY for
    slider blocking — the castling test lifts the moving king and rook off
    the board. PRECONDITION: skipped squares must hold pieces of
    `by_color`'s OPPONENT (the castler's own king/rook). The skip is only
    applied to slider occupancy; the king/knight/pawn attacker terms still
    read the unskipped board, so a skipped square holding one of
    `by_color`'s own king/knight/pawn attackers would produce a phantom
    attack that lifted-board semantics would not. The castling caller
    satisfies this by construction; any new caller must too.

    Replaces per-square `is_attacked` queries in the search step: the
    round-4 device profile showed the castling path's 14 vmapped
    single-square queries costing ~930 us/step in serialized gather
    fusions, while this whole-board form is elementwise logic over the
    same (64, 8, 7) ray-piece tensor the move generator already gathers
    (shared by XLA CSE). Unbatched; vmap for lanes.
    """
    rsq_t = jnp.asarray(T.RAYS)  # (64, 8, 7) static
    rvalid = rsq_t >= 0
    rpiece = board64[jnp.clip(rsq_t, 0)]  # same gather as movegen → CSE
    rocc = (rpiece > 0) & rvalid
    if skip_own1 is not None:
        rocc &= rsq_t != skip_own1
    if skip_own2 is not None:
        rocc &= rsq_t != skip_own2
    before = exclusive_cumsum_small(rocc.astype(jnp.int32), axis=2)
    is_first = rocc & (before == 0)
    # enemy slider sliding along this (symmetric) direction — elementwise
    # piece-type tests, not a SLIDER_MASK value-gather
    pt = piece_type(rpiece)
    enemy = piece_color(rpiece) == by_color
    ortho = jnp.asarray(_ORTHO_DIR)[None, :, None]
    slider_ok = (pt == 4) | ((pt == 3) & ortho) | ((pt == 2) & ~ortho)
    slider_hit = jnp.any(is_first & slider_ok & enemy, axis=(1, 2))

    king_code = jnp.where(by_color == 0, T.W_KING, T.B_KING)
    king_hit = jnp.any(rvalid[:, :, 0] & (rpiece[:, :, 0] == king_code), axis=1)

    kt = jnp.asarray(T.KNIGHT_TARGETS)  # (64, 8) static
    ktp = jnp.where(kt >= 0, board64[jnp.clip(kt, 0)], 0)
    knight_code = jnp.where(by_color == 0, T.W_KNIGHT, T.B_KNIGHT)
    knight_hit = jnp.any(ktp == knight_code, axis=1)

    # pawns of by_color attacking sq sit on the squares a pawn of the
    # *opposite* color on sq would attack. Gather through each CONSTANT
    # per-color table and select by color — board64[dynamic_idx] lowers to
    # a serialized per-element gather on TPU (round-5 device profile).
    ps0 = np.asarray(T.PAWN_CAPTURES[1])  # (64, 2) static
    ps1 = np.asarray(T.PAWN_CAPTURES[0])
    ps = jnp.where(by_color == 0, jnp.asarray(ps0), jnp.asarray(ps1))
    psp_w = board64[np.clip(ps0, 0, 63)]
    psp_b = board64[np.clip(ps1, 0, 63)]
    psp = jnp.where(ps >= 0, jnp.where(by_color == 0, psp_w, psp_b), 0)
    pawn_code = jnp.where(by_color == 0, T.W_PAWN, T.B_PAWN)
    pawn_hit = jnp.any(psp == pawn_code, axis=1)

    return slider_hit | king_hit | knight_hit | pawn_hit


def is_attacked(board64: jnp.ndarray, sq: jnp.ndarray, by_color: jnp.ndarray) -> jnp.ndarray:
    """Is `sq` attacked by `by_color` on `board64`? Single-square query used
    for check detection and castling-path tests; O(8 dirs × 7 steps) gathers.
    All args unbatched (vmap for lanes)."""
    rays = jnp.asarray(T.RAYS)[sq]  # (8, 7)
    valid = rays >= 0
    ray_pieces = jnp.where(valid, board64[jnp.clip(rays, 0)], 0)  # (8, 7)
    occupied = ray_pieces > 0
    # first occupied step along each ray. exclusive_cumsum_small instead of
    # jnp.cumsum: XLA:TPU lowers cumsum to a reduce-window that cost
    # ~230 us/step across this function's call sites in the round-4 device
    # profile; 3 shifted adds are fused elementwise code.
    before = exclusive_cumsum_small(occupied.astype(jnp.int32), axis=1)
    is_first = occupied & (before == 0)
    slider_ok = jnp.asarray(T.SLIDER_MASK)[
        jnp.arange(8, dtype=jnp.int32)[:, None], ray_pieces
    ]  # (8, 7) does this piece slide along this dir
    enemy = piece_color(ray_pieces) == by_color
    slider_hit = jnp.any(is_first & slider_ok & enemy & valid)

    # king adjacency: first step of each ray
    first_sq_piece = ray_pieces[:, 0]
    king_code = jnp.where(by_color == 0, T.W_KING, T.B_KING)
    king_hit = jnp.any(valid[:, 0] & (first_sq_piece == king_code))

    knight_tgts = jnp.asarray(T.KNIGHT_TARGETS)[sq]  # (8,)
    kvalid = knight_tgts >= 0
    knight_code = jnp.where(by_color == 0, T.W_KNIGHT, T.B_KNIGHT)
    knight_hit = jnp.any(kvalid & (board64[jnp.clip(knight_tgts, 0)] == knight_code))

    # pawns of by_color attacking sq sit on the squares a pawn of the
    # *opposite* color on sq would attack
    pawn_srcs = jnp.asarray(T.PAWN_CAPTURES)[1 - by_color, sq]  # (2,)
    pvalid = pawn_srcs >= 0
    pawn_code = jnp.where(by_color == 0, T.W_PAWN, T.B_PAWN)
    pawn_hit = jnp.any(pvalid & (board64[jnp.clip(pawn_srcs, 0)] == pawn_code))

    return slider_hit | king_hit | knight_hit | pawn_hit


def king_square(board64: jnp.ndarray, color: jnp.ndarray) -> jnp.ndarray:
    """Square of `color`'s king, or -1 if absent (unbatched)."""
    king_code = jnp.where(color == 0, T.W_KING, T.B_KING)
    mask = board64 == king_code
    return jnp.where(jnp.any(mask), jnp.argmax(mask), -1)


def in_check(b: Board) -> jnp.ndarray:
    king_code = jnp.where(b.stm == 0, T.W_KING, T.B_KING)
    return jnp.any(attack_map(b.board, 1 - b.stm) & (b.board == king_code))


# variant-terminal kinds, from the side to move's perspective
TERM_NONE, TERM_LOSS, TERM_WIN, TERM_DRAW = 0, 1, 2, 3


def node_rules(b: Board, variant: str = "standard"):
    """Per-node legality + variant-terminal classification (unbatched).

    The reference delegates these rules to Fairy-Stockfish
    (src/stockfish.rs:245-260 sets UCI_Variant); here each variant is a
    statically compiled branch shared by the device search step and the
    host oracle. Host rule spec: chess/variants.py. Returns:
    - parent_illegal: the move leading HERE violated the mover's duty
      (left its king en prise; exploded its own king in atomic; gave
      check in racingKings). The search refutes the parent move.
    - checked: side to move is in check (mate vs stalemate scoring).
    - term_kind: TERM_* game end by variant rule at this node
      (TERM_LOSS → -(MATE-ply), TERM_WIN → MATE-ply, TERM_DRAW → 0).
    """
    us = b.stm
    them = 1 - us
    our_k = king_square(b.board, us)
    their_k = king_square(b.board, them)
    their_k_c = jnp.maximum(their_k, 0)
    # whole-board attack maps (one per color) instead of per-king
    # is_attacked queries: elementwise over the ray tensors the move
    # generator gathers anyway (see attack_map). A missing king's
    # board==code one-hot is all-False, so the our_k>=0 guard is implicit.
    att_us = attack_map(b.board, us)
    att_them = attack_map(b.board, them)
    our_king_code = jnp.where(us == 0, T.W_KING, T.B_KING)
    their_king_code = jnp.where(them == 0, T.W_KING, T.B_KING)
    their_k_attacked = jnp.any(att_us & (b.board == their_king_code))
    self_check = (their_k < 0) | their_k_attacked
    checked = jnp.any(att_them & (b.board == our_king_code))
    kind = jnp.int32(TERM_NONE)

    if variant == "antichess":
        # no check concept, kings are ordinary pieces; running out of
        # moves/pieces WINS (handled at move-exhaustion, not here)
        return jnp.bool_(False), jnp.bool_(False), kind
    if variant == "atomic":
        adj = (
            (their_k >= 0) & (our_k >= 0)
            & jnp.any(jnp.asarray(T.KING_TARGETS)[their_k_c] == our_k)
        )
        lost = our_k < 0  # mover exploded our king: mover wins — even if
        # its own king exploded too (host: _move_is_safe checks the
        # enemy king first)
        illegal = ~lost & (
            (their_k < 0)
            | (their_k_attacked & ~adj)
        )
        checked = checked & ~adj  # adjacent kings can never be in check
        kind = jnp.where(lost, TERM_LOSS, kind)
        return illegal, checked, kind
    if variant == "horde":
        # white is the kingless horde: no check duty/right for white
        illegal = jnp.where(them == 1, self_check, False)
        checked = jnp.where(us == 1, checked, False)
        white_dead = ~jnp.any(piece_color(b.board) == 0)
        kind = jnp.where((us == 0) & white_dead, TERM_LOSS, kind)
        return illegal, checked, kind
    if variant == "kingOfTheHill":
        hill = (
            (their_k == 27) | (their_k == 28)
            | (their_k == 35) | (their_k == 36)
        )
        kind = jnp.where(hill, TERM_LOSS, kind)  # mover reached the hill
        return self_check, checked, kind
    if variant == "racingKings":
        our8 = our_k >= 56
        their8 = their_k >= 56
        illegal = self_check | checked  # giving check is illegal
        # white moves first, so black gets one rejoinder: white-on-goal
        # is only a win once it is white's move again; black-on-goal wins
        # immediately; both → draw (host: RacingKings._variant_outcome)
        kind = jnp.where(
            our8 & their8, TERM_DRAW,
            jnp.where(
                their8 & (them == 1), TERM_LOSS,
                jnp.where(our8 & (us == 0), TERM_WIN, kind),
            ),
        )
        return illegal, jnp.bool_(False), kind
    if variant == "threeCheck":
        them_checks = jnp.where(
            us == 0, b.extra[EXTRA_CHECKS + 1], b.extra[EXTRA_CHECKS + 0]
        )
        kind = jnp.where(them_checks >= 3, TERM_LOSS, kind)
        return self_check, checked, kind
    return self_check, checked, kind  # standard / chess960 / crazyhouse


def make_move(b: Board, move: jnp.ndarray, variant: str = "standard") -> Board:
    """Apply an encoded move (from | to<<6 | promo<<12) to one lane.

    Castling is encoded king-takes-own-rook (matching the host library and
    UCI_Chess960 semantics); en passant and promotion are inferred from the
    board, so no flag bits are needed. `variant` is a STATIC flag: each
    variant compiles its own program, keeping the standard path free of
    variant branches (reference analog: Fairy-Stockfish's variant rules
    behind `UCI_Variant`, src/stockfish.rs:245-260). Crazyhouse drops are
    encoded as DROP_FLAG | ptype<<12 | to<<6 | to.
    """
    frm = move & 63
    to = (move >> 6) & 63
    promo = (move >> 12) & 7
    is_drop = ((move >> 15) & 1) == 1 if variant == "crazyhouse" else None

    board = b.board
    piece = board[frm]
    target = board[to]
    us = b.stm
    them = 1 - us

    is_pawn = piece_type(piece) == 0
    is_king = piece_type(piece) == 5
    is_castle = is_king & (piece_color(target) == us) & (piece_type(target) == 3)
    if is_drop is not None:
        is_pawn &= ~is_drop
        is_king &= ~is_drop
        is_castle &= ~is_drop

    # en passant capture: pawn moves diagonally onto the empty ep square
    is_ep = is_pawn & (to == b.ep) & (target == 0) & ((to & 7) != (frm & 7))
    ep_victim = jnp.where(us == 0, to - 8, to + 8)
    ep_victim_c = jnp.clip(ep_victim, 0, 63)

    # (for drops frm == to and the square is empty, so clearing is a no-op)
    new_board = board.at[frm].set(0)
    new_board = jnp.where(
        is_ep, new_board.at[ep_victim_c].set(0), new_board
    )

    # normal placement (promotion replaces the pawn)
    promo_piece = jnp.asarray(T.PROMO_TO_PIECE)[jnp.clip(promo, 0, 5)] + 6 * us
    placed = jnp.where(promo > 0, promo_piece, piece)
    if is_drop is not None:
        # dropped piece: promo bits carry the ptype (0..4 = P..Q)
        drop_piece = 1 + jnp.clip(promo, 0, 4) + 6 * us
        placed = jnp.where(is_drop, drop_piece, placed)
    normal_board = new_board.at[to].set(placed)

    # castling: clear rook square too, then place king on g/c and rook on f/d
    rank_base = jnp.where(us == 0, 0, 56)
    kingside = to > frm
    k_dest = rank_base + jnp.where(kingside, 6, 2)
    r_dest = rank_base + jnp.where(kingside, 5, 3)
    castle_board = new_board.at[to].set(0)
    castle_board = castle_board.at[k_dest].set(piece)
    castle_board = castle_board.at[r_dest].set(jnp.where(us == 0, T.W_ROOK, T.B_ROOK))

    out_board = jnp.where(is_castle, castle_board, normal_board)

    # castling rights: clear own on king move; clear a rook square on touch
    cast = b.castling
    own_slots = jnp.arange(4, dtype=jnp.int32) // 2 == us
    cast = jnp.where(is_king & own_slots, -1, cast)
    touched = (cast == frm) | (cast == to)
    if is_drop is not None:
        touched &= ~is_drop
    cast = jnp.where(touched, -1, cast)

    # new ep square on double pawn push
    dbl = is_pawn & (jnp.abs(to - frm) == 16)
    if variant == "horde":
        # back-rank doubles (horde pawns on rank 1) set no ep square
        dbl &= ~((us == 0) & ((frm >> 3) == 0))
    new_ep = jnp.where(dbl, (frm + to) // 2, -1)

    capture = (piece_color(target) == them) | is_ep

    if variant == "atomic":
        # explosion: a capture removes the capturer and every NON-PAWN
        # piece within one king-step of the landing square (the captured
        # piece itself is removed regardless); exploded rook squares lose
        # their castling rights (host spec: chess/variants.py
        # AtomicPosition._post_move_hook)
        zone_sqs = jnp.asarray(T.KING_TARGETS)[to]  # (8,), -1 padded
        # one-hot compare, not scatter: a clipped -1 pad would write False
        # over square a1 (nondeterministically vs a real True at duplicate
        # index 0), letting an a1 piece survive an explosion
        sq64 = jnp.arange(64, dtype=jnp.int32)
        in_zone = jnp.any(
            (sq64[None, :] == zone_sqs[:, None]) & (zone_sqs >= 0)[:, None],
            axis=0,
        )
        in_zone = in_zone | (sq64 == to)
        exploded = jnp.where(
            in_zone & (piece_type(out_board) != 0), 0, out_board
        )
        # the capturer itself is always removed, pawn or not
        exploded = exploded.at[to].set(0)
        out_board = jnp.where(capture, exploded, out_board)
        cast = jnp.where(
            capture & (cast >= 0) & in_zone[jnp.clip(cast, 0, 63)], -1, cast
        )
        # a side whose king explodes has no castling rights (the device
        # representation, like from_position, ties rights to a live king)
        wk_alive = jnp.any(out_board == T.W_KING)
        bk_alive = jnp.any(out_board == T.B_KING)
        slot_alive = jnp.where(jnp.arange(4, dtype=jnp.int32) < 2, wk_alive, bk_alive)
        cast = jnp.where(capture & ~slot_alive, -1, cast)
    pawnish = is_pawn
    if is_drop is not None:
        # a pawn drop is a pawn move (resets the fifty-move clock)
        pawnish |= is_drop & (promo == 0)
    new_halfmove = jnp.where(pawnish | capture, 0, b.halfmove + 1)

    extra = b.extra
    if variant == "threeCheck":
        # did this move give check? (mover attacks the enemy king)
        ek = king_square(out_board, them)
        gave_check = (ek >= 0) & is_attacked(out_board, jnp.maximum(ek, 0), us)
        extra = extra.at[EXTRA_CHECKS + us].add(
            jnp.where(gave_check, 1, 0)
        )
    elif variant == "crazyhouse":
        with jax.named_scope("step.pocket"):
            # Every index below (pocket slot, promoted-bit word) is a
            # traced value, batched under the lane vmap: `extra[i]` and
            # `extra.at[i]` there are gathers and scatters the TPU runs
            # lane by lane. So the ten pocket counters take one-hot adds
            # and the promoted bitboard rides as its two words, selected
            # by `sq >= 32`; bit(sq) lives in extra[10 + sq // 32].
            lo0 = extra[EXTRA_PROMOTED]
            hi0 = extra[EXTRA_PROMOTED + 1]

            def get_bit(lo, hi, sq):
                return (jnp.where(sq >= 32, hi, lo) >> (sq & 31)) & 1

            def with_bit(lo, hi, sq, val):
                bit = jnp.int32(1) << (sq & 31)
                upper = sq >= 32

                def put(w):
                    return jnp.where(val == 1, w | bit, w & ~bit)

                return (jnp.where(upper, lo, put(lo)),
                        jnp.where(upper, put(hi), hi))

            was_promoted_mover = get_bit(lo0, hi0, frm) & jnp.where(is_drop, 0, 1)
            cap_sq = jnp.where(is_ep, ep_victim_c, to)
            victim_code = jnp.where(is_ep, board[ep_victim_c], target)
            real_capture = capture & ~is_castle & ~is_drop
            cap_promoted = get_bit(lo0, hi0, cap_sq) & jnp.where(real_capture, 1, 0)
            # pocket gains the captured piece, demoted to pawn if promoted,
            # and pays for a drop
            cap_type = jnp.where(
                cap_promoted == 1, 0, jnp.maximum(piece_type(victim_code), 0)
            )
            slots = jnp.arange(10, dtype=jnp.int32)
            pocket_slot = us * 5 + jnp.clip(cap_type, 0, 4)
            drop_slot = us * 5 + jnp.clip(promo, 0, 4)
            pockets = (
                extra[EXTRA_POCKET:EXTRA_POCKET + 10]
                + (real_capture & (slots == pocket_slot)).astype(jnp.int32)
                - (is_drop & (slots == drop_slot)).astype(jnp.int32)
            )
            # bits: clear mover origin + capture square, then set
            # destination when the arriving piece is promoted (fresh
            # promotion or transport)
            lo, hi = with_bit(lo0, hi0, frm, jnp.int32(0))
            lo, hi = with_bit(
                lo, hi, cap_sq,
                jnp.where(real_capture, 0, get_bit(lo, hi, cap_sq)),
            )
            dest_promoted = jnp.where(
                is_drop, 0, jnp.where(promo > 0, 1, was_promoted_mover)
            )
            lo, hi = with_bit(lo, hi, to, dest_promoted)
            extra = jnp.concatenate([pockets, jnp.stack([lo, hi])])

    return Board(
        board=out_board,
        stm=them,
        ep=new_ep,
        castling=cast,
        halfmove=new_halfmove,
        extra=extra,
    )


def move_piece_changes(b: Board, move: jnp.ndarray, variant: str = "standard"):
    """The ≤4 piece placements/removals a move causes, as fixed slots
    (codes (4,), squares (4,), signs (4,)); code 0 marks an unused slot.

    Feeds the incremental NNUE accumulator update (board768 path): castling
    touches 4 slots (king out/in, rook out/in), captures/promotions ≤3,
    crazyhouse drops 1 (pockets are invisible to board features).
    Slot layout: [mover out, capture out, mover in, rook in(castle)].
    """
    frm = move & 63
    to = (move >> 6) & 63
    promo = (move >> 12) & 7
    is_drop = ((move >> 15) & 1) == 1 if variant == "crazyhouse" else None
    board = b.board
    piece = board[frm]
    target = board[to]
    us = b.stm

    is_pawn = piece_type(piece) == 0
    is_king = piece_type(piece) == 5
    is_castle = is_king & (piece_color(target) == us) & (piece_type(target) == 3)
    if is_drop is not None:
        is_pawn &= ~is_drop
        is_king &= ~is_drop
        is_castle &= ~is_drop
    is_ep = is_pawn & (to == b.ep) & (target == 0) & ((to & 7) != (frm & 7))
    ep_victim = jnp.where(us == 0, to - 8, to + 8)

    # slot 0: mover leaves frm (unused for drops: nothing leaves the board)
    c0, s0, g0 = piece, frm, jnp.int32(-1)
    if is_drop is not None:
        c0 = jnp.where(is_drop, 0, c0)
    # slot 1: captured piece leaves (normal capture, ep victim, or the
    # castling rook leaving its origin square)
    cap_code = jnp.where(
        is_castle, target,
        jnp.where(is_ep, board[jnp.clip(ep_victim, 0, 63)], target),
    )
    cap_sq = jnp.where(is_ep, jnp.clip(ep_victim, 0, 63), to)
    c1 = jnp.where(piece_color(cap_code) >= 0, cap_code, 0)
    c1 = jnp.where(is_castle | is_ep | (piece_color(target) == 1 - us), c1, 0)
    s1, g1 = cap_sq, jnp.int32(-1)
    # slot 2: mover arrives (promoted piece, or king to its castle square)
    rank_base = jnp.where(us == 0, 0, 56)
    kingside = to > frm
    k_dest = rank_base + jnp.where(kingside, 6, 2)
    promo_piece = jnp.asarray(T.PROMO_TO_PIECE)[jnp.clip(promo, 0, 5)] + 6 * us
    placed = jnp.where(promo > 0, promo_piece, piece)
    if is_drop is not None:
        placed = jnp.where(is_drop, 1 + jnp.clip(promo, 0, 4) + 6 * us, placed)
    c2 = placed
    s2 = jnp.where(is_castle, k_dest, to)
    g2 = jnp.int32(1)
    # slot 3: castling rook arrives
    r_dest = rank_base + jnp.where(kingside, 5, 3)
    c3 = jnp.where(is_castle, jnp.where(us == 0, T.W_ROOK, T.B_ROOK), 0)
    s3, g3 = r_dest, jnp.int32(1)

    codes = jnp.stack([c0, c1, c2, c3])
    sqs = jnp.stack([s0, s1, s2, s3])
    signs = jnp.stack([g0, g1, g2, g3])
    return codes, sqs, signs


# batched versions
_B_AXES = Board(0, 0, 0, 0, 0, 0)
v_make_move = jax.vmap(make_move, in_axes=(_B_AXES, 0))
v_in_check = jax.vmap(in_check, in_axes=(_B_AXES,))


def to_position_debug(b: Board) -> str:
    """ASCII board for debugging (single lane, host)."""
    chars = ".PNBRQKpnbrqk"
    arr = np.asarray(b.board)
    rows = []
    for rank in range(7, -1, -1):
        rows.append(" ".join(chars[arr[rank * 8 + f]] for f in range(8)))
    return "\n".join(rows)
