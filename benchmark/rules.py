"""The benchmark's own chess rules: standard chess and crazyhouse.

A plain mailbox implementation, independent of the program under test
(it imports nothing of ``fishnet_tpu``). It serves three users inside the
benchmark: the game generator (legal playouts), the reference search
(pseudo-legal moves refuted by king capture, as the device search defines
them) and the check that a served line is a legal line.

Conventions, chosen to match the wire format the engine speaks:
squares a1=0 .. h8=63; piece codes 0 empty, 1-6 white P N B R Q K,
7-12 black; a move is ``from | to<<6 | promo<<12`` (promo 1-4 = N B R Q),
castling is king-takes-own-rook, a crazyhouse drop is
``DROP | ptype<<12 | to<<6 | to`` (ptype 0-4 = P N B R Q).
"""
from __future__ import annotations

from typing import List, Optional

DROP = 1 << 15
START_FEN = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
START_FEN_ZH = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR[] w KQkq - 0 1"

_KNIGHT = [(1, 2), (2, 1), (2, -1), (1, -2), (-1, -2), (-2, -1), (-2, 1), (-1, 2)]
_KING = [(1, 0), (1, 1), (0, 1), (-1, 1), (-1, 0), (-1, -1), (0, -1), (1, -1)]
_ROOK_D = [(1, 0), (0, 1), (-1, 0), (0, -1)]
_BISHOP_D = [(1, 1), (-1, 1), (-1, -1), (1, -1)]


def _steps(deltas):
    out = []
    for sq in range(64):
        f, r = sq & 7, sq >> 3
        out.append([
            (r + dr) * 8 + f + df for df, dr in deltas
            if 0 <= f + df < 8 and 0 <= r + dr < 8
        ])
    return out


def _rays(deltas):
    out = []
    for sq in range(64):
        f, r = sq & 7, sq >> 3
        rays = []
        for df, dr in deltas:
            ray, nf, nr = [], f + df, r + dr
            while 0 <= nf < 8 and 0 <= nr < 8:
                ray.append(nr * 8 + nf)
                nf, nr = nf + df, nr + dr
            rays.append(ray)
        out.append(rays)
    return out


KNIGHT_T = _steps(_KNIGHT)
KING_T = _steps(_KING)
ROOK_R = _rays(_ROOK_D)
BISHOP_R = _rays(_BISHOP_D)
# squares from which a pawn of `color` attacks sq
PAWN_ATT_FROM = [
    _steps([(-1, -1), (1, -1)]),  # white pawns sit one rank below
    _steps([(-1, 1), (1, 1)]),
]
PAWN_CAP_TO = [_steps([(-1, 1), (1, 1)]), _steps([(-1, -1), (1, -1)])]


class Pos:
    """One position. Immutable by convention: ``make`` returns a new one."""

    __slots__ = ("board", "stm", "ep", "castling", "halfmove", "zh",
                 "pockets", "promoted")

    def __init__(self, board, stm, ep, castling, halfmove, zh=False,
                 pockets=None, promoted=0):
        self.board = board  # list of 64 codes
        self.stm = stm
        self.ep = ep  # en-passant target square or -1
        self.castling = castling  # [wK, wQ, bK, bQ] rook squares or -1
        self.halfmove = halfmove
        self.zh = zh
        self.pockets = pockets if pockets is not None else [0] * 10
        self.promoted = promoted  # bitmask of promoted pieces (crazyhouse)

    def key(self):
        """What makes two positions the same for repetition."""
        return (tuple(self.board), self.stm, self.ep, tuple(self.castling),
                tuple(self.pockets))

    def king_sq(self, color: int) -> int:
        try:
            return self.board.index(6 + 6 * color)
        except ValueError:
            return -1


def start(variant: str = "standard") -> Pos:
    back = [4, 2, 3, 5, 6, 3, 2, 4]
    board = back + [1] * 8 + [0] * 32 + [7] * 8 + [c + 6 for c in back]
    return Pos(board, 0, -1, [7, 0, 63, 56], 0, zh=(variant == "crazyhouse"))


def start_fen(variant: str = "standard") -> str:
    return START_FEN_ZH if variant == "crazyhouse" else START_FEN


def attacked(board, sq: int, by: int) -> bool:
    """Is `sq` attacked by a piece of colour `by`?"""
    base = 6 * by
    for s in PAWN_ATT_FROM[by][sq]:
        if board[s] == base + 1:
            return True
    for s in KNIGHT_T[sq]:
        if board[s] == base + 2:
            return True
    for s in KING_T[sq]:
        if board[s] == base + 6:
            return True
    for ray in ROOK_R[sq]:
        for s in ray:
            c = board[s]
            if c:
                if c == base + 4 or c == base + 5:
                    return True
                break
    for ray in BISHOP_R[sq]:
        for s in ray:
            c = board[s]
            if c:
                if c == base + 3 or c == base + 5:
                    return True
                break
    return False


def mover_left_king_en_prise(p: Pos) -> bool:
    """True when the side that has just moved stands in check: the move
    that led to `p` was illegal (the device search's king-capture rule)."""
    them = 1 - p.stm
    k = p.king_sq(them)
    return k < 0 or attacked(p.board, k, p.stm)


def _castle_ok(p: Pos, slot: int) -> Optional[int]:
    us = p.stm
    rsq = p.castling[us * 2 + slot]
    ksq = p.king_sq(us)
    if rsq < 0 or ksq < 0:
        return None
    base = 0 if us == 0 else 56
    k_dest = base + (6 if slot == 0 else 2)
    r_dest = base + (5 if slot == 0 else 3)
    lo_k, hi_k = min(ksq, k_dest), max(ksq, k_dest)
    lo_r, hi_r = min(rsq, r_dest), max(rsq, r_dest)
    board = p.board
    for s in range(base, base + 8):
        if s in (ksq, rsq):
            continue
        if (lo_k <= s <= hi_k or lo_r <= s <= hi_r) and board[s]:
            return None
    lifted = list(board)
    lifted[ksq] = 0
    lifted[rsq] = 0
    for s in range(lo_k, hi_k + 1):
        if attacked(lifted, s, 1 - us):
            return None
    return ksq | (rsq << 6)


def pseudo_moves(p: Pos, captures_only: bool = False) -> List[int]:
    """Pseudo-legal moves (castling fully resolved; other moves may leave
    the mover's king en prise). captures_only: the quiescence set — every
    capture, en passant and capturing promotions included, nothing else."""
    board, us = p.board, p.stm
    them = 1 - us
    out: List[int] = []
    fwd = 8 if us == 0 else -8
    start_rank = 1 if us == 0 else 6
    promo_rank = 6 if us == 0 else 1
    for sq in range(64):
        code = board[sq]
        if code == 0 or (code > 6) != (us == 1):
            continue
        t = (code - 1) % 6
        if t == 0:
            rank = sq >> 3
            promos = (1, 2, 3, 4) if rank == promo_rank else (0,)
            for to in PAWN_CAP_TO[us][sq]:
                tc = board[to]
                if (tc and (tc > 6) == (them == 1)) or to == p.ep:
                    for pr in promos:
                        out.append(sq | (to << 6) | (pr << 12))
            if not captures_only:
                to = sq + fwd
                if 0 <= to < 64 and board[to] == 0:
                    for pr in promos:
                        out.append(sq | (to << 6) | (pr << 12))
                    if rank == start_rank and board[to + fwd] == 0:
                        out.append(sq | ((to + fwd) << 6))
        elif t == 1 or t == 5:
            for to in (KNIGHT_T if t == 1 else KING_T)[sq]:
                tc = board[to]
                if tc == 0:
                    if not captures_only:
                        out.append(sq | (to << 6))
                elif (tc > 6) == (them == 1):
                    out.append(sq | (to << 6))
        else:
            rays = []
            if t in (3, 4):
                rays += ROOK_R[sq]
            if t in (2, 4):
                rays += BISHOP_R[sq]
            for ray in rays:
                for to in ray:
                    tc = board[to]
                    if tc == 0:
                        if not captures_only:
                            out.append(sq | (to << 6))
                    else:
                        if (tc > 6) == (them == 1):
                            out.append(sq | (to << 6))
                        break
    if not captures_only:
        for slot in (0, 1):
            mv = _castle_ok(p, slot)
            if mv is not None:
                out.append(mv)
        if p.zh:
            for pt in range(5):
                if p.pockets[us * 5 + pt] <= 0:
                    continue
                for to in range(64):
                    if board[to]:
                        continue
                    if pt == 0 and (to >> 3) in (0, 7):
                        continue
                    out.append(DROP | (pt << 12) | (to << 6) | to)
    return out


def make(p: Pos, mv: int) -> Pos:
    board = list(p.board)
    us = p.stm
    them = 1 - us
    pockets = p.pockets
    promoted = p.promoted
    castling = list(p.castling)
    to = (mv >> 6) & 63
    if mv & DROP:
        pt = (mv >> 12) & 7
        board[to] = 1 + pt + 6 * us
        pockets = list(pockets)
        pockets[us * 5 + pt] -= 1
        promoted &= ~(1 << to)
        return Pos(board, them, -1, castling,
                   0 if pt == 0 else p.halfmove + 1, p.zh, pockets, promoted)
    frm = mv & 63
    promo = (mv >> 12) & 7
    piece = board[frm]
    target = board[to]
    t = (piece - 1) % 6
    is_castle = t == 5 and target and (target > 6) == (us == 1) \
        and (target - 1) % 6 == 3
    is_ep = t == 0 and to == p.ep and target == 0 and (to & 7) != (frm & 7)
    capture = bool(target and (target > 6) == (them == 1)) or is_ep
    board[frm] = 0
    if is_castle:
        base = 0 if us == 0 else 56
        kingside = to > frm
        board[to] = 0
        board[base + (6 if kingside else 2)] = piece
        board[base + (5 if kingside else 3)] = 4 + 6 * us
    else:
        cap_sq = to
        victim = target
        if is_ep:
            cap_sq = to - 8 if us == 0 else to + 8
            victim = board[cap_sq]
            board[cap_sq] = 0
        if p.zh:
            mover_promoted = (promoted >> frm) & 1
            promoted &= ~(1 << frm)
            if capture:
                cap_type = 0 if (promoted >> cap_sq) & 1 else (victim - 1) % 6
                pockets = list(pockets)
                pockets[us * 5 + min(cap_type, 4)] += 1
                promoted &= ~(1 << cap_sq)
            if promo or mover_promoted:
                promoted |= 1 << to
            else:
                promoted &= ~(1 << to)
        board[to] = (1 + promo + 6 * us) if promo else piece
    if t == 5:
        castling[us * 2] = castling[us * 2 + 1] = -1
    for i in range(4):
        if castling[i] in (frm, to):
            castling[i] = -1
    ep = (frm + to) // 2 if t == 0 and abs(to - frm) == 16 else -1
    halfmove = 0 if (t == 0 or capture) else p.halfmove + 1
    return Pos(board, them, ep, castling, halfmove, p.zh, pockets, promoted)


def legal_moves(p: Pos) -> List[int]:
    return [m for m in pseudo_moves(p)
            if not mover_left_king_en_prise(make(p, m))]


def _sq_name(sq: int) -> str:
    return "abcdefgh"[sq & 7] + str((sq >> 3) + 1)


def uci(mv: int) -> str:
    """The engine's spelling: king-takes-rook castling, ``P@e4`` drops."""
    to = (mv >> 6) & 63
    if mv & DROP:
        return "PNBRQ"[(mv >> 12) & 7] + "@" + _sq_name(to)
    s = _sq_name(mv & 63) + _sq_name(to)
    promo = (mv >> 12) & 7
    return s + " nbrq"[promo] if promo else s


def parse_uci(p: Pos, text: str) -> Optional[int]:
    """The legal move spelled `text` in `p`, or None. Accepts both
    king-takes-rook and king-moves-two castling."""
    moves = legal_moves(p)
    by_name = {uci(m): m for m in moves}
    if text in by_name:
        return by_name[text]
    ksq = p.king_sq(p.stm)
    if len(text) == 4 and ksq >= 0 and text[:2] == _sq_name(ksq):
        base = 0 if p.stm == 0 else 56
        for slot, dest in ((0, base + 6), (1, base + 2)):
            rsq = p.castling[p.stm * 2 + slot]
            if rsq >= 0 and text[2:] == _sq_name(dest):
                return by_name.get(_sq_name(ksq) + _sq_name(rsq))
    return None
