"""Shared transposition table in HBM for the lockstep batched search.

The reference's engines keep a per-process TT inside Stockfish's C++
(fishnet sizes it via engine defaults; reference: README.md:76 "~64 MiB
RAM per core" is mostly this table). Here ONE table is shared by every
search lane on the chip: entries live in HBM arrays carried through the
search while_loop, probed/stored with batched gathers/scatters.

Race tolerance (SURVEY.md §7.3 "lock-free XOR trick"): a batched scatter
with colliding indices may interleave lanes arbitrarily per ELEMENT, so
an entry row can be torn (lane A's key word with lane B's data word).
Every entry therefore stores `check = hash2 ^ meta ^ move`; a probe
recomputes the XOR and a torn entry simply fails validation and reads as
a miss — stale or corrupt entries can never return a wrong score, only
cost a re-search.

Entry layout (one packed (4,) int32 row per slot — see TTable):
    [0] check: hash2 ^ meta ^ move    (validation word, uint32 bits)
    [1] meta:  (score+32768) << 10 | searched_depth << 2 | flag
    [2] move:  the node's best move encoding (-1 when none)
    [3] generation (0 for plain always-replace stores; see `store`)
Mate-range scores are never stored (ply-relative mate distances don't
transpose; skipping them keeps the table sound without ply adjustment).

Helper-lane stores (Lazy-SMP lane groups, engine/tpu.py) opt into a
depth-preferred, generation-aware replacement policy: within the current
generation a shallower store never evicts a deeper entry, so the flood
of low-depth writes from K-1 helper lanes can't wash out the primary
path's deep entries. The generation word is NOT covered by the XOR check
(a torn generation only mis-prefers replacement, never corrupts
validation) and probes ignore it entirely.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

FLAG_EXACT = 0
FLAG_LOWER = 1  # score is a lower bound (fail-high: score >= beta)
FLAG_UPPER = 2  # score is an upper bound (fail-low: score <= alpha0)

_SCORE_BIAS = 32768
_DEPTH_MASK = 0xFF
_MAX_STORE = 30000  # skip mate-range scores (|MATE|-1000 = 31000 > this)

# two independent 32-bit zobrist tables from one seeded PRNG; host-side
# constants baked into the program. Layout: piece-square | ep | castling |
# stm | variant extras (pocket counts, check counters, promoted bits)
_rng = np.random.default_rng(0xF15F_4E7)
_EP_OFF = 13 * 64
_CASTLE_OFF = _EP_OFF + 65
_STM_OFF = _CASTLE_OFF + 4 * 65
_POCKET_OFF = _STM_OFF + 2  # 10 slots × counts 0..16
_CHECKS_OFF = _POCKET_OFF + 10 * 17  # 2 colors × 0..3 checks
_PROMOTED_OFF = _CHECKS_OFF + 2 * 4  # 64 promoted-square bits
_VARIANT_OFF = _PROMOTED_OFF + 64  # per-variant salt (shared-table safety)
_Z_SHAPE = _VARIANT_OFF + 8
# identical boards under different rule sets must never share a TT entry
# (the engine keeps ONE table across all chunks) — each variant XORs a
# fixed salt into the key. threeCheck/crazyhouse extras already perturb
# the hash, but the rule-mask variants have no extra state to do it.
_VARIANT_ID = {
    "standard": 0, "threeCheck": 1, "crazyhouse": 2, "antichess": 3,
    "atomic": 4, "horde": 5, "kingOfTheHill": 6, "racingKings": 7,
}
# the host's copies (history keys, engine/tpu.py::_history_arrays) and
# the device constants are the same draw, in this order
Z1_HOST = _rng.integers(0, 2**32, _Z_SHAPE, dtype=np.uint32)
Z2_HOST = _rng.integers(0, 2**32, _Z_SHAPE, dtype=np.uint32)
Z1 = jnp.asarray(Z1_HOST)
Z2 = jnp.asarray(Z2_HOST)
_SQ = np.arange(64)


def hash_boards_host(board, stm, ep, castling, extra,
                     variant: str = "standard"):
    """`hash_board` in numpy, bit for bit, for N positions: numpy int32
    board (N,64) codes, stm (N,), ep (N,), castling (N,4), extra (N,12)
    → (h1, h2) uint32 (N,). The plain gather form `hash_board`'s one-hot
    selects stand for; `variant` folds the same extras in."""
    xor = np.bitwise_xor.reduce
    vid = _VARIANT_ID.get(variant, 0)

    def fold(z):
        # z[0:64] (code 0, an empty square) never folds in
        h = xor(np.where(board > 0, z[board * 64 + _SQ], np.uint32(0)), axis=-1)
        h ^= z[_EP_OFF + ep + 1]
        h ^= xor(z[_CASTLE_OFF + np.arange(4) * 65 + castling + 1], axis=-1)
        h ^= z[_STM_OFF + (stm != 0)]
        if vid:
            h ^= z[_VARIANT_OFF + vid]
        if variant == "threeCheck":
            checks = np.clip(extra[:, :2], 0, 3)
            h ^= xor(z[_CHECKS_OFF + np.arange(2) * 4 + checks], axis=-1)
        elif variant == "crazyhouse":
            pockets = np.clip(extra[:, :10], 0, 16)
            h ^= xor(z[_POCKET_OFF + np.arange(10) * 17 + pockets], axis=-1)
            bits = (extra[:, 10 + _SQ // 32] >> (_SQ % 32)) & 1
            h ^= xor(np.where(bits == 1, z[_PROMOTED_OFF + _SQ], np.uint32(0)), axis=-1)
        return h

    return fold(Z1_HOST), fold(Z2_HOST)


class TTable(NamedTuple):
    """Packed entry rows: data[..., 0]=check (uint32 bits), 1=meta,
    2=move, 3=pad. One (N, 4) array instead of three (N,) arrays so a
    probe is ONE row gather and a store ONE row scatter — the round-5
    device profile showed each extra big-table gather/scatter costing
    tens of us/step, and the split layout paid 3 gathers + 6 scatters
    per step. (Pad to 4: power-of-two rows tile cleanly.)"""
    data: jnp.ndarray  # (..., N, 4) int32

    @property
    def check(self) -> jnp.ndarray:  # uint32 view
        return jax.lax.bitcast_convert_type(self.data[..., 0], jnp.uint32)

    @property
    def meta(self) -> jnp.ndarray:
        return self.data[..., 1]

    @property
    def move(self) -> jnp.ndarray:
        return self.data[..., 2]

    @property
    def size(self) -> int:
        return self.data.shape[-2]


def make_table(size_log2: int = 20) -> TTable:
    """2**size_log2 slots × 16 bytes (default 2^20 = 16 MiB HBM)."""
    n = 1 << size_log2
    return TTable(data=jnp.zeros((n, 4), jnp.int32))


def hash_board(board64, stm, ep, castling, extra=None, variant: str = "standard"):
    """→ (h1, h2) uint32 pair for one position; batched via vmap/broadcast.

    board64 (…,64) int32 codes 0..12; ep scalar -1..63; castling (…,4)
    rook squares or -1; stm 0|1. halfmove is deliberately excluded
    (standard engine practice: 50-move distance doesn't transpose).
    `variant` (STATIC) folds Board.extra in: crazyhouse pockets + promoted
    bits, threeCheck counters — standard hashes are unchanged."""
    sq = jnp.arange(64, dtype=jnp.int32)
    mask = board64 > 0

    # TPU formulation note (round-5 device profile): `z[board64 * 64 + sq]`
    # is a data-dependent gather that lowers to a serialized kCustom fusion
    # (~29 us/step per table inside the search step). Every dynamic lookup
    # below is therefore a one-hot select against a STATIC slice of z —
    # exactly one branch matches, so the folded values (and the hashes)
    # are bit-identical to the gather form.
    def onehot_pick(zslice, val):
        """XOR term z[off + val] as a one-hot select; zslice (K,) static,
        val (...,) in [0, K)."""
        k = zslice.shape[0]
        oh = val[..., None] == jnp.arange(k, dtype=jnp.int32)
        return jnp.sum(jnp.where(oh, zslice, jnp.uint32(0)), axis=-1)

    def fold(z):
        zps = z[: 13 * 64].reshape(13, 64)  # static slice: [code, sq]
        sel = jnp.zeros_like(board64).astype(jnp.uint32)
        for code in range(1, 13):
            sel = jnp.where(board64 == code, zps[code], sel)
        rows = jnp.where(mask, sel, 0)
        h = jax.lax.reduce(
            rows, jnp.uint32(0), jax.lax.bitwise_xor, (rows.ndim - 1,)
        )
        h ^= onehot_pick(z[_EP_OFF:_EP_OFF + 65], ep + 1)
        for i in range(4):
            off = _CASTLE_OFF + i * 65
            h ^= onehot_pick(z[off:off + 65], castling[..., i] + 1)
        h ^= jnp.where(stm == 0, z[_STM_OFF], z[_STM_OFF + 1])
        vid = _VARIANT_ID.get(variant, 0)
        if vid:
            h ^= z[_VARIANT_OFF + vid]
        if variant == "threeCheck":
            for c in (0, 1):
                off = _CHECKS_OFF + c * 4
                h ^= onehot_pick(z[off:off + 4], jnp.clip(extra[..., c], 0, 3))
        elif variant == "crazyhouse":
            for slot in range(10):
                off = _POCKET_OFF + slot * 17
                h ^= onehot_pick(
                    z[off:off + 17], jnp.clip(extra[..., slot], 0, 16)
                )
            words = extra[..., 10:12]
            bits = (
                jnp.right_shift(words[..., sq // 32], sq % 32) & 1
            ) == 1
            prows = jnp.where(bits, z[_PROMOTED_OFF + sq], 0)
            h ^= jax.lax.reduce(
                prows, jnp.uint32(0), jax.lax.bitwise_xor, (prows.ndim - 1,)
            )
        return h

    return fold(Z1), fold(Z2)


def pack_meta(score, depth, flag):
    return ((score + _SCORE_BIAS) << 10) | (depth << 2) | flag


def unpack_meta(meta):
    score = (meta >> 10) - _SCORE_BIAS
    depth = (meta >> 2) & _DEPTH_MASK
    flag = meta & 3
    return score, depth, flag


def probe(tt: TTable, h1, h2, depth_left, alpha, beta,
          deep_bounds: bool = False):
    """Batched probe: → (usable, score, move, ordering_move).

    usable: entry valid AND deep enough AND its bound cuts the (alpha,
    beta) window. ordering_move: the stored move whenever the entry is
    merely valid (usable for move ordering even when depth is too
    shallow).

    deep_bounds (STATIC): additionally accept DEEPER LOWER/UPPER entries
    as cutoffs (the reference engine's depth >= rule). Sound for finding
    the best MOVE, but the cutoff value then depends on what else was
    searched — move jobs opt in for strength; analysis keeps the exact
    rule below for deterministic scores."""
    slot = (h1 & jnp.uint32(tt.size - 1)).astype(jnp.int32)
    rows = tt.data[slot]  # (..., 4): ONE gather for check+meta+move
    check = jax.lax.bitcast_convert_type(rows[..., 0], jnp.uint32)
    meta = rows[..., 1]
    move = rows[..., 2]
    valid = (check ^ meta.astype(jnp.uint32) ^ move.astype(jnp.uint32)) == h2
    valid &= meta != 0
    score, depth, flag = unpack_meta(meta)
    # EXACT depth match, not >=: an entry stored at depth d is a bound on
    # the depth-d value of the node. The search's value at remaining depth
    # d' < d is a DIFFERENT number (quiescence truncates differently), and
    # a deeper bound does not bound it — substituting deeper values is what
    # made TT-enabled root scores drift hardest from the plain search.
    # Deeper entries still help via the ordering move.
    #
    # Determinism caveat: with null-move pruning + LMR active (the
    # default since round 4), node values are window- and path-dependent
    # (a reduced late move is skipped or re-searched depending on alpha;
    # a null child can't null-move again), so TT cutoffs can shift root
    # scores a little versus the plain search — exactly as they do in
    # Stockfish, whose persistent hash the reference inherits
    # (tests/test_tt.py bounds the drift). Bit-exact TT-on-vs-off scores
    # hold only under FISHNET_TPU_NO_PRUNING=1.
    if deep_bounds:
        # the reference rule: any at-least-as-deep entry cuts (EXACT
        # included — a deeper exact value is the strongest hit of all)
        deep_enough = depth >= jnp.maximum(depth_left, 0)
    else:
        deep_enough = depth == jnp.maximum(depth_left, 0)
    cuts = jnp.where(
        flag == FLAG_EXACT,
        True,
        jnp.where(flag == FLAG_LOWER, score >= beta, score <= alpha),
    )
    usable = valid & deep_enough & cuts
    return usable, score, jnp.where(usable, move, -1), jnp.where(valid, move, -1)


def store(tt: TTable, h1, h2, score, depth, flag, move, mask,
          prefer_deep: bool = False, gen=None):
    """Batched store; lanes with mask=False write nothing. Always-replace
    scheme (simple and effective for short batched searches).

    prefer_deep (STATIC) switches to depth-preferred, generation-aware
    replacement for helper-lane dispatches: a slot holding a same-
    generation entry of strictly greater depth is kept. Entries from any
    other generation (including gen-0 plain stores and empty slots) are
    always replaceable, so the policy self-heals across chunks without a
    sweep. The extra row gather costs one more big-table access per store
    site, which is why the plain path doesn't pay it. A torn old row can
    misreport its depth and squat for the rest of the generation — rare
    (needs a same-slot collision) and bounded to one chunk."""
    storable = mask & (jnp.abs(score) <= _MAX_STORE)
    slot = (h1 & jnp.uint32(tt.size - 1)).astype(jnp.int32)
    gen_i = jnp.int32(0) if gen is None else jnp.asarray(gen, jnp.int32)
    if prefer_deep:
        old = tt.data[slot]  # (..., 4) row gather (pre-write snapshot)
        _, old_depth, _ = unpack_meta(old[..., 1])
        keep_old = (
            (old[..., 1] != 0)
            & (old[..., 3] == gen_i)
            & (old_depth > depth)
        )
        storable = storable & ~keep_old
    slot = jnp.where(storable, slot, tt.size)  # out-of-range → dropped
    meta = pack_meta(score, depth, flag)
    check = h2 ^ meta.astype(jnp.uint32) ^ move.astype(jnp.uint32)
    rows = jnp.stack(
        [
            jax.lax.bitcast_convert_type(check, jnp.int32),
            meta, move, jnp.broadcast_to(gen_i, meta.shape),
        ],
        axis=-1,
    )
    # ONE row scatter; colliding lanes may still interleave per element
    # (rows can tear) — exactly the race the XOR check word tolerates
    return TTable(data=tt.data.at[slot].set(rows, mode="drop"))
