"""NNUE evaluation network (HalfKAv2_hm feature set) in JAX.

The reference ships Stockfish's nets as opaque binaries inside the engine
(reference: build.rs:8-9 embeds nn-1c0000000000.nnue + nn-37f18f62d772.nnue;
the engines evaluate them in C++). Here the network is a first-class model:
HalfKAv2_hm features (32 horizontally-mirrored king buckets × 11 piece
kinds × 64 squares = 22528 inputs per perspective), a perspective-shared
feature transform, and a bucketed layer stack selected by piece count —
resident in HBM as arrays, evaluated by XLA, and trainable in-framework
(fishnet_tpu.models.train).

Weights are float (bf16/f32) rather than Stockfish's int8/int16: the MXU
natively prefers bf16, and quantization is a later optimization, not a
architectural requirement as it is on CPU.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import tables as T
from ..ops.board import king_square, piece_color, piece_type

NUM_KING_BUCKETS = 32
NUM_PIECE_KINDS = 11  # our P N B R Q, their P N B R Q, kings (shared plane)
NUM_SQUARES = 64
NUM_FEATURES = NUM_KING_BUCKETS * NUM_PIECE_KINDS * NUM_SQUARES  # 22528
# board768: 12 piece kinds × 64 squares per perspective, no king buckets,
# so its updates are *always* incremental (≤4 changed features/move). A
# king-bucketed set has to rebuild a perspective whenever its king moves;
# an imported net pays that across lanes, compacted (nnue_import.py's
# header, ops/search.py::_refresh_stale), our own NnueParams of that set
# refresh every step (acc_scheme).
NUM_FEATURES_768 = 12 * 64
NUM_OUTPUT_BUCKETS = 8
OUTPUT_SCALE = 600.0  # network output [-1,1]-ish → centipawns

# king bucket: files a-d (after mirroring) × 8 ranks
_KING_BUCKET = np.full(64, -1, dtype=np.int32)
for _sq in range(64):
    _f, _r = _sq & 7, _sq >> 3
    if _f < 4:
        _KING_BUCKET[_sq] = _r * 4 + _f
KING_BUCKET = _KING_BUCKET


class NnueParams(NamedTuple):
    ft_w: jnp.ndarray  # (NUM_FEATURES, L1)
    ft_b: jnp.ndarray  # (L1,)
    l1_w: jnp.ndarray  # (NUM_OUTPUT_BUCKETS, 2*L1, H1)
    l1_b: jnp.ndarray  # (NUM_OUTPUT_BUCKETS, H1)
    l2_w: jnp.ndarray  # (NUM_OUTPUT_BUCKETS, H1, H2)
    l2_b: jnp.ndarray  # (NUM_OUTPUT_BUCKETS, H2)
    out_w: jnp.ndarray  # (NUM_OUTPUT_BUCKETS, H2)
    out_b: jnp.ndarray  # (NUM_OUTPUT_BUCKETS,)

    @property
    def l1(self) -> int:
        return self.ft_w.shape[1]


def init_params(
    key, l1: int = 256, h1: int = 16, h2: int = 32, dtype=jnp.float32,
    feature_set: str = "halfkav2_hm",
) -> NnueParams:
    num_features = {
        "halfkav2_hm": NUM_FEATURES,
        "board768": NUM_FEATURES_768,
    }[feature_set]
    k = jax.random.split(key, 4)
    return NnueParams(
        ft_w=(jax.random.normal(k[0], (num_features, l1)) * 0.02).astype(dtype),
        ft_b=jnp.full((l1,), 0.5, dtype),
        l1_w=(jax.random.normal(k[1], (NUM_OUTPUT_BUCKETS, 2 * l1, h1))
              * (1.0 / np.sqrt(2 * l1))).astype(dtype),
        l1_b=jnp.zeros((NUM_OUTPUT_BUCKETS, h1), dtype),
        l2_w=(jax.random.normal(k[2], (NUM_OUTPUT_BUCKETS, h1, h2))
              * (1.0 / np.sqrt(h1))).astype(dtype),
        l2_b=jnp.zeros((NUM_OUTPUT_BUCKETS, h2), dtype),
        out_w=(jax.random.normal(k[3], (NUM_OUTPUT_BUCKETS, h2))
               * (1.0 / np.sqrt(h2))).astype(dtype),
        out_b=jnp.zeros((NUM_OUTPUT_BUCKETS,), dtype),
    )


# ------------------------------------------------------------------ features


def feature_index(code: jnp.ndarray, sq: jnp.ndarray,
                  perspective: jnp.ndarray, ksq: jnp.ndarray) -> jnp.ndarray:
    """HalfKAv2_hm feature row of piece `code` on `sq` for one perspective
    whose king stands on `ksq`; -1 where code == 0 (code and sq broadcast).

    Orientation: flip ranks for black's perspective, then mirror files so
    the king lands on files a-d (the _hm halving).
    """
    flip = jnp.where(perspective == 1, 56, 0)
    o_sq = sq ^ flip
    o_ksq = ksq ^ flip
    mirror = jnp.where((o_ksq & 7) > 3, 7, 0)
    o_sq = o_sq ^ mirror
    o_ksq = o_ksq ^ mirror
    bucket = jnp.asarray(KING_BUCKET)[o_ksq]

    pt = piece_type(code)  # -1 empty, 0..5
    col = piece_color(code)
    kind = jnp.where(pt == 5, 10, jnp.where(col == perspective, pt, 5 + pt))
    idx = bucket * (NUM_PIECE_KINDS * NUM_SQUARES) + kind * NUM_SQUARES + o_sq
    return jnp.where(code > 0, idx, -1)


def feature_indices(board64: jnp.ndarray, perspective: jnp.ndarray,
                    ksq: jnp.ndarray) -> jnp.ndarray:
    """(64,) feature index per square for one perspective; -1 where empty."""
    sq = jnp.arange(64, dtype=jnp.int32)
    return feature_index(board64, sq, perspective, ksq)


def refresh_accumulator(params: NnueParams, board64: jnp.ndarray,
                        perspective: jnp.ndarray) -> jnp.ndarray:
    """(L1,) accumulator for one perspective, recomputed from scratch."""
    ksq = king_square(board64, perspective)
    idx = feature_indices(board64, perspective, jnp.maximum(ksq, 0))
    rows = params.ft_w[jnp.clip(idx, 0)]  # (64, L1)
    rows = jnp.where((idx >= 0)[:, None], rows, 0)
    return params.ft_b + jnp.sum(rows, axis=0, dtype=acc_dtype(params))


def accumulators(params: NnueParams, board64: jnp.ndarray) -> jnp.ndarray:
    """(2, L1): white and black perspective accumulators."""
    return jnp.stack(
        [
            refresh_accumulator(params, board64, jnp.int32(0)),
            refresh_accumulator(params, board64, jnp.int32(1)),
        ]
    )


def feature_index_768(code: jnp.ndarray, sq: jnp.ndarray,
                      perspective: jnp.ndarray) -> jnp.ndarray:
    """board768 feature row for one piece; -1 when code==0 (empty)."""
    pt = piece_type(code)
    col = piece_color(code)
    kind = jnp.where(col == perspective, pt, 6 + pt)
    o_sq = sq ^ jnp.where(perspective == 1, 56, 0)
    return jnp.where(code > 0, kind * 64 + o_sq, -1)


def feature_indices_768(board64: jnp.ndarray, perspective: jnp.ndarray) -> jnp.ndarray:
    sq = jnp.arange(64, dtype=jnp.int32)
    return feature_index_768(board64, sq, perspective)


def refresh_accumulator_768(params: NnueParams, board64: jnp.ndarray,
                            perspective: jnp.ndarray) -> jnp.ndarray:
    idx = feature_indices_768(board64, perspective)
    rows = params.ft_w[jnp.clip(idx, 0)]
    rows = jnp.where((idx >= 0)[:, None], rows, 0)
    return params.ft_b + jnp.sum(rows, axis=0, dtype=acc_dtype(params))


def accumulators_768(params: NnueParams, board64: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack(
        [
            refresh_accumulator_768(params, board64, jnp.int32(0)),
            refresh_accumulator_768(params, board64, jnp.int32(1)),
        ]
    )


def apply_acc_updates_768(params: NnueParams, acc: jnp.ndarray,
                          codes: jnp.ndarray, sqs: jnp.ndarray,
                          signs: jnp.ndarray) -> jnp.ndarray:
    """Incrementally update a (2, L1) accumulator pair.

    codes/sqs/signs: (K,) piece changes (code 0 → no-op). Cost: 2K gathers
    of an (L1,) row — this is the whole point of board768.
    """
    # The per-slot rows are never needed individually — only their signed
    # SUM. Build a (NUM_FEATURES,) weight vector W with <= K nonzero
    # entries in {-1, +1} (slot one-hots scaled by sign; idx -1 matches
    # nothing) and contract it against ft_w once. ~16x less work than
    # gathering/selecting K rows (round-5 device profile: the row-select
    # form cost 180 us/step at B=256), and exact: int paths are integer
    # sums; float paths multiply rows by +-1 (exact) and add zeros, with
    # one fixed reduction order shared by the device step and the host
    # oracle (both call this function).
    nf = params.ft_w.shape[0]
    feat = jnp.arange(nf, dtype=jnp.int32)
    for persp in (0, 1):
        idx = feature_index_768(codes, sqs, jnp.int32(persp))  # (K,)
        w = jnp.sum(
            jnp.where(idx[:, None] == feat[None, :], signs[:, None], 0),
            axis=0,
        )  # (NF,) int32 in {-1, 0, +1}
        delta = jnp.sum(
            params.ft_w * w[:, None].astype(params.ft_w.dtype), axis=0,
            dtype=acc_dtype(params),
        )
        acc = acc.at[persp].add(delta)
    return acc


def cast_params(params, dtype=jnp.bfloat16):
    """Quantize the network weights (bf16 by default — the MXU's native
    input type; SURVEY §7.2), of either params type. Search accumulators
    stay f32 (init_state allocates acc in f32 regardless), so incremental
    updates keep their precision; matmuls run bf16×f32→f32 which XLA maps
    onto the MXU. Evaluations may drift a few centipawns vs f32 — use the
    f32 master weights for training and parity tests."""
    return jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), params)


# int8 quantization scales (Stockfish-style fixed-point ladder):
# activations live in [0, QA] (int), weights are rounded to 1/QW steps;
# a matmul accumulates at scale QA*QW and the >>QW_SHIFT rescales back.
QA = 127  # activation quant — fits int8 for the MXU's int8 dot path
QW = 64
QW_SHIFT = 6


def quantize_int8(params: NnueParams) -> NnueParams:
    """f32 master weights → int fixed-point (SURVEY §7.2's int8 path).

    ft_w is int16 (the accumulator sums ≤33 rows, far within int32);
    hidden/output weights are int8, biases pre-scaled int32. Incremental
    accumulator updates become EXACT integer adds (no f32 drift down the
    search stack), and the hidden matmuls run int8×int8→int32 — the
    MXU's highest-throughput mode. Same NnueParams container: the
    integer dtype is the dispatch flag (is_int8)."""
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    return NnueParams(
        ft_w=jnp.asarray(np.round(f(params.ft_w) * QA), jnp.int16),
        ft_b=jnp.asarray(np.round(f(params.ft_b) * QA), jnp.int32),
        l1_w=jnp.asarray(
            np.clip(np.round(f(params.l1_w) * QW), -127, 127), jnp.int8
        ),
        l1_b=jnp.asarray(np.round(f(params.l1_b) * QA * QW), jnp.int32),
        l2_w=jnp.asarray(
            np.clip(np.round(f(params.l2_w) * QW), -127, 127), jnp.int8
        ),
        l2_b=jnp.asarray(np.round(f(params.l2_b) * QA * QW), jnp.int32),
        out_w=jnp.asarray(
            np.clip(np.round(f(params.out_w) * QW), -127, 127), jnp.int8
        ),
        out_b=jnp.asarray(np.round(f(params.out_b) * QA * QW), jnp.int32),
    )


def is_int8(params) -> bool:
    return (
        isinstance(params, NnueParams)
        and jnp.issubdtype(jnp.asarray(params.ft_w).dtype, jnp.integer)
    )


def acc_dtype(params) -> jnp.dtype:
    """Search accumulator dtype for a params set (int32 under int8
    quantization — integer adds are exact; f32 otherwise)."""
    return jnp.int32 if is_int8(params) else jnp.float32


def is_board768(params) -> bool:
    return (
        isinstance(params, NnueParams)
        and params.ft_w.shape[0] == NUM_FEATURES_768
    )


def acc_scheme(params, variant: str = "standard"):
    """The incremental accumulator scheme the search carries down its
    stack for these params, or None where every step refreshes from the
    board: "board768" (every move a ≤4-row delta, apply_acc_updates_768),
    "halfka" (an imported StockfishNet: a ≤4-row delta a perspective, and
    a refresh of the perspective whose king moved — nnue_import.acc_*).
    None for our own king-relative NnueParams, and for atomic, whose
    explosions exceed the 4 slots of board.move_piece_changes."""
    if variant == "atomic":
        return None
    if is_board768(params):
        return "board768"
    if not isinstance(params, NnueParams):
        return "halfka"
    return None


# ------------------------------------------------------------------- forward


def _crelu(x):
    return jnp.clip(x, 0.0, 1.0)


def output_bucket(board64: jnp.ndarray) -> jnp.ndarray:
    count = jnp.sum(board64 > 0)
    return jnp.clip((count - 1) // 4, 0, NUM_OUTPUT_BUCKETS - 1)


def _bucket_weights(params: NnueParams, bucket: jnp.ndarray):
    """Layer-stack weights for one output bucket, selected by an 8-way
    where-chain instead of `w[bucket]` — the data-dependent gather lowers
    to a serialized per-lane fusion on TPU (round-5 device profile) while
    the select chain is vectorized; the selected values (and downstream
    matmul shapes, hence float bits) are identical."""
    picked = None
    for n in range(NUM_OUTPUT_BUCKETS):
        cur = (params.l1_w[n], params.l1_b[n], params.l2_w[n],
               params.l2_b[n], params.out_w[n], params.out_b[n])
        if picked is None:
            picked = cur
        else:
            picked = tuple(
                jnp.where(bucket == n, c, p) for c, p in zip(cur, picked)
            )
    return picked


def forward_from_acc(params: NnueParams, acc: jnp.ndarray, stm: jnp.ndarray,
                     bucket: jnp.ndarray) -> jnp.ndarray:
    """Centipawn score from the side to move's perspective (scalar f32)."""
    own = jnp.where(stm == 0, acc[0], acc[1])
    opp = jnp.where(stm == 0, acc[1], acc[0])
    if is_int8(params):
        # fixed-point ladder: activations [0,QA] int8, weights 1/QW
        # steps, int8×int8→int32 dots (the MXU's fastest mode), >>6
        # rescale between layers; exact integer arithmetic throughout
        w1, b1, w2, b2, ow, ob = _bucket_weights(params, bucket)
        x = jnp.clip(jnp.concatenate([own, opp]), 0, QA).astype(jnp.int8)
        h = jnp.matmul(x, w1, preferred_element_type=jnp.int32) + b1
        h = jnp.clip(h >> QW_SHIFT, 0, QA).astype(jnp.int8)
        h = jnp.matmul(h, w2, preferred_element_type=jnp.int32) + b2
        h = jnp.clip(h >> QW_SHIFT, 0, QA).astype(jnp.int8)
        out = jnp.matmul(h, ow, preferred_element_type=jnp.int32) + ob
        return out.astype(jnp.float32) * (OUTPUT_SCALE / (QA * QW))
    x = jnp.concatenate([_crelu(own), _crelu(opp)])  # (2*L1,)
    w1, b1, w2, b2, ow, ob = _bucket_weights(params, bucket)
    h = _crelu(x @ w1 + b1)
    h = _crelu(h @ w2 + b2)
    out = h @ ow + ob
    return out * OUTPUT_SCALE


def evaluate(params, board64: jnp.ndarray, stm: jnp.ndarray) -> jnp.ndarray:
    """Full evaluation of one lane (refresh + forward); dispatches on the
    feature set statically (by table shape / params type). Accepts either
    our NnueParams or an imported Stockfish net (models/nnue_import.py)."""
    if not isinstance(params, NnueParams):
        from . import nnue_import

        return nnue_import.evaluate_sf(params, board64, stm)
    if is_board768(params):
        acc = accumulators_768(params, board64)
    else:
        acc = accumulators(params, board64)
    return forward_from_acc(params, acc, stm, output_bucket(board64))


v_evaluate = jax.vmap(evaluate, in_axes=(None, 0, 0))


# ------------------------------------------------- host reference (numpy)


def evaluate_reference(params: NnueParams, board64: np.ndarray, stm: int) -> float:
    """Pure-numpy reference implementation for parity tests."""
    p = jax.tree_util.tree_map(np.asarray, params)
    accs = []
    if p.ft_w.shape[0] == NUM_FEATURES_768:
        for persp in (0, 1):
            acc = p.ft_b.astype(np.float64).copy()
            for sq in range(64):
                code = int(board64[sq])
                if code == 0:
                    continue
                pt = (code - 1) % 6
                col = 0 if code <= 6 else 1
                kind = pt if col == persp else 6 + pt
                o_sq = sq ^ (56 if persp == 1 else 0)
                acc += p.ft_w[kind * 64 + o_sq]
            accs.append(acc)
        own, opp = (accs[0], accs[1]) if stm == 0 else (accs[1], accs[0])
        x = np.concatenate([np.clip(own, 0, 1), np.clip(opp, 0, 1)])
        ob = min((int(np.sum(board64 > 0)) - 1) // 4, NUM_OUTPUT_BUCKETS - 1)
        h = np.clip(x @ p.l1_w[ob] + p.l1_b[ob], 0, 1)
        h = np.clip(h @ p.l2_w[ob] + p.l2_b[ob], 0, 1)
        return float((h @ p.out_w[ob] + p.out_b[ob]) * OUTPUT_SCALE)
    for persp in (0, 1):
        king_code = 6 if persp == 0 else 12
        ksq = int(np.argmax(board64 == king_code))
        flip = 56 if persp == 1 else 0
        o_ksq = ksq ^ flip
        mirror = 7 if (o_ksq & 7) > 3 else 0
        o_ksq ^= mirror
        bucket = KING_BUCKET[o_ksq]
        acc = p.ft_b.astype(np.float64).copy()
        for sq in range(64):
            code = int(board64[sq])
            if code == 0:
                continue
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = 10 if pt == 5 else (pt if col == persp else 5 + pt)
            o_sq = (sq ^ flip) ^ mirror
            idx = bucket * (NUM_PIECE_KINDS * NUM_SQUARES) + kind * NUM_SQUARES + o_sq
            acc += p.ft_w[idx]
        accs.append(acc)
    own, opp = (accs[0], accs[1]) if stm == 0 else (accs[1], accs[0])
    x = np.concatenate([np.clip(own, 0, 1), np.clip(opp, 0, 1)])
    ob = min((int(np.sum(board64 > 0)) - 1) // 4, NUM_OUTPUT_BUCKETS - 1)
    h = np.clip(x @ p.l1_w[ob] + p.l1_b[ob], 0, 1)
    h = np.clip(h @ p.l2_w[ob] + p.l2_b[ob], 0, 1)
    return float((h @ p.out_w[ob] + p.out_b[ob]) * OUTPUT_SCALE)


# -------------------------------------------------------------- persistence


def save_params(params: NnueParams, path: str | Path) -> None:
    path = Path(path)
    meta = {
        "format": "fishnet-tpu-nnue-v1",
        "feature_set": (
            "board768" if params.ft_w.shape[0] == NUM_FEATURES_768 else "HalfKAv2_hm"
        ),
        "l1": int(params.ft_w.shape[1]),
        "h1": int(params.l1_w.shape[2]),
        "h2": int(params.l2_w.shape[2]),
        "output_buckets": NUM_OUTPUT_BUCKETS,
        "output_scale": OUTPUT_SCALE,
    }
    arrays = {f: np.asarray(getattr(params, f)) for f in NnueParams._fields}
    np.savez_compressed(path, __meta__=json.dumps(meta), **arrays)


def load_params(path: str | Path) -> NnueParams:
    with np.load(Path(path), allow_pickle=False) as z:
        meta = json.loads(str(z["__meta__"]))
        if meta.get("format") != "fishnet-tpu-nnue-v1":
            raise ValueError(f"unknown nnue format: {meta.get('format')!r}")
        return NnueParams(**{f: jnp.asarray(z[f]) for f in NnueParams._fields})
