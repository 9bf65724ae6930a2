"""Importer for Stockfish `.nnue` network files (HalfKAv2_hm).

The reference embeds two Stockfish nets as opaque binaries and lets the
C++ engine evaluate them (reference: build.rs:8-9 embeds
nn-1c0000000000.nnue + nn-37f18f62d772.nnue; src/assets.rs:15 ships them
inside the asset archive). Here the file format itself is parsed on the
host and the network becomes device-resident arrays evaluated by XLA —
the "ship weights, not binaries" design (SURVEY.md §7.2).

Supported layout — the SFNNv5-era HalfKAv2_hm serialization as written by
the public nnue-pytorch trainer and read by Stockfish 15/16:

    uint32 version | uint32 net_hash | uint32 len | len×u8 description
    FeatureTransformer:
        uint32 ft_hash
        int16 biases[L1]
        int16 weights[22528 × L1]          (row-major, feature-major)
        int32 psqt_weights[22528 × 8]      (8 PSQT output buckets)
    Network (8 layer stacks, stored bucket-by-bucket):
        uint32 hash
        per bucket b in 0..8:
            fc_0: int32 biases[16],  int8 weights[16 × L1]
            fc_1: int32 biases[32],  int8 weights[32 × 30]
            fc_2: int32 biases[1],   int8 weights[1 × 32]

    * FT activation is pairwise "squared clipped ReLU": each perspective's
      L1 accumulator is split in halves, clamp(x,0,QA) of the two halves
      multiplied elementwise → L1/2 values per perspective, concatenated
      (side to move first) → L1 inputs to fc_0.
    * fc_0 has 16 rows; row 15 is the *skip connection* added directly to
      the output (nnue-pytorch docs), rows 0..15 feed a clipped ReLU.
      fc_1 consumes 30 inputs: 15 clipped + 15 squared-clipped values.
    * Any int16/int8/int32 array section may instead be stored LEB128-
      compressed: magic b"COMPRESSED_LEB128" + uint32 byte_count + stream.
    * Quantization scales: FT 127 (QA), hidden weights 64 (QB),
      output scale 16. `load_nnue` keeps the file's integers
      (StockfishNetInt); `dequantize` divides them into float32
      (StockfishNet), for the float programs and controls.

SCOPE — the search path. `TpuEngine(params=<StockfishNet>)` (a parsed
file through `weights_path=*.nnue`, or seeded weights: the benchmark's
`halfka3072` configuration) searches with the accumulator and PSQT pair
carried down the stack (ops/search.py, nnue.acc_scheme == "halfka"): a
move updates both perspectives by its ≤ 4 changed rows (acc_update_pair),
a king's move leaves its own perspective stale, and the stale (lane,
perspective) pairs of a lockstep step are compacted into a few slots and
rebuilt from the board there (search._refresh_stale, acc_refresh_row).
An earlier version of this header argued that incremental HalfKAv2_hm
"cannot win inside a lockstep vmapped step" because a vmapped `cond`
runs both branches. Nobody had measured it. On the chip (PERF.md §6,
PR 35; 64 lanes, L1 3,072, µs a step): compacted refresh 369, a fixed
32-row gather every lane-step 573, the 64-row full refresh every step the
cell's rate of 4.6-4.8 against 8.6-9.1 positions/s; 4.5-4.9 % of pushed
perspectives are rebuilt. Atomic alone stays on the full refresh.

THE PAIR. Stockfish evaluates with both embedded nets (src/evaluate.cpp,
Eval::use_smallnet and Eval::evaluate): where the material imbalance is
large the small net answers, and where its value then comes out small
the big net evaluates the position again and its value stands. `DualNet`
holds both for the search: one feature table whose rows are the big
net's L1 and PSQT columns followed by the small net's, so the stack row,
the ≤ 4-row update and the refresh serve both nets with one op each
(nnue.acc_scheme == "dual"), and `forward_dual_from_acc` applies the
rule lane by lane. `evaluate_dual_reference` is the plain numpy rule.

THE INTEGERS. A net as its file holds it (`StockfishNetInt`, what
`load_nnue` returns), or both of a deployment's (`DualNetInt`: the two
nets' int16 L1 columns joined into one row, their int32 PSQT columns in a
side table), is searched in Stockfish's own arithmetic (nnue.acc_scheme ==
"int"): int16 rows and int32 PSQT sums ride down the stack, and
`forward_int_from_acc` is the integer ladder of Stockfish 15.1-16
(SFNNv5-v7: src/nnue/nnue_feature_transformer.h's 127 / 128 pairwise
transform, layers/affine_transform.h, clipped_relu.h, sqr_clipped_relu.h,
nnue_architecture.h's propagate). `evaluate_int_reference` and
`evaluate_dual_int_reference` are the plain numpy int64 mirror.

Anything that doesn't match this layout (different sizes, unknown
section lengths) raises UnsupportedNnueFormat rather than misparsing.
There are no real `.nnue` files in this build environment, so the parser
is validated by synthetic round-trip against its own writer
(tests/test_nnue_import.py); the layout constants above are the public
ones and size checks are strict enough to fail loudly on mismatch.
"""
from __future__ import annotations

import dataclasses
import struct
from functools import partial
from pathlib import Path

import jax
import numpy as np

from . import nnue

LEB_MAGIC = b"COMPRESSED_LEB128"
NUM_FEATURES = nnue.NUM_FEATURES  # 22528 (32 buckets × 11 kinds × 64 sq)
NUM_PSQT_BUCKETS = 8
NUM_STACKS = 8
FC0_OUT = 16  # 15 hidden + 1 skip row
FC1_IN = 30  # 15 clipped + 15 squared-clipped
FC1_OUT = 32

QA = 127.0  # feature-transformer scale (activations 0..127 ≡ 0..1)
QB = 64.0  # hidden-layer weight scale
OUTPUT_SCALE = 16.0  # FV_SCALE: quantized net output / 16 = centipawns
NNUE2SCORE = 600.0  # float-model output ±1 ≡ ±600 cp (nnue-pytorch)
# quantized storage scales (nnue-pytorch serializer):
#   ft w,b              × QA
#   fc0/fc1 w           × QB          fc0/fc1 b × QA·QB
#   fc2 w               × NNUE2SCORE·OUTPUT_SCALE/QA
#   fc2 b, psqt w       × NNUE2SCORE·OUTPUT_SCALE


class UnsupportedNnueFormat(ValueError):
    pass


_ARRAY_FIELDS = (
    "ft_w", "ft_b", "psqt_w",
    "fc0_w", "fc0_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b",
)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=list(_ARRAY_FIELDS),
    meta_fields=["version", "net_hash", "description"],
)
@dataclasses.dataclass(frozen=True)
class StockfishNet:
    """Dequantized HalfKAv2_hm net; array fields are float32.

    A pytree whose metadata is static, so a net passes straight through
    jit (e.g. as the `params` of ops.search.search_batch_jit)."""

    ft_w: np.ndarray  # (NUM_FEATURES, L1)
    ft_b: np.ndarray  # (L1,)
    psqt_w: np.ndarray  # (NUM_FEATURES, 8) pawn-value units
    fc0_w: np.ndarray  # (8, 16, L1)
    fc0_b: np.ndarray  # (8, 16)
    fc1_w: np.ndarray  # (8, 32, 30)
    fc1_b: np.ndarray  # (8, 32)
    fc2_w: np.ndarray  # (8, 1, 32)
    fc2_b: np.ndarray  # (8, 1)
    version: int = 0
    net_hash: int = 0
    description: bytes = b""

    @property
    def l1(self) -> int:
        return self.ft_w.shape[1]

    def as_device(self) -> "StockfishNet":
        import jax.numpy as jnp

        return dataclasses.replace(
            self, **{f: jnp.asarray(getattr(self, f)) for f in _ARRAY_FIELDS}
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=list(_ARRAY_FIELDS),
    meta_fields=["version", "net_hash", "description"],
)
@dataclasses.dataclass(frozen=True)
class StockfishNetInt:
    """A HalfKAv2_hm net as its file stores it: int16 ft_w / ft_b, int32
    psqt_w, int8 fc*_w, int32 fc*_b, in StockfishNet's shapes. The search
    computes it in Stockfish's integers (nnue.acc_scheme "int");
    `dequantize` gives the float32 StockfishNet of the same file."""

    ft_w: np.ndarray
    ft_b: np.ndarray
    psqt_w: np.ndarray
    fc0_w: np.ndarray
    fc0_b: np.ndarray
    fc1_w: np.ndarray
    fc1_b: np.ndarray
    fc2_w: np.ndarray
    fc2_b: np.ndarray
    version: int = 0
    net_hash: int = 0
    description: bytes = b""

    @property
    def l1(self) -> int:
        return self.ft_w.shape[1]

    def as_device(self) -> "StockfishNetInt":
        import jax.numpy as jnp

        return dataclasses.replace(
            self, **{f: jnp.asarray(getattr(self, f)) for f in _ARRAY_FIELDS}
        )


_STACK_FIELDS = _ARRAY_FIELDS[3:]  # fc0_w .. fc2_b


@partial(jax.tree_util.register_dataclass,
         data_fields=list(_STACK_FIELDS), meta_fields=[])
@dataclasses.dataclass(frozen=True)
class NetStacks:
    """One net's eight layer stacks (the fields of StockfishNet behind its
    feature transformer): float32 in a DualNet, the file's int8 weights
    and int32 biases in a DualNetInt."""

    fc0_w: np.ndarray  # (8, 16, L1)
    fc0_b: np.ndarray
    fc1_w: np.ndarray
    fc1_b: np.ndarray
    fc2_w: np.ndarray
    fc2_b: np.ndarray

    @property
    def l1(self) -> int:
        return self.fc0_w.shape[-1]


# a DualNet's row width is a multiple of this: a TPU v5e lays a table of
# such rows out row by row, where at 3,216 its default layout for the
# parameter is column-major and every program that takes the table copied
# all 290 MB into rows first (0.9 ms a segment; PERF.md §6)
ROW_ALIGN = 128

# Stockfish's choice between its two nets (src/evaluate.cpp, src/types.h)
PAWN_VALUE = 208
PIECE_VALUES = (PAWN_VALUE, 781, 825, 1276, 2538, 0)  # P N B R Q K
SMALLNET_MATERIAL = 962  # |simple_eval| above it: the small net answers
SMALLNET_REEVAL = 236  # |small net's value| below it: the big net again
# ... by piece code (0 empty, 1-6 white P..K, 7-12 black): white's side
_MATERIAL_BY_CODE = np.array(
    (0,) + PIECE_VALUES + tuple(-v for v in PIECE_VALUES), np.int32)


@partial(jax.tree_util.register_dataclass,
         data_fields=["ft", "ft_b", "big", "small"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DualNet:
    """Both nets of a Stockfish deployment, resident together.

    ft (NUM_FEATURES, W) is every feature's row as the search stack holds
    it: the big net's L1 weights and PSQT columns, then the small net's,
    then zeros up to a multiple of ROW_ALIGN — W = 3,328 for L1 3,072 and
    128, where the two nets take 3,216; ft_b (W,) the biases in the same
    layout, 0 under the PSQT columns and the padding. big / small: each
    net's stacks."""

    ft: np.ndarray
    ft_b: np.ndarray
    big: NetStacks
    small: NetStacks

    @classmethod
    def from_nets(cls, big: StockfishNet, small: StockfishNet) -> "DualNet":
        """Host arrays of two nets (as load_nnue returns them) joined."""
        big, small = (jax.tree.map(np.asarray, n) for n in (big, small))
        zeros = np.zeros(NUM_PSQT_BUCKETS, np.float32)
        used = big.ft_w.shape[1] + small.ft_w.shape[1] + 2 * NUM_PSQT_BUCKETS
        pad = -used % ROW_ALIGN
        return cls(
            ft=np.concatenate(
                [big.ft_w, big.psqt_w, small.ft_w, small.psqt_w,
                 np.zeros((NUM_FEATURES, pad), np.float32)], axis=1),
            ft_b=np.concatenate(
                [big.ft_b, zeros, small.ft_b, zeros, np.zeros(pad, np.float32)]),
            big=NetStacks(**{f: getattr(big, f) for f in _STACK_FIELDS}),
            small=NetStacks(**{f: getattr(small, f) for f in _STACK_FIELDS}),
        )

    @property
    def big_width(self) -> int:
        """Where the small net's columns start in a row."""
        return self.big.l1 + NUM_PSQT_BUCKETS

    @property
    def small_width(self) -> int:
        return self.small.l1 + NUM_PSQT_BUCKETS

    def as_device(self) -> "DualNet":
        import jax.numpy as jnp

        return jax.tree.map(jnp.asarray, self)


@partial(jax.tree_util.register_dataclass,
         data_fields=["ft", "ft_b", "psqt", "big", "small"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DualNetInt:
    """Both nets of a Stockfish deployment in the integers of their files.

    ft (NUM_FEATURES, W) int16: every feature's row of the big net's L1
    weights, then the small net's, then zeros up to a multiple of
    ROW_ALIGN (W = 3,200 = 25 x 128 for L1 3,072 and 128: no padding);
    ft_b (W,) int16 the biases in the same layout; psqt
    (NUM_FEATURES, 16) int32 the big net's 8 PSQT columns, then the
    small net's. big / small: each net's int8 / int32 stacks. The search
    stack holds a perspective's int16 row and its int32 PSQT sums beside
    it, and one gather, scatter and refresh of each serves both nets."""

    ft: np.ndarray
    ft_b: np.ndarray
    psqt: np.ndarray
    big: NetStacks
    small: NetStacks

    @classmethod
    def from_nets(cls, big: StockfishNetInt,
                  small: StockfishNetInt) -> "DualNetInt":
        """Host arrays of two integer nets (as load_nnue returns them)
        joined."""
        big, small = (jax.tree.map(np.asarray, n) for n in (big, small))
        pad = -(big.l1 + small.l1) % ROW_ALIGN
        return cls(
            ft=np.concatenate([big.ft_w, small.ft_w,
                               np.zeros((NUM_FEATURES, pad), np.int16)], axis=1),
            ft_b=np.concatenate([big.ft_b, small.ft_b, np.zeros(pad, np.int16)]),
            psqt=np.concatenate([big.psqt_w, small.psqt_w], axis=1),
            big=NetStacks(**{f: getattr(big, f) for f in _STACK_FIELDS}),
            small=NetStacks(**{f: getattr(small, f) for f in _STACK_FIELDS}),
        )

    def as_device(self) -> "DualNetInt":
        import jax.numpy as jnp

        return jax.tree.map(jnp.asarray, self)


def is_int_net(net) -> bool:
    """An integer net (StockfishNetInt, DualNetInt): searched in
    Stockfish's arithmetic (nnue.acc_scheme "int")."""
    return isinstance(net, (StockfishNetInt, DualNetInt))


# ------------------------------------------------------------------ LEB128


def _leb128_decode(buf: memoryview, count: int) -> tuple[np.ndarray, int]:
    """Decode `count` signed LEB128 integers; returns (values, bytes_used)."""
    out = np.empty(count, dtype=np.int64)
    pos = 0
    end = len(buf)
    for i in range(count):
        result = 0
        shift = 0
        while True:
            if pos >= end:
                raise UnsupportedNnueFormat("truncated LEB128 stream")
            b = buf[pos]
            pos += 1
            result |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                if b & 0x40:  # sign-extend
                    result |= -(1 << shift)
                break
        out[i] = result
    return out, pos


def _leb128_encode(values: np.ndarray) -> bytes:
    out = bytearray()
    for v in map(int, values):
        while True:
            b = v & 0x7F
            v >>= 7
            if (v == 0 and not b & 0x40) or (v == -1 and b & 0x40):
                out.append(b)
                break
            out.append(b | 0x80)
    return bytes(out)


class _Reader:
    def __init__(self, data: bytes):
        self.data = memoryview(data)
        self.pos = 0

    def u32(self) -> int:
        (v,) = struct.unpack_from("<I", self.data, self.pos)
        self.pos += 4
        return v

    def bytes(self, n: int) -> bytes:
        b = bytes(self.data[self.pos : self.pos + n])
        if len(b) != n:
            raise UnsupportedNnueFormat("truncated file")
        self.pos += n
        return b

    def array(self, dtype, count: int) -> np.ndarray:
        """Read `count` values, either raw little-endian or LEB128-block."""
        magic_len = len(LEB_MAGIC)
        if bytes(self.data[self.pos : self.pos + magic_len]) == LEB_MAGIC:
            self.pos += magic_len
            nbytes = self.u32()
            values, used = _leb128_decode(self.data[self.pos :], count)
            if used != nbytes:
                raise UnsupportedNnueFormat(
                    f"LEB128 block length mismatch: header {nbytes}, used {used}"
                )
            self.pos += used
            info = np.iinfo(dtype)
            if values.min() < info.min or values.max() > info.max:
                raise UnsupportedNnueFormat("LEB128 value out of dtype range")
            return values.astype(dtype)
        itemsize = np.dtype(dtype).itemsize
        raw = self.bytes(count * itemsize)
        return np.frombuffer(raw, dtype=np.dtype(dtype).newbyteorder("<")).astype(dtype)

    def eof(self) -> bool:
        return self.pos == len(self.data)


# ------------------------------------------------------------------- parse


def _infer_l1(total: int, header_end: int) -> int:
    """Solve file size for L1 given the fixed layout (raw, uncompressed)."""
    # size = ft_hash(4) + 2*L1 + 2*NF*L1 + 4*NF*8 + net_hash(4)
    #        + 8 * (4*16 + 16*L1 + 4*32 + 32*30 + 4 + 32)
    body = total - header_end
    for l1 in (64, 128, 256, 512, 1024, 1536, 2048, 2560, 3072):
        ft = 4 + 2 * l1 + 2 * NUM_FEATURES * l1 + 4 * NUM_FEATURES * NUM_PSQT_BUCKETS
        stacks = 4 + NUM_STACKS * (
            4 * FC0_OUT + FC0_OUT * l1 + 4 * FC1_OUT + FC1_OUT * FC1_IN + 4 + FC1_OUT
        )
        if ft + stacks == body:
            return l1
    raise UnsupportedNnueFormat(
        f"cannot infer L1 from file size {total} (compressed files carry "
        "explicit block lengths; raw files must match a known L1)"
    )


def load_nnue(path: str | Path, l1: int | None = None) -> StockfishNetInt:
    """Parse a `.nnue` file into its own integers (`dequantize` gives the
    float32 net)."""
    data = Path(path).read_bytes()
    r = _Reader(data)
    version = r.u32()
    net_hash = r.u32()
    desc_len = r.u32()
    if desc_len > 4096:
        raise UnsupportedNnueFormat(f"implausible description length {desc_len}")
    description = r.bytes(desc_len)

    ft_hash = r.u32()  # noqa: F841 — validated only by downstream size checks
    if l1 is None:
        try:
            l1 = _infer_l1(len(data), r.pos - 4)
        except UnsupportedNnueFormat:
            if LEB_MAGIC in data:  # compressed sections shrink the file
                raise UnsupportedNnueFormat(
                    "pass l1= explicitly for compressed files"
                ) from None
            raise
    if l1 % 2:
        raise UnsupportedNnueFormat("L1 must be even (pairwise activation)")

    ft_b = r.array(np.int16, l1)
    ft_w = r.array(np.int16, NUM_FEATURES * l1).reshape(NUM_FEATURES, l1)
    psqt = r.array(np.int32, NUM_FEATURES * NUM_PSQT_BUCKETS).reshape(
        NUM_FEATURES, NUM_PSQT_BUCKETS
    )

    _net_hash2 = r.u32()
    fc0_w = np.empty((NUM_STACKS, FC0_OUT, l1), np.int8)
    fc0_b = np.empty((NUM_STACKS, FC0_OUT), np.int32)
    fc1_w = np.empty((NUM_STACKS, FC1_OUT, FC1_IN), np.int8)
    fc1_b = np.empty((NUM_STACKS, FC1_OUT), np.int32)
    fc2_w = np.empty((NUM_STACKS, 1, FC1_OUT), np.int8)
    fc2_b = np.empty((NUM_STACKS, 1), np.int32)
    for b in range(NUM_STACKS):
        fc0_b[b] = r.array(np.int32, FC0_OUT)
        fc0_w[b] = r.array(np.int8, FC0_OUT * l1).reshape(FC0_OUT, l1)
        fc1_b[b] = r.array(np.int32, FC1_OUT)
        fc1_w[b] = r.array(np.int8, FC1_OUT * FC1_IN).reshape(FC1_OUT, FC1_IN)
        fc2_b[b] = r.array(np.int32, 1)
        fc2_w[b] = r.array(np.int8, FC1_OUT).reshape(1, FC1_OUT)
    if not r.eof():
        raise UnsupportedNnueFormat(
            f"{len(data) - r.pos} trailing bytes after last layer stack"
        )

    return StockfishNetInt(
        ft_w=ft_w, ft_b=ft_b, psqt_w=psqt,
        fc0_w=fc0_w, fc0_b=fc0_b, fc1_w=fc1_w, fc1_b=fc1_b,
        fc2_w=fc2_w, fc2_b=fc2_b,
        version=version, net_hash=net_hash, description=description,
    )


def dequantize(net: StockfishNetInt) -> StockfishNet:
    """The float32 net of a file's integers, by the storage scales above
    (the float programs, the bf16 control and the float tests take this).
    fc1's columns stay in the file's order."""
    n = jax.tree.map(np.asarray, net)
    return StockfishNet(
        ft_w=(n.ft_w / QA).astype(np.float32),
        ft_b=(n.ft_b / QA).astype(np.float32),
        psqt_w=(n.psqt_w / (NNUE2SCORE * OUTPUT_SCALE)).astype(np.float32),
        fc0_w=(n.fc0_w / QB).astype(np.float32),
        fc0_b=(n.fc0_b / (QA * QB)).astype(np.float32),
        fc1_w=(n.fc1_w / QB).astype(np.float32),
        fc1_b=(n.fc1_b / (QA * QB)).astype(np.float32),
        fc2_w=(n.fc2_w / (NNUE2SCORE * OUTPUT_SCALE / QA)).astype(np.float32),
        fc2_b=(n.fc2_b / (NNUE2SCORE * OUTPUT_SCALE)).astype(np.float32),
        version=net.version, net_hash=net.net_hash,
        description=net.description,
    )


# ------------------------------------------------------------------ forward
#
# One accumulator row a perspective: its L1 values and, behind them, its 8
# PSQT sums — (L1 + 8,) float32, whatever the weights' type, so the PSQT
# sums ride down the search stack with the accumulator (ops/search.py:
# SearchState.acc holds two such rows a ply). A row is the bias plus the
# rows of the pieces' features; a move changes ≤ 4 of them a perspective
# (acc_update_pair), and a move of a perspective's king changes them all
# (acc_refresh_row). The products are float32 on every backend
# (precision=HIGHEST: a TPU's default is one bfloat16 pass, about 2 cp off
# over 3,072 terms). An integer net's row is a pair of arrays instead: its
# int16 L1 values and its int32 PSQT sums, (L1,) and (8,) (a DualNetInt's
# (W,) and (16,)), added in their own integers as Stockfish adds them.

# the rows a refresh gathers: the pieces a board of that program can hold
REFRESH_ROWS = 32
REFRESH_ROWS_HORDE = 64  # 36 pawns + 16: no compaction buys anything


def refresh_rows(variant: str = "standard") -> int:
    return REFRESH_ROWS_HORDE if variant == "horde" else REFRESH_ROWS


def _int_tables(net):
    """An integer net's (int16 L1 table, int32 PSQT table, int16 biases)."""
    if isinstance(net, DualNetInt):
        return net.ft, net.psqt, net.ft_b
    return net.ft_w, net.psqt_w, net.ft_b


def _rows(net, idx):
    """[ft_w | psqt_w][idx] → (..., L1 + 8) float32: a gather of whole
    rows (a DualNet's table holds them so, both nets' side by side); an
    integer net's (int16 rows, int32 PSQT rows)."""
    import jax.numpy as jnp

    if is_int_net(net):
        ft, psqt, _ = _int_tables(net)
        return jnp.asarray(ft)[idx], jnp.asarray(psqt)[idx]
    if isinstance(net, DualNet):
        return jnp.asarray(net.ft)[idx].astype(jnp.float32)
    return jnp.concatenate(
        [jnp.asarray(net.ft_w)[idx], jnp.asarray(net.psqt_w)[idx]], axis=-1
    ).astype(jnp.float32)


def _bias_row(net):
    """The row of a perspective with no piece: the biases, 0 under the
    PSQT columns (an integer net's: its int16 biases, int32 zeros)."""
    import jax.numpy as jnp

    if is_int_net(net):
        _, psqt, ft_b = _int_tables(net)
        return jnp.asarray(ft_b), jnp.zeros((psqt.shape[1],), jnp.int32)
    if isinstance(net, DualNet):
        return jnp.asarray(net.ft_b).astype(jnp.float32)
    return jnp.concatenate(
        [jnp.asarray(net.ft_b).astype(jnp.float32),
         jnp.zeros((NUM_PSQT_BUCKETS,), jnp.float32)]
    )


def acc_refresh_row(net, board64, perspective, n_rows: int = REFRESH_ROWS):
    """(L1 + 8,) accumulator row of one perspective from the board (a
    DualNet's: both nets' row, DualNet.ft's width): the bias plus the
    rows of its ≤ n_rows pieces, their squares compacted into n_rows
    slots first so that no row is gathered for an empty square."""
    import jax.numpy as jnp

    from ..ops.board import king_square

    ksq = jnp.maximum(king_square(board64, perspective), 0)
    idx64 = nnue.feature_indices(board64, perspective, ksq)
    if n_rows >= 64:
        idx, used = jnp.clip(idx64, 0), idx64 >= 0
    else:
        occ = idx64 >= 0
        slot = jnp.cumsum(occ) - 1
        hit = occ[None, :] & (
            slot[None, :] == jnp.arange(n_rows, dtype=jnp.int32)[:, None]
        )  # (n_rows, 64), one square a used slot
        idx = jnp.sum(jnp.where(hit, idx64[None, :], 0), axis=1)
        used = jnp.any(hit, axis=1)
    if is_int_net(net):
        # a slot that is not used adds 0; an int16 sum wraps as
        # Stockfish's adds do
        return jax.tree.map(
            lambda bias, rows: bias + jnp.sum(
                jnp.where(used[:, None], rows, 0), axis=0, dtype=bias.dtype),
            _bias_row(net), _rows(net, idx))
    # a slot that is not used adds its row times 0
    return _bias_row(net) + jnp.sum(_rows(net, idx) * used[:, None], axis=0)


def acc_refresh_pair(net, board64, n_rows: int = REFRESH_ROWS):
    """(2, L1 + 8): white's and black's rows from the board (an integer
    net's: the pair of (2, L1) int16 rows and (2, 8) int32 sums)."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(lambda p: acc_refresh_row(net, board64, p, n_rows))(
        jnp.arange(2, dtype=jnp.int32)
    )


def acc_update_pair(net, pair, board64, codes, sqs, signs):
    """The child's (2, L1 + 8) pair (an integer net's: its (2, L1) int16
    rows and (2, 8) int32 sums) from the parent's by the move's piece
    changes (board.move_piece_changes: (K,) codes, squares, signs; code 0
    is an unused slot), and which perspectives that is not enough for:
    (2,) bool, true where a king of that colour is among the changes, so
    its perspective's king square — bucket and mirror, every feature —
    may have changed and the row has to be rebuilt from the child's
    board. board64 is the PARENT's: a perspective that is not refreshed
    has its king where it was. 2K rows gathered."""
    import jax.numpy as jnp

    from ..ops.board import king_square, piece_color, piece_type

    persp = jnp.arange(2, dtype=jnp.int32)
    ksq = jnp.stack([jnp.maximum(king_square(board64, p), 0) for p in (0, 1)])
    idx = nnue.feature_index(
        codes[None, :], sqs[None, :], persp[:, None], ksq[:, None]
    )  # (2, K)
    weight = jnp.where(idx >= 0, signs[None, :], 0)
    idx = jnp.clip(idx, 0)
    # a gather and an add a slot: all K slots' rows as one (2, K, L1 + 8)
    # tensor cost the chip a relayout and a reduction of their own
    # (PERF.md §6, PR 35: 43 → 33 µs a 64-lane step at L1 3,072)
    if is_int_net(net):
        # each table in its own integers (±1 and 0 products; int16 adds
        # wrap as Stockfish's do)
        parts = [_rows(net, idx[:, k]) for k in range(codes.shape[0])]
        delta = tuple(
            sum(p[t] * weight[:, k, None].astype(p[t].dtype)
                for k, p in enumerate(parts))
            for t in (0, 1))
    else:
        delta = sum(
            _rows(net, idx[:, k]) * weight[:, k, None]  # ±1 and 0: exact
            for k in range(codes.shape[0])
        )
    king = (codes > 0) & (piece_type(codes) == 5)
    stale = jnp.any(
        king[None, :] & (piece_color(codes)[None, :] == persp[:, None]), axis=1
    )
    if is_int_net(net):
        return jax.tree.map(jnp.add, tuple(pair), delta), stale
    return pair + delta, stale


def _pick_stack(values, bucket):
    """values (8·n,) of all eight stacks → the (n,) of stack `bucket`,
    by a one-hot select and a sum of zeros: exact, and no per-lane
    gather."""
    import jax.numpy as jnp

    v = values.reshape(NUM_STACKS, -1)
    hot = jnp.arange(NUM_STACKS, dtype=jnp.int32) == bucket
    return jnp.sum(jnp.where(hot[:, None], v, 0), axis=0)


def forward_sf_from_acc(net, pair, stm, bucket):
    """Centipawns from the side to move's view, from a (2, L1 + 8) pair;
    net: a StockfishNet, or one net's NetStacks.

    Each layer is computed for all eight stacks as ONE product — fc0 as
    (128, L1) · (L1,) — and the stack's outputs picked afterwards: under
    the search step's vmap that is a (B, L1) × (L1, 128) matrix product,
    where selecting the stack's weights first would move 16 × L1 of them
    a lane a step."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    l1 = net.fc0_w.shape[-1]
    half = l1 // 2
    own = jnp.where(stm == 0, pair[0], pair[1])
    opp = jnp.where(stm == 0, pair[1], pair[0])

    def pairwise(acc):
        c = jnp.clip(acc[:l1], 0.0, 1.0)
        return c[:half] * c[half:]

    x = jnp.concatenate([pairwise(own), pairwise(opp)])  # (L1,)

    def stack_layer(w, b, v):
        # w (8, n, k), b (8, n), v (k,) → the (n,) of this lane's stack
        n = w.shape[1]
        all8 = jnp.dot(
            jnp.asarray(w).reshape(NUM_STACKS * n, -1).astype(f32), v,
            precision=hi,
        ) + jnp.asarray(b).reshape(-1).astype(f32)
        return _pick_stack(all8, bucket)

    h0 = stack_layer(net.fc0_w, net.fc0_b, x)  # (16,)
    skip = h0[FC0_OUT - 1]
    h = jnp.clip(h0[:FC0_OUT - 1], 0.0, 1.0)
    h1 = jnp.clip(
        stack_layer(net.fc1_w, net.fc1_b, jnp.concatenate([h, jnp.square(h)])),
        0.0, 1.0,
    )
    out = stack_layer(net.fc2_w, net.fc2_b, h1)[0]
    ps = _pick_stack((own[l1:] - opp[l1:]).astype(f32), bucket)[0] / 2.0
    return (out + skip + ps) * NNUE2SCORE


def material_imbalance(board64):
    """Stockfish's simple_eval from white's side: 208 a pawn and the
    non-pawn material (N 781, B 825, R 1276, Q 2538), white's less
    black's. The rule reads its magnitude, the same from either side."""
    import jax.numpy as jnp

    # a compare against the twelve codes, not a gather from the table: on
    # a TPU v5e a vmapped 13-entry gather cost 39 µs a 64-lane step
    codes = jnp.arange(1, _MATERIAL_BY_CODE.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(board64[:, None] == codes[None, :],
                             jnp.asarray(_MATERIAL_BY_CODE[1:]), 0))


def forward_dual_from_acc(net: DualNet, pair, stm, bucket, board64):
    """The pair's value by Stockfish's rule, from a (2, W) pair of both
    nets' rows: → (centipawns, int32, from the side to move's view;
    (3,) int32 one-hot: the small net's value kept, the small net chosen
    and handed back to the big net, the big net chosen).

    Each net's value is forward_sf_from_acc's, truncated to an integer as
    Stockfish's Value is before it is compared. Both nets run for every
    lane: under lockstep a lane's branch is a select, and the small net's
    layers cost a few percent of the big one's."""
    import jax
    import jax.numpy as jnp

    wb, ws = net.big_width, net.small_width
    v_big = jnp.int32(forward_sf_from_acc(net.big, pair[:, :wb], stm, bucket))
    with jax.named_scope("step.eval_small"):
        v_small = jnp.int32(
            forward_sf_from_acc(net.small, pair[:, wb:wb + ws], stm, bucket))
        small, reeval, kept = _smallnet_rule(v_small, board64)
    return (jnp.where(kept, v_small, v_big),
            jnp.stack([kept, reeval, ~small]).astype(jnp.int32))


def _smallnet_rule(v_small, board64):
    """Stockfish's use_smallnet and its re-evaluation, lane by lane: →
    (the small net chosen, handed back to the big net, its value kept)."""
    import jax.numpy as jnp

    small = jnp.abs(material_imbalance(board64)) > SMALLNET_MATERIAL
    reeval = small & (jnp.abs(v_small) < SMALLNET_REEVAL)
    return small, reeval, small & ~reeval


# Stockfish's integer ladder (SF 15.1-16; nnue_architecture.h propagate):
# the skip output y15 counts 127 · 64 a unit, the value 600 · 16
SKIP_NUM = int(NNUE2SCORE * OUTPUT_SCALE)  # 9,600
SKIP_DEN = int(QA * QB)  # 8,128
# SqrClippedReLU: min(127, y² >> 19) reaches 127 at |y| = 8,160, so y is
# clamped there first and y² stays inside int32
SQR_SHIFT = 19  # 2 · WeightScaleBits + 7
SQR_CLAMP = 8160
SHIFT = 6  # WeightScaleBits: ClippedReLU's y >> 6


def forward_int_from_acc(net, pair, stm, bucket):
    """A net's Value from the side to move's view, in Stockfish's integer
    arithmetic, from the pair (int16 rows (2, >= L1), int32 PSQT sums
    (2, 8)); net: a StockfishNetInt, or one net's int NetStacks.

    x_j = clamp(a_j, 0, 127) · clamp(a_(j + L1/2), 0, 127) / 128 for the
    side to move, then the other (int8, 0..126); y = fc0 x, int8 x int8
    products summed in int32, all eight stacks as one product as
    forward_sf_from_acc computes them; fc1 takes [min(127, y² >> 19)
    of the unclipped y, clamp(y >> 6, 0, 127)] of y's first 15 (the
    file's order); then clamp(· >> 6, 0, 127) and fc2; the skip
    y15 · 9,600 / 8,128; Value = ((psqt_stm - psqt_other) / 2 + fc2 +
    skip) / 16. Every / truncates toward zero (lax.div), as C++'s."""
    import jax.numpy as jnp
    from jax import lax

    i32, i8 = jnp.int32, jnp.int8
    rows, psqt = pair
    l1 = net.fc0_w.shape[-1]
    half = l1 // 2

    def side(a, first):
        return jnp.where(stm == 0, a[first], a[1 - first])

    def pairwise(acc):
        c = jnp.clip(acc[:l1].astype(i32), 0, 127)
        return (c[:half] * c[half:]) >> 7

    x = jnp.concatenate([pairwise(side(rows, 0)), pairwise(side(rows, 1))])

    def stack_layer(w, b, v):
        # w (8, n, k) int8, b (8, n) int32, v (k,) → this stack's (n,)
        n = w.shape[1]
        all8 = jnp.dot(jnp.asarray(w).reshape(NUM_STACKS * n, -1),
                       v.astype(i8), preferred_element_type=i32)
        return _pick_stack(all8 + jnp.asarray(b).reshape(-1), bucket)

    y = stack_layer(net.fc0_w, net.fc0_b, x)  # (16,)
    hidden = jnp.clip(y[:FC0_OUT - 1], -SQR_CLAMP, SQR_CLAMP)
    sqr = jnp.minimum((hidden * hidden) >> SQR_SHIFT, 127)
    act = jnp.clip(y[:FC0_OUT - 1] >> SHIFT, 0, 127)
    z = stack_layer(net.fc1_w, net.fc1_b, jnp.concatenate([sqr, act]))
    out = stack_layer(net.fc2_w, net.fc2_b, jnp.clip(z >> SHIFT, 0, 127))[0]
    skip = lax.div(y[FC0_OUT - 1] * SKIP_NUM, i32(SKIP_DEN))
    ps = lax.div(_pick_stack(side(psqt, 0) - side(psqt, 1), bucket)[0], i32(2))
    return lax.div(ps + out + skip, i32(OUTPUT_SCALE))


def forward_int(net, pair, stm, bucket, board64):
    """An integer net's eval from its pair (int16 rows, int32 PSQT sums):
    → (Value, int32; (3,) which net answered, forward_dual_from_acc's
    one-hot for a DualNetInt, zeros for one net). The ladder's ops are
    `step.eval_int`; a DualNetInt's small net and the rule
    `step.eval_small`."""
    import jax
    import jax.numpy as jnp

    rows, psqt = pair
    if not isinstance(net, DualNetInt):
        with jax.named_scope("step.eval_int"):
            return (forward_int_from_acc(net, pair, stm, bucket),
                    jnp.zeros((3,), jnp.int32))
    wb, ws, nb = net.big.l1, net.small.l1, NUM_PSQT_BUCKETS
    with jax.named_scope("step.eval_int"):
        v_big = forward_int_from_acc(
            net.big, (rows[:, :wb], psqt[:, :nb]), stm, bucket)
    with jax.named_scope("step.eval_small"):
        v_small = forward_int_from_acc(
            net.small, (rows[:, wb:wb + ws], psqt[:, nb:]), stm, bucket)
        small, reeval, kept = _smallnet_rule(v_small, board64)
    return (jnp.where(kept, v_small, v_big),
            jnp.stack([kept, reeval, ~small]).astype(jnp.int32))


def evaluate_sf(net, board64, stm):
    """Centipawn-ish score for one position, SFNNv5 semantics, in jax:
    both rows from the board, then forward_sf_from_acc — what the search
    computes at a node whose accumulator came down its stack (a DualNet:
    both nets' rows, then the rule of forward_dual_from_acc)."""
    pair = acc_refresh_pair(net, board64, 64)
    bucket = nnue.output_bucket(board64)
    if is_int_net(net):
        return forward_int(net, pair, stm, bucket, board64)[0]
    if isinstance(net, DualNet):
        return forward_dual_from_acc(net, pair, stm, bucket, board64)[0]
    return forward_sf_from_acc(net, pair, stm, bucket)


def evaluate_sf_reference(net: StockfishNet, board64: np.ndarray, stm: int) -> float:
    """Pure-numpy mirror of evaluate_sf for parity tests."""
    l1 = net.ft_w.shape[1]
    half = l1 // 2
    accs, psqts = [], []
    for persp in (0, 1):
        king_code = 6 if persp == 0 else 12
        ksq = int(np.argmax(board64 == king_code))
        flip = 56 if persp == 1 else 0
        o_ksq = ksq ^ flip
        mirror = 7 if (o_ksq & 7) > 3 else 0
        o_ksq ^= mirror
        bucket = nnue.KING_BUCKET[o_ksq]
        acc = net.ft_b.astype(np.float64).copy()
        ps = np.zeros(NUM_PSQT_BUCKETS)
        for sq in range(64):
            code = int(board64[sq])
            if code == 0:
                continue
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = 10 if pt == 5 else (pt if col == persp else 5 + pt)
            o_sq = (sq ^ flip) ^ mirror
            idx = bucket * (11 * 64) + kind * 64 + o_sq
            acc += net.ft_w[idx]
            ps += net.psqt_w[idx]
        accs.append(acc)
        psqts.append(ps)
    own, opp = (0, 1) if stm == 0 else (1, 0)

    def pairwise(a):
        c = np.clip(a, 0.0, 1.0)
        return c[:half] * c[half:]

    x = np.concatenate([pairwise(accs[own]), pairwise(accs[opp])])
    ob = min((int(np.sum(board64 > 0)) - 1) // 4, NUM_PSQT_BUCKETS - 1)
    h0 = net.fc0_w[ob] @ x + net.fc0_b[ob]
    skip = h0[15]
    h = np.clip(h0[:15], 0.0, 1.0)
    h1 = np.clip(net.fc1_w[ob] @ np.concatenate([h, h * h]) + net.fc1_b[ob], 0.0, 1.0)
    out = float((net.fc2_w[ob] @ h1 + net.fc2_b[ob])[0])
    psqt = (psqts[own][ob] - psqts[opp][ob]) / 2.0
    return (out + skip + psqt) * NNUE2SCORE


def evaluate_dual_reference(big: StockfishNet, small: StockfishNet,
                            board64: np.ndarray, stm: int) -> int:
    """Pure-numpy mirror of the two-net rule (forward_dual_from_acc):
    each net's value truncated to an integer, the small net's where the
    material imbalance is large and its value is not small, else the big
    net's."""
    board64 = np.asarray(board64)
    material = int(_MATERIAL_BY_CODE[board64].sum())
    if abs(material) > SMALLNET_MATERIAL:
        v_small = int(evaluate_sf_reference(small, board64, stm))
        if abs(v_small) >= SMALLNET_REEVAL:
            return v_small
    return int(evaluate_sf_reference(big, board64, stm))


def _cdiv(a: int, b: int) -> int:
    """a / b truncated toward zero, as C++ divides integers."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b > 0) else -q


def _fits(value, bits: int, what: str) -> None:
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    if not np.all((np.asarray(value) >= lo) & (np.asarray(value) <= hi)):
        raise OverflowError(f"{what} leaves int{bits}: the draw would wrap")


def evaluate_int_reference(net: StockfishNetInt, board64: np.ndarray,
                           stm: int) -> int:
    """Plain numpy int64 mirror of forward_int_from_acc for one position:
    both perspectives' rows summed from the board, no batching, no
    incremental rows, nothing of jax. An accumulator value outside int16,
    or a PSQT sum or a product outside int32, raises: Stockfish's adds
    would wrap there, which this mirror does not model."""
    n = jax.tree.map(np.asarray, net)
    board64 = np.asarray(board64)
    l1 = n.ft_w.shape[1]
    half = l1 // 2
    accs, psqts = [], []
    for persp in (0, 1):
        king_code = 6 if persp == 0 else 12
        ksq = int(np.argmax(board64 == king_code))
        flip = 56 if persp == 1 else 0
        mirror = 7 if ((ksq ^ flip) & 7) > 3 else 0
        bucket = nnue.KING_BUCKET[ksq ^ flip ^ mirror]
        acc = n.ft_b.astype(np.int64)
        ps = np.zeros(NUM_PSQT_BUCKETS, np.int64)
        for sq in range(64):
            code = int(board64[sq])
            if code == 0:
                continue
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = 10 if pt == 5 else (pt if col == persp else 5 + pt)
            idx = bucket * (11 * 64) + kind * 64 + ((sq ^ flip) ^ mirror)
            acc = acc + n.ft_w[idx].astype(np.int64)
            ps = ps + n.psqt_w[idx].astype(np.int64)
        _fits(acc, 16, "an accumulator value")
        _fits(ps, 32, "a PSQT sum")
        accs.append(acc)
        psqts.append(ps)
    own, opp = (0, 1) if stm == 0 else (1, 0)

    def pairwise(a):
        c = np.clip(a, 0, 127)
        return (c[:half] * c[half:]) // 128

    x = np.concatenate([pairwise(accs[own]), pairwise(accs[opp])])
    ob = min((int(np.sum(board64 > 0)) - 1) // 4, NUM_STACKS - 1)
    y = n.fc0_w[ob].astype(np.int64) @ x + n.fc0_b[ob]
    _fits(y, 32, "an fc0 output")
    sqr = np.minimum((y[:15] * y[:15]) >> 19, 127)
    act = np.clip(y[:15] >> 6, 0, 127)
    z = n.fc1_w[ob].astype(np.int64) @ np.concatenate([sqr, act]) + n.fc1_b[ob]
    h = np.clip(z >> 6, 0, 127)
    out = int((n.fc2_w[ob].astype(np.int64) @ h + n.fc2_b[ob])[0])
    _fits(int(y[15]) * SKIP_NUM, 32, "the skip's product")
    skip = _cdiv(int(y[15]) * SKIP_NUM, SKIP_DEN)
    psqt = _cdiv(int(psqts[own][ob] - psqts[opp][ob]), 2)
    _fits(psqt + out + skip, 32, "the value's sum")
    return _cdiv(psqt + out + skip, int(OUTPUT_SCALE))


def evaluate_dual_int_reference(big: StockfishNetInt, small: StockfishNetInt,
                                board64: np.ndarray, stm: int) -> int:
    """Plain numpy mirror of forward_int on a DualNetInt: the small net's
    Value where the material imbalance is large and that value is not
    small, else the big net's (evaluate_dual_reference's rule)."""
    board64 = np.asarray(board64)
    material = int(_MATERIAL_BY_CODE[board64].sum())
    if abs(material) > SMALLNET_MATERIAL:
        v_small = evaluate_int_reference(small, board64, stm)
        if abs(v_small) >= SMALLNET_REEVAL:
            return v_small
    return evaluate_int_reference(big, board64, stm)


# ---------------------------------------------------- synthetic writer (tests)


def write_nnue(path: str | Path, net_q: dict, compress_ft: bool = False) -> None:
    """Serialize quantized arrays into the `.nnue` layout (test fixture).

    net_q keys: ft_b int16[L1], ft_w int16[NF,L1], psqt int32[NF,8],
    and per-stack lists fc0_b/fc0_w/fc1_b/fc1_w/fc2_b/fc2_w."""
    l1 = net_q["ft_b"].shape[0]
    out = bytearray()
    out += struct.pack("<I", net_q.get("version", 0x7AF32F20))
    out += struct.pack("<I", net_q.get("net_hash", 0x1337))
    desc = net_q.get("description", b"fishnet-tpu synthetic test net")
    out += struct.pack("<I", len(desc)) + desc

    def emit(arr: np.ndarray, compress: bool = False):
        nonlocal out
        flat = arr.reshape(-1)
        if compress:
            payload = _leb128_encode(flat)
            out += LEB_MAGIC + struct.pack("<I", len(payload)) + payload
        else:
            out += flat.astype(flat.dtype.newbyteorder("<")).tobytes()

    out += struct.pack("<I", net_q.get("ft_hash", 0x5D69D5B8))
    emit(net_q["ft_b"].astype(np.int16))
    emit(net_q["ft_w"].astype(np.int16).reshape(-1), compress=compress_ft)
    emit(net_q["psqt"].astype(np.int32))
    out += struct.pack("<I", net_q.get("stack_hash", 0x63337156))
    for b in range(NUM_STACKS):
        emit(net_q["fc0_b"][b].astype(np.int32))
        emit(net_q["fc0_w"][b].astype(np.int8))
        emit(net_q["fc1_b"][b].astype(np.int32))
        emit(net_q["fc1_w"][b].astype(np.int8))
        emit(net_q["fc2_b"][b].astype(np.int32))
        emit(net_q["fc2_w"][b].astype(np.int8))
    Path(path).write_bytes(bytes(out))
