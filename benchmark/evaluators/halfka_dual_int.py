"""Plain evaluation with both nets of a Stockfish deployment in Stockfish's
own integer arithmetic, numpy int64: the reference's eval for the
configurations that name ``engine.evaluator`` = "halfka_dual_int".

The deployment is ``halfka_dual``'s (lichess-org/fishnet build.rs:8-9
embeds nn-1c0000000000.nnue at L1 3,072 and nn-37f18f62d772.nnue at L1
128; Stockfish's src/evaluate.cpp chooses between them), computed as the
files are stored and as Stockfish 15.1-16 computes them (SFNNv5-v7:
src/nnue/nnue_feature_transformer.h, layers/affine_transform.h,
clipped_relu.h, sqr_clipped_relu.h, nnue_architecture.h). For one net:

* each perspective's row: ``acc = ft_b + sum ft_w[f]`` (int16 values; a
  sum outside int16 is refused: Stockfish's adds would wrap there) and
  ``psqt = sum psqt_w[f]`` (8 int32), over ``halfka.py``'s features;
* ``x_j = clamp(acc_j, 0, 127) * clamp(acc_(j + L1/2), 0, 127) / 128``,
  the side to move's L1/2 values, then the other side's (0..126);
* stack b = (pieces - 1) // 4: ``y = fc0_w[b] x + fc0_b[b]`` (16, int32);
  of the first 15, ``sqr = min(127, (y * y) >> 19)`` of the unclipped y
  and ``act = clamp(y >> 6, 0, 127)``; ``z = fc1_w[b] [sqr, act] +
  fc1_b[b]`` (the file's order: squared first); ``h = clamp(z >> 6, 0,
  127)``; ``out = fc2_w[b] h + fc2_b[b]``;
* ``skip = y_15 * 9600 / 8128``; ``value = ((psqt_own[b] - psqt_opp[b]) /
  2 + out + skip) / 16``; every / truncates toward zero, as C++ divides.

Then the rule of ``halfka_dual.py`` (962 and 236, on these integer
values), and the clamp the search applies.

The weights are ``halfka.py``'s draw for the configuration's two seeds,
stored at the file's scales (ft x 127, PSQT x 9,600, fc0/fc1 weights x
64, their biases x 8,128, fc2's weights x 9,600 / 127 and its bias x
9,600), rounded, clamped to their types, with fc1's 30 input columns put
into the file's order (the draw's float forward takes them [clipped,
squared]): ``halfka_dual``'s nets quantized.

No incremental update, no batching, nothing of the program.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict

import numpy as np


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_evaluator_{name}_for_dual_int", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


halfka = _sibling("halfka")
dual = _sibling("halfka_dual")  # the rule and simple_eval

SMALLNET_MATERIAL = dual.SMALLNET_MATERIAL
SMALLNET_REEVAL = dual.SMALLNET_REEVAL
material = dual.material

QA, QB = 127, 64  # the feature transformer's and the hidden weights' scales
OUT = 600 * 16  # a unit of the float output in the value's 1/16 cp
HIDDEN = halfka.FC0_OUT - 1  # 15
# (key, scale, type) of each stored array
STORAGE = (
    ("ft_w", QA, np.int16), ("ft_b", QA, np.int16), ("psqt_w", OUT, np.int32),
    ("fc0_w", QB, np.int8), ("fc0_b", QA * QB, np.int32),
    ("fc1_w", QB, np.int8), ("fc1_b", QA * QB, np.int32),
    ("fc2_w", OUT / QA, np.int8), ("fc2_b", OUT, np.int32),
)

_made: Dict[tuple, Dict[str, Dict[str, np.ndarray]]] = {}  # the last pair
clipped: Dict[str, int] = {}  # values the type's range clamped, by net


def quantize(w: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """One net of ``halfka.py``'s draw as a file stores it: → ({key:
    integers}, how many values their type's range clamped)."""
    out, n_clamped = {}, 0
    for key, scale, dtype in STORAGE:
        value = np.asarray(w[key], np.float64)
        if key == "fc1_w":  # [clipped, squared] -> the file's [squared, clipped]
            value = np.concatenate([value[..., HIDDEN:], value[..., :HIDDEN]], axis=-1)
        value = np.rint(value * scale)
        info = np.iinfo(dtype)
        n_clamped += int(np.sum((value < info.min) | (value > info.max)))
        out[key] = np.clip(value, info.min, info.max).astype(dtype)
    return out, n_clamped


def load_weights(engine_cfg: dict, root) -> Dict[str, Dict[str, np.ndarray]]:
    """{"big": integers, "small": integers}: ``halfka.py``'s draw of each
    {seed, l1}, quantized; the pair is kept, since the harness asks twice
    a run. ``clipped`` says how many values the integer types clamped."""
    spec = engine_cfg["weights"]
    key = tuple((int(spec[n]["seed"]), int(spec[n]["l1"])) for n in ("big", "small"))
    if key not in _made:
        _made.clear()
        pair = {}
        for name, k in zip(("big", "small"), key):
            pair[name], clipped[name] = quantize(halfka._draw(*k))
        _made[key] = pair
    return _made[key]


def _cdiv(a: int, b: int) -> int:
    """a / b toward zero."""
    q = abs(a) // abs(b)
    return q if (a >= 0) == (b > 0) else -q


def _inside(values, bits: int, what: str) -> None:
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    v = np.asarray(values)
    if v.min() < lo or v.max() > hi:
        raise OverflowError(f"{what} leaves int{bits}")


def net_value(w: Dict[str, np.ndarray], pos) -> int:
    """One net's integer value of a ``rules.Pos`` from the side to move's
    view (before the search's clamp)."""
    board, stm = pos.board, pos.stm
    occupied = [(sq, c) for sq, c in enumerate(board) if c]
    accs, psqts = [], []
    for persp in (0, 1):
        flip = 56 if persp else 0
        ksq = board.index(12 if persp else 6) ^ flip
        mirror = 7 if (ksq & 7) > 3 else 0
        ksq ^= mirror
        bucket = (ksq >> 3) * 4 + (ksq & 7)
        acc = w["ft_b"].astype(np.int64)
        psqt = np.zeros(halfka.STACKS, np.int64)
        for sq, code in occupied:
            pt = (code - 1) % 6
            col = 0 if code <= 6 else 1
            kind = 10 if pt == 5 else (pt if col == persp else 5 + pt)
            f = (bucket * halfka.PIECE_KINDS + kind) * halfka.SQUARES + (sq ^ flip ^ mirror)
            acc = acc + w["ft_w"][f]
            psqt = psqt + w["psqt_w"][f]
        _inside(acc, 16, "an accumulator value")
        _inside(psqt, 32, "a PSQT sum")
        accs.append(acc)
        psqts.append(psqt)
    own, opp = (0, 1) if stm == 0 else (1, 0)
    half = w["ft_b"].shape[0] // 2

    def pairwise(acc):
        c = np.clip(acc, 0, 127)
        return c[:half] * c[half:] // 128

    x = np.concatenate([pairwise(accs[own]), pairwise(accs[opp])])
    b = min(max((len(occupied) - 1) // 4, 0), halfka.STACKS - 1)
    y = w["fc0_w"][b].astype(np.int64) @ x + w["fc0_b"][b]
    _inside(y, 32, "an fc0 output")
    sqr = np.minimum(y[:HIDDEN] * y[:HIDDEN] >> 19, 127)
    act = np.clip(y[:HIDDEN] >> 6, 0, 127)
    z = w["fc1_w"][b].astype(np.int64) @ np.concatenate([sqr, act]) + w["fc1_b"][b]
    h = np.clip(z >> 6, 0, 127)
    out = int(w["fc2_w"][b][0].astype(np.int64) @ h) + int(w["fc2_b"][b][0])
    _inside(int(y[HIDDEN]) * OUT, 32, "the skip's product")
    skip = _cdiv(int(y[HIDDEN]) * OUT, QA * QB)
    psqt = _cdiv(int(psqts[own][b]) - int(psqts[opp][b]), 2)
    _inside(psqt + out + skip, 32, "the value's sum")
    return _cdiv(psqt + out + skip, 16)


def evaluate(w: Dict[str, Dict[str, np.ndarray]], pos) -> int:
    """Static eval of a ``rules.Pos`` from the side to move's view: the
    rule over both nets' integer values, clamped as the search clamps."""
    value = None
    if abs(material(pos)) > SMALLNET_MATERIAL:
        value = net_value(w["small"], pos)
        if abs(value) < SMALLNET_REEVAL:
            value = None
    if value is None:
        value = net_value(w["big"], pos)
    return max(-halfka.SCORE_CLAMP, min(halfka.SCORE_CLAMP, value))


def program_params(weights: Dict[str, Dict[str, np.ndarray]]):
    """What ``TpuEngine(params=...)`` takes for this pair: both nets in
    their integers, resident together, on the incremental path.

    A program without that (no integer net types, or a search with no
    integer scheme for them) would compute another arithmetic under this
    configuration's name, or nothing at all: it does not run the
    configuration, and is told so here, before anything is built."""
    from fishnet_tpu.models import nnue
    from fishnet_tpu.models import nnue_import

    pair = getattr(nnue_import, "DualNetInt", None)
    one = getattr(nnue_import, "StockfishNetInt", None)
    scheme = getattr(nnue, "acc_scheme", None)
    if pair is None or one is None or scheme is None:
        raise RuntimeError(
            "evaluator halfka_dual_int: this program holds no integer nets "
            "(fishnet_tpu.models.nnue_import.DualNetInt) to search with")
    params = pair.from_nets(*(one(**weights[n]) for n in ("big", "small")))
    if scheme(params) != "int":
        raise RuntimeError(
            "evaluator halfka_dual_int: this program's search has no "
            "integer scheme for the pair (fishnet_tpu.models.nnue.acc_scheme)")
    return params.as_device()


def net_work(shapes: Dict[str, int]) -> Dict[str, float]:
    """By ``work_count``'s rules, both nets as ``halfka.net_work`` counts
    one, in the types they are stored and computed in: a feature row's L1
    columns 2 bytes and its PSQT columns 4, a stack's weights 1 byte and
    its biases 4, the pairs read and written at those widths. The
    operations are counted as halfka's (integer multiply-adds as two).
    ``peaks.json`` has no int8 peak, so ``step.mfu_roofline_share`` holds
    the operations to the bf16 197 T: the bytes bound every cell today,
    so the share reads the same either way."""
    flops = bytes_ = 0.0
    fc0, fc1, psqt = shapes["fc0"], shapes["fc1"], shapes["psqt"]
    fc1_in = 2 * (fc0 - 1)
    for l1 in (shapes["l1"], shapes["l1_small"]):
        row = l1 + psqt
        flops += (2 * halfka.MAX_PIECE_CHANGES * row + l1
                  + 2 * (l1 * fc0 + fc1_in * fc1 + fc1) + (fc0 - 1))
        row_bytes = 2 * l1 + 4 * psqt
        bytes_ += (2 * halfka.MAX_PIECE_CHANGES * row_bytes
                   + (l1 * fc0 + fc1_in * fc1 + fc1)  # int8 weights
                   + 4 * (fc0 + fc1 + 1)  # int32 biases
                   + 2 * 2 * row_bytes)  # the pair read and written
    return {"flops": flops, "bytes": bytes_}


def acc_update_bytes(shapes: Dict[str, int]) -> int:
    """The least the row-gather-and-add of one node expansion moves: 4 rows
    a perspective of both nets' int16 L1 columns and int32 PSQT columns,
    and both pairs read and written — 12 rows of 3,200 x 2 + 16 x 4 =
    6,464 B at the published widths, 77,568 B: the bytes a roofline of
    the update holds its kernel to."""
    row = 2 * (shapes["l1"] + shapes["l1_small"]) + 4 * 2 * shapes["psqt"]
    return (2 * halfka.MAX_PIECE_CHANGES + 2 * 2) * row
