"""Plain evaluation with both nets of a Stockfish deployment, in numpy
float32: the reference's eval for the configurations that name
``engine.evaluator`` = "halfka_dual".

The deployment, from its published description (lichess-org/fishnet
build.rs:8-9 embeds nn-1c0000000000.nnue, HalfKAv2_hm at L1 3,072, and
nn-37f18f62d772.nnue, the same layout at L1 128; the choice between them is
Stockfish's src/evaluate.cpp, ``Eval::use_smallnet`` and ``Eval::evaluate``,
with the piece values of src/types.h):

* ``m = 208 * (own pawns - their pawns) + (own non-pawn material -
  theirs)``, knight 781, bishop 825, rook 1276, queen 2538;
* where ``|m| > 962`` the small net evaluates; where its value ``v`` then
  has ``|v| < 236`` the big net evaluates the position again, and its value
  stands; elsewhere the big net evaluates;
* each net's value is ``evaluators/halfka.py::evaluate`` for that net's
  weights: ``(out + skip + psqt / 2) * 600``, truncated to an int, as
  Stockfish's integer Value is before it is compared.

No incremental update, no batching, nothing of the program. Both nets are
made from the seeds the configuration states (``engine.weights`` = {big:
{seed, l1}, small: {seed, l1}}) with ``halfka.py``'s draw, and kept here:
``halfka.py`` keeps one net, so drawing through it would make the 277 MB
net twice a run.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict

import numpy as np


def _halfka():
    """The one-net evaluator beside this file (the same mathematics)."""
    path = Path(__file__).with_name("halfka.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_evaluator_halfka_for_dual", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


halfka = _halfka()

PAWN_VALUE = 208
NON_PAWN = (781, 825, 1276, 2538)  # N B R Q
SMALLNET_MATERIAL = 962
SMALLNET_REEVAL = 236
# by piece code (0 empty, 1-6 white P N B R Q K, 7-12 black): white's side
MATERIAL = (0, PAWN_VALUE) + NON_PAWN + (0, -PAWN_VALUE) \
    + tuple(-v for v in NON_PAWN) + (0,)

_made: Dict[tuple, Dict[str, Dict[str, np.ndarray]]] = {}  # the last pair drawn


def load_weights(engine_cfg: dict, root) -> Dict[str, Dict[str, np.ndarray]]:
    """{"big": weights, "small": weights}, each as ``halfka.py`` draws a
    net from {seed, l1}; the pair is kept, since the harness asks twice a
    run."""
    spec = engine_cfg["weights"]
    key = tuple((int(spec[n]["seed"]), int(spec[n]["l1"])) for n in ("big", "small"))
    if key not in _made:
        _made.clear()
        _made[key] = {name: halfka._draw(*k) for name, k in zip(("big", "small"), key)}
    return _made[key]


def material(pos) -> int:
    """Stockfish's simple_eval from white's side; the rule reads its size."""
    return sum(MATERIAL[c] for c in pos.board)


def evaluate(w: Dict[str, Dict[str, np.ndarray]], pos) -> int:
    """Static eval of a ``rules.Pos`` in centipawns from the side to move's
    view, by the rule above; clamped as the search clamps it."""
    if abs(material(pos)) > SMALLNET_MATERIAL:
        value = halfka.evaluate(w["small"], pos)
        if abs(value) >= SMALLNET_REEVAL:
            return value
    return halfka.evaluate(w["big"], pos)


def program_params(weights: Dict[str, Dict[str, np.ndarray]]):
    """What ``TpuEngine(params=...)`` takes for this pair: both nets
    resident together, on the incremental path, choosing per node.

    A program without that (no type that holds both nets, or a search
    with no scheme for it) would evaluate something else under this
    configuration's name, or nothing at all: it does not run the
    configuration, and is told so here, before anything is built."""
    from fishnet_tpu.models import nnue
    from fishnet_tpu.models import nnue_import

    dual = getattr(nnue_import, "DualNet", None)
    scheme = getattr(nnue, "acc_scheme", None)
    if dual is None or scheme is None:
        raise RuntimeError(
            "evaluator halfka_dual: this program holds no pair of nets "
            "(fishnet_tpu.models.nnue_import.DualNet) to choose between")
    params = dual.from_nets(*(nnue_import.StockfishNet(**weights[n])
                              for n in ("big", "small")))
    if scheme(params) != "dual":
        raise RuntimeError(
            "evaluator halfka_dual: this program's search has no incremental "
            "scheme for the pair (fishnet_tpu.models.nnue.acc_scheme)")
    return params.as_device()


def _one_net(shapes: Dict[str, int], l1: int) -> Dict[str, int]:
    return dict(shapes, l1=l1)


def net_work(shapes: Dict[str, int]) -> Dict[str, float]:
    """By ``work_count``'s rules: what the program does at every node, both
    nets' update of their <= 4 rows a perspective and both nets' forward
    (each as ``halfka.net_work`` counts one net), from the configuration's
    ``net_shapes`` (``l1`` the big net's, ``l1_small`` the small one's)."""
    big = halfka.net_work(_one_net(shapes, shapes["l1"]))
    small = halfka.net_work(_one_net(shapes, shapes["l1_small"]))
    return {k: big[k] + small[k] for k in ("flops", "bytes")}


def acc_update_bytes(shapes: Dict[str, int]) -> int:
    """The least the row-gather-and-add of one node expansion moves: 4 rows
    a perspective of both nets' L1 + PSQT columns, and both pairs read and
    written. What ``metrics/nnue.dual_acc_update_roofline_share`` holds the
    kernel to."""
    return sum(halfka.acc_update_bytes(_one_net(shapes, shapes[key]))
               for key in ("l1", "l1_small"))
