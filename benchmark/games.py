"""Games from the seed, and the planner's chunk tiling (the benchmark's copy).

A game is a legal playout from the variant's start position under a seeded
policy: every legal move is valued by the configuration's reference eval
(`evaluators/<name>.py::evaluate`) one ply deep and
one is drawn from a softmax over those values. No position repeats inside a
game (so no repetition history reaches the search), and no game ends before
its last ply. Analysis is node-budgeted, but a node budget does not fix the
lockstep steps an answer takes: over seeds the games moved that by 252-286
(PERF.md section 2, PR 34), and the rate with it. So a traffic file states
a `pool_seed`: the games are the same for every `--seed`, and the seed
deals them out in another order (`deal`) and draws the sample that is held
against the reference.
"""
from __future__ import annotations

import math
import random
from typing import List, Optional, Tuple

from . import rules

MAX_CHUNK_POSITIONS = 6  # upstream src/ipc.rs:23


def play_game(weights, evaluator, variant: str, plies: int,
              rng: random.Random, temperature_cp: float = 80.0) -> List[str]:
    """UCI moves of one playout of exactly `plies` plies."""
    while True:
        p = rules.start(variant)
        seen = {p.key()}
        moves: List[str] = []
        while len(moves) < plies:
            cands = []
            for mv in rules.legal_moves(p):
                child = rules.make(p, mv)
                if child.key() in seen:
                    continue
                cands.append((mv, child, -evaluator.evaluate(weights, child)))
            if not cands:
                break
            top = max(v for _m, _c, v in cands)
            weights_ = [math.exp((v - top) / temperature_cp) for _m, _c, v in cands]
            # draw without replacement until a move that does not end the game
            order = list(range(len(cands)))
            chosen = None
            while order:
                i = rng.choices(order, [weights_[j] for j in order])[0]
                order.remove(i)
                if rules.legal_moves(cands[i][1]):
                    chosen = cands[i]
                    break
            if chosen is None:
                break
            moves.append(rules.uci(chosen[0]))
            p = chosen[1]
            seen.add(p.key())
        if len(moves) == plies:
            return moves
        # the playout ran into a dead end: draw another (rare)


def make_games(weights, evaluator, variant: str, n_games: int, plies: int,
               seed: int) -> List[List[str]]:
    return [
        play_game(weights, evaluator, variant, plies,
                  random.Random(f"{seed}:{variant}:{g}"))
        for g in range(n_games)
    ]


def deal(n_games: int, seed: int) -> List[int]:
    """The order in which a run hands out a fixed pool's games."""
    order = list(range(n_games))
    random.Random(f"{seed}:deal").shuffle(order)
    return order


def tile(n_moves: int) -> List[List[Tuple[Optional[int], int]]]:
    """Chunks of one analysis job as the planner cuts them (upstream
    queue.rs:546-700, this repo's client/planner.py): positions 0..n_moves
    in reverse, groups of 5, each later group led by the previous group's
    last position as a warm-up whose answer is discarded (index None).
    Each entry is (position_index or None, number of moves played)."""
    positions = list(range(n_moves, -1, -1))
    prevs: List[Optional[int]] = [None] + positions[:-1]
    pairs = list(zip(prevs, positions))
    group = MAX_CHUNK_POSITIONS - 1
    chunks = []
    for start in range(0, len(pairs), group):
        chunk: List[Tuple[Optional[int], int]] = []
        for prev, cur in pairs[start:start + group]:
            if prev is not None and not chunk:
                chunk.append((None, prev))
            chunk.append((cur, cur))
        chunks.append(chunk)
    return chunks
