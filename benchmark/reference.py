"""The plain reference and the comparison that decides ``correct``.

What the timed path produced (the answers the window delivered, as plain
dicts made at the delivery point) is held against:

* ``delivery``     every position owed by a finished chunk answered exactly
                   once, under its own index and url (LaneScheduler);
* ``bad_lines``    answers whose depth, score table or lines are not what
                   the rules allow: a depth without a score, a line that is
                   not a legal line from the position, a best move that is
                   not the line's first move (compiled segment, PV table);
* ``d1_gap_cp``    widest gap between the served depth-1 score and the
                   reference's depth-1 value: one full-width ply plus the
                   capture-only quiescence with stand-pat that the search
                   defines, evaluated by the configuration's evaluator
                   (`evaluators/<name>.py`: plain numpy float32). Depth 1 has
                   no window, no reduction and no pruning, so the value does
                   not depend on move order or on what the shared table
                   holds, and any eval precision below float32 moves it;
* ``d1_move_gap_cp`` widest gap by which the served depth-1 move's value
                   lies below the reference's best (the reference's values);
* ``leaf_inexact_pct`` over every (answer, depth) line: the score a search
                   backs up along its principal line is the static eval of
                   that line's last position, so served score and reference
                   eval at the end of the served line agree to the
                   centipawn. The share of lines on which they do not covers
                   the deep search's bookkeeping (windows, re-searches, PV
                   copy) at every depth the budget reached. Lines cut short
                   by a table hit end above their leaf and read inexact in
                   sound runs too (15-30 %); single gaps there are large, so
                   the share is what is held, not a gap.

Two more numbers come from the harness, not from the answers:
``delivery`` above, and ``programs_inside``: programs built or loaded
inside the measured window, which must be none.

The reference imports nothing of the program.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

from . import rules

INF = 32500
STACK_PLIES = 32  # the search's static stack: deeper nodes are leaves


class Budget(Exception):
    pass


class Reference:
    def __init__(self, weights, evaluator, node_cap: int = 20000):
        """evaluator: the configuration's (`cells.load_evaluator`), whose
        `evaluate(weights, pos)` is the leaf eval."""
        self.w = weights
        self.evaluator = evaluator
        self.node_cap = node_cap
        self.nodes = 0

    def eval(self, p: rules.Pos) -> int:
        return self.evaluator.evaluate(self.w, p)

    def qs(self, p: rules.Pos, alpha: int, beta: int, ply: int) -> int:
        """Capture-only quiescence with a stand-pat floor; `p` is known to
        be a legal position (its mover did not leave the king en prise)."""
        self.nodes += 1
        if self.nodes > self.node_cap:
            raise Budget()
        if p.halfmove >= 100:
            return 0
        stand = self.eval(p)
        if ply >= STACK_PLIES or stand >= beta:
            return stand
        caps = rules.pseudo_moves(p, captures_only=True)
        if not caps:
            return stand
        best = stand
        alpha = max(alpha, stand)
        for mv in caps:
            if alpha >= beta:
                break
            child = rules.make(p, mv)
            if rules.mover_left_king_en_prise(child):
                continue
            v = -self.qs(child, -beta, -alpha, ply + 1)
            if v > best:
                best = v
            alpha = max(alpha, best)
        return best

    def depth1(self, p: rules.Pos) -> Dict[int, int]:
        """Value of every legal root move at depth 1, each with the full
        window (so each is exact)."""
        self.nodes = 1
        out = {}
        for mv in rules.pseudo_moves(p):
            child = rules.make(p, mv)
            if rules.mover_left_king_en_prise(child):
                continue
            out[mv] = -self.qs(child, -INF, INF, 1)
        return out


def replay(variant: str, moves: List[str]) -> Optional[rules.Pos]:
    p = rules.start(variant)
    for text in moves:
        mv = rules.parse_uci(p, text)
        if mv is None:
            return None
        p = rules.make(p, mv)
    return p


def walk_line(p: rules.Pos, line: List[str]) -> Optional[rules.Pos]:
    """Position at the end of `line`, or None if it is not a legal line."""
    for text in line:
        mv = rules.parse_uci(p, text)
        if mv is None:
            return None
        p = rules.make(p, mv)
    return p


def check_answer(ref: Reference, ans: dict) -> dict:
    """All readings of one answer. `ans` has variant, moves, depth,
    best_move, scores {depth: ("cp"|"mate", v)}, pvs {depth: [uci]}."""
    out = {"bad": [], "d1_gap": None, "d1_move_gap": None, "leaf_gaps": []}
    root = replay(ans["variant"], ans["moves"])
    if root is None:
        out["bad"].append("game prefix does not replay")
        return out
    depth = ans["depth"]
    if depth < 1:
        if rules.legal_moves(root):
            out["bad"].append("depth 0 on a position that has legal moves")
        return out
    scores, pvs = ans["scores"], ans["pvs"]
    for d in range(1, depth + 1):
        if d not in scores or d not in pvs:
            out["bad"].append(f"depth {d} of {depth} has no score or line")
    final = pvs.get(depth) or []
    if not final:
        out["bad"].append("no final line")
    elif ans["best_move"] is None or (
        rules.parse_uci(root, ans["best_move"])
        != rules.parse_uci(root, final[0])
    ):
        out["bad"].append("best move is not the final line's first move")
    for d, line in pvs.items():
        end = walk_line(root, line)
        if end is None:
            out["bad"].append(f"depth-{d} line is not legal: {' '.join(line)}")
            continue
        kind, val = scores.get(d, ("none", 0))
        if kind != "cp" or not line or end.halfmove >= 100:
            continue
        leaf = ref.eval(end)
        out["leaf_gaps"].append(abs(val - (leaf if len(line) % 2 == 0 else -leaf)))
    # depth 1 ran to its end only if the search went on to depth 2
    if depth >= 2 and scores.get(1, ("none", 0))[0] == "cp" and pvs.get(1):
        try:
            values = ref.depth1(root)
        except Budget:
            return out
        if values:
            best = max(values.values())
            out["d1_gap"] = abs(scores[1][1] - best)
            served = rules.parse_uci(root, pvs[1][0])
            if served in values:
                out["d1_move_gap"] = best - values[served]
    return out


def compare(ref: Reference, answers: List[dict], counted: Dict[str, int],
            limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict], dict]:
    """→ (correct, {name: {"value", "limit"}}, detail). `counted`: the
    numbers the harness counted itself (delivery, programs_inside)."""
    bad: List[str] = []
    d1, d1m, leaf = [], [], []
    worst = None
    for ans in answers:
        r = check_answer(ref, ans)
        bad += [f"{ans['id']}: {b}" for b in r["bad"]]
        if r["d1_gap"] is not None:
            d1.append(r["d1_gap"])
            if worst is None or r["d1_gap"] > worst[0]:
                worst = (r["d1_gap"], ans["id"])
        if r["d1_move_gap"] is not None:
            d1m.append(r["d1_move_gap"])
        leaf += r["leaf_gaps"]
    numbers = {
        **counted,
        "bad_lines": len(bad),
        "d1_gap_cp": max(d1) if d1 else None,
        "d1_move_gap_cp": max(d1m) if d1m else None,
        "leaf_inexact_pct": (100.0 * sum(1 for g in leaf if g) / len(leaf)
                             if leaf else None),
    }
    checks = {}
    correct = bool(answers)
    for name, limit in limits.items():
        value = numbers.get(name)
        # a number that could not be read (no answer deep enough) fails:
        # a run that answers nothing comparable proves nothing
        ok = value is not None and value <= limit
        correct = correct and ok
        checks[name] = {"value": value, "limit": limit}
    detail = {
        "answers": len(answers), "d1_compared": len(d1),
        "lines_compared": len(leaf),
        "leaf_gap_median": statistics.median(leaf) if leaf else None,
        "leaf_gap_p90": (sorted(leaf)[int(0.9 * (len(leaf) - 1))] if leaf else None),
        "d1_gap_mean": (sum(d1) / len(d1)) if d1 else None,
        "worst_d1": worst, "bad": bad[:5],
    }
    return correct, checks, detail
