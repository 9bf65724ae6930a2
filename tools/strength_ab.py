"""Head-to-head strength A/B: a board768 net (device search) vs PyEngine.

VERDICT r1 #8's acceptance check: the shipped net must beat the old one
head-to-head. This harness plays N games of (device search @ depth D)
against PyEngine (material+mobility, depth d) from varied short random
openings, alternating colors, and prints W/D/L + score.

All games play SIMULTANEOUSLY: each move cycle batches every live game
where it is the net's turn into one lockstep search dispatch (the same
lanes-are-cheap property the engine exploits), so N games cost ~one
game's worth of dispatches instead of N.

Usage:
  python tools/strength_ab.py --net fishnet_tpu/assets/nnue-board768-64.npz \
      --games 200 --depth 3
  python tools/strength_ab.py --net old.npz --label old ...   # compare runs
"""
from __future__ import annotations

import argparse
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", required=True)
    ap.add_argument("--opponent-net", default=None,
                    help="net-vs-net: the opponent plays device search "
                         "with THIS net (at --py-depth) instead of "
                         "PyEngine")
    ap.add_argument("--games", type=int, default=200)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--py-depth", type=int, default=2)
    ap.add_argument("--max-plies", type=int, default=160)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--label", default="net")
    ap.add_argument("--skill", type=int, default=None,
                    help="lichess move-job skill 1-8 for the NET side: "
                         "root moves become lanes and the engine's "
                         "weakness sampler picks (validates the skill "
                         "model, reference src/api.rs:248-283)")
    ap.add_argument("--opponent-skill", type=int, default=None,
                    help="same, for the opponent side (requires "
                         "--opponent-net for net-vs-net, or uses the "
                         "same net)")
    ap.add_argument("--device", action="store_true",
                    help="run on the real accelerator (default: force "
                         "CPU, the tool's historical mode — device runs "
                         "are ~50x faster per cycle)")
    ap.add_argument("--helpers", type=int, default=1,
                    help="Lazy-SMP helper lanes per game position for the "
                         "full-strength move dispatches (1 disables; "
                         "skill-sampled dispatches already decompose root "
                         "moves into lanes and ignore this)")
    args = ap.parse_args()

    if not args.device:
        from tools import force_cpu  # noqa: F401  (JAX_PLATFORMS=cpu)
    import numpy as np

    from fishnet_tpu.utils import enable_compile_cache

    enable_compile_cache()  # lane-bucket programs persist across runs

    from fishnet_tpu.chess import Position
    from fishnet_tpu.engine.pyengine import MATE_VALUE, PySearch
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops.board import from_position, stack_boards

    params = nnue.load_params(args.net)
    rng = random.Random(args.seed)

    def py_move(pos):
        s = PySearch()
        best, line = s.negamax(
            pos, args.py_depth, -MATE_VALUE * 2, MATE_VALUE * 2, 0
        )
        return line[0] if line else None

    from fishnet_tpu.engine.tpu import _decode_uci as decode_uci

    PAD = 16  # lane bucket granularity: few distinct compiled shapes

    # ONE lane shape for the whole match: per-cycle batch sizes shrink as
    # games finish, and every distinct width is a fresh XLA compile (plus
    # the round-5 narrowing path would compile its own widths per shape —
    # a first run of this tool spent ~an hour compiling instead of
    # playing). Dead lanes re-search boards[0]; a lockstep step costs the
    # same either way, so uniform width trades no real time for one
    # compile per (depth, max_ply).
    from fishnet_tpu.ops.search import search_batch_resumable
    from fishnet_tpu.ops import tt as tt_mod

    B0 = ((args.games + PAD - 1) // PAD) * PAD
    # Lazy-SMP helper lanes (engine/tpu.py layout): primaries in rows
    # [0, B0), then K-1 replica blocks — row h*B0 + r re-searches row r
    # with perturbed ordering through the side's shared TT. Still one
    # compiled shape per match; the picks come from primary rows only.
    K = max(1, args.helpers)
    helper_kw = {}
    if K > 1:
        import jax.numpy as jnp

        jit_arr = np.zeros(B0 * K, np.int32)
        for h in range(1, K):
            for r in range(B0):
                jit_arr[h * B0 + r] = r * K + h  # nonzero ⇔ helper lane
        helper_kw = dict(
            order_jitter=jnp.asarray(jit_arr),
            group=jnp.asarray(np.arange(B0 * K, dtype=np.int32) % B0),
            prefer_deep_store=True,
        )
    # one persistent TT per side, carried across move cycles (the engine
    # keeps one per process too): without it every move re-searches its
    # whole tree and a 160-game match costs ~an hour of device time
    side_tt = {}
    side_gen = {}  # per-side TT generation, bumped per dispatch (engine
    # parity: old-generation entries lose depth-preferred protection)

    def device_moves(positions, p=None, depth=None, side="net"):
        """One batched dispatch: best move per position (None on fail)."""
        if not positions:
            return []
        p = params if p is None else p
        depth = args.depth if depth is None else depth
        boards = [from_position(pos) for pos in positions]
        block = boards + [boards[0]] * (B0 - len(boards))
        roots = stack_boards(block * K)
        if side not in side_tt:
            side_tt[side] = tt_mod.make_table(21)
        kw = dict(helper_kw)
        if K > 1:
            side_gen[side] = (side_gen.get(side, 0) + 1) & 0x3FFFFFFF
            kw["tt_gen"] = side_gen[side]
            req = np.zeros(B0 * K, bool)
            req[: len(boards)] = True  # stop when the real games resolve
            kw["required"] = req
        out = search_batch_resumable(
            p, roots, depth, 500_000, max_ply=depth + 3, narrow=False,
            tt=side_tt[side], **kw,
        )
        side_tt[side] = out.pop("tt")
        ms = np.asarray(out["move"])[: len(boards)]
        return [decode_uci(int(m)) if int(m) >= 0 else None for m in ms]

    def device_moves_skill(positions, skill, p=None, depth=None, tag=""):
        """Move-job-style picks: each position's legal root moves become
        lanes (depth-1 search from the child), ranked, then sampled via
        the engine's skill_pick — the exact weakening path move jobs use
        (engine/tpu.py _move_job)."""
        if not positions:
            return []
        from fishnet_tpu.client.wire import SkillLevel
        from fishnet_tpu.engine.tpu import skill_pick

        p = params if p is None else p
        depth = args.depth if depth is None else depth
        sf_skill = SkillLevel(skill).engine_skill_level
        lane_pos, boards, legals = [], [], []
        for gi, pos in enumerate(positions):
            legal = pos.legal_moves()
            legals.append(legal)
            for m in legal:
                lane_pos.append(gi)
                boards.append(from_position(pos.push(m)))
        # power-of-two buckets (floor 256): root-move lane counts vary
        # every cycle and each distinct width is a fresh XLA compile, so
        # coarse pow2 padding keeps it to 1-2 programs per match; same
        # narrow=False + per-side persistent TT as device_moves
        B = 256
        while B < len(boards):
            B *= 2
        roots = stack_boards(boards + [boards[0]] * (B - len(boards)))
        skey = f"skill-{tag[:3]}-{B}"
        if skey not in side_tt:
            side_tt[skey] = tt_mod.make_table(21)
        out = search_batch_resumable(
            p, roots, max(depth - 1, 0), 500_000, max_ply=depth + 3,
            narrow=False, tt=side_tt[skey],
        )
        side_tt[skey] = out.pop("tt")
        scores = np.asarray(out["score"])
        picks = []
        k = 0
        for gi, legal in enumerate(legals):
            ranked = sorted(
                ((-int(scores[k + j]), j) for j in range(len(legal))),
                key=lambda t: (-t[0], t[1]),
            )
            k += len(legal)
            r = random.Random(f"{args.seed}:{tag}:{gi}:{len(legal)}")
            pick = skill_pick(ranked, sf_skill, r)
            picks.append(legal[pick[1]].uci())
        return picks

    opp_params = (
        nnue.load_params(args.opponent_net) if args.opponent_net else None
    )

    # set up all games, then advance them in lockstep cycles
    games = []
    for g in range(args.games):
        pos = Position.initial()
        for _ in range(rng.randrange(2, 6)):  # varied opening
            moves = pos.legal_moves()
            if not moves:
                break
            pos = pos.push(rng.choice(moves))
        games.append({"pos": pos, "net_color": g % 2, "plies": 0,
                      "result": None, "live": True})

    w = d = l = 0

    def settle(g, outcome):
        nonlocal w, d, l
        g["live"] = False
        g["result"] = outcome
        if outcome is None:
            d += 1
        elif outcome == g["net_color"]:
            w += 1
        else:
            l += 1

    cycle = 0
    while any(g["live"] for g in games):
        cycle += 1
        # terminal checks
        opp_turn = []
        for g in games:
            if not g["live"]:
                continue
            pos = g["pos"]
            oc = pos.outcome()
            if oc is not None:
                settle(g, oc[0])
                continue
            if g["plies"] >= args.max_plies or not pos.legal_moves():
                settle(g, None)
                continue
            if pos.turn != g["net_color"]:
                if opp_params is not None or args.opponent_skill is not None:
                    opp_turn.append(g)
                    continue
                uci = py_move(pos)  # host-side PyEngine reply
                if uci is None:
                    settle(g, None)
                    continue
                g["pos"] = pos.push_uci(uci)
                g["plies"] += 1
        # opponent device replies (net-vs-net / skill-vs-skill modes):
        # one batched dispatch
        if args.opponent_skill is not None:
            opp_ucis = device_moves_skill(
                [g["pos"] for g in opp_turn], args.opponent_skill,
                p=opp_params, depth=args.py_depth, tag=f"opp{cycle}",
            )
        else:
            opp_ucis = device_moves(
                [g["pos"] for g in opp_turn], p=opp_params,
                depth=args.py_depth, side="opp",
            )
        for g, uci in zip(opp_turn, opp_ucis):
            if uci is None:
                settle(g, None)
                continue
            g["pos"] = g["pos"].push_uci(uci)
            g["plies"] += 1
        # net replies: every live game at the net's turn, one dispatch
        net_turn = [
            g for g in games
            if g["live"] and g["pos"].outcome() is None
            and g["pos"].legal_moves() and g["pos"].turn == g["net_color"]
        ]
        if args.skill is not None:
            ucis = device_moves_skill(
                [g["pos"] for g in net_turn], args.skill, tag=f"net{cycle}",
            )
        else:
            ucis = device_moves([g["pos"] for g in net_turn], side="net")
        for g, uci in zip(net_turn, ucis):
            if uci is None:
                settle(g, None)
                continue
            g["pos"] = g["pos"].push_uci(uci)
            g["plies"] += 1
        if cycle % 5 == 0 or cycle <= 3:
            done = sum(1 for g in games if not g["live"])
            print(
                f"[{args.label}] cycle {cycle}: {done}/{args.games} games "
                f"done, +{w} ={d} -{l}",
                flush=True,
            )
    n = max(args.games, 1)
    score = (w + 0.5 * d) / n
    # Wilson 95% interval on the score fraction (draws as half-wins):
    # the standard interval for match results at these game counts
    z = 1.96
    mid = (score + z * z / (2 * n)) / (1 + z * z / n)
    half = (
        z * ((score * (1 - score) + z * z / (4 * n)) / n) ** 0.5
        / (1 + z * z / n)
    )
    print(
        f"[{args.label}] final: +{w} ={d} -{l} over {args.games} games, "
        f"score {score:.3f} (95% CI {mid - half:.3f}-{mid + half:.3f})"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
