"""Measure time-to-depth and node counts for the production search shape.

VERDICT r3 #2: `tpu_depth` defaults must be backed by a measured
depth × wall-clock × nodes table at the production program shape
(MAX_PLY=32 unless FISHNET_TPU_MAX_PLY trims it), not guesses. Run on
the TPU for times; on CPU the node counts are still exact (the lockstep
program is platform-deterministic) and wall-clock says nothing about the
device.

Usage:
  python tools/depth_table.py --depths 4,6,8 --lanes 256
  FISHNET_TPU_NO_PRUNING=1 python tools/depth_table.py ...   # A/B pruning
  python tools/depth_table.py --force-cpu ...                # node counts only
  python tools/depth_table.py --helpers 4 ...                # Lazy-SMP lanes

--helpers K > 1 replicates each root across K-1 extra lanes with perturbed
move ordering (ops/search.py order_jitter), sharing the TT with
depth-preferred stores — the engine's helper-lane configuration. The JSON
then counts primary lanes/nodes separately and reports lockstep steps,
the platform-independent cost proxy (equal widths ⇒ wall ∝ steps).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--depths", default="4,6,8")
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--budget", type=int, default=5_000_000)
    ap.add_argument("--max-ply", type=int, default=None,
                    help="default: engine MAX_PLY (32 in production)")
    ap.add_argument("--tt-log2", type=int, default=21)
    ap.add_argument("--helpers", type=int, default=1,
                    help="Lazy-SMP lanes per position (1 disables)")
    ap.add_argument("--force-cpu", action="store_true")
    args = ap.parse_args()

    if args.force_cpu:
        from tools import force_cpu  # noqa: F401

    import jax
    import numpy as np

    from fishnet_tpu.chess import Position
    from fishnet_tpu.engine.tpu import MAX_PLY
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import tt as tt_mod
    from fishnet_tpu.ops.board import from_position, stack_boards
    from fishnet_tpu.ops.search import _PRUNING, search_batch_resumable
    from fishnet_tpu.utils import enable_compile_cache

    enable_compile_cache()
    max_ply = args.max_ply or MAX_PLY
    platform = jax.default_backend()

    fens = [
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        "r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3",
        "r3k2r/p1ppqpb1/bn2pnp1/3PN3/1p2P3/2N2Q1p/PPPBBPPP/R3K2R w KQkq - 0 1",
        "rnbq1k1r/pp1Pbppp/2p5/8/2B5/8/PPP1NnPP/RNBQK2R w KQ - 1 8",
        "r4rk1/1pp1qppp/p1np1n2/2b1p1B1/2B1P1b1/P1NP1N2/1PP1QPPP/R4RK1 w - - 0 10",
        "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
        "4k3/8/8/8/8/8/4P3/4K3 w - - 0 1",
        "6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1",
    ]
    import jax.numpy as jnp

    B = args.lanes
    K = max(1, args.helpers)
    Bt = B * K
    boards = [from_position(Position.from_fen(fens[i % len(fens)]))
              for i in range(B)]
    # helper layout mirrors bench.py: primaries in rows [0, B), then K-1
    # replica blocks; row h*B + j helps primary j via the shared TT
    roots = stack_boards(boards * K)
    helper_kw = {}
    if K > 1:
        jit_arr = np.zeros(Bt, np.int32)
        for h in range(1, K):
            for j in range(B):
                jit_arr[h * B + j] = j * K + h  # nonzero ⇔ helper lane
        required = np.zeros(Bt, bool)
        required[:B] = True  # a depth is "done" when the primaries are
        helper_kw = dict(
            order_jitter=jnp.asarray(jit_arr),
            group=jnp.asarray(np.arange(Bt, dtype=np.int32) % B),
            required=required, prefer_deep_store=True, tt_gen=1,
        )
    from fishnet_tpu.assets import load_default_params

    params = load_default_params("board768") or nnue.init_params(
        jax.random.PRNGKey(0), l1=64, feature_set="board768"
    )
    tt = tt_mod.make_table(args.tt_log2) if args.tt_log2 else None

    for d in (int(x) for x in args.depths.split(",") if x):
        # fresh TT per depth so depths don't subsidize each other
        tt_d = tt_mod.make_table(args.tt_log2) if args.tt_log2 else None
        # warmup dispatch compiles the (Bt, max_ply) program
        out = search_batch_resumable(
            params, roots, 1, 64, max_ply=max_ply, tt=tt_d, **helper_kw,
        )
        out.pop("tt")
        jax.block_until_ready(out["nodes"])
        tt_d = tt_mod.make_table(args.tt_log2) if args.tt_log2 else None
        t0 = time.perf_counter()
        out = search_batch_resumable(
            params, roots, d, args.budget, max_ply=max_ply, tt=tt_d,
            max_steps=50_000_000, **helper_kw,
        )
        out.pop("tt")
        jax.block_until_ready(out["nodes"])
        wall = time.perf_counter() - t0
        nodes = int(np.asarray(out["nodes"]).sum())
        primary_nodes = int(np.asarray(out["nodes"])[:B].sum())
        print(json.dumps({
            "depth": d, "lanes": B, "helpers": K, "nodes": nodes,
            "primary_nodes": primary_nodes,
            "steps": int(out["steps"]),
            "wall_s": round(wall, 3), "nps": round(nodes / wall),
            "per_pos_nodes": primary_nodes // B,
            "platform": platform, "pruning": _PRUNING,
            "done": bool(np.asarray(out["done"])[:B].all()),
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
