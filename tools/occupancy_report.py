"""Replay a bench-like stage and report per-segment lane occupancy.

Continuous lane refill (ops/search.py search_stream, round 7) keeps the
compiled lockstep step at full width by resplicing DONE lanes with queued
positions at segment boundaries. This tool makes that claim inspectable:
it streams a multipv-style workload (more positions than lanes) through
search_stream and prints a per-segment table of live / helper / idle lane
counts plus the aggregate live-lane fraction — the same counters the
engine's LaneScheduler logs per session (engine/tpu.py occupancy_totals).

Usage:
  python tools/occupancy_report.py --lanes 192 --depth 6 --tt-log2 21
  python tools/occupancy_report.py --smoke            # fast CPU shape
  python tools/occupancy_report.py --format=github    # ::warning below threshold

--format=github emits a workflow warning annotation when the mean live
fraction falls below --threshold (default 0.5): sustained low occupancy
means the refill queue drained long before the stragglers finished, i.e.
the stage is paying full-width step cost for mostly-idle lanes.

Round 8 (segment pipeline): every row also shows the boundary's host
transfer count and host/device wall-clock split (utils/syncstats.py via
search_stream), and the summary line reports the aggregate boundary
share host_ms/(host_ms+device_ms). --host-share-threshold warns (a
::warning annotation under --format=github) when that share exceeds the
bound — the asynchronous boundary exists precisely to keep it small.

Round 10 (mesh parity): --mesh-ab runs the stage single-device and then
sharded over every local device (search_stream(mesh=make_mesh())) and
FAILS on any per-position result divergence — shard-local refill and the
stacked boundary summary must be bit-identical to the flat stream. The
TT is disabled for both passes when set (a sharded table hashes into
per-device shards, which legitimately changes move ordering). Sharded
rows grow a per-shard live-lane column and the JSON summary a per-shard
mean live fraction list.

Round 9 (session recovery): --stats-db PATH reads the client's sqlite
stats store and prepends the latest SupervisorStats snapshot (replay /
bisection / quarantine counters, exported by the client's summary loop)
plus the persisted quarantine list — one line per poison fingerprint.
--stats-only prints that report and exits without importing JAX or
running the occupancy stage, so it works on a machine with no
accelerator at all.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _recovery_report(db_path: str, emit_json: bool) -> int:
    """Print the latest persisted SupervisorStats + quarantine list."""
    import sqlite3

    if not os.path.exists(db_path):
        print(f"recovery: no stats db at {db_path}")
        return 1
    con = sqlite3.connect(db_path)
    try:
        try:
            row = con.execute(
                "SELECT timestamp, counters FROM supervisor_stats "
                "ORDER BY id DESC LIMIT 1"
            ).fetchone()
            quarantine = con.execute(
                "SELECT timestamp, fingerprint, batch_id, position_index "
                "FROM supervisor_quarantine ORDER BY id"
            ).fetchall()
        except sqlite3.Error as e:
            print(f"recovery: stats db has no supervisor tables ({e})")
            return 1
    finally:
        con.close()

    if row is None:
        print("recovery: no SupervisorStats snapshot recorded yet")
        counters = {}
    else:
        counters = json.loads(row[1])
        print(f"recovery: SupervisorStats at {row[0]}")
        for key in sorted(counters):
            print(f"  {key:>20} {counters[key]}")
    print(f"quarantine: {len(quarantine)} poison position(s)")
    for ts, fp, batch, idx in quarantine:
        print(f"  {fp}  batch={batch} index={idx}  at {ts}")
    if emit_json:
        print("RECOVERY " + json.dumps({
            "counters": counters,
            "quarantine": [
                {"fingerprint": fp, "batch_id": batch, "position_index": idx}
                for _, fp, batch, idx in quarantine
            ],
        }))
    return 0


def _boards(lanes: int, variant: str, cap: int | None = None):
    """Every root-move board of the standard 8-FEN set (the production
    multipv workload, 229 boards), tiled up if --lanes exceeds it —
    the report needs MORE positions than lanes to exercise refill.
    `cap` (the --smoke path) truncates the queue so CI pays for a
    handful of refills, not the full production drain."""
    from bench import FENS_STANDARD
    from fishnet_tpu.chess import Position
    from fishnet_tpu.ops.board import from_position, stack_boards

    boards = []
    for fen in FENS_STANDARD:
        p = Position.from_fen(fen)
        for m in p.legal_moves():
            boards.append(from_position(p.push(m)))
    floor = lanes + max(lanes // 4, 2)
    while len(boards) < floor:
        boards.append(boards[len(boards) % 229])
    if cap is not None:
        boards = boards[: max(cap, floor)]
    return stack_boards(boards), len(boards)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=192)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--budget", type=int, default=5_000_000)
    ap.add_argument("--segment", type=int, default=None,
                    help="segment steps (default: FISHNET_TPU_SEGMENT)")
    ap.add_argument("--max-ply", type=int, default=32)
    ap.add_argument("--tt-log2", type=int, default=21)
    ap.add_argument("--net", choices=("random", "default"), default="default")
    ap.add_argument("--threshold", type=float, default=0.5,
                    help="annotate when mean live fraction is below this")
    ap.add_argument("--host-share-threshold", type=float, default=0.25,
                    help="annotate when the boundary host share "
                         "host_ms/(host_ms+device_ms) exceeds this")
    ap.add_argument("--mesh-ab", action="store_true",
                    help="run the stage single-device then sharded over "
                         "all local devices (TT disabled for both); FAIL "
                         "on any result divergence")
    ap.add_argument("--format", choices=("text", "github"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="print a machine-readable summary line")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU shape for CI (8 lanes, depth 2, toy net)")
    ap.add_argument("--stats-db", default=None, metavar="PATH",
                    help="prepend the latest SupervisorStats snapshot and "
                         "quarantine list from this client stats sqlite db")
    ap.add_argument("--stats-only", action="store_true",
                    help="with --stats-db: print the recovery report and "
                         "exit without running the occupancy stage")
    args = ap.parse_args()

    if args.stats_db is not None:
        rc = _recovery_report(args.stats_db, args.json)
        if args.stats_only:
            return rc

    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        args.lanes, args.depth, args.max_ply = 8, 2, 6
        args.budget, args.tt_log2, args.net = 50_000, 0, "random"
        # segments must be shorter than a single toy search or every
        # position finishes inside segment 1 and the live fraction reads
        # as pure idle — 48 steps gives the smoke a real refill cadence.
        # The straggler drain tail dominates a 10-position queue, so the
        # production threshold would warn on every smoke run; the smoke
        # gate is completion + accounting, not toy-shape occupancy
        args.segment = args.segment or 48
        args.threshold = min(args.threshold, 0.3)

    import jax
    import numpy as np

    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.utils import enable_compile_cache

    enable_compile_cache()
    if args.net == "default":
        from fishnet_tpu.assets import load_default_params

        params = load_default_params("board768")
        if params is None:
            raise RuntimeError("packaged net missing; use --net=random")
    else:
        params = nnue.init_params(
            jax.random.PRNGKey(0), l1=64, feature_set="board768")

    roots, n = _boards(args.lanes, "standard",
                       cap=(args.lanes + max(args.lanes // 4, 2)
                            if args.smoke else None))
    depth = np.full(n, args.depth, np.int32)
    budget = np.full(n, args.budget, np.int32)

    mesh = None
    if args.mesh_ab:
        from fishnet_tpu.parallel.mesh import make_mesh

        ndev = jax.device_count()
        if args.lanes % ndev:
            print(f"ERROR: --mesh-ab needs --lanes divisible by the "
                  f"{ndev} local devices")
            return 1
        mesh = make_mesh()
        if args.tt_log2:
            # a sharded table hashes into per-device shards; that
            # legitimately reorders moves, so the A/B drops the TT
            print("mesh A/B: TT disabled for both passes "
                  "(sharded vs flat tables hash differently)")

    def run(on_mesh=None):
        # the table (and the running state) are DONATED into the segment
        # jits, so every pass gets its own fresh table
        tt = None
        if args.tt_log2 and not args.mesh_ab:
            from fishnet_tpu.ops import tt as tt_mod

            tt = tt_mod.make_table(args.tt_log2)
        t0 = time.perf_counter()
        out = S.search_stream(
            params, roots, depth, budget, max_ply=args.max_ply,
            width=args.lanes, segment_steps=args.segment, tt=tt,
            mesh=on_mesh,
        )
        jax.block_until_ready(out["nodes"])
        return out, time.perf_counter() - t0

    out, wall = run(on_mesh=mesh)
    flat_base = run() if args.mesh_ab else None

    # ops-level rows: {segment, steps, live, refilled, idle, queue} plus
    # the round-8 syncstats columns {transfers, host_ms, device_ms}
    # (the engine's LaneScheduler adds helper counts on top of these)
    occ = out["occupancy"]
    lane_steps = sum(o["steps"] * args.lanes for o in occ) or 1
    live_steps = sum(o["steps"] * (o["live"] + o["refilled"]) for o in occ)
    mean_live = live_steps / lane_steps
    host_ms = sum(o["host_ms"] for o in occ)
    device_ms = sum(o["device_ms"] for o in occ)
    boundary_share = host_ms / max(host_ms + device_ms, 1e-9)
    transfers = sum(o["transfers"] for o in occ)
    done = int(np.asarray(out["done"]).sum())

    has_shard = bool(occ) and "shard_live" in occ[0]
    shard_hdr = f" {'shard live':>18}" if has_shard else ""
    print(f"{'seg':>4} {'steps':>6} {'live':>5} {'idle':>5} "
          f"{'refill':>6} {'queue':>5} {'xfers':>5} {'host_ms':>8} "
          f"{'dev_ms':>8} {'share':>6}{shard_hdr}")
    for o in occ:
        tot = o["host_ms"] + o["device_ms"]
        share = o["host_ms"] / tot if tot > 0 else 0.0
        shard_col = ""
        if has_shard:
            shard_col = " " + ",".join(str(x) for x in o["shard_live"])
        print(f"{o['segment']:>4} {o['steps']:>6} {o['live']:>5} "
              f"{o['idle']:>5} {o['refilled']:>6} {o['queue']:>5} "
              f"{o['transfers']:>5} {o['host_ms']:>8.2f} "
              f"{o['device_ms']:>8.2f} {share:>6.3f}{shard_col}")
    print(f"positions {done}/{n} done, width {args.lanes}, "
          f"{len(occ)} segments, {out['refills']} refills, "
          f"mean live fraction {mean_live:.3f}, "
          f"boundary share {boundary_share:.3f} "
          f"({transfers} transfers), wall {wall:.2f}s")
    if args.json:
        summary = {
            "lanes": args.lanes, "positions": n, "done": done,
            "segments": len(occ), "refills": out["refills"],
            "mean_live_frac": round(mean_live, 4),
            "host_ms": round(host_ms, 1),
            "device_ms": round(device_ms, 1),
            "boundary_share": round(boundary_share, 4),
            "transfers": transfers,
            "wall_s": round(wall, 3),
        }
        if has_shard:
            ndev = len(occ[0]["shard_live"])
            local = args.lanes // ndev
            denom = sum(o["steps"] * local for o in occ) or 1
            summary["ndev"] = ndev
            summary["shard_mean_live"] = [
                round(sum(o["steps"] * o["shard_live"][s] for o in occ)
                      / denom, 4)
                for s in range(ndev)
            ]
        print("OCCUPANCY " + json.dumps(summary))

    if flat_base is not None:
        fout, fwall = flat_base
        diverged = []
        for key in ("score", "move", "nodes", "pv_len", "pv", "done"):
            if not np.array_equal(np.asarray(fout[key]),
                                  np.asarray(out[key])):
                diverged.append(key)
        print(f"mesh A/B: single-device {fwall:.2f}s / sharded "
              f"{wall:.2f}s over {mesh.devices.size} devices")
        if diverged:
            msg = (f"sharded results diverge from the single-device "
                   f"stream on: {', '.join(diverged)} — shard-local "
                   "refill must be bit-identical")
            if args.format == "github":
                print(f"::error title=mesh-ab divergence::{msg}")
            else:
                print(f"ERROR: {msg}")
            return 1

    if done < n:
        msg = (f"only {done}/{n} positions finished — raise --budget or "
               f"lower --depth")
        if args.format == "github":
            print(f"::error title=occupancy-report incomplete::{msg}")
        else:
            print(f"ERROR: {msg}")
        return 1
    if mean_live < args.threshold:
        msg = (f"mean live lane fraction {mean_live:.3f} below threshold "
               f"{args.threshold} — the refill queue drained long before "
               f"the stragglers finished")
        if args.format == "github":
            print(f"::warning title=occupancy-report::{msg}")
        else:
            print(f"WARNING: {msg}")
    if boundary_share > args.host_share_threshold:
        msg = (f"boundary host share {boundary_share:.3f} exceeds "
               f"{args.host_share_threshold} — the host is stalling the "
               "device at segment boundaries; shrink the boundary work "
               "or raise FISHNET_TPU_SEGMENT")
        if args.format == "github":
            print(f"::warning title=occupancy-report host-share::{msg}")
        else:
            print(f"WARNING: {msg}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
