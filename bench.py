"""Benchmark: batched alpha-beta + NNUE nodes/sec on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The north-star metric (BASELINE.md) is nodes/sec/chip on a 256-position
batch. vs_baseline divides by the reference client's own per-core NPS
scheduling prior (400 knps, reference: src/stats.rs:203-214) × host cores —
the documented proxy for "Stockfish-AVX2 on the same host" since this image
bundles no Stockfish binary to measure directly.

Hang-proofing (round-2 lesson: a device-side hang starved the in-process
ramp and the artifact recorded nothing): every stage runs in its OWN
subprocess with its own wall-clock timeout, and streams timestamped
phase heartbeats (compile_start / compile_done / exec segments) to stderr
so a recorded tail localizes any hang to compile vs run. A stage that
dies never takes the harness down. Every RESULT row names the device it
ran on (platform, device_kind, device_count); with no device to measure,
or no stage that ran, the exit is non-zero with the reason on stderr —
there is no CPU fallback.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# (lanes, depth) ramp: known-good shapes first (docs/tpu-hang.md bisection),
# so small real numbers are on record before the north-star shape — which is
# attempted last because a device hang there costs the later stages.
# (64,3)/(128,3)/(256,3) middle shapes added in round 5 (VERDICT r4 weak #2:
# the round-4 ramp had no middle shape, so when (256,4) died the recorded
# headline under-reported the same session's matrix numbers by ~3x)
STAGES = [(8, 2), (64, 2), (64, 3), (128, 3), (256, 3), (256, 4),
          (512, 3), (1024, 3)]

# Device stages run with FISHNET_TPU_SELECT_UPDATES=1 FIRST: the round-3
# bisection (docs/tpu-hang.md) pinned the B>=16/max_ply>=4 hang/worker-crash
# on a suspected miscompiled scatter, and the one-hot select mode is the
# CPU-proven candidate fix. A stage that dies in select mode is retried once
# in the default scatter mode, so the artifact records which compile path
# (if any) works on the hardware.
SELECT_FIRST = os.environ.get("BENCH_SELECT_FIRST", "1") != "0"


def _hb(t0: float, msg: str) -> None:
    # shared phase-heartbeat formatter: the engine supervisor's child host
    # (engine/host.py) emits the same scheme over its pipe protocol
    from fishnet_tpu.utils.heartbeat import stamp

    stamp(t0, msg, tag="bench")


# BASELINE.md benchmark-config position sets
FENS_STANDARD = [
    "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
    "r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3",
    "r3k2r/p1ppqpb1/bn2pnp1/3PN3/1p2P3/2N2Q1p/PPPBBPPP/R3K2R w KQkq - 0 1",
    "rnbq1k1r/pp1Pbppp/2p5/8/2B5/8/PPP1NnPP/RNBQK2R w KQ - 1 8",
    "r4rk1/1pp1qppp/p1np1n2/2b1p1B1/2B1P1b1/P1NP1N2/1PP1QPPP/R4RK1 w - - 0 10",
    "8/2p5/3p4/KP5r/1R3p1k/8/4P1P1/8 w - - 0 1",
    "4k3/8/8/8/8/8/4P3/4K3 w - - 0 1",
    "6k1/5ppp/8/8/8/8/5PPP/3R2K1 w - - 0 1",
]
# Chess960 starting arrays (X-FEN; castling via rook files — the device
# castling rows store rook squares, so FRC is the same compiled program)
FENS_960 = [
    "bqnbrkrn/pppppppp/8/8/8/8/PPPPPPPP/BQNBRKRN w KQkq - 0 1",
    "nrbqkbrn/pppppppp/8/8/8/8/PPPPPPPP/NRBQKBRN w KQkq - 0 1",
    "rkbnnbqr/pppppppp/8/8/8/8/PPPPPPPP/RKBNNBQR w KQkq - 0 1",
    "qrknnrbb/pppppppp/8/8/8/8/PPPPPPPP/QRKNNRBB w KQkq - 0 1",
]
FENS_VARIANT = {
    "crazyhouse": [
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR[] w KQkq - 0 1",
        "rnb1kbnr/ppp1pppp/8/3p4/3P4/8/PPPqPPPP/RNBQKBNR[Pp] w KQkq - 0 4",
    ],
    "threeCheck": [
        "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1",
        "r1bqkbnr/pppp1ppp/2n5/4p3/2B1P3/5N2/PPPP1PPP/RNBQK2R b KQkq - 3 3",
    ],
}


def _roots_for(B: int, variant: str, fen_set: str):
    """B lane roots (+ multipv lane table when fen_set == 'multipv')."""
    from fishnet_tpu.chess import Position
    from fishnet_tpu.chess.variants import from_fen
    from fishnet_tpu.ops.board import from_position, stack_boards

    if fen_set == "960":
        fens = FENS_960
    elif fen_set == "variant":
        fens = FENS_VARIANT[variant]
    else:
        fens = FENS_STANDARD
    if variant == "standard":
        positions = [Position.from_fen(f) for f in fens]
    else:
        positions = [from_fen(f, variant) for f in fens]
    if fen_set == "multipv":
        # BASELINE config 3: every legal root move of every position
        # becomes a lane — the engine's multipv decomposition
        boards = []
        for p in positions:
            for m in p.legal_moves():
                boards.append(from_position(p.push(m)))
        boards = boards[:B]
        return stack_boards(boards + [boards[0]] * (B - len(boards)))
    return stack_boards(
        [from_position(positions[i % len(positions)]) for i in range(B)]
    )


def _all_boards_for(B: int, variant: str, fen_set: str):
    """The UNTRUNCATED workload for the refill comparison: every
    root-move board of the multipv decomposition (229 for the standard
    8-FEN set), or the fen set tiled to 2*B positions otherwise — more
    positions than lanes is the regime continuous refill exists for."""
    from fishnet_tpu.chess import Position
    from fishnet_tpu.chess.variants import from_fen
    from fishnet_tpu.ops.board import from_position, stack_boards

    if fen_set == "960":
        fens = FENS_960
    elif fen_set == "variant":
        fens = FENS_VARIANT[variant]
    else:
        fens = FENS_STANDARD
    if variant == "standard":
        positions = [Position.from_fen(f) for f in fens]
    else:
        positions = [from_fen(f, variant) for f in fens]
    if fen_set == "multipv":
        boards = []
        for p in positions:
            for m in p.legal_moves():
                boards.append(from_position(p.push(m)))
    else:
        boards = [
            from_position(positions[i % len(positions)])
            for i in range(2 * B)
        ]
    return stack_boards(boards), len(boards)


def _bench_refill(t0: float, params, B: int, depth: int, budget: int,
                  variant: str, fen_set: str, max_ply: int, tt,
                  stream: bool, mode: str, device: dict,
                  tt_log2: int, bench_dtype: str, mesh=None) -> None:
    """Refill A/B stage (ISSUE 4): positions_done_per_s over the SAME
    N-position workload at the SAME width B — chunk-serial width-B
    batches drained one after another (stream=False, the
    `_go_multiple_locked` regime) vs one full-width program whose DONE
    lanes are respliced with queued positions at segment boundaries
    (stream=True, ops/search.py search_stream). Occupancy counters land
    in the RESULT JSON either way.

    mesh (BENCH_MESH, round 10): both passes run sharded over the mesh
    devices — serial through search_batch_resumable(mesh=...), streamed
    through search_stream(mesh=...) with shard-local refill — and the
    stream summary grows per-shard mean live fractions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fishnet_tpu.ops import search as S

    seg = int(os.environ.get("BENCH_SEG", "1024"))
    roots, N = _all_boards_for(B, variant, fen_set)
    depth_all = np.full(N, depth, np.int32)
    budget_all = np.full(N, budget, np.int32)
    _hb(t0, f"refill stage: N={N} positions, width={B}, "
            f"mode={'stream' if stream else 'serial'}")

    def serial_pass(tt):
        """ceil(N/B) strictly-serial width-B dispatches; the last batch
        runs mostly padding — exactly the chunk-drain waste refill
        removes."""
        done = 0
        nodes = 0
        for lo in range(0, N, B):
            idx = np.arange(lo, min(lo + B, N))
            pad = np.concatenate([idx, np.full(B - idx.size, idx[0])])
            batch = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)[pad]), roots)
            d_arr = np.where(np.arange(B) < idx.size, depth, 0)
            b_arr = np.where(np.arange(B) < idx.size, budget, 0)
            out = S.search_batch_resumable(
                params, batch,
                d_arr.astype(np.int32), b_arr.astype(np.int32),
                max_ply=max_ply, segment_steps=seg, tt=tt,
                variant=variant, mesh=mesh,
            )
            tt = out.pop("tt")
            jax.block_until_ready(out["nodes"])
            done += int(np.asarray(out["done"])[: idx.size].sum())
            nodes += int(np.asarray(out["nodes"])[: idx.size].sum())
        return done, nodes, tt, None

    def stream_pass(tt):
        out = S.search_stream(
            params, roots, depth_all, budget_all, max_ply=max_ply,
            width=B, segment_steps=seg, tt=tt, variant=variant,
            mesh=mesh,
        )
        jax.block_until_ready(out["nodes"])
        done = int(np.asarray(out["done"]).sum())
        nodes = int(np.asarray(out["nodes"]).sum())
        occ = out["occupancy"]
        lane_steps = sum(o["steps"] * B for o in occ) or 1
        live_steps = sum(o["steps"] * o["live"] for o in occ)
        host_ms = sum(o["host_ms"] for o in occ)
        device_ms = sum(o["device_ms"] for o in occ)
        summary = {
            "segments": len(occ),
            "refills": out["refills"],
            "mean_live_frac": round(live_steps / lane_steps, 4),
            # the host/device wall-clock split of every boundary
            # interval and the transfer count (utils/syncstats.py via
            # search_stream)
            "host_ms": round(host_ms, 1),
            "device_ms": round(device_ms, 1),
            "boundary_share": round(
                host_ms / max(host_ms + device_ms, 1e-9), 4),
            "transfers": sum(o["transfers"] for o in occ),
        }
        if mesh is not None:
            # per-shard mean live fraction (shard_live columns from
            # search_stream's mesh occupancy rows): imbalance here means
            # the most-free-shard admission policy is not keeping up
            ndev = mesh.devices.size
            local = B // ndev
            denom = sum(o["steps"] * local for o in occ) or 1
            summary["ndev"] = ndev
            summary["shard_mean_live"] = [
                round(sum(o["steps"] * o["shard_live"][s] for o in occ)
                      / denom, 4)
                for s in range(ndev)
            ]
        return done, nodes, out["tt"], summary

    run = stream_pass if stream else serial_pass
    from fishnet_tpu.obs import trace

    refill_mode = "stream" if stream else "serial"
    _hb(t0, "exec_start warmup pass (compiles all programs)")
    with trace.span("bench.warmup", "bench", mode=refill_mode, B=B, N=N):
        done, nodes, tt, occ = run(tt)
    _hb(t0, f"exec_done warmup (done={done}/{N})")
    _hb(t0, "exec_start timed pass")
    t1 = time.perf_counter()
    with trace.span("bench.search", "bench", mode=refill_mode, B=B, N=N):
        done, nodes, tt, occ = run(tt)
    dt = time.perf_counter() - t1
    _hb(t0, f"exec_done timed: done={done}/{N}, {nodes:,} nodes in {dt:.2f}s")
    print(
        "RESULT "
        + json.dumps({
            "nps": nodes / dt,
            "B": B,
            "depth": depth,
            "nodes": nodes,
            "dt": dt,
            **device,
            "variant": variant,
            "fen_set": fen_set,
            "row_mode": mode,
            "max_ply": max_ply,
            "positions": N,
            "positions_done": done,
            "positions_done_per_s": round(done / dt, 1),
            "refill": "stream" if stream else "serial",
            "mesh": 0 if mesh is None else int(mesh.devices.size),
            "occupancy": occ,
            "net": os.environ.get("BENCH_NET", "random"),
            "dtype": bench_dtype or "f32",
            "tt_log2": tt_log2,
        }),
        flush=True,
    )
    rec = trace.RECORDER
    if rec is not None:
        path = rec.flight_dump(
            settings.get_str("FISHNET_TPU_TRACE_DIR"),
            f"bench-refill-{'stream' if stream else 'serial'}-b{B}",
        )
        _hb(t0, f"trace dumped to {path}")


def stage_main(B: int, depth: int, budget: int, variant: str = "standard",
               fen_set: str = "standard") -> None:
    """Child process: run one (B, depth) stage with phase heartbeats.

    On success prints exactly one stdout line: RESULT {json}."""
    from fishnet_tpu.obs import trace
    from fishnet_tpu.utils import settings

    # phase transitions go through the shared recorder (off unless
    # FISHNET_TPU_TRACE_DIR is set), so a bench run produces the same
    # Chrome-trace timeline as the engine — not just stderr stamps
    rec = trace.install_from_settings("bench")
    t0 = time.monotonic()
    mode = ("select" if settings.get_bool("FISHNET_TPU_SELECT_UPDATES")
            else "scatter")
    _hb(t0, f"stage B={B} depth={depth} variant={variant} set={fen_set} "
            f"row_mode={mode}: importing jax")
    with trace.span("bench.import_jax", "bench"):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from fishnet_tpu.utils import enable_compile_cache

        enable_compile_cache()
        # every RESULT row names the device it ran on
        device = {
            "platform": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
        }
    _hb(t0, f"devices={jax.devices()} platform={device['platform']}")

    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S

    roots = _roots_for(B, variant, fen_set)
    # stage knobs (inherited via env by the stage subprocess):
    #   BENCH_NET=default  → the packaged trained net (production weights)
    #   BENCH_DTYPE=bf16|int8 → quantized eval path (SURVEY §7.2)
    #   BENCH_MAX_PLY=N    → production stack height (default: depth+1 toy)
    bench_net = os.environ.get("BENCH_NET", "")
    if bench_net == "default":
        from fishnet_tpu.assets import load_default_params

        params = load_default_params("board768")
        if params is None:
            raise RuntimeError("packaged net missing")
    elif bench_net in ("", "random"):
        params = nnue.init_params(
            jax.random.PRNGKey(0), l1=64, feature_set="board768"
        )
    else:
        # a typo'd net name must not record a random-weights run under a
        # trained-net label (same fail-loudly rule as BENCH_DTYPE below)
        raise RuntimeError(f"unknown BENCH_NET {bench_net!r}")
    bench_dtype = os.environ.get("BENCH_DTYPE", "").lower()
    if bench_dtype in ("bf16", "bfloat16"):
        params = nnue.cast_params(params, jnp.bfloat16)
    elif bench_dtype == "int8":
        # retired after round 5 measured it at 37.2 knps vs 58-95 knps f32
        # (docs/profile-r5.md) — the engine gates the same path behind
        # FISHNET_TPU_EXPERIMENTAL_INT8 now; fail loudly rather than
        # record a number for a config production refuses to run
        raise RuntimeError("BENCH_DTYPE=int8 retired: measured slower than f32")
    elif bench_dtype not in ("", "f32", "float32"):
        # a typo'd dtype must not silently record an f32 run under the
        # wrong label — these artifacts are the round's perf record
        raise RuntimeError(f"unknown BENCH_DTYPE {bench_dtype!r}")
    max_ply = int(os.environ.get("BENCH_MAX_PLY", str(depth + 1)))
    # BENCH_HELPERS=K > 1: Lazy-SMP layout. The B fen-set lanes become the
    # PRIMARIES (rows [0, B)); K-1 replica blocks follow, so helper row
    # h*B + j re-searches primary j's root with perturbed move ordering
    # (ops/search.py order_jitter), sharing work only through the TT.
    # positions_done_per_s counts primaries only — helpers are the means,
    # not the deliverable — while nps keeps counting every lane (it is a
    # machine-throughput number).
    helpers = max(1, int(os.environ.get("BENCH_HELPERS", "1")))
    Bt = B * helpers
    order_jitter = None
    group = None
    required = None
    if helpers > 1:
        roots = jax.tree.map(
            lambda a: jnp.concatenate([a] * helpers, axis=0), roots)
        jit_arr = np.zeros(Bt, np.int32)
        grp_arr = np.arange(Bt, dtype=np.int32) % B
        for h in range(1, helpers):
            for j in range(B):
                jit_arr[h * B + j] = j * helpers + h  # nonzero ⇔ helper
        order_jitter = jnp.asarray(jit_arr)
        group = jnp.asarray(grp_arr)
        required = np.zeros(Bt, bool)
        required[:B] = True  # stop the moment every primary is DONE
    depth_arr = jnp.full((Bt,), depth, jnp.int32)
    budget_arr = jnp.full((Bt,), budget, jnp.int32)
    prefer_deep = helpers > 1
    tt_gen = 1 if helpers > 1 else 0

    # BENCH_MESH set → the refill A/B stage runs sharded over every local
    # device (shard-local refill, stacked boundary summaries); B must
    # divide over the devices. Only meaningful with BENCH_REFILL — the
    # lockstep single-batch stage below stays single-device
    mesh = None
    if os.environ.get("BENCH_MESH", "") not in ("", "0", "false", "no"):
        from fishnet_tpu.parallel.mesh import make_mesh

        mesh = make_mesh()
        if B % mesh.devices.size:
            raise RuntimeError(
                f"BENCH_MESH: width {B} must divide over "
                f"{mesh.devices.size} devices")
        _hb(t0, f"mesh: {mesh.devices.size} devices")

    # optional shared transposition table (BENCH_TT_LOG2=21 etc.); off by
    # default so the metric stays a raw search-throughput number. Mesh
    # stages take the per-device sharded table instead (each device
    # hashes into its private shard)
    tt = None
    tt_log2 = int(os.environ.get("BENCH_TT_LOG2", "0"))
    if tt_log2:
        if mesh is not None:
            from fishnet_tpu.parallel.mesh import make_sharded_table

            tt = make_sharded_table(mesh, tt_log2)
        else:
            from fishnet_tpu.ops import tt as tt_mod

            tt = tt_mod.make_table(tt_log2)

    # BENCH_REFILL set → the refill A/B stage instead of the lockstep
    # single-batch stage: same width, same workload (the FULL multipv
    # decomposition, more positions than lanes), measured chunk-serial
    # ("0") or streamed through the continuous-refill path ("1")
    refill_env = os.environ.get("BENCH_REFILL", "")
    if refill_env != "":
        _bench_refill(t0, params, B, depth, budget, variant, fen_set,
                      max_ply, tt, refill_env not in ("0", "false", "no"),
                      mode, device, tt_log2, bench_dtype, mesh=mesh)
        return
    if mesh is not None:
        raise RuntimeError("BENCH_MESH requires BENCH_REFILL (the A/B "
                           "stage); the lockstep stage is single-device")
    _hb(t0, "inputs built")

    # compile each program explicitly so a compiler hang is distinguishable
    # from an execution hang in the heartbeat tail
    _hb(t0, "compile_start init_state")
    with trace.span("bench.compile", "bench", program="init_state"):
        state = S._init_state_jit(
            params, roots, depth_arr, budget_arr, max_ply, variant,
            order_jitter=order_jitter, group=group,
        )
        jax.block_until_ready(state.bt)
    _hb(t0, "compile_done init_state (and executed)")
    # short segments let the lane-narrowing path retire finished lanes
    # mid-batch (ops/search.py search_batch_resumable narrow=True) — with
    # one 20k-step segment a depth-3 batch finishes before the first
    # narrowing checkpoint and the finish-tail eats ~60% of wall clock
    seg = int(os.environ.get("BENCH_SEG", "1024"))
    _hb(t0, f"compile_start run_segment(seg={seg})")
    # the trailing args (deep_tt, prefer_deep, tt_gen) must mirror the
    # timed search_batch_resumable call exactly — tt_gen is a TRACED
    # operand, so even its weak-vs-strong int32 typing must match or
    # this precompile misses and a cold XLA compile lands in the timed
    # region
    with trace.span("bench.compile", "bench", program="run_segment",
                    seg=seg):
        lowered = S._run_segment_jit.lower(
            params, state, tt, seg, variant, False, prefer_deep,
            jnp.int32(tt_gen),
        )
        _hb(t0, "  lowered")
        compiled = lowered.compile()
    _hb(t0, "compile_done run_segment")
    # program cost accounting (obs/perf.py): the Compiled object is
    # already in hand, so the FLOPs/bytes/memory read is free — it
    # rides the RESULT row into the perf ledger and the
    # fishnet_program_* gauges
    program_cost = {}
    try:
        from fishnet_tpu.obs import perf as obs_perf
        from fishnet_tpu.utils import settings as _settings

        if _settings.get_bool("FISHNET_TPU_PERF_PROGRAMS"):
            program_cost = obs_perf.record_program_cost(
                "run_segment", compiled)
    except Exception as e:
        print(f"bench: program cost capture failed: {e}",
              file=sys.stderr, flush=True)
    # pre-compile every narrowed width down to the floor: the warmup and
    # timed runs can take DIFFERENT narrowing trajectories (a warm TT
    # changes when lanes finish), and a cold 10-40 s XLA compile landing
    # inside the timed region would corrupt the recorded nps. Narrowing
    # targets are powers of two >= 64 (ops/search.py), regardless of B.
    w = 64
    while w * 2 < Bt:
        w *= 2
    while w >= 64:
        sub = jax.tree.map(lambda a: a[:w], state)
        _hb(t0, f"compile_start run_segment(width={w})")
        with trace.span("bench.compile", "bench", program="run_segment",
                        width=w):
            S._run_segment_jit.lower(
                params, sub, tt, seg, variant, False, prefer_deep,
                jnp.int32(tt_gen),
            ).compile()
        w //= 2
    _hb(t0, "compile_done narrowed widths")

    helper_kw = dict(
        order_jitter=order_jitter, group=group, required=required,
        prefer_deep_store=prefer_deep, tt_gen=tt_gen,
    )
    _hb(t0, "exec_start warmup search")
    with trace.span("bench.warmup", "bench", B=Bt, depth=depth):
        out = S.search_batch_resumable(
            params, roots, depth_arr, budget_arr, max_ply=max_ply,
            segment_steps=seg, tt=tt, variant=variant, **helper_kw,
        )
        tt = out.pop("tt")
        jax.block_until_ready(out["nodes"])
    _hb(t0, f"exec_done warmup (steps={int(out['steps'])})")

    _hb(t0, "exec_start timed search")
    t1 = time.perf_counter()
    with trace.span("bench.search", "bench", B=Bt, depth=depth):
        out = S.search_batch_resumable(
            params, roots, depth_arr, budget_arr, max_ply=max_ply,
            segment_steps=seg, tt=tt, variant=variant, **helper_kw,
        )
        out.pop("tt")
        jax.block_until_ready(out["nodes"])
    dt = time.perf_counter() - t1
    total_nodes = int(np.asarray(out["nodes"]).sum())
    primary_nodes = int(np.asarray(out["nodes"])[:B].sum())
    _hb(t0, f"exec_done timed: {total_nodes:,} nodes in {dt:.2f}s")

    print(
        "RESULT "
        + json.dumps(
            {
                "nps": total_nodes / dt,
                "B": B,
                "depth": depth,
                "nodes": total_nodes,
                "dt": dt,
                **device,
                "variant": variant,
                "fen_set": fen_set,
                "row_mode": mode,
                "max_ply": max_ply,
                # primaries only: with helpers the first B rows are the
                # analysed positions; helper completions are not output
                "positions_done_per_s": round(
                    float(np.asarray(out["done"])[:B].sum()) / dt, 1
                ),
                "helpers": helpers,
                "primary_nodes": primary_nodes,
                "net": os.environ.get("BENCH_NET", "random"),
                "dtype": bench_dtype or "f32",
                "tt_log2": tt_log2,
                "program_cost": program_cost,
            }
        ),
        flush=True,
    )
    if rec is not None:
        path = rec.flight_dump(
            settings.get_str("FISHNET_TPU_TRACE_DIR"),
            f"bench-b{B}-d{depth}",
        )
        _hb(t0, f"trace dumped to {path}")


def run_stage(B: int, depth: int, budget: int, timeout: float,
              select: bool = False,
              variant: str = "standard",
              fen_set: str = "standard",
              extra_env: dict | None = None) -> dict | None:
    """Parent: launch one stage subprocess; return its RESULT or None."""
    import tempfile

    t0 = time.monotonic()
    cmd = [sys.executable, os.path.abspath(__file__),
           "--stage", str(B), str(depth), str(budget), variant, fen_set]
    env = dict(os.environ)
    # "0" opts into the legacy scatter mode (select is the in-code
    # default since round 5 — see ops/search.py _SELECT_UPDATES)
    env["FISHNET_TPU_SELECT_UPDATES"] = "1" if select else "0"
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    # child stderr goes to a file, not a pipe: on timeout-kill a pipe's
    # contents are lost (TimeoutExpired.stderr is None on this platform),
    # and the heartbeat tail is most needed exactly then
    with tempfile.NamedTemporaryFile("w+", suffix=".bench-hb") as hb:
        try:
            r = subprocess.run(
                cmd, stdout=subprocess.PIPE, stderr=hb, text=True,
                timeout=timeout, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
        except subprocess.TimeoutExpired:
            hb.seek(0)
            tail = "".join(
                l for l in hb.read()[-4000:].splitlines(True)
                if "experimental" not in l
            )
            print(f"bench stage B={B} d={depth} "
                  f"mode={'select' if select else 'scatter'} TIMED OUT after "
                  f"{timeout:.0f}s; heartbeat tail:\n{tail}",
                  file=sys.stderr, flush=True)
            return None
        hb.seek(0)
        for line in hb.read().splitlines():
            if "experimental" not in line:
                print(line, file=sys.stderr, flush=True)
    if r.returncode != 0:
        print(f"bench stage B={B} d={depth} rc={r.returncode} "
              f"({time.monotonic() - t0:.0f}s)", file=sys.stderr, flush=True)
        return None
    for line in r.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    print(f"bench stage B={B} d={depth}: no RESULT line", file=sys.stderr)
    return None


def run_serve_stage(timeout: float) -> dict | None:
    """Closed-loop latency row for the HTTP serving front-end
    (fishnet_tpu/serve/): boots `fishnet_tpu serve --backend python` as
    a subprocess, drives it with closed-loop client threads (each sends
    its next request the moment the previous one answers), and reports
    request latency p50/p99, the shed (429) rate, and positions/s. The
    python backend keeps the row measuring the serving layer itself —
    admission, HTTP framing, session fan-in — not device search speed;
    BENCH_SERVE_BACKEND overrides for an end-to-end device row."""
    import http.client
    import signal
    import threading

    backend = os.environ.get("BENCH_SERVE_BACKEND", "python")
    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "8"))
    per_client = int(os.environ.get("BENCH_SERVE_REQUESTS", "12"))
    start_fen = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    t0 = time.monotonic()

    proc = subprocess.Popen(
        [sys.executable, "-m", "fishnet_tpu", "serve",
         "--backend", backend, "--serve-port", "0", "--no-conf"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    try:
        host_port = None
        assert proc.stdout is not None
        while time.monotonic() - t0 < min(timeout, 120.0):
            line = proc.stdout.readline()
            if not line:
                break
            if "serve: listening on " in line:
                host_port = line.split("serve: listening on ", 1)[1].strip()
                break
        if host_port is None:
            print("bench serve_latency: server never came up",
                  file=sys.stderr, flush=True)
            return None
        host, _, port_s = host_port.rpartition(":")
        port = int(port_s)
        # drain the server's remaining stdout so it can't block on a
        # full pipe while we measure
        threading.Thread(
            target=lambda: proc.stdout.read(), daemon=True
        ).start()

        lock = threading.Lock()
        lat_ms: list = []
        shed = [0]
        failed = [0]
        positions = [0]

        def one_client(cid: int) -> None:
            conn = http.client.HTTPConnection(host, port, timeout=60.0)
            try:
                for i in range(per_client):
                    n_pos = 1 + (i % 2)
                    body = json.dumps({
                        "id": f"bench-{cid}-{i}",
                        "tenant": f"bench{cid % 2}",
                        # depth 1 keeps the python backend's share of
                        # the latency in the low ms, so p50/p99 track
                        # the serving layer rather than the fallback
                        # engine's search speed
                        "positions": [{"fen": start_fen, "moves": []}] * n_pos,
                        "depth": 1,
                        "timeout_ms": 30_000,
                    })
                    t1 = time.monotonic()
                    try:
                        conn.request("POST", "/analyse", body=body,
                                     headers={"Content-Type":
                                              "application/json"})
                        resp = conn.getresponse()
                        resp.read()
                    except (OSError, ValueError, http.client.HTTPException):
                        with lock:
                            failed[0] += 1
                        conn.close()
                        conn = http.client.HTTPConnection(
                            host, port, timeout=60.0)
                        continue
                    dt_ms = (time.monotonic() - t1) * 1000.0
                    with lock:
                        if resp.status == 200:
                            lat_ms.append(dt_ms)
                            positions[0] += n_pos
                        elif resp.status == 429:
                            shed[0] += 1
                        else:
                            failed[0] += 1
            finally:
                conn.close()

        t_load = time.monotonic()
        threads = [threading.Thread(target=one_client, args=(cid,))
                   for cid in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)
        wall_s = max(time.monotonic() - t_load, 1e-6)

        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30.0)
        except subprocess.TimeoutExpired:
            print("bench serve_latency: server ignored SIGTERM",
                  file=sys.stderr, flush=True)
            return None
        if not lat_ms:
            print("bench serve_latency: no request completed",
                  file=sys.stderr, flush=True)
            return None
        lat_ms.sort()
        total = len(lat_ms) + shed[0] + failed[0]
        return {
            "backend": backend,
            "clients": clients,
            "requests_ok": len(lat_ms),
            "p50_ms": round(lat_ms[len(lat_ms) // 2], 2),
            "p99_ms": round(lat_ms[min(len(lat_ms) - 1,
                                       (len(lat_ms) * 99) // 100)], 2),
            "shed_rate": round(shed[0] / max(total, 1), 4),
            "failed": failed[0],
            "positions_per_s": round(positions[0] / wall_s, 1),
        }
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10.0)


def run_serve_slo_stage(timeout: float) -> dict | None:
    """SLO accounting row (round 14): closed-loop MIXED tenants against
    a live serve subprocess — an interactive tenant firing 1-position
    /bestmove requests under a tight deadline interleaved with a batch
    tenant firing 4-position /analyse requests under a loose one.
    Reports client-side p50/p99 per kind plus the server's own SLO
    accounting (obs/metrics.py SloRecorder) scraped from /metrics:
    deadline-miss rate and the queue-wait share of total latency —
    the two numbers the admission controller is supposed to keep low
    for interactive traffic even with batch load present."""
    import http.client
    import signal
    import socket
    import threading

    backend = os.environ.get("BENCH_SERVE_BACKEND", "python")
    clients = int(os.environ.get("BENCH_SLO_CLIENTS", "6"))
    per_client = int(os.environ.get("BENCH_SLO_REQUESTS", "10"))
    start_fen = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    t0 = time.monotonic()

    # reserve a loopback port for the metrics endpoint — the settings
    # switch only accepts a concrete positive port, so bind-and-release
    # an ephemeral one (the tiny reuse race is acceptable for a bench)
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    metrics_port = sock.getsockname()[1]
    sock.close()
    env = dict(os.environ, FISHNET_TPU_METRICS_PORT=str(metrics_port))

    proc = subprocess.Popen(
        [sys.executable, "-m", "fishnet_tpu", "serve",
         "--backend", backend, "--serve-port", "0", "--no-conf"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
    )
    try:
        host_port = None
        assert proc.stdout is not None
        while time.monotonic() - t0 < min(timeout, 120.0):
            line = proc.stdout.readline()
            if not line:
                break
            if "serve: listening on " in line:
                host_port = line.split("serve: listening on ", 1)[1].strip()
                break
        if host_port is None:
            print("bench serve_slo: server never came up",
                  file=sys.stderr, flush=True)
            return None
        host, _, port_s = host_port.rpartition(":")
        port = int(port_s)
        threading.Thread(
            target=lambda: proc.stdout.read(), daemon=True
        ).start()

        lock = threading.Lock()
        lat_ms: dict = {"analysis": [], "bestmove": []}
        shed = [0]
        failed = [0]

        def one_client(cid: int) -> None:
            interactive = cid % 2 == 0
            conn = http.client.HTTPConnection(host, port, timeout=60.0)
            try:
                for i in range(per_client):
                    if interactive:
                        kind, path = "bestmove", "/bestmove"
                        body = json.dumps({
                            "id": f"slo-i{cid}-{i}",
                            "tenant": "interactive",
                            "priority": "interactive",
                            "positions": [{"fen": start_fen, "moves": []}],
                            "level": 1,
                            # tight enough that queueing behind batch
                            # work shows up as deadline misses
                            "timeout_ms": 500,
                        })
                    else:
                        kind, path = "analysis", "/analyse"
                        body = json.dumps({
                            "id": f"slo-b{cid}-{i}",
                            "tenant": "batch",
                            "priority": "batch",
                            "positions": [
                                {"fen": start_fen, "moves": []}
                            ] * 4,
                            "depth": 1,
                            "timeout_ms": 30_000,
                        })
                    t1 = time.monotonic()
                    try:
                        conn.request("POST", path, body=body,
                                     headers={"Content-Type":
                                              "application/json"})
                        resp = conn.getresponse()
                        resp.read()
                    except (OSError, ValueError, http.client.HTTPException):
                        with lock:
                            failed[0] += 1
                        conn.close()
                        conn = http.client.HTTPConnection(
                            host, port, timeout=60.0)
                        continue
                    dt_ms = (time.monotonic() - t1) * 1000.0
                    with lock:
                        if resp.status == 200:
                            lat_ms[kind].append(dt_ms)
                        elif resp.status == 429:
                            shed[0] += 1
                        else:
                            failed[0] += 1
            finally:
                conn.close()

        threads = [threading.Thread(target=one_client, args=(cid,))
                   for cid in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=timeout)

        # scrape the server's SLO accounting BEFORE stopping it
        slo: dict = {}
        try:
            mconn = http.client.HTTPConnection(
                "127.0.0.1", metrics_port, timeout=10.0)
            mconn.request("GET", "/metrics")
            text = mconn.getresponse().read().decode("utf-8")
            mconn.close()
            for mline in text.splitlines():
                if mline.startswith("#") or "{" in mline:
                    continue  # skip comments and histogram buckets
                name, _, value = mline.partition(" ")
                if name.startswith("fishnet_slo_"):
                    slo[name] = float(value)
        except (OSError, ValueError, http.client.HTTPException) as e:
            print(f"bench serve_slo: metrics scrape failed: {e}",
                  file=sys.stderr, flush=True)

        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=30.0)
        except subprocess.TimeoutExpired:
            print("bench serve_slo: server ignored SIGTERM",
                  file=sys.stderr, flush=True)
            return None
        if not (lat_ms["analysis"] or lat_ms["bestmove"]):
            print("bench serve_slo: no request completed",
                  file=sys.stderr, flush=True)
            return None

        def pcts(vals: list) -> dict | None:
            if not vals:
                return None
            vals = sorted(vals)
            return {
                "requests_ok": len(vals),
                "p50_ms": round(vals[len(vals) // 2], 2),
                "p99_ms": round(vals[min(len(vals) - 1,
                                         (len(vals) * 99) // 100)], 2),
            }

        def slo_sum(what: str) -> float:
            return sum(v for k, v in slo.items()
                       if k.startswith(f"fishnet_slo_{what}_"))

        requests = slo_sum("requests_total")
        misses = slo_sum("deadline_miss_total")
        latency_sum = sum(v for k, v in slo.items()
                          if k.startswith("fishnet_slo_latency_ms_")
                          and k.endswith("_sum"))
        queue_sum = sum(v for k, v in slo.items()
                        if k.startswith("fishnet_slo_queue_ms_")
                        and k.endswith("_sum"))
        return {
            "backend": backend,
            "clients": clients,
            "interactive": pcts(lat_ms["bestmove"]),
            "batch": pcts(lat_ms["analysis"]),
            "shed": shed[0],
            "failed": failed[0],
            "deadline_miss_rate": (
                round(misses / requests, 4) if requests else None
            ),
            "queue_wait_share": (
                round(queue_sum / latency_sum, 4) if latency_sum else None
            ),
        }
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10.0)


def run_fleet_stage(timeout: float) -> dict | None:
    """Fleet scaling row (ISSUE 12): the same position workload pushed
    through the fleet coordinator (fishnet_tpu/fleet/) over 1/2/4
    fakehost-backed members with a fixed per-chunk service latency.
    Each member serializes its chunks (one in-flight dispatch, like the
    real supervised engine), so ideal scaling is linear in members;
    the row reports positions/s per member count, scaling efficiency
    vs the single-member run, and the redispatch count (0 — nothing
    dies here; the chaos gate owns the loss path). CPU-only, no JAX.

    Knobs: BENCH_FLEET=0 skips; BENCH_FLEET_MEMBERS="1,2,4" member
    counts; BENCH_FLEET_POSITIONS per-count workload (default 48);
    BENCH_FLEET_LATENCY_MS per-chunk member latency (default 30)."""
    import asyncio

    from fishnet_tpu.client.backoff import RandomizedBackoff
    from fishnet_tpu.client.ipc import Chunk, WorkPosition
    from fishnet_tpu.client.logger import Logger
    from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
    from fishnet_tpu.fleet import FleetCoordinator
    from fishnet_tpu.fleet.member import make_local_member
    from fishnet_tpu.obs.metrics import MetricsRegistry

    counts = [int(c) for c in
              os.environ.get("BENCH_FLEET_MEMBERS", "1,2,4").split(",")]
    positions = int(os.environ.get("BENCH_FLEET_POSITIONS", "48"))
    latency_ms = float(os.environ.get("BENCH_FLEET_LATENCY_MS", "30"))
    start_fen = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    deadline_budget = min(timeout, 120.0)

    def one_chunk(i: int) -> Chunk:
        work = AnalysisWork(
            id=f"fleetbench{i:04d}",
            nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
            timeout_s=deadline_budget, depth=1, multipv=None,
        )
        return Chunk(
            work=work, deadline=time.monotonic() + deadline_budget,
            variant="standard", flavor=EngineFlavor.TPU,
            positions=[WorkPosition(
                work=work, position_index=0, url=None, skip=False,
                root_fen=start_fen, moves=[])],
        )

    async def measure(n_members: int) -> dict:
        members = [
            make_local_member(
                f"bench{i}",
                host_cmd=[
                    sys.executable, "-m", "fishnet_tpu.engine.fakehost",
                    "--script", '{"chunks": ["ok"]}',
                    "--hb-interval", "0.05",
                    "--latency-ms", str(latency_ms),
                ],
                logger=Logger(verbose=0),
                hb_interval=0.05, hb_timeout=2.0,
                backoff=RandomizedBackoff(max_s=0.1),
            )
            for i in range(n_members)
        ]
        coord = FleetCoordinator(
            members, logger=Logger(verbose=0),
            registry=MetricsRegistry(), loss_window=1.0,
        )
        try:
            await coord.start()  # spawn cost stays out of the window
            # one warm round so every member has served a chunk
            await asyncio.gather(
                *(coord.go_multiple(one_chunk(10_000 + i))
                  for i in range(n_members)))
            t0 = time.monotonic()
            await asyncio.gather(
                *(coord.go_multiple(one_chunk(i))
                  for i in range(positions)))
            wall_s = max(time.monotonic() - t0, 1e-6)
        finally:
            await coord.close()
        return {
            "positions_per_s": round(positions / wall_s, 1),
            "redispatches": coord.stats.redispatches,
            "losses": coord.stats.losses,
        }

    rows = {}
    base_pps = None
    for n in counts:
        try:
            row = asyncio.run(
                asyncio.wait_for(measure(n), timeout=deadline_budget))
        except (Exception, asyncio.TimeoutError) as e:
            print(f"bench fleet_scaling: {n}-member run failed: {e}",
                  file=sys.stderr, flush=True)
            return None
        if base_pps is None:
            base_pps = row["positions_per_s"]
        row["scaling_x"] = round(row["positions_per_s"] / base_pps, 2)
        row["efficiency"] = round(row["scaling_x"] / max(n / counts[0], 1),
                                  3)
        rows[str(n)] = row
    return {
        "latency_ms": latency_ms,
        "positions": positions,
        "members": rows,
    }


def run_fleet_tail_stage(timeout: float) -> dict | None:
    """Fleet tail-latency row (ISSUE 15): 3 fakehost members, one a
    deliberate straggler, the same chunk stream run with hedged
    dispatch off and on. Hedging duplicates the straggler's unfinished
    positions to a free member once deadline slack runs low
    (first-answer-wins through the exactly-once ledger), so the row
    reports per-chunk p50/p99 latency plus the loss and hedge counters
    for both modes — the p99 delta is the feature. CPU-only, no JAX.

    Knobs: BENCH_FLEET_TAIL=0 skips; BENCH_FLEET_TAIL_CHUNKS rounds
    (default 12); BENCH_FLEET_TAIL_LATENCY_MS straggler latency
    (default 200)."""
    import asyncio

    from fishnet_tpu.client.backoff import RandomizedBackoff
    from fishnet_tpu.client.ipc import Chunk, WorkPosition
    from fishnet_tpu.client.logger import Logger
    from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
    from fishnet_tpu.fleet import FleetCoordinator
    from fishnet_tpu.fleet.member import make_local_member
    from fishnet_tpu.obs.metrics import MetricsRegistry

    rounds = int(os.environ.get("BENCH_FLEET_TAIL_CHUNKS", "12"))
    straggle_ms = float(os.environ.get("BENCH_FLEET_TAIL_LATENCY_MS", "200"))
    start_fen = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
    ttl = 2.0

    def one_chunk(i: int, chunk_ttl: float) -> Chunk:
        work = AnalysisWork(
            id=f"fleettail{i:04d}",
            nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
            timeout_s=chunk_ttl, depth=1, multipv=None,
        )
        return Chunk(
            work=work, deadline=time.monotonic() + chunk_ttl,
            variant="standard", flavor=EngineFlavor.TPU,
            positions=[WorkPosition(
                work=work, position_index=p, url=None, skip=False,
                root_fen=start_fen, moves=[])
                for p in range(3)],
        )

    async def measure(hedge: bool) -> dict:
        members = [
            make_local_member(
                name,
                host_cmd=[
                    sys.executable, "-m", "fishnet_tpu.engine.fakehost",
                    "--script", '{"chunks": ["ok"]}',
                    "--hb-interval", "0.05",
                    "--latency-ms", str(ms),
                ],
                logger=Logger(verbose=0),
                hb_interval=0.05, hb_timeout=2.0,
                backoff=RandomizedBackoff(max_s=0.1),
            )
            for name, ms in (
                ("straggler", straggle_ms), ("fast0", 0), ("fast1", 0),
            )
        ]
        coord = FleetCoordinator(
            members, logger=Logger(verbose=0),
            registry=MetricsRegistry(), loss_window=5.0,
            # fire the hedge halfway into the straggler's service time,
            # well before the deadline — the hedge must be able to win
            hedge=hedge, hedge_slack_ms=int(ttl * 1000 - straggle_ms / 2),
        )
        lat = []
        try:
            await coord.start()
            # warm round outside the timing (ttl far past the trigger)
            await coord.go_multiple(one_chunk(9_000, 30.0))
            for i in range(rounds):
                t0 = time.monotonic()
                await coord.go_multiple(one_chunk(i, ttl))
                lat.append(time.monotonic() - t0)
        finally:
            await coord.close()
        lat.sort()
        return {
            "p50_ms": round(lat[len(lat) // 2] * 1000, 1),
            "p99_ms": round(lat[min(len(lat) - 1,
                                    int(len(lat) * 0.99))] * 1000, 1),
            "losses": coord.stats.losses,
            "hedges": coord.stats.hedges,
            "hedge_wins": coord.stats.hedge_wins,
        }

    rows = {}
    for mode, hedge in (("hedge_off", False), ("hedge_on", True)):
        try:
            rows[mode] = asyncio.run(
                asyncio.wait_for(measure(hedge),
                                 timeout=min(timeout, 120.0)))
        except (Exception, asyncio.TimeoutError) as e:
            print(f"bench fleet_tail: {mode} run failed: {e}",
                  file=sys.stderr, flush=True)
            return None
    return {
        "members": 3,
        "straggler_latency_ms": straggle_ms,
        "chunks": rounds,
        **rows,
    }


def run_autoscale_flash_stage(timeout: float) -> dict | None:
    """Elastic-capacity row (ISSUE 16): the identical open-loop flash
    crowd (tools/loadgen.py, 10x base rate, fixed seed) fired at a
    ServeApp fronting a one-member-floor fakehost fleet, autoscaler off
    vs on. The off run shows what a fixed floor does under a burst
    (queue growth, SLO deadline misses, sheds); the on run must show a
    strictly lower miss rate, the member count rising during the burst
    and returning to the floor afterwards, and at most one up/down
    reversal (the hysteresis asymmetry). Answers stay bit-identical —
    the autoscaler only changes membership, never dispatch planning
    (tests/test_autoscaler.py owns that assertion). CPU-only, no JAX.

    Knobs: BENCH_AUTOSCALE=0 skips; BENCH_AUTOSCALE_RPS base rate
    (default 2); BENCH_AUTOSCALE_LATENCY_MS member service latency
    (default 80)."""
    import asyncio

    from fishnet_tpu.client.backoff import RandomizedBackoff
    from fishnet_tpu.client.logger import Logger
    from fishnet_tpu.client.wire import EngineFlavor
    from fishnet_tpu.engine.session import EngineSession
    from fishnet_tpu.fleet import FleetCoordinator
    from fishnet_tpu.fleet.autoscaler import AutoscaleConfig, Autoscaler
    from fishnet_tpu.fleet.member import make_local_member
    from fishnet_tpu.obs.metrics import MetricsRegistry
    from fishnet_tpu.serve.server import ServeApp
    from tools.loadgen import LoadProfile, generate_schedule, run_load

    base_rps = float(os.environ.get("BENCH_AUTOSCALE_RPS", "2"))
    latency_ms = float(os.environ.get("BENCH_AUTOSCALE_LATENCY_MS", "80"))
    profile = LoadProfile(
        pattern="flash", duration_s=8.0, base_rps=base_rps,
        flash_factor=10.0, flash_start=0.125, flash_len=0.375,
        tenants=3, bestmove_ratio=0.0, positions=2, depth=1,
        timeout_ms=1500,
    )
    # one schedule, one seed: both modes replay the same arrivals
    schedule = generate_schedule(profile, seed=42)
    as_cfg = AutoscaleConfig(
        min_members=1, max_members=3, interval_s=0.15,
        up_queue=1, up_ticks=2, down_ticks=5,
        loss_cooldown_s=1.0, drain_timeout_s=20.0,
    )

    def member(name: str):
        return make_local_member(
            name,
            host_cmd=[
                sys.executable, "-m", "fishnet_tpu.engine.fakehost",
                "--script", '{"chunks": ["ok"]}',
                "--hb-interval", "0.05",
                "--latency-ms", str(latency_ms),
            ],
            logger=Logger(verbose=0),
            hb_interval=0.05, hb_timeout=2.0,
            backoff=RandomizedBackoff(max_s=0.1),
        )

    async def drive(autoscale_on: bool) -> dict:
        coord = FleetCoordinator(
            [member("as0")], logger=Logger(verbose=0),
            registry=MetricsRegistry(), loss_window=1.0,
            local_factory=member,
        )
        app = ServeApp(
            EngineSession(coord, flavor=EngineFlavor.TPU),
            # positions-denominated admission: 4 concurrent 2-position
            # requests; the member's serial chunk service is the real
            # bottleneck the autoscaler relieves
            max_inflight=8, max_queue=96,
            logger=Logger(verbose=0), registry=MetricsRegistry(),
        )
        autoscaler = (
            Autoscaler(coord, app.admission, config=as_cfg,
                       registry=app.registry, logger=Logger(verbose=0))
            if autoscale_on else None
        )
        members_trace = []

        def on_tick(t):
            n = len(coord.members)
            if not members_trace or members_trace[-1][1] != n:
                members_trace.append([round(t, 2), n])

        try:
            await coord.start()
            host, port = await app.start("127.0.0.1", 0)
            if autoscaler is not None:
                autoscaler.start()
            report = await run_load(
                host, port, schedule, logger=Logger(verbose=0),
                drain_timeout_s=60.0, on_tick=on_tick,
            )
            if autoscaler is not None:
                # post-burst: the loop must drain back to the floor
                floor_deadline = time.monotonic() + 25.0
                while time.monotonic() < floor_deadline:
                    snap = autoscaler.snapshot()
                    if (snap["members"] == as_cfg.min_members
                            and snap["draining"] is None):
                        break
                    await asyncio.sleep(0.1)
        finally:
            if autoscaler is not None:
                await autoscaler.stop()
            await app.drain_and_stop()
            await coord.close()

        snap = app.registry.snapshot()
        late = sum(v for k, v in snap.items()
                   if k.startswith("fishnet_slo_deadline_miss_total_"))
        d = report.as_dict()
        # deadline-miss rate over the whole schedule: answered-late
        # (SloRecorder deadline_miss), failed (the engine refuses to
        # search past an expired deadline — a 500 here IS a missed
        # deadline), and shed all violated the request's SLO
        violations = late + d["errors"] + d["shed"]
        row = {
            "ok": d["ok"],
            "shed": d["shed"],
            "errors": d["errors"],
            "answered_late": late,
            "p99_ms": d["per_kind"].get("analysis", {}).get("p99_ms", 0.0),
            "miss_rate": round(violations / max(len(schedule), 1), 4),
            "members_trace": members_trace,
            "members_final": len(coord.members),
        }
        if autoscaler is not None:
            seq = [dec.action for dec in autoscaler.decisions
                   if dec.action in ("up", "down")]
            row.update({
                "ups": autoscaler.stats.ups,
                "downs": autoscaler.stats.downs,
                # a second up-burst after a down is a flap: hysteresis
                # promises at most one reversal per burst
                "reversals": sum(
                    1 for a, b in zip(seq, seq[1:])
                    if a == "down" and b == "up"
                ),
                "member_seconds": round(autoscaler.stats.member_seconds, 1),
            })
        return row

    rows = {}
    for mode, flag in (("autoscale_off", False), ("autoscale_on", True)):
        try:
            rows[mode] = asyncio.run(
                asyncio.wait_for(drive(flag), timeout=min(timeout, 120.0)))
        except (Exception, asyncio.TimeoutError) as e:
            print(f"bench autoscale_flash: {mode} run failed: {e}",
                  file=sys.stderr, flush=True)
            return None
    return {
        "requests": len(schedule),
        "latency_ms": latency_ms,
        "floor": as_cfg.min_members,
        "ceiling": as_cfg.max_members,
        **rows,
    }


def run_cache_zipf_stage(timeout: float) -> dict | None:
    """Analysis-cache row (ISSUE 17): a Zipf-distributed position
    stream (tools/loadgen.py --fingerprint-dist zipf, s=1.1 — the
    opening-theory-dominated population the cache is built for)
    replayed closed-loop against an in-process ServeApp on the python
    backend, cache off vs on. Three legs over ONE schedule:

      cold  — cache off: every position is a real search (the
              pre-cache baseline);
      fill  — a fresh cache sees the same stream: the Zipf head starts
              repeating mid-run (`first_pass_hit_ratio` is the benefit
              a cache gets with NO warmup);
      warm  — the same stream again on the filled cache: the steady
              state of a long-running fleet.

    The acceptance bar is warm >= 5x cold on effective positions/s;
    the row also carries the hit ratio and resident bytes, and checks
    every warm answer bit-identical (scores/pvs/best_move/depth/nodes)
    to its cold twin. CPU-only, no JAX.

    Knobs: BENCH_CACHE=0 skips; BENCH_CACHE_REQUESTS (default 40);
    BENCH_CACHE_DEPTH (default 1 — keeps the python backend's search
    in the tens of ms, big enough to dwarf a ~1ms hit, small enough
    that the cold leg finishes in seconds)."""
    import asyncio

    from fishnet_tpu.cache.keys import engine_identity
    from fishnet_tpu.cache.store import AnalysisCache
    from fishnet_tpu.client.logger import Logger
    from fishnet_tpu.client.wire import EngineFlavor
    from fishnet_tpu.engine.pyengine import PyEngine
    from fishnet_tpu.engine.session import EngineSession
    from fishnet_tpu.obs.metrics import MetricsRegistry
    from fishnet_tpu.serve.server import ServeApp
    from tools.loadgen import LoadProfile, generate_schedule, request_body

    n_requests = int(os.environ.get("BENCH_CACHE_REQUESTS", "40"))
    depth = int(os.environ.get("BENCH_CACHE_DEPTH", "1"))
    profile = LoadProfile(
        pattern="steady", duration_s=60.0, base_rps=2.0,
        tenants=3, bestmove_ratio=0.0, positions=2, depth=depth,
        timeout_ms=30_000,
        fingerprint_dist="zipf", fingerprint_pool=24,
        fingerprint_zipf_s=1.1,
    )
    schedule = generate_schedule(profile, seed=42)[:n_requests]
    bodies = [request_body(req, i) for i, req in enumerate(schedule)]
    n_positions = sum(len(b["positions"]) for b in bodies)

    async def http_post(host, port, payload_obj):
        reader, writer = await asyncio.open_connection(host, port)
        try:
            payload = json.dumps(payload_obj).encode("utf-8")
            head = (
                f"POST /analyse HTTP/1.1\r\nHost: {host}\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n"
                "Connection: close\r\n\r\n"
            )
            writer.write(head.encode("latin-1") + payload)
            await writer.drain()
            raw = await reader.read()
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass
        header, _, body_bytes = raw.partition(b"\r\n\r\n")
        status = int(header.decode("latin-1").split(None, 2)[1])
        return status, (json.loads(body_bytes) if body_bytes else {})

    def comparable(resp_body):
        # the search-determined payload; wall-clock fields (time_s,
        # nps, latency) legitimately differ between a cached answer
        # and a fresh search
        return [
            {k: r.get(k)
             for k in ("scores", "pvs", "best_move", "depth", "nodes")}
            for r in resp_body.get("results", [])
        ]

    async def replay(cache) -> dict:
        """One closed-loop pass over the schedule; returns wall time
        and the comparable answers keyed by request id."""
        app = ServeApp(
            EngineSession(PyEngine(max_depth=depth),
                          flavor=EngineFlavor.OFFICIAL),
            max_inflight=8, max_queue=16, default_timeout_ms=30_000,
            logger=Logger(verbose=0), registry=MetricsRegistry(),
            cache=cache,
        )
        answers = {}
        try:
            host, port = await app.start("127.0.0.1", 0)
            t0 = time.monotonic()
            for body in bodies:
                status, resp = await http_post(host, port, body)
                if status != 200:
                    raise RuntimeError(
                        f"request {body['id']} answered {status}")
                answers[body["id"]] = comparable(resp)
            wall_s = max(time.monotonic() - t0, 1e-6)
        finally:
            await app.drain_and_stop()
        return {"wall_s": wall_s, "answers": answers}

    async def drive() -> dict:
        cold = await replay(None)

        ident = engine_identity(PyEngine(max_depth=depth),
                                EngineFlavor.OFFICIAL)
        cache = AnalysisCache(ident)  # memory-only: the row measures
        fill = await replay(cache)    # the tier, not the sqlite sink
        c_fill = cache.counters()
        first_pass_ratio = c_fill["hit_ratio"]

        warm = await replay(cache)
        c_warm = cache.counters()
        warm_hits = c_warm["hits"] - c_fill["hits"]
        warm_total = warm_hits + (c_warm["misses"] - c_fill["misses"])

        identical = all(
            cold["answers"][rid] == warm["answers"][rid]
            for rid in cold["answers"]
        )
        cold_pps = n_positions / cold["wall_s"]
        warm_pps = n_positions / warm["wall_s"]
        return {
            "requests": len(bodies),
            "positions": n_positions,
            "depth": depth,
            "pool": profile.fingerprint_pool,
            "zipf_s": profile.fingerprint_zipf_s,
            "cold_pos_per_s": round(cold_pps, 1),
            "warm_pos_per_s": round(warm_pps, 1),
            "speedup": round(warm_pps / max(cold_pps, 1e-9), 1),
            "first_pass_hit_ratio": first_pass_ratio,
            "warm_hit_ratio": round(
                warm_hits / max(warm_total, 1), 4),
            "entries": c_warm["entries"],
            "bytes": c_warm["bytes"],
            "coalesced": c_warm["coalesced"],
            "bit_identical": identical,
        }

    try:
        return asyncio.run(
            asyncio.wait_for(drive(), timeout=min(timeout, 240.0)))
    except (Exception, asyncio.TimeoutError) as e:
        print(f"bench cache_zipf: run failed: {e}",
              file=sys.stderr, flush=True)
        return None


def mesh_scaling_child(ndev: int) -> None:
    """Child: the FULL multipv workload (229 root-move boards of the
    standard 8-FEN set) streamed through one registry-driven engine on
    an `ndev`-device mesh at width 8*ndev — the pod-slice shape where
    one logical engine's lane count grows with its device count.

    Prints one RESULT line. positions_per_kstep (positions retired per
    1000 per-shard device steps) is the hardware-independent scaling
    metric: on a real pod each shard is a chip and wall-clock tracks
    per-shard steps, while on a forced-device CPU host all shards
    time-share one core, so wall positions/s (also reported) cannot show
    device parallelism. Mean live occupancy per shard comes straight
    from the stream's boundary summaries."""
    # must land before the first jax import in this process
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    t0 = time.monotonic()
    import numpy as np

    import jax  # noqa: F401  (device init under the forced flag)
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.parallel.mesh import make_mesh, make_sharded_table

    _hb(t0, f"mesh_scaling ndev={ndev}: building workload")
    width = 8 * ndev
    roots, n_all = _all_boards_for(width, "standard", "multipv")
    # first 96 root-move boards: > width at every ndev (so refill fires
    # everywhere), small enough that the width-8 run — ~12 serial fill
    # generations on one core — fits the stage budget. The CI perf gate
    # (BENCH_GATE) trims further: the scaling story is unchanged and the
    # deterministic counters stay deterministic at any fixed count.
    n_pos = min(int(os.environ.get("BENCH_MESH_SCALING_POS", "96")), n_all)
    roots = jax.tree_util.tree_map(lambda a: a[:n_pos], roots)
    # depth 1, staggered node budgets: 96 distinct root-move boards
    # park at different boundaries on different shards (different move
    # counts, different budgets), so refill and the finished-lane
    # gathers interleave — deeper lanes would push the width-8 run to
    # many minutes on a 1-core host without changing the scaling story
    depths = np.ones(n_pos, np.int32)
    budget = np.asarray(
        [1_500 + 250 * (i % 7) for i in range(n_pos)], np.int32)
    params = nnue.init_params(
        jax.random.PRNGKey(3), l1=32, feature_set="board768")
    mesh = make_mesh(ndev)
    kw = dict(max_ply=6, width=width, segment_steps=30, mesh=mesh)

    # warmup: the SAME shapes (compilation is shape-keyed) at a budget
    # low enough to drain in seconds — still deep enough to fire refill
    # and the finished-lane gathers, so every program is warm before
    # the timed pass
    _hb(t0, f"exec_start warmup stream (width={width}, N={n_pos})")
    S.search_stream(params, roots, depths,
                    np.full(n_pos, 200, np.int32),
                    tt=make_sharded_table(mesh, 10), **kw)
    _hb(t0, "exec_start timed stream")
    t1 = time.perf_counter()
    out = S.search_stream(params, roots, depths, budget,
                          tt=make_sharded_table(mesh, 10), **kw)
    dt = time.perf_counter() - t1
    _hb(t0, f"exec_done timed: {dt:.2f}s")

    done = int(np.asarray(out["done"]).sum())
    steps = int(np.asarray(out["steps"]))  # per-shard device steps
    occ = out["occupancy"]
    lane_steps = sum(r["live"] * r["steps"] for r in occ)
    denom = max(sum(width * r["steps"] for r in occ), 1)
    local = width // ndev
    shard_occ = [
        round(sum(r["shard_live"][s] * r["steps"] for r in occ)
              / max(sum(local * r["steps"] for r in occ), 1), 3)
        for s in range(ndev)
    ]
    print(
        "RESULT "
        + json.dumps({
            "ndev": ndev,
            "width": width,
            "positions": n_pos,
            "done": done,
            "dt": round(dt, 2),
            "positions_per_s": round(n_pos / dt, 2),
            "steps_per_shard": steps,
            "positions_per_kstep": round(n_pos / max(steps, 1) * 1000, 2),
            "mean_live_occupancy": round(lane_steps / denom, 3),
            "shard_live_occupancy": shard_occ,
            "refills": int(out["refills"]),
            "boundaries": len(occ),
        }),
        flush=True,
    )


def run_mesh_scaling_stage(timeout: float) -> dict | None:
    """Mesh scaling row (partition-rule registry): the SAME multipv
    workload through one registry-derived sharded engine at ndev =
    1/2/4/8 virtual devices, width 8*ndev. scaling_x is the
    positions-per-shard-step ratio vs ndev=1 — the wall-clock scaling a
    real pod slice sees, measured on CPU where the shards time-share
    one core (wall positions/s rides along per row for reference).

    Knobs: BENCH_MESH_SCALING=0 skips; BENCH_MESH_SCALING_NDEV
    (default "1,2,4,8")."""
    import tempfile

    counts = [int(c) for c in os.environ.get(
        "BENCH_MESH_SCALING_NDEV", "1,2,4,8").split(",")]
    here = os.path.dirname(os.path.abspath(__file__))
    t0 = time.monotonic()
    rows: dict = {}
    base_ppk = None
    for ndev in counts:
        remaining = timeout - (time.monotonic() - t0)
        if remaining < 60.0:
            print(f"bench mesh_scaling: skipping ndev={ndev} "
                  "(stage budget spent)", file=sys.stderr, flush=True)
            break
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        with tempfile.NamedTemporaryFile("w+", suffix=".bench-hb") as hb:
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--mesh-scaling-stage", str(ndev)],
                    stdout=subprocess.PIPE, stderr=hb, text=True,
                    timeout=remaining, env=env, cwd=here,
                )
            except subprocess.TimeoutExpired:
                hb.seek(0)
                tail = hb.read()[-2000:]
                print(f"bench mesh_scaling: ndev={ndev} TIMED OUT; "
                      f"heartbeat tail:\n{tail}",
                      file=sys.stderr, flush=True)
                break  # keep the rows already measured
        if r.returncode != 0:
            print(f"bench mesh_scaling: ndev={ndev} rc={r.returncode}",
                  file=sys.stderr, flush=True)
            break
        row = None
        for line in r.stdout.splitlines():
            if line.startswith("RESULT "):
                row = json.loads(line[len("RESULT "):])
        if row is None:
            print(f"bench mesh_scaling: ndev={ndev}: no RESULT line",
                  file=sys.stderr, flush=True)
            break
        if row["done"] != row["positions"]:
            print(f"bench mesh_scaling: ndev={ndev} left "
                  f"{row['positions'] - row['done']} unfinished",
                  file=sys.stderr, flush=True)
            break
        if base_ppk is None:
            base_ppk = row["positions_per_kstep"]
        row["scaling_x"] = round(
            row["positions_per_kstep"] / max(base_ppk, 1e-9), 2)
        rows[str(ndev)] = row
    if not rows:
        return None
    return {"ndev": rows}


def run_coldstart_stage(timeout: float) -> dict | None:
    """Cold-start A/B row (AOT program assets, fishnet_tpu/aot/):
    time-to-first-result of a FRESH engine process, plain JIT vs booted
    against a pre-packed bundle. Three subprocesses: `fishnet_tpu pack`
    builds the bundle, then two tools/aot_smoke.py --child runs (one
    with FISHNET_TPU_AOT=0, one against the bundle) each boot, warm up,
    and search 16 lanes to the first result. Both children disable the
    persistent XLA cache so the A/B isolates the bundle itself — with
    the disk cache on, the JIT side is half-warm too and the row
    under-reports what a fresh autoscaled replica actually saves.
    BENCH_COLDSTART_PLY sets the stack height (default 8, toy; 32 for
    the production shape — pack time grows with it)."""
    import shutil
    import tempfile

    ply = os.environ.get("BENCH_COLDSTART_PLY", "8")
    here = os.path.dirname(os.path.abspath(__file__))
    child = os.path.join(here, "tools", "aot_smoke.py")
    tmp = tempfile.mkdtemp(prefix="bench-coldstart-")
    store = os.path.join(tmp, "store")
    env = {
        **os.environ,
        "FISHNET_TPU_MAX_PLY": ply,
        "FISHNET_TPU_WARMUP_BUCKETS": "16",
        "FISHNET_TPU_HELPERS": "1",
        "FISHNET_TPU_NO_COMPILE_CACHE": "1",
    }
    env.pop("FISHNET_TPU_TRACE_DIR", None)

    def run_one(tag: str, argv: list, extra: dict,
                budget: float) -> tuple[float, int] | None:
        t1 = time.monotonic()
        try:
            r = subprocess.run(
                argv, cwd=here, env={**env, **extra},
                capture_output=True, text=True, timeout=budget,
            )
        except subprocess.TimeoutExpired:
            print(f"bench cold_start: {tag} timed out",
                  file=sys.stderr, flush=True)
            return None
        if r.returncode != 0:
            tail = (r.stdout or "").splitlines()[-3:]
            print(f"bench cold_start: {tag} exited {r.returncode}: {tail}",
                  file=sys.stderr, flush=True)
            return None
        return time.monotonic() - t1, r.returncode

    try:
        t0 = time.monotonic()
        packed = run_one(
            "pack",
            [sys.executable, "-m", "fishnet_tpu", "pack",
             "--aot-bundle", store, "--no-conf"],
            {"FISHNET_TPU_AOT": "0"}, timeout,
        )
        if packed is None:
            return None
        pack_s = packed[0]
        budget = max(60.0, timeout - (time.monotonic() - t0))
        cold = run_one(
            "jit-cold",
            [sys.executable, child, "--child",
             os.path.join(tmp, "cold.json")],
            {"FISHNET_TPU_AOT": "0"}, budget,
        )
        budget = max(60.0, timeout - (time.monotonic() - t0))
        warm = run_one(
            "aot-warm",
            [sys.executable, child, "--child",
             os.path.join(tmp, "warm.json")],
            {"FISHNET_TPU_AOT": "1", "FISHNET_TPU_AOT_DIR": store},
            budget,
        )
        if cold is None or warm is None:
            return None
        with open(os.path.join(tmp, "warm.json")) as f:
            warm_rep = json.load(f)
        if warm_rep.get("stats", {}).get("misses", 0):
            # a missing program means the row is measuring a partial
            # bundle, not warmup-free boot — report it as a failure
            print(f"bench cold_start: warm boot missed: "
                  f"{warm_rep['stats']}", file=sys.stderr, flush=True)
            return None
        return {
            "pack_s": round(pack_s, 2),
            "cold_first_result_s": round(cold[0], 2),
            "warm_first_result_s": round(warm[0], 2),
            "speedup": round(cold[0] / max(warm[0], 1e-9), 2),
            "programs": warm_rep.get("aot", {}).get("programs", 0),
            "loads": warm_rep.get("stats", {}).get("loads", 0),
            "max_ply": int(ply),
            "lanes": 16,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def device_preflight(timeout: float = 120.0) -> str:
    """What a fresh process sees: "" when it sees an accelerator (or the
    CPU, where JAX_PLATFORMS asked for it), else the reason it does not.
    A child asks, so this process stays off JAX until the last stage
    child has exited (a local chip belongs to one process at a time)."""
    from fishnet_tpu.engine.base import cpu_asked_for

    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return f"jax did not initialise within {timeout:.0f}s"
    if r.returncode != 0:
        return ("jax failed to initialise: "
                + (r.stderr.strip().splitlines() or ["?"])[-1])
    platform = r.stdout.strip().splitlines()[-1]
    if platform == "cpu" and not cpu_asked_for():
        return ("jax found no accelerator (default backend cpu) and "
                "JAX_PLATFORMS did not ask for the cpu")
    return ""


def _ledger_record(results: dict, source: str = "bench",
                   emit: bool = False) -> None:
    """Append one run's RESULT rows to the perf ledger (obs/perf.py)
    and, when emit is set, write the next BENCH_rNN.json artifact from
    it. Backfills the checked-in BENCH/MULTICHIP history first
    (idempotent) so the trend series is populated even on a fresh
    checkout. Never raises: a broken ledger must not cost the bench
    run its stdout contract."""
    try:
        from fishnet_tpu.obs import perf as obs_perf
    except Exception as e:
        print(f"bench: perf ledger unavailable: {e}",
              file=sys.stderr, flush=True)
        return
    try:
        ledger = obs_perf.PerfLedger.open()
        try:
            ledger.backfill()
            # this asks JAX for its devices, in THIS process: callers
            # come here only after their last stage child has exited
            obs_perf.claim_device()
            run_id = f"{source}-{int(time.time())}"
            n = ledger.ingest_results(
                run_id, results, source=source,
                info=obs_perf.build_info(),
            )
            print(f"bench: perf ledger {ledger.path}: recorded {n} "
                  f"metrics as {run_id}", file=sys.stderr, flush=True)
            if emit and n:
                path = ledger.emit_bench_round(run_id)
                if path:
                    print(f"bench: emitted {path} from the ledger",
                          file=sys.stderr, flush=True)
        finally:
            ledger.close()
    except Exception as e:
        print(f"bench: perf ledger write failed: {e}",
              file=sys.stderr, flush=True)


def gate_main() -> None:
    """CI perf-gate rows (BENCH_GATE=1): only the quick deterministic
    counters — a toy search stage (total nodes, positions done) and a
    1/2-device mesh-scaling pair (positions_per_kstep, steps, refills,
    occupancy) — appended to the perf ledger under gate_* row names so
    they build their own baseline series, never mixed with full bench
    rows. tools/perf_report.py --check gates the counter tier against
    the rolling baseline; wall-clock values ride along report-only
    (docs/perf.md)."""
    t_start = time.monotonic()
    timeout = float(os.environ.get("BENCH_GATE_TIMEOUT", "900"))
    results: dict = {}

    res = run_stage(8, 2, 3000, timeout * 0.5, select=SELECT_FIRST,
                    extra_env={"BENCH_SEG": "64"})
    if res is not None:
        results["gate_search"] = res
    print("bench config gate_search: "
          + (json.dumps(res) if res else "FAILED"),
          file=sys.stderr, flush=True)

    os.environ.setdefault("BENCH_MESH_SCALING_NDEV", "1,2")
    os.environ.setdefault("BENCH_MESH_SCALING_POS", "32")
    remaining = timeout - (time.monotonic() - t_start)
    mesh = None
    if remaining > 60.0:
        mesh = run_mesh_scaling_stage(remaining)
    if mesh is not None:
        results["gate_mesh"] = mesh
    print("bench config gate_mesh: "
          + (json.dumps(mesh) if mesh else "FAILED"),
          file=sys.stderr, flush=True)

    _ledger_record(results, source="gate")
    print(json.dumps({
        "metric": "perf-gate deterministic rows",
        "value": len(results),
        "unit": "rows",
        "vs_baseline": 1.0 if results else 0.0,
    }))


def main() -> None:
    # 1024 lanes = the measured v5e throughput sweet spot
    # (docs/profile-r5.md; 2048 falls off a VMEM cliff)
    B = int(os.environ.get("BENCH_LANES", "1024"))
    DEPTH = int(os.environ.get("BENCH_DEPTH", "4"))
    BUDGET = int(os.environ.get("BENCH_BUDGET", "200000"))
    stage_timeout = float(os.environ.get("BENCH_STAGE_TIMEOUT", "420"))
    total_budget = float(os.environ.get("BENCH_TOTAL_BUDGET", "1800"))
    t_start = time.monotonic()

    stages = [s for s in STAGES if s[0] <= B]
    if (B, DEPTH) not in stages:
        stages.append((B, DEPTH))

    why = device_preflight()
    if why:
        # a benchmark of the device that measured the CPU instead would
        # be worse than none
        sys.exit(f"bench: no device to measure: {why}")

    best = None  # result dict with max nps
    fails = 0
    # the row-write mode that last worked on this device; start from the
    # candidate-fix mode (SELECT_FIRST) and fall back per shape
    good_mode: bool | None = None
    for b, d in stages:
        if time.monotonic() - t_start > total_budget - stage_timeout:
            print("bench: total budget nearly spent; stopping ramp",
                  file=sys.stderr, flush=True)
            break
        preferred = SELECT_FIRST if good_mode is None else good_mode
        modes = [preferred, not preferred]  # retry a dead shape in the other mode
        res = None
        for m in modes:
            res = run_stage(b, d, BUDGET, stage_timeout, select=m)
            if res is not None:
                good_mode = m
                break
            if time.monotonic() - t_start > total_budget - stage_timeout:
                break
        if res is None:
            fails += 1
            if fails >= 2:
                # two consecutive dead shapes (both modes): the device
                # is gone; don't burn the rest of the budget on it
                print("bench: two consecutive stage failures; stopping ramp",
                      file=sys.stderr, flush=True)
                break
            continue
        fails = 0
        if best is None or res["nps"] > best["nps"]:
            best = res

    # BASELINE.md config matrix (configs 3-5): multipv-5 decomposition,
    # chess960, crazyhouse + threeCheck — each its own subprocess in the
    # mode that worked for the headline ramp. Results go to
    # bench_matrix.json (the driver consumes only the single stdout line).
    if best is None:
        sys.exit("bench: no device stage produced a result (stage "
                 "failures are logged above)")
    matrix = {}
    if os.environ.get("BENCH_MATRIX", "1") != "0":
        # (name, B, depth, variant, fen_set, extra_env):
        # cfg3-5 = BASELINE.md's config matrix; dtype stages answer
        # VERDICT r4 #4 (int8/bf16 never perf-measured); production =
        # VERDICT r4 #5 (MAX_PLY=32 stack, shipped net, shared TT — the
        # configuration chunk-serving actually runs, vs the toy shapes)
        cfg_stages = [
            ("cfg3_multipv5", 128, 3, "standard", "multipv", None),
            ("cfg4_chess960", 64, 3, "standard", "960", None),
            ("cfg5_crazyhouse", 64, 3, "crazyhouse", "variant", None),
            ("cfg5_threecheck", 64, 3, "threeCheck", "variant", None),
            ("dtype_bf16", 64, 3, "standard", "standard",
             {"BENCH_DTYPE": "bf16"}),
            # dtype_int8 row retired: round 5 measured 37.2 knps vs
            # 58-95 knps f32, and the engine now gates the int8 path
            # behind FISHNET_TPU_EXPERIMENTAL_INT8 (it is a net loss)
            # multipv fen_set: DISTINCT positions per lane — repeating the
            # 8 standard FENs across lanes lets the shared TT dedup whole
            # subtrees, which deflates the nodes/sec metric while doing
            # the same per-position work (round-5 measurement note).
            # B=192: the 8 FENs decompose into 229 root-move boards, so
            # 192 is the largest stage width with no duplicate padding
            ("production_d6_mp32", 192, 6, "standard", "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21"}),
            # continuous lane refill A/B (round 7): the SAME production
            # workload — all 229 root-move boards, MORE positions than
            # the 192 lanes — drained chunk-serially in width-192 batches
            # (the last batch runs 80% padding) vs streamed through one
            # full-width program with DONE lanes respliced at segment
            # boundaries (ops/search.py search_stream). Acceptance:
            # refill-on positions_done_per_s >= 1.3x refill-off at the
            # same width, with occupancy counters in the refill row.
            # Ahead of helper_lanes_k4 (recorded in round 6) so a tight
            # BENCH_TOTAL_BUDGET skips the rerun, not this round's A/B
            ("production_d6_mp32_serial", 192, 6, "standard", "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21", "BENCH_REFILL": "0"}),
            # the stream row: packed boundary summaries, donated
            # segment buffers and speculative next-segment dispatch
            # (ops/search.py search_stream); host_ms / device_ms /
            # transfers in its occupancy summary
            ("production_d6_mp32_refill", 192, 6, "standard", "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21", "BENCH_REFILL": "1"}),
            # mesh parity A/B (round 10): the production refill workload
            # sharded over 8 devices (XLA_FLAGS forces 8 virtual CPU
            # devices when no real mesh is present; on a TPU pod slice
            # the flag is inert and the real chips shard). _mesh_serial
            # drains chunk-serial width-192 sharded batches; _mesh_refill
            # streams with shard-local refill (parallel/mesh.py). The
            # refill row's occupancy summary carries per-shard mean live
            # fractions and the boundary transfer count — acceptance is
            # refill mean_live_frac strictly above serial at the same
            # width, with transfers = 1 on no-finish boundaries
            ("production_d6_mp32_mesh_serial", 192, 6, "standard",
             "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21", "BENCH_REFILL": "0",
              "BENCH_MESH": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}),
            ("production_d6_mp32_mesh_refill", 192, 6, "standard",
             "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21", "BENCH_REFILL": "1",
              "BENCH_MESH": "1",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}),
            # same production shape with 3 Lazy-SMP helper lanes riding
            # each of the 192 primaries (768 lanes total, shared 2M-slot
            # TT): the round-6 acceptance comparison is this row's
            # positions_done_per_s and completed depth vs
            # production_d6_mp32 at the same deadline
            ("helper_lanes_k4", 192, 6, "standard", "multipv",
             {"BENCH_MAX_PLY": "32", "BENCH_NET": "default",
              "BENCH_TT_LOG2": "21", "BENCH_HELPERS": "4"}),
        ]
        for name, b, d, var, fset, xenv in cfg_stages:
            remaining = total_budget - (time.monotonic() - t_start)
            if remaining < 120.0:
                print(f"bench: skipping {name} (budget spent)",
                      file=sys.stderr, flush=True)
                matrix[name] = None
                continue
            res = run_stage(
                b, d, BUDGET, min(stage_timeout, remaining),
                select=(good_mode if good_mode is not None else SELECT_FIRST),
                variant=var, fen_set=fset, extra_env=xenv,
            )
            matrix[name] = res
            print(f"bench config {name}: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # serving-layer latency row (round 11): host-side closed loop over
    # the HTTP front-end; runs on the python backend so it measures
    # admission + framing + session fan-in, independent of the device
    if os.environ.get("BENCH_SERVE", "1") != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 120.0:
            print("bench: skipping serve_latency (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["serve_latency"] = None
        else:
            res = run_serve_stage(min(stage_timeout, remaining))
            matrix["serve_latency"] = res
            print("bench config serve_latency: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # SLO accounting row (round 14): mixed interactive/batch tenants in
    # one closed loop; deadline-miss rate and queue-wait share come from
    # the server's own SloRecorder via /metrics, p50/p99 per kind from
    # the client side
    if os.environ.get("BENCH_SERVE_SLO", "1") != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 120.0:
            print("bench: skipping serve_slo (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["serve_slo"] = None
        else:
            res = run_serve_slo_stage(min(stage_timeout, remaining))
            matrix["serve_slo"] = res
            print("bench config serve_slo: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # fleet scaling row (round 12): 1/2/4 fakehost members behind the
    # coordinator; ideal scaling is linear (each member serializes its
    # chunks at a fixed service latency), so positions/s and efficiency
    # here measure the coordinator's admission + ledger overhead
    if os.environ.get("BENCH_FLEET", "1") != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 120.0:
            print("bench: skipping fleet_scaling (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["fleet_scaling"] = None
        else:
            res = run_fleet_stage(min(stage_timeout, remaining))
            matrix["fleet_scaling"] = res
            print("bench config fleet_scaling: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # fleet tail row (ISSUE 15): the same 3-member fleet with one
    # straggler, hedge off vs on — the p99 delta is the hedged-dispatch
    # feature, next to fleet_scaling's throughput story
    if os.environ.get("BENCH_FLEET_TAIL",
                      os.environ.get("BENCH_FLEET", "1")) != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 60.0:
            print("bench: skipping fleet_tail (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["fleet_tail"] = None
        else:
            res = run_fleet_tail_stage(min(stage_timeout, remaining))
            matrix["fleet_tail"] = res
            print("bench config fleet_tail: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # autoscale flash row (ISSUE 16): the same open-loop flash crowd,
    # autoscaler off vs on — the miss-rate delta and the member-count
    # trace are the elastic-capacity feature next to fleet_scaling's
    # static-membership story
    if os.environ.get("BENCH_AUTOSCALE",
                      os.environ.get("BENCH_FLEET", "1")) != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 60.0:
            print("bench: skipping autoscale_flash (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["autoscale_flash"] = None
        else:
            res = run_autoscale_flash_stage(min(stage_timeout, remaining))
            matrix["autoscale_flash"] = res
            print("bench config autoscale_flash: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # analysis-cache row (ISSUE 17): a Zipf position stream replayed
    # cache-off vs cache-on — the warm-vs-cold positions/s ratio is
    # the memoization feature next to serve_latency's cold-path story
    if os.environ.get("BENCH_CACHE", "1") != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 60.0:
            print("bench: skipping cache_zipf (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["cache_zipf"] = None
        else:
            res = run_cache_zipf_stage(min(stage_timeout, remaining))
            matrix["cache_zipf"] = res
            print("bench config cache_zipf: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # mesh scaling row (partition-rule registry): one registry-driven
    # engine over 1/2/4/8 virtual devices at width 8*ndev, same multipv
    # workload — positions-per-shard-step scaling is the pod-slice
    # story next to fleet_scaling's many-engines story
    if os.environ.get("BENCH_MESH_SCALING", "1") != "0":
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 120.0:
            print("bench: skipping mesh_scaling (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["mesh_scaling"] = None
        else:
            res = run_mesh_scaling_stage(min(stage_timeout * 2, remaining))
            matrix["mesh_scaling"] = res
            print("bench config mesh_scaling: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)

    # cold-start A/B row (AOT program assets, round 13): time-to-first-
    # result of a fresh engine subprocess, plain JIT vs a pre-packed
    # bundle. Opt-in (BENCH_COLDSTART=1) — the pack leg recompiles the
    # full program set once more, which a tight-budget ramp shouldn't pay
    if os.environ.get("BENCH_COLDSTART", "0") not in ("", "0", "false",
                                                      "no"):
        remaining = total_budget - (time.monotonic() - t_start)
        if remaining < 120.0:
            print("bench: skipping cold_start (budget spent)",
                  file=sys.stderr, flush=True)
            matrix["cold_start"] = None
        else:
            res = run_coldstart_stage(min(stage_timeout * 2, remaining))
            matrix["cold_start"] = res
            print("bench config cold_start: "
                  + (json.dumps(res) if res else "FAILED"),
                  file=sys.stderr, flush=True)
    if matrix:
        try:
            with open(os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "bench_matrix.json"), "w") as f:
                json.dump({"headline": best, "configs": matrix}, f, indent=1)
        except OSError as e:
            print(f"bench: could not write bench_matrix.json: {e}",
                  file=sys.stderr, flush=True)

    cores = os.cpu_count() or 1
    baseline = 400_000 * cores  # reference NPS prior × host cores
    headline = {
        "metric": (
            f"batched alpha-beta+NNUE nodes/sec/chip "
            f"(B={best['B']}, depth={best['depth']}, "
            f"platform={best['platform']}, "
            f"row_mode={best.get('row_mode', 'scatter')})"
        ),
        "value": round(best["nps"]),
        "unit": "nodes/sec",
        "vs_baseline": round(best["nps"] / baseline, 4),
    }
    # perf ledger (obs/perf.py, docs/perf.md): every RESULT row of this
    # run becomes ledger history, and the next BENCH_rNN.json artifact
    # is emitted from the ledger — build-info + env fingerprint attached
    results = {"headline": {"value": headline["value"],
                            "vs_baseline": headline["vs_baseline"]},
               "ramp_best": best}
    results.update({k: v for k, v in matrix.items() if v is not None})
    _ledger_record(results, source="bench", emit=True)
    print(json.dumps(headline))


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--stage":
        stage_main(
            int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
            *(sys.argv[5:7] or ()),
        )
    elif len(sys.argv) >= 2 and sys.argv[1] == "--mesh-scaling-stage":
        mesh_scaling_child(int(sys.argv[2]))
    elif os.environ.get("BENCH_GATE") == "1":
        gate_main()
    else:
        main()
