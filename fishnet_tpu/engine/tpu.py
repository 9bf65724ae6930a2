"""The TPU batch engine: chunks in, PositionResponses out.

Replaces the reference's engine subprocess + UCI dialogue (reference:
src/stockfish.rs:222-465) with a host→device dispatch: all positions of a
chunk (and all multipv root moves) become lanes of one lockstep
alpha-beta search. Iterative deepening runs host-side, filling the same
multipv×depth score/pv matrices the UCI parser would have accumulated.

Lane counts are padded to fixed buckets so XLA compiles a handful of
program shapes, then caches.
"""
from __future__ import annotations

import asyncio
import contextlib
import sys
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..chess.position import Position
from ..chess.variants import from_fen
from ..client.ipc import Chunk, Matrix, PositionResponse, WorkPosition
from ..client.wire import AnalysisWork, MoveWork, Score
from ..models import nnue
from ..ops import search as search_ops
from ..ops import tt as tt_mod
from ..ops.board import (
    from_position,
    position_fields,
    stack_boards,
    stack_fields,
)
from ..obs import inflight as obs_inflight
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..ops.search import INF, MATE, search_batch_resumable
from ..utils import sanitize
from ..utils import settings
from ..utils import syncstats
from ..utils.syncstats import SyncStats
from .base import EngineError, require_accelerator
from .session import ChunkSubmit

# static stack depth; supports search depths up to MAX_PLY-1, with the
# tail past the nominal depth doubling as quiescence headroom (32 leaves
# depth-22 move jobs 10 QS plies — reference skill-8 depth, src/api.rs:275-281).
# Env-tunable because compile cost scales with it: tests and CPU smoke runs
# set a small value (the full program takes minutes to compile on XLA:CPU)
MAX_PLY = settings.get_int("FISHNET_TPU_MAX_PLY")
# 16 covers every single-pv chunk (planner emits ≤10 positions per chunk,
# incl. skip-overlap re-appends — client/planner.py); 64 covers multipv
# root-move lanes. Fewer buckets = fewer cold XLA compiles to warm up.
LANE_BUCKETS = (16, 64, 128, 256)

# aspiration window half-widths tried in order by _search_windowed (the
# final full-width attempt is implicit). Measured on the standard 8-FEN
# set at depth 5 via aspiration_stats (docs/depth.md §"Aspiration
# deltas, measured"): (15, 120) searched the fewest total nodes of the
# six schedules tried — a narrow first rung fails ~2/3 of the time but
# the windowed tree it cuts outweighs the re-searches, and the 120 rung
# catches 90% of the escapees. The old hardcoded (30, 200) measured ~5%
# more nodes; wider schedules up to (60, 250) measured ~9-14% more.
ASPIRATION_DELTAS = settings.get_csv_int("FISHNET_TPU_ASPIRATION") or (15, 120)


def _decode_uci(m: int) -> str:
    frm, to, promo = m & 63, (m >> 6) & 63, (m >> 12) & 7
    if (m >> 15) & 1:  # crazyhouse drop: P@e4 style
        return "PNBRQ"[promo & 7] + "@" + "abcdefgh"[to & 7] + str((to >> 3) + 1)
    s = (
        "abcdefgh"[frm & 7] + str((frm >> 3) + 1)
        + "abcdefgh"[to & 7] + str((to >> 3) + 1)
    )
    if promo:
        s += " nbrqk"[promo]  # 5 = king (antichess promotion)
    return s


# chunk.variant → device search program (ops/search.py static flag);
# variants not listed fall back to host engines via the planner routing.
# All seven lichess variants the reference analyses (src/logger.rs:201-213)
# run on device.
DEVICE_VARIANTS = {
    "standard": "standard",
    "chess960": "standard",
    "fromPosition": "standard",
    "threeCheck": "threeCheck",
    "3check": "threeCheck",
    "crazyhouse": "crazyhouse",
    "antichess": "antichess",
    "atomic": "atomic",
    "horde": "horde",
    "kingOfTheHill": "kingOfTheHill",
    "racingKings": "racingKings",
}


def _position_keys(positions, variant: str):
    """(h1, h2) uint32 arrays, one entry a Position: `tt.hash_board`'s
    keys computed on the host, no device put and no fetch."""
    b = stack_fields([position_fields(p) for p in positions])
    return tt_mod.hash_boards_host(
        b.board, b.stm, b.ep, b.castling, b.extra, variant)


def _fill_history_row(hh, hm, h1, h2, halfmoves, keep_last=0):
    """One lane's repetition-history rows (`hh` (MAX_HIST, 2), `hm`
    (MAX_HIST,), written in place) from its tail's keys and halfmove
    clocks, oldest first, right-aligned; only positions occurring >=2x
    in the tail stay planted, the last keep_last slots always do (see
    `TpuEngine._history_arrays`)."""
    n = len(halfmoves)
    if not n:
        return
    lo = search_ops.MAX_HIST - n
    hh[lo:, 0], hh[lo:, 1] = h1, h2
    hm[lo:] = halfmoves
    filled = hm != search_ops.HIST_HM_SENTINEL
    pairs = [tuple(hh[k]) for k in range(search_ops.MAX_HIST)]
    for k in range(search_ops.MAX_HIST - keep_last):
        if filled[k] and pairs.count(pairs[k]) < 2:
            hm[k] = search_ops.HIST_HM_SENTINEL
            hh[k] = 0


class _ChunkReplay:
    """A chunk's positions replayed from their root FENs, each line of
    play walked once.

    A chunk is mostly consecutive plies of one game (the planner's
    tiling), so each position's moves are a prefix of the deepest one's:
    replaying every position from its root validates the shared prefix
    again and again. Here positions with the same root whose moves are
    a prefix of a longer position's share that position's walk; a
    position that shares nothing is a line of its own. Every move is
    validated by `Position.parse_uci` as a per-position replay would,
    when the first position (in chunk order) that needs it asks, so an
    illegal move raises the same `IllegalMoveError` at the same
    position. `parsed` counts the moves validated."""

    class _Line:
        __slots__ = ("root_fen", "moves", "boards", "legal")

        def __init__(self, root_fen: str, moves: tuple):
            self.root_fen = root_fen
            self.moves = moves  # the deepest member's moves
            self.boards: List[Position] = []  # boards[k]: after k moves
            self.legal: dict = {}  # k -> legal moves of boards[k]

        def legal_at(self, k: int):
            if k not in self.legal:
                self.legal[k] = self.boards[k].legal_moves()
            return self.legal[k]

    def __init__(self, positions, variant: str):
        self.variant = variant
        self.parsed = 0  # moves validated by the walks
        self._moves = [tuple(wp.moves) for wp in positions]
        self._line_of: List[_ChunkReplay._Line] = [None] * len(positions)
        by_root: dict = {}
        # longest first: a position joins the first line it is a prefix of
        for i in sorted(range(len(positions)),
                        key=lambda i: -len(self._moves[i])):
            mv = self._moves[i]
            lines = by_root.setdefault(positions[i].root_fen, [])
            line = next((ln for ln in lines if ln.moves[:len(mv)] == mv),
                        None)
            if line is None:
                line = self._Line(positions[i].root_fen, mv)
                lines.append(line)
            self._line_of[i] = line

    def _walk(self, i: int):
        """Position i's line, walked (and validated) at least as deep as
        position i's moves, and that depth."""
        line = self._line_of[i]
        if not line.boards:
            line.boards.append(from_fen(line.root_fen, self.variant))
        n = len(self._moves[i])
        while len(line.boards) <= n:
            k = len(line.boards) - 1
            board = line.boards[k]
            move = board.parse_uci(line.moves[k], line.legal_at(k))
            line.boards.append(board.push(move))
            self.parsed += 1
        return line, n

    def position(self, i: int):
        """(board after position i's moves, its legal moves where the
        walk needs them anyway for a deeper position, else None) — the
        list `Position.outcome(legal_moves=...)` takes."""
        line, n = self._walk(i)
        legal = line.legal_at(n) if n < len(line.moves) else None
        return line.boards[n], legal

    def game(self, i: int) -> List[Position]:
        """The boards before position i, oldest first."""
        line, n = self._walk(i)
        return line.boards[:n]

    def history(self, idxs, variant: str):
        """(hh, hm) of each position in idxs, as
        `TpuEngine._history_arrays([game(i)], 1, variant)` gives them,
        with the keys hashed once for each line over the boards its
        positions' tails cover."""
        def tail(i):
            line, n = self._walk(i)
            return line, range(max(0, n - search_ops.MAX_HIST), n)

        covered: dict = {}  # line -> the board indices its tails cover
        for i in idxs:
            line, ks = tail(i)
            covered.setdefault(line, set()).update(ks)
        keys = {}  # line -> (board index -> row, h1, h2)
        for line, ks in covered.items():
            if ks:
                ks = sorted(ks)
                h1, h2 = _position_keys([line.boards[k] for k in ks], variant)
                keys[line] = ({k: j for j, k in enumerate(ks)}, h1, h2)
        out = []
        for i in idxs:
            line, ks = tail(i)
            hh = np.zeros((search_ops.MAX_HIST, 2), np.uint32)
            hm = np.full(search_ops.MAX_HIST, search_ops.HIST_HM_SENTINEL,
                         np.int32)
            if ks:
                row, h1, h2 = keys[line]
                js = [row[k] for k in ks]
                _fill_history_row(hh, hm, h1[js], h2[js],
                                  [line.boards[k].halfmove for k in ks])
            out.append((hh, hm))
        return out


def _score_from_int(v: int, root_ply_to_mate_sign: int = 1) -> Score:
    if v >= MATE - 1000:
        return Score.mate((MATE - v + 1) // 2)
    if v <= -(MATE - 1000):
        return Score.mate(-((MATE + v + 1) // 2))
    return Score.cp(int(v))


def skill_pick(ranked, sf_skill: int, rng):
    """Pick a (score, idx) entry from descending-ranked root moves with
    lichess skill semantics (the TPU-native analog of Stockfish's "Skill
    Level", reference src/api.rs:248-283 maps level 1-8 → Skill Level):
    below full strength the move is drawn from the near-best candidates
    with probability decaying in the cp gap, the acceptance window
    (120 - 2*skill) widening as skill drops. Shared by the engine's move
    jobs and tools/strength_ab.py's skill-vs-skill validation."""
    import math

    top = ranked[0][0]
    if sf_skill >= 20 or len(ranked) == 1:
        return ranked[0]
    weakness = 120 - 2 * sf_skill
    cands = [r for r in ranked if top - r[0] <= 3 * weakness]
    weights = [math.exp(-(top - r[0]) / weakness) for r in cands]
    return rng.choices(cands, weights=weights, k=1)[0]


def _move_job_floor(variant: str) -> int:
    """Minimum move-job lane count per variant — MUST match what
    warmup_variants precompiles, or the first job pays a cold compile
    against its 7 s deadline. Crazyhouse drops push legal counts past
    64, so its bucket is 128."""
    return 128 if variant == "crazyhouse" else 64


# the boundary phases of LaneScheduler._drive_session ("wait" and
# "other" are SyncStats' own: blocked in fetch(), covered by no phase)
PHASES = ("reap", "admit", "refill", "dispatch", "wait", "lanes", "pv",
          "account", "other")
# where a program can be built from, as `compiles_<where>` counts it
# (aot/registry.py's compile listener reads the thread's
# syncstats.where(); any other phase or step counts as "other")
COMPILE_SITES = ("refill", "dispatch", "pv", "session_setup",
                 "submit_history", "other")

# what a lane of a drive session is at a segment's dispatch (_Session.role):
# free — no job's, and last seen DONE, so it stays DONE until a splice;
# primary / helper — a job's; stray — no job's, but not yet seen DONE:
# the session's base lanes before its first boundary, a helper whose
# position was finalized, a lane cut at its position's deadline. The
# device does not know the host let a stray lane go: it searches on
# until it parks, and holds the segment open meanwhile.
ROLE_FREE, ROLE_PRIMARY, ROLE_HELPER, ROLE_STRAY = range(4)
# a segment's lane-steps by those roles, from the summary's run column
# (ops/search.py SUM_RUN): the lane-steps of primary and helper lanes,
# the steps each kind of lane ran, the steps the segment ran after its
# last primary lane parked, dispatches that ran no step at all, and
# segments that the watched-lane stop ended (ops/search.py _run_segment:
# the session watches its primaries)
LANE_STEP_COUNTERS = (
    "lane_steps_assigned", "lane_steps_primary_run",
    "lane_steps_helper_run", "lane_steps_stray_run", "lane_steps_free_run",
    "steps_after_last_primary", "segments_empty", "segments_primary_stop",
)


def _lane_step_counts(n: int, run: np.ndarray, roles: np.ndarray,
                      done: np.ndarray, cap: int) -> dict:
    """One segment's LANE_STEP_COUNTERS: n its steps, run the (B,)
    steps each lane was not DONE, roles the (B,) ROLE_* at dispatch,
    done the (B,) DONE flags at its end, cap its step limit. A segment
    stopped at its last primary when it ran, some lane still runs, every
    primary is DONE and the cap did not bind."""
    primary = roles == ROLE_PRIMARY
    helper = roles == ROLE_HELPER
    return {
        "lane_steps_assigned": n * int(np.count_nonzero(primary | helper)),
        "lane_steps_primary_run": int(run[primary].sum()),
        "lane_steps_helper_run": int(run[helper].sum()),
        "lane_steps_stray_run": int(run[roles == ROLE_STRAY].sum()),
        "lane_steps_free_run": int(run[roles == ROLE_FREE].sum()),
        "steps_after_last_primary":
            n - int(run[primary].max()) if primary.any() else 0,
        "segments_empty": int(n == 0),
        "segments_primary_stop": int(
            0 < n < cap and bool(primary.any()) and bool(done[primary].all())
            and not done.all()),
    }


# what the `session` span's args add up over the session
SESSION_TOTALS = ("segments", "steps", "positions_done") + LANE_STEP_COUNTERS + (
    "position_queue_ms", "position_engine_ms", "positions_finalized")


def _pad_lanes(n: int) -> int:
    for b in LANE_BUCKETS:
        if n <= b:
            return b
    return ((n + 255) // 256) * 256


class TpuEngine(ChunkSubmit):
    """Batched analysis engine. `variants` lists what it accepts (the
    planner routes only those here — client/planner.py tpu_variants)."""

    def __init__(
        self,
        params: Optional[nnue.NnueParams] = None,
        weights_path: Optional[str] = None,
        # a Stockfish deployment's second net (upstream's EvalFileSmall):
        # with a .nnue weights_path, both are resident and every eval
        # chooses between them (models/nnue_import.py DualNetInt)
        weights_small_path: Optional[str] = None,
        max_depth: int = 12,  # production value flows from configure.tpu_depth
        seed: int = 1234,
        tt_size_log2: int = 21,  # 2M slots ≈ 24 MiB HBM; 0 disables
        max_lanes: Optional[int] = None,  # single-dispatch lane ceiling
        helper_lanes: Optional[int] = None,  # Lazy-SMP lanes per position (K)
        refill: Optional[bool] = None,  # continuous lane refill (LaneScheduler)
        mesh_refill: Optional[bool] = None,  # refill on mesh hosts too
        logger=None,  # client Logger for operational warnings; stderr if None
    ) -> None:
        from ..obs import perf as obs_perf
        from ..utils import enable_compile_cache

        enable_compile_cache()  # restarts reuse compiled search programs
        # all chips on the host run one sharded program: lanes shard over a
        # 1-D mesh and each device advances its shard independently — the
        # TPU equivalent of the reference's engine-process-per-core
        # (src/main.rs:151-161). Single-device hosts skip the mesh.
        from ..parallel import distributed as dist_mod
        from ..parallel.mesh import make_mesh, make_sharded_table

        # FISHNET_TPU_MESH_HOSTS > 1: join the jax.distributed pod
        # BEFORE the first jax.devices() call, so the mesh below spans
        # the global device set — one logical engine across processes
        dist_mod.ensure_initialized(logger=logger)
        # this process owns the engine, so it is the one that may ask
        # JAX what it runs on; a supervised parent reads this off the
        # host's ready frame (engine/host.py)
        self.device = obs_perf.claim_device()
        # fail before anything is built: JAX falls back to XLA:CPU in
        # silence when it finds no chip
        require_accelerator(self.device["platform"])
        n_dev = self.device["count"]
        self.mesh = make_mesh() if n_dev > 1 else None
        self.n_dev = n_dev if self.mesh is not None else 1
        # one shared transposition table for every lane and every chunk —
        # the per-process persistent hash (reference: Stockfish's TT,
        # ~64 MiB/core README.md:76). Sharded per device under the mesh.
        # Chunks are dispatched one at a time (self._lock): concurrent
        # executor threads would otherwise interleave whole-table swaps
        # and silently discard each other's stores.
        self.tt_size_log2 = tt_size_log2
        if not tt_size_log2:
            self.tt = None
        elif self.mesh is not None:
            self.tt = make_sharded_table(self.mesh, tt_size_log2)
        else:
            self.tt = tt_mod.make_table(tt_size_log2)
        self._lock = threading.Lock()
        # what the nets were read from: the analysis cache keys on both
        # (cache/keys.py engine_identity)
        self.weights_path = weights_path
        self.weights_small_path = weights_small_path
        if weights_small_path and not (
                params is None and str(weights_path or "").endswith(".nnue")):
            raise ValueError(
                "a small net (weights_small_path) goes beside a big .nnue "
                "net read from weights_path")
        if params is None:
            if weights_path and str(weights_path).endswith(".nnue"):
                # real Stockfish network file (models/nnue_import.py),
                # searched in the integers it holds
                from ..models import nnue_import

                params = nnue_import.load_nnue(weights_path)
                if weights_small_path:
                    params = nnue_import.DualNetInt.from_nets(
                        params, nnue_import.load_nnue(weights_small_path))
                params = params.as_device()
            elif weights_path:
                params = nnue.load_params(weights_path)
            else:
                # packaged weights (assets.py); board768 = the
                # fully-incremental fast path (see models/nnue.py)
                from ..assets import load_default_params

                params = load_default_params("board768")
            if params is None:
                params = nnue.init_params(
                    jax.random.PRNGKey(seed), l1=64, feature_set="board768"
                )
        self._logger = logger
        # AOT program assets (fishnet_tpu/aot/): when a packed bundle
        # matches this process's fingerprint, the wrapped search jits
        # load serialized executables instead of compiling, and warmup
        # below becomes a no-op. Install is idempotent process-wide.
        from ..aot import registry as aot_registry

        self.aot = aot_registry.install_from_settings(logger=self._warn)
        # FISHNET_TPU_DTYPE quantizes the weights (SURVEY §7.2):
        # bf16 → MXU-native float inputs, f32 accumulators. The int8
        # fixed-point ladder (nnue.quantize_int8) measured a NET LOSS at
        # the production shape (round 5, bench_matrix.json dtype_int8:
        # 37.2 knps vs 58-95 knps f32 — int32 dots keep the MXU idle),
        # so it survives only as an experiment behind an extra flag.
        dtype_env = (settings.get_str("FISHNET_TPU_DTYPE") or "").lower()
        if dtype_env in ("bf16", "bfloat16"):
            # refuses an integer net (a .nnue file): ValueError
            params = nnue.cast_params(params, jnp.bfloat16)
        elif dtype_env == "int8":
            if settings.get_bool("FISHNET_TPU_EXPERIMENTAL_INT8"):
                self._warn(
                    "experimental int8 weights enabled: measured SLOWER "
                    "than f32 at production shapes (37.2 vs 58-95 knps, "
                    "round-5 bench)"
                )
                if nnue.is_board768(params):
                    params = nnue.quantize_int8(params)
            else:
                self._warn(
                    "FISHNET_TPU_DTYPE=int8 ignored: measured a net loss "
                    "vs f32 (37.2 vs 58-95 knps); set "
                    "FISHNET_TPU_EXPERIMENTAL_INT8=1 to run it anyway"
                )
        self.params = params
        # what the eval computes in ("int" for a .nnue file's integers):
        # the session span and the analysis cache's identity name it
        self.arithmetic = nnue.arithmetic(params)
        self.max_depth = max_depth
        # B=2048 falls off the VMEM cliff on v5e (docs/tpu-hang.md round 5:
        # ~1024 lanes is the ceiling) — never let one dispatch exceed it;
        # multipv is the only shape that can (every legal root move of
        # every chunk position becomes a lane)
        self.max_lanes = (
            max_lanes
            if max_lanes is not None
            else settings.get_int("FISHNET_TPU_MAX_LANES")
        )
        # Lazy-SMP helper lanes (docs/profile-r5.md §"Batch completion of
        # deep searches"): an analysed position may occupy up to K lanes —
        # one PRIMARY whose score/PV is the reported result (oracle
        # semantics intact), plus up to K-1 HELPERS searching the same
        # root with jittered move ordering, staggered aspiration windows
        # and +1-ply depth offsets, communicating only through the shared
        # TT. K=1 disables the machinery entirely and is bit-identical to
        # the pre-helper engine; no TT forces K=1 (helpers without the
        # communication channel are pure waste).
        if helper_lanes is None:
            helper_lanes = settings.get_int("FISHNET_TPU_HELPERS")
        self.helper_lanes = max(1, min(int(helper_lanes), 16))
        if self.tt is None:
            self.helper_lanes = 1
        # TT generation counter, bumped per chunk: helper-mode stores
        # carry it so depth-preferred replacement never protects stale
        # entries from earlier chunks (ops/tt.py store)
        self._tt_gen = 0
        # Continuous lane refill (continuous batching from LLM serving,
        # Orca OSDI'22, mapped onto search lanes): single-pv analysis
        # chunks flow through the LaneScheduler, which keeps one
        # full-width compiled step busy by splicing queued positions
        # into DONE lanes at segment boundaries instead of narrowing
        # and draining chunks serially. On mesh hosts the scheduler
        # drives the shard_map'd segment/refill callables
        # (parallel/mesh.py): each device resplices ITS lanes locally
        # and the boundary is one stacked-summary fetch, so the same
        # occupancy win extends across chips. FISHNET_TPU_MESH_REFILL=0
        # pins meshed engines back to strict chunk-serial dispatch
        # (single-device hosts ignore it); everything else — move jobs,
        # multipv, refill off — takes the chunk-serial path, which
        # stays bit-identical to the pre-refill engine.
        if refill is None:
            refill = settings.get_bool("FISHNET_TPU_REFILL")
        self.refill = bool(refill)
        if mesh_refill is None:
            mesh_refill = settings.get_bool("FISHNET_TPU_MESH_REFILL")
        self.mesh_refill = bool(mesh_refill)
        self._scheduler = LaneScheduler(self)
        # per-segment occupancy accounting (live/helper/idle lane
        # counts, refill events), surfaced into bench rows and logs
        self.occupancy_log: List[dict] = []
        self.occupancy_totals = {
            "segments": 0, "steps": 0, "lane_steps": 0,
            "live_lane_steps": 0, "helper_lane_steps": 0,
            "idle_lane_steps": 0, "positions_done": 0,
            # lanes spliced, and the boundaries that spliced any
            "refills": 0, "refill_splices": 0,
            # segment-boundary cost split (utils/syncstats.py): wall-clock
            # the host spent blocked on device results vs doing boundary
            # bookkeeping, plus the host-device transfer count
            "host_ms": 0.0, "device_ms": 0.0, "transfers": 0,
            # counted on the device, in the segment's loop carry, and
            # read from the boundary summary's last rows: node expansions,
            # the moves their lists hold, the drops among those; the
            # accumulator's work; which of two nets answered (search_ops
            # SEGMENT_COUNTERS)
            **{name: 0 for name in search_ops.SEGMENT_COUNTERS},
            # the same segments' lane-steps by the role each lane had at
            # dispatch (LANE_STEP_COUNTERS), the speculative dispatches
            # that found every lane parked, and the segments that ended
            # when their last primary lane parked
            **{name: 0 for name in LANE_STEP_COUNTERS},
            # the same boundary intervals by what the host was doing
            # (SyncStats.phase): sums to host_ms + device_ms, and
            # phase_wait_ms is device_ms
            **{f"phase_{name}_ms": 0.0 for name in PHASES},
            # drive sessions (wall inside _drive_session; set-up is
            # entry to the first boundary interval, tail the last
            # boundary to return) and the time between them: some
            # thread inside _submit / the driver waiting for the engine
            # lock / work queued but nobody driving (the 50 ms poll of
            # run_chunk) / nothing submitted at all — the caller's time
            "sessions": 0, "session_ms": 0.0, "session_setup_ms": 0.0,
            "session_tail_ms": 0.0,
            "gap_ms": 0.0, "gap_submit_ms": 0.0, "gap_lock_ms": 0.0,
            "gap_handoff_ms": 0.0, "gap_starved_ms": 0.0,
            # the submit path, per chunk and position: game-prefix
            # replay and history hash, both on the host, TT warm
            "chunks_submitted": 0, "positions_submitted": 0,
            "submit_ms": 0.0, "submit_replay_ms": 0.0,
            "submit_history_ms": 0.0, "submit_ttwarm_ms": 0.0,
            # Σ moves of the submitted positions, and the moves the
            # chunk's line walks validated (_ChunkReplay): a chunk of
            # consecutive plies validates its shared prefix once
            "submit_prefix_moves": 0, "submit_moves_parsed": 0,
            # per finalized position: queued (the job made in _submit)
            # to its first lane, and queued to _finalize
            "position_queue_ms": 0.0, "position_engine_ms": 0.0,
            "positions_finalized": 0,
            # programs built or loaded while serving a chunk, by where
            **{f"compiles_{site}": 0 for site in COMPILE_SITES},
            "compile_ms": 0.0,
        }
        # per-delta aspiration accounting {delta: [windowed, fail_lo,
        # fail_hi, nodes]} — the measured basis for ASPIRATION_DELTAS
        # (see docs/depth.md §"Aspiration deltas, measured")
        self.aspiration_stats: dict = {}
        # exactly-once delivery hook: called as (wp, response) the moment
        # a position's result is finalized, before the chunk completes.
        # engine/host.py points this at its `partial` frame emitter so
        # the supervisor's session journal sees incremental progress.
        self.on_response = None
        # chunk-aware sibling of on_response, called as (chunk, wp,
        # response) from the same exactly-once delivery point: the
        # analysis cache (fishnet_tpu/cache/) fills from here, so
        # speculative, replayed and re-dispatched results populate it
        # once — the chunk carries the variant/work shape the cache key
        # needs and the bare WorkPosition doesn't.
        self.on_deliver = None
        # TT warm slices (cache/ttwarm.py, FISHNET_TPU_CACHE_TT):
        # when set, _submit splices persisted opening-prefix TT rows
        # into the shared table before the chunk's refill jobs run, and
        # run_chunk exports the rows the search earned back out.
        self.tt_warm = None
        self.tt_warm_prefix = 8

    def _warn(self, msg: str) -> None:
        if self._logger is not None:
            self._logger.warn(msg)
        else:
            print(f"W: {msg}", file=sys.stderr, flush=True)

    def warmup(self, buckets=None, log=None, deep=None) -> List[str]:
        """Pre-compile the hot search program for every production lane
        bucket.

        XLA caches one program per (lane bucket, MAX_PLY) shape; without
        this, the first chunk of a new shape pays 20-40 s of compile
        against its deadline (move jobs have a 7 s deadline — they would
        always fail cold; a first 128/256-lane multipv chunk used to race
        a cold compile too). The reference similarly does its engine prep
        before workers start (Assets::prepare, src/main.rs:94).
        FISHNET_TPU_WARMUP_BUCKETS="16" overrides (e.g. CPU smoke runs
        where each extra compile costs minutes). log: optional callable
        for per-bucket progress lines. deep: compile the distinct
        deep-TT move-job program too; default None = only for the
        untrimmed production bucket set (explicit-bucket callers that
        will serve move jobs must pass deep=True — the program is
        REQUIRED before the first 7 s-deadline move job)."""
        import time as _time

        # an explicitly trimmed set — env var OR caller-supplied buckets
        # (CPU smoke runs/tests) — skips the extra deep_tt program below;
        # only the no-argument production default pays for full prep
        trimmed = buckets is not None
        if buckets is None:
            buckets = (
                settings.get_csv_int("FISHNET_TPU_WARMUP_BUCKETS")
                or LANE_BUCKETS
            )
            trimmed = settings.is_set("FISHNET_TPU_WARMUP_BUCKETS")
        want_deep = deep if deep is not None else not trimmed
        covered = ["buckets"] + (["deep"] if want_deep else [])
        # AOT bundle covering exactly what this warmup would compile:
        # skip it — the wrapped jits load serialized executables at
        # first dispatch in milliseconds instead of compiling here.
        from ..aot import registry as aot_registry

        if aot_registry.warm_covers(*covered):
            rep = aot_registry.boot_report()
            if log is not None:
                log(
                    f"warmup: skipped — AOT bundle {rep.get('fingerprint')} "
                    f"preloads {rep.get('programs')} programs (covers "
                    f"{','.join(rep.get('covers') or [])}); executables "
                    f"load at first dispatch"
                )
            return covered
        for b in buckets:
            b = self._pad(b)
            t0 = _time.monotonic()
            roots = stack_boards([from_position(Position.initial())] * b)
            # with helper lanes enabled, every production analysis
            # dispatch compiles the helper-mode program (prefer_deep
            # stores are a static flag) — warm THAT variant, or the
            # first chunk pays the cold compile anyway
            self._search(
                roots, np.ones(b, np.int32), np.full(b, 64, np.int32),
                helper_store=self.helper_lanes > 1,
            )
            if log is not None:
                log(
                    f"warmup: {b}-lane search program compiled "
                    f"({_time.monotonic() - t0:.1f}s)"
                )
        # move jobs run a DISTINCT program (deep-bounds TT probes are a
        # static compile flag) at the 64-lane root-move bucket — without
        # this the first move job pays a cold compile against its 7 s
        # deadline and always fails. Skipped by default whenever the
        # bucket set was trimmed (env var or explicit caller buckets —
        # usually a CPU smoke run/test that serves no move jobs and
        # where each extra compile costs minutes).
        if not want_deep:
            return covered
        b = self._pad(64)  # root-move lanes pad to 64 for ≤64 legal moves
        t0 = _time.monotonic()
        roots = stack_boards([from_position(Position.initial())] * b)
        self._search(
            roots, np.ones(b, np.int32), np.full(b, 64, np.int32),
            deep_tt=True,
        )
        if log is not None:
            log(
                f"warmup: {b}-lane move-job program compiled "
                f"({_time.monotonic() - t0:.1f}s)"
            )
        return covered

    def warmup_variants(self, log=None) -> List[str]:
        """Compile the per-variant search programs (each variant is a
        distinct statically compiled program — a cold compile at the
        first variant chunk would race its deadline; move jobs' 7 s
        deadline always loses that race). Meant to run in the background
        AFTER the standard warmup. Runs WITHOUT the engine lock against
        a scratch TT of the production shape: holding the serving lock
        across a 20-40 s compile would stall a live move job past its
        7 s deadline before its own clock even started (XLA's compile
        cache is process-wide, so the compiled program still serves the
        live table).

        FISHNET_TPU_WARMUP_VARIANTS: comma list, "all", or "none";
        default warms all device variants on real accelerators and none
        on CPU (where each extra compile costs minutes — tests and smoke
        runs)."""
        import time as _time

        env = settings.get_str("FISHNET_TPU_WARMUP_VARIANTS") or "auto"
        if env.lower() == "auto":
            if jax.default_backend() == "cpu":
                return []
            variants = sorted(set(DEVICE_VARIANTS.values()) - {"standard"})
        elif env.lower() in ("", "none"):
            return []
        elif env.lower() == "all":
            variants = sorted(set(DEVICE_VARIANTS.values()) - {"standard"})
        else:
            variants = [v for v in env.split(",") if v]
        from ..aot import registry as aot_registry

        if aot_registry.warm_covers("variants"):
            if log is not None:
                log(
                    "warmup: variant programs covered by the AOT bundle; "
                    "background compiles skipped"
                )
            return []
        for variant in variants:
            # 16 lanes / exact-depth probes: analysis chunks.
            # _move_job_floor lanes / deep-bounds probes: move-job
            # root-move lanes (the reference routes ALL move jobs to the
            # variant engine, src/queue.rs:562-568, so this is the
            # deadline-critical one)
            for b, deep in ((16, False), (_move_job_floor(variant), True)):
                b = self._pad(b)
                t0 = _time.monotonic()
                start = from_fen(
                    {
                        "crazyhouse": (
                            "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR[] "
                            "w KQkq - 0 1"
                        ),
                        "horde": (
                            "rnbqkbnr/pppppppp/8/1PP2PP1/PPPPPPPP/PPPPPPPP/"
                            "PPPPPPPP/PPPPPPPP w kq - 0 1"
                        ),
                        "racingKings": "8/8/8/8/8/8/krbnNBRK/qrbnNBRQ w - - 0 1",
                    }.get(variant, "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"),
                    variant,
                )
                roots = stack_boards([from_position(start)] * b)
                self._search(
                    roots, np.ones(b, np.int32), np.full(b, 64, np.int32),
                    variant=variant, deep_tt=deep,
                    # a fresh scratch per dispatch: segment dispatches
                    # DONATE the table (ops/search.py), so a shared
                    # scratch would be consumed by the first search
                    tt_override=self._scratch_tt(),
                    # analysis dispatches run the helper-mode program
                    # when helper lanes are on; move jobs stay plain
                    helper_store=(not deep) and self.helper_lanes > 1,
                )
                if log is not None:
                    log(
                        f"warmup: {variant} {b}-lane program compiled "
                        f"({_time.monotonic() - t0:.1f}s)"
                    )
        return variants

    async def go_multiple(self, chunk: Chunk) -> List[PositionResponse]:
        loop = asyncio.get_running_loop()
        try:
            return await loop.run_in_executor(None, self._go_multiple_sync, chunk)
        except EngineError:
            raise
        except Exception as e:  # device/compile errors surface as EngineError
            raise EngineError(f"tpu engine failed: {e}") from e

    async def close(self) -> None:
        pass

    # ----------------------------------------------------------------- sync

    def _pad(self, n: int) -> int:
        b = _pad_lanes(n)
        if b % self.n_dev:
            b = ((b + self.n_dev - 1) // self.n_dev) * self.n_dev
        return b

    def _scratch_tt(self):
        """A throwaway table with the SAME shape as self.tt — warmup
        compiles the production program shapes against it without
        touching (or locking) the live table."""
        if self.tt is None:
            return None
        from ..parallel.mesh import make_sharded_table

        if self.mesh is not None:
            return make_sharded_table(self.mesh, self.tt_size_log2)
        return tt_mod.make_table(self.tt_size_log2)

    def _search(self, roots, depth_arr, budget_arr, deadline=None,
                variant="standard", hist=None, window=None,
                deep_tt=False, tt_override=None, order_jitter=None,
                group=None, required=None, helper_store=False):
        # the TT is shared across variants: variant state is hashed into
        # the key (ops/tt.py), so entries can't collide across rule sets.
        # tt_override: search against a caller-owned table (warmup
        # scratch) and leave self.tt alone — such calls don't need the
        # engine lock.
        # order_jitter/group/required: Lazy-SMP lane-group layout (see
        # search_batch_resumable); helper_store switches TT stores to the
        # depth-preferred generation-aware policy. helper_store is a
        # STATIC compile flag: it is set for ALL analysis dispatches
        # whenever helper lanes are enabled (multipv groups too, which
        # benefit from the same shallow-write protection) so warmup
        # compiles exactly one program per bucket either way.
        out = search_batch_resumable(
            self.params, roots, jnp.asarray(depth_arr),
            jnp.asarray(budget_arr), max_ply=MAX_PLY,
            deadline=deadline,
            tt=self.tt if tt_override is None else tt_override,
            mesh=self.mesh,
            variant=variant, hist=hist, window=window, deep_tt=deep_tt,
            order_jitter=order_jitter, group=group, required=required,
            prefer_deep_store=helper_store,
            tt_gen=self._tt_gen if helper_store else 0,
            # deep_tt = move jobs: their narrowed widths would be
            # deep-bounds programs warmup never compiled, and a cold XLA
            # compile inside the 7 s move deadline loses the job. Their
            # lanes are one position's root moves at uniform depth — they
            # finish together, so narrowing has nothing to retire anyway.
            # Analysis narrows through warmed widths only (LANE_BUCKETS
            # halvings land on LANE_BUCKETS members).
            narrow=not deep_tt,
        )
        if tt_override is None:
            self.tt = out.pop("tt")
        else:
            out.pop("tt")
        return {k: np.asarray(v) for k, v in out.items()}

    def _search_windowed(self, roots, depth_arr, budget_arr, deadline,
                         variant, hist, prev_score, use_win,
                         required=None, win_scale=None, order_jitter=None,
                         group=None, helper_store=False):
        """Aspiration-windowed dispatch (classic iterative-deepening win:
        a narrow window around the previous depth's score cuts most of
        the tree; a fail-low/high re-searches wider, settled lanes ride
        along at depth 0 / budget 1). Returns the merged result dict with
        per-lane nodes summed over attempts.

        Helper-lane extensions: `required` marks the primary lanes —
        only THEIR fail-low/high triggers a re-search (a helper failing
        its window costs nothing; its TT entries already landed), and
        the dispatch stops once all primaries finish. `win_scale` widens
        each lane's delta (staggered helper windows: a helper searching
        a wider window than its primary fails less and seeds EXACT
        entries the primary's re-search can use). Helpers ride along on
        the FIRST attempt only — re-search attempts are primary-only."""
        B = int(depth_arr.shape[0])
        deltas = ASPIRATION_DELTAS + (None,)  # None = full window
        primary = (
            np.ones(B, bool) if required is None
            else np.asarray(required, bool)
        )
        scale = (
            np.ones(B, np.int64) if win_scale is None
            else np.asarray(win_scale, np.int64)
        )
        merged = None
        nodes_acc = np.zeros(B, np.int64)
        live = np.ones(B, bool)
        prev_score = np.asarray(prev_score, np.int64)
        for delta in deltas:
            if delta is None or not use_win.any():
                alpha_w = np.full(B, -INF, np.int32)
                beta_w = np.full(B, INF, np.int32)
            else:
                # clip into [-INF, INF]: a clipped-to-INF bound reads as
                # no-window on that side (the fail checks below exclude it)
                alpha_w = np.where(
                    use_win, np.maximum(prev_score - delta * scale, -INF), -INF
                ).astype(np.int32)
                beta_w = np.where(
                    use_win, np.minimum(prev_score + delta * scale, INF), INF
                ).astype(np.int32)
            out = self._search(
                roots,
                np.where(live, depth_arr, 0).astype(np.int32),
                np.where(live, budget_arr, 1).astype(np.int32),
                deadline, variant=variant, hist=hist,
                window=(alpha_w, beta_w),
                order_jitter=order_jitter, group=group,
                required=required, helper_store=helper_store,
            )
            if merged is None:
                merged = {k: np.array(v) for k, v in out.items()}
            else:
                for k in ("score", "move", "pv", "pv_len", "done"):
                    merged[k][live] = out[k][live]
            nodes_acc[live] += out["nodes"][live]
            score = out["score"]
            fail_lo = (
                live & primary & out["done"]
                & (score <= alpha_w) & (alpha_w > -INF)
            )
            fail_hi = (
                live & primary & out["done"]
                & (score >= beta_w) & (beta_w < INF)
            )
            fail = fail_lo | fail_hi
            if delta is not None and use_win.any():
                st = self.aspiration_stats.setdefault(delta, [0, 0, 0, 0])
                st[0] += int((use_win & live & primary).sum())
                st[1] += int(fail_lo.sum())
                st[2] += int(fail_hi.sum())
                st[3] += int(out["nodes"][live].sum())
            # lanes that didn't finish (deadline) stay merged as not-done
            live = fail
            if not live.any():
                break
            if deadline is not None and time.monotonic() >= deadline:
                # fail-low/high lanes hold only a BOUND — without the
                # wider re-search it must not be reported as a score
                merged["done"][live] = False
                break
        merged["nodes"] = nodes_acc
        return merged

    @staticmethod
    def _plan_helpers(n_primary: int, B: int, k_max: int, hardness):
        """Allocate the dispatch's spare lanes as helpers, hardest
        positions first: → list of (primary_row, helper_index) with
        helper_index 1..k_max-1, at most k_max-1 helpers per primary,
        at most B - n_primary total. Round-robin in descending-hardness
        order, so every hard position gets its first helper before any
        gets its second. hardness[j] <= 0 excludes primary j (settled,
        terminal, or budget-exhausted lanes get no helpers)."""
        spare = B - n_primary
        out: list = []
        if k_max <= 1 or spare <= 0 or n_primary <= 0:
            return out
        hardness = [int(h) for h in hardness]
        order = sorted(range(n_primary), key=lambda r: (-hardness[r], r))
        grants = [0] * n_primary
        while len(out) < spare:
            progressed = False
            for r in order:
                if len(out) >= spare:
                    break
                if hardness[r] > 0 and grants[r] < k_max - 1:
                    grants[r] += 1
                    out.append((r, grants[r]))
                    progressed = True
            if not progressed:
                break
        return out

    def _helper_width(self, n: int) -> int:
        """Dispatch width for n primaries with helper lanes enabled: grow
        the lane bucket toward n*K so the planner has spare rows to fill
        (a wider lockstep program costs nearly the same per step on TPU —
        docs/depth.md us/step tables — and the narrowing floor is 64
        anyway), but never above the device ceiling. K=1 keeps the
        pre-helper width exactly."""
        B = self._pad(n)
        K = self.helper_lanes
        if K > 1:
            grown = self._pad(min(n * K, self.max_lanes))
            if grown <= max(self.max_lanes, B):
                B = max(B, grown)
        return B

    @staticmethod
    def _history_arrays(hist_lists, B, variant="standard", keep_last=0):
        """Per-lane reversible game tails → device seed arrays.

        hist_lists: list (≤B) of list[Position], oldest first, ending at
        the lane root's parent. The reference hands the engine the whole
        game (`position fen ... moves ...`, src/stockfish.rs:298-306), and
        Stockfish's draw rule (Position::is_draw) scores a repetition as
        a draw when the earlier occurrence is INSIDE the search path, or
        when the position already occurred twice before/at the root. The
        in-search half is the device's path scan; this seeds the other
        half: only game positions occurring >=2x in the reversible tail
        are planted (a single pre-root occurrence is NOT a draw on
        re-visit — distance > ply in Stockfish's check). Chain validity
        (no irreversible move in between, rule50 window) is re-checked on
        device via halfmove distances.

        keep_last: the last keep_last tail entries are planted even when
        they occur only once. Move jobs and multipv decompose the search
        root's legal moves into lanes, so the root itself sits in the
        tail — a return to it inside a lane IS an in-search twofold
        repetition (distance <= ply in Stockfish's check) and must score
        as a draw on first re-visit."""
        from ..ops.search import HIST_HM_SENTINEL, MAX_HIST

        hh = np.zeros((B, MAX_HIST, 2), np.uint32)
        hm = np.full((B, MAX_HIST), HIST_HM_SENTINEL, np.int32)
        tails = [hist[-MAX_HIST:] for hist in hist_lists]
        flat = [p for tail in tails for p in tail]
        if flat:
            # on the host: the keys are `tt.hash_board`'s bit for bit,
            # and a device call here would wait behind a running segment
            h1, h2 = _position_keys(flat, variant)
            at = 0
            for lane, tail in enumerate(tails):
                n = len(tail)
                _fill_history_row(hh[lane], hm[lane], h1[at:at + n],
                                  h2[at:at + n], [p.halfmove for p in tail],
                                  keep_last)
                at += n
        return hh, hm

    @classmethod
    def _history_arrays_shared(cls, hist, B, variant="standard", keep_last=0):
        """One history list shared by all B lanes (move jobs: every
        root-move lane has the same game prefix). Hashes the tail ONCE
        and broadcasts — the per-lane version costs B×MAX_HIST
        position_fields calls on the host, against the 7 s move-job
        deadline."""
        hh1, hm1 = cls._history_arrays([hist], 1, variant, keep_last)
        return (
            np.broadcast_to(hh1, (B,) + hh1.shape[1:]).copy(),
            np.broadcast_to(hm1, (B,) + hm1.shape[1:]).copy(),
        )

    def _go_multiple_sync(self, chunk: Chunk) -> List[PositionResponse]:
        # single-pv analysis chunks flow through the occupancy-driven
        # LaneScheduler when refill is on — on mesh hosts too, via the
        # sharded segment/refill callables (FISHNET_TPU_MESH_REFILL=0
        # opts a meshed engine out); every other shape takes the strict
        # chunk-serial path UNCHANGED — with refill off the engine is
        # bit-identical to the pre-refill code by construction
        # (enforced by tests).
        work = chunk.work
        with syncstats.serving(self.occupancy_totals):
            if (
                self.refill
                and (self.mesh is None or self.mesh_refill)
                and isinstance(work, AnalysisWork)
                and work.effective_multipv() == 1
            ):
                return self._scheduler.run_chunk(chunk)
            with self._lock:
                return self._go_multiple_locked(chunk)

    def _go_multiple_locked(self, chunk: Chunk) -> List[PositionResponse]:
        started = time.monotonic()
        # one TT generation per chunk: helper-mode stores from THIS chunk
        # out-rank each other by depth but always replace earlier chunks'
        # entries (ops/tt.py store; wraps long before int32 overflow)
        self._tt_gen = (self._tt_gen + 1) & 0x3FFFFFFF
        replay = _ChunkReplay(chunk.positions, chunk.variant)
        positions = []
        games = []  # per position: the replayed game prefix (oldest first)
        for i in range(len(chunk.positions)):
            positions.append(replay.position(i)[0])
            games.append(replay.game(i))

        work = chunk.work
        if isinstance(work, MoveWork):
            return self._move_job(chunk, positions, games, work, started)
        assert isinstance(work, AnalysisWork)
        multipv = work.effective_multipv()
        target_depth = min(work.depth or self.max_depth, self.max_depth, MAX_PLY - 1)
        budget = work.nodes.get(chunk.flavor.eval_flavor())

        if multipv > 1:
            responses = self._analyse_multipv(
                chunk, positions, games, multipv, target_depth, budget, started
            )
        else:
            responses = self._analyse_single(
                chunk, positions, games, target_depth, budget, started
            )
        return responses

    def _move_job(self, chunk, positions, games, work: MoveWork, started):
        """Play jobs with lichess skill semantics (reference:
        src/api.rs:248-283 maps level 1-8 → movetime/Skill Level/depth;
        src/stockfish.rs:309-333 passes them to the engine).

        Root moves become lanes (one depth-1 search per legal move, deepened
        iteratively); weakening is the TPU-native analog of Stockfish's
        "Skill Level": below full strength, the move is drawn from the
        near-best candidates with probability decaying in the cp gap, with
        the acceptance window widening as the engine skill drops."""
        import random

        level = work.level
        target_depth = min(level.depth, self.max_depth, MAX_PLY - 1)
        hard_deadline = chunk.deadline - 0.25  # 7 s job deadline
        # movetime is a soft budget for DEEPENING; depth 1 always runs to
        # completion under the hard deadline so a move is always produced
        soft_deadline = min(
            hard_deadline, started + level.movetime_ms / 1000.0
        )
        variant = DEVICE_VARIANTS.get(chunk.variant, "standard")

        responses = []
        for wp, pos, game in zip(chunk.positions, positions, games):
            # move jobs dispatch per position (unlike analysis chunks), so
            # each position's reported time is its own measured slice
            p_start = time.monotonic()
            if pos.outcome() is not None:
                responses.append(self._terminal_response(chunk, wp, pos, 0.001))
                continue
            legal = pos.legal_moves()
            # pad to the variant's warmed move-job bucket so every job
            # shares ONE pre-compiled deep-probe program (a <=16-legal
            # endgame would otherwise bucket to a 16-lane program nothing
            # compiles ahead of its 7 s deadline) — lanes are cheap,
            # cold compiles are not
            B = self._pad(max(len(legal), _move_job_floor(variant)))
            boards = [from_position(pos.push(m)) for m in legal]
            roots = stack_boards(boards + [boards[0]] * (B - len(boards)))
            # every root-move lane shares the same history: the game
            # prefix plus the position the move was played from — which
            # is the SEARCH ROOT, seeded unconditionally (keep_last=1):
            # returning to it inside a lane is an in-search repetition
            hist = self._history_arrays_shared(
                game + [pos], B, variant, keep_last=1
            )

            ranked = []
            depth_reached = 0
            nodes_total = 0
            for depth in range(1, target_depth + 1):
                depth_arr = np.zeros(B, np.int32)
                depth_arr[: len(legal)] = depth - 1
                out = self._search(
                    roots, depth_arr, np.full(B, 10_000_000, np.int32),
                    hard_deadline if depth == 1 else soft_deadline,
                    variant=variant, hist=hist,
                    # move jobs report a MOVE, not a score: deeper TT
                    # bounds cut more (reference depth>= rule) and the
                    # score-determinism concern doesn't apply
                    deep_tt=True,
                )
                if not bool(out["done"][: len(legal)].all()):
                    break  # movetime/deadline hit: keep the previous depth
                nodes_total += int(out["nodes"][: len(legal)].sum()) + len(legal)
                ranked = sorted(
                    ((-int(out["score"][j]), j) for j in range(len(legal))),
                    key=lambda t: (-t[0], t[1]),
                )
                depth_reached = depth
                if time.monotonic() >= soft_deadline:
                    break
            if depth_reached == 0:
                raise EngineError("move job deadline expired before depth 1")

            sf_skill = level.engine_skill_level  # -9..20
            # rng seeded per job for reproducibility
            pick = skill_pick(
                ranked, sf_skill, random.Random(f"{work.id}:{wp.position_index}")
            )
            best_move = legal[pick[1]].uci()

            scores, pvs = Matrix(), Matrix()
            scores.set(1, depth_reached, _score_from_int(pick[0]))
            pvs.set(1, depth_reached, [best_move])
            dt = max(time.monotonic() - p_start, 1e-6)
            responses.append(
                PositionResponse(
                    work=chunk.work, position_index=wp.position_index,
                    url=wp.url, scores=scores, pvs=pvs, best_move=best_move,
                    depth=depth_reached, nodes=nodes_total, time_s=dt,
                    nps=int(nodes_total / dt),
                )
            )
        return responses

    def _terminal_response(self, chunk, wp: WorkPosition, pos: Position,
                           elapsed: float) -> PositionResponse:
        winner, _ = pos.outcome()
        scores, pvs = Matrix(), Matrix()
        scores.set(1, 0, Score.mate(0) if winner is not None else Score.cp(0))
        pvs.set(1, 0, [])
        return PositionResponse(
            work=chunk.work, position_index=wp.position_index, url=wp.url,
            scores=scores, pvs=pvs, best_move=None, depth=0, nodes=0,
            time_s=elapsed,
        )

    def _analyse_single(self, chunk, positions, games, target_depth, budget,
                        started):
        terminal = {
            i for i, p in enumerate(positions) if p.outcome() is not None
        }
        lanes = [i for i in range(len(positions)) if i not in terminal]

        scores = [Matrix() for _ in positions]
        pvs = [Matrix() for _ in positions]
        depth_reached = [0] * len(positions)
        best_moves: List[Optional[str]] = [None] * len(positions)
        nodes_total = [0] * len(positions)

        if lanes:
            n = len(lanes)
            K = self.helper_lanes
            B = self._helper_width(n)
            boards = [from_position(positions[i]) for i in lanes]
            pad_board = boards[0]
            variant = DEVICE_VARIANTS.get(chunk.variant, "standard")
            hist_hh, hist_hm = self._history_arrays(
                [games[i] for i in lanes], B, variant
            )
            per_pos_budget = budget if budget is not None else 10_000_000
            # primary-indexed iterative-deepening state (length n)
            remaining = np.full(n, per_pos_budget, dtype=np.int64)
            prev_score = np.zeros(n, np.int64)
            have_prev = np.zeros(n, bool)
            # hardness drives the helper planner: the previous depth's
            # primary node count — the lane that took the most serial
            # work is the one bounding the next depth's lockstep wall
            hardness = np.ones(n, np.int64)

            deadline = chunk.deadline - 0.25  # leave slack to package results
            for depth in range(1, target_depth + 1):
                # ---- lane-group layout for this depth: primaries in
                # rows 0..n-1, helpers next, inert padding after. Helper
                # h of primary j searches j's root with jittered move
                # ordering; odd helpers at the SAME depth (their exact-
                # depth TT entries are consumable THIS iteration — probe
                # requires exact depth, ops/tt.py), even helpers one ply
                # DEEPER (their entries feed ordering now and cutoffs
                # next iteration). All are abandoned mid-flight the
                # moment every primary finishes (required mask).
                helpers = (
                    self._plan_helpers(
                        n, B, K, np.where(remaining > 0, hardness, 0)
                    )
                    if K > 1
                    else []
                )
                roots = stack_boards(
                    boards
                    + [boards[j] for j, _h in helpers]
                    + [pad_board] * (B - n - len(helpers))
                )
                depth_arr = np.zeros(B, np.int32)
                depth_arr[:n] = depth
                budget_arr = np.ones(B, np.int32)
                budget_arr[:n] = np.clip(remaining, 0, 2**31 - 1)
                use_full = np.zeros(B, bool)
                use_full[:n] = (
                    have_prev & (np.abs(prev_score) < MATE - 1000)
                    & (depth >= 2)
                )
                prev_full = np.zeros(B, np.int64)
                prev_full[:n] = prev_score
                if K > 1:
                    hh = hist_hh.copy()
                    hm = hist_hm.copy()
                    jitter = np.zeros(B, np.int32)
                    grp = np.arange(B, dtype=np.int32)
                    scale_arr = np.ones(B, np.int64)
                    req = np.zeros(B, bool)
                    req[:n] = True
                    for idx, (j, h) in enumerate(helpers):
                        r = n + idx
                        hh[r] = hist_hh[j]
                        hm[r] = hist_hm[j]
                        # same depth for odd h, +1 ply for even h
                        depth_arr[r] = min(depth + (1 - (h & 1)), target_depth)
                        budget_arr[r] = budget_arr[j]
                        jitter[r] = j * K + h  # != 0, unique per (j, h)
                        grp[r] = j
                        scale_arr[r] = 1 << min(h, 4)  # staggered windows
                        use_full[r] = use_full[j]
                        prev_full[r] = prev_score[j]
                    hist_args = dict(
                        required=req, win_scale=scale_arr,
                        order_jitter=jitter, group=grp, helper_store=True,
                    )
                    hist_d = (hh, hm)
                else:
                    # K=1: identical arguments (and compiled programs) to
                    # the pre-helper engine — bit-for-bit the same search
                    hist_args = {}
                    hist_d = (hist_hh, hist_hm)
                out = self._search_windowed(
                    roots, depth_arr, budget_arr, deadline,
                    variant, hist_d, prev_full, use_full, **hist_args,
                )
                exhausted_all = True
                for j, i in enumerate(lanes):
                    if remaining[j] <= 0 or not bool(out["done"][j]):
                        continue  # lane skipped, or stopped mid-depth on deadline
                    # helper nodes are charged to their primary: the
                    # position consumed that work against its server
                    # budget (same honesty rule as multipv's root-move
                    # lanes; helpers are abandoned at primary completion,
                    # so the charge is the work actually spent)
                    lane_nodes = int(out["nodes"][j])
                    help_nodes = sum(
                        int(out["nodes"][n + idx])
                        for idx, (jj, _h) in enumerate(helpers)
                        if jj == j
                    )
                    hardness[j] = max(lane_nodes, 1)
                    nodes_total[i] += lane_nodes + help_nodes
                    remaining[j] -= lane_nodes + help_nodes
                    sc = int(out["score"][j])
                    prev_score[j] = sc
                    have_prev[j] = True
                    scores[i].set(1, depth, _score_from_int(sc))
                    pv = [
                        _decode_uci(int(m))
                        for m in out["pv"][j][: int(out["pv_len"][j])]
                        if m >= 0
                    ]
                    pvs[i].set(1, depth, pv)
                    depth_reached[i] = depth
                    mv = int(out["move"][j])
                    best_moves[i] = _decode_uci(mv) if mv >= 0 else None
                    if remaining[j] > 0:
                        exhausted_all = False
                if exhausted_all or time.monotonic() >= deadline:
                    break

        # deadline hit before even depth 1 finished: no usable result for
        # some lane — fail the whole chunk so the server reassigns it
        # (reference forgets failed batches, src/queue.rs:226-233)
        if any(depth_reached[i] == 0 for i in lanes):
            raise EngineError("chunk deadline expired before depth 1 completed")

        elapsed = max(time.monotonic() - started, 1e-6)
        times = self._apportion_time(elapsed, nodes_total)
        responses = []
        for i, wp in enumerate(chunk.positions):
            if i in terminal:
                responses.append(
                    self._terminal_response(chunk, wp, positions[i], times[i])
                )
                continue
            nps = int(nodes_total[i] / times[i]) if times[i] > 0 else None
            responses.append(
                PositionResponse(
                    work=chunk.work, position_index=wp.position_index,
                    url=wp.url, scores=scores[i], pvs=pvs[i],
                    best_move=best_moves[i], depth=depth_reached[i],
                    nodes=nodes_total[i], time_s=times[i], nps=nps,
                )
            )
        return responses

    @staticmethod
    def _apportion_time(elapsed: float, nodes: list) -> list:
        """Chunk wall-clock → per-position times, proportional to each
        position's node count.

        All positions of a chunk share one batched dispatch, so there is
        no true per-position wall time; the reference reports what the
        engine measured per `go` (src/stockfish.rs:351-392). The honest
        decomposition of shared lockstep time is by node share — the
        per-position times sum to the chunk's real elapsed, and the
        implied nps is the chunk's uniform lockstep throughput (a
        uniform elapsed/len split instead made light positions look
        slow and heavy ones implausibly fast, round-3 advisor flag)."""
        total = sum(nodes)
        n = max(len(nodes), 1)
        if total <= 0:
            return [elapsed / n] * n
        return [elapsed * nd / total for nd in nodes]

    def _analyse_multipv(self, chunk, positions, games, multipv, target_depth,
                         budget, started):
        """MultiPV via root-move-partitioned lanes: every legal root move
        of EVERY chunk position becomes a lane, all searched together in
        one dispatch per iterative-deepening depth. This is where batching
        beats the reference hardest — Stockfish pays ~multipv× for
        MultiPV (reference: src/stockfish.rs:272 sets MultiPV and the
        engine re-searches), while lanes are just lanes here.

        Node accounting: every legal root move gets a lane, so a position
        spends ~len(legal)× a single-PV search's NODES against the same
        server budget (remaining//len(legal) per lane per round, so a
        round never exceeds the remaining budget). Wall-clock is what
        matters on TPU — the lanes run in the same lockstep dispatch —
        and the budget check stops deepening once the pool is spent.

        Lane ceiling: multipv is the only path whose lane count scales
        with chunk content (positions × legal moves), so it is the only
        one that can blow past `max_lanes` (~1024 on v5e before the VMEM
        cliff, docs/tpu-hang.md round 5). Positions are partitioned
        greedily into dispatch groups of ≤ max_lanes lanes, searched
        sequentially against the shared chunk deadline."""
        live = [i for i, p in enumerate(positions) if p.outcome() is None]
        legal: dict[int, list] = {i: positions[i].legal_moves() for i in live}

        groups: List[List[int]] = []
        cur: List[int] = []
        cur_lanes = 0
        for i in live:
            n = len(legal[i])
            if cur and cur_lanes + n > self.max_lanes:
                groups.append(cur)
                cur, cur_lanes = [], 0
            # a single position over the ceiling still gets its own group:
            # root-move lanes are indivisible (chess tops out ~218 legal,
            # far under the production ceiling — only tiny test ceilings
            # can hit this)
            cur.append(i)
            cur_lanes += n
        if cur:
            groups.append(cur)
        if len(groups) > 1:
            total_lanes = sum(len(legal[i]) for i in live)
            self._warn(
                f"multipv chunk wants {total_lanes} lanes, over the "
                f"{self.max_lanes}-lane device ceiling; splitting into "
                f"{len(groups)} sequential dispatch groups (expect "
                "proportionally longer wall-clock against the same deadline)"
            )

        scores = [Matrix() for _ in positions]
        pvs = [Matrix() for _ in positions]
        depth_reached = [0] * len(positions)
        best_moves: List[Optional[str]] = [None] * len(positions)
        nodes_total = [0] * len(positions)

        for group in groups:
            self._analyse_multipv_group(
                chunk, positions, games, multipv, target_depth, budget,
                group, legal, scores, pvs, depth_reached, best_moves,
                nodes_total,
            )

        if any(depth_reached[i] == 0 for i in live):
            raise EngineError(
                "chunk deadline expired before depth 1 completed (multipv)"
            )

        elapsed = max(time.monotonic() - started, 1e-6)
        times = self._apportion_time(elapsed, nodes_total)
        responses = []
        for i, wp in enumerate(chunk.positions):
            if i not in live:
                responses.append(
                    self._terminal_response(chunk, wp, positions[i], times[i])
                )
                continue
            responses.append(
                PositionResponse(
                    work=chunk.work, position_index=wp.position_index,
                    url=wp.url, scores=scores[i], pvs=pvs[i],
                    best_move=best_moves[i], depth=depth_reached[i],
                    nodes=nodes_total[i], time_s=times[i],
                    nps=int(nodes_total[i] / times[i]) if times[i] > 0 else None,
                )
            )
        return responses

    def _analyse_multipv_group(self, chunk, positions, games, multipv,
                               target_depth, budget, live, legal, scores,
                               pvs, depth_reached, best_moves, nodes_total):
        """One ≤max_lanes dispatch group of `_analyse_multipv`: build the
        lane table for `live`'s root moves and iterate depths, folding
        results into the caller's shared per-position accumulators."""
        # lane table: (position index, move index) per lane
        lane_pos: List[int] = []
        lane_move: List[int] = []
        boards = []
        for i in live:
            for j, m in enumerate(legal[i]):
                lane_pos.append(i)
                lane_move.append(j)
                boards.append(from_position(positions[i].push(m)))

        if boards:
            B = self._pad(max(len(boards), 64))
            roots = stack_boards(boards + [boards[0]] * (B - len(boards)))
            variant = DEVICE_VARIANTS.get(chunk.variant, "standard")
            # lane k's root is positions[lane_pos[k]].push(move): history =
            # that game's prefix plus the position itself (the search
            # root — seeded unconditionally via keep_last, same reasoning
            # as move jobs). Hash each distinct position's tail once and
            # fan out to its lanes.
            from ..ops.search import HIST_HM_SENTINEL

            hh_pos, hm_pos = self._history_arrays(
                [games[i] + [positions[i]] for i in live], len(live),
                variant, keep_last=1,
            )
            pos_row = {i: r for r, i in enumerate(live)}
            hh = np.zeros((B,) + hh_pos.shape[1:], hh_pos.dtype)
            hm = np.full((B,) + hm_pos.shape[1:], HIST_HM_SENTINEL,
                         hm_pos.dtype)
            for k, i in enumerate(lane_pos):
                hh[k] = hh_pos[pos_row[i]]
                hm[k] = hm_pos[pos_row[i]]
            hist = (hh, hm)
            per_pos_budget = budget if budget is not None else 10_000_000
            remaining = {i: per_pos_budget for i in live}

            deadline = chunk.deadline - 0.25
            for depth in range(1, target_depth + 1):
                depth_arr = np.zeros(B, np.int32)
                budget_arr = np.ones(B, np.int32)
                for k, i in enumerate(lane_pos):
                    if remaining[i] > 0:
                        depth_arr[k] = depth - 1
                        budget_arr[k] = min(
                            max(remaining[i] // max(len(legal[i]), 1), 1),
                            2**31 - 1,
                        )
                out = self._search(
                    roots, depth_arr, budget_arr, deadline,
                    variant=variant, hist=hist,
                    # root-move lanes already fill the dispatch, so no
                    # helper replication here — but the depth-preferred
                    # store policy still applies (and keeps the compiled
                    # program identical to the warmed helper-mode one)
                    helper_store=self.helper_lanes > 1,
                )
                done = out["done"]
                # fold lanes back per position
                per_pos_done = {i: True for i in live}
                for k, i in enumerate(lane_pos):
                    if remaining[i] > 0 and not bool(done[k]):
                        per_pos_done[i] = False
                ranked: dict[int, list] = {i: [] for i in live}
                for k, (i, j) in enumerate(zip(lane_pos, lane_move)):
                    if remaining[i] <= 0 or not per_pos_done[i]:
                        continue
                    m = legal[i][j]
                    child_score = -int(out["score"][k])
                    child_pv = [
                        _decode_uci(int(x))
                        for x in out["pv"][k][: int(out["pv_len"][k])]
                        if x >= 0
                    ]
                    ranked[i].append((child_score, j, [m.uci()] + child_pv))
                progressed = False
                for i in live:
                    if remaining[i] <= 0 or not per_pos_done[i] or not ranked[i]:
                        continue
                    step_nodes = sum(
                        int(out["nodes"][k])
                        for k, pi in enumerate(lane_pos)
                        if pi == i
                    ) + len(legal[i])
                    nodes_total[i] += step_nodes
                    remaining[i] -= step_nodes
                    rl = sorted(ranked[i], key=lambda t: (-t[0], t[1]))
                    for rank, (sc, _j, line) in enumerate(rl[:multipv], start=1):
                        scores[i].set(rank, depth, _score_from_int(sc))
                        pvs[i].set(rank, depth, line)
                    depth_reached[i] = depth
                    best_moves[i] = rl[0][2][0]
                    if remaining[i] > 0:
                        progressed = True
                if not progressed or time.monotonic() >= deadline:
                    break


# ---------------------------------------------- continuous lane refill


class _RefillJob:
    """One analysed position flowing through the LaneScheduler.

    Carries its own iterative-deepening and aspiration-window state so
    it progresses independently of every other position sharing the
    batch — the per-lane decomposition of what `_analyse_single` +
    `_search_windowed` track batch-wide. The per-depth policy here must
    stay EXACTLY equivalent per lane (window schedule, fail-low/high
    checks, budget charging), or refill-on scores drift from refill-off
    ones with no TT involved."""

    __slots__ = (
        "entry", "wp", "pos", "board", "variant", "target_depth",
        "remaining", "deadline", "hh", "hm", "depth", "delta_idx",
        "prev_score", "have_prev", "hardness", "scores", "pvs",
        "depth_reached", "best_move", "nodes_total", "nodes_depth",
        "lane", "helpers", "traced", "t_queued", "t_spliced",
    )

    def __init__(self, entry, wp, pos, board, variant, target_depth,
                 budget, deadline, hh, hm):
        self.entry = entry
        self.wp = wp
        self.pos = pos
        self.board = board  # position_fields(pos): numpy, never a device array
        self.variant = variant
        self.target_depth = target_depth
        self.remaining = budget  # node budget left (host int)
        self.deadline = deadline
        self.hh = hh  # (MAX_HIST, 2) repetition-history hashes
        self.hm = hm  # (MAX_HIST,) halfmove distances
        self.depth = 1  # depth currently being searched
        self.delta_idx = 0  # index into ASPIRATION_DELTAS + (None,)
        self.prev_score = 0
        self.have_prev = False
        self.hardness = 1  # previous depth's node count (helper planner)
        self.scores = Matrix()
        self.pvs = Matrix()
        self.depth_reached = 0
        self.best_move: Optional[str] = None
        self.nodes_total = 0
        self.nodes_depth = 0  # nodes across the current depth's attempts
        self.lane = -1  # primary lane index while admitted
        self.helpers: dict = {}  # helper lane index -> helper number h
        # request-scoped tracing (host-side bookkeeping ONLY — nothing
        # here ever reaches a device buffer): traced is the per-request
        # sampling verdict hoisted out of the boundary loop
        self.traced = False
        # monotonic: the job made, and the position won its first lane
        # (0.0 until then)
        self.t_queued = time.monotonic()
        self.t_spliced = 0.0


class _ChunkEntry:
    """Per-chunk completion tracking shared between the submitting
    thread and whichever thread is currently driving the device."""

    def __init__(self, chunk: Chunk, started: float):
        self.chunk = chunk
        self.started = started
        self.n_open = 0
        self.responses: dict = {}  # position_index -> PositionResponse
        self.error: Optional[str] = None
        self.event = threading.Event()
        # TT warm-slice plan (cache/ttwarm.py): (prefix key, slots)
        # per position, filled by _submit when the engine has a warm
        # store attached; run_chunk exports these slots on completion
        self.tt_warm: list = []


class LaneScheduler:
    """Occupancy-driven scheduling of the lockstep search (ISSUE 4).

    `_go_multiple_locked` drains chunks strictly serially and a batch
    finishes when its HARDEST position does, so finished lanes idle —
    masked but still stepping — until the power-of-two narrowing halves
    the width. The scheduler applies iteration-level ("continuous")
    batching instead: one pending-position queue fed by every
    concurrently submitted single-pv analysis chunk, one full-width
    compiled step, and at every segment boundary finished lanes are
    refilled (ops/search.py refill_lanes) with queued positions,
    earliest deadline first. Genuinely-spare lanes run Lazy-SMP helpers
    (`_plan_helpers`), and each `PositionResponse` is emitted the moment
    its position finishes rather than when its whole chunk does.

    Concurrency (combining driver): any number of executor threads call
    `run_chunk` concurrently. Each submits its positions to the shared
    queue, then either becomes THE driver — taking the engine lock and
    dispatching segments that serve everyone's jobs — or waits for its
    responses. The engine lock is released between drive sessions so
    move jobs and multipv chunks (which take the serial path) can
    interleave. Per-admission TT generation tags flow into the (B,)
    tt_gen array of `_run_segment_jit`, so depth-preferred replacement
    never protects entries from an earlier occupant of the same lane."""

    def __init__(self, engine: "TpuEngine"):
        self.engine = engine
        self._q_lock = threading.Lock()
        self._pending: List[_RefillJob] = []
        self._driving = False
        self._jitter_seq = 0
        # the time no session runs, accounted when the next one starts
        # (all under _q_lock): when the last session ended, since when
        # work has been queued with nobody driving, and the _submit
        # calls open now / closed since the last session ended
        self._session_end: Optional[float] = None
        self._pending_since: Optional[float] = None
        self._submits_open: dict = {}
        self._submits_done: List[tuple] = []
        # FISHNET_TPU_SANITIZE, captured once: _deliver pays a single
        # attribute test per position, nothing per boundary
        self._sanitize = sanitize.enabled()

    # ------------------------------------------------------- submission

    def run_chunk(self, chunk: Chunk) -> List[PositionResponse]:
        entry = self._submit(chunk)
        while not entry.event.is_set():
            with self._q_lock:
                drive = not self._driving
                if drive:
                    self._driving = True
            if drive:
                try:
                    self._drive(entry)
                finally:
                    with self._q_lock:
                        self._driving = False
            else:
                entry.event.wait(0.05)
        if entry.error:
            raise EngineError(entry.error)
        if self.engine.tt_warm is not None and entry.tt_warm:
            self._tt_warm_export(entry)
        return [entry.responses[wp.position_index] for wp in chunk.positions]

    def _tt_warm_plan(self, entry: _ChunkEntry, wp, pos, variant) -> None:
        """Opening-prefix TT warm-up (cache/ttwarm.py): compute the TT
        slots of this position and its direct children, remember them on
        the entry for export after the chunk, and splice any persisted
        slice for the same prefix into the shared table. Splicing swaps
        `eng.tt` and so only happens under the queue lock while no drive
        loop is live (the drive loop re-reads `eng.tt` per segment and
        writes it back in its `finally`, which would clobber a
        concurrent swap); a busy engine just skips the warm start."""
        from ..cache import ttwarm as cache_ttwarm

        eng = self.engine
        store = eng.tt_warm
        if store is None or eng.tt is None:
            return
        try:
            key = cache_ttwarm.prefix_fingerprint(
                wp.root_fen, wp.moves, eng.tt_warm_prefix
            )
            children = [pos.push(m) for m in pos.legal_moves()]
            boards = [pos] + children[: cache_ttwarm.MAX_SLICE_ROWS - 1]
            h1, _h2 = _position_keys(boards, variant)
            mask = (1 << eng.tt_size_log2) - 1
            slots = [int(h) & mask for h in h1]
            entry.tt_warm.append((key, slots))
            rows = store.lookup(eng.tt_size_log2, key)
            if not rows:
                return
            with self._q_lock:
                tt = eng.tt
                if (
                    not self._driving
                    and tt is not None
                    and tt.data.ndim == 2
                ):
                    data, n = cache_ttwarm.splice_rows(tt.data, rows)
                    if n:
                        eng.tt = tt._replace(data=data)
                        store.splices += 1
                        store.warm_slots += n
        except Exception as e:
            eng._warn(f"tt warm plan failed: {e}")

    def _tt_warm_export(self, entry: _ChunkEntry) -> None:
        """After a chunk completes, read back the slots planned in
        `_tt_warm_plan` from a table snapshot and persist the non-empty
        rows. Reads a gathered slice from whatever `eng.tt` points at
        now — rows from a later occupant of the same slot still
        self-validate on splice, so staleness is safe."""
        from ..cache import ttwarm as cache_ttwarm

        eng = self.engine
        store = eng.tt_warm
        tt = eng.tt
        if store is None or tt is None or tt.data.ndim != 2:
            return
        try:
            for key, slots in entry.tt_warm:
                idx = np.asarray(slots, dtype=np.int64)
                rows = cache_ttwarm.extract_rows(
                    np.asarray(tt.data[idx]), slots
                )
                if rows:
                    store.record(eng.tt_size_log2, key, rows)
        except Exception as e:
            eng._warn(f"tt warm export failed: {e}")

    @staticmethod
    @contextlib.contextmanager
    def _submit_step(rec, nest_id: str, name: str, spent: dict):
        """One timed step of _submit for one position: adds its
        milliseconds to spent[name], labels the thread for the compile
        listener (`submit_<name>`), and with a recorder on leaves a
        `submit.<name>` async pair under the chunk's `submit` pair."""
        if rec is not None:
            rec.nest("submit." + name, nest_id, "b", "engine")
        try:
            with syncstats.step("submit_" + name) as st:
                yield
        finally:
            spent[name] += st.ms
            if rec is not None:
                rec.nest("submit." + name, nest_id, "e", "engine")

    def _submit(self, chunk: Chunk) -> _ChunkEntry:
        """Replay, hash and queue one chunk's positions. Runs on the
        caller's executor thread, several at once and mostly while no
        session runs, so it is counted (submit_* totals, and the
        `gap_submit_ms` part of the time between sessions) and, with a
        recorder on, traced as nestable async pairs — never as thread
        "X" spans, which a reader of the timeline takes for a drive
        session's host work."""
        t_sub = time.monotonic()
        entry = _ChunkEntry(chunk, t_sub)
        spent = {"replay": 0.0, "history": 0.0, "ttwarm": 0.0}
        rec = obs_trace.RECORDER
        nest_id = ""
        if rec is not None and chunk.positions:
            # the chunks of one batch share a work id: the first
            # position's index tells their async tracks apart
            wp0 = chunk.positions[0]
            ctx0 = next((wp.ctx for wp in chunk.positions
                         if wp.ctx and wp.ctx.get("trace_id")), None)
            nest_id = (f"{ctx0['trace_id'] if ctx0 else chunk.work.id}"
                       f":{wp0.position_index}")
            rec.nest("submit", nest_id, "b", "engine",
                     positions=len(chunk.positions))
        replay = _ChunkReplay(chunk.positions, chunk.variant)
        with self._q_lock:
            self._submits_open[id(entry)] = t_sub
        try:
            jobs = self._plan_jobs(entry, replay, rec, nest_id, spent)
            entry.n_open = len(jobs)
            if not jobs:
                entry.event.set()
            with self._q_lock:
                if jobs and not self._pending:
                    self._pending_since = time.monotonic()
                self._pending.extend(jobs)
        finally:
            t_end = time.monotonic()
            with self._q_lock:
                del self._submits_open[id(entry)]
                self._submits_done.append((t_sub, t_end))
                # several threads submit at once: their shared totals
                # are added under the lock
                tot = self.engine.occupancy_totals
                tot["chunks_submitted"] += 1
                tot["positions_submitted"] += len(chunk.positions)
                tot["submit_prefix_moves"] += sum(
                    len(wp.moves) for wp in chunk.positions)
                tot["submit_moves_parsed"] += replay.parsed
                tot["submit_ms"] += (t_end - t_sub) * 1000.0
                for name, ms in spent.items():
                    tot[f"submit_{name}_ms"] += ms
            if rec is not None and nest_id:
                rec.nest("submit", nest_id, "e", "engine")
        return entry

    def _plan_jobs(self, entry: _ChunkEntry, replay: _ChunkReplay, rec,
                   nest_id: str, spent: dict) -> List[_RefillJob]:
        """The chunk's positions as refill jobs; terminal positions are
        answered here and now."""
        eng = self.engine
        chunk = entry.chunk
        work = chunk.work
        assert isinstance(work, AnalysisWork)
        target_depth = min(
            work.depth or eng.max_depth, eng.max_depth, MAX_PLY - 1
        )
        budget = work.nodes.get(chunk.flavor.eval_flavor())
        per_pos_budget = budget if budget is not None else 10_000_000
        variant = DEVICE_VARIANTS.get(chunk.variant, "standard")
        deadline = chunk.deadline - 0.25  # slack to package results
        live = []
        for i, wp in enumerate(chunk.positions):
            with self._submit_step(rec, nest_id, "replay", spent):
                pos, legal = replay.position(i)
                over = pos.outcome(legal) is not None
            if over:
                self._deliver(
                    entry, wp, eng._terminal_response(chunk, wp, pos, 0.001)
                )
            else:
                live.append((i, pos))
        history = []
        if live:
            with self._submit_step(rec, nest_id, "history", spent):
                history = replay.history([i for i, _ in live], variant)
        jobs = []
        for (i, pos), (hh, hm) in zip(live, history):
            wp = chunk.positions[i]
            if eng.tt_warm is not None:
                with self._submit_step(rec, nest_id, "ttwarm", spent):
                    self._tt_warm_plan(entry, wp, pos, variant)
            job = _RefillJob(
                entry, wp, pos, position_fields(pos), variant, target_depth,
                per_pos_budget, deadline, hh, hm,
            )
            ctx = wp.ctx
            if ctx and ctx.get("trace_id"):
                tid = ctx["trace_id"]
                obs_inflight.REGISTRY.position(
                    tid, wp.position_index or 0, "queued"
                )
                if rec is not None and obs_trace.sampled(tid):
                    job.traced = True
                    rec.instant(
                        "position.queued", "request",
                        **obs_trace.ctx_args(
                            ctx, position_index=wp.position_index
                        ),
                    )
                    rec.flow("request", tid, "t")
            jobs.append(job)
        return jobs

    def _deliver(self, entry: _ChunkEntry, wp, response) -> None:
        """Exactly-once delivery point for one position's result: every
        finalized response — terminal shortcut or searched — lands in
        `entry.responses` through here, and only here, so the
        `on_response` streaming hook fires once per position."""
        if self._sanitize:
            sanitize.check_delivery_once(
                entry.responses, wp.position_index,
                "engine/tpu.py::LaneScheduler._deliver")
        entry.responses[wp.position_index] = response
        ctx = wp.ctx
        if ctx and ctx.get("trace_id"):
            tid = ctx["trace_id"]
            obs_inflight.REGISTRY.position(
                tid, wp.position_index or 0, "delivered"
            )
            rec = obs_trace.RECORDER
            if rec is not None and obs_trace.sampled(tid):
                rec.instant(
                    "position.delivered", "request",
                    **obs_trace.ctx_args(
                        ctx, position_index=wp.position_index,
                        depth=response.depth, nodes=response.nodes,
                    ),
                )
                rec.flow("request", tid, "t")
        hook = self.engine.on_response
        if hook is not None:
            try:
                hook(wp, response)
            except Exception as e:
                self.engine._warn(f"on_response hook failed: {e}")
        deliver = self.engine.on_deliver
        if deliver is not None:
            try:
                deliver(entry.chunk, wp, response)
            except Exception as e:
                self.engine._warn(f"on_deliver hook failed: {e}")

    def _finalize(self, job: _RefillJob, now: float,
                  error: Optional[str] = None) -> None:
        entry = job.entry
        tot = self.engine.occupancy_totals
        # a position never admitted waited in the queue all along
        t_lane = job.t_spliced or now
        tot["position_queue_ms"] += (t_lane - job.t_queued) * 1000.0
        tot["position_engine_ms"] += (now - job.t_queued) * 1000.0
        tot["positions_finalized"] += 1
        if job.traced and job.t_spliced > 0.0:
            rec = obs_trace.RECORDER
            if rec is not None:
                # retroactive lane-residency span: first splice →
                # finalize, one per position (re-admissions for deeper
                # iterations reuse the lane inside this window)
                rec.complete(
                    "position.lane", job.t_spliced * 1e6,
                    (now - job.t_spliced) * 1e6, cat="request",
                    args=obs_trace.ctx_args(
                        job.wp.ctx, position_index=job.wp.position_index,
                        error=error,
                    ),
                )
        if error is not None:
            entry.error = error
        else:
            dt = max(now - entry.started, 1e-6)
            nps = int(job.nodes_total / dt) if job.nodes_total else None
            self._deliver(entry, job.wp, PositionResponse(
                work=entry.chunk.work, position_index=job.wp.position_index,
                url=job.wp.url, scores=job.scores, pvs=job.pvs,
                best_move=job.best_move, depth=job.depth_reached,
                nodes=job.nodes_total, time_s=dt, nps=nps,
            ))
            tot["positions_done"] += 1
        entry.n_open -= 1
        if entry.n_open <= 0:
            entry.event.set()

    # ---------------------------------------------------------- driving

    def _drive(self, entry: _ChunkEntry) -> None:
        while not entry.event.is_set():
            with self._q_lock:
                if not self._pending:
                    return
            # lock released between sessions: a blocked move job or
            # multipv chunk gets the device before the next session
            lock_req_s = time.monotonic()
            with self.engine._lock:
                self._drive_session(entry, lock_req_s)

    def _close_gap(self, g1: float, lock_req_s: float) -> None:
        """Account the time since the last session ended, at the moment
        `g1` the next one starts (caller holds _q_lock). Every instant
        of the gap goes to the first of: some thread was inside _submit
        (`gap_submit_ms`); the driver was waiting for the engine lock
        (`gap_lock_ms`); work was queued but no thread had picked the
        driving up (`gap_handoff_ms`, run_chunk's 50 ms poll); nothing
        was submitted or queued (`gap_starved_ms`) — the caller's time,
        not the engine's."""
        g0 = self._session_end
        if g0 is None:
            return  # the engine's first session: nothing came before
        submits = self._submits_done + [
            (t0, g1) for t0 in self._submits_open.values()
        ]
        since = self._pending_since
        marks = [lock_req_s, since] + [t for iv in submits for t in iv]
        cuts = sorted({g0, g1} | {t for t in marks
                                  if t is not None and g0 < t < g1})
        parts = {"submit": 0.0, "lock": 0.0, "handoff": 0.0, "starved": 0.0}
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2.0
            if any(t0 <= mid < t1 for t0, t1 in submits):
                part = "submit"
            elif mid >= lock_req_s:
                part = "lock"
            elif since is not None and mid >= since:
                part = "handoff"
            else:
                part = "starved"
            parts[part] += b - a
        tot = self.engine.occupancy_totals
        tot["gap_ms"] += (g1 - g0) * 1000.0
        for part, seconds in parts.items():
            tot[f"gap_{part}_ms"] += seconds * 1000.0

    def _drive_session(self, entry: _ChunkEntry, lock_req_s: float) -> None:
        """One fixed-width drive session: admit, dispatch segments,
        process boundaries, until no lane is running (`_Session.run`).
        Jobs of OTHER device variants stay queued (each variant is a
        distinct static program); a later session picks them up."""
        eng = self.engine
        tot = eng.occupancy_totals
        t_enter = time.monotonic()
        with self._q_lock:
            if not self._pending:
                return
            self._close_gap(t_enter, lock_req_s)
            self._pending.sort(key=lambda j: j.deadline)
            variant = self._pending[0].variant
            n_hint = sum(1 for j in self._pending if j.variant == variant)
            filler = next(
                j for j in self._pending if j.variant == variant
            ).board
            # what the session's width was chosen from, and against:
            # the row that shows the race between submitters and driver
            # and which arithmetic the net answers in (nnue.arithmetic)
            session_args = {"n_hint": n_hint, "pending": len(self._pending),
                            "variant": variant,
                            "arithmetic": eng.arithmetic}
        at_start = {k: tot[k] for k in SESSION_TOTALS}
        B = eng._helper_width(min(max(n_hint, 1), eng.max_lanes))
        session = _Session(self, entry, variant, filler, B, t_enter)
        session_args["setup_ms"] = round(session.setup_ms, 3)
        try:
            session.run()
        except BaseException as e:
            # the driver died mid-session (device fault, OOM...): fail
            # every admitted job so no submitting thread waits forever
            now = time.monotonic()
            for job in session.active:
                session.release(job, None)
                self._finalize(job, now, error=f"tpu engine failed: {e}")
            # jobs released at a park boundary whose _finalize was still
            # deferred behind a PV pull: complete them with what the
            # summary recorded, or their submitters wait forever
            for job, _lane, _depth, final in session.pv_pending:
                if final:
                    self._finalize(job, now)
            session.pv_pending.clear()
            raise
        finally:
            eng.tt = session.tt
            t_end = time.monotonic()
            # last boundary → here: the final account and the refill
            # that found nothing to admit
            tail_ms = session.credit(t_end)
            tot["session_tail_ms"] += tail_ms
            tot["sessions"] += 1
            with self._q_lock:
                self._session_end = t_end
                self._pending_since = t_end if self._pending else None
                # closed before this session ended: in no later gap
                self._submits_done.clear()
            rec = obs_trace.RECORDER
            if rec is not None:
                spent = {k: tot[k] - at_start[k] for k in SESSION_TOTALS}
                for k in ("position_queue_ms", "position_engine_ms"):
                    spent[k] = round(spent[k], 3)
                spent["positions"] = spent.pop("positions_done")
                rec.complete(
                    "session", t_enter * 1e6, (t_end - t_enter) * 1e6,
                    cat="engine",
                    args=dict(session_args, width=B,
                              tail_ms=round(tail_ms, 3), **spent),
                )

    def _record_occupancy(self, width, steps, live, helpers, refilled,
                          queue, host_ms=0.0, device_ms=0.0,
                          transfers=0, phases=None, shard=None,
                          counts=None):
        eng = self.engine
        tot = eng.occupancy_totals
        idle = width - live - helpers
        # with host_ms and device_ms, whichever way the row goes: the
        # phase totals sum to the two
        for name, ms in (phases or {}).items():
            tot[f"phase_{name}_ms"] += ms
        for name, n in (counts or {}).items():
            tot[name] += n
        if steps == 0 and refilled == 0:
            # Pipelined overrun dispatch: the prefetched segment ran zero
            # steps because every lane finished during the previous one.
            # Its sync costs are real, but a no-op segment must not become
            # an occupancy row — consumers weight columns by `steps`, and
            # a refilled lane always steps at least once, so nothing else
            # is lost by dropping it. It is counted, as `segments_empty`
            # in `counts` above.
            tot["host_ms"] += host_ms
            tot["device_ms"] += device_ms
            tot["transfers"] += transfers
            return
        tot["segments"] += 1
        tot["steps"] += steps
        tot["lane_steps"] += steps * width
        tot["live_lane_steps"] += steps * live
        tot["helper_lane_steps"] += steps * helpers
        tot["idle_lane_steps"] += steps * idle
        tot["refills"] += refilled
        tot["refill_splices"] += int(refilled > 0)
        tot["host_ms"] += host_ms
        tot["device_ms"] += device_ms
        tot["transfers"] += transfers
        row = {
            "segment": tot["segments"], "width": width, "steps": steps,
            "live": live, "helpers": helpers, "idle": idle,
            "refilled": refilled, "queue": queue,
            "transfers": transfers, "host_ms": host_ms,
            "device_ms": device_ms,
        }
        if shard is not None:
            # mesh sessions: per-shard busy-lane counts, admissions and
            # device step counts (shard_live counts LANES — primaries
            # plus helpers — where the scalar `live` counts positions)
            row.update(shard)
        eng.occupancy_log.append(row)
        if len(eng.occupancy_log) > 4096:
            del eng.occupancy_log[:-4096]
        rec = obs_trace.RECORDER
        if rec is not None:
            # lane-occupancy counter tracks render under the segment
            # spans SyncStats.boundary() emitted for this interval
            rec.counter("lanes.live", live, "engine")
            rec.counter("lanes.helpers", helpers, "engine")
            rec.counter("lanes.idle", idle, "engine")
            rec.counter("queue.depth", queue, "engine")
        # mirror the scheduler's ad-hoc totals into the metrics registry
        # (boundary-rate, not step-rate: a handful of locked updates per
        # segment, invisible next to a single device fetch)
        reg = obs_metrics.REGISTRY
        reg.absorb_totals("fishnet_occupancy", tot)
        reg.gauge("fishnet_lanes_live").set(live)
        reg.gauge("fishnet_queue_depth").set(queue)
        reg.histogram("fishnet_boundary_host_ms").observe(host_ms)


class _Session:
    """One fixed-width drive session of the LaneScheduler: the host-side
    lane tables, the device state and table handles, and the boundary
    loop (`run`). One segment is always in flight; a boundary is
    processed from its packed summary (one small transfer), and when
    every boundary decision is already settled the NEXT segment is
    dispatched speculatively before blocking, so the host bookkeeping
    overlaps device compute.

    The methods named as the spans — `reap`, `admit`, `refill`,
    `dispatch`, `lanes`, `pv`, `account` — each open their own
    `stats.phase(...)`; `run` is the order they are called in.

    `state` and `tt` are the ONLY handles to the device state and
    table: the segment and splice programs donate their operands, and
    every call rebinds both to the outputs.

    Shard-aware: under a mesh the SAME loop drives the shard_map'd
    segment/refill callables (parallel/mesh.py) — B is padded to a
    multiple of n_dev by _helper_width, each device owns `local`
    consecutive lanes, and every boundary is one stacked-summary
    fetch."""

    deltas = ASPIRATION_DELTAS + (None,)  # None = full window

    def __init__(self, sched: LaneScheduler, entry: _ChunkEntry,
                 variant: str, filler, B: int, t_enter: float):
        from ..ops.search import HIST_HM_SENTINEL, MAX_HIST

        self.sched = sched
        self.eng = eng = sched.engine
        self.tot = eng.occupancy_totals
        self.entry = entry
        self.variant = variant
        self.B = B
        self.K = eng.helper_lanes
        self.mesh = mesh = eng.mesh
        self.n_shard = eng.n_dev if mesh is not None else 1
        self.local = B // self.n_shard
        # mesh-topology-aware admission: free lists index GLOBAL shards
        # (lane numbering spans the whole pod) but new work is admitted
        # only into shards whose device this process can address — on a
        # single-host mesh that is every shard, so the historical
        # assignment is unchanged bit-for-bit
        if mesh is not None:
            from ..parallel import distributed as _dist

            self.fillable_shards = set(_dist.addressable_shards(mesh))
        else:
            self.fillable_shards = {0}
        self.seg = settings.get_int("FISHNET_TPU_SEGMENT")
        self.prefer_deep = self.K > 1 and eng.tt is not None

        # host-side lane tables
        self.lane_job: List[Optional[_RefillJob]] = [None] * B  # primary owner
        self.lane_owner: List[Optional[_RefillJob]] = [None] * B  # helper owner
        self.lane_alpha = np.full(B, -INF, np.int64)
        self.lane_beta = np.full(B, INF, np.int64)
        self.gen = np.zeros(B, np.int32)
        # ROLE_* of every lane; the base state's lanes search (for a
        # step or two) until a boundary sees them DONE
        self.role = np.full(B, ROLE_STRAY, np.int8)
        self.active: List[_RefillJob] = []

        # idle base state: budget-0 lanes park in DONE within two steps.
        # Built from host rows, like every refill after it: one call
        # whose operands are numpy at the session's width; `idle` splices
        # the same row into one lane
        self.filler = filler
        self.idle_hh = np.zeros((MAX_HIST, 2), np.uint32)
        self.idle_hm = np.full(MAX_HIST, HIST_HM_SENTINEL, np.int32)
        with syncstats.step("session_setup"):
            self.state = search_ops._init_state_jit(
                eng.params, stack_fields([filler] * B),
                np.zeros(B, np.int32), np.zeros(B, np.int32),
                MAX_PLY, variant,
                hist_hash=np.stack([self.idle_hh] * B),
                hist_halfmove=np.stack([self.idle_hm] * B),
                root_alpha=np.full((B,), -INF, np.int32),
                root_beta=np.full((B,), INF, np.int32),
                order_jitter=np.zeros((B,), np.int32),
                group=np.zeros((B,), np.int32),
            )
            if mesh is not None:
                from ..parallel.mesh import shard_batch

                # place the base state sharded before the first
                # dispatch: the sharded segment donates its operands,
                # and donation only takes when the input already
                # carries the sharding
                self.state = shard_batch(mesh, self.state)
        self.tt = eng.tt

        # admissions accumulated between boundaries as host rows, flushed
        # as ONE refill_lanes call before each dispatch
        self.adm: dict = {k: [] for k in (
            "lane", "board", "depth", "budget", "alpha", "beta",
            "jitter", "group", "hh", "hm",
        )}
        # PV pulls deferred past speculative boundaries as (job, lane,
        # depth, final) — the PV row is the one per-lane result NOT in
        # the packed summary
        self.pv_pending: List[tuple] = []
        self.last_device_s = 0.0
        # the last boundary's (B,) per-lane node counts (`left`), and
        # the node budget each lane was last staged with (`share`)
        self.nodes_row = None
        self.lane_budget = np.zeros(B, np.int64)

        # the session's boundary intervals open here: what came before
        # is set-up, and the first admission and refill are phases of
        # the first segment
        self.stats = SyncStats()
        self.t_mark = t_enter
        self.setup_ms = self.credit(self.stats.interval_open_s)
        self.tot["session_setup_ms"] += self.setup_ms

    def credit(self, upto: float) -> float:
        """Add the wall-clock since the last credit to session_ms:
        at every boundary, so that counters read in the middle of a
        session are short by one interval at most."""
        ms = (upto - self.t_mark) * 1000.0
        self.tot["session_ms"] += ms
        self.t_mark = upto
        return ms

    # ------------------------------------------------------ the loop

    def run(self) -> None:
        now = time.monotonic()
        self.reap(now, None)
        self.admit(now)
        n_adm, adm_shard = self.refill()
        pend = None
        if self.active:
            pend = self.dispatch(n_adm, adm_shard, False)
        while pend is not None:
            summ, meta = pend
            nxt = None
            now = time.monotonic()
            if self.settled(now):
                nxt = self.dispatch(0, None, True)
            raw_summ = self.stats.fetch(summ, "summary")
            self.residency(meta)
            n, shard_steps, nodes_row, now = self.lanes(raw_summ, meta)
            self.reap(now, nodes_row)
            self.admit(now)
            if nxt is None:
                # PV pulls read the resolved state BEFORE the refill
                # splice below resets those lanes
                self.pv(now)
            self.account(meta, n, shard_steps)
            if nxt is not None:
                pend = nxt
                continue
            n_adm, adm_shard = self.refill()
            if not self.active:
                break  # next session handles the rest
            pend = self.dispatch(n_adm, adm_shard, False)

    def settled(self, now: float) -> bool:
        """No admissions staged, no PV owed, nothing queued, no deadline
        within ~2 segments: this boundary will redispatch the state
        unchanged, so segment k+1 can be issued now (donating the
        in-flight outputs in place)."""
        margin = now + 2.0 * self.last_device_s
        return (not self.adm["lane"] and not self.pv_pending
                and self.q_len_locked() == 0
                and all(margin < j.deadline for j in self.active))

    # ---------------------------------------------------- the phases

    def reap(self, now: float, nodes_row) -> None:
        """Fail or finalize jobs past their chunk deadline."""
        with self.stats.phase("reap"):
            for job in list(self.active):
                if now >= job.deadline:
                    self.release(job, nodes_row)
                    self.active.remove(job)
                    if self.pv_pending:
                        # the response built below holds job.pvs BY
                        # REFERENCE: a deferred pull landing after it
                        # would mutate an already-sent response
                        self.pv_pending[:] = [
                            e for e in self.pv_pending if e[0] is not job
                        ]
                    if job.depth_reached == 0:
                        # no usable result: fail the chunk so the
                        # server reassigns it (same contract as the
                        # serial path)
                        self.sched._finalize(
                            job, now,
                            error="chunk deadline expired before "
                                  "depth 1 completed",
                        )
                    else:
                        self.sched._finalize(job, now)

    def admit(self, now: float) -> None:
        """Admit pending positions, earliest deadline first, then spend
        leftover free lanes on Lazy-SMP helpers.

        Free lanes are tracked per shard and every position lands on
        the shard with the most free lanes (ties → lowest shard),
        hardest-deadline-first within the boundary, so queued positions
        spread across devices instead of piling onto shard 0's early
        lanes; its helpers land beside it while that shard has room.
        A lane let go of that still searches, and is not reused here,
        gets the idle row. With one shard this is exactly the historical
        ascending-lane assignment (one list, front pops) — the
        single-device bit-identity contract holds."""
        sched = self.sched
        active = self.active
        lane_job, lane_owner, local = self.lane_job, self.lane_owner, self.local
        with self.stats.phase("admit"):
            free_by_shard: List[List[int]] = [
                [] for _ in range(self.n_shard)]
            for i in range(self.B):
                if lane_job[i] is None and lane_owner[i] is None:
                    if (i // local) in self.fillable_shards:
                        free_by_shard[i // local].append(i)
            n_free = sum(len(f) for f in free_by_shard)

            def take_lane(near: int = -1) -> int:
                # a helper goes to its primary's shard while it has
                # room: each shard's segment stops at its own watched
                # lanes, so one that holds only helpers would run on
                # until they park, and the boundary waits for it
                s = near // local if near >= 0 else -1
                if s < 0 or not free_by_shard[s]:
                    s = max(
                        range(self.n_shard),
                        key=lambda i: len(free_by_shard[i])
                    )
                return free_by_shard[s].pop(0)

            if not self.entry.event.is_set():
                with sched._q_lock:
                    sched._pending.sort(key=lambda j: j.deadline)
                    take: List[_RefillJob] = []
                    for j in list(sched._pending):
                        if len(take) >= n_free:
                            break
                        if j.variant != self.variant:
                            continue
                        sched._pending.remove(j)
                        take.append(j)
                for job in take:
                    if now >= job.deadline:
                        sched._finalize(
                            job, now,
                            error="chunk deadline expired before "
                                  "depth 1 completed",
                        )
                        continue
                    self.admit_primary(job, take_lane())
                    n_free -= 1
                    active.append(job)
            # ---- spend leftover free lanes on Lazy-SMP helpers
            if self.K > 1 and self.tt is not None and n_free and active:
                n_act = len(active)
                cur = sum(len(j.helpers) for j in active)
                hardness = [
                    j.hardness if self.left(j) > 0 else 0
                    for j in active
                ]
                plan = TpuEngine._plan_helpers(
                    n_act, n_act + cur + n_free, self.K, hardness
                )
                want: dict = {}
                for r, _h in plan:
                    want[r] = want.get(r, 0) + 1
                for r, job in enumerate(active):
                    while n_free and len(job.helpers) < want.get(r, 0):
                        self.admit_helper(
                            job, take_lane(job.lane), len(job.helpers) + 1
                        )
                        n_free -= 1
            # a primary staged at this boundary runs beside the helpers
            # placed above: its budget is settled against theirs
            adm = self.adm
            for job in active:
                if job.helpers and job.lane in adm["lane"]:
                    i = adm["lane"].index(job.lane)
                    budget = int(np.clip(
                        self.share(job, job.lane), 1, 2**31 - 1))
                    adm["budget"][i] = self.lane_budget[job.lane] = budget
            # a lane let go of while it still searched, and not reused
            # above, is stopped at the next splice: no node is spent on
            # a position past its last charge (`release`). Before the
            # first boundary the stray lanes are the idle base state's
            if self.nodes_row is not None:
                staged = set(self.adm["lane"])
                for lane in np.flatnonzero(self.role == ROLE_STRAY):
                    if int(lane) not in staged:
                        self.idle(int(lane))

    def refill(self):
        """Flush the staged admissions in ONE refill splice: the staged
        host rows go to the one splice program of this width, whatever
        their number; under a mesh it runs through shard_map, each
        device rewriting only its own lanes. → (count, per-shard
        admission counts or None)."""
        adm = self.adm
        with self.stats.phase("refill", lanes=len(adm["lane"])):
            n_adm = len(adm["lane"])
            if not n_adm:
                return 0, None
            adm_shard = (
                None if self.mesh is None else np.bincount(
                    np.asarray(adm["lane"], np.int64) // self.local,
                    minlength=self.n_shard,
                ).astype(int).tolist()
            )
            splice_args = (
                self.eng.params, self.state, stack_fields(adm["board"]),
                adm["lane"],
                np.asarray(adm["depth"], np.int32),
                np.asarray(adm["budget"], np.int32),
            )
            splice_kw = dict(
                variant=self.variant,
                hist_hash=np.stack(adm["hh"]),
                hist_halfmove=np.stack(adm["hm"]),
                root_alpha=np.asarray(adm["alpha"], np.int32),
                root_beta=np.asarray(adm["beta"], np.int32),
                order_jitter=np.asarray(adm["jitter"], np.int32),
                group=np.asarray(adm["group"], np.int32),
            )
            if self.mesh is not None:
                from ..parallel.mesh import refill_lanes_sharded

                self.state = refill_lanes_sharded(
                    self.mesh, *splice_args, **splice_kw)
            else:
                self.state = search_ops.refill_lanes(
                    *splice_args, **splice_kw)
            for k in adm:
                adm[k].clear()
            return n_adm, adm_shard

    def dispatch(self, n_adm, adm_shard, speculative):
        """Dispatch one segment on (state, tt) → (its boundary summary,
        what the boundary that reaps it records about it)."""
        active = self.active
        with self.stats.phase("account"):
            meta = {
                "live": len(active),
                "helpers": sum(len(j.helpers) for j in active),
                "refilled": n_adm,
                "queue": 0 if speculative else self.q_len_locked(),
                "shard_live": self.shard_occup(),
                "shard_refilled": adm_shard,
                "traced": self.traced_snapshot(),
                "roles": self.role.copy(),
            }
        meta["t0"] = time.monotonic()
        # the segment ends when its last primary lane parks (or at the
        # cap): a helper still searching carries on in the next segment
        # and holds no position's next iteration
        watch = meta["roles"] == ROLE_PRIMARY
        with self.stats.phase("dispatch", steps=self.seg,
                              speculative=speculative):
            if self.mesh is not None:
                from ..parallel.mesh import run_segment_sharded

                # each device advances its shard locally; the summary
                # arrives stacked (n_shard, local + SUM_TAIL, SUM_W)
                self.state, self.tt, _n, summ = run_segment_sharded(
                    self.mesh, self.eng.params, self.state, self.tt,
                    self.seg, variant=self.variant,
                    prefer_deep=self.prefer_deep,
                    tt_gen=jnp.asarray(self.gen), watch=watch,
                )
            else:
                self.state, self.tt, _n, summ = search_ops._run_segment_jit(
                    self.eng.params, self.state, self.tt, self.seg,
                    self.variant, False, self.prefer_deep,
                    jnp.asarray(self.gen), jnp.asarray(watch),
                )
        return summ, meta

    def lanes(self, raw_summ, meta: dict):
        """The parks of one boundary, from its packed summary: helper
        lanes that parked on their own are charged and freed, then each
        parked primary lane gets its verdict (`on_parked`); the
        segment's lane-steps are counted by the roles `meta` took at
        dispatch. → (steps, per-shard steps, the (B,) node counts,
        now)."""
        with self.stats.phase("lanes"):
            summ, n, shard_steps = self.canon_summ(raw_summ)
            self.count_device(raw_summ)
            lane_done = summ[:, search_ops.SUM_DONE].astype(bool)
            self.stats.count(_lane_step_counts(
                n, summ[:, search_ops.SUM_RUN], meta["roles"],
                lane_done, self.seg))
            nodes_row = self.nodes_row = summ[:, search_ops.SUM_NODES]
            # lanes whose park was already handled at an earlier
            # speculative boundary (admission staged, splice still
            # pending) report DONE again — skip them
            staged = set(self.adm["lane"])
            now = time.monotonic()
            # helper lanes that parked on their own: charge+free
            lane_owner, lane_job = self.lane_owner, self.lane_job
            for lane in range(self.B):
                job = lane_owner[lane]
                if (job is not None and lane_done[lane]
                        and lane not in staged):
                    hn = int(nodes_row[lane])
                    job.nodes_total += hn
                    job.remaining -= hn
                    del job.helpers[lane]
                    lane_owner[lane] = None
                    self.role[lane] = ROLE_FREE
            # primary lanes that parked: aspiration verdict
            for lane in range(self.B):
                job = lane_job[lane]
                if (job is None or not lane_done[lane]
                        or lane in staged):
                    continue
                self.on_parked(
                    job, lane,
                    int(summ[lane, search_ops.SUM_SCORE]),
                    int(summ[lane, search_ops.SUM_MOVE]),
                    int(nodes_row[lane]), nodes_row, now,
                )
            # a lane let go of and now seen DONE stays so: free — but
            # not one a staged splice is about to restart
            seen = lane_done.copy()
            if staged:
                seen[list(staged)] = False
            self.role[(self.role == ROLE_STRAY) & seen] = ROLE_FREE
        return n, shard_steps, nodes_row, now

    def pv(self, now: float) -> None:
        """Materialize deferred PV rows from the resolved state, then
        finalize the jobs whose response waited only on the PV. The
        whole (B, max_ply) root-PV block and its (B,) lengths come
        home — a few kB whose shapes are the session's, not the
        count's — and the rows owed are picked on the host. Must run
        BEFORE refill: a refill splice resets the spliced lanes' PV
        tables."""
        with self.stats.phase("pv"):
            if not self.pv_pending:
                return
            st = self.state
            pv_rows = self.stats.fetch(st.pv[:, 0], "pv")
            pv_lens = self.stats.fetch(
                st.nt[:, 0, search_ops.NT_PVLEN], "pv_len")
            for job, lane, depth, final in self.pv_pending:
                pv = [
                    _decode_uci(int(m))
                    for m in pv_rows[lane][: int(pv_lens[lane])]
                    if m >= 0
                ]
                job.pvs.set(1, depth, pv)
                if final:
                    self.sched._finalize(job, now)
            self.pv_pending.clear()

    def account(self, meta: dict, n: int, shard_steps) -> None:
        """Close the boundary interval and record the segment it
        reaped."""
        snap = self.stats.boundary()
        self.credit(self.stats.interval_open_s)
        self.last_device_s = snap["device_ms"] / 1000.0
        with self.stats.phase("account"):
            self.sched._record_occupancy(
                self.B, n, meta["live"], meta["helpers"],
                meta["refilled"], meta["queue"],
                snap["host_ms"], snap["device_ms"],
                snap["transfers"], snap["phases"],
                counts=snap["counts"],
                shard=None if self.mesh is None else {
                    "shard_live": meta["shard_live"],
                    "shard_refilled":
                        meta["shard_refilled"]
                        or [0] * self.n_shard,
                    "shard_steps": shard_steps,
                },
            )

    # ------------------------------------------- admission and policy

    def window_for(self, job: _RefillJob, scale: int):
        """Per-lane mirror of _search_windowed's window: narrow
        around the previous depth's score, widening per failed
        attempt, full-width first at depth 1 / after a mate score."""
        use_win = (
            job.have_prev
            and abs(job.prev_score) < MATE - 1000
            and job.depth >= 2
        )
        delta = self.deltas[min(job.delta_idx, len(self.deltas) - 1)]
        if not use_win or delta is None:
            return -INF, INF, None
        return (
            max(job.prev_score - delta * scale, -INF),
            min(job.prev_score + delta * scale, INF),
            delta,
        )

    def stage(self, lane, board, depth, budget, alpha, beta, jit, grp,
              hh, hm) -> None:
        """Stage one lane's admission for the next refill splice."""
        adm = self.adm
        adm["lane"].append(lane)
        adm["board"].append(board)
        adm["depth"].append(depth)
        budget = int(np.clip(budget, 1 if depth else 0, 2**31 - 1))
        adm["budget"].append(budget)
        self.lane_budget[lane] = budget
        adm["alpha"].append(alpha)
        adm["beta"].append(beta)
        adm["jitter"].append(jit)
        adm["group"].append(grp)
        adm["hh"].append(hh)
        adm["hm"].append(hm)
        self.lane_alpha[lane] = alpha
        self.lane_beta[lane] = beta
        # fresh TT generation per admission: depth-preferred
        # replacement must never protect the lane's previous
        # occupant's entries (ops/tt.py store)
        eng = self.eng
        eng._tt_gen = (eng._tt_gen + 1) & 0x3FFFFFFF
        self.gen[lane] = eng._tt_gen

    def idle(self, lane: int) -> None:
        """Stage the idle row on a lane: the base state's depth 0 and
        budget 0 (it parks in DONE within two steps)."""
        self.stage(lane, self.filler, 0, 0, -INF, INF, 0, 0,
                   self.idle_hh, self.idle_hm)

    def admit_primary(self, job: _RefillJob, lane: int) -> None:
        job.lane = lane
        self.lane_job[lane] = job
        self.role[lane] = ROLE_PRIMARY
        job.t_spliced = time.monotonic()
        wp = job.wp
        if wp.ctx:
            obs_inflight.REGISTRY.position(
                wp.ctx.get("trace_id"), wp.position_index or 0,
                "lane", lane=lane,
            )
        if job.traced:
            rec = obs_trace.RECORDER
            if rec is not None:
                rec.instant(
                    "position.spliced", "request",
                    **obs_trace.ctx_args(
                        wp.ctx, position_index=wp.position_index,
                        lane=lane,
                    ),
                )
                rec.flow("request", wp.ctx["trace_id"], "t")
        a, b, _delta = self.window_for(job, 1)
        self.stage(lane, job.board, job.depth, self.left(job), a, b,
                   0, lane, job.hh, job.hm)

    def admit_helper(self, job: _RefillJob, lane: int, h: int) -> None:
        # same layout as _analyse_single: odd h at the primary's
        # depth (exact-depth TT entries consumable THIS iteration),
        # even h one ply deeper; staggered window scale; nonzero
        # unique jitter; group = primary lane
        sched = self.sched
        budget = self.share(job, lane)
        job.helpers[lane] = h
        self.lane_owner[lane] = job
        self.role[lane] = ROLE_HELPER
        sched._jitter_seq = (sched._jitter_seq & 0xFFFF) + 1
        a, b, _delta = self.window_for(job, 1 << min(h, 4))
        d = min(job.depth + (1 - (h & 1)), job.target_depth)
        self.stage(lane, job.board, d, budget, a, b,
                   sched._jitter_seq, job.lane, job.hh, job.hm)

    def left(self, job: _RefillJob) -> int:
        """The job's node budget left for a decision: `remaining`, less
        the nodes its helper lanes still searching had spent by the last
        boundary (a helper is charged when it parks or is let go of). A
        segment that ran until every lane parked left none of them; one
        that ends at its last primary's park can. Lanes staged for a
        splice have not started."""
        row = self.nodes_row
        if row is None or not job.helpers:
            return job.remaining
        staged = self.adm["lane"]
        return job.remaining - sum(
            int(row[h]) for h in job.helpers if h not in staged)

    def share(self, job: _RefillJob, lane: int) -> int:
        """The node budget for `lane`, about to be staged for the job:
        what is `left`, less what the job's other lanes still searching
        will spend beside it. In lockstep a lane spends nodes at about
        the rate of any other, up to what is left of its own budget
        (c), so the lane gets the x with x + sum(min(x, c)) = left."""
        budget = self.left(job)
        row = self.nodes_row
        if row is None:
            return budget
        staged = self.adm["lane"]
        caps = []
        for other in (job.lane, *job.helpers):
            if other == lane or other < 0:
                continue
            spent = 0 if other in staged else int(row[other])
            if other == job.lane:
                budget -= spent  # `left` charges a primary at its park
            caps.append(int(self.lane_budget[other]) - spent)
        caps.sort()
        k = len(caps)
        for i, c in enumerate(caps):
            x = budget // (1 + k - i)
            if x <= c:
                return x
            budget -= max(c, 0)
        return budget

    def release(self, job: _RefillJob, nodes_row) -> None:
        """Free the job's primary + helper lanes; mid-flight helper
        work is charged at its last-boundary node count (nodes_row:
        the latest boundary's (B,) per-lane node counts — the work
        actually spent against the position's budget, same honesty
        rule as _analyse_single's helper charging). The lanes turn
        stray: those not yet DONE that `admit` does not reuse get the
        idle row at the next splice, so they spend nothing uncharged."""
        if job.lane >= 0:
            self.lane_job[job.lane] = None
            self.role[job.lane] = ROLE_STRAY
            job.lane = -1
        for hl in list(job.helpers):
            if nodes_row is not None:
                hn = int(nodes_row[hl])
                job.nodes_total += hn
                job.remaining -= hn
            self.lane_owner[hl] = None
            self.role[hl] = ROLE_STRAY
        job.helpers.clear()

    def on_parked(self, job: _RefillJob, lane: int, score: int,
                  move: int, nodes: int, nodes_row, now: float) -> None:
        """One primary lane parked in DONE: fail-low/high re-search,
        next depth, or finalize — the per-lane equivalent of one
        `_search_windowed` attempt boundary. The fail checks and the
        widening schedule mirror that method exactly, so with no TT a
        refilled lane's score chain is bit-identical to the serial
        path's. The verdict and all bookkeeping come from the packed
        boundary summary; the PV row is deferred to `pv`, which reads
        it from the next RESOLVED state — legal because a DONE lane is
        frozen until the refill splice that `pv` always precedes."""
        job.nodes_depth += nodes
        a_w = int(self.lane_alpha[lane])
        b_w = int(self.lane_beta[lane])
        fail_lo = score <= a_w and a_w > -INF
        fail_hi = score >= b_w and b_w < INF
        delta = self.deltas[min(job.delta_idx, len(self.deltas) - 1)]
        if a_w > -INF or b_w < INF:
            # same per-delta accounting as _search_windowed
            st = self.eng.aspiration_stats.setdefault(delta, [0, 0, 0, 0])
            st[0] += 1
            st[1] += int(fail_lo)
            st[2] += int(fail_hi)
            st[3] += nodes
        if (fail_lo or fail_hi) and delta is not None:
            # re-search the same depth with the next wider window;
            # the lane stays this job's — only its window changes
            job.delta_idx += 1
            a, b, _d = self.window_for(job, 1)
            self.stage(lane, job.board, job.depth, self.share(job, lane), a, b,
                       0, lane, job.hh, job.hm)
            return
        # depth complete: record, charge the depth's nodes, advance
        job.prev_score = score
        job.have_prev = True
        job.hardness = max(nodes, 1)
        job.nodes_total += job.nodes_depth
        job.remaining -= job.nodes_depth
        job.nodes_depth = 0
        job.delta_idx = 0
        job.scores.set(1, job.depth, _score_from_int(score))
        job.depth_reached = job.depth
        job.best_move = _decode_uci(move) if move >= 0 else None
        final = (
            job.depth >= job.target_depth
            or self.left(job) <= 0
            or now >= job.deadline
        )
        self.pv_pending.append((job, lane, job.depth, final))
        if final:
            self.release(job, nodes_row)
            self.active.remove(job)
            return  # _finalize waits in pv for the PV row
        job.depth += 1
        a, b, _d = self.window_for(job, 1)
        self.stage(lane, job.board, job.depth, self.share(job, lane), a, b,
                   0, lane, job.hh, job.hm)

    # --------------------------------------- what a boundary records

    def q_len_locked(self) -> int:
        with self.sched._q_lock:
            return len(self.sched._pending)

    def traced_snapshot(self):
        """(ctx, lane, position_index) for every sampled job resident
        in this segment — captured at dispatch, because by the time
        the boundary is processed jobs may have parked/finalized."""
        if obs_trace.RECORDER is None:
            return ()
        return [
            (j.wp.ctx, j.lane, j.wp.position_index)
            for j in self.active if j.traced
        ]

    def residency(self, meta: dict) -> None:
        """Retroactive per-position residency spans for the segment
        just reaped: which lanes a request's positions occupied while
        the device ran — the finest grain of the request waterfall."""
        with self.stats.phase("account"):
            t0_s, t1_s = meta["t0"], time.monotonic()
            rec = obs_trace.RECORDER
            if rec is None:
                return
            for ctx, lane, idx in meta["traced"]:
                rec.complete(
                    "segment.residency", t0_s * 1e6,
                    (t1_s - t0_s) * 1e6, cat="request",
                    args=obs_trace.ctx_args(
                        ctx, lane=lane, position_index=idx
                    ),
                )

    def canon_summ(self, raw):
        """Boundary summary → ((B, SUM_W) lane rows, step count,
        per-shard step list). Single-device summaries are
        (B + SUM_TAIL, SUM_W) (ops/search.py); sharded ones come back
        stacked (n_shard, local + SUM_TAIL, SUM_W) and the step count
        is the max over shards (devices park independently)."""
        B, local = self.B, self.local
        if self.mesh is None:
            return raw[:B], int(raw[B, search_ops.SUM_DONE]), None
        lanes = raw[:, :local, :].reshape(B, search_ops.SUM_W)
        shard_steps = [
            int(x) for x in raw[:, local, search_ops.SUM_DONE]
        ]
        return lanes, max(shard_steps), shard_steps

    def count_device(self, raw) -> None:
        """The summary's last rows hold what the segment's loop counted,
        movegen, accumulator and eval work (summed over shards): into the
        interval's snapshot. (The lane rows' SUM_RUN column is read in
        `lanes`, against the roles the lanes had at dispatch.)"""
        B, local = self.B, self.local
        for row, read in enumerate((search_ops.movegen_counts,
                                    search_ops.acc_counts,
                                    search_ops.eval_counts)):
            self.stats.count(read(
                raw[B + row] if self.mesh is None else raw[:, local + row]))

    def shard_occup(self):
        """Busy (primary or helper) lane count per shard, or None
        off-mesh — the per-shard occupancy column of the log."""
        if self.mesh is None:
            return None
        local = self.local
        return [
            sum(
                1 for i in range(s * local, (s + 1) * local)
                if self.lane_job[i] is not None
                or self.lane_owner[i] is not None
            )
            for s in range(self.n_shard)
        ]
