#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fishnet-tpu still starts on a chip.

    python chip_smoke.py              one TPU chip, the normal path
    python chip_smoke.py --chips 4    four chips, the mesh phase only

One chip: starts the in-repo fake lichess server (tests/fake_server.py),
spawns the client through its normal entry point (`python -m fishnet_tpu
run --backend tpu`, supervisor on) at production width, feeds it one
analysis job of a real game's length and one move job, and passes only
if every ply came back scored, the move is legal, the device the ENGINE
HOST CHILD reported is a TPU, the supervisor saw one spawn and no death,
breaker trip, CPU-fallback chunk or quarantine, and the client exits 0
on SIGINT. Then it starts the client once more and times `ready` again:
cold against warm is the check that the compile cache hits.

This process never imports JAX on that path: a local chip belongs to one
process, and it must be the engine host child.

Four chips (--chips 4): in this one process, which then owns all four —
the same >= 64 positions through TpuEngine on the 4-device mesh (shard-
aware refill on) and through the single-device path, see mesh_phase().

--rehearse-cpu runs the same control flow on XLA:CPU with the CPU
shrinkers of tests/conftest.py. It must be asked for, is never taken as
a fallback, and always ends in "ok": false with platform "cpu".

Every line on stdout is one JSON object; the last one is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
or, on any failure, {"ok": false, ...} with a non-zero exit code.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from importlib import metadata
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
START_FEN = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
# Anderssen - Kieseritzky, London 1851, cut two moves before the mate so
# no analysed position is terminal: 42 plies, 43 positions
GAME_A = (
    "e2e4 e7e5 f2f4 e5f4 f1c4 d8h4 e1f1 b7b5 c4b5 g8f6 g1f3 h4h6 d2d3 f6h5 "
    "f3h4 h6g5 h4f5 c7c6 g2g4 h5f6 h1g1 c6b5 h2h4 g5g6 h4h5 g6g5 d1f3 f6g8 "
    "c1f4 g5f6 b1c3 f8c5 c3d5 f6b2 f4d6 c5g1 e4e5 b2a1 f1e2 b8a6 f5g7 e8d8"
).split()
# Morphy - Duke Karl / Count Isouard, Paris 1858, first 30 plies
GAME_B = (
    "e2e4 e7e5 g1f3 d7d6 d2d4 c8g4 d4e5 g4f3 d1f3 d6e5 f1c4 g8f6 f3b3 d8e7 "
    "b1c3 c7c6 c1g5 b7b5 c3b5 c6b5 c4b5 b8d7 e1c1 a8d8 d1d7 d8d7 h1d1 e7e6 "
    "b5d7 f6d7"
).split()
SKIP_PLIES = (3,)  # one skipped position, as lichess sends them

# what tests/conftest.py sets so the suite survives XLA:CPU (CPU_SHRINK)
# and the other switches that narrow the engine; none of these may be in
# force on the chip
SHRINKERS = (
    "FISHNET_TPU_MAX_PLY", "FISHNET_TPU_WARMUP_BUCKETS",
    "FISHNET_TPU_HELPERS", "FISHNET_TPU_REFILL", "FISHNET_TPU_MAX_LANES",
    "FISHNET_TPU_WARMUP_VARIANTS", "FISHNET_TPU_MESH_REFILL",
)
CPU_SHRINK = {
    "FISHNET_TPU_MAX_PLY": "8", "FISHNET_TPU_WARMUP_BUCKETS": "16",
    "FISHNET_TPU_HELPERS": "1",
}
PRODUCTION = {"max_ply": 32, "helpers": 4, "refill": True, "max_lanes": 1024}
PRODUCTION_BUCKETS = [16, 64, 128, 256]

READY_LINE = "Supervised TPU engine host ready."
DEVICE_RE = re.compile(r"engine host: ready on device (\{.*\})\s*$")
COUNTERS_RE = re.compile(r"Supervisor counters: (\{.*\})\s*$")
WARMUP_RE = re.compile(
    r"engine host: warmup: (?:(\w+) )?(\d+)-lane (search |move-job )?"
    r"program compiled \(([\d.]+)s\)"
)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def last_line(ok: bool, device: Optional[dict],
              failed: Optional[List[str]] = None) -> str:
    """The line the driver reads. A pass carries `ok` and `device` and
    nothing else; a failure names the checks that failed."""
    dev = None
    if device:
        dev = {"platform": device.get("platform"),
               "kind": device.get("kind"), "count": device.get("count")}
    if ok:
        return json.dumps({"ok": True, "device": dev})
    return json.dumps({"ok": False, "device": dev,
                       "failed": list(failed or [])})


class Checks:
    def __init__(self) -> None:
        self.failed: List[str] = []

    def check(self, name: str, ok: bool, **detail) -> bool:
        emit({"check": name, "ok": bool(ok), **detail})
        if not ok:
            self.failed.append(name)
        return bool(ok)


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return ""


def _settings_in_force() -> dict:
    """The engine settings this environment (which the client and its
    host child inherit) puts in force, through the repo's own registry
    (pure stdlib)."""
    from fishnet_tpu.utils import settings

    return {
        "max_ply": settings.get_int("FISHNET_TPU_MAX_PLY"),
        "helpers": settings.get_int("FISHNET_TPU_HELPERS"),
        "refill": settings.get_bool("FISHNET_TPU_REFILL"),
        "mesh_refill": settings.get_bool("FISHNET_TPU_MESH_REFILL"),
        "max_lanes": settings.get_int("FISHNET_TPU_MAX_LANES"),
        "warmup_buckets_override":
            settings.raw("FISHNET_TPU_WARMUP_BUCKETS") or None,
    }


def _cache_dir() -> str:
    from fishnet_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_CACHE_DIR))


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if not n.startswith("."))
    except OSError:
        return 0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _children(pid: int) -> List[int]:
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                stat = f.read()
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        if ppid == pid:
            out.append(int(name))
    return out


class Client:
    """The client subprocess with its stdout drained continuously: an
    unread pipe fills at 64 KB and blocks the client mid-warmup, which
    looks exactly like a hang."""

    def __init__(self, args: List[str], metrics_port: int) -> None:
        self.t0 = time.monotonic()
        self.lines: List[tuple] = []  # (seconds since spawn, text)
        self._cv = threading.Condition()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "fishnet_tpu"] + args,
            cwd=REPO, stdout=subprocess.PIPE,
            env=dict(os.environ, FISHNET_TPU_METRICS_PORT=str(metrics_port)),
            stderr=subprocess.STDOUT, text=True, bufsize=1,
        )
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self) -> None:
        for raw in self.proc.stdout:
            line = raw.rstrip("\n")
            if "cpu_aot_loader" in line:
                continue  # XLA:CPU machine-feature spam (rehearsal only)
            with self._cv:
                self.lines.append((time.monotonic() - self.t0, line))
                self._cv.notify_all()
            print(f"  | {line}", file=sys.stderr, flush=True)
        with self._cv:
            self._cv.notify_all()

    def wait_line(self, needle: str, timeout: float) -> Optional[float]:
        """Seconds since spawn at which a line containing `needle` was
        seen, or None on timeout / client exit."""
        end = time.monotonic() + timeout
        seen = 0
        with self._cv:
            while True:
                for t, line in self.lines[seen:]:
                    if needle in line:
                        return t
                seen = len(self.lines)
                left = end - time.monotonic()
                if left <= 0 or (self.proc.poll() is not None
                                 and not self._reader.is_alive()):
                    return None
                self._cv.wait(min(left, 1.0))

    def matches(self, rx: "re.Pattern") -> List[tuple]:
        with self._cv:
            return [(t, m) for t, line in self.lines
                    for m in [rx.search(line)] if m]

    def sigint_and_wait(self, timeout: float) -> Optional[int]:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        self._reader.join(timeout=10.0)
        return rc

    def kill(self) -> None:
        """Stop the client and whatever it started (the engine host runs
        in its own session, so it does not die with a process group)."""
        kids = _children(self.proc.pid) if self.proc.poll() is None else []
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        for pid in kids:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


def _scrape_metrics(port: int) -> Dict[str, str]:
    """fishnet_build_info HELP line and fishnet_supervisor_* values from
    the client's /metrics endpoint (FISHNET_TPU_METRICS_PORT)."""
    import urllib.request

    out: Dict[str, str] = {}
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5.0) as r:
            text = r.read().decode("utf-8")
    except OSError as e:
        return {"error": str(e)}
    for line in text.splitlines():
        if line.startswith("# HELP fishnet_build_info "):
            out["build_info"] = line[len("# HELP fishnet_build_info "):]
        elif line.startswith("fishnet_supervisor_"):
            k, _, v = line.partition(" ")
            out[k] = v
    return out


def _legal_after(moves: List[str], reply: Optional[str]) -> bool:
    """Is `reply` (UCI) a legal move after `moves` from the start?"""
    from fishnet_tpu.chess import Position

    p = Position.initial()
    for u in moves:
        p = p.push(p.parse_uci(u))
    try:
        return bool(reply) and p.parse_uci(reply) in p.legal_moves()
    except (ValueError, KeyError, IndexError):
        return False


def _client_args(url: str, depth: int) -> List[str]:
    return ["run", "--no-conf", "--endpoint", url, "--key", "testkey",
            "--backend", "tpu", "--cores", "1", "--tpu-depth", str(depth),
            "--no-stats-file"]


def client_phase(args, checks: Checks, t_start: float,
                 rehearse: bool) -> Optional[dict]:
    """One chip, the normal path. Returns the device the host reported."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from fake_server import FakeLichess  # pure stdlib

    def left() -> float:
        return args.budget - (time.monotonic() - t_start)

    port = _free_port()
    srv = FakeLichess().start()
    plies = len(GAME_A)
    srv.add_analysis_job("smoke-an", START_FEN, GAME_A, skip=SKIP_PLIES,
                         timeout_ms=60_000)
    device: Optional[dict] = None
    client = Client(_client_args(srv.url, args.depth), port)
    try:
        # ---- ready (cold) ------------------------------------------------
        ready_s = client.wait_line(READY_LINE, timeout=max(left() - 240, 60))
        if not checks.check("host_ready", ready_s is not None,
                            seconds_to_ready_cold=ready_s,
                            client_rc=client.proc.poll()):
            return None
        devs = client.matches(DEVICE_RE)
        if devs:
            device = json.loads(devs[-1][1].group(1))
        warm = [(m.group(1), int(m.group(2)), (m.group(3) or "").strip(),
                 float(m.group(4)))
                for t, m in client.matches(WARMUP_RE) if t <= ready_s]
        buckets = sorted(b for v, b, kind, _ in warm if kind == "search")
        # executables an AOT bundle offered and the runtime rejected are
        # a fallback to JIT: counted by the registry, shown here
        aot = [ln for _, ln in client.lines if "AOT assets active" in ln]
        emit({"phase": "aot", "enabled": bool(aot),
              "report": aot[-1] if aot else None})
        emit({"phase": "ready_cold", "seconds_to_ready": ready_s,
              "programs_compiled_before_ready": len(warm),
              "lane_buckets": buckets,
              "move_job_program": any(k == "move-job" for _, _, k, _ in warm),
              "compile_seconds": [s for *_, s in warm],
              "device_reported_by_host": device})
        checks.check("device_reported_by_host", device is not None)
        if not rehearse:
            checks.check("lane_buckets_production",
                         buckets == PRODUCTION_BUCKETS, buckets=buckets)

        # ---- move job: queued only now — the fake never re-queues a
        # batch the client had to forget. With one worker it is acquired
        # once the analysis batch (queued first) is done.
        move_moves = GAME_B[:8]
        srv.add_move_job("smoke-mv1", START_FEN, move_moves, level=8)

        # ---- analysis ----------------------------------------------------
        t_wait = time.monotonic()
        final = None
        while left() > 200 and client.proc.poll() is None:
            for body in srv.analyses.get("smoke-an", []):
                parts = body.get("analysis") or []
                if parts and parts[0] is not None:
                    final = parts
            if final is not None:
                break
            time.sleep(0.2)
        done_s = time.monotonic() - client.t0
        scored = [p for p in (final or []) if p and not p.get("skipped")]
        bad = [i for i, p in enumerate(final or [])
               if p is None or (not p.get("skipped") and not (
                   "score" in p and p.get("depth") is not None
                   and (p.get("nodes") or 0) > 0))]
        checks.check(
            "analysis_complete",
            final is not None and len(final) == plies + 1 and not bad
            and len(scored) == plies + 1 - len(SKIP_PLIES),
            plies=plies, positions=len(final or []), scored=len(scored),
            bad_plies=bad, seconds_since_spawn=round(done_s, 1),
            waited_s=round(time.monotonic() - t_wait, 1),
        )
        if scored:
            emit({"phase": "analysis", "depth_asked": args.depth,
                  "plies_scored": len(scored),
                  "nodes": sum(p["nodes"] for p in scored),
                  "depths": sorted({p["depth"] for p in scored}),
                  "sample": scored[-1]})

        # ---- the move job's answer ----------------------------------------
        def move_answer(job_id: str) -> Optional[dict]:
            """Wait until the job is acquired, then a little longer than
            its 7 s deadline for the answer."""
            while (any(j["work"]["id"] == job_id for j in list(srv.jobs))
                   and left() > 200 and client.proc.poll() is None):
                time.sleep(0.1)
            end = time.monotonic() + 20.0
            while (time.monotonic() < end and job_id not in srv.moves
                   and client.proc.poll() is None):
                time.sleep(0.1)
            return srv.moves.get(job_id)

        move_ok, move_tries = False, 0
        for job_id in ("smoke-mv1", "smoke-mv2"):
            move_tries += 1
            if move_tries > 1:
                srv.add_move_job(job_id, START_FEN, move_moves, level=8)
            body = move_answer(job_id)
            if body is not None:
                best = (body.get("move") or {}).get("bestmove")
                move_ok = _legal_after(move_moves, best)
                emit({"phase": "move_job", "job": job_id, "bestmove": best,
                      "legal": move_ok})
                break
            emit({"finding": "move job missed its deadline (a cold "
                             "in-search compile?)", "job": job_id})
        checks.check("move_job_legal_bestmove", move_ok, tries=move_tries)

        # ---- background variant compiles ---------------------------------
        def variants() -> List[str]:
            return [f"{m.group(1)}:{m.group(2)}"
                    for _, m in client.matches(WARMUP_RE) if m.group(1)]

        want = 0 if rehearse else 14  # 7 variants x (analysis, move job)
        while (len(variants()) < want and left() > 260
               and client.proc.poll() is None):
            time.sleep(1.0)
        got = variants()
        emit({"phase": "variant_programs", "compiled_in_background": got,
              "expected": want, "complete": len(got) >= want})
        if len(got) < want:
            emit({"finding": "background variant compiles were still "
                             "running when the smoke moved on",
                  "compiled": len(got), "expected": want})

        # ---- metrics surface, then a clean stop --------------------------
        emit({"phase": "metrics_scrape", **_scrape_metrics(port)})
        host_alive = client.proc.poll() is None
        rc = client.sigint_and_wait(timeout=90.0)
        checks.check("client_exits_0_on_sigint", host_alive and rc == 0,
                     rc=rc)
        counters = client.matches(COUNTERS_RE)
        sup = json.loads(counters[-1][1].group(1)) if counters else None
        emit({"phase": "supervisor_counters", "counters": sup})
        checks.check(
            "supervisor_clean",
            sup is not None and sup.get("spawns") == 1
            and sup.get("deaths") == 0 and sup.get("breaker_trips") == 0
            and sup.get("fallback_chunks") == 0
            and sup.get("quarantined") == 0
            and sup.get("quarantine_routed") == 0,
        )
    finally:
        client.kill()
        srv.stop()

    # ---- second start: is the compile cache warm? ------------------------
    cache = _cache_dir()
    emit({"phase": "compile_cache", "dir": cache,
          "entries": _cache_entries(cache)})
    if left() < 150:
        emit({"finding": "no time left for the warm restart",
              "seconds_left": round(left(), 1)})
        return device
    srv2 = FakeLichess().start()
    client2 = Client(_client_args(srv2.url, args.depth), port)
    try:
        warm_s = client2.wait_line(READY_LINE, timeout=max(left() - 60, 30))
        rc2 = client2.sigint_and_wait(timeout=60.0)
        emit({"phase": "ready_warm", "seconds_to_ready": warm_s,
              "seconds_to_ready_cold": ready_s, "client_rc": rc2,
              "compile_cache_entries": _cache_entries(cache)})
        checks.check("warm_restart", warm_s is not None and rc2 == 0)
    finally:
        client2.kill()
        srv2.stop()
    return device


# ------------------------------------------------------------- four chips


def mesh_phase(args, checks: Checks, rehearse: bool) -> Optional[dict]:
    """--chips 4: this process owns every chip. The same positions go
    through TpuEngine on the 4-device mesh (shard-aware refill on) and
    through the single-device path. Two comparisons:

    - production settings (per-shard 2^21 TT, helper lanes): every
      position answered with a legal best move and nodes > 0 on both
      paths. Node counts and even moves may differ — each shard hashes
      into its own TT — so agreement is printed, not asserted.
    - uncoupled lanes (no TT, no helpers), the setting under which
      tests/test_mesh_refill.py promises bit-identity
      (test_stream_mesh_matches_single_device,
      test_engine_mesh_refill_matches_serial): best move, depth, nodes,
      score and PV matrices must be EQUAL position by position.
    """
    import asyncio

    import jax
    import numpy as np

    from fishnet_tpu.chess import Position
    from fishnet_tpu.client.ipc import Chunk, WorkPosition
    from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
    from fishnet_tpu.engine import tpu as tpu_mod
    from fishnet_tpu.engine.tpu import TpuEngine

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit({"phase": "devices", "device": device,
          "ids": [d.id for d in devs], "max_ply": tpu_mod.MAX_PLY})
    if not checks.check("four_devices", len(devs) == 4, count=len(devs)):
        return device

    games = [GAME_A[:i] for i in range(len(GAME_A) + 1)]
    games += [GAME_B[:i] for i in range(1, len(GAME_B) + 1)]
    assert len(games) >= 64
    if rehearse:
        # XLA:CPU needs ~25 min for 73 positions on one virtual device;
        # the rehearsal is about control flow, so it takes every sixth
        games = games[::6]

    def chunk():
        work = AnalysisWork(
            id="mesh-smoke",
            nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
            timeout_s=60.0, depth=args.depth, multipv=None)
        return Chunk(
            work=work, deadline=time.monotonic() + 900,
            variant="standard", flavor=EngineFlavor.TPU,
            positions=[
                WorkPosition(work=work, position_index=i, url=None,
                             skip=False, root_fen=START_FEN, moves=list(g))
                for i, g in enumerate(games)
            ])

    def single_device(engine):
        # the single-device path of this same process (the pin the
        # refill and pipeline suites use), with a table of its own
        engine.mesh = None
        engine.n_dev = 1
        engine.tt = engine._scratch_tt()
        return engine

    def run(label, engine):
        t0 = time.monotonic()
        resp = asyncio.run(engine.go_multiple(chunk()))
        dt = time.monotonic() - t0
        by_idx = {r.position_index: r for r in resp}
        bad = []
        for i, g in enumerate(games):
            r = by_idx.get(i)
            if not (r is not None and r.nodes > 0
                    and _legal_after(g, r.best_move)):
                bad.append(i)
        emit({"phase": label, "positions": len(games),
              "answered": len(resp), "bad": bad,
              "nodes": sum(r.nodes for r in resp),
              "seconds_incl_compile": round(dt, 1)})
        checks.check(f"{label}_all_answered_legal",
                     len(resp) == len(games) and not bad)
        return by_idx

    # ---- production settings --------------------------------------------
    mesh_eng = TpuEngine(max_depth=args.depth)
    checks.check("engine_built_a_4_device_mesh",
                 mesh_eng.mesh is not None and mesh_eng.n_dev == 4
                 and mesh_eng.refill and mesh_eng.mesh_refill,
                 helpers=mesh_eng.helper_lanes, refill=mesh_eng.refill,
                 mesh_refill=mesh_eng.mesh_refill)
    got_mesh = run("mesh_production", mesh_eng)
    shards = mesh_eng.tt.data.addressable_shards
    tt_devs = sorted(s.device.id for s in shards)
    tt_used = [int(np.count_nonzero(np.asarray(s.data))) for s in shards]
    steps = [0] * 4
    for row in mesh_eng.occupancy_log:
        for i, n in enumerate(row.get("shard_steps") or []):
            steps[i] += int(n)
    emit({"phase": "mesh_residency", "tt_shard_devices": tt_devs,
          "tt_shard_nonzero_words": tt_used, "shard_steps": steps})
    checks.check("tt_shards_on_four_devices",
                 len(set(tt_devs)) == 4 and all(n > 0 for n in tt_used))
    checks.check("every_shard_stepped", all(n > 0 for n in steps))

    got_single = run("single_production",
                     single_device(TpuEngine(max_depth=args.depth)))
    same = sum(1 for i in got_mesh
               if i in got_single
               and got_mesh[i].best_move == got_single[i].best_move)
    emit({"phase": "production_agreement", "same_best_move": same,
          "of": len(games),
          "note": "printed, not asserted: per-shard TTs and helper "
                  "lanes make the two paths search different trees"})

    # ---- uncoupled lanes: bit-identity ----------------------------------
    def flat(by_idx):
        return [(i, r.best_move, r.depth, r.nodes, r.scores.matrix,
                 r.pvs.matrix) for i, r in sorted(by_idx.items())]

    plain = dict(max_depth=args.depth, tt_size_log2=0, helper_lanes=1)
    plain_mesh_eng = TpuEngine(**plain)
    a = flat(run("mesh_uncoupled", plain_mesh_eng))
    b = flat(run("single_uncoupled", single_device(TpuEngine(**plain))))
    diff = [x[0] for x, y in zip(a, b) if x != y]
    checks.check("mesh_equals_single_device_uncoupled",
                 len(a) == len(b) and not diff, differing_positions=diff,
                 compared="best_move, depth, nodes, score and pv matrices")

    # ---- the sharded segment's own output state -------------------------
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import from_position, stack_boards
    from fishnet_tpu.parallel.mesh import run_segment_sharded, shard_batch

    B = plain_mesh_eng._pad(len(games))
    roots = stack_boards([from_position(Position.initial())] * B)
    state = S._init_state_jit(
        plain_mesh_eng.params, roots, np.full(B, 2, np.int32),
        np.full(B, 10_000, np.int32), tpu_mod.MAX_PLY, "standard")
    state = shard_batch(plain_mesh_eng.mesh, state)
    out_state, _tt, n, _summ = run_segment_sharded(
        plain_mesh_eng.mesh, plain_mesh_eng.params, state, None, 64)
    jax.block_until_ready(out_state.lane)
    st_devs = sorted(s.device.id for s in out_state.lane.addressable_shards)
    emit({"phase": "state_residency", "lanes": B,
          "state_shard_devices": st_devs,
          "steps_per_shard": [int(x) for x in np.asarray(n)]})
    checks.check("state_shards_on_four_devices", len(set(st_devs)) == 4
                 and all(int(x) > 0 for x in np.asarray(n)))
    return device


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--depth", type=int, default=4,
                    help="search depth (--tpu-depth): this system's depth, "
                         "cut so the run takes minutes")
    ap.add_argument("--budget", type=float, default=1100.0,
                    help="seconds this run may take; optional waits (the "
                         "background variant compiles, the warm restart) "
                         "are cut short to stay inside it")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="same control flow on XLA:CPU at toy width; "
                         "always ends in ok:false, platform cpu")
    args = ap.parse_args(argv)
    t_start = time.monotonic()
    checks = Checks()
    device: Optional[dict] = None
    sys.path.insert(0, REPO)

    # one environment for this process and everything it starts: the
    # client inherits it, and the host child inherits the client's
    env = os.environ
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["PYTHONUNBUFFERED"] = "1"
    if args.rehearse_cpu:
        # the mesh phase keeps helpers and refill on: they ARE the phase
        shrink = CPU_SHRINK if args.chips == 1 else {
            "FISHNET_TPU_MAX_PLY": CPU_SHRINK["FISHNET_TPU_MAX_PLY"]}
        env.update(shrink, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")

    try:
        so = os.path.join(REPO, "fishnet_tpu", "cc", "libchesscore.so")
        so_at_start = os.path.exists(so)
        emit({"phase": "versions", "python": sys.version.split()[0],
              "jax": _version("jax"), "jaxlib": _version("jaxlib"),
              "libtpu": _version("libtpu"), "chips_asked": args.chips,
              "rehearse_cpu": args.rehearse_cpu, "depth": args.depth,
              "JAX_PLATFORMS": env.get("JAX_PLATFORMS"),
              "XLA_FLAGS": env.get("XLA_FLAGS")})
        if not args.rehearse_cpu:
            from fishnet_tpu.engine.base import cpu_asked_for

            set_shrinkers = [k for k in SHRINKERS if env.get(k)]
            if cpu_asked_for() or set_shrinkers:
                print("chip_smoke: refusing to run: JAX_PLATFORMS puts the "
                      f"cpu first, or CPU shrinkers are set ({set_shrinkers})"
                      ". This is the chip smoke; --rehearse-cpu is the CPU "
                      "rehearsal.", file=sys.stderr, flush=True)
                print(last_line(False, None, ["environment_asks_for_cpu"
                                              if cpu_asked_for() else
                                              "cpu_shrinkers_set"]))
                return 2
        in_force = _settings_in_force()
        emit({"phase": "settings", **in_force,
              "compile_cache_dir": _cache_dir(),
              "compile_cache_entries_at_start": _cache_entries(_cache_dir()),
              "JAX_COMPILATION_CACHE_DIR":
                  env.get("JAX_COMPILATION_CACHE_DIR")})
        if not args.rehearse_cpu:
            checks.check("production_width",
                         all(in_force[k] == v for k, v in PRODUCTION.items())
                         and in_force["warmup_buckets_override"] is None)
        if args.chips == 4:
            device = mesh_phase(args, checks, args.rehearse_cpu)
        else:
            device = client_phase(args, checks, t_start, args.rehearse_cpu)
        import shutil

        emit({"phase": "native_chess_core", "so_at_start": so_at_start,
              "so_at_end": os.path.exists(so), "gxx": shutil.which("g++"),
              "meaning": ("built here" if os.path.exists(so)
                          and not so_at_start else
                          "was already there" if so_at_start else
                          "python rules path")})
    except Exception as e:  # the last line must still be ours
        import traceback

        traceback.print_exc()
        checks.check("smoke_ran_to_its_end", False,
                     error=f"{type(e).__name__}: {e}")

    on_tpu = bool(device) and device.get("platform") == "tpu"
    checks.check("device_is_tpu", on_tpu and device.get("count") == args.chips,
                 device=device)
    emit({"phase": "done", "seconds": round(time.monotonic() - t_start, 1),
          "failed": checks.failed})
    ok = not checks.failed and not args.rehearse_cpu
    if args.rehearse_cpu:
        rest = [c for c in checks.failed if c != "device_is_tpu"]
        emit({"rehearsal": "cpu", "checks_other_than_the_device_passed":
              not rest, "failed": rest})
    print(last_line(ok, device, checks.failed), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
