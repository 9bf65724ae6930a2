"""Acceptance smoke for multi-host mesh lowering (parallel/distributed.py).

Proves the pod-slice contract end to end, in real processes (the whole
point is a Mesh spanning process boundaries — a single-process
forced-device run exercises none of the jax.distributed placement or
the host-level boundary exchange):

1. **single reference** — one process, XLA forced to 2 CPU devices:
   a pipelined `search_stream` chunk over a staggered 4-position
   workload on a 2-device mesh; records scores/moves/nodes/PVs and the
   per-boundary occupancy log.
2. **distributed pair** — two concurrent processes, 1 CPU device each,
   joined via `jax.distributed` (FISHNET_TPU_MESH_HOSTS=2 + coordinator
   settings, exactly the env a `pod:2` fleet member injects): the SAME
   chunk through the SAME registry-derived sharded callables, with the
   boundary summary and finished-lane PV rows assembled through the
   addressable-shard fetches + host exchange.

Gate (any failure exits 1):

* both distributed processes come up (process_count == 2) and finish;
* scores, moves, nodes, PVs and total step counts bit-identical to the
  single-process reference — same global mesh shape (2 devices), same
  shard layout, so the lowering must not change a single bit;
* every no-finish boundary in the distributed run cost exactly ONE
  SyncStats fetch on the reporting host — the pipelined scheduler's
  one-fetch-per-boundary property survives multi-host lowering.

    JAX_PLATFORMS=cpu python tools/mesh_smoke.py
    JAX_PLATFORMS=cpu python tools/mesh_smoke.py --format=github
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

REPO_ROOT = Path(__file__).resolve().parents[1]

SMOKE_ENV = {
    "JAX_PLATFORMS": "cpu",
    "FISHNET_TPU_MAX_PLY": "8",
    "FISHNET_TPU_HELPERS": "1",
    # short segments: lanes park at different boundaries
    "FISHNET_TPU_SEGMENT": "150",
}
CHILD_TIMEOUT_S = 540.0

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"
GAME = ["e2e4", "c7c5", "g1f3"]
# staggered: lanes park at different boundaries on different shards, so
# the finished-lane gather path runs while other lanes are still live
DEPTHS = [1, 3, 2, 3]
WIDTH = 4
BUDGET = 120_000
MAX_PLY = 6
TT_LOG2 = 10


class SmokeFailure(Exception):
    pass


# --------------------------------------------------------------- child


def run_child(role: str, out_path: str) -> int:
    """--role single|dist: run the workload on a 2-device mesh and write
    a JSON report. Both distributed processes drive the identical loop
    (SPMD discipline); only process 0 writes."""
    pid = 0
    if role == "dist":
        # must run before ANY device use: jax.distributed turns the two
        # 1-device processes into one 2-device platform
        from fishnet_tpu.parallel import distributed as dist

        if not dist.ensure_initialized():
            print("  [child] FISHNET_TPU_MESH_HOSTS not set", flush=True)
            return 1
        import jax

        pid = jax.process_index()
        if jax.process_count() != 2:
            print(f"  [child] process_count={jax.process_count()}",
                  flush=True)
            return 1

    import jax
    import numpy as np

    from fishnet_tpu.chess import Position
    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S
    from fishnet_tpu.ops.board import from_position, stack_boards
    from fishnet_tpu.parallel.mesh import make_mesh, make_sharded_table
    from fishnet_tpu.utils.syncstats import SyncStats

    t0 = time.monotonic()
    mesh = make_mesh()
    if mesh.devices.size != 2:
        print(f"  [child] mesh has {mesh.devices.size} device(s), want 2",
              flush=True)
        return 1

    params = nnue.init_params(jax.random.PRNGKey(3), l1=64,
                              feature_set="board768")
    boards, p = [], Position.from_fen(START)
    for uci in [None] + GAME:
        if uci is not None:
            p = p.push(p.parse_uci(uci))
        boards.append(from_position(p))
    roots = stack_boards(boards)

    stats = SyncStats()
    out = S.search_stream(
        params, roots,
        np.asarray(DEPTHS, np.int32),
        np.full(len(DEPTHS), BUDGET, np.int32),
        max_ply=MAX_PLY, width=WIDTH,
        tt=make_sharded_table(mesh, TT_LOG2),
        mesh=mesh, sync_stats=stats,
    )
    report = {
        "role": role,
        "process_index": pid,
        "process_count": int(jax.process_count()),
        "devices": int(mesh.devices.size),
        "scores": np.asarray(out["score"]).astype(int).tolist(),
        "moves": np.asarray(out["move"]).astype(int).tolist(),
        "nodes": np.asarray(out["nodes"]).astype(int).tolist(),
        "pv": np.asarray(out["pv"]).astype(int).tolist(),
        "pv_len": np.asarray(out["pv_len"]).astype(int).tolist(),
        "done": np.asarray(out["done"]).astype(bool).tolist(),
        "steps": int(np.asarray(out["steps"])),
        "occupancy": [
            {k: r[k] for k in ("segment", "steps", "live", "refilled",
                               "transfers")}
            for r in out["occupancy"]
        ],
        "wall_s": round(time.monotonic() - t0, 2),
    }
    if pid == 0:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(report, fh)
    print(f"  [child p{pid}] done in {report['wall_s']}s: "
          f"scores={report['scores']} steps={report['steps']}", flush=True)
    return 0


# -------------------------------------------------------------- parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _load_json(path: Path, what: str) -> dict:
    try:
        return json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError) as e:
        raise SmokeFailure(f"{what} unreadable: {e}") from None


def _drain(tag: str, proc: subprocess.Popen, timeout_s: float) -> None:
    try:
        stdout, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise SmokeFailure(f"{tag} timed out after {timeout_s:.0f}s")
    for line in (stdout or "").splitlines():
        print(f"  [{tag}] {line}")
    if proc.returncode != 0:
        raise SmokeFailure(f"{tag} exited {proc.returncode}")


def run_smoke(keep: bool) -> None:
    tmp = Path(tempfile.mkdtemp(prefix="mesh-smoke-"))
    base = {**os.environ, **SMOKE_ENV}
    for k in ("XLA_FLAGS", "FISHNET_TPU_MESH_HOSTS",
              "FISHNET_TPU_MESH_COORDINATOR",
              "FISHNET_TPU_MESH_PROCESS_ID"):
        base.pop(k, None)
    me = str(Path(__file__).resolve())
    try:
        # ---- 1. single-process reference, forced 2 devices -----------
        ref_json = tmp / "ref.json"
        print("mesh-smoke: single-process reference (2 forced devices)",
              flush=True)
        proc = subprocess.Popen(
            [sys.executable, me, "--role", "single", "--out",
             str(ref_json)],
            cwd=str(REPO_ROOT),
            env={**base,
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
            text=True, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        _drain("single", proc, CHILD_TIMEOUT_S)
        ref = _load_json(ref_json, "single-process report")
        if not all(ref["done"]):
            raise SmokeFailure(f"reference left positions unfinished: "
                               f"{ref['done']}")

        # ---- 2. two-process jax.distributed pair ---------------------
        port = _free_port()
        dist_json = tmp / "dist.json"
        print(f"mesh-smoke: distributed pair (coordinator 127.0.0.1:"
              f"{port}, exchange on {port + 1})", flush=True)
        procs = []
        for pid in (0, 1):
            env = {
                **base,
                "FISHNET_TPU_MESH_HOSTS": "2",
                "FISHNET_TPU_MESH_COORDINATOR": f"127.0.0.1:{port}",
                "FISHNET_TPU_MESH_PROCESS_ID": str(pid),
            }
            procs.append(subprocess.Popen(
                [sys.executable, me, "--role", "dist", "--out",
                 str(dist_json)],
                cwd=str(REPO_ROOT), env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            ))
        # both must run concurrently — drain sequentially only after
        # both are launched (a worker blocks in initialize() until the
        # coordinator is up, and vice versa for the exchange)
        errs = []
        for pid, proc in enumerate(procs):
            try:
                _drain(f"dist p{pid}", proc, CHILD_TIMEOUT_S)
            except SmokeFailure as e:
                errs.append(str(e))
                for other in procs:
                    if other.poll() is None:
                        other.kill()
        if errs:
            raise SmokeFailure("; ".join(errs))
        dist = _load_json(dist_json, "distributed report")
        if dist["process_count"] != 2:
            raise SmokeFailure(
                f"distributed run spanned {dist['process_count']} "
                "process(es), want 2")

        # ---- 3. bit-identity ----------------------------------------
        for key in ("scores", "moves", "nodes", "pv", "pv_len", "done",
                    "steps"):
            if ref[key] != dist[key]:
                raise SmokeFailure(
                    f"distributed {key} diverged from single-process "
                    f"reference: {dist[key]} vs {ref[key]}")
        print(f"mesh-smoke: bit-identical — scores {ref['scores']}, "
              f"nodes {ref['nodes']}, {ref['steps']} steps")

        # ---- 4. one fetch per no-finish boundary ---------------------
        occ = dist["occupancy"]
        if not occ:
            raise SmokeFailure("distributed run recorded no boundaries")
        nofin = [r for r in occ[:-1] if r["refilled"] == 0]
        if not nofin:
            raise SmokeFailure("no quiet boundaries; shrink the segment")
        costly = [r for r in nofin if r["transfers"] != 1]
        if costly:
            raise SmokeFailure(
                "no-finish boundaries cost more than one fetch on the "
                f"reporting host: {costly}")
        print(f"mesh-smoke: boundary fetches ok — {len(nofin)} quiet "
              f"boundaries, all 1 transfer ({len(occ)} total)")
    finally:
        if not keep:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
        else:
            print(f"mesh-smoke: artifacts kept at {tmp}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--role", choices=["single", "dist"],
                        help=argparse.SUPPRESS)
    parser.add_argument("--out", metavar="OUT_JSON",
                        help=argparse.SUPPRESS)
    parser.add_argument("--keep", action="store_true",
                        help="keep the tempdir (reports)")
    parser.add_argument("--format", choices=["text", "github"],
                        default="text")
    args = parser.parse_args(argv)

    if args.role:
        return run_child(args.role, args.out)

    try:
        run_smoke(args.keep)
    except SmokeFailure as e:
        if args.format == "github":
            print(f"::error title=mesh smoke::{e}")
        print(f"mesh-smoke: FAIL: {e}")
        return 1
    print("mesh-smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
