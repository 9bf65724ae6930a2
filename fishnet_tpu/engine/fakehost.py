"""Scriptable fake engine host: deterministic fault injection.

Speaks the exact supervisor↔host protocol of engine/host.py but executes
a *fault script* instead of a real engine, so every supervisor path —
heartbeat-stall kill, deadline kill, crash respawn, corrupt-frame kill,
circuit-breaker trip and probe recovery — is exercisable in tier-1 on
CPU with no JAX import at all. tools/chaos.py replays the same scripts
against a live supervisor for manual soak testing.

A script is a JSON object:

    {"boot":   ["ready", "crash:3", "stall", "slow:2.0", ...],
     "chunks": ["ok", "hang", "stall", "crash:9", "corrupt",
                "slow:1.5", "err", "ok:333", ...]}

An optional `"device": {platform, kind, count}` rides the ready frame as
the real host's device report does. `boot[i]` is the startup behavior of
the i-th host incarnation;
`chunks[j]` the behavior for the j-th chunk EVER dispatched (counted
across respawns). Position-level `submit()` traffic (engine/session.py)
reaches a fakehost child the same way chunks do: SupervisedEngine's
ChunkSubmit conformance wraps the request as a one-position chunk and
ships it over this pipe protocol, so serve-layer tests can script the
fake host behind the HTTP front-end too. Lists are extended by repeating their last entry. The
cross-incarnation counters persist in --state (a JSON file) — without
it, every respawn would replay the script from the top and a
crash-then-recover sequence could never be expressed.

Actions:
    ready       boot only: warm up instantly and send ready
    ok[:CP]     reply with a depth-1 response per position, score cp CP
                (default 777 — a signature tests use to tell the fake
                host's responses from the CPU fallback engine's)
    slow:S      sleep S seconds (heartbeats continue), then ok
    slow-after:K[:S]  chunks 0..K-1 answer instantly, every later chunk
                sleeps S seconds (default 1.0) first — a member that
                *becomes* a straggler, for load-balancing tests (the
                chunk counter persists in --state, so the K-th chunk is
                counted across respawns like everything else)
    hang        keep heartbeating, never reply — killed at the deadline
    stall       stop ALL output and sleep forever — killed by the
                heartbeat watchdog
    crash:N     exit immediately with status N
    corrupt     write garbage bytes into the frame stream
    err         reply with an err frame (host stays alive)

Session-recovery actions (round 9) — these stream `partial` frames so
the supervisor's journal/replay/bisect/quarantine ladder is exercisable
deterministically (tests/test_recovery.py, tools/chaos.py --scenario):

    partial-ok[:CP]  a partial frame per position, then ok
    dup-partial      every partial sent twice (exactly-once check), then ok
    die-after:N      N partials, then exit 9 (kill-after-k-partials)
    stall-at:N       N partials, then stop ALL output (watchdog kill)
    hang-at:N        N partials, then heartbeat-only silence — killed at
                     the deadline, or earlier by progress_timeout
    crash-on-fp:P    stream partials per position in order, but exit 9 on
                     the position whose fingerprint starts with P — the
                     deterministic poison position the ladder must isolate

The `--echo PATH` flag appends one JSON line per boot ({"t":"boot",
argv, FISHNET_TPU_* env}) and per chunk ({"t":"go", positions, fps}) so
tests can assert the respawned child re-received the full engine config
and exactly which positions each incarnation was asked to search.
Engine-config flags of the real host (--backend/--weights/--depth/
--helpers/--refill/--partials/--hb-interval) are accepted and echoed,
never interpreted.

`--latency-ms N` adds a fixed N-millisecond service delay to EVERY
chunk before its scripted action runs (heartbeats continue). Unlike the
one-shot `slow:S` action this models a member's steady-state speed, so
fleet load-balancing and scaling tests (tests/test_fleet.py, bench.py
fleet_scaling) can build deterministically asymmetric members.
`--jitter-ms N` layers uniform [0, N] ms of per-chunk jitter on top —
service-time VARIANCE rather than speed — drawn from a RNG seeded by
(--jitter-seed, chunk index), so the delay sequence is reproducible
across runs and across respawns of the same member (an incarnation
resuming at chunk k sleeps exactly what the dead one would have).

`FlakyProxy` (in-process, asyncio) is the NETWORK counterpart of the
fault scripts: a TCP shim between a remote fleet member (HttpEngine)
and its serve endpoint that injects connection-level faults —
`refuse-for:S` (listener closed for S seconds: real ECONNREFUSED, the
transient fault fleet/faults.py retries in-dispatch), `reset-after-
headers` (RST after the request head: a mid-stream loss), and
`delay:MS` (added connect latency). tools/chaos.py --scenario
fleet-flap drives it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import socket as _socket
import struct
import sys
import threading
import time
from typing import Optional, Tuple

from ..client.ipc import wire_position_fingerprint
from .frames import FrameError, PipeClosed, read_frame, write_frame

FAKE_CP = 777  # default signature score for "ok" responses

NAMED_SCRIPTS = {
    # one-fault scripts, then recovered: the canonical chaos menu
    "ok": {"chunks": ["ok"]},
    "hang": {"chunks": ["hang", "ok"]},
    "stall": {"chunks": ["stall", "ok"]},
    "crash": {"chunks": ["crash:9", "ok"]},
    "corrupt": {"chunks": ["corrupt", "ok"]},
    "slow": {"chunks": ["slow:2.0", "ok"]},
    "err": {"chunks": ["err", "ok"]},
    # dies repeatedly, then recovers — trips a small-threshold breaker
    # and lets a later probe restore the primary path
    "flap": {"chunks": ["crash:9", "crash:9", "crash:9", "ok"]},
    # boot-time faults: warmup that never heartbeats / dies / crawls
    "boot-stall": {"boot": ["stall", "ready"]},
    "boot-crash": {"boot": ["crash:7", "ready"]},
    "boot-slow": {"boot": ["slow:3.0"]},
    # session-recovery ladder rungs (round 9)
    "partials": {"chunks": ["partial-ok"]},
    "die-mid-chunk": {"chunks": ["die-after:2", "partial-ok"]},
    "hang-mid-chunk": {"chunks": ["hang-at:1", "partial-ok"]},
    "dup-partial": {"chunks": ["dup-partial"]},
    # fast for one chunk, then a 1s straggler — the fleet planner must
    # shift load off it (tests/test_fleet.py least-backlog spread)
    "straggler": {"chunks": ["slow-after:1:1.0"]},
}


def _load_script(spec: str) -> dict:
    if spec in NAMED_SCRIPTS:
        return NAMED_SCRIPTS[spec]
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            return json.load(f)
    return json.loads(spec)


def _action(seq, index, default):
    if not seq:
        return default
    return seq[min(index, len(seq) - 1)]


class _State:
    """Cross-incarnation counters, persisted so respawns advance the
    script instead of replaying it."""

    def __init__(self, path):
        self.path = path
        self.data = {"boot": 0, "chunks": 0}
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    self.data.update(json.load(f))
            except (OSError, ValueError):
                pass

    def bump(self, key: str) -> int:
        n = self.data[key]
        self.data[key] = n + 1
        if self.path:
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.data, f)
            os.replace(tmp, self.path)
        return n


def _fake_response(wp: dict, cp: int) -> dict:
    return {
        "position_index": wp.get("position_index"),
        "url": wp.get("url"),
        "scores": [[None, {"cp": cp}]],
        "pvs": [[None, ["e2e4"]]],
        "best_move": "e2e4",
        "depth": 1,
        "nodes": 1,
        "time_s": 0.001,
        "nps": 1000,
    }


class FlakyProxy:
    """Scriptable TCP shim: client ↔ proxy ↔ target, with injectable
    connection-level faults. Runs inside the caller's event loop (tests
    and tools/chaos.py build it next to the coordinator).

    Actions (`await set_fault(...)`):

        none                 transparent pipe (the default)
        refuse-for:S         close the listening socket for S seconds —
                             connecting clients get a genuine
                             ECONNREFUSED (kernel RSTs the SYN), the
                             transient connect-phase fault the fleet
                             retries in-dispatch; the listener re-opens
                             on the SAME port when the window ends
        reset-after-headers  accept, swallow the request head, then RST
                             (SO_LINGER 0) — the request hit the wire
                             and died mid-response: a loss, never
                             retried blindly
        delay:MS             hold each new connection MS milliseconds
                             before piping — a slow network path
    """

    def __init__(self, target_host: str, target_port: int):
        self.target_host = target_host
        self.target_port = target_port
        self.host = "127.0.0.1"
        self.port = 0
        self.conns = 0  # connections actually accepted
        self._mode = "none"
        self._server: Optional[asyncio.AbstractServer] = None
        self._resume_task: Optional[asyncio.Task] = None

    async def start(self) -> Tuple[str, int]:
        self._server = await asyncio.start_server(
            self._handle, self.host, self.port or 0
        )
        self.port = self._server.sockets[0].getsockname()[1]
        return self.host, self.port

    async def close(self) -> None:
        if self._resume_task is not None:
            self._resume_task.cancel()
            self._resume_task = None
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

    async def set_fault(self, action: str) -> None:
        if action in ("", "none"):
            self._mode = "none"
            return
        if action.startswith("refuse-for:"):
            secs = float(action.split(":", 1)[1])
            await self._pause_listener(secs)
            return
        if action == "reset-after-headers" or action.startswith("delay:"):
            self._mode = action
            return
        raise ValueError(f"flaky_proxy: unknown action {action!r}")

    async def wait_recovered(self) -> None:
        """Block until a pending refuse-for window has re-opened the
        listener (chaos scenarios sequence their phases on this)."""
        if self._resume_task is not None:
            await self._resume_task
            self._resume_task = None

    async def _pause_listener(self, secs: float) -> None:
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()
            self._server = None

        async def _resume() -> None:
            await asyncio.sleep(secs)
            # same port: members keep their configured address across
            # the outage, exactly like a real host rebooting
            self._server = await asyncio.start_server(
                self._handle, self.host, self.port
            )

        self._resume_task = asyncio.ensure_future(_resume())

    async def _handle(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        self.conns += 1
        mode = self._mode
        upstream_w: Optional[asyncio.StreamWriter] = None
        try:
            if mode == "reset-after-headers":
                buf = b""
                while b"\r\n\r\n" not in buf:
                    data = await reader.read(1024)
                    if not data:
                        break
                    buf += data
                sock = writer.get_extra_info("socket")
                if sock is not None:
                    # linger(on, 0): close() sends RST, not FIN — the
                    # client sees a reset mid-response, not a clean EOF
                    sock.setsockopt(
                        _socket.SOL_SOCKET, _socket.SO_LINGER,
                        struct.pack("ii", 1, 0),
                    )
                return
            if mode.startswith("delay:"):
                await asyncio.sleep(float(mode.split(":", 1)[1]) / 1000.0)
            upstream_r, upstream_w = await asyncio.open_connection(
                self.target_host, self.target_port
            )
            await asyncio.gather(
                self._pipe(reader, upstream_w),
                self._pipe(upstream_r, writer),
            )
        except (ConnectionError, OSError, asyncio.IncompleteReadError):
            pass  # either side dropped; the other gets torn down below
        finally:
            for w in (writer, upstream_w):
                if w is None:
                    continue
                w.close()
                try:
                    await w.wait_closed()
                except (ConnectionError, OSError):
                    pass

    @staticmethod
    async def _pipe(
        reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        try:
            while True:
                data = await reader.read(65536)
                if not data:
                    break
                writer.write(data)
                await writer.drain()
        finally:
            try:
                writer.write_eof()
            except (OSError, RuntimeError):
                pass  # transport already closed


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fishnet-tpu-fake-host")
    p.add_argument("--script", required=True,
                   help="named script, inline JSON, or @path")
    p.add_argument("--state", default=None,
                   help="JSON file persisting script position across respawns")
    p.add_argument("--hb-interval", type=float, default=0.05)
    p.add_argument("--echo", default=None,
                   help="append one JSON line per boot/chunk for config-"
                        "fidelity and replay-suffix assertions")
    # engine-config flags of the real host (engine/host.py): accepted so
    # a supervisor-built host_cmd works verbatim; echoed, not interpreted
    p.add_argument("--backend", default=None)
    p.add_argument("--weights", default=None)
    p.add_argument("--depth", type=int, default=None)
    p.add_argument("--helpers", type=int, default=None)
    p.add_argument("--refill", type=int, default=None)
    p.add_argument("--partials", type=int, default=1)
    # fixed per-chunk service delay (fleet asymmetric-member tests);
    # applied before every chunk's scripted action, heartbeats continue
    p.add_argument("--latency-ms", type=float, default=0.0)
    # uniform per-chunk latency jitter in [0, N] ms on top of
    # --latency-ms, drawn from a --jitter-seed'd RNG so a given member
    # incarnation replays the identical delay sequence
    p.add_argument("--jitter-ms", type=float, default=0.0)
    p.add_argument("--jitter-seed", type=int, default=0)
    # clock-sync fault injection (obs/trace.py ClockSync): report a
    # monotonic clock running S seconds BEHIND the real one in hb/ready
    # `mono` fields, and stream a synthetic child trace ring stamped on
    # that same skewed clock — the supervisor's offset estimate must
    # land the merged events back on the parent timeline regardless
    p.add_argument("--trace-skew", type=float, default=None)
    args = p.parse_args(argv)

    script = _load_script(args.script)
    state = _State(args.state)
    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer

    def echo(record: dict) -> None:
        if args.echo:
            with open(args.echo, "a") as f:
                f.write(json.dumps(record) + "\n")

    echo({
        "t": "boot",
        "argv": list(argv) if argv is not None else sys.argv[1:],
        "env": {
            k: v for k, v in os.environ.items()
            if k.startswith("FISHNET_TPU_")
        },
    })

    wlock = threading.Lock()
    stalled = threading.Event()

    def send(obj: dict) -> None:
        with wlock:
            write_frame(stdout, obj)

    def fake_mono() -> float:
        # the child's (possibly skewed) view of its monotonic clock
        return time.monotonic() - (args.trace_skew or 0.0)

    def ticker() -> None:
        seq = 0
        while not stalled.wait(args.hb_interval):
            seq += 1
            try:
                send({"t": "hb", "phase": "fake", "busy_s": 0.0,
                      "seq": seq, "mono": fake_mono()})
            except OSError:
                os._exit(1)

    threading.Thread(target=ticker, daemon=True).start()

    def freeze() -> None:
        stalled.set()  # heartbeats cease; process lingers until killed
        while True:
            time.sleep(3600)

    boot = _action(script.get("boot"), state.bump("boot"), "ready")
    if boot.startswith("crash:"):
        os._exit(int(boot.split(":", 1)[1]))
    elif boot == "stall":
        freeze()
    elif boot.startswith("slow:"):
        time.sleep(float(boot.split(":", 1)[1]))
    ready = {"t": "ready", "mono": fake_mono()}
    if isinstance(script.get("device"), dict):
        ready["device"] = script["device"]
    send(ready)

    while True:
        try:
            msg = read_frame(stdin)
        except (PipeClosed, FrameError):
            return 0
        t = msg.get("t")
        if t == "quit":
            return 0
        if t != "go":
            continue
        gid = msg.get("id")
        positions = msg.get("chunk", {}).get("positions", [])
        fps = [wire_position_fingerprint(wp) for wp in positions]
        echo({"t": "go", "positions": len(positions), "fps": fps})
        chunk_idx = state.bump("chunks")
        action = _action(script.get("chunks"), chunk_idx, "ok")
        if args.latency_ms > 0:
            time.sleep(args.latency_ms / 1000.0)
        if args.jitter_ms > 0:
            # seeded per chunk INDEX (not per boot) so a respawned
            # incarnation resuming at chunk k sleeps the same jitter
            # the dead one would have
            jrng = random.Random(f"{args.jitter_seed}:{chunk_idx}")
            time.sleep(jrng.uniform(0.0, args.jitter_ms) / 1000.0)

        if args.trace_skew is not None:
            # one synthetic span per chunk, stamped on the SKEWED clock
            # (same epoch the mono fields report) — the supervisor must
            # shift it back onto the parent timeline when absorbing
            span = {
                "name": "fake.search", "cat": "host", "ph": "X",
                "ts": fake_mono() * 1e6,
                "dur": args.hb_interval * 1e6,
                "pid": os.getpid(), "tid": 1,
            }
            tids = sorted({
                wp["ctx"]["trace_id"] for wp in positions
                if isinstance(wp.get("ctx"), dict)
                and wp["ctx"].get("trace_id")
            })
            if tids:
                span["args"] = {"trace_ids": tids}
            # request flow hops on this child's track, same skewed clock
            # (like the real host's search span): the merged dump must
            # show each request's causal chain crossing into this
            # process — and into the survivor after a re-dispatch
            send({"t": "trace", "events": [span] + [{
                "name": "request", "cat": "request", "ph": "t",
                "id": t_id, "ts": span["ts"],
                "pid": os.getpid(), "tid": 1,
            } for t_id in tids]})

        def send_partial(wp: dict, times: int = 1, cp: int = FAKE_CP) -> None:
            frame = {
                "t": "partial",
                "id": gid,
                "fp": wire_position_fingerprint(wp),
                "response": _fake_response(wp, cp),
            }
            # echo request ctx like the real host (engine/host.py): the
            # chaos continuity scenarios assert trace_ids survive a
            # kill-mid-chunk through the journaled partials
            if isinstance(wp.get("ctx"), dict):
                frame["ctx"] = wp["ctx"]
            for _ in range(times):
                send(frame)

        if action.startswith("crash:"):
            os._exit(int(action.split(":", 1)[1]))
        elif action == "stall":
            freeze()
        elif action == "hang":
            while True:  # heartbeats keep flowing; never answer
                time.sleep(3600)
        elif action == "corrupt":
            with wlock:
                stdout.write(b"\xde\xad\xbe\xef" * 8)
                stdout.flush()
            freeze()
        elif action == "err":
            send({"t": "err", "id": gid, "error": "scripted engine error"})
            continue
        elif action.startswith("die-after:"):
            # k positions finish and stream out, then the child dies —
            # the supervisor must replay only the unfinished suffix
            k = int(action.split(":", 1)[1])
            for wp in positions[:k]:
                send_partial(wp)
            time.sleep(2 * args.hb_interval)  # let the frames flush
            os._exit(9)
        elif action.startswith("stall-at:"):
            k = int(action.split(":", 1)[1])
            for wp in positions[:k]:
                send_partial(wp)
            freeze()
        elif action.startswith("hang-at:"):
            # the device-hang signature mid-chunk: partial stream stops,
            # heartbeats keep flowing
            k = int(action.split(":", 1)[1])
            for wp in positions[:k]:
                send_partial(wp)
            while True:
                time.sleep(3600)
        elif action.startswith("crash-on-fp:"):
            # deterministic poison position, addressed by fingerprint so
            # it stays poison across replays/bisections/batches
            prefix = action.split(":", 1)[1]
            for wp in positions:
                if wire_position_fingerprint(wp).startswith(prefix):
                    time.sleep(2 * args.hb_interval)
                    os._exit(9)
                send_partial(wp)
            send({"t": "ok", "id": gid,
                  "responses": [_fake_response(wp, FAKE_CP)
                                for wp in positions]})
        elif action == "dup-partial":
            for wp in positions:
                send_partial(wp, times=2)
            send({"t": "ok", "id": gid,
                  "responses": [_fake_response(wp, FAKE_CP)
                                for wp in positions]})
        else:
            cp = FAKE_CP
            if action.startswith("slow:"):
                time.sleep(float(action.split(":", 1)[1]))
            elif action.startswith("slow-after:"):
                parts = action.split(":")
                after = int(parts[1])
                delay = float(parts[2]) if len(parts) > 2 else 1.0
                if chunk_idx >= after:
                    time.sleep(delay)
            elif action.startswith("ok:"):
                cp = int(action.split(":", 1)[1])
            elif action.startswith("partial-ok"):
                part = action.split(":", 1)
                if len(part) == 2:
                    cp = int(part[1])
                for wp in positions:
                    send_partial(wp, cp=cp)
            send({"t": "ok", "id": gid,
                  "responses": [_fake_response(wp, cp) for wp in positions]})


if __name__ == "__main__":
    sys.exit(main())
