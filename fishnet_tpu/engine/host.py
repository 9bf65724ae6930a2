"""Engine host: the child-process side of the supervised engine.

Runs ONE engine (TPU by default) behind the length-framed pipe protocol
(engine/frames.py) so the parent supervisor can hard-kill it when the
device wedges — restoring the reference's "an engine is always killable"
invariant (reference src/main.rs:263-390) that an in-process JAX dispatch
breaks (a blocked device call keeps its executor thread, the engine lock,
and the device forever; docs/tpu-hang.md).

Protocol (all frames are JSON objects with a "t" tag):

  child → parent
    hb     {phase, busy_s, seq}   ticker thread, every --hb-interval
    ready  {aot, mesh, device}    warmup finished; chunks may be sent.
                                  device = {platform, kind, count} as
                                  JAX reports it HERE, in the process
                                  that owns the chip
    log    {msg}                  relayed to the parent's logger
    partial {id, fp, response,    one finished position, streamed as the
             ctx?}                engine's exactly-once delivery hook
                                  fires (feeds the supervisor's session
                                  journal; fp = client/ipc.py fingerprint;
                                  ctx = the position's request context
                                  when it rode the chunk wire)
    ok     {id, responses}        chunk result (client/ipc.py wire form)
    err    {id, error}            chunk failed but the host is still sane
  parent → child
    go     {id, chunk}            analyse one chunk
    quit   {}                     clean shutdown

Liveness contract: the ticker thread keeps beating through a blocked
device dispatch (JAX releases the GIL), so a silent heartbeat stream
means the process is frozen or dead — the supervisor kills on that. A
flowing stream with phase=search busy past the chunk deadline is the
device-hang signature — the supervisor kills on that too. Warmup is
allowed to run long (minutes of XLA compiles) exactly because its
heartbeats keep flowing with phase=warmup.

Run as:  python -m fishnet_tpu.engine.host --backend tpu|py [...]
"""
from __future__ import annotations

import argparse
import asyncio
import os
import sys
import threading
import time

from ..client.ipc import chunk_from_wire, position_fingerprint, response_to_wire
from ..obs import trace
from ..utils.heartbeat import PhaseTracker
from .base import EXIT_NO_ACCELERATOR, NoAcceleratorError
from .frames import FrameError, PipeClosed, read_frame, write_frame


def _build_engine(args, log):
    if args.backend == "py":
        from .pyengine import PyEngine

        return PyEngine(max_depth=args.depth or 3)
    from .tpu import TpuEngine

    engine = TpuEngine(
        weights_path=args.weights or None,
        max_depth=args.depth or 12,
        helper_lanes=args.helpers,
        refill=None if args.refill is None else bool(args.refill),
        mesh_refill=(None if args.mesh_refill is None
                     else bool(args.mesh_refill)),
    )
    if not args.skip_warmup:
        from ..aot import registry as aot_registry

        engine.warmup(None, log)
        if aot_registry.warm_covers("variants"):
            # every variant program is preloaded from the AOT bundle —
            # spinning the compile thread anyway would silently paper
            # over bundle misses (the aot smoke asserts it stays quiet)
            log("warmup: variant programs preloaded from AOT bundle; "
                "background compile thread skipped")
        else:
            # variant programs compile in the background, same as the old
            # in-process wiring (client/app.py round 5) — chunks interleave
            # behind the engine lock while the remaining shapes warm
            threading.Thread(
                target=lambda: engine.warmup_variants(log), daemon=True
            ).start()
    return engine


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fishnet-tpu-engine-host")
    p.add_argument("--backend", choices=["tpu", "py"], default="tpu")
    p.add_argument("--weights", default=None)
    p.add_argument("--depth", type=int, default=None)
    # Lazy-SMP lanes per analysed position (engine/tpu.py helper_lanes);
    # None defers to FISHNET_TPU_HELPERS / the engine default, 1 disables
    p.add_argument("--helpers", type=int, default=None)
    # continuous lane refill (engine/tpu.py LaneScheduler); None defers
    # to FISHNET_TPU_REFILL / the engine default, 0 disables
    p.add_argument("--refill", type=int, default=None)
    # shard-aware refill on multi-chip hosts (parallel/mesh.py sharded
    # callables); None defers to FISHNET_TPU_MESH_REFILL, 0 pins meshed
    # engines back to chunk-serial dispatch
    p.add_argument("--mesh-refill", type=int, default=None)
    # stream per-position `partial` frames for the supervisor's session
    # journal (engine/supervisor.py recovery ladder); 0 disables
    p.add_argument("--partials", type=int, default=1)
    p.add_argument("--hb-interval", type=float, default=1.0)
    p.add_argument("--skip-warmup", action="store_true")
    args = p.parse_args(argv)

    stdin = sys.stdin.buffer
    stdout = sys.stdout.buffer
    # anything the engine prints must not corrupt the frame stream
    sys.stdout = sys.stderr

    wlock = threading.Lock()
    phases = PhaseTracker("boot")
    # the host records its own ring (FISHNET_TPU_TRACE_DIR is forwarded
    # by the supervisor's engine_env overlay); the ticker streams
    # increments to the parent, which owns the merged timeline — a
    # SIGKILL'd child loses nothing that already crossed the pipe
    recorder = trace.install_from_settings("engine-host")
    if recorder is not None:
        recorder.set_thread_name("host-main")

    def send(obj: dict) -> None:
        with wlock:
            write_frame(stdout, obj)

    def log(msg) -> None:
        try:
            send({"t": "log", "msg": str(msg)})
        except OSError:
            pass

    stop = threading.Event()

    def send_trace() -> None:
        """Drain the ring into trace frames (batched well under the
        8 MiB frame cap)."""
        if recorder is None:
            return
        events = recorder.drain()
        while events:
            batch, events = events[:2000], events[2000:]
            send({"t": "trace", "events": batch})

    def ticker() -> None:
        while not stop.wait(args.hb_interval):
            snap = phases.snapshot()
            snap["t"] = "hb"
            # child monotonic reading: the supervisor's ClockSync pairs
            # it with its own receive time to map our timestamps onto
            # the parent timeline (re-checked every heartbeat)
            snap["mono"] = time.monotonic()
            try:
                send(snap)
                send_trace()
            except OSError:
                os._exit(1)  # parent gone; nothing left to serve

    threading.Thread(target=ticker, daemon=True).start()

    phases.enter("warmup")
    try:
        with trace.span("warmup", "host"):
            engine = _build_engine(args, log)
    except NoAcceleratorError as e:
        log(f"boot refused: {e}")
        return EXIT_NO_ACCELERATOR
    except Exception as e:
        log(f"engine construction/warmup failed: {type(e).__name__}: {e}")
        return 1
    # the ready frame carries the AOT boot report so the supervisor can
    # log (and the fleet surface) whether this replica booted warm, plus
    # the mesh topology (parallel/partition.py) so a pod: fleet member's
    # health surfaces how many devices/processes its one logical engine
    # actually spans, and the device the engine runs on — the parent
    # never asks JAX itself (a py-backend host has none to report)
    from ..aot import registry as aot_registry
    from ..parallel.partition import default_topology

    device = getattr(engine, "device", None)
    send({
        "t": "ready", "mono": time.monotonic(),
        "aot": aot_registry.boot_report(),
        # default_topology() asks JAX for its devices: only an engine
        # that runs on them has a mesh to report
        "mesh": default_topology() if device else None,
        "device": device,
    })
    phases.enter("idle")

    # stream each finished position the moment the engine's exactly-once
    # delivery hook fires (engine/tpu.py LaneScheduler._deliver), tagged
    # with the in-flight go id so the supervisor can journal it
    cur = {"id": None}

    def emit_partial(wp, res) -> None:
        try:
            frame = {
                "t": "partial",
                "id": cur["id"],
                "fp": position_fingerprint(wp),
                "response": response_to_wire(res),
            }
            # request context rides the partial so the supervisor's
            # journal (and a replay after a mid-chunk kill) can keep
            # the position pinned to its originating trace
            if wp.ctx:
                frame["ctx"] = wp.ctx
            send(frame)
        except OSError:
            pass  # parent gone mid-stream; the ticker exits for us

    if args.partials and hasattr(engine, "on_response"):
        engine.on_response = emit_partial

    while True:
        try:
            msg = read_frame(stdin)
        except PipeClosed:
            break
        except FrameError as e:
            log(f"protocol error from supervisor: {e}")
            return 2
        t = msg.get("t")
        if t == "quit":
            break
        if t != "go":
            log(f"ignoring unknown frame type {t!r}")
            continue
        chunk = chunk_from_wire(msg["chunk"])
        cur["id"] = msg.get("id")
        phases.enter("search")
        # sampled request contexts riding the chunk link this child's
        # search span into each request's causal chain (flow id =
        # trace_id, same as every other hop)
        tids = sorted({
            wp.ctx["trace_id"] for wp in chunk.positions
            if wp.ctx and wp.ctx.get("trace_id")
        })
        tids = [t for t in tids if trace.sampled(t)]
        try:
            with trace.span("search", "host", id=msg.get("id"),
                            positions=len(chunk.positions),
                            trace_ids=tids):
                if recorder is not None:
                    for t_id in tids:
                        recorder.flow("request", t_id, "t")
                responses = asyncio.run(engine.go_multiple(chunk))
        except Exception as e:
            send({
                "t": "err",
                "id": msg.get("id"),
                "error": f"{type(e).__name__}: {e}",
            })
        else:
            send({
                "t": "ok",
                "id": msg.get("id"),
                "responses": [response_to_wire(r) for r in responses],
            })
        phases.enter("idle")

    try:
        asyncio.run(engine.close())
    except Exception as e:
        log(f"engine close failed: {type(e).__name__}: {e}")
    try:
        send_trace()  # final flush: a clean quit ships the tail too
    except OSError:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
