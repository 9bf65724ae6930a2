"""Process-isolated engine supervisor: proxy + watchdog + circuit breaker.

The reference client's core robustness invariant is that an engine is
always killable: the per-core worker races each chunk against its
deadline and kills/respawns the Stockfish *subprocess* on overrun
(reference src/main.rs:263-390). The in-process TPU engine broke that
invariant — a wedged device leaves a zombie executor thread holding the
engine lock forever (docs/tpu-hang.md). `SupervisedEngine` restores it
by hosting the engine in a child process (engine/host.py) behind the
`Engine` protocol:

- **Phase heartbeats** (engine/frames.py protocol) prove the child is
  alive; the watchdog hard-kills it when the stream stalls for
  `hb_timeout`, or when an in-flight chunk overruns its deadline (the
  device-hang signature: heartbeats flow, the search phase never ends).
- **Respawn** is gated by `RandomizedBackoff` (reset on the first
  successful chunk) and re-runs the child's warmup, whose long XLA
  compiles are covered by warmup-phase heartbeats rather than a fixed
  timeout.
- **Session recovery** (round 9): the child streams each finished
  position as a `partial` frame (engine/host.py, fed by the
  LaneScheduler's exactly-once delivery hook) into an in-memory session
  journal keyed by position fingerprint (client/ipc.py). After a kill,
  the recovery ladder re-dispatches only the unfinished suffix
  (*replay*); a residual set that fails twice without progress is split
  in half (*bisection*) until the faulting position is isolated; an
  isolated poison position is *quarantined* — routed to the CPU
  fallback individually, this chunk and every later chunk, while the
  rest of the work stays on the TPU path. Failure becomes a
  per-position event instead of a per-engine event.
- **Circuit breaker**: after `breaker_threshold` child deaths within
  `breaker_window` seconds, the flavor degrades to the pure-Python CPU
  engine (engine/pyengine.py) so the client keeps acquiring and
  submitting work while the device is wedged. Every `probe_interval`
  seconds one chunk probes the child path; a successful probe restores
  it. Deaths the recovery ladder absorbs (it will replay/bisect/
  quarantine within the chunk) do NOT feed the breaker window — only
  one breaker-visible death is recorded when the ladder gives up, so a
  single poison position can no longer trip the whole-engine breaker.

Fault paths are exercised deterministically by pointing `host_cmd` at
the scriptable fake host (engine/fakehost.py); tests/test_supervisor.py
and tests/test_recovery.py cover every branch on CPU, and
tools/chaos.py replays the same scripts interactively (`--scenario`
runs the CI acceptance ladder end-to-end).
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time
from collections import deque
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Deque, Dict, List, Optional, Set, Tuple

from ..client.backoff import RandomizedBackoff
from ..client.ipc import (
    Chunk,
    PositionResponse,
    WorkPosition,
    chunk_to_wire,
    position_fingerprint,
    responses_from_wire,
)
from ..client.logger import Logger
from ..obs import perf as obs_perf
from ..obs import trace as obs_trace
from ..utils import sanitize
from ..utils import settings
from .base import EXIT_NO_ACCELERATOR, EngineError, NoAcceleratorError
from .frames import FrameError, PipeClosed, encode, read_frame_async
from .session import ChunkSubmit

# the child must be able to `import fishnet_tpu` no matter where the
# parent was launched from
_PKG_PARENT = str(Path(__file__).resolve().parents[2])


def default_host_cmd(
    backend: str = "tpu",
    weights: Optional[str] = None,
    depth: Optional[int] = None,
    weights_small: Optional[str] = None,
    hb_interval: float = 1.0,
    helpers: Optional[int] = None,
    refill: Optional[bool] = None,
    mesh_refill: Optional[bool] = None,
    partials: Optional[bool] = None,
) -> List[str]:
    cmd = [
        sys.executable, "-m", "fishnet_tpu.engine.host",
        "--backend", backend, "--hb-interval", str(hb_interval),
    ]
    if weights:
        cmd += ["--weights", str(weights)]
    if weights_small:
        cmd += ["--weights-small", str(weights_small)]
    if depth is not None:
        cmd += ["--depth", str(depth)]
    if helpers is not None:
        # Lazy-SMP lane groups (engine/tpu.py helper_lanes); 1 disables
        cmd += ["--helpers", str(helpers)]
    if refill is not None:
        # continuous lane refill (engine/tpu.py LaneScheduler); 0 disables
        cmd += ["--refill", "1" if refill else "0"]
    if mesh_refill is not None:
        # shard-aware refill on multi-chip hosts; 0 pins meshed engines
        # back to chunk-serial dispatch (FISHNET_TPU_MESH_REFILL)
        cmd += ["--mesh-refill", "1" if mesh_refill else "0"]
    if partials is not None:
        # incremental per-position result streaming for the supervisor's
        # session journal (engine/host.py partial frames); 0 disables
        cmd += ["--partials", "1" if partials else "0"]
    return cmd


@dataclass
class SupervisorStats:
    """Plain counters; introspected by tests and tools/chaos.py."""

    spawns: int = 0
    deaths: int = 0  # involuntary child exits + supervisor kills
    kills: int = 0
    hb_stalls: int = 0
    deadline_kills: int = 0
    protocol_errors: int = 0
    breaker_trips: int = 0
    breaker_resets: int = 0
    probes: int = 0
    fallback_chunks: int = 0
    chunks_ok: int = 0
    # session recovery (round 9)
    partials: int = 0            # partial frames journaled
    duplicate_partials: int = 0  # exactly-once: re-sent partials ignored
    replays: int = 0             # re-dispatches resumed with a journal-shrunk suffix
    replayed_positions: int = 0  # positions recovered from the journal, not re-searched
    bisections: int = 0          # residual splits isolating a faulting position
    quarantined: int = 0         # poison positions routed individually to CPU
    quarantine_routed: int = 0   # positions pre-routed via the quarantine list
    progress_stalls: int = 0     # kills for a stalled partial stream


class _ChildErrReply(EngineError):
    """`err` reply frame: the child handled the failure itself and is
    still sane — not a death, never retried by the recovery ladder."""


def _consume_exc(fut: asyncio.Future) -> None:
    # futures may be resolved with an exception after their awaiter gave
    # up (kill races); retrieve it so asyncio doesn't log "never retrieved"
    if not fut.cancelled():
        fut.exception()


class SupervisedEngine(ChunkSubmit):
    """`Engine`-protocol proxy to a child engine host.

    Reusable after `close()` (the worker's drop-and-respawn pattern
    closes the engine on any error and asks the factory again — the
    factory caches this object, so breaker state survives the drop)."""

    def __init__(
        self,
        host_cmd: Optional[List[str]] = None,
        *,
        backend: str = "tpu",
        weights_path: Optional[str] = None,
        weights_small_path: Optional[str] = None,
        max_depth: Optional[int] = None,
        helper_lanes: Optional[int] = None,
        refill: Optional[bool] = None,
        mesh_refill: Optional[bool] = None,
        logger: Optional[Logger] = None,
        hb_interval: float = 1.0,
        hb_timeout: Optional[float] = None,
        deadline_margin: float = 0.15,
        breaker_threshold: int = 3,
        breaker_window: float = 600.0,
        probe_interval: float = 60.0,
        fallback_factory=None,
        backoff: Optional[RandomizedBackoff] = None,
        env: Optional[dict] = None,
        replay: Optional[bool] = None,
        bisect_max: Optional[int] = None,
        quarantine: Optional[bool] = None,
        progress_timeout: Optional[float] = None,
        stats_recorder=None,
    ) -> None:
        # session-recovery policy (None defers to the settings registry)
        self.replay = (
            settings.get_bool("FISHNET_TPU_REPLAY")
            if replay is None else bool(replay)
        )
        self.bisect_max = (
            settings.get_int("FISHNET_TPU_BISECT_MAX")
            if bisect_max is None else int(bisect_max)
        )
        self.quarantine_on = (
            settings.get_bool("FISHNET_TPU_QUARANTINE")
            if quarantine is None else bool(quarantine)
        )
        # optional hang bisection: with >=1 partial delivered this
        # dispatch, a partial stream silent for this long is killed even
        # though heartbeats flow — the device-hang signature caught
        # before the deadline, leaving the ladder time to bisect
        self.progress_timeout = progress_timeout
        # what the child's nets are read from (cache/keys.py
        # engine_identity keys on both)
        self.weights_path = weights_path
        self.weights_small_path = weights_small_path
        self.host_cmd = host_cmd or default_host_cmd(
            backend=backend, weights=weights_path, depth=max_depth,
            weights_small=weights_small_path,
            hb_interval=hb_interval, helpers=helper_lanes, refill=refill,
            mesh_refill=mesh_refill, partials=self.replay,
        )
        self.logger = logger or Logger()
        self.hb_interval = hb_interval
        # N missed beats = dead, not slow: generous enough for scheduler
        # jitter, far under any chunk deadline
        self.hb_timeout = hb_timeout if hb_timeout is not None else 8 * hb_interval
        self.deadline_margin = deadline_margin
        self.breaker_threshold = breaker_threshold
        self.breaker_window = breaker_window
        self.probe_interval = probe_interval
        self.fallback_factory = fallback_factory
        self.env = env
        self.stats = SupervisorStats()

        self._lock = asyncio.Lock()  # one in-flight chunk, like TpuEngine
        self._backoff = backoff or RandomizedBackoff()
        self.proc: Optional[asyncio.subprocess.Process] = None
        self._reader: Optional[asyncio.Task] = None
        self._ready: Optional[asyncio.Future] = None
        self._pending = None  # (go id, future) for the in-flight chunk
        self._last_frame = 0.0
        self._phase: dict = {}
        # last ready frame's AOT boot report (engine/host.py): did this
        # child boot warm from a program bundle, and what does it cover
        self.aot_report: Optional[dict] = None
        self.mesh_report: Optional[dict] = None  # host mesh topology
        # {platform, kind, count} of the device the CHILD runs on, as
        # its own JAX reported it — this process never asks JAX, which
        # would take a local chip away from the child
        self.device: Optional[dict] = None
        self._last_log = ""  # child's last log line: names a boot failure
        self._down_noted = True  # no live child yet
        self._closing = False
        self._go_id = 0
        self._deaths: Deque[float] = deque()
        self._breaker_open = False
        self._next_probe = 0.0
        self._fallback = None
        # session journal: fp -> wire response, filled by partial frames
        # from the CURRENT dispatch. Single-writer invariant (lint rule
        # conc-journal-writer): mutated only via _journal_record /
        # _journal_reset, so the recovery ladder can trust its contents.
        self._journal: Dict[str, dict] = {}
        self._journal_expect: Set[str] = set()
        self._last_partial: Optional[float] = None
        # FISHNET_TPU_SANITIZE, captured once: duplicate partials then
        # verify payload consistency (identical replay is designed;
        # a DIFFERENT answer for a journaled fingerprint is a bug)
        self._sanitize = sanitize.enabled()
        # poison positions (by content fingerprint), routed individually
        # to the CPU fallback for the rest of this process's life
        self._quarantine: Set[str] = set()
        # position-ack observer (fleet/coordinator.py): called with
        # (fp, wire_response) for every partial accepted into the
        # journal, so an upstream dispatcher can keep its own
        # exactly-once ledger even when this engine's ladder gives up
        # and the journaled results above never leave go_multiple
        self.on_partial = None
        self._ladder_active = False
        self._stats_recorder = stats_recorder
        # trace timeline (obs/trace.py): when FISHNET_TPU_TRACE_DIR is
        # set, the parent ring holds the merged supervisor+host timeline
        # (the child streams increments over trace frames) and the
        # recovery ladder dumps it as the flight recorder. Install the
        # module-global recorder only if the app hasn't already.
        self._trace_dir = settings.get_str("FISHNET_TPU_TRACE_DIR")
        if self._trace_dir and obs_trace.RECORDER is None:
            obs_trace.install_from_settings("supervisor")
        # child-monotonic → parent-monotonic mapping; rebuilt per child
        # incarnation in _spawn (each process has its own epoch)
        self._clock = obs_trace.ClockSync()

    # --------------------------------------------------------------- health

    @property
    def breaker_open(self) -> bool:
        """Public breaker state for upstream health checks (the fleet
        coordinator drains members whose engines degraded to fallback)."""
        return self._breaker_open

    @property
    def heartbeat_age(self) -> Optional[float]:
        """Seconds since the child's last frame, or None with no live
        child — the fleet's per-member liveness signal."""
        if self.proc is None or self._down_noted:
            return None
        return max(time.monotonic() - self._last_frame, 0.0)

    # ------------------------------------------------------------ lifecycle

    async def start(self) -> None:
        """Spawn the child and wait for warmup (heartbeat-governed, no
        fixed timeout — XLA compiles run minutes with phase=warmup beats).
        Called by app startup; `go_multiple` also self-heals lazily."""
        async with self._lock:
            await self._ensure_ready(None)

    async def close(self) -> None:
        self._closing = True
        try:
            proc = self.proc
            if proc is not None and proc.returncode is None:
                try:
                    await self._send({"t": "quit"})
                    await asyncio.wait_for(proc.wait(), timeout=2.0)
                except (EngineError, asyncio.TimeoutError):
                    await self._kill("shutdown", count=False)
            if self._reader is not None:
                self._reader.cancel()
                await asyncio.gather(self._reader, return_exceptions=True)
            if self._fallback is not None:
                fallback, self._fallback = self._fallback, None
                await fallback.close()
        finally:
            self.proc = None
            self._reader = None
            self._ready = None
            self._pending = None
            self._down_noted = True
            self._closing = False

    # ------------------------------------------------------------- dispatch

    async def go_multiple(self, chunk: Chunk) -> List[PositionResponse]:
        async with self._lock:
            if self._breaker_open:
                if time.monotonic() >= self._next_probe:
                    self.stats.probes += 1
                    self.logger.info(
                        "Circuit breaker: probing the supervised engine path"
                    )
                    try:
                        # probes bypass the recovery ladder: one cheap
                        # dispatch decides whether the child path is back
                        responses = await self._go_child(chunk, probe=True)
                    except EngineError as e:
                        self._next_probe = time.monotonic() + self.probe_interval
                        self.logger.warn(
                            f"Probe failed ({e}); staying on CPU fallback"
                        )
                        return await self._go_fallback(chunk)
                    self._breaker_open = False
                    self.stats.breaker_resets += 1
                    self.logger.headline(
                        "Circuit breaker CLOSED: supervised engine recovered"
                    )
                    return responses
                return await self._go_fallback(chunk)
            try:
                return await self._go_child(chunk)
            except EngineError:
                if self._breaker_open and time.monotonic() < chunk.deadline:
                    # this very death tripped the breaker: salvage the
                    # chunk on the fallback instead of failing it
                    return await self._go_fallback(chunk)
                raise

    async def _go_fallback(self, chunk: Chunk) -> List[PositionResponse]:
        if self._fallback is None:
            if self.fallback_factory is not None:
                self._fallback = self.fallback_factory()
            else:
                from .pyengine import PyEngine

                self._fallback = PyEngine()
        self.stats.fallback_chunks += 1
        try:
            return await self._fallback.go_multiple(chunk)
        except EngineError:
            raise
        except Exception as e:
            raise EngineError(f"fallback engine failed: {e}") from e

    async def _go_child(
        self, chunk: Chunk, probe: bool = False
    ) -> List[PositionResponse]:
        deadline = chunk.deadline - self.deadline_margin
        pairs = [(wp, position_fingerprint(wp)) for wp in chunk.positions]
        if probe or not self.replay:
            # legacy whole-chunk semantics: one dispatch, all-or-nothing
            responses = await self._dispatch_once(
                chunk, [wp for wp, _ in pairs], deadline
            )
            self.stats.chunks_ok += 1
            return responses

        results: Dict[str, PositionResponse] = {}
        healthy: List[Tuple[WorkPosition, str]] = []
        routed: List[Tuple[WorkPosition, str]] = []
        for wp, fp in pairs:
            if self.quarantine_on and fp in self._quarantine:
                routed.append((wp, fp))
            else:
                healthy.append((wp, fp))
        if healthy:
            await self._run_ladder(chunk, healthy, results, deadline)
        for wp, fp in routed:
            # known-poison positions go straight to the CPU fallback,
            # one at a time, without risking the child
            self.stats.quarantine_routed += 1
            results[fp] = await self._go_quarantined(chunk, wp)
        self.stats.chunks_ok += 1
        return [results[fp] for _, fp in pairs]

    async def _dispatch_once(
        self, chunk: Chunk, wps: List[WorkPosition], deadline: Optional[float]
    ) -> List[PositionResponse]:
        """One go/ok round-trip for a (sub-)chunk. Success clears the
        breaker window and resets the respawn backoff; an `err` reply
        raises `_ChildErrReply`; any death/kill raises plain EngineError
        (the recovery ladder's cue to harvest the journal and retry)."""
        # clear stale journal state BEFORE _ensure_ready: a leftover
        # _last_partial from a killed dispatch must not trigger a
        # progress-stall kill during the respawned child's warmup
        self._journal_reset()
        self._last_partial = None
        await self._ensure_ready(deadline)
        sub = (
            chunk if len(wps) == len(chunk.positions)
            else replace(chunk, positions=list(wps))
        )
        self._go_id += 1
        gid = self._go_id
        fut = asyncio.get_running_loop().create_future()
        fut.add_done_callback(_consume_exc)
        self._journal_reset(expect=[position_fingerprint(wp) for wp in wps])
        self._pending = (gid, fut)
        # sampled request contexts riding the sub-chunk: the dispatch
        # span lists them and carries each flow, so a replayed suffix
        # after a kill shows up as another linked dispatch on the same
        # trace_id (the ladder reuses the same WorkPositions, ctx intact)
        tids = sorted({
            wp.ctx["trace_id"] for wp in wps
            if wp.ctx and wp.ctx.get("trace_id")
        })
        tids = [t for t in tids if obs_trace.sampled(t)]
        try:
            with obs_trace.span(
                "supervisor.dispatch", "supervisor",
                id=gid, batch=str(chunk.work.id), positions=len(wps),
                trace_ids=tids,
            ):
                rec = obs_trace.RECORDER
                if rec is not None:
                    for t_id in tids:
                        rec.flow("request", t_id, "t")
                await self._send(
                    {"t": "go", "id": gid, "chunk": chunk_to_wire(sub)}
                )
                reply = await self._watch(
                    fut, deadline, kill_on_deadline=True,
                    label=f"chunk of batch {chunk.work.id}",
                )
        finally:
            self._pending = None
        if reply.get("t") == "err":
            # the child handled the failure itself and is still sane
            raise _ChildErrReply(f"engine host: {reply.get('error')}")
        try:
            responses = responses_from_wire(chunk.work, reply["responses"])
        except (KeyError, TypeError, ValueError) as e:
            self.stats.protocol_errors += 1
            await self._kill(f"malformed ok frame: {e}")
            raise EngineError(f"engine host sent a malformed result: {e}") from e
        if len(responses) != len(wps):
            self.stats.protocol_errors += 1
            await self._kill(
                f"ok frame carries {len(responses)} responses "
                f"for {len(wps)} positions"
            )
            raise EngineError("engine host returned a mismatched result count")
        self._deaths.clear()
        self._backoff.reset()
        return responses

    # ------------------------------------------------------ recovery ladder

    async def _run_ladder(
        self,
        chunk: Chunk,
        pairs: List[Tuple[WorkPosition, str]],
        results: Dict[str, PositionResponse],
        deadline: float,
    ) -> None:
        """Replay → bisect → quarantine. Work is a queue of position
        groups (initially one group: the whole chunk). A failed dispatch
        first harvests finished positions from the session journal; a
        shrunken residual is simply retried (*replay*). A residual that
        fails twice with no progress is split in half (*bisection*,
        consistent with docs/tpu-hang.md: B=8 is clean at shapes where
        B>=16 faults) until the faulting position is isolated; an
        isolated repeat offender is *quarantined* to the CPU fallback.
        The death budget (`bisect_max`), the chunk deadline, and the
        backoff-vs-deadline check in `_ensure_ready` bound the ladder."""
        queue: Deque[List[Tuple[WorkPosition, str]]] = deque([list(pairs)])
        fail_counts: Dict[Tuple[str, ...], int] = {}
        attempts = 0
        self._ladder_active = True
        try:
            while queue:
                group = queue.popleft()
                try:
                    responses = await self._dispatch_once(
                        chunk, [wp for wp, _ in group], deadline
                    )
                except _ChildErrReply:
                    raise
                except EngineError as e:
                    attempts += 1
                    harvested = self._harvest(chunk, group, results)
                    residual = [
                        (wp, fp) for wp, fp in group if fp not in results
                    ]
                    if not residual:
                        # every position of the group was already streamed
                        self.stats.replays += 1
                        self.stats.replayed_positions += harvested
                        continue
                    now = time.monotonic()
                    if now >= deadline:
                        self._breaker_count(f"{e}")
                        raise
                    if attempts > self.bisect_max:
                        self._breaker_count(f"{e}")
                        raise EngineError(
                            f"recovery ladder exhausted after {attempts} "
                            f"child deaths for batch {chunk.work.id}: {e}"
                        ) from e
                    if harvested:
                        # progress: hand the respawned child the suffix
                        self.stats.replays += 1
                        self.stats.replayed_positions += harvested
                        self.logger.warn(
                            f"Replaying {len(residual)} unfinished of "
                            f"{len(group)} positions after: {e}"
                        )
                        queue.appendleft(residual)
                        continue
                    gkey = tuple(fp for _, fp in residual)
                    fails = fail_counts.get(gkey, 0) + 1
                    fail_counts[gkey] = fails
                    if fails < 2:
                        queue.appendleft(residual)  # plain retry
                    elif len(residual) == 1:
                        wp, fp = residual[0]
                        if not self.quarantine_on:
                            self._breaker_count(f"{e}")
                            raise
                        self._quarantine_add(fp, wp, chunk)
                        results[fp] = await self._go_quarantined(chunk, wp)
                    else:
                        mid = len(residual) // 2
                        self.stats.bisections += 1
                        self.logger.warn(
                            f"Bisecting a {len(residual)}-position "
                            f"residual that failed twice ({e})"
                        )
                        queue.appendleft(residual[mid:])
                        queue.appendleft(residual[:mid])
                else:
                    for (wp, fp), res in zip(group, responses):
                        results[fp] = res  # ok reply wins over any partial
        finally:
            self._ladder_active = False

    def _harvest(
        self,
        chunk: Chunk,
        group: List[Tuple[WorkPosition, str]],
        results: Dict[str, PositionResponse],
    ) -> int:
        """Recover journaled partials of a failed dispatch into results.
        Returns how many positions were saved from re-search."""
        harvested = 0
        for wp, fp in group:
            wire = self._journal.get(fp)
            if wire is None or fp in results:
                continue
            try:
                results[fp] = responses_from_wire(chunk.work, [wire])[0]
            except (KeyError, TypeError, ValueError):
                self.stats.protocol_errors += 1
                continue  # malformed journal entry: just re-search it
            harvested += 1
        return harvested

    async def _go_quarantined(
        self, chunk: Chunk, wp: WorkPosition
    ) -> PositionResponse:
        responses = await self._go_fallback(replace(chunk, positions=[wp]))
        if len(responses) != 1:
            raise EngineError(
                "fallback engine returned a mismatched result count"
            )
        return responses[0]

    def _quarantine_add(self, fp: str, wp: WorkPosition, chunk: Chunk) -> None:
        self._quarantine.add(fp)
        self.stats.quarantined += 1
        self.logger.error(
            f"Quarantined poison position {fp} (batch {chunk.work.id}, "
            f"index {wp.position_index}): it alone goes to the CPU "
            "fallback; the rest of the chunk stays on the engine path"
        )
        if self._stats_recorder is not None:
            try:
                self._stats_recorder.record_quarantine(
                    fp, str(chunk.work.id), wp.position_index
                )
            except Exception as e:
                self.logger.warn(f"quarantine sink write failed: {e}")

    # ------------------------------------------------------ session journal

    def _journal_reset(self, expect=()) -> None:
        """Start a fresh journal for one dispatch (with _journal_record,
        the ONLY write path — lint rule conc-journal-writer)."""
        self._journal = {}
        self._journal_expect = set(expect)

    def _journal_record(self, fp: str, wire: dict,
                        ctx: Optional[dict] = None) -> None:
        """Deliver one partial frame into the journal: the single write
        path (lint rule conc-journal-writer), called only from the
        reader task so the ladder can trust exactly-once contents."""
        if fp not in self._journal_expect:
            return  # stale or alien fingerprint
        if fp in self._journal:
            if self._sanitize:
                sanitize.check_replay_consistent(
                    self._journal, fp, wire,
                    "engine/supervisor.py::_journal_record")
            self.stats.duplicate_partials += 1
            return  # exactly-once: re-sent partials are ignored
        self._journal[fp] = wire
        self.stats.partials += 1
        self._last_partial = time.monotonic()
        # ctx rode the partial frame (engine/host.py): pin the journal
        # event to its request so a post-kill harvest/replay stays on
        # the same causal chain in the merged timeline
        rec = obs_trace.RECORDER
        if (rec is not None and ctx and ctx.get("trace_id")
                and obs_trace.sampled(ctx["trace_id"])):
            rec.instant("position.journaled", "request",
                        **obs_trace.ctx_args(ctx, fp=fp))
            rec.flow("request", ctx["trace_id"], "t")
        if self.on_partial is not None:
            try:
                self.on_partial(fp, wire)
            except Exception as e:  # observer bugs must not kill delivery
                self.logger.warn(f"on_partial observer failed: {e}")

    # ------------------------------------------------------------- watchdog

    async def _watch(self, fut, deadline, kill_on_deadline: bool, label: str):
        """Await `fut` under watchdog policy: kill on heartbeat stall
        (always) or deadline overrun (chunks: yes; warmup: give up but
        let the child keep compiling for the next chunk)."""
        while True:
            if fut.done():
                return fut.result()  # raises EngineError if the child died
            now = time.monotonic()
            hb_age = now - self._last_frame
            if hb_age > self.hb_timeout:
                self.stats.hb_stalls += 1
                await self._kill(
                    f"missed heartbeats for {hb_age:.1f}s during {label}"
                )
                raise EngineError(
                    f"engine host missed heartbeats during {label}"
                )
            if deadline is not None and now >= deadline:
                if kill_on_deadline:
                    self.stats.deadline_kills += 1
                    phase = self._phase.get("phase", "?")
                    await self._kill(
                        f"{label} overran its deadline (phase={phase})"
                    )
                    raise EngineError(f"{label} overran its deadline")
                raise EngineError(f"engine host not ready in time for {label}")
            if (
                self.progress_timeout is not None
                and self._last_partial is not None
                and now - self._last_partial > self.progress_timeout
            ):
                # heartbeats flow but the partial stream went silent: the
                # device-hang signature, caught while deadline budget
                # remains for the recovery ladder to replay/bisect
                self.stats.progress_stalls += 1
                await self._kill(
                    f"partial stream stalled for "
                    f"{now - self._last_partial:.1f}s during {label}"
                )
                raise EngineError(
                    f"engine host stopped streaming results during {label}"
                )
            timeout = max(self.hb_timeout - hb_age, self.hb_interval / 4)
            if deadline is not None:
                timeout = min(timeout, deadline - now)
            if self.progress_timeout is not None and self._last_partial is not None:
                timeout = min(
                    timeout,
                    self._last_partial + self.progress_timeout - now,
                )
            # the min() clamps above can go non-positive when a deadline
            # passes between checks; floor it so wait() never gets <=0
            # and the loop re-checks the policy branches promptly
            timeout = max(timeout, 0.01)
            await asyncio.wait([fut], timeout=timeout)

    async def _ensure_ready(self, deadline: Optional[float]) -> None:
        # _down_noted, not returncode: a crashed child's returncode stays
        # None until the event loop reaps it, but the reader task notes
        # the death the moment the pipe closes
        if self.proc is None or self._down_noted or self.proc.returncode is not None:
            if self._backoff.pending():
                delay = self._backoff.next()
                if deadline is not None and time.monotonic() + delay >= deadline:
                    raise EngineError(
                        "respawn backoff would outlast the chunk deadline"
                    )
                self.logger.warn(
                    f"Waiting {delay:.1f}s before respawning the engine host"
                )
                await asyncio.sleep(delay)
            await self._spawn()
        assert self._ready is not None
        if not self._ready.done():
            await self._watch(
                self._ready, deadline, kill_on_deadline=False, label="warmup"
            )
        else:
            self._ready.result()  # re-raise a recorded startup failure

    async def _spawn(self) -> None:
        env = dict(os.environ)
        env["PYTHONPATH"] = _PKG_PARENT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        # engine-affecting FISHNET_TPU_* vars explicitly, so a future
        # sanitized-env spawn can't strand engine config on the parent
        # side (lint rule config-engine-wire keeps this line honest)
        env.update(settings.engine_env())
        if self.env:
            env.update({k: str(v) for k, v in self.env.items()})
        try:
            self.proc = await asyncio.create_subprocess_exec(
                *self.host_cmd,
                stdin=asyncio.subprocess.PIPE,
                stdout=asyncio.subprocess.PIPE,
                stderr=None,  # engine logs/tracebacks pass through
                # own process group: ^C at the client must not reach the
                # engine mid-chunk (same as engine/uci.py)
                start_new_session=True,
                env=env,
            )
        except OSError as e:
            self._down_noted = False
            self._note_down(f"spawn failed: {e}")
            raise EngineError(f"failed to spawn engine host: {e}") from e
        self.stats.spawns += 1
        self._down_noted = False
        self._last_frame = time.monotonic()
        self._phase = {}
        # fresh child, fresh monotonic epoch: the old offset is garbage
        self._clock = obs_trace.ClockSync()
        rec = obs_trace.RECORDER
        if rec is not None:
            rec.set_process_name("engine-host", pid=self.proc.pid)
            rec.instant("spawn", "supervisor", pid=self.proc.pid)
        ready = asyncio.get_running_loop().create_future()
        ready.add_done_callback(_consume_exc)
        self._ready = ready
        self._reader = asyncio.ensure_future(self._read_loop(self.proc, ready))

    async def _read_loop(self, proc, ready_fut) -> None:
        reason = "engine host exited"
        boot_refused = False
        try:
            while True:
                try:
                    msg = await read_frame_async(proc.stdout)
                except PipeClosed:
                    if not ready_fut.done():
                        # a boot that failed: the pipe closes a moment
                        # before the loop reaps the child, so wait for
                        # the status — it tells a refused boot, which
                        # no respawn cures, from a fault that may pass
                        try:
                            await asyncio.wait_for(proc.wait(), timeout=2.0)
                        except asyncio.TimeoutError:
                            pass
                    rc = proc.returncode
                    if rc is not None and rc != 0:
                        reason = f"engine host exited with status {rc}"
                        if not ready_fut.done():
                            boot_refused = rc == EXIT_NO_ACCELERATOR
                            if self._last_log:
                                # the child's last words name the failure
                                reason += f": {self._last_log}"
                    break
                except FrameError as e:
                    self.stats.protocol_errors += 1
                    reason = f"corrupt frame: {e}"
                    await self._kill(reason)
                    break
                self._last_frame = time.monotonic()
                t = msg.get("t")
                if t == "hb":
                    self._phase = msg
                    mono = msg.get("mono")
                    if isinstance(mono, (int, float)):
                        # re-check the clock offset on every heartbeat;
                        # ClockSync keeps the min (= least pipe latency)
                        self._clock.sample(float(mono), self._last_frame)
                elif t == "ready":
                    mono = msg.get("mono")
                    if isinstance(mono, (int, float)):
                        # config-time estimate: first usable offset
                        self._clock.sample(float(mono), self._last_frame)
                    mesh_rep = msg.get("mesh")
                    if isinstance(mesh_rep, dict):
                        # pod members span devices on several processes;
                        # surface the topology next to the AOT report
                        self.mesh_report = mesh_rep
                    dev = msg.get("device")
                    if isinstance(dev, dict):
                        self.device = dev
                        obs_perf.note_device(dev)
                        self.logger.info(
                            "engine host: ready on device "
                            + json.dumps(dev, sort_keys=True)
                        )
                    rep = msg.get("aot")
                    if isinstance(rep, dict):
                        # surfaced into fleet member health and logs: a
                        # replica that booted warm (AOT bundle) vs cold
                        self.aot_report = rep
                        if rep.get("enabled"):
                            self.logger.info(
                                f"engine host: AOT assets active — "
                                f"{rep.get('programs', 0)} programs "
                                f"(bundle {rep.get('fingerprint', '?')}, "
                                f"covers "
                                f"{','.join(rep.get('covers') or []) or 'none'}"
                                f", {rep.get('errors', 0)} rejected)"
                            )
                    if not ready_fut.done():
                        ready_fut.set_result(True)
                elif t == "trace":
                    # merge the child's drained ring increment onto the
                    # parent timeline (host.py ships a hb frame carrying
                    # "mono" before any trace frame, so an offset exists
                    # by the time events arrive; 0.0 is a safe fallback
                    # for hosts that never sent one)
                    rec = obs_trace.RECORDER
                    if rec is not None:
                        off = self._clock.offset_us
                        rec.absorb(
                            msg.get("events") or (),
                            off if off is not None else 0.0,
                        )
                elif t in ("ok", "err"):
                    if self._pending is not None and self._pending[0] == msg.get("id"):
                        fut = self._pending[1]
                        if not fut.done():
                            fut.set_result(msg)
                elif t == "partial":
                    # journal one streamed position for the in-flight
                    # dispatch. Buffered partials are always drained
                    # before this coroutine's finally fails the pending
                    # future, so a post-crash harvest sees all of them.
                    fp = msg.get("fp")
                    wire = msg.get("response")
                    if (
                        self._pending is not None
                        and self._pending[0] == msg.get("id")
                        and isinstance(fp, str)
                        and isinstance(wire, dict)
                    ):
                        self._journal_record(
                            fp, wire,
                            ctx=obs_trace.ctx_from_wire(msg.get("ctx")),
                        )
                elif t == "log":
                    self._last_log = str(msg.get("msg", ""))
                    self.logger.info(f"engine host: {self._last_log}")
        except asyncio.CancelledError:
            raise
        finally:
            err = (
                NoAcceleratorError(reason) if boot_refused
                else EngineError(reason)
            )
            if not ready_fut.done():
                ready_fut.set_exception(err)
            if self._pending is not None and not self._pending[1].done():
                self._pending[1].set_exception(err)
            self._note_down(reason)

    # ------------------------------------------------------- death handling

    async def _kill(self, reason: str, count: bool = True) -> None:
        proc = self.proc
        if proc is None or proc.returncode is not None:
            return
        if count:
            self.stats.kills += 1
            self.logger.warn(f"Killing engine host: {reason}")
            self._note_down(reason)
        try:
            proc.kill()
        except ProcessLookupError:
            pass
        try:
            await asyncio.wait_for(proc.wait(), timeout=10.0)
        except asyncio.TimeoutError:
            self.logger.error("Engine host ignored SIGKILL (unreapable?)")

    def _note_down(self, reason: str) -> None:
        """Record one involuntary child death (idempotent per incarnation).
        Deaths the recovery ladder will absorb stay invisible to the
        circuit breaker — the ladder records exactly one breaker-visible
        death via `_breaker_count` if it gives up."""
        if self._down_noted:
            return
        self._down_noted = True
        if self._closing:
            return  # voluntary shutdown, not a fault
        # flight recorder: every involuntary death — crash, hb stall,
        # deadline kill, progress stall — lands here exactly once per
        # incarnation, with the child's streamed spans already merged
        self._flight_dump("child-death", reason)
        self.stats.deaths += 1
        self._backoff.next()  # arm the respawn delay
        if self._ladder_active:
            self.logger.warn(f"Engine host down: {reason} (recovery ladder active)")
            return
        self._breaker_count(reason)

    def _flight_dump(self, slug: str, reason: str) -> None:
        """Dump the merged trace ring next to the journal
        (FISHNET_TPU_TRACE_DIR). Best-effort: forensics must never turn
        a recoverable death into an unrecoverable one."""
        rec = obs_trace.RECORDER
        if rec is None or not self._trace_dir:
            return
        rec.instant("flight-dump", "supervisor", reason=reason)
        try:
            path = rec.flight_dump(self._trace_dir, slug)
        except OSError as e:
            self.logger.warn(f"Flight-recorder dump failed: {e}")
        else:
            self.logger.warn(f"Flight recorder: trace dumped to {path}")

    def _breaker_count(self, reason: str) -> None:
        """One breaker-window death; trips the breaker on the Nth within
        the window."""
        now = time.monotonic()
        self._deaths.append(now)
        while self._deaths and now - self._deaths[0] > self.breaker_window:
            self._deaths.popleft()
        if not self._breaker_open and len(self._deaths) >= self.breaker_threshold:
            self._breaker_open = True
            self._flight_dump("breaker-trip", reason)
            self.stats.breaker_trips += 1
            self._next_probe = now + self.probe_interval
            self._deaths.clear()
            self.logger.error(
                f"Engine host died {self.breaker_threshold} times within "
                f"{self.breaker_window:.0f}s ({reason}); circuit breaker OPEN "
                "— degrading to the CPU fallback engine"
            )
        else:
            self.logger.warn(f"Engine host down: {reason}")

    # ------------------------------------------------------------- plumbing

    async def _send(self, obj: dict) -> None:
        proc = self.proc
        if proc is None or proc.stdin is None:
            raise EngineError("engine host is not running")
        try:
            proc.stdin.write(encode(obj))
            await proc.stdin.drain()
        except (BrokenPipeError, ConnectionResetError, OSError) as e:
            raise EngineError(f"engine host pipe write failed: {e}") from e
