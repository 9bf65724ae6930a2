"""Thread-safe ring-buffer tracing on the monotonic clock.

The recorder is a bounded deque of Chrome trace-event dicts (the JSON
format Perfetto and chrome://tracing load natively): complete events
("ph": "X") for spans, instants ("i") for point markers, counter samples
("C") for time series, nestable async pairs ("b"/"e") for work that
overlaps across threads. Timestamps are `time.monotonic()` in microseconds
— never wall clock (lint rule obs-wall-clock): an NTP step must not be
able to fold a hang timeline over itself.

Cost model, in order of importance:

1. Tracing OFF (default): `RECORDER` is None. Instrumentation sites do
   `rec = trace.RECORDER` / `if rec is not None` — one attribute load
   and one identity check, zero allocation. The module-level `span()`
   helper returns a shared no-op context manager for the same price.
2. Tracing ON: one small dict append per event into a
   `collections.deque(maxlen=N)` — append and the implied eviction are
   atomic under the GIL, so the hot path takes no lock. Only drain /
   snapshot / export touch the lock-free deque in bulk.

Cross-process story: the engine host child owns its own recorder and its
ticker thread drains new events into `{"t": "trace", "events": [...]}`
frames; the supervisor `absorb()`s them into the parent ring after
shifting timestamps by the ClockSync offset. Because the parent holds
the merged ring at all times, a SIGKILL'd child still leaves its spans
in the flight-recorder dump — there is no end-of-life flush to lose.

Keep this module pure stdlib (no JAX, no numpy): it is imported by
conftest, fishnet-lint, and the engine host before JAX initializes.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import zlib
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "RECORDER",
    "ClockSync",
    "TraceRecorder",
    "counter",
    "ctx_args",
    "flow",
    "install",
    "install_from_settings",
    "instant",
    "make_ctx",
    "new_id",
    "now_us",
    "sampled",
    "span",
    "uninstall",
]

# Module-global recorder. None means tracing is off; every
# instrumentation site guards on exactly this:
#     rec = trace.RECORDER
#     if rec is not None: rec.instant(...)
RECORDER: Optional["TraceRecorder"] = None


def now_us() -> float:
    """The trace clock: monotonic microseconds."""
    return time.monotonic() * 1e6


class _NullSpan:
    """Shared no-op context manager returned by span() when tracing is
    off — no allocation on the hot path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """Context manager that emits one complete event on exit.

    Exception-safe: the event is emitted whether or not the body raised,
    and a raise annotates the event with the exception type (the span
    still closes, so the timeline never shows a hole where an error
    happened). The exception itself propagates unchanged.
    """

    __slots__ = ("_rec", "_name", "_cat", "_args", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 args: Optional[dict]) -> None:
        self._rec = rec
        self._name = name
        self._cat = cat
        self._args = args

    def __enter__(self) -> "_Span":
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.monotonic()
        args = self._args
        if exc_type is not None:
            args = dict(args) if args else {}
            args["error"] = exc_type.__name__
        self._rec.complete(
            self._name,
            self._t0 * 1e6,
            (t1 - self._t0) * 1e6,
            cat=self._cat,
            args=args,
        )
        return False


class TraceRecorder:
    """Bounded ring of Chrome trace events, safe to append from any
    thread. Oldest events fall off the back (deque maxlen), so the ring
    always holds the *last* window of activity — exactly what a flight
    recorder wants."""

    def __init__(self, capacity: int = 65536,
                 process_name: Optional[str] = None,
                 pid: Optional[int] = None) -> None:
        self.capacity = max(16, int(capacity))
        self.pid = os.getpid() if pid is None else int(pid)
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity
        )
        self._meta_lock = threading.Lock()
        self._process_names: Dict[int, str] = {}
        self._thread_names: Dict[Tuple[int, int], str] = {}
        self._dump_lock = threading.Lock()
        # Approximate (unlocked) count of everything ever emitted;
        # emitted - len(ring) estimates eviction for trace_report.
        self.emitted = 0
        if process_name:
            self.set_process_name(process_name)

    # -------------------------------------------------------- identity

    def set_process_name(self, name: str, pid: Optional[int] = None) -> None:
        with self._meta_lock:
            self._process_names[self.pid if pid is None else pid] = name

    def set_thread_name(self, name: str, tid: Optional[int] = None) -> None:
        with self._meta_lock:
            key = (self.pid, self._tid() if tid is None else tid)
            self._thread_names[key] = name

    @staticmethod
    def _tid() -> int:
        # Mask to 32 bits: CPython thread idents are pointer-sized and
        # make Perfetto's track labels unreadable at full width.
        return threading.get_ident() & 0xFFFFFFFF

    # ------------------------------------------------------------ emit

    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "app", args: Optional[dict] = None,
                 tid: Optional[int] = None) -> None:
        """One complete event ("X") with explicit start/duration — used
        both by _Span on exit and by retroactive emitters (SyncStats
        boundary accounting describes an interval that already ended)."""
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": ts_us,
            "dur": max(dur_us, 0.0),
            "pid": self.pid,
            "tid": self._tid() if tid is None else tid,
        }
        if args:
            ev["args"] = args
        self._events.append(ev)
        self.emitted += 1

    def span(self, name: str, cat: str = "app", **args) -> _Span:
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "app", **args) -> None:
        ev = {
            "name": name,
            "cat": cat,
            "ph": "i",
            "s": "t",
            "ts": now_us(),
            "pid": self.pid,
            "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        self._events.append(ev)
        self.emitted += 1

    def counter(self, name: str, value: float, cat: str = "app") -> None:
        self._events.append({
            "name": name,
            "cat": cat,
            "ph": "C",
            "ts": now_us(),
            "pid": self.pid,
            "tid": 0,
            "args": {"value": value},
        })
        self.emitted += 1

    def nest(self, name: str, nest_id: str, phase: str,
             cat: str = "app", **args) -> None:
        """One end of a nestable async slice ("b" begins, "e" ends):
        events sharing `cat` and `nest_id` stack on one async track of
        their own, whatever thread emitted them — the form for work
        that overlaps across threads (several executor threads in
        LaneScheduler._submit at once), where thread-track "X" spans
        would claim a thread's timeline that a drive session also
        uses."""
        if phase not in ("b", "e"):
            raise ValueError(f"nest phase must be b/e, got {phase!r}")
        ev = {
            "name": name,
            "cat": cat,
            "ph": phase,
            "id": str(nest_id),
            "ts": now_us(),
            "pid": self.pid,
            "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        self._events.append(ev)
        self.emitted += 1

    def flow(self, name: str, flow_id: str, phase: str = "t",
             cat: str = "app", ts_us: Optional[float] = None,
             tid: Optional[int] = None,
             args: Optional[dict] = None) -> None:
        """The span-link primitive: a Chrome flow event tying slices on
        different tracks (threads, processes) into one causal chain.

        phase "s" starts a flow, "t" carries it through an intermediate
        slice, "f" terminates it. Events sharing the same `flow_id`
        render as arrows in Perfetto; a request's trace_id is its flow
        id, so every hop a request takes — HTTP edge, admission, chunk
        dispatch, lane splice, delivery — hangs off one arrow chain even
        after absorb() merges the rings of four processes (flow ids are
        strings, immune to the timestamp shift)."""
        if phase not in ("s", "t", "f"):
            raise ValueError(f"flow phase must be s/t/f, got {phase!r}")
        ev = {
            "name": name,
            "cat": cat,
            "ph": phase,
            "id": str(flow_id),
            "ts": now_us() if ts_us is None else ts_us,
            "pid": self.pid,
            "tid": self._tid() if tid is None else tid,
        }
        if phase == "f":
            # bind to the enclosing slice's end, not the next slice's
            # start — the chain must not imply causality that isn't there
            ev["bp"] = "e"
        if args:
            ev["args"] = args
        self._events.append(ev)
        self.emitted += 1

    # ------------------------------------------------- cross-process IO

    def drain(self) -> List[dict]:
        """Pop every currently-buffered event (oldest first). The child
        ticker calls this to stream increments to the supervisor; each
        event leaves the ring exactly once."""
        out: List[dict] = []
        pop = self._events.popleft
        try:
            while True:
                out.append(pop())
        except IndexError:
            pass
        return out

    def absorb(self, events: Iterable[dict],
               offset_us: float = 0.0) -> int:
        """Merge foreign events (a child's drained increment) into this
        ring, shifting their timestamps by offset_us — the ClockSync
        estimate mapping the child's monotonic clock onto ours."""
        n = 0
        for ev in events:
            if not isinstance(ev, dict) or "ph" not in ev:
                continue
            ev = dict(ev)
            try:
                ev["ts"] = float(ev.get("ts", 0.0)) + offset_us
            except (TypeError, ValueError):
                continue
            self._events.append(ev)
            self.emitted += 1
            n += 1
        return n

    # ---------------------------------------------------------- export

    def snapshot(self, window_s: Optional[float] = None) -> List[dict]:
        """Copy of the ring (non-destructive), optionally clipped to the
        trailing window_s seconds of trace time."""
        evs = list(self._events)
        if window_s is not None:
            cutoff = now_us() - window_s * 1e6
            evs = [
                e for e in evs
                if float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
                >= cutoff
            ]
        return evs

    def _metadata_events(self) -> List[dict]:
        with self._meta_lock:
            procs = dict(self._process_names)
            threads = dict(self._thread_names)
        out: List[dict] = []
        for pid, name in sorted(procs.items()):
            out.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": name},
            })
        for (pid, tid), name in sorted(threads.items()):
            out.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": name},
            })
        return out

    def export(self, window_s: Optional[float] = None) -> dict:
        """The Chrome trace-event JSON object — load the dumped file
        straight into Perfetto / chrome://tracing. Top-level
        `buildInfo` (git sha, jax versions, backend, devices — the
        perf layer's cross-host join key) is an extra key the trace
        viewers ignore and tools/trace_report.py --compare reports."""
        evs = self.snapshot(window_s)
        evs.sort(key=lambda e: float(e.get("ts", 0.0)))
        out = {
            "traceEvents": self._metadata_events() + evs,
            "displayTimeUnit": "ms",
        }
        try:
            from . import perf

            out["buildInfo"] = perf.build_info()
        except Exception:
            pass  # a dump without build info is still a valid trace
        return out

    def dump(self, path: str, window_s: Optional[float] = None) -> str:
        """Write the export atomically (tmp + rename): a watcher tailing
        the trace dir never reads a half-written JSON."""
        with self._dump_lock:
            tmp = f"{path}.tmp.{self.pid}"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(self.export(window_s), fh)
            os.replace(tmp, path)
        return path

    def flight_dump(self, dir_path: str, reason: str,
                    window_s: Optional[float] = None) -> str:
        """The flight-recorder write: dump the trailing window into
        dir_path with a self-describing, collision-free name. Called by
        the supervisor's recovery ladder next to its journal."""
        os.makedirs(dir_path, exist_ok=True)
        safe = "".join(
            c if (c.isalnum() or c in "-_") else "-" for c in reason
        )
        stamp = time.strftime("%Y%m%dT%H%M%S")
        base = f"trace-{safe}-{stamp}-pid{self.pid}"
        path = os.path.join(dir_path, base + ".json")
        n = 1
        while os.path.exists(path):
            path = os.path.join(dir_path, f"{base}-{n}.json")
            n += 1
        return self.dump(path, window_s)


class ClockSync:
    """Child-monotonic → parent-monotonic offset estimator.

    time.monotonic() has an arbitrary per-process epoch, so child event
    timestamps mean nothing on the parent timeline until shifted. Each
    sample pairs a child reading (the "mono" field the host puts in its
    ready and hb frames) with the parent's receive time:

        offset = parent_recv_mono - child_mono

    overestimates the true epoch difference by exactly the one-way
    pipe+scheduling latency, which is strictly positive — so the MINIMUM
    over samples is the best available estimate, it can only improve as
    heartbeats keep arriving, and one quiet-moment frame pins it tight.
    Estimated from the ready frame at config time, re-checked on every
    heartbeat (supervisor._read_loop).
    """

    def __init__(self) -> None:
        self.offset_us: Optional[float] = None
        self.samples = 0

    def sample(self, child_mono_s: float,
               parent_recv_mono_s: float) -> float:
        off = (parent_recv_mono_s - child_mono_s) * 1e6
        if self.offset_us is None or off < self.offset_us:
            self.offset_us = off
        self.samples += 1
        return self.offset_us


# ----------------------------------------------------- request context
#
# A request context is the 5-tuple the tentacles of a single user
# request carry across every process boundary:
#
#     {"trace_id", "span_id", "tenant", "kind", "deadline_ms"}
#
# represented as a plain JSON-safe dict so it rides the existing wire
# dicts and pipe frames untouched (client/ipc.py chunk wire field
# "ctx", engine/frames.py partial frames, serve protocol JSON).
# trace_id names the whole request and doubles as its flow id; span_id
# names the hop that stamped the context (the parent span of everything
# downstream). The context is pure metadata: it must never reach an
# engine input or a _GroupKey — search results are bit-identical with
# tracing on or off.

CTX_KEYS = ("trace_id", "span_id", "tenant", "kind", "deadline_ms")


def new_id() -> str:
    """A fresh 16-hex-char trace/span id (64 random bits)."""
    return os.urandom(8).hex()


def make_ctx(tenant: str, kind: str, deadline_ms: Optional[int] = None,
             trace_id: Optional[str] = None,
             span_id: Optional[str] = None) -> dict:
    """Stamp a request context at an edge (serve front-end, lichess
    client). Reuses a caller-supplied trace_id (an upstream header)
    or mints one."""
    return {
        "trace_id": trace_id or new_id(),
        "span_id": span_id or new_id(),
        "tenant": str(tenant or "")[:32],
        "kind": str(kind or "")[:16],
        "deadline_ms": int(deadline_ms) if deadline_ms else None,
    }


def ctx_from_wire(obj) -> Optional[dict]:
    """Validate a context read off a wire dict / pipe frame. Foreign
    junk degrades to None (no context) rather than crashing a frame
    reader mid-chunk."""
    if not isinstance(obj, dict) or not obj.get("trace_id"):
        return None
    ctx = {k: obj.get(k) for k in CTX_KEYS}
    ctx["trace_id"] = str(ctx["trace_id"])[:32]
    ctx["span_id"] = str(ctx.get("span_id") or "")[:32]
    return ctx


def ctx_args(ctx: Optional[dict], **extra) -> dict:
    """Span-args annotation for a context: every per-request span gets
    args.trace_id so trace_report can reassemble the waterfall even
    where flow arrows were evicted from a ring."""
    if not ctx:
        return extra
    out = {"trace_id": ctx.get("trace_id"),
           "tenant": ctx.get("tenant"),
           "kind": ctx.get("kind")}
    out.update(extra)
    return out


def sampled(trace_id: str) -> bool:
    """Deterministic per-request sampling decision, shared by every
    process that sees the id: the same trace_id hashes to the same
    verdict on the serve edge, the supervisor, and the engine host, so
    a sampled request is traced at EVERY hop or none (no half
    waterfalls). Rate from FISHNET_TPU_TRACE_SAMPLE in [0, 1]."""
    rate = _sample_rate()
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return (zlib.crc32(trace_id.encode("utf-8", "replace")) & 0xFFFFFFFF) \
        < rate * 4294967296.0


def _sample_rate() -> float:
    from ..utils import settings

    raw = settings.get_str("FISHNET_TPU_TRACE_SAMPLE")
    try:
        return min(1.0, max(0.0, float(raw)))
    except (TypeError, ValueError):
        return 1.0


# ------------------------------------------------- module-level helpers
#
# Convenience wrappers for non-hot-path call sites; all are free when
# tracing is off. Hot loops should hoist `rec = trace.RECORDER` instead.


def flow(name: str, flow_id: str, phase: str = "t", cat: str = "app",
         ts_us: Optional[float] = None, args: Optional[dict] = None) -> None:
    rec = RECORDER
    if rec is not None:
        rec.flow(name, flow_id, phase, cat, ts_us=ts_us, args=args)


def span(name: str, cat: str = "app", **args):
    rec = RECORDER
    if rec is None:
        return NULL_SPAN
    return rec.span(name, cat, **args)


def instant(name: str, cat: str = "app", **args) -> None:
    rec = RECORDER
    if rec is not None:
        rec.instant(name, cat, **args)


def counter(name: str, value: float, cat: str = "app") -> None:
    rec = RECORDER
    if rec is not None:
        rec.counter(name, value, cat)


def install(recorder: TraceRecorder) -> TraceRecorder:
    global RECORDER
    RECORDER = recorder
    return recorder


def uninstall() -> None:
    global RECORDER
    RECORDER = None


def install_from_settings(process_name: str) -> Optional[TraceRecorder]:
    """Install the module-global recorder iff FISHNET_TPU_TRACE_DIR is
    set (tracing's single opt-in switch); ring size from
    FISHNET_TPU_TRACE_BUF. Returns the recorder, or None when tracing
    stays off."""
    from ..utils import settings

    trace_dir = settings.get_str("FISHNET_TPU_TRACE_DIR")
    if not trace_dir:
        return None
    capacity = settings.get_int("FISHNET_TPU_TRACE_BUF")
    return install(TraceRecorder(capacity=capacity,
                                 process_name=process_name))
