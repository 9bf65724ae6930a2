"""Host-device synchronization instrumentation for the segment pipeline.

Every host-blocking materialization of a device value in the streaming
loops (ops/search.py search_stream, engine/tpu.py LaneScheduler) routes
through ONE choke point — SyncStats.fetch — so the per-boundary cost the
round-5 profile flagged (~290 us/step fixed gap, amplified by the
round-7 scheduler's full-result fetch at every boundary) is *measured*,
not guessed: how many transfers, how many elements, and how long the
host sat blocked on the device per segment.

The split reported per segment:

  device_ms  wall-clock the host spent BLOCKED inside fetch() — with a
             single summary fetch per boundary this approximates the
             device's segment compute time;
  host_ms    everything else in the boundary interval — scheduling,
             refill staging, result bookkeeping: the part the pipeline
             overlaps with the next segment's device compute;
  phases     the same interval by what the host was doing, measured
             where it ran: `with stats.phase("refill"):` adds its
             elapsed time to the interval's table (and, with a recorder
             on, leaves a `phase.refill` span with its true start and
             end). Phases are exclusive self-times and do not nest: a
             fetch() inside one pauses it and counts as "wait", so
             wait == device_ms, and what no phase covers is "other" —
             the table always sums to host_ms + device_ms.

The phase a thread is in is also what the engine's compile listener
(engine/tpu.py) reads through `where()` to say which part of the
program asked XLA for a program.

fishnet-lint's conc-host-sync rule (lint/concurrency_rules.py) flags
raw int()/np.asarray()/block_until_ready() on jit outputs inside the
scheduler's segment loop; routing through fetch() is the sanctioned
form precisely because it keeps these counters honest.

Keep this module free of JAX imports at module scope — like settings.py
it is imported by conftest and the linter before JAX initializes; numpy
only (np.asarray blocks on jax.Array inputs without importing jax).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional, Tuple

import numpy as np

from ..obs import trace as _trace


# What this thread is doing, for callbacks JAX makes on it with no
# argument of ours (the backend-compile listener): `sink` is the
# counter dict of the engine the thread is serving, `label` the phase
# or submit step it is in. Thread-local because several executor
# threads submit while one drives.
class _ThreadState(threading.local):
    sink: Optional[dict] = None
    label = "other"


_THREAD = _ThreadState()


def where() -> Tuple[Optional[dict], str]:
    """(counter dict of the engine this thread serves or None, label)."""
    return _THREAD.sink, _THREAD.label


@contextlib.contextmanager
def serving(sink: dict):
    """`with serving(totals):` — this thread works for the engine whose
    counters are `totals` until the block ends."""
    prev = _THREAD.sink
    _THREAD.sink = sink
    try:
        yield
    finally:
        _THREAD.sink = prev


class step:
    """`with step("submit_history") as s:` — label this thread's work
    outside a boundary interval (the submit path, session set-up) and
    time it: `s.ms` holds the elapsed milliseconds after the block."""

    __slots__ = ("_label", "_prev", "_t0", "ms")

    def __init__(self, label: str) -> None:
        self._label = label
        self.ms = 0.0

    def __enter__(self) -> "step":
        self._prev = _THREAD.label
        _THREAD.label = self._label
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.ms = (time.monotonic() - self._t0) * 1000.0
        _THREAD.label = self._prev
        return False


class _Phase:
    """One `with stats.phase(name):` block (see SyncStats.phase)."""

    __slots__ = ("_stats", "_name", "_args", "_t0", "_mark", "_self_s",
                 "_paused", "_prev")

    def __init__(self, stats: "SyncStats", name: str, args: dict) -> None:
        self._stats = stats
        self._name = name
        self._args = args

    def __enter__(self) -> "_Phase":
        self._prev = _THREAD.label
        _THREAD.label = self._name
        self._self_s = 0.0
        self._paused = False
        self._stats._phase = self
        self._t0 = self._mark = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        stats = self._stats
        stats._phase = None
        _THREAD.label = self._prev
        self_ms = (self._self_s + t1 - self._mark) * 1000.0
        table = stats._seg_phases
        table[self._name] = table.get(self._name, 0.0) + self_ms
        rec = _trace.RECORDER
        if rec is not None:
            args = self._args
            if self._paused:
                # fetches inside the span are not this phase's time
                args = dict(args, self_ms=round(self_ms, 3))
            rec.complete("phase." + self._name, self._t0 * 1e6,
                         (t1 - self._t0) * 1e6, cat="sync",
                         args=args or None)
        return False


class SyncStats:
    """Per-segment transfer and blocked-time accounting.

    One instance per streaming run (or one long-lived instance per
    engine); boundary() closes the current segment's accounting window
    and returns its snapshot dict.
    """

    def __init__(self) -> None:
        self.transfers_total = 0
        self.elements_total = 0
        self.blocked_ms_total = 0.0
        self.segments_total = 0
        self._seg_transfers = 0
        self._seg_elements = 0
        self._seg_blocked_ms = 0.0
        self._seg_phases: dict = {}
        self._seg_counts: dict = {}
        self._phase: Optional[_Phase] = None
        self._seg_start = time.monotonic()

    @property
    def interval_open_s(self) -> float:
        """time.monotonic() at which the current interval opened: the
        construction, or the last boundary()."""
        return self._seg_start

    # ------------------------------------------------------------ phase

    def phase(self, name: str, **args) -> _Phase:
        """Context manager: the block's elapsed time, less any fetch()
        inside it, is added to the current interval's `phases[name]`;
        with a recorder on it also emits one `phase.<name>` span (args
        as given) from the same two clock reads. Do not nest."""
        return _Phase(self, name, args)

    def count(self, counts: dict) -> None:
        """Add what was counted for this interval (the boundary
        summary's device counters, the lane-steps by role) to the
        interval's `counts`."""
        table = self._seg_counts
        for name, n in counts.items():
            table[name] = table.get(name, 0) + n

    # ------------------------------------------------------------ fetch

    def fetch(self, value, label: str = "") -> np.ndarray:
        """Materialize a device value on the host, counting one transfer
        and the wall-clock spent blocked. The single sanctioned host-sync
        site for the segment loops (lint rule conc-host-sync)."""
        t0 = time.monotonic()
        arr = np.asarray(value)
        t1 = time.monotonic()
        dt_ms = (t1 - t0) * 1000.0
        ph = self._phase
        if ph is not None:  # the enclosing phase stood still meanwhile
            ph._self_s += t0 - ph._mark
            ph._mark = t1
            ph._paused = True
        rec = _trace.RECORDER
        if rec is not None:
            rec.complete(
                "fetch", t0 * 1e6, dt_ms * 1000.0, cat="sync",
                args={"label": label, "elements": int(arr.size)},
            )
        self._seg_transfers += 1
        self._seg_elements += int(arr.size)
        self._seg_blocked_ms += dt_ms
        self.transfers_total += 1
        self.elements_total += int(arr.size)
        self.blocked_ms_total += dt_ms
        return arr

    # --------------------------------------------------------- boundary

    def boundary(self) -> dict:
        """Close the current segment's accounting window.

        Returns {"transfers", "elements", "device_ms", "host_ms",
        "phases", "counts"} for the interval since the previous
        boundary() (or construction): device_ms is the blocked-in-fetch
        time, host_ms the remainder of the interval's wall-clock, phases
        the same wall-clock by phase name ("wait" is device_ms, "other"
        what no phase covered), counts what count() was given.
        """
        now = time.monotonic()
        wall_ms = (now - self._seg_start) * 1000.0
        device_ms = round(self._seg_blocked_ms, 3)
        host_ms = round(max(wall_ms - self._seg_blocked_ms, 0.0), 3)
        phases = {k: round(v, 3) for k, v in self._seg_phases.items()}
        phases["wait"] = device_ms
        phases["other"] = round(
            max(host_ms - sum(self._seg_phases.values()), 0.0), 3)
        snap = {
            "transfers": self._seg_transfers,
            "elements": self._seg_elements,
            "device_ms": device_ms,
            "host_ms": host_ms,
            "phases": phases,
            "counts": self._seg_counts,
        }
        rec = _trace.RECORDER
        if rec is not None:
            # the interval itself, placed after the fact; what happened
            # inside it is on the ring already as the phase.* and fetch
            # spans, each with the start and end of the clock reads
            # that made this snapshot (tools/trace_report.py checks the
            # two against each other)
            rec.complete("segment", self._seg_start * 1e6,
                         wall_ms * 1000.0, cat="sync", args=dict(snap))
        self.segments_total += 1
        self._seg_transfers = 0
        self._seg_elements = 0
        self._seg_blocked_ms = 0.0
        self._seg_phases = {}
        self._seg_counts = {}
        self._seg_start = now
        return snap

