"""From a profiler trace to numbers: busy per blocked second, top ops, gaps.

The reduction works on plain event lists, so it can be checked on a small
recorded trace (tests/benchmark/data). ``load_xplane`` is the thin part
that turns the profiler's ``.xplane.pb`` into those lists.

The slice is read between *marks*: the instant the trace began (the
anchor, unless a segment program was in flight then) and the boundaries
the sampler saw inside it, each with the steps
and the blocked seconds the scheduler logged for its counted segments up
to there. Between two marks lie whole boundary intervals: the scheduler's
host work with the device idle, then a segment program (an event of the
``XLA Modules`` line whose name holds ``run_segment``) on which it blocks.
What the slice gives is the seconds the device ran an operation for every
second the host was blocked on it in the same intervals
(``busy_per_wait``); the window's busy seconds are that times the blocked
seconds logged for the window's segments. A share of idle time taken from
the slice alone would swing with which two or three of a window's two
hundred segments the slice met, and with how many of them it holds; this
does neither.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

SEGMENT_MARK = "run_segment"
ANCHOR = "bench.anchor"
GAP_FLOOR_NS = 20_000
NO_SPAN = "unattributed"


def load_xplane(trace_dir: str) -> Dict[str, list]:
    """→ {"ops": [Event], "modules": [Event], "anchor_ns": int|None,
    "devices": n} from the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        return {"ops": [], "modules": [], "anchor_ns": None, "devices": 0}
    data = ProfileData.from_file(paths[-1])
    ops: List[Event] = []
    modules: List[Event] = []
    anchor = None
    devices = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += 1
            if not plane.name.endswith(":0"):
                continue  # one chip per cell; a later PR averages chips
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = int(e.start_ns)
    return {"ops": ops, "modules": modules, "anchor_ns": anchor,
            "devices": devices}


CONTAINERS = ("while", "conditional", "call")


def split_op_name(name: str) -> Tuple[str, str]:
    """HLO op event name → (kind, shape), e.g. (`sort`, `s32[64,4962]`). A
    result that is a tuple reads `(s32[..]{..}, pred[..])`: its shape is
    cut to the first element."""
    _head, sep, rest = name.partition(" = ")
    if not sep:
        return "", name[:64]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        inner, tail = rest[1:i], rest[i + 1:]
        shape = inner[:inner.find("]") + 1] + ",.." if "]" in inner else inner
    else:
        shape, _, tail = rest.partition(" ")
    kind = tail.strip().split("(", 1)[0].strip()
    return kind, shape.split("{", 1)[0].strip()


def short_op_name(name: str) -> str:
    """`kind shape`: the instruction's number changes with every compile,
    its kind and shape say what it is."""
    kind, shape = split_op_name(name)
    return f"{kind} {shape}".strip()[:64]


def is_container(name: str) -> bool:
    """A while loop, a conditional or a call is on the trace as one event
    over everything inside it, gaps included: it is not an operation that
    ran, its body's operations are."""
    return split_op_name(name)[0] in CONTAINERS


Mark = Tuple[float, int, int, float]  # monotonic s; segments, steps, blocked s so far


def reduce_trace(ops: Sequence[Event], modules: Sequence[Event],
                 marks: Sequence[Mark] = (),
                 host_spans: Sequence[Tuple[str, float, float]] = (),
                 anchor_ns: Optional[int] = None,
                 anchor_mono_s: Optional[float] = None) -> dict:
    """marks: instants between which whole boundary intervals lie, each
    with what was logged up to it (`log_marks`); host_spans:
    (name, start_s, dur_s); both on the monotonic clock, which the anchor
    pair maps onto the trace's."""
    import numpy as np

    out = {"intervals": 0, "segment_programs": 0, "segment_device_s": 0.0,
           "segments": None, "steps": None, "wait_s": None, "busy_per_wait": None,
           "busy_s": None, "window_s": None, "between_sessions_s": 0.0,
           "device_ops": [], "idle_gaps": []}
    ops = [o for o in ops if not is_container(o[0])]
    if not ops:
        return out
    starts = np.fromiter((o[1] for o in ops), np.int64, len(ops))
    ends = starts + np.fromiter((o[2] for o in ops), np.int64, len(ops))
    shift = None
    if anchor_ns is not None and anchor_mono_s is not None:
        shift = anchor_ns - int(anchor_mono_s * 1e9)
    at = sorted((int(m[0] * 1e9) + shift,) + tuple(m[1:]) for m in marks) \
        if shift is not None else []
    if len(at) >= 2:
        lo, hi = at[0][0], at[-1][0]
        out["intervals"] = len(at) - 1
        out["segments"] = [at[0][1], at[-1][1]]  # after the first, up to the last
        out["steps"] = at[-1][2] - at[0][2]
        out["wait_s"] = at[-1][3] - at[0][3]
        segs = [m for m in modules
                if SEGMENT_MARK in m[0] and m[1] >= lo and m[1] + m[2] <= hi]
        out["segment_programs"] = len(segs)
        out["segment_device_s"] = sum(m[2] for m in segs) / 1e9
    else:
        # no whole interval: busy over the traced span is still reported
        # for the device key, nothing is carried over the window
        lo, hi = int(starts.min()), int(ends.max())
    keep = (ends > lo) & (starts < hi)
    names = [o[0] for o, k in zip(ops, keep) if k]
    starts = np.clip(starts[keep], lo, hi)
    ends = np.clip(ends[keep], lo, hi)
    order = np.argsort(starts, kind="stable")
    s_sorted, e_sorted = starts[order], ends[order]
    # covered-so-far: a gap opens wherever the next start lies past it
    reach = np.maximum.accumulate(e_sorted)
    prev_reach = np.concatenate([[lo], reach[:-1]])
    gap_len = np.maximum(s_sorted - prev_reach, 0)
    tail_gap = max(hi - (int(reach[-1]) if len(reach) else lo), 0)
    idle = int(gap_len.sum()) + tail_gap
    busy = (hi - lo) - idle
    out["busy_s"] = busy / 1e9
    out["window_s"] = (hi - lo) / 1e9
    if out["intervals"] and out["wait_s"] > 0:
        out["busy_per_wait"] = out["busy_s"] / out["wait_s"]
    by_op: Dict[str, int] = {}
    for name, d in zip(names, (ends - starts).tolist()):
        by_op[name] = by_op.get(name, 0) + d
    short: Dict[str, int] = {}
    for name, d in by_op.items():
        key = short_op_name(name)
        short[key] = short.get(key, 0) + d
    out["device_ops"] = [
        [k, v / 1e9] for k, v in
        sorted(short.items(), key=lambda kv: -kv[1])[:10]
    ]
    spans_ns = []
    if shift is not None:
        spans_ns = [(n, int(s * 1e9) + shift, int((s + d) * 1e9) + shift)
                    for n, s, d in host_spans]
        spans_ns = [sp for sp in spans_ns if sp[2] > lo and sp[1] < hi]
    # gaps between the ops of one step are the program's own scheduling
    # gaps; only longer ones are asked what the host was doing. The
    # narrowest host span over a gap's middle names it.
    by_span: Dict[str, int] = {}
    big = np.nonzero(gap_len >= GAP_FLOOR_NS)[0]
    small_total = int(gap_len.sum()) - int(gap_len[big].sum())
    gap_list = [(int(prev_reach[i]), int(s_sorted[i])) for i in big.tolist()]
    if tail_gap >= GAP_FLOOR_NS:
        gap_list.append((hi - tail_gap, hi))
    else:
        small_total += tail_gap
    for g0, g1 in gap_list:
        # a long gap can run from one span into another (or out of every
        # span): cut it where spans begin and end, name each piece
        edges = sorted({g0, g1} | {t for _n, a, b in spans_ns
                                   for t in (a, b) if g0 < t < g1})
        for s, e in zip(edges, edges[1:]):
            mid = (s + e) // 2
            best = None
            for n, a, b in spans_ns:
                if a <= mid < b and (best is None or b - a < best[1]):
                    best = (n, b - a)
            key = best[0] if best else NO_SPAN
            by_span[key] = by_span.get(key, 0) + e - s
    # with the scheduler's spans on the trace's clock, a gap under none of
    # them is time between two drive sessions
    between = by_span.get(NO_SPAN, 0) if spans_ns else 0
    if between:
        by_span["between_sessions"] = by_span.pop(NO_SPAN)
    out["between_sessions_s"] = between / 1e9
    if small_total:
        by_span["between_ops_under_20us"] = small_total
    out["idle_gaps"] = [
        [k, v / 1e9] for k, v in
        sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    ]
    return out


def log_marks(marks: Sequence[Tuple[float, int, int]],
              segment_log: Sequence[Tuple[int, int, float]]) -> List[Mark]:
    """The tracer's marks (t, segments, steps) with, in place of the
    totals, the steps and blocked seconds logged for the counted segments
    after the first mark and up to each: what a stall of the scheduler
    while the profiler started (seen on the chip: 47 ms in a fetch of the
    boundary before, credited after the anchor) cannot reach."""
    out = []
    for t, segments, _steps in marks:
        rows = [r for r in segment_log if marks[0][1] < r[0] <= segments]
        out.append((t, segments, sum(r[1] for r in rows),
                    sum(r[2] for r in rows) / 1e3))
    return out


def window_busy_s(blocked_s: float, trace: Optional[dict]) -> Optional[float]:
    """Seconds the device was busy over the whole measured window: the
    seconds the scheduler was blocked in the boundary intervals of the
    window's counted segments (its log) times the busy seconds per blocked
    second that the slice's whole boundary intervals show. None without a
    slice that held one."""
    if not trace or trace.get("busy_per_wait") is None or blocked_s <= 0:
        return None
    return blocked_s * trace["busy_per_wait"]


def session_s(occupancy: dict) -> float:
    """Seconds of the window inside the boundary intervals of drive
    sessions: the two halves of every one, as the scheduler counts them."""
    return (occupancy.get("host_ms", 0.0) + occupancy.get("device_ms", 0.0)) / 1e3
