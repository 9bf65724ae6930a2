"""From a profiler trace to numbers: busy, idle, cycles, top ops, gaps.

The reduction works on plain event lists, so it can be checked on a small
recorded trace (tests/benchmark/data). ``load_xplane`` is the thin part
that turns the profiler's ``.xplane.pb`` into those lists.

A *cycle* is one dispatch -> boundary round of the scheduler: on the
device it is one execution of the segment program (an event of the
``XLA Modules`` line whose name holds ``run_segment``) and whatever
follows it until the next one starts. The slice that idle time is taken
over runs from the start of the first segment program that lies wholly
inside the trace to the start of the last one, so it spans whole cycles
and nothing else. The profiler takes a second or two to start, so the
slice can run from the end of one drive session into the next: a gap that
none of the scheduler's host spans lies over is time between sessions,
and is taken out of the slice before the idle share inside sessions is
worked out.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

SEGMENT_MARK = "run_segment"
ANCHOR = "bench.anchor"
MIN_CYCLES = 3
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


def reduce_trace(ops: Sequence[Event], modules: Sequence[Event],
                 host_spans: Sequence[Tuple[str, float, float]] = (),
                 anchor_ns: Optional[int] = None,
                 anchor_mono_s: Optional[float] = None) -> dict:
    """host_spans: (name, start_s, dur_s) on the monotonic clock; the
    anchor pair maps that clock onto the trace's."""
    segs = sorted((m for m in modules if SEGMENT_MARK in m[0]),
                  key=lambda m: m[1])
    out = {"cycles": max(len(segs) - 1, 0), "segment_programs": len(segs),
           "busy_s": None, "window_s": None, "idle_share": None,
           "between_sessions_s": 0.0,
           "segment_device_s": sum(m[2] for m in segs) / 1e9,
           "device_ops": [], "idle_gaps": [], "whole_cycles": False}
    ops = [o for o in ops if not is_container(o[0])]
    if not ops:
        return out
    if len(segs) >= MIN_CYCLES + 1:
        lo, hi = segs[0][1], segs[-1][1]
        out["whole_cycles"] = True
    else:
        # too few cycles: busy over the traced span is still reported
        # for the device key, the idle share is not
        lo = min(o[1] for o in ops)
        hi = max(o[1] + o[2] for o in ops)
    import numpy as np

    starts = np.fromiter((o[1] for o in ops), np.int64, len(ops))
    ends = starts + np.fromiter((o[2] for o in ops), np.int64, len(ops))
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
    if anchor_ns is not None and anchor_mono_s is not None:
        shift = anchor_ns - int(anchor_mono_s * 1e9)
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
    if out["whole_cycles"] and hi - lo > between:
        out["idle_share"] = 100.0 * (idle - between) / (hi - lo - between)
    if small_total:
        by_span["between_ops_under_20us"] = small_total
    out["idle_gaps"] = [
        [k, v / 1e9] for k, v in
        sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
    ]
    return out


def window_busy_s(occupancy: dict, trace: Optional[dict]) -> Optional[float]:
    """Seconds the device was busy over the whole measured window. The
    trace is a slice of whole cycles inside one drive session; the window
    is sessions and the gaps between them, in which the device runs
    nothing. So: the slice's busy share times the window's in-session
    time, which the scheduler's counters give (host_ms + device_ms are
    the two halves of every dispatch->boundary cycle of the window)."""
    if not trace or trace.get("idle_share") is None:
        return None
    in_session_s = (occupancy.get("host_ms", 0.0)
                    + occupancy.get("device_ms", 0.0)) / 1e3
    if in_session_s <= 0:
        return None
    return in_session_s * (1.0 - trace["idle_share"] / 100.0)
