"""Summarize a trace dump (obs/trace.py Chrome trace-event JSON).

A flight-recorder dump (engine/supervisor.py writes one into
FISHNET_TPU_TRACE_DIR on child death, progress stall, or breaker trip)
or any TraceRecorder.dump() file holds the merged supervisor+host
timeline. This tool turns it into the two summaries the ROADMAP's
measurement items need without opening Perfetto:

- **per-phase time shares**: total duration per span name (warmup,
  search, supervisor.dispatch, queue.acquire, segment, ...) with the
  SyncStats device/host split (the `segment` spans' args) called out
  as a share of segment time — the profiling lever for the ~290 us/step
  fixed per-segment gap.
- **boundary phases**: the segment time by what the host was doing —
  the scheduler's `phase.*` spans (self time, fetches inside taken
  out), `wait` (the `fetch` spans) and `other` (what no phase covered,
  from the args): count, total, share of segment time; under it the
  refill phase by what it did — splices (boundaries that spliced),
  lanes a splice and ms a splice, from the `lanes` argument of the
  `phase.refill` spans (the engine's `refill_splices`, `refills` and
  `phase_refill_ms` are the same three over a window).
- **lane-steps**: the segments' lane-steps by the role each lane had at
  dispatch (the `lane_steps_*`, `steps_after_last_primary`,
  `segments_empty` and `segments_primary_stop` counts in the `segment`
  spans' args, engine/tpu.py LANE_STEP_COUNTERS): parked lane-steps (a
  primary or helper lane DONE while the segment ran on), the steps run
  after a segment's last primary parked, the dispatches that ran no
  step, and the segments that ended when their last primary parked.
- **sessions**: one row per `session` span — the width the drive
  session got, what was pending when it chose, set-up, segments, and
  its parked lane-steps, tail steps and empty dispatches.
- **boundary-gap histogram**: the distribution of gaps between
  consecutive `segment` spans on the host timeline — the fixed
  per-boundary cost itself, bucketed.

Cross-validation: every `segment` span carries its SyncStats snapshot
in args (device_ms/host_ms/phases), while the `fetch` and `phase.*`
spans inside it were emitted where the work ran, from the same clock
reads — so device_ms must equal the sum of the `fetch` spans and
host_ms the sum of the `phase.*` self times plus the args' `other`,
to well under 1%; `--selftest` (and tests/test_trace.py) assert that.

Request waterfalls: `--request <trace_id>` reconstructs one request's
causal chain from its span links — every span/instant whose args carry
the trace_id (directly or in a dispatch span's `trace_ids` list) plus
the `request` flow hops tying the processes together — and renders it
as a start-ordered waterfall. When the dump holds the serve edge's
`slo.observe` instant for that request, the reconstructed end-to-end
time is cross-checked within 1% against the latency the SLO histogram
actually recorded (same idiom as the SyncStats/segment check).

Usage:
  python tools/trace_report.py TRACE.json
  python tools/trace_report.py TRACE.json --format=github   # CI step
  python tools/trace_report.py TRACE.json --json
  python tools/trace_report.py TRACE.json --request aabbccdd11223344
  python tools/trace_report.py --compare BASE.json CAND.json

`--compare A B` diffs two dumps — per-phase time-share movement and
the boundary-gap distribution shift, with each dump's `buildInfo`
stamp (obs/perf.py) rendered so you know which build produced which
side.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional

# gap buckets in milliseconds (upper bounds; the last is open-ended)
GAP_BUCKETS_MS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 50.0, 250.0)
# the scheduler's lane-step counts among a `segment` span's `counts`
# (engine/tpu.py LANE_STEP_COUNTERS); the rest are the device's
LANE_STEPS = (
    "lane_steps_assigned", "lane_steps_primary_run", "lane_steps_helper_run",
    "lane_steps_stray_run", "lane_steps_free_run", "steps_after_last_primary",
    "segments_empty", "segments_primary_stop",
)


def _parked(counts: dict) -> Optional[int]:
    """Lane-steps a primary or helper lane sat DONE, where the counts
    have them."""
    if "lane_steps_assigned" not in counts:
        return None
    return (int(counts["lane_steps_assigned"])
            - int(counts.get("lane_steps_primary_run", 0))
            - int(counts.get("lane_steps_helper_run", 0)))


def load_events(path: str) -> List[dict]:
    """Load and minimally validate a Chrome trace-event file. Raises
    ValueError on anything Perfetto would reject outright."""
    with open(path, "r", encoding="utf-8") as fh:
        obj = json.load(fh)
    if isinstance(obj, list):
        events = obj  # bare-array form is also valid Chrome trace JSON
    elif isinstance(obj, dict) and isinstance(obj.get("traceEvents"), list):
        events = obj["traceEvents"]
    else:
        raise ValueError(f"{path}: not a Chrome trace-event file")
    out = []
    for ev in events:
        if not isinstance(ev, dict) or "ph" not in ev:
            raise ValueError(f"{path}: malformed trace event: {ev!r}")
        out.append(ev)
    return out


def _spans(events: List[dict], name: Optional[str] = None) -> List[dict]:
    return [
        e for e in events
        if e.get("ph") == "X" and (name is None or e.get("name") == name)
    ]


def summarize(events: List[dict]) -> dict:
    """The report dict: phase shares, segment split, boundary gaps."""
    spans = _spans(events)
    per_name: Dict[str, dict] = defaultdict(
        lambda: {"count": 0, "total_ms": 0.0}
    )
    for e in spans:
        row = per_name[str(e.get("name"))]
        row["count"] += 1
        row["total_ms"] += float(e.get("dur", 0.0)) / 1000.0

    # SyncStats cross-validation: the snapshot each `segment` span
    # carries in its args vs the spans emitted inside the interval
    seg = _spans(events, "segment")
    seg_args = [e.get("args") or {} for e in seg]
    args_device = sum(float(a.get("device_ms", 0.0)) for a in seg_args)
    args_host = sum(float(a.get("host_ms", 0.0)) for a in seg_args)
    args_other = sum(
        float((a.get("phases") or {}).get("other", 0.0)) for a in seg_args
    )
    # what the device counted in the segments' loops (ops/search.py
    # SEGMENT_COUNTERS: movegen_*, acc_*, eval_*) and the lane-steps by
    # role (LANE_STEPS), where the trace has them
    seg_counts: Dict[str, int] = defaultdict(int)
    for a in seg_args:
        for name, n in (a.get("counts") or {}).items():
            seg_counts[name] += int(n)
    lane_steps = {k: seg_counts.pop(k) for k in LANE_STEPS if k in seg_counts}
    if lane_steps:
        lane_steps["parked"] = _parked(lane_steps)
    boundary: Dict[str, dict] = defaultdict(
        lambda: {"count": 0, "total_ms": 0.0}
    )
    # the refill phase by what it spliced: `phase.refill` carries the
    # lanes staged at its boundary (0: nothing to splice), which sum to
    # the engine's `refills` over its `refill_splices`
    splices = lanes_spliced = 0
    for e in spans:
        name = str(e.get("name"))
        if name.startswith("phase."):
            # self time: a phase that fetched says so in its args
            args = e.get("args") or {}
            self_ms = args.get(
                "self_ms", float(e.get("dur", 0.0)) / 1000.0)
            row = boundary[name[len("phase."):]]
            row["count"] += 1
            row["total_ms"] += float(self_ms)
            if name == "phase.refill" and args.get("lanes"):
                splices += 1
                lanes_spliced += int(args["lanes"])
    span_host = sum(row["total_ms"] for row in boundary.values())
    span_device = per_name.get("fetch", {}).get("total_ms", 0.0)
    if span_device:
        boundary["wait"] = {"count": per_name["fetch"]["count"],
                            "total_ms": span_device}
    if args_other:
        boundary["other"] = {"count": len(seg), "total_ms": args_other}

    t_first = min((float(e.get("ts", 0.0)) for e in spans), default=0.0)
    sessions = [
        dict(e.get("args") or {},
             start_ms=round((float(e.get("ts", 0.0)) - t_first) / 1000.0, 3),
             dur_ms=round(float(e.get("dur", 0.0)) / 1000.0, 3),
             parked=_parked(e.get("args") or {}))
        for e in sorted(_spans(events, "session"),
                        key=lambda e: float(e.get("ts", 0.0)))
    ]

    # boundary gaps: start-to-start minus duration of consecutive
    # segment spans per (pid, tid) track, i.e. time between the end of
    # one boundary window and the start of the next
    gaps_ms: List[float] = []
    by_track: Dict[tuple, List[dict]] = defaultdict(list)
    for e in seg:
        by_track[(e.get("pid"), e.get("tid"))].append(e)
    for track in by_track.values():
        track.sort(key=lambda e: float(e.get("ts", 0.0)))
        for prev, cur in zip(track, track[1:]):
            gap = (
                float(cur.get("ts", 0.0))
                - float(prev.get("ts", 0.0))
                - float(prev.get("dur", 0.0))
            ) / 1000.0
            if gap >= 0.0:
                gaps_ms.append(gap)
    hist = [0] * (len(GAP_BUCKETS_MS) + 1)
    for g in gaps_ms:
        for i, ub in enumerate(GAP_BUCKETS_MS):
            if g <= ub:
                hist[i] += 1
                break
        else:
            hist[-1] += 1

    total_ms = sum(row["total_ms"] for row in per_name.values())
    seg_total = args_device + args_host
    return {
        "events": len(events),
        "spans": len(spans),
        "phases": {
            name: {
                "count": row["count"],
                "total_ms": round(row["total_ms"], 3),
                "share": round(row["total_ms"] / total_ms, 4)
                if total_ms > 0 else 0.0,
            }
            for name, row in sorted(
                per_name.items(), key=lambda kv: -kv[1]["total_ms"]
            )
        },
        "segments": {
            "count": len(seg),
            "device_ms": round(args_device, 3),
            "host_ms": round(args_host, 3),
            "device_share": round(args_device / seg_total, 4)
            if seg_total > 0 else 0.0,
            "host_share": round(args_host / seg_total, 4)
            if seg_total > 0 else 0.0,
            # what the spans inside the intervals add up to, for
            # cross-validation: fetches; phase self times + other
            "span_device_ms": round(span_device, 3),
            "span_host_ms": round(span_host + args_other, 3),
            "counts": dict(seg_counts),
            "lane_steps": lane_steps,
        },
        "boundary_phases": {
            name: {
                "count": row["count"],
                "total_ms": round(row["total_ms"], 3),
                "share": round(row["total_ms"] / seg_total, 4)
                if seg_total > 0 else 0.0,
            }
            for name, row in sorted(
                boundary.items(), key=lambda kv: -kv[1]["total_ms"]
            )
        },
        "refill": {
            "splices": splices,
            "lanes": lanes_spliced,
            "lanes_per_splice": round(lanes_spliced / splices, 2)
            if splices else 0.0,
            "ms_per_splice": round(
                boundary["refill"]["total_ms"] / splices, 3)
            if splices else 0.0,
        },
        "sessions": sessions,
        "boundary_gaps": {
            "count": len(gaps_ms),
            "buckets_ms": list(GAP_BUCKETS_MS),
            "histogram": hist,
            "mean_ms": round(sum(gaps_ms) / len(gaps_ms), 3)
            if gaps_ms else 0.0,
            "max_ms": round(max(gaps_ms), 3) if gaps_ms else 0.0,
        },
    }


def load_build_info(path: str) -> dict:
    """The `buildInfo` stamp obs/trace.py export() writes at the dump's
    top level (absent on bare-array dumps and pre-stamp files)."""
    try:
        with open(path, "r", encoding="utf-8") as fh:
            obj = json.load(fh)
    except (OSError, ValueError, json.JSONDecodeError):
        return {}
    if isinstance(obj, dict) and isinstance(obj.get("buildInfo"), dict):
        return obj["buildInfo"]
    return {}


def compare(a: dict, b: dict) -> dict:
    """Diff two summarize() reports: per-phase time-share movement and
    the boundary-gap distribution shift. `a` is the baseline, `b` the
    candidate; deltas are b - a (so positive share_delta = that phase
    grew). The phase table covers the union of names, with phases
    present on only one side carried at zero on the other — a phase
    appearing or vanishing is itself signal (e.g. a warmup span that
    stopped amortizing)."""
    names = list(
        dict.fromkeys(list(a["phases"].keys()) + list(b["phases"].keys()))
    )
    zero = {"count": 0, "total_ms": 0.0, "share": 0.0}
    phases = {}
    for name in names:
        ra, rb = a["phases"].get(name, zero), b["phases"].get(name, zero)
        phases[name] = {
            "a_total_ms": ra["total_ms"],
            "b_total_ms": rb["total_ms"],
            "a_share": ra["share"],
            "b_share": rb["share"],
            "share_delta": round(rb["share"] - ra["share"], 4),
            "ratio": round(rb["total_ms"] / ra["total_ms"], 3)
            if ra["total_ms"] > 0 else None,
        }
    phases = dict(sorted(
        phases.items(), key=lambda kv: -abs(kv[1]["share_delta"])
    ))
    ga, gb = a["boundary_gaps"], b["boundary_gaps"]
    sa, sb = a["segments"], b["segments"]
    return {
        "phases": phases,
        "segments": {
            "a_count": sa["count"], "b_count": sb["count"],
            "device_share_delta": round(
                sb["device_share"] - sa["device_share"], 4),
            "host_share_delta": round(
                sb["host_share"] - sa["host_share"], 4),
        },
        "boundary_gaps": {
            "a_count": ga["count"], "b_count": gb["count"],
            "a_mean_ms": ga.get("mean_ms", 0.0),
            "b_mean_ms": gb.get("mean_ms", 0.0),
            "mean_delta_ms": round(
                gb.get("mean_ms", 0.0) - ga.get("mean_ms", 0.0), 3),
            "a_max_ms": ga["max_ms"], "b_max_ms": gb["max_ms"],
            "max_delta_ms": round(gb["max_ms"] - ga["max_ms"], 3),
            "buckets_ms": ga["buckets_ms"],
            "a_histogram": ga["histogram"],
            "b_histogram": gb["histogram"],
        },
    }


def render_compare(cmp: dict, label_a: str, label_b: str) -> str:
    lines = [
        f"compare: A={label_a}  B={label_b}  (deltas are B - A)",
        "",
        f"{'phase':<24} {'A share':>8} {'B share':>8} {'delta':>8} "
        f"{'B/A ms':>7}",
    ]
    for name, row in cmp["phases"].items():
        ratio = f"{row['ratio']:>7.2f}" if row["ratio"] is not None \
            else f"{'new':>7}"
        lines.append(
            f"{name:<24} {row['a_share']:>8.1%} {row['b_share']:>8.1%} "
            f"{row['share_delta']:>+8.1%} {ratio}"
        )
    seg = cmp["segments"]
    lines += [
        "",
        f"segments: {seg['a_count']} -> {seg['b_count']}  "
        f"device share {seg['device_share_delta']:+.1%}  "
        f"host share {seg['host_share_delta']:+.1%}",
    ]
    gaps = cmp["boundary_gaps"]
    lines += [
        "",
        f"boundary gaps: {gaps['a_count']} -> {gaps['b_count']}  "
        f"mean {gaps['a_mean_ms']:.3f} -> {gaps['b_mean_ms']:.3f}ms "
        f"({gaps['mean_delta_ms']:+.3f})  "
        f"max {gaps['a_max_ms']:.3f} -> {gaps['b_max_ms']:.3f}ms "
        f"({gaps['max_delta_ms']:+.3f})",
    ]
    edges = ["0"] + [str(b) for b in gaps["buckets_ms"]]
    for i, (na, nb) in enumerate(
            zip(gaps["a_histogram"], gaps["b_histogram"])):
        hi = edges[i + 1] if i < len(gaps["buckets_ms"]) else "inf"
        lines.append(f"  ({edges[i] if i else '0'}, {hi}]: {na} -> {nb}")
    return "\n".join(lines)


def request_events(events: List[dict], trace_id: str) -> List[dict]:
    """Every event on one request's causal chain: spans/instants whose
    args carry the trace_id (their own or in a dispatch span's
    `trace_ids` list) and the `request` flow hops with that id."""
    out = []
    for e in events:
        args = e.get("args") or {}
        if args.get("trace_id") == trace_id:
            out.append(e)
            continue
        tids = args.get("trace_ids")
        if isinstance(tids, list) and trace_id in tids:
            out.append(e)
            continue
        if e.get("ph") in ("s", "t", "f") and str(e.get("id")) == trace_id:
            out.append(e)
    return out


def request_waterfall(events: List[dict], trace_id: str) -> Optional[dict]:
    """One request's start-ordered waterfall, or None if the dump holds
    nothing for that id."""
    evs = request_events(events, trace_id)
    if not evs:
        return None
    spans = [e for e in evs if e.get("ph") == "X"]
    instants = [e for e in evs if e.get("ph") == "i"]
    flows = [e for e in evs if e.get("ph") in ("s", "t", "f")]
    t0 = min(float(e.get("ts", 0.0)) for e in evs)
    rows = []
    for e in sorted(spans + instants,
                    key=lambda e: float(e.get("ts", 0.0))):
        rows.append({
            "name": str(e.get("name")),
            "pid": e.get("pid"),
            "start_ms": round((float(e.get("ts", 0.0)) - t0) / 1000.0, 3),
            "dur_ms": round(float(e.get("dur", 0.0)) / 1000.0, 3)
            if e.get("ph") == "X" else None,
            "args": {
                k: v for k, v in (e.get("args") or {}).items()
                if k not in ("trace_id", "trace_ids")
            },
        })
    http = [e for e in spans if e.get("name") == "http.request"]
    slo = [e for e in instants if e.get("name") == "slo.observe"]
    http_ms = (
        max(float(e.get("dur", 0.0)) for e in http) / 1000.0
        if http else None
    )
    slo_ms = (
        float((slo[0].get("args") or {}).get("total_ms", 0.0))
        if slo else None
    )
    last = max(
        float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
        for e in spans + instants
    )
    return {
        "request": trace_id,
        "events": len(evs),
        "flow_hops": len(flows),
        "processes": sorted({e.get("pid") for e in evs}),
        "span_total_ms": round((last - t0) / 1000.0, 3),
        "http_ms": round(http_ms, 3) if http_ms is not None else None,
        "slo_total_ms": round(slo_ms, 3) if slo_ms is not None else None,
        "rows": rows,
    }


def request_crosscheck(wf: dict, tolerance: float = 0.01) -> List[str]:
    """The <=1% agreement contract between the reconstructed waterfall
    and the SLO histogram observation the serve edge recorded for this
    request. Silently passes when the dump lacks either side (a
    client-chunk trace has no serve edge)."""
    http_ms, slo_ms = wf.get("http_ms"), wf.get("slo_total_ms")
    if http_ms is None or slo_ms is None:
        return []
    ref = max(abs(slo_ms), 1e-9)
    if abs(http_ms - slo_ms) / ref > tolerance:
        return [
            f"request {wf['request']}: http.request span is "
            f"{http_ms:.3f}ms but the SLO histogram observed "
            f"{slo_ms:.3f}ms (>{tolerance:.0%} apart)"
        ]
    return []


def render_waterfall(wf: dict) -> str:
    procs = ", ".join(str(p) for p in wf["processes"])
    lines = [
        f"request {wf['request']}: {wf['events']} events across "
        f"{len(wf['processes'])} process(es) [{procs}], "
        f"{wf['flow_hops']} flow hops, "
        f"{wf['span_total_ms']:.3f}ms end to end",
        "",
        f"{'start_ms':>10} {'dur_ms':>10}  {'pid':>7}  name",
    ]
    for row in wf["rows"]:
        dur = f"{row['dur_ms']:>10.3f}" if row["dur_ms"] is not None \
            else f"{'·':>10}"
        lines.append(
            f"{row['start_ms']:>10.3f} {dur}  {row['pid']!s:>7}  "
            f"{row['name']}"
        )
    if wf["slo_total_ms"] is not None:
        lines += [
            "",
            f"slo observation: {wf['slo_total_ms']:.3f}ms total "
            f"(http span {wf['http_ms']:.3f}ms)"
            if wf["http_ms"] is not None else
            f"slo observation: {wf['slo_total_ms']:.3f}ms total",
        ]
    return "\n".join(lines)


def crosscheck(report: dict, tolerance: float = 0.01) -> List[str]:
    """The <=1% agreement contract between the SyncStats snapshots in
    the `segment` spans' args and the spans emitted inside them: the
    `fetch` spans against device_ms, the `phase.*` self times (plus
    the args' `other`) against host_ms. Returns human-readable
    violations."""
    seg = report["segments"]
    out = []
    for key, what in (("device", "fetch"), ("host", "phase.* + other")):
        spans_ms = seg[f"span_{key}_ms"]
        args_ms = seg[f"{key}_ms"]
        ref = max(abs(args_ms), 1e-9)
        if abs(spans_ms - args_ms) / ref > tolerance:
            out.append(
                f"{what} spans sum to {spans_ms:.3f}ms but the segments' "
                f"SyncStats args carry {key}_ms {args_ms:.3f}ms "
                f"(>{tolerance:.0%} apart)"
            )
    return out


def render_text(report: dict) -> str:
    lines = [
        f"trace: {report['events']} events, {report['spans']} spans",
        "",
        f"{'phase':<24} {'count':>7} {'total_ms':>12} {'share':>7}",
    ]
    for name, row in report["phases"].items():
        lines.append(
            f"{name:<24} {row['count']:>7} {row['total_ms']:>12.3f} "
            f"{row['share']:>6.1%}"
        )
    seg = report["segments"]
    if seg["count"]:
        lines += [
            "",
            f"segments: {seg['count']}  device {seg['device_ms']:.3f}ms "
            f"({seg['device_share']:.1%})  host {seg['host_ms']:.3f}ms "
            f"({seg['host_share']:.1%})",
        ]
        if seg.get("counts"):
            lines.append("  counted on the device: " + "  ".join(
                f"{name} {n}" for name, n in seg["counts"].items()))
        ls = seg.get("lane_steps")
        if ls:
            assigned = ls["lane_steps_assigned"]
            lines.append(
                f"  lane-steps: assigned {assigned}, run by primary "
                f"{ls.get('lane_steps_primary_run', 0)} / helper "
                f"{ls.get('lane_steps_helper_run', 0)} / stray "
                f"{ls.get('lane_steps_stray_run', 0)} / free "
                f"{ls.get('lane_steps_free_run', 0)}; parked {ls['parked']} "
                f"({ls['parked'] / assigned if assigned else 0.0:.1%} of "
                f"assigned); tail steps "
                f"{ls.get('steps_after_last_primary', 0)}; empty dispatches "
                f"{ls.get('segments_empty', 0)}; stopped at the last "
                f"primary {ls.get('segments_primary_stop', 0)}")
    if report["boundary_phases"]:
        lines += [
            "",
            f"{'boundary phase':<24} {'count':>7} {'total_ms':>12} "
            f"{'of seg':>7}",
        ]
        for name, row in report["boundary_phases"].items():
            lines.append(
                f"{name:<24} {row['count']:>7} {row['total_ms']:>12.3f} "
                f"{row['share']:>6.1%}"
            )
    refill = report["refill"]
    if refill["splices"]:
        lines.append(
            f"refill: {refill['splices']} splices of "
            f"{refill['lanes_per_splice']:.1f} lanes, "
            f"{refill['ms_per_splice']:.3f}ms a splice")
    if report["sessions"]:
        lines += [
            "",
            f"{'session at ms':>14} {'dur_ms':>10} {'width':>6} "
            f"{'pending':>8} {'setup_ms':>9} {'segments':>9} "
            f"{'positions':>10} {'parked':>9} {'tail':>7} {'empty':>6}",
        ]
        for row in report["sessions"]:
            parked = row.get("parked")
            lines.append(
                f"{row['start_ms']:>14.3f} {row['dur_ms']:>10.3f} "
                f"{row.get('width', 0):>6} {row.get('pending', 0):>8} "
                f"{row.get('setup_ms', 0.0):>9.3f} "
                f"{row.get('segments', 0):>9} "
                f"{row.get('positions', 0):>10} "
                f"{'-' if parked is None else parked:>9} "
                f"{row.get('steps_after_last_primary', '-'):>7} "
                f"{row.get('segments_empty', '-'):>6}"
            )
    gaps = report["boundary_gaps"]
    if gaps["count"]:
        lines += ["", "boundary gaps (ms):"]
        edges = ["0"] + [str(b) for b in gaps["buckets_ms"]]
        for i, n in enumerate(gaps["histogram"]):
            hi = edges[i + 1] if i < len(gaps["buckets_ms"]) else "inf"
            lines.append(f"  ({edges[i] if i else '0'}, {hi}]: {n}")
        lines.append(f"  max: {gaps['max_ms']:.3f}ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="trace-report")
    parser.add_argument("trace", nargs="?", default=None,
                        help="Chrome trace-event JSON file")
    parser.add_argument("--json", action="store_true",
                        help="emit the report as JSON")
    parser.add_argument(
        "--compare", nargs=2, metavar=("A.json", "B.json"), default=None,
        help="diff two dumps (A = baseline, B = candidate): per-phase "
             "time-share movement and the boundary-gap shift",
    )
    parser.add_argument(
        "--format", choices=["text", "github"], default="text",
        help="github: workflow annotations + step summary lines",
    )
    parser.add_argument(
        "--selftest", action="store_true",
        help="fail unless SyncStats args and segment child spans agree "
             "within 1%% (the dump's internal cross-validation)",
    )
    parser.add_argument(
        "--request", metavar="TRACE_ID", default=None,
        help="render one request's waterfall from its span links and "
             "cross-check it within 1%% against the serve latency "
             "histogram observation for that request",
    )
    args = parser.parse_args(argv)

    if args.compare is not None:
        path_a, path_b = args.compare
        reports = []
        for path in (path_a, path_b):
            try:
                reports.append(summarize(load_events(path)))
            except (OSError, ValueError, json.JSONDecodeError) as e:
                msg = f"unreadable trace {path}: {e}"
                if args.format == "github":
                    print(f"::error title=trace-report::{msg}")
                else:
                    print(f"trace-report: {msg}", file=sys.stderr)
                return 2
        cmp = compare(reports[0], reports[1])
        for label, path in (("A", path_a), ("B", path_b)):
            info = load_build_info(path)
            if info:
                cmp.setdefault("build_info", {})[label] = info
        if args.json:
            print(json.dumps(cmp, indent=2))
            return 0
        if args.format == "github":
            gaps = cmp["boundary_gaps"]
            print(
                f"::notice title=trace-report compare::"
                f"{path_a} vs {path_b}: boundary gap mean "
                f"{gaps['a_mean_ms']:.3f} -> {gaps['b_mean_ms']:.3f}ms "
                f"({gaps['mean_delta_ms']:+.3f})"
            )
        print(render_compare(cmp, path_a, path_b))
        for label in ("A", "B"):
            info = cmp.get("build_info", {}).get(label)
            if info:
                fields = " ".join(
                    f"{k}={info[k]}" for k in sorted(info))
                print(f"build {label}: {fields}")
        return 0

    if args.trace is None:
        parser.error("trace file required (or use --compare A B)")

    try:
        events = load_events(args.trace)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        msg = f"unreadable trace {args.trace}: {e}"
        if args.format == "github":
            print(f"::error title=trace-report::{msg}")
        else:
            print(f"trace-report: {msg}", file=sys.stderr)
        return 2

    if args.request is not None:
        wf = request_waterfall(events, args.request)
        if wf is None:
            msg = f"no events for request {args.request} in {args.trace}"
            if args.format == "github":
                print(f"::error title=trace-report::{msg}")
            else:
                print(f"trace-report: {msg}", file=sys.stderr)
            return 2
        violations = request_crosscheck(wf)
        if args.json:
            print(json.dumps(wf, indent=2))
        else:
            if args.format == "github":
                print(
                    f"::notice title=trace-report request::"
                    f"{wf['request']}: {wf['events']} events, "
                    f"{len(wf['processes'])} processes, "
                    f"{wf['span_total_ms']:.3f}ms end to end"
                )
            print(render_waterfall(wf))
        for msg in violations:
            if args.format == "github":
                print(f"::error title=trace-report crosscheck::{msg}")
            else:
                print(f"trace-report: CROSSCHECK FAILED: {msg}",
                      file=sys.stderr)
        return 1 if violations else 0

    report = summarize(events)
    violations = crosscheck(report) if args.selftest else []

    if args.json:
        print(json.dumps(report, indent=2))
    elif args.format == "github":
        seg = report["segments"]
        print(
            f"::notice title=trace-report::{args.trace}: "
            f"{report['events']} events, {report['spans']} spans, "
            f"{seg['count']} segments "
            f"(device {seg['device_share']:.1%} / "
            f"host {seg['host_share']:.1%})"
        )
        print(render_text(report))
    else:
        print(render_text(report))

    for msg in violations:
        if args.format == "github":
            print(f"::error title=trace-report crosscheck::{msg}")
        else:
            print(f"trace-report: CROSSCHECK FAILED: {msg}", file=sys.stderr)
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
