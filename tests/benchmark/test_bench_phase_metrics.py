"""The per-layer metrics that read the scheduler's phase, gap and submit
counters: a value where the program keeps the counters, nothing (never
0) where it does not — the parent commit, the stand-in engine — and a
timeline with the program's async `submit` pairs on it reduces like one
without them."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
import fake_engine  # noqa: E402

BENCH = json.load(open(ROOT / "BENCHMARK.json"))

# a 50 s window: sessions 26 s (host 13, device 13), 24 s between them
OCC = {
    "host_ms": 13_000.0, "device_ms": 13_000.0,
    "phase_refill_ms": 6_500.0, "phase_lanes_ms": 1_300.0,
    "phase_admit_ms": 650.0, "phase_reap_ms": 130.0, "phase_pv_ms": 520.0,
    "gap_ms": 24_000.0, "gap_submit_ms": 15_000.0, "gap_starved_ms": 4_000.0,
    "submit_ms": 16_500.0, "positions_submitted": 110,
}
EXPECTED = {
    "scheduler.refill_host_share": 25.0,
    "scheduler.lane_loop_host_share": 10.0,
    "scheduler.gap_submit_share": 30.0,
    "scheduler.gap_starved_share": 8.0,
    "scheduler.submit_ms_per_position": 150.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_counters(name):
    value = cells.load_reader(name)({"occupancy": dict(OCC), "window_s": 50.0})
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_where_the_program_keeps_no_such_counter(name):
    """The parent commit's occupancy_totals and the stand-in engine's."""
    read = cells.load_reader(name)
    parent = {"segments": 90, "steps": 36_000, "host_ms": 13_000.0,
              "device_ms": 13_000.0, "transfers": 200, "refills": 400}
    assert read({"occupancy": parent, "window_s": 50.0}) is None
    assert read({"occupancy": {}, "window_s": 50.0}) is None
    fake = dict(fake_engine.FakeAdapter(*fake_engine.cell_weights(ROOT)).counters())
    assert read({"occupancy": fake, "window_s": 50.0}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_is_declared_for_the_cell_it_reads(name):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    meta = json.load(open(ROOT / "benchmark/metrics" / f"{name}.json"))
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == meta[key], key
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "Engine / LaneScheduler boundary"
    assert "workloads" not in entry  # every cell reads it (PR 29)
    cell = cells.load_cell(ROOT, "standard.trickle")
    assert name in [m["name"] for m in cell["per_layer"]]


def test_window_without_submits_reads_no_time_per_position():
    occ = dict(OCC, positions_submitted=0, submit_ms=0.0)
    read = cells.load_reader("scheduler.submit_ms_per_position")
    assert read({"occupancy": occ, "window_s": 50.0}) is None


def _two_sessions():
    """Three cycles, a second with no session, two cycles (the recorded
    module's name, synthetic times), with the program's spans as it
    emits them now: `session` over each drive session, `segment` over
    each boundary interval, `phase.*` and `fetch` inside."""
    ms = 1_000_000
    mod = json.load(open(Path(__file__).resolve().parent
                         / "data/trace_v5e_small.json"))["module_name"]
    starts = [0, 100 * ms, 200 * ms, 300 * ms, 1400 * ms, 1500 * ms]
    mods = [(mod, t, 60 * ms) for t in starts]
    ops = [("%fusion.1 = s32[64,64]{1,0} fusion(%a)", t, 60 * ms) for t in starts]
    events = []

    def x(name, t0_ms, dur_ms, **args):
        events.append({"ph": "X", "name": name, "ts": t0_ms * 1000.0,
                       "dur": dur_ms * 1000.0, "pid": 1, "tid": 1, "args": args})

    x("session", -5, 405)
    x("session", 1390, 210)
    for t in starts:
        t_ms = t / ms
        x("segment", t_ms - 5, 100)
        x("phase.dispatch", t_ms - 5, 4, steps=400)
        x("fetch", t_ms, 60, label="summary")
        x("phase.lanes", t_ms + 60, 10)
        x("phase.refill", t_ms + 70, 25)
    return ops, mods, events


# the counters where the trace began (5 ms before the first program, as
# session one's span) and at each boundary, seen 5 ms after its program:
# 400 steps and 60 ms blocked each
MARKS = [(-0.005, 0, 0, 0.0)] + [
    (t / 1e3 + 0.065, k + 1, 400 * (k + 1), 0.06 * (k + 1))
    for k, t in enumerate([0, 100, 200, 300, 1400, 1500])]


def _reduce(ops, mods, events):
    return tr.reduce_trace(ops, mods, MARKS, _x_spans(events),
                           anchor_ns=0, anchor_mono_s=0.0)


def _x_spans(events):
    """What benchmark/measure.py::host_spans takes from the ring."""
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev["name"]
        if name == "fetch":
            name = "fetch:" + str((ev.get("args") or {}).get("label", ""))
        out.append((name, ev["ts"] / 1e6, ev["dur"] / 1e6))
    return out


def test_async_submit_pairs_leave_the_reduction_as_it_was():
    """The submit path runs between sessions. Its events are async
    pairs and instants, which the harness does not take for host spans:
    the time between two sessions stays time between two sessions."""
    ops, mods, events = _two_sessions()
    plain = _reduce(ops, mods, events)
    with_submits = list(events)
    for i, (t0, t1) in enumerate([(500, 900), (700, 1350)]):  # overlapping
        for name, a, b in (("submit", t0, t1), ("submit.replay", t0, t0 + 50),
                           ("submit.history", t0 + 50, t1)):
            for ph, t in (("b", a), ("e", b)):
                with_submits.append({"ph": ph, "name": name, "cat": "engine",
                                     "id": f"w{i}:0", "ts": t * 1000.0,
                                     "pid": 1, "tid": 2 + i})
    with_submits.append({"ph": "i", "name": "position.queued", "s": "t",
                         "ts": 880_000.0, "pid": 1, "tid": 2})
    got = _reduce(ops, mods, with_submits)
    assert got["between_sessions_s"] == plain["between_sessions_s"]
    assert got["busy_per_wait"] == plain["busy_per_wait"] == pytest.approx(1.0)
    assert got["idle_gaps"] == plain["idle_gaps"]
    # between the sessions: 400 ms .. 1390 ms of the timeline
    assert plain["between_sessions_s"] == pytest.approx(0.990)
    gaps = dict(map(tuple, plain["idle_gaps"]))
    assert "segment.device" not in gaps and "segment.host" not in gaps
    # gaps inside sessions carry the names of what the host was doing:
    # five whole gaps, each lanes 10 ms, refill 25, the next dispatch 4,
    # and 1 ms of the next segment before its program starts; before the
    # first program its dispatch and that 1 ms, after the last the 5 ms of
    # `lanes` until its boundary was seen
    assert gaps["phase.refill"] == pytest.approx(0.025 * 5)
    assert gaps["phase.lanes"] == pytest.approx(0.010 * 5 + 0.005)
    assert gaps["phase.dispatch"] == pytest.approx(0.004 * 6)
    assert gaps["segment"] == pytest.approx(0.001 * 6)
    # a session's set-up and tail: under its span and no narrower one
    assert gaps["session"] == pytest.approx(0.005 + 0.005)
    assert plain["intervals"] == 6 and plain["wait_s"] == pytest.approx(0.36)
    assert plain["busy_s"] == pytest.approx(0.36)


def test_an_x_span_over_a_submit_would_have_hidden_the_time_between_sessions():
    """Why the program may not use a thread span there: the same time
    under an `X` event is named as the host's work inside a session in the
    ledger's idle gaps."""
    ops, mods, events = _two_sessions()
    plain = _reduce(ops, mods, events)
    wrong = events + [{"ph": "X", "name": "submit", "ts": 500_000.0,
                       "dur": 850_000.0, "pid": 1, "tid": 2}]
    got = _reduce(ops, mods, wrong)
    assert got["between_sessions_s"] < plain["between_sessions_s"]
    assert dict(map(tuple, got["idle_gaps"]))["submit"] == pytest.approx(0.850)
    # what is carried over the window does not lean on the spans at all
    assert got["busy_per_wait"] == plain["busy_per_wait"]
