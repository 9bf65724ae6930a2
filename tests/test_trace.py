"""Trace-core tests: ring recorder, clock sync, cross-process merge,
and the SyncStats <-> trace_report cross-validation contract.

Everything here runs without JAX — obs/trace.py is pure stdlib and the
cross-process tests drive the supervisor against the scriptable fake
host (fishnet_tpu/engine/fakehost.py), including its --trace-skew
clock-sync fault injection.
"""
import asyncio
import json
import sys
import time

import pytest

from fishnet_tpu.client.ipc import Chunk, WorkPosition
from fishnet_tpu.client.logger import Logger
from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
from fishnet_tpu.engine.base import EngineError
from fishnet_tpu.engine.supervisor import SupervisedEngine
from fishnet_tpu.obs import trace
from fishnet_tpu.utils.syncstats import SyncStats
from tools import trace_report

START = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"


@pytest.fixture(autouse=True)
def _clean_recorder():
    """Tracing state is a module global; never leak it across tests."""
    trace.uninstall()
    yield
    trace.uninstall()


# ------------------------------------------------------------- recorder


def test_ring_eviction_keeps_newest():
    rec = trace.TraceRecorder(capacity=32, process_name="t")
    for i in range(100):
        rec.instant(f"ev{i}")
    evs = rec.snapshot()
    assert len(evs) == 32
    # the ring holds the *last* window: oldest events fell off the back
    assert [e["name"] for e in evs] == [f"ev{i}" for i in range(68, 100)]
    assert rec.emitted == 100


def test_capacity_floor():
    rec = trace.TraceRecorder(capacity=1)
    assert rec.capacity == 16


def test_span_nesting_and_exception_safety():
    rec = trace.install(trace.TraceRecorder(capacity=256,
                                            process_name="t"))
    with rec.span("outer", "test", k=1):
        with rec.span("inner", "test"):
            pass
        with pytest.raises(ValueError):
            with rec.span("failing", "test"):
                raise ValueError("boom")
    evs = rec.snapshot()
    by_name = {e["name"]: e for e in evs}
    # inner closes before outer (emitted on exit), and the failing span
    # still landed — annotated, with the exception propagated above
    assert [e["name"] for e in evs] == ["inner", "failing", "outer"]
    assert by_name["failing"]["args"]["error"] == "ValueError"
    assert by_name["outer"]["args"] == {"k": 1}
    # nesting is consistent: outer's window contains inner's
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_tracing_off_is_free():
    assert trace.RECORDER is None
    # the module helpers are no-ops returning the SHARED null span — no
    # per-call allocation on the hot path
    s1 = trace.span("anything", "x", a=1)
    s2 = trace.span("else")
    assert s1 is s2 is trace.NULL_SPAN
    with s1:
        pass
    trace.instant("nothing")
    trace.counter("nothing", 1.0)


def test_drain_and_absorb_with_offset():
    child = trace.TraceRecorder(capacity=64, pid=4242)
    child.complete("work", ts_us=1000.0, dur_us=500.0)
    parent = trace.TraceRecorder(capacity=64, pid=1)
    batch = child.drain()
    assert len(batch) == 1
    assert child.snapshot() == []  # drain empties the ring exactly once
    n = parent.absorb(batch, offset_us=1e6)
    assert n == 1
    ev = parent.snapshot()[0]
    assert ev["ts"] == pytest.approx(1000.0 + 1e6)
    assert ev["pid"] == 4242  # provenance survives the merge
    # malformed foreign events are skipped, not crashed on
    assert parent.absorb([{"no": "ph"}, "junk", None]) == 0


def test_dump_is_valid_chrome_trace(tmp_path):
    rec = trace.TraceRecorder(capacity=64, process_name="proc-a")
    rec.set_thread_name("main")
    with rec.span("phase", "test", detail="x"):
        time.sleep(0.001)
    rec.instant("marker", "test")
    rec.counter("depth", 3)
    path = rec.dump(str(tmp_path / "trace.json"))
    obj = json.loads((tmp_path / "trace.json").read_text())
    assert path == str(tmp_path / "trace.json")
    assert obj["displayTimeUnit"] == "ms"
    evs = obj["traceEvents"]
    assert all("ph" in e for e in evs)
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
    assert any(e["args"]["name"] == "proc-a" for e in meta)
    data = [e for e in evs if e["ph"] != "M"]
    assert {e["ph"] for e in data} == {"X", "i", "C"}
    # non-meta events are time-sorted for viewers that care
    ts = [e["ts"] for e in data]
    assert ts == sorted(ts)
    # and trace_report loads it as-is
    assert len(trace_report.load_events(str(path))) == len(evs)


def test_flight_dump_names_do_not_collide(tmp_path):
    rec = trace.TraceRecorder(capacity=64)
    rec.instant("x")
    p1 = rec.flight_dump(str(tmp_path), "child death!")
    p2 = rec.flight_dump(str(tmp_path), "child death!")
    assert p1 != p2
    assert "child-death-" in p1  # reason sanitized into the filename
    for p in (p1, p2):
        json.loads(open(p).read())


def test_clock_sync_takes_minimum():
    cs = trace.ClockSync()
    assert cs.sample(10.0, 12.0) == pytest.approx(2e6)
    # a slower round-trip must not loosen the estimate
    assert cs.sample(20.0, 23.0) == pytest.approx(2e6)
    # a tighter one improves it
    assert cs.sample(30.0, 31.5) == pytest.approx(1.5e6)
    assert cs.samples == 3


# ------------------------------------- SyncStats cross-validation (1%)


def test_syncstats_segments_crosscheck_within_1pct():
    """The acceptance contract: the device/host split each `segment`
    span carries in its args agrees, within trace_report's 1%
    tolerance, with the spans emitted inside the interval where the
    work ran — device_ms with the `fetch` spans, host_ms with the
    `phase.*` self times plus what no phase covered."""
    rec = trace.install(trace.TraceRecorder(capacity=4096,
                                            process_name="t"))
    stats = SyncStats()
    import numpy as np

    class OnDevice:
        """Takes a millisecond to reach the host, like a device value
        (a plain numpy fetch is a microsecond: all rounding)."""

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.001)
            return np.arange(100)

    for _ in range(5):
        with stats.phase("refill"):
            time.sleep(0.002)
        for _ in range(2):
            stats.fetch(OnDevice(), label="test")
        with stats.phase("pv", rows=3):
            time.sleep(0.001)
            stats.fetch(OnDevice(), label="pv")  # pauses the phase
        time.sleep(0.001)  # under no phase: "other"
        snap = stats.boundary()
        assert snap["transfers"] == 3
        ph = snap["phases"]
        assert ph["wait"] == snap["device_ms"]
        assert ph["refill"] >= 2.0 and ph["pv"] >= 1.0 and ph["other"] >= 1.0
        assert sum(ph.values()) == pytest.approx(
            snap["host_ms"] + snap["device_ms"], rel=0.01)
    events = rec.export()["traceEvents"]
    names = {e["name"] for e in events}
    assert "segment.device" not in names and "segment.host" not in names
    report = trace_report.summarize(events)
    assert report["segments"]["count"] == 5
    assert trace_report.crosscheck(report, tolerance=0.01) == []
    # fetch spans are on the timeline too
    assert report["phases"]["fetch"]["count"] == 15
    table = report["boundary_phases"]
    assert table["refill"]["count"] == 5 and table["wait"]["count"] == 15
    assert sum(r["share"] for r in table.values()) == pytest.approx(
        1.0, abs=0.01)
    # the pv span covers its fetch but claims only its own time
    pv = [e for e in events if e["name"] == "phase.pv"]
    assert all(e["args"]["self_ms"] * 1000.0 < e["dur"] for e in pv)
    assert all(e["args"]["rows"] == 3 for e in pv)
    # segment windows are contiguous by construction (boundary() reuses
    # one clock reading to close a window and open the next), so any
    # gaps that survive float rounding are negligible
    assert report["boundary_gaps"]["max_ms"] < 1.0


def test_boundary_gap_histogram_buckets():
    rec = trace.TraceRecorder(capacity=256)
    # four segments on one track with known start-to-start gaps:
    # 200us, 3ms, 100ms after the preceding segment's 1ms window
    starts_us = [0.0, 1200.0, 5200.0, 106200.0]
    for ts in starts_us:
        rec.complete("segment", ts, 1000.0, cat="sync", tid=7)
    report = trace_report.summarize(rec.snapshot())
    gaps = report["boundary_gaps"]
    assert gaps["count"] == 3
    assert gaps["max_ms"] == pytest.approx(100.0)
    by_bucket = dict(zip(
        [*gaps["buckets_ms"], "inf"], gaps["histogram"]))
    assert by_bucket[0.25] == 1   # 0.2ms gap
    assert by_bucket[5.0] == 1    # 3ms gap
    assert by_bucket[250.0] == 1  # 100ms gap


def test_trace_report_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"notATrace": true}')
    with pytest.raises(ValueError):
        trace_report.load_events(str(bad))
    assert trace_report.main([str(bad)]) == 2


def _gap_dump(path, n_seg, seg_us, gap_us, extra=None):
    evs = []
    ts = 0.0
    for _ in range(n_seg):
        evs.append({"ph": "X", "name": "segment", "ts": ts, "dur": seg_us,
                    "pid": 1, "tid": 1, "args": {}})
        ts += seg_us + gap_us
    if extra:
        evs.append(extra)
    doc = {"traceEvents": evs,
           "buildInfo": {"git_sha": "abc", "backend": "cpu"}}
    path.write_text(json.dumps(doc), encoding="utf-8")


def test_trace_report_compare(tmp_path, capsys):
    """--compare A B: the boundary-gap shift and per-phase share
    movement between two dumps, each labeled with its buildInfo."""
    a, b = tmp_path / "A.json", tmp_path / "B.json"
    _gap_dump(a, 4, 1000.0, 200.0)
    # candidate: 3x the boundary gap plus a phase A never had
    _gap_dump(b, 4, 1000.0, 600.0,
              extra={"ph": "X", "name": "warmup", "ts": 0.0,
                     "dur": 2000.0, "pid": 1, "tid": 2})
    ra = trace_report.summarize(trace_report.load_events(str(a)))
    rb = trace_report.summarize(trace_report.load_events(str(b)))
    cmp = trace_report.compare(ra, rb)
    gaps = cmp["boundary_gaps"]
    assert gaps["a_mean_ms"] == pytest.approx(0.2)
    assert gaps["b_mean_ms"] == pytest.approx(0.6)
    assert gaps["mean_delta_ms"] == pytest.approx(0.4)
    assert cmp["phases"]["warmup"]["ratio"] is None  # new phase
    assert cmp["phases"]["segment"]["share_delta"] < 0  # diluted

    assert trace_report.main(["--compare", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "boundary gaps" in out
    assert "git_sha=abc" in out  # both sides' build stamps render


# ------------------------------------------- cross-process (fake host)


def fake_cmd(script, extra=(), hb_interval=0.05):
    return [
        sys.executable, "-m", "fishnet_tpu.engine.fakehost",
        "--script",
        script if isinstance(script, str) else json.dumps(script),
        "--hb-interval", str(hb_interval),
        *extra,
    ]


def make_chunk(ttl=30.0, n_positions=2, depth=1):
    work = AnalysisWork(
        id="trjob001",
        nodes=NodeLimit(sf16=4_000_000, classical=8_000_000),
        timeout_s=30.0,
        depth=depth,
        multipv=None,
    )
    positions = [
        WorkPosition(work=work, position_index=i, url=None, skip=False,
                     root_fen=START, moves=[])
        for i in range(n_positions)
    ]
    return Chunk(work=work, deadline=time.monotonic() + ttl,
                 variant="standard", flavor=EngineFlavor.TPU,
                 positions=positions)


def make_supervisor(script, extra=(), **kw):
    kw.setdefault("hb_interval", 0.05)
    kw.setdefault("hb_timeout", 0.6)
    kw.setdefault("deadline_margin", 0.15)
    kw.setdefault("logger", Logger(verbose=0))
    return SupervisedEngine(fake_cmd(script, extra=extra), **kw)


@pytest.mark.faultinject
@pytest.mark.subproc
def test_skewed_child_clock_lands_on_parent_timeline(tmp_path, monkeypatch):
    """fakehost --trace-skew 123 reports a monotonic clock 123 s behind
    the real one in its mono fields AND stamps its streamed trace events
    on that same skewed clock. ClockSync must therefore estimate a
    ~+123 s offset and absorb() must land `fake.search` within the
    supervisor's real dispatch window — not two minutes in the past."""
    skew = 123.0
    monkeypatch.setenv("FISHNET_TPU_TRACE_DIR", str(tmp_path))

    async def main():
        sup = make_supervisor({"chunks": ["ok"]},
                              extra=["--trace-skew", str(skew)])
        try:
            t0_us = trace.now_us()
            await sup.go_multiple(make_chunk())
            t1_us = trace.now_us()
            rec = trace.RECORDER
            assert rec is not None  # supervisor installed it from env
            assert sup._clock.offset_us == pytest.approx(
                skew * 1e6, abs=5e6)
            fake = [e for e in rec.snapshot()
                    if e.get("name") == "fake.search"]
            assert fake, "child trace frame never absorbed"
            for ev in fake:
                # on the parent timeline, inside the dispatch window
                # (generous slack: offset error is bounded by pipe
                # latency, microseconds — seconds here catch only the
                # catastrophic un-shifted case, which would be off by
                # the full 123 s)
                assert t0_us - 5e6 <= ev["ts"] <= t1_us + 5e6
        finally:
            await sup.close()

    asyncio.run(main())


@pytest.mark.faultinject
@pytest.mark.subproc
def test_child_death_flight_dump(tmp_path, monkeypatch):
    """A crashed child must leave a loadable merged flight dump: the
    supervisor's recovery ladder writes trace-child-death-*.json into
    FISHNET_TPU_TRACE_DIR, and trace_report parses it."""
    monkeypatch.setenv("FISHNET_TPU_TRACE_DIR", str(tmp_path))

    async def main():
        sup = make_supervisor({"chunks": ["crash:9", "ok"]})
        try:
            # the recovery ladder may replay/quarantine its way to a
            # result or surface the failure — either way the child died
            # and the flight recorder must have fired
            try:
                await sup.go_multiple(make_chunk(ttl=10.0))
            except EngineError:
                pass
            assert sup.stats.deaths >= 1
        finally:
            await sup.close()

    asyncio.run(main())
    dumps = sorted(tmp_path.glob("trace-child-death-*.json"))
    assert dumps, "no flight dump written on child death"
    # every dump parses; the supervisor's ladder markers are on the
    # timeline of each, and — because the ring persists across dumps and
    # the ladder re-dispatches after the first death — the dispatch span
    # (closed with its error annotation) appears in the union
    names = set()
    for dump in dumps:
        events = trace_report.load_events(str(dump))
        report = trace_report.summarize(events)
        assert report["events"] == len(events)
        names |= {e.get("name") for e in events}
    assert "flight-dump" in names
    assert "spawn" in names
    assert "supervisor.dispatch" in names


@pytest.mark.faultinject
@pytest.mark.subproc
def test_tracing_off_no_dump_no_recorder(tmp_path, monkeypatch):
    """Default path: FISHNET_TPU_TRACE_DIR unset — no recorder is
    installed, a crash writes nothing, and the run still recovers."""
    monkeypatch.delenv("FISHNET_TPU_TRACE_DIR", raising=False)

    async def main():
        sup = make_supervisor({"chunks": ["crash:9", "ok"]})
        try:
            try:
                await sup.go_multiple(make_chunk(ttl=10.0))
            except EngineError:
                pass
            assert sup.stats.deaths >= 1
            assert trace.RECORDER is None
        finally:
            await sup.close()

    asyncio.run(main())
    assert list(tmp_path.glob("trace-*.json")) == []
