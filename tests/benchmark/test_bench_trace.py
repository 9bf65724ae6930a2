"""The reduction from a device trace to busy seconds per blocked second,
top ops and named gaps, on a small recorded trace (one v5e, PR 24's run)
with known numbers."""
import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

DATA = json.load(open(Path(__file__).resolve().parent / "data/trace_v5e_small.json"))
OPS = [(n, s, d) for n, s, d in DATA["ops"]]
SPAN = max(s + d for _n, s, d in OPS)


def brute_busy(ops, lo, hi):
    """Covered nanoseconds by painting them, one by one."""
    covered = bytearray(hi - lo)
    for _n, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            covered[a - lo:b - lo] = b"\x01" * (b - a)
    return sum(covered)


def modules(n, period):
    return [(DATA["module_name"], i * period, period - 1000) for i in range(n)]


def marks(times_ns, steps_each=400, wait_each_s=0.05):
    """The counters read at `times_ns` (the trace's clock, anchor 0 = 0 s):
    so many steps and blocked seconds more at each."""
    return [(t / 1e9, i, i * steps_each, i * wait_each_s) for i, t in enumerate(times_ns)]


def reduce(ops, mods, at, spans=(), **kw):
    return tr.reduce_trace(ops, mods, marks(at, **kw), spans,
                           anchor_ns=0, anchor_mono_s=0.0)


def test_recorded_ops_reduce_to_known_busy_per_blocked_second():
    period = SPAN // 4
    mods = modules(5, period)
    # the trace began 2 ms before the recorded ops, the device idle
    lo, hi = -2_000_000, 3 * period + period // 2
    out = reduce(OPS, mods, [lo, period + 5, 2 * period + 5, hi])
    busy = brute_busy(OPS, lo, hi)
    assert out["intervals"] == 3 and out["segments"] == [0, 3]
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["steps"] == 1200 and out["wait_s"] == pytest.approx(0.15)
    assert out["busy_per_wait"] == pytest.approx(busy / 1e9 / 0.15)
    # the programs that lie wholly between the first and the last mark
    assert out["segment_programs"] == 3
    assert out["segment_device_s"] == pytest.approx(3 * (period - 1000) / 1e9)
    # the step's big sort leads, as PR 24's breakdown had it
    assert out["device_ops"][0][0].startswith("sort s32[64,4962]")
    assert len(out["device_ops"]) <= 10
    assert out["device_ops"][0][1] >= out["device_ops"][1][1]


def test_the_number_of_intervals_does_not_move_what_is_read():
    """One boundary interval or five: busy per blocked second is the same
    where the device does the same under every wait, which a share of idle
    time over the slice is not (n segments, n - 1 refills)."""
    ms = 1_000_000
    # every 100 ms: 40 ms of host work, the device idle, then a 60 ms
    # program on which the host blocks for 61 ms
    ops = [("%fusion.1 = s32[64,64]{1,0} fusion(%a)", k * 100 * ms + 40 * ms, 60 * ms)
           for k in range(6)]
    mods = [("jit__run_segment(1)", s, d) for _n, s, d in ops]
    got = [reduce(ops, mods, [k * 100 * ms + ms for k in range(n + 1)],
                  wait_each_s=0.061) for n in (1, 2, 5)]
    assert [g["intervals"] for g in got] == [1, 2, 5]
    for g in got:
        assert g["busy_per_wait"] == pytest.approx(60 / 61)
        assert tr.window_busy_s(30.5, g) == pytest.approx(30.0)
    # a window cut from the first program's start to the last one's reads
    # 1/1 busy for one program and 5/9 for five: that was the old share


def test_without_two_marks_nothing_is_carried_over_the_window():
    period = SPAN // 2
    for at in ([], [period]):
        out = reduce(OPS, modules(3, period), at)
        assert out["intervals"] == 0 and out["busy_per_wait"] is None
        assert out["busy_s"] is not None  # busy over the traced span still reads
        assert tr.window_busy_s(1.0, out) is None
    # marks that cannot be put on the trace's clock are no marks
    bare = tr.reduce_trace(OPS, modules(3, period), marks([0, period]))
    assert bare["intervals"] == 0 and bare["busy_s"] is not None
    assert tr.reduce_trace([], [])["busy_s"] is None
    # a window in which the host never blocked carries nothing either
    flat = reduce(OPS, modules(3, period), [-2_000_000, period], wait_each_s=0.0)
    assert flat["intervals"] == 1 and flat["busy_per_wait"] is None


def test_gaps_are_named_by_the_host_span_over_them():
    ops = [("%a = s32[4]{0} add(s32[4] %x)", 0, 1000),
           ("%b = s32[4]{0} add(s32[4] %x)", 101_000, 1000),
           ("%c = s32[4]{0} add(s32[4] %x)", 402_000, 1000),
           ("%d = s32[4]{0} add(s32[4] %x)", 403_500, 1000),
           ("%e = s32[4]{0} add(s32[4] %x)", 500_000, 1000)]
    mods = [("jit__run_segment(1)", t, 500) for t in (0, 101_000, 402_000, 500_000)]
    spans = [("segment", 0.0, 0.0005), ("segment.host", 0.0, 0.00011),
             ("fetch:summary", 0.00015, 0.0002)]
    out = reduce(ops, mods, [0, 500_000], spans)
    gaps = dict(map(tuple, out["idle_gaps"]))
    # a gap is cut where a span begins or ends: the 300 us gap lies under
    # the end of segment.host (8), the fetch (200) and bare `segment` (92)
    assert gaps["segment.host"] == pytest.approx(108_000 / 1e9)
    assert gaps["fetch:summary"] == pytest.approx(200_000 / 1e9)
    assert gaps["segment"] == pytest.approx(187_500 / 1e9)
    assert gaps["between_ops_under_20us"] == pytest.approx(500 / 1e9)
    assert out["busy_s"] == pytest.approx(4_000 / 1e9)


def test_op_names_keep_kind_and_shape():
    assert tr.short_op_name(OPS[0][0]) == "sort s32[64,4962]"
    assert tr.short_op_name("%fusion.558 = s32[4962,64]{1,0:T(8,128)} fusion(s32[64,4096] %p)") \
        == "fusion s32[4962,64]"
    assert tr.short_op_name("bench.anchor") == "bench.anchor"
    loop = ("%while.3 = (s32[64,33,16]{2,1,0:T(8,128)}, pred[4]{0}) "
            "while((s32[64,33,16]{2,1,0}, pred[4]{0}) %tuple.1), condition=%c, body=%b")
    assert tr.short_op_name(loop) == "while s32[64,33,16],.."
    assert tr.is_container(loop) and not tr.is_container(OPS[0][0])


def test_a_loop_is_not_an_operation_that_ran():
    """The while loop is on the trace as one event over its whole body:
    counting it would call the gaps between the body's ops busy."""
    period = SPAN // 4
    loop = ("%while.3 = (s32[64,33,16]{2,1,0}, pred[4]{0}) while((s32[64,33,16]{2,1,0}, "
            "pred[4]{0}) %t), condition=%c, body=%b", 0, SPAN)
    at = [period // 2, 3 * period]
    plain = reduce(OPS, modules(5, period), at)
    with_loop = reduce(OPS + [loop], modules(5, period), at)
    assert with_loop["busy_s"] == plain["busy_s"]
    assert with_loop["busy_per_wait"] == plain["busy_per_wait"]
    assert all(not name.startswith("while") for name, _s in with_loop["device_ops"])


def test_time_between_two_sessions_is_named_and_moves_nothing():
    """A slice that runs from the end of one drive session into the next:
    three boundary intervals, a second with no session, two more. The
    second is no blocked time and no busy time, so busy per blocked second
    is what it is inside the sessions; the gap is named for the breakdown
    where the scheduler's spans say that no session ran."""
    ms = 1_000_000
    mod = DATA["module_name"]
    starts = [0, 100 * ms, 200 * ms, 300 * ms, 1400 * ms, 1500 * ms]
    mods = [(mod, t, 60 * ms) for t in starts]
    ops = [("%fusion.1 = s32[64,64]{1,0} fusion(%a)", t, 60 * ms) for t in starts]
    # session one ends with its last boundary at 400 ms; session two
    # starts dispatching at 1395 ms
    spans = [("segment.device", t / 1e9, 0.06) for t in starts] + \
            [("segment.host", (t + 60 * ms) / 1e9, 0.04) for t in starts[:4]] + \
            [("segment.host", 1.395, 0.005), ("segment.host", 1.46, 0.04),
             ("segment.host", 1.56, 0.04)]
    at = [t + 65 * ms for t in starts]  # each boundary seen 5 ms after its program
    out = reduce(ops, mods, at, spans, wait_each_s=0.06)
    assert out["intervals"] == 5
    assert out["window_s"] == pytest.approx(1.5) and out["busy_s"] == pytest.approx(0.3)
    assert out["busy_per_wait"] == pytest.approx(1.0)
    assert out["between_sessions_s"] == pytest.approx(0.995)
    gaps = dict(out["idle_gaps"])
    assert gaps["between_sessions"] == pytest.approx(0.995)
    assert gaps["segment.host"] == pytest.approx(0.205)
    bare = reduce(ops, mods, at, wait_each_s=0.06)
    assert bare["between_sessions_s"] == 0.0
    assert bare["busy_per_wait"] == pytest.approx(1.0)
    assert dict(bare["idle_gaps"])["unattributed"] == pytest.approx(1.2)
