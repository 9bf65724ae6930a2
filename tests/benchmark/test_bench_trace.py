"""The reduction from a device trace to busy, idle, cycles and top ops, on
a small recorded trace (one v5e, PR 24's run) with known numbers."""
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


def test_recorded_ops_reduce_to_known_busy_and_idle():
    period = SPAN // 4
    mods = modules(5, period)
    out = tr.reduce_trace(OPS, mods)
    lo, hi = 0, 4 * period
    busy = brute_busy(OPS, lo, hi)
    assert out["whole_cycles"] and out["cycles"] == 4
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["idle_share"] == pytest.approx(100.0 * (1 - busy / (hi - lo)))
    assert 0.0 < out["idle_share"] < 100.0
    # the step's big sort leads, as PR 24's breakdown had it
    assert out["device_ops"][0][0].startswith("sort s32[64,4962]")
    assert len(out["device_ops"]) <= 10
    assert out["device_ops"][0][1] >= out["device_ops"][1][1]


def test_fewer_than_three_whole_cycles_gives_no_idle_share():
    period = SPAN // 2
    out = tr.reduce_trace(OPS, modules(3, period))
    assert out["cycles"] == 2 and out["idle_share"] is None
    assert out["busy_s"] is not None  # busy over the traced span still reads
    assert tr.reduce_trace([], [])["busy_s"] is None


def test_gaps_are_named_by_the_host_span_over_them():
    ops = [("%a = s32[4]{0} add(s32[4] %x)", 0, 1000),
           ("%b = s32[4]{0} add(s32[4] %x)", 101_000, 1000),
           ("%c = s32[4]{0} add(s32[4] %x)", 402_000, 1000),
           ("%d = s32[4]{0} add(s32[4] %x)", 403_500, 1000),
           ("%e = s32[4]{0} add(s32[4] %x)", 500_000, 1000)]
    mods = [("jit__run_segment(1)", t, 500) for t in (0, 101_000, 402_000, 500_000)]
    spans = [("segment", 0.0, 0.0005), ("segment.host", 0.0, 0.00011),
             ("fetch:summary", 0.00015, 0.0002)]
    out = tr.reduce_trace(ops, mods, spans, anchor_ns=0, anchor_mono_s=0.0)
    gaps = dict(map(tuple, out["idle_gaps"]))
    # a gap is cut where a span begins or ends: the 300 us gap lies under
    # the end of segment.host (8), the fetch (200) and bare `segment` (92)
    assert gaps["segment.host"] == pytest.approx(108_000 / 1e9)
    assert gaps["fetch:summary"] == pytest.approx(200_000 / 1e9)
    assert gaps["segment"] == pytest.approx(187_500 / 1e9)
    assert gaps["between_ops_under_20us"] == pytest.approx(500 / 1e9)
    assert out["idle_share"] == pytest.approx(100.0 * 496_000 / 500_000)


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
    plain = tr.reduce_trace(OPS, modules(5, period))
    with_loop = tr.reduce_trace(OPS + [loop], modules(5, period))
    assert with_loop["busy_s"] == plain["busy_s"]
    assert with_loop["idle_share"] == plain["idle_share"]
    assert all(not name.startswith("while") for name, _s in with_loop["device_ops"])


def test_time_between_two_sessions_is_not_idle_inside_a_session():
    """A slice that runs from the end of one drive session into the next:
    three cycles, a second with no session, two cycles. With the
    scheduler's spans over the sessions, the second between them is taken
    out; without spans nothing can tell it from idle inside a session."""
    ms = 1_000_000
    mod = DATA["module_name"]
    starts = [0, 100 * ms, 200 * ms, 300 * ms, 1400 * ms, 1500 * ms]
    mods = [(mod, t, 60 * ms) for t in starts]
    ops = [("%fusion.1 = s32[64,64]{1,0} fusion(%a)", t, 60 * ms) for t in starts]
    # session one ends with its last boundary at 400 ms; session two
    # starts dispatching at 1395 ms
    spans = [("segment.device", t / 1e9, 0.06) for t in starts] + \
            [("segment.host", (t + 60 * ms) / 1e9, 0.04) for t in starts[:4]] + \
            [("segment.host", 1.395, 0.005), ("segment.host", 1.46, 0.04)]
    out = tr.reduce_trace(ops, mods, spans, anchor_ns=0, anchor_mono_s=0.0)
    assert out["cycles"] == 5 and out["whole_cycles"]
    assert out["window_s"] == pytest.approx(1.5) and out["busy_s"] == pytest.approx(0.3)
    assert out["between_sessions_s"] == pytest.approx(0.995)
    # inside sessions: 0.505 s, of which 0.3 s busy
    assert out["idle_share"] == pytest.approx(100.0 * 0.205 / 0.505)
    gaps = dict(out["idle_gaps"])
    assert gaps["between_sessions"] == pytest.approx(0.995)
    assert gaps["segment.host"] == pytest.approx(0.205)
    bare = tr.reduce_trace(ops, mods)
    assert bare["between_sessions_s"] == 0.0
    assert bare["idle_share"] == pytest.approx(100.0 * 1.2 / 1.5)
    assert dict(bare["idle_gaps"])["unattributed"] == pytest.approx(1.2)
