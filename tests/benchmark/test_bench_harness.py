"""The harness end to end with a stand-in engine: the last line's keys,
`correct` true on a sound engine and false on each planted fault and on
the lower-precision control, no result on a CPU, nothing printed as a
metric by a rehearsal, an unknown device kind refused."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import loadgen, measure, run  # noqa: E402
import fake_engine  # noqa: E402

WEIGHTS, EVALUATOR = fake_engine.cell_weights(ROOT)
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


class NoTrace:
    """Stands where the profiler would: a slice that recorded nothing."""
    error = None
    anchor_mono = None
    slice_s = stop_s = 0.0
    # (s, segments, steps): the anchor's reading, then a recorded slice's
    marks_at = ((0.0, 0, 0),)

    def __init__(self, *_a):
        import threading

        self.captured = threading.Event()

    def take(self):
        self.captured.set()

    def marks(self):
        return list(self.marks_at)


def drive(tmp_path, fault=None, weights=WEIGHTS, workload="standard.trickle",
          trace=False, rehearsal=None, device=V5E, seconds=1.2, seed=2147483659,
          variant=None, **traffic):
    cell = fake_engine.toy_cell(ROOT, workload, variant=variant, **traffic)
    lines = []
    result = loadgen.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace,
        make_engine=lambda: fake_engine.FakeAdapter(weights, EVALUATOR, fault),
        device=device, t_start=time.monotonic(), rehearsal=rehearsal,
        control=None, say=lines.append, trace_dir=str(tmp_path / "trace"),
        tracer_factory=NoTrace)
    return result, lines


def test_last_line_has_the_contracts_keys(tmp_path):
    result, lines = drive(tmp_path)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device", "window", "checks"}
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    assert set(result["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in result["metrics"] and "positions_per_s" in result["metrics"]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == result["window"]["answers"] > 0
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(result)
    assert any(ln.startswith("work: ") and "per position" in ln for ln in lines)


def test_window_counts_only_what_it_delivered(tmp_path):
    result, _ = drive(tmp_path, seconds=1.0)
    pps = result["metrics"]["positions_per_s"]["value"]
    assert pps == pytest.approx(result["window"]["answers"] / 1.0)


@pytest.mark.parametrize("fault,check", [
    ("score", "d1_gap_cp"),
    ("move", "d1_move_gap_cp"),
    ("best_move", "bad_lines"),
    ("illegal_line", "bad_lines"),
    ("twice", "delivery"),
    ("wrong_index", "delivery"),
    ("dropped", "delivery"),
    ("deep_score", "leaf_inexact_pct"),
])
def test_a_planted_fault_reads_not_correct(tmp_path, fault, check):
    result, _ = drive(tmp_path, fault=fault)
    assert result["correct"] is False
    c = result["checks"][check]
    assert c["value"] is None or c["value"] > c["limit"]


def test_crazyhouse_cell_sound_and_faulty(tmp_path):
    ok, _ = drive(tmp_path, variant="crazyhouse")
    assert ok["correct"] is True
    bad, _ = drive(tmp_path, variant="crazyhouse", fault="score")
    assert bad["correct"] is False


def test_a_program_built_inside_the_window_reads_not_correct(tmp_path, monkeypatch):
    """Nothing may compile inside the measured window: a run that did is
    not a measurement, and says so through `correct`."""
    class Counting(measure.CompileCounter):
        def snapshot(self):
            self.built += 1  # one more at the close than at the open
            return super().snapshot()

    monkeypatch.setattr(measure, "CompileCounter", Counting)
    result, _ = drive(tmp_path)
    c = result["checks"]["programs_inside"]
    assert result["correct"] is False and c["value"] == 1 > c["limit"] == 0


def test_shapes_that_cannot_be_warmed_fail_the_run(tmp_path):
    with pytest.raises(RuntimeError):
        drive(tmp_path, fault="cold_shapes",
              warm_shapes=[{"width": 16, "counts": 4}])


def test_lower_precision_control_reads_not_correct(tmp_path):
    """The control at a size a test can hold: the same stand-in, its
    weights rounded to bfloat16, must fail the depth-1 gap."""
    result, _ = drive(tmp_path, weights=fake_engine.bf16_weights(WEIGHTS),
                      seconds=2.5)
    assert result["correct"] is False
    c = result["checks"]["d1_gap_cp"]
    assert c["value"] > c["limit"]


def test_no_answers_is_not_correct(tmp_path):
    cell = fake_engine.toy_cell(ROOT)
    result = loadgen.run_cell(
        cell, seed=1, seconds=0.3, trace=False,
        make_engine=lambda: fake_engine.FakeAdapter(WEIGHTS, EVALUATOR, latency_s=5.0),
        device=V5E, t_start=time.monotonic(), rehearsal=None, control=None,
        say=lambda s: None, trace_dir=str(tmp_path / "t"))
    assert result["correct"] is False and result["attempted"] == 0


def test_rehearsal_never_prints_a_metric(tmp_path):
    for trace in (False, True):
        result, _ = drive(tmp_path, rehearsal={}, device=CPU, trace=trace)
        assert result["metrics"] == {}
        assert "busy_s" not in result["device"] and "breakdown" not in result
        assert result["window"]["rehearsal"] is True


def test_traced_run_reports_per_layer_names_only(tmp_path):
    result, _ = drive(tmp_path, trace=True)
    bench = json.load(open(ROOT / "BENCHMARK.json"))
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(result["metrics"]) <= per_layer
    # counters were there to read; the device trace was not, and a reader
    # that finds nothing leaves its metric out rather than print 0
    assert "scheduler.live_lane_share" in result["metrics"]
    assert "scheduler.position_p95_s" in result["metrics"]
    assert "device.idle_share" not in result["metrics"]
    assert "device.session_idle_share" not in result["metrics"]
    assert "step.mfu_roofline_share" not in result["metrics"]


def test_traced_run_carries_the_slice_over_the_whole_window(tmp_path, monkeypatch):
    """With a recorded slice in the profiler's place and the stand-in's
    log beside it (1 ms blocked a segment): what the last line gives the driver as busy_s over
    window_s is the window's, its blocked seconds times the slice's busy
    seconds per blocked second, not the slice's own."""
    from benchmark import trace_reduce

    data = json.load(open(Path(__file__).resolve().parent / "data/trace_v5e_small.json"))
    ops = [tuple(o) for o in data["ops"]]
    span = max(s + d for _n, s, d in ops)
    mods = [(data["module_name"], i * (span // 4), span // 4 - 1000) for i in range(4)]
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda _d: {
        "ops": ops, "modules": mods, "anchor_ns": 0, "devices": 1})
    monkeypatch.setattr(NoTrace, "anchor_mono", 0.0)
    n = int(span / 1e6) + 2  # as many segments as keep the host blocked longer than the ops ran
    monkeypatch.setattr(NoTrace, "marks_at", (
        (-0.002, 5, 0), ((span + 1000) / 1e9, 5 + n, 0)))
    result, lines = drive(tmp_path, trace=True, seconds=2.0)
    m, dev = result["metrics"], result["device"]
    sl = result["window"]["notes"]["slice"]
    assert sl["intervals"] == 1 and 0 < sl["busy_s"] < sl["window_s"]
    assert sl["wait_s"] == pytest.approx(n / 1e3)
    assert sl["busy_per_wait"] == pytest.approx(sl["busy_s"] / (n / 1e3))
    in_session = m["scheduler.session_share"]["value"] / 100.0 * 2.0
    blocked = result["window"]["notes"]["blocked_s"]
    # the stand-in logs every segment, so its log and its totals agree
    assert blocked == pytest.approx(
        in_session * (1 - m["scheduler.boundary_host_share"]["value"] / 100.0))
    assert dev["window_s"] == 2.0 and 0 < dev["busy_s"] < dev["window_s"]
    assert dev["busy_s"] == pytest.approx(blocked * sl["busy_per_wait"])
    assert m["device.session_idle_share"]["value"] == pytest.approx(
        100.0 * (1 - dev["busy_s"] / in_session))
    assert m["device.idle_share"]["value"] == pytest.approx(
        100.0 * (1 - dev["busy_s"] / dev["window_s"]))
    assert m["device.idle_share"]["value"] > m["device.session_idle_share"]["value"]
    assert m["segment.step_us"]["value"] == pytest.approx(
        1e6 * 4 * (span // 4 - 1000) / 1e9 / (10 * n))
    assert 0 < m["step.mfu_roofline_share"]["value"] < 100
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_unknown_device_kind_is_an_error(tmp_path):
    with pytest.raises(SystemExit):
        drive(tmp_path, trace=True, device={"platform": "tpu", "kind": "TPU v9", "count": 1})


def test_on_a_cpu_there_is_no_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = run.main(["--workload", "standard.trickle", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code == run.EXIT_NO_DEVICE != 0
    assert out.out.strip() == ""
    assert "no accelerator" in out.err
    with pytest.raises(measure.NoDevice):
        measure.claim_device(1, rehearsal=False)
    assert measure.claim_device(1, rehearsal=True)["platform"] == "cpu"


def test_unknown_workload_is_refused(capsys):
    code = run.main(["--workload", "no.such", "--seed", "1", "--seconds", "1"])
    assert code == run.EXIT_BAD_CELL
    assert capsys.readouterr().out.strip() == ""
