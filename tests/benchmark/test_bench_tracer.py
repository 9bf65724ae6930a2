"""The traced run cannot die on its own profiler: the slice is cut by
device work (the first boundary that spends a step budget, or `max_s`),
and a tracer that fails,
never returns or ends without a slice costs the run its trace-sourced
metrics and nothing else: `trace: failed`, a result, no exception."""
import json
import shutil
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchmark import cells, loadgen, measure, trace_reduce  # noqa: E402
import fake_engine  # noqa: E402

WEIGHTS, EVALUATOR = fake_engine.cell_weights(ROOT)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
BENCH = json.load(open(ROOT / "BENCHMARK.json"))
TRACE_SOURCED = {m["name"] for m in BENCH["per_layer"] if m["source"] == "device_trace"}
PHASE_METRICS = ["scheduler.refill_host_share", "scheduler.lane_loop_host_share",
                 "scheduler.gap_submit_share", "scheduler.gap_starved_share",
                 "scheduler.submit_ms_per_position"]
class ScriptedCounters:
    """A scheduler that passes a boundary every `period_s`, `steps` steps
    each, from `start()` on."""

    def __init__(self, period_s, steps):
        self.period_s, self.steps = period_s, steps
        self.t0 = None

    def start(self):
        self.t0 = time.monotonic()

    def __call__(self):
        n = 0 if self.t0 is None else int((time.monotonic() - self.t0) / self.period_s)
        return {"segments": n, "steps": n * self.steps}


class ScriptedSampler(measure.BoundarySampler):
    """Never polls: `feed` appends boundary records, one every 10 ms, each
    `steps` steps and as many segments later as `script` says (2: two
    boundaries the poll saw as one), and then no more, whatever the
    machine's load. The scheduler's log beside it: `wait_ms` blocked a
    segment (the first of a feed `first_wait_ms`), written `log_lag_s`
    after the count moved."""

    def __init__(self):
        self.now = {"segments": 0, "steps": 0}
        self.log = []
        super().__init__(lambda: dict(self.now))

    def segment_log(self, since, until):
        return [r for r in self.log if since < r[0] <= until]

    def feed(self, script, steps, wait_ms=5.0, first_wait_ms=None, log_lag_s=0.0):
        def go():
            for i, k in enumerate(script):
                time.sleep(0.01)
                rows = [(self.now["segments"] + n + 1, steps,
                         first_wait_ms if (first_wait_ms and i == n == 0) else wait_ms)
                        for n in range(k)]
                self.now["segments"] += k
                self.now["steps"] += k * steps
                self.records.append(self.read())
                time.sleep(log_lag_s)
                self.log += rows

        threading.Thread(target=go, daemon=True).start()
        return self


class FakeProfiler(measure.DeviceTracer):
    """The real `take`, with the profiler's two calls replaced."""

    def __init__(self, *a, stop="returns", start_raises=False, on_start=None):
        super().__init__(*a)
        self.stop_mode, self.start_raises, self.on_start = stop, start_raises, on_start
        self.unblock = threading.Event()

    def start_profiler(self):
        if self.start_raises:
            raise RuntimeError("no profiler on this machine")
        if self.on_start is not None:
            self.on_start()
        return time.monotonic()

    def stop_profiler(self):
        if self.stop_mode == "never":
            self.unblock.wait(60.0)
        elif self.stop_mode == "raises":
            raise RuntimeError("stop_trace: the device did not answer")


# --------------------------------------------------------- the slice's cut


def take_scripted(tmp_path, script, steps_each, max_s, **feed):
    sampler = ScriptedSampler()
    tracer = FakeProfiler(str(tmp_path), sampler, {"steps": 150, "max_s": max_s},
                          sampler.segment_log,
                          on_start=lambda: sampler.feed(script, steps_each, **feed))
    sampler.feed([1], 700)  # the boundary it waits for before it starts
    t0 = time.monotonic()
    tracer.take()
    assert tracer.error is None and tracer.captured.is_set()
    assert tracer.slice_s is not None and tracer.stop_s is not None
    return tracer, sampler, time.monotonic() - t0


@pytest.mark.parametrize("steps_each,script,ended_on,intervals", [
    (300, [1], "work", 1),           # one segment spends the budget: one boundary interval
    (100, [1, 1], "work", 2),        # a session's short last segment, then a whole one
    (40, [1] * 4, "work", 4),
    (100, [2], "work", 1),           # two boundaries the poll saw as one: the steps count
    (100, [1], "max_s", 1),          # the steps are not there yet
    (300, [], "max_s", 0),           # boundaries never come
])
def test_slice_ends_at_the_boundary_that_spends_the_step_budget_or_on_max_s(
        tmp_path, steps_each, script, ended_on, intervals):
    tracer, sampler, took = take_scripted(
        tmp_path, script, steps_each, 5.0 if ended_on == "work" else 0.4)
    assert tracer.cut == {"intervals": intervals, "steps": sum(script) * steps_each,
                          "ended_on": ended_on,
                          "in_flight_at_anchor": False if script else None}
    if ended_on == "work":
        assert took < 4.0
        # the budget is overshot by less than the segment that spent it
        assert tracer.cut["steps"] - script[-1] * steps_each < 150
    else:
        assert 0.4 <= took < 4.0
    # the reading where the trace began: the boundary waited for is in it
    assert tracer.anchor_mark == (tracer.anchor_mono, 1, 700)
    marks = tracer.marks()
    assert marks[0] == tracer.anchor_mark and len(marks) == 1 + len(script)
    assert [m[1:] for m in marks[1:]] == [
        (1 + sum(script[:i + 1]), 700 + sum(script[:i + 1]) * steps_each)
        for i in range(len(script))]
    # a boundary after the slice's end is on no mark
    sampler.feed([1], 700)
    time.sleep(0.1)
    assert len(sampler.records) == 2 + len(script) and tracer.marks() == marks


@pytest.mark.parametrize("script,ended_on,intervals,steps", [
    ([1, 1], "work", 1, 300),   # the first boundary closes no whole interval: one more
    ([1], "max_s", 0, 0),
])
def test_a_program_in_flight_when_the_trace_began_opens_no_whole_interval(
        tmp_path, script, ended_on, intervals, steps):
    """The scheduler was blocked on the first segment for 200 ms and the
    trace had run for 10: it was launched before. Its part before the
    anchor is on no trace, so the reading starts at its boundary."""
    tracer, sampler, _took = take_scripted(
        tmp_path, script, 300, 5.0 if ended_on == "work" else 0.4, first_wait_ms=200.0)
    assert tracer.cut == {"intervals": intervals, "steps": steps, "ended_on": ended_on,
                          "in_flight_at_anchor": True}
    marks = tracer.marks()
    assert tracer.anchor_mark not in marks
    assert [m[1:] for m in marks] == [(2 + i, 1000 + 300 * i) for i in range(len(script))]
    # what the log gives for them: nothing up to the first, the second's own after
    logged = trace_reduce.log_marks(marks, sampler.segment_log(marks[0][1], marks[-1][1]))
    assert [m[2:] for m in logged] == [(0, 0.0), (300, pytest.approx(0.005))][:len(script)]


def test_a_segment_counted_and_not_yet_logged_is_waited_for(tmp_path):
    """`segments` moves a few statements before the log's row is written:
    until the row is there nothing is decided."""
    tracer, sampler, took = take_scripted(tmp_path, [1], 300, 5.0, log_lag_s=0.15)
    assert took >= 0.15
    assert tracer.cut == {"intervals": 1, "steps": 300, "ended_on": "work",
                          "in_flight_at_anchor": False}


def test_steps_count_from_the_reading_where_the_trace_began(tmp_path):
    """What was accounted before the trace began is not in it, whatever
    the sampler recorded before."""
    sampler = ScriptedSampler()
    sampler.feed([1, 1], 5000)
    time.sleep(0.1)
    assert len(sampler.records) == 2
    tracer = FakeProfiler(str(tmp_path), sampler, {"steps": 150, "max_s": 5.0},
                          sampler.segment_log,
                          on_start=lambda: sampler.feed([1, 1], 100))
    sampler.feed([1], 5000)
    tracer.take()
    assert tracer.anchor_mark[1:] == (3, 15_000)
    assert tracer.cut == {"intervals": 2, "steps": 200, "ended_on": "work",
                          "in_flight_at_anchor": False}


def test_boundaries_on_a_polling_sampler():
    """The real sampler thread over scripted counters."""
    counters = ScriptedCounters(0.03, 100)
    sampler = measure.BoundarySampler(counters)
    sampler.start()
    try:
        counters.start()
        assert sampler.wait_boundaries(3, 5.0) >= 3
        assert sampler.wait_boundaries(1000, 0.1) < 1000
    finally:
        sampler.stop()
    assert all(steps == 100 * segments for _t, segments, steps in sampler.records)
    assert [r[1] for r in sampler.records] == sorted({r[1] for r in sampler.records})


def test_marks_carry_what_the_scheduler_logged_not_its_totals():
    """The totals also hold the boundaries of programs that ran no step,
    and whatever stalled the scheduler while the profiler started; the
    log holds each counted segment's own interval."""
    marks = [(10.0, 7, 5000), (10.4, 8, 5400), (10.9, 10, 6100)]
    log = [(6, 350, 90.0), (7, 200, 60.0), (8, 400, 139.0), (9, 300, 104.0),
           (10, 400, 141.0), (11, 500, 170.0)]
    assert trace_reduce.log_marks(marks, log) == [
        (10.0, 7, 0, 0.0), (10.4, 8, 400, pytest.approx(0.139)),
        (10.9, 10, 1100, pytest.approx(0.384))]
    assert trace_reduce.log_marks(marks[:1], log) == [(10.0, 7, 0, 0.0)]
    assert trace_reduce.window_busy_s(26.0, {"busy_per_wait": 0.998}) == pytest.approx(25.948)
    assert trace_reduce.window_busy_s(0.0, {"busy_per_wait": 0.998}) is None
    assert trace_reduce.window_busy_s(26.0, {"busy_per_wait": None}) is None
    assert trace_reduce.window_busy_s(26.0, None) is None


# ------------------------------------------- the run around a tracer at fault


def drive(tmp_path, factory, trace_limits=None, t_start=None, seconds=1.2,
          first_run=False):
    cell = fake_engine.toy_cell(ROOT)
    cell["limits"] = dict(cell["limits"], trace=dict(
        cell["limits"]["trace"], **(trace_limits or {})))
    lines = []
    result = loadgen.run_cell(
        cell, seed=2147483659, seconds=seconds, trace=True,
        make_engine=lambda: fake_engine.FakeAdapter(WEIGHTS, EVALUATOR),
        device=V5E, t_start=time.monotonic() if t_start is None else t_start,
        rehearsal=None, control=None, say=lines.append,
        trace_dir=str(tmp_path / "trace"), tracer_factory=factory,
        first_run=first_run)
    return result, lines


def assert_sound_without_a_trace(result, lines, why):
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    failed = [ln for ln in lines if ln.startswith("trace: ")]
    assert len(failed) == 1 and failed[0].startswith("trace: failed: ")
    assert why in failed[0]
    got = set(result["metrics"])
    assert {"scheduler.live_lane_share", "scheduler.boundary_host_share",
            "scheduler.session_share", "scheduler.position_p95_s",
            "segment.nodes_per_s"} <= got
    assert not got & TRACE_SOURCED
    assert "busy_s" not in result["device"] and "breakdown" not in result
    assert list(result)[-1] == "checks"
    json.dumps(result)


@pytest.mark.parametrize("started_s_ago", [
    326.0,  # into a warm run: a few seconds are left of 360 less the 30 kept
    400.0,  # nothing is left, and nothing is waited
])
def test_a_profiler_that_never_returns_costs_the_trace_not_the_run(
        tmp_path, started_s_ago):
    """`take` sets `captured` and `stop_trace` never comes back: the wait
    ends with the run's allowance, the run says so and prints what the
    counters give."""
    made = []

    def factory(*a):
        made.append(FakeProfiler(*a, stop="never"))
        return made[0]

    t0 = time.monotonic()
    try:
        result, lines = drive(tmp_path, factory,
                              t_start=time.monotonic() - started_s_ago)
    finally:
        made[0].unblock.set()
    assert time.monotonic() - t0 < 30.0
    tracer = made[0]
    assert tracer.captured.is_set() and tracer.slice_s is not None
    assert tracer.stop_s is None and tracer.error is None
    assert_sound_without_a_trace(result, lines, "the profiler had not returned")
    assert any("allowed 360 s" in ln for ln in lines if ln.startswith("trace: failed"))


@pytest.mark.parametrize("kwargs,why", [
    ({"start_raises": True}, "no profiler on this machine"),
    ({"stop": "raises"}, "the device did not answer"),
])
def test_a_tracer_with_an_error_reads_as_before(tmp_path, kwargs, why):
    result, lines = drive(tmp_path, lambda *a: FakeProfiler(*a, **kwargs))
    assert_sound_without_a_trace(result, lines, why)


def test_a_tracer_that_ends_without_a_slice_is_a_failed_trace(tmp_path):
    class NoSlice:
        error = None
        anchor_mono = slice_s = stop_s = None

        def __init__(self, *_a):
            self.captured = threading.Event()

        def take(self):
            self.captured.set()

    result, lines = drive(tmp_path, NoSlice)
    assert_sound_without_a_trace(result, lines, "without a slice")


def test_the_wait_is_the_runs_allowance_less_what_is_still_to_do():
    tcfg = cells.load_json(ROOT / "benchmark/limits.json")["trace"]
    assert tcfg["run_allowance_s"] == {"warm": 360, "compiling": 1200}
    waits = []

    class Thread:
        def join(self, timeout):
            waits.append(timeout)

        def is_alive(self):
            return True

    class Tracer:
        error = slice_s = stop_s = None

    now = time.monotonic()
    # 120 s into a warm run: 360 - 30 kept - 120 gone = 210 s to wait
    why = loadgen._wait_for_tracer(Tracer, Thread(), tcfg, now - 120.0, first_run=False)
    assert waits[-1] == pytest.approx(360 - tcfg["reserve_s"] - 120.0, abs=0.5)
    assert "allowed 360 s" in why
    # a cell's first run in its checkout compiles, and is allowed the time
    why = loadgen._wait_for_tracer(Tracer, Thread(), tcfg, now - 120.0, first_run=True)
    assert waits[-1] == pytest.approx(1200 - tcfg["reserve_s"] - 120.0, abs=0.5)
    assert "allowed 1200 s" in why
    # nothing left: no wait, not a negative one
    loadgen._wait_for_tracer(Tracer, Thread(), tcfg, now - 1000.0, first_run=False)
    assert waits[-1] == 0.0


class RecordedSlice(FakeProfiler):
    """A profiler that 'recorded' tests/benchmark/data's ops: the trace's
    clock starts 2 ms before the anchor would, and the stand-in's segments
    (1 ms blocked each in its log) fall where they fall."""

    def marks(self):
        span = RecordedSlice.span_s
        real = super().marks()
        # the anchor's reading 2 ms before the ops, the last one after them
        return [(self.anchor_mono - 0.002,) + real[0][1:],
                (self.anchor_mono + span,) + real[-1][1:]]


def recorded(monkeypatch):
    data = json.load(open(Path(__file__).resolve().parent / "data/trace_v5e_small.json"))
    ops = [tuple(o) for o in data["ops"]]
    period = max(s + d for _n, s, d in ops) // 4
    mods = [(data["module_name"], i * period, period - 1000) for i in range(4)]
    RecordedSlice.span_s = (4 * period + 1000) / 1e9
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda _d: {
        "ops": ops, "modules": mods, "anchor_ns": 0, "devices": 1})
    return ops


def test_a_trace_that_came_is_read_and_its_cut_is_printed(tmp_path, monkeypatch):
    ops = recorded(monkeypatch)
    made = []

    def factory(*a):
        made.append(RecordedSlice(*a))
        return made[0]

    result, lines = drive(tmp_path, factory, {"steps": 20, "max_s": 2.0}, seconds=2.0)
    trace_lines = [ln for ln in lines if ln.startswith("trace: ")]
    assert len(trace_lines) == 2 and "failed" not in trace_lines[0]
    assert "1 whole boundary intervals" in trace_lines[0]
    assert "4 segment programs" in trace_lines[0] and f"{len(ops)} device ops" in trace_lines[0]
    assert trace_lines[1].startswith("trace: cut ") and "ops_before_anchor" in trace_lines[1]
    assert "'ended_on': 'work'" in trace_lines[1]
    assert "'in_flight_at_anchor': False" in trace_lines[1]
    sl = result["window"]["notes"]["slice"]
    assert sl["intervals"] == 1 and sl["wait_s"] > 0
    assert sl["busy_per_wait"] == pytest.approx(sl["busy_s"] / sl["wait_s"])
    # the window's busy seconds: its blocked seconds times the slice's ratio
    share = result["metrics"]["scheduler.boundary_host_share"]["value"] / 100.0
    in_session = result["metrics"]["scheduler.session_share"]["value"] / 100.0 * 2.0
    blocked = in_session * (1 - share)
    assert result["device"]["busy_s"] == pytest.approx(blocked * sl["busy_per_wait"])
    assert result["device"]["window_s"] == 2.0 and "breakdown" in result
    assert result["metrics"]["device.session_idle_share"]["value"] == pytest.approx(
        100.0 * (1 - result["device"]["busy_s"] / in_session))
    assert result["metrics"]["device.idle_share"]["value"] == pytest.approx(
        100.0 * (1 - result["device"]["busy_s"] / 2.0))
    assert result["metrics"]["segment.step_us"]["value"] > 0


@pytest.mark.parametrize("first_run,allowed", [(False, 360), (True, 1200)])
def test_a_cells_first_run_in_its_checkout_has_the_allowance_of_a_run_that_compiles(
        tmp_path, first_run, allowed):
    """Not what the run built decides it (a warm run that misses the cache
    once would be waited for past the driver's limit), but whether a run of
    the cell got through in this checkout before: run.py's marker."""
    made = []

    def factory(*a):
        made.append(FakeProfiler(*a, stop="never"))
        return made[0]

    try:
        result, lines = drive(tmp_path, factory, first_run=first_run,
                              t_start=time.monotonic() - allowed - 5.0)
    finally:
        made[0].unblock.set()
    assert_sound_without_a_trace(result, lines, f"allowed {allowed} s")


def test_run_py_marks_a_cell_that_ran(tmp_path, monkeypatch):
    from benchmark import run

    seen = []

    def run_cell(cell, **kw):
        seen.append(kw["first_run"])
        return {"checks": {}}

    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run.cells, "load_cell", lambda root, name: {"chips": 1})
    monkeypatch.setattr(run.measure, "claim_device", lambda chips, rehearsal: V5E)
    monkeypatch.setattr(run.measure, "program_engine_factory", lambda cell, r: None)
    monkeypatch.setattr(run.loadgen, "run_cell", run_cell)
    argv = ["--workload", "standard.trickle", "--seed", "1", "--seconds", "1"]
    assert run.main(argv) == 0 and run.main(argv) == 0
    assert run.main(["--workload", "other.cell"] + argv[2:]) == 0
    assert seen == [True, False, True]
    assert (tmp_path / ".cache/bench_ran/standard.trickle").exists()
    # a rehearsal on the CPU is no run of the cell
    monkeypatch.setattr(run.measure, "prepare_environment", lambda r, c: None)
    assert run.main(["--workload", "third.cell", "--rehearse-cpu"] + argv[2:]) == 0
    assert seen[-1] is True and not (tmp_path / ".cache/bench_ran/third.cell").exists()


@pytest.mark.parametrize("occupancy,busy_s", [
    ({"host_ms": 300.0, "device_ms": 700.0}, None),  # no trace came, or no whole interval in it
    ({}, 0.5),                                        # no session ran
])
def test_trace_sourced_readers_return_nothing_without_busy_seconds_or_a_session(
        occupancy, busy_s):
    ctx = {"occupancy": occupancy, "busy_s": busy_s, "window_s": 0.0, "nodes": 0,
           "per_node": {"flops": 1.0, "bytes": 1.0},
           "peak": {"flops_per_s": 1.0, "bytes_per_s": 1.0}}
    for name in ("device.session_idle_share", "device.idle_share", "step.mfu_roofline_share"):
        assert cells.load_reader(name)(ctx) is None, name


# ----------------------------------------------- room for a data-files-only PR


def test_the_slices_budget_is_the_benchmarks_alone(tmp_path):
    """One constant for every cell: a configuration's file describes a
    deployment and carries no setting of the harness."""
    made = []

    def factory(*a):
        made.append(FakeProfiler(*a))
        return made[0]

    cell = fake_engine.toy_cell(ROOT)
    limits = cells.load_json(ROOT / "benchmark/limits.json")["trace"]
    assert sorted(limits) == ["max_s", "reserve_s", "run_allowance_s", "steps"]
    drive(tmp_path, factory, {"max_s": 0.1})
    assert made[0].cfg["steps"] == limits["steps"] == 150
    assert "trace" not in cell["config"]
    for path in (ROOT / "benchmark/configs").glob("*.json"):
        assert "trace" not in cells.load_json(path), path


def test_readers_are_handed_the_cells_configuration(tmp_path, monkeypatch):
    seen = {}

    def capture(cell, ctx):
        seen.update(ctx)
        return {}

    monkeypatch.setattr(cells, "read_per_layer", capture)
    drive(tmp_path, lambda *a: FakeProfiler(*a), {"max_s": 0.1})
    assert seen["config"]["variant"] == "standard"
    assert seen["config"]["max_moves"] == 218 and "net_shapes" in seen["config"]
    assert seen["per_node"]["bytes"] > 0


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_metrics_read_every_cell(tmp_path, name):
    """A configuration added with new files and two new entries is read by
    the phase metrics as by the ten older ones: none lists its cells."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    cfg = json.load(open(root / "benchmark/configs/standard.json"))
    cfg.update(name="crazyhouse", variant="crazyhouse", max_moves=538)
    (root / "benchmark/configs/crazyhouse.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="crazyhouse",
                                 file="benchmark/configs/crazyhouse.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="crazyhouse.trickle",
                                   config="crazyhouse"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell(root, "crazyhouse.trickle", bench_dir=root / "benchmark")
    assert name in [m["name"] for m in cell["per_layer"]]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    assert "workloads" not in entry
