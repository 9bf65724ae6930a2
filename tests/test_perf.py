"""fishnet-perf tests: the sqlite ledger round-trip, backfill
idempotence over the checked-in bench artifacts, the direction/noise
math behind the regression gate, report-only semantics for rows without
a matching env fingerprint, bench-round emission, and a CPU smoke of
the cost_analysis capture path.

The gate's acceptance contract lives here: a first run (no baseline)
passes, a seeded 10% regression in a deterministic counter metric
fails, and a wall-clock swing or fingerprint mismatch never hard-fails.
"""
import json
from pathlib import Path

import pytest

from fishnet_tpu.obs import metrics as obs_metrics
from fishnet_tpu.obs import perf
from tools import perf_report


FP = "feedc0de9abc"


def seed(ledger, runs, fingerprint=FP, bench_row="search",
         metric="positions_per_kstep"):
    """n runs of {bench_row: {metric: value}} under one fingerprint."""
    for i, value in enumerate(runs):
        ledger.ingest_run(
            f"run{i}", {bench_row: {metric: float(value)}},
            sha=f"sha{i}", fingerprint=fingerprint,
        )


# ------------------------------------------------------------------ ledger


def test_ledger_round_trip(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    n = led.ingest_run(
        "r1", {"search": {"nps": 123.5, "nodes": 9.0}},
        sha="abc", fingerprint=FP,
    )
    assert n == 2
    led.close()

    led = perf.PerfLedger.open(p)
    run = led.latest_run()
    assert run["run_id"] == "r1"
    assert run["fingerprint"] == FP
    assert led.run_metrics("r1") == {
        "search": {"nps": 123.5, "nodes": 9.0}
    }
    led.close()


def test_ledger_replace_is_idempotent(tmp_path):
    led = perf.PerfLedger.open(str(tmp_path / "perf.db"))
    led.ingest_run("r1", {"search": {"nodes": 1.0}})
    seq1 = led.latest_run()["seq"]
    led.ingest_run("r1", {"search": {"nodes": 2.0}})
    assert led.latest_run()["seq"] == seq1  # same run keeps its seq
    assert led.run_metrics("r1") == {"search": {"nodes": 2.0}}
    led.close()


def _write_artifacts(root):
    """The artifact shapes backfill reads, small: a driver record whose
    tail holds a config line and the headline, a failed round with
    nothing but its exit code, a ledger-emitted record with a `rows`
    table, and two multichip dry-run records."""
    headline = {"metric": "nodes/sec/chip", "value": 1725,
                "unit": "nodes/sec", "vs_baseline": 0.0043}
    arts = {
        "BENCH_r01.json": {"n": 1, "cmd": "python bench.py", "rc": 124,
                           "tail": "", "parsed": None},
        "BENCH_r02.json": {
            "n": 2, "cmd": "python bench.py", "rc": 0, "parsed": headline,
            "tail": ('bench config cfg4_chess960: {"nps": 900.5, "B": 64}\n'
                     + json.dumps(headline) + "\n"),
        },
        "BENCH_r03.json": {"n": 3, "cmd": "perf-ledger", "rc": 0,
                           "rows": {"ramp_best": {"nps": 1200.0, "B": 8}}},
        "MULTICHIP_r01.json": {"n_devices": 8, "rc": 1, "ok": False,
                               "skipped": False, "tail": "boom"},
        "MULTICHIP_r02.json": {
            "n_devices": 8, "rc": 0, "ok": True, "skipped": False,
            "tail": "dryrun_multichip ok: 8 devices, train loss 0.0958, "
                    "search nodes 4848"},
    }
    for name, obj in arts.items():
        (root / name).write_text(json.dumps(obj))


def test_backfill_ingests_artifacts_idempotently(tmp_path):
    _write_artifacts(tmp_path)
    led = perf.PerfLedger.open(":memory:")
    n1 = led.backfill(str(tmp_path))
    n2 = led.backfill(str(tmp_path))
    assert n1 > 0 and n1 == n2
    runs = {r["run_id"]: r for r in led.runs()}
    # every round ingests, including the failed early one
    assert set(runs) == {
        "backfill:BENCH_r01", "backfill:BENCH_r02", "backfill:BENCH_r03",
        "backfill:MULTICHIP_r01", "backfill:MULTICHIP_r02"}
    assert led.run_metrics("backfill:BENCH_r01") == {
        "artifact": {"rc": 124.0}}
    assert led.run_metrics("backfill:BENCH_r02")["headline"]["value"] == 1725
    assert led.run_metrics("backfill:BENCH_r03") == {
        "ramp_best": {"nps": 1200.0, "B": 8.0}}
    # backfilled history carries no env fingerprint: never gated
    assert all(r["fingerprint"] == "" for r in runs.values())
    led.close()


def test_history_filters_on_fingerprint(tmp_path):
    led = perf.PerfLedger.open(str(tmp_path / "perf.db"))
    seed(led, [100, 101, 102])
    led.ingest_run("other", {"search": {"positions_per_kstep": 55.0}},
                   fingerprint="0ther")
    hist = led.history("search", "positions_per_kstep", fingerprint=FP)
    assert [v for _, v in hist] == [100.0, 101.0, 102.0]
    led.close()


def test_flatten_result():
    flat = perf.flatten_result({
        "nps": 10, "ok": True, "name": "skipped", "lanes": [1, 2],
        "summary": {"p99": 4.5, "deep": {"x": 1}},
    })
    assert flat == {
        "nps": 10.0, "ok": 1.0, "summary.p99": 4.5, "summary.deep.x": 1.0,
    }


def test_split_mesh_rows():
    rows = {}
    rest = perf.split_mesh_rows(rows, "mesh_scaling", {
        "ndev": {"1": {"positions_per_s": 5.0},
                 "2": {"positions_per_s": 9.0}},
        "warm_x": 1.2,
    })
    assert set(rows) == {"mesh_scaling_ndev1", "mesh_scaling_ndev2"}
    assert rest == {"warm_x": 1.2}
    # a stage's own RESULT carries ndev as an int: passes through
    res = {"ndev": 8, "nps": 1.0}
    assert perf.split_mesh_rows({}, "stage", res) is res


def test_emit_bench_round(tmp_path):
    (tmp_path / "BENCH_r04.json").write_text("{}", encoding="utf-8")
    led = perf.PerfLedger.open(":memory:")
    led.ingest_run("r1", {"search": {"nodes": 7.0}},
                   sha="abc", fingerprint=FP)
    out = led.emit_bench_round("r1", root=str(tmp_path))
    assert out.endswith("BENCH_r05.json")  # next round after r04
    obj = json.loads(Path(out).read_text(encoding="utf-8"))
    assert obj["n"] == 5
    assert obj["run_id"] == "r1"
    assert obj["git_sha"] == "abc"
    assert obj["fingerprint"] == FP
    assert "build_info" in obj
    assert obj["rows"] == {"search": {"nodes": 7.0}}
    # the emitted artifact parses back into the same rows
    assert perf._parse_bench_artifact(out) == {"search": {"nodes": 7.0}}
    led.close()


# --------------------------------------------------------------- direction


@pytest.mark.parametrize("metric,direction,tier", [
    ("positions_per_kstep", "up", "counter"),
    ("scaling_x", "up", "counter"),
    ("mean_live_occupancy", "up", "counter"),
    ("transfers_per_boundary", "down", "counter"),
    ("nodes", "flat", "counter"),
    ("steps_per_shard", "flat", "counter"),
    ("rc", "flat", "counter"),
    ("flops", "down", "counter"),
    ("bytes_accessed", "down", "counter"),
    ("positions_per_s", "up", "wallclock"),
    ("summary.p99", "down", "wallclock"),
    ("compile_ms", "down", "wallclock"),
    ("dt", "down", "wallclock"),
    ("unknown_metric", "flat", "wallclock"),
])
def test_direction_table(metric, direction, tier):
    assert perf_report.classify(metric) == (direction, tier)


def test_noise_band_floor_and_spread():
    # identical history: the floor applies
    assert perf_report.noise_band([100.0] * 5, "counter") == \
        pytest.approx(perf_report.DEFAULT_COUNTER_BAND)
    # noisy history: 2x relative stdev beats the floor
    band = perf_report.noise_band([90.0, 110.0, 95.0, 105.0], "counter")
    assert band > perf_report.DEFAULT_COUNTER_BAND
    # wall-clock series always get the wide band
    assert perf_report.noise_band([100.0] * 5, "wallclock") == \
        pytest.approx(perf_report.WALLCLOCK_BAND)


# -------------------------------------------------------------------- gate


def test_first_run_passes(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100])  # one run: nothing to compare against
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 0


def test_seeded_counter_regression_fails(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100, 100.5, 101, 100.2, 90])  # 10% drop on an up-counter
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 1
    # report-only mode still exits clean on the same ledger
    assert perf_report.main(["--ledger", p, "--no-backfill"]) == 0


def test_improvement_passes(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100, 100.5, 101, 110])  # up-counter moving up
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 0


def test_flat_metric_regresses_in_both_directions(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [1000, 1000, 1000, 1100], metric="nodes")
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 1


def test_fingerprint_mismatch_is_report_only(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100, 100, 100, 100])
    # same metric collapses 10% on DIFFERENT hardware/env: not gated
    led.ingest_run(
        "hw", {"search": {"positions_per_kstep": 90.0}},
        sha="zzz", fingerprint="0therhardware",
    )
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 0


def test_unfingerprinted_run_is_report_only(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100, 100, 100, 100], fingerprint="")
    led.close()
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 0


def test_wallclock_swing_never_gates(tmp_path):
    p = str(tmp_path / "perf.db")
    led = perf.PerfLedger.open(p)
    seed(led, [100, 100, 100, 50], metric="positions_per_s")
    led.close()
    report = None
    assert perf_report.main(
        ["--ledger", p, "--check", "--no-backfill"]) == 0
    led = perf.PerfLedger.open(p)
    report = perf_report.evaluate(led)
    led.close()
    (row,) = report["rows"]
    assert row["status"] == "regression" and not row["gated"]


def test_check_passes_on_unmodified_repo(tmp_path):
    """Acceptance: a fresh ledger built from the checked-in artifacts
    gates nothing (backfilled history has no fingerprint)."""
    p = str(tmp_path / "fresh.db")
    assert perf_report.main(["--ledger", p, "--check"]) == 0


# ------------------------------------------------------------------- costs


def test_program_cost_cpu_smoke():
    jax = pytest.importorskip("jax")
    jnp = jax.numpy

    def f(x):
        return (x @ x).sum()

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((16, 16), jnp.float32)).compile()
    cost = perf.program_cost(compiled)
    assert cost.get("flops", 0.0) > 0
    reg = obs_metrics.MetricsRegistry()
    recorded = perf.record_program_cost("run_segment!", compiled,
                                        registry=reg)
    assert recorded
    snap = reg.snapshot()
    assert snap["fishnet_program_flops_run_segment"] > 0


def test_build_info_gauge_renders():
    reg = obs_metrics.MetricsRegistry()
    info = perf.register_build_info(registry=reg)
    assert "git_sha" in info
    text = reg.render_prometheus()
    assert "fishnet_build_info 1" in text
    assert f"git_sha={info['git_sha']}" in text


def test_live_snapshot_shape():
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("fishnet_lanes_live").set(3)
    snap = perf.live_snapshot(registry=reg, ledger_path=":memory:")
    assert snap["build"]
    assert snap["metrics"] == {"fishnet_lanes_live": 3.0}
    assert "fingerprint" in snap and "programs" in snap
