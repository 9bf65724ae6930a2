"""One process owns the chip, and nothing hides which device did the work.

Small tier-1 tests for the bring-up rules (none compiles a search
program): the supervised parent never touches JAX; the engine host's
`ready` frame carries the device and the supervisor keeps it; `--backend
tpu` refuses an un-asked-for CPU backend and the refusal is not retried;
the compile cache is placed from outside; chip_smoke.py's last line.
"""
import asyncio
import json
import subprocess
import sys
from pathlib import Path

import pytest

from fishnet_tpu.client.logger import Logger
from fishnet_tpu.engine import base as engine_base
from fishnet_tpu.engine import host as engine_host
from fishnet_tpu.engine.base import (
    EXIT_NO_ACCELERATOR,
    EngineError,
    NoAcceleratorError,
    require_accelerator,
)
from fishnet_tpu.engine.supervisor import SupervisedEngine
from fishnet_tpu.obs import perf
from fishnet_tpu.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]
TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def perf_state(monkeypatch):
    """obs/perf.py's recorded device, isolated from the rest of the run."""
    monkeypatch.setattr(perf, "_device_report", None)
    monkeypatch.setattr(perf, "_build_info_cache", None)
    monkeypatch.setattr(perf, "_owns_device", False)


# ------------------------------------------------ the parent stays off JAX


def test_supervised_parent_never_imports_jax():
    """Importing everything a supervised client or serve parent runs,
    and asking for build info the way they do, must not even import
    jax — let alone initialise a backend, which on a local chip would
    lock the engine host child out of it."""
    code = (
        "import sys\n"
        "import fishnet_tpu.client.app, fishnet_tpu.serve.server\n"
        "import fishnet_tpu.engine.supervisor, fishnet_tpu.fleet\n"
        "from fishnet_tpu.obs import perf\n"
        "info = perf.register_build_info()\n"
        "assert info['jax'] and info['backend'] == '', info\n"
        "assert perf.env_fingerprint() == ''\n"
        "perf.live_snapshot(ledger_path=':memory:')\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=str(REPO), text=True,
        capture_output=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_build_info_takes_the_device_from_the_ready_frame(perf_state):
    assert perf.build_info()["backend"] == ""
    perf.note_device(TPU)
    info = perf.build_info()
    assert (info["backend"], info["device_kind"], info["device_count"]) == (
        "tpu", "TPU v5 lite", 1)
    # a parent that only noted a report has not claimed the device
    assert perf.env_fingerprint() == ""


# ------------------------------------------- ready frame → supervisor


def _fakehost_cmd(script: dict):
    return [sys.executable, "-m", "fishnet_tpu.engine.fakehost",
            "--script", json.dumps(script), "--hb-interval", "0.05"]


def test_ready_frame_device_round_trips_through_the_supervisor(perf_state):
    lines = []

    class Capture(Logger):
        def info(self, text):
            lines.append(text)

    async def go():
        eng = SupervisedEngine(
            _fakehost_cmd({"device": TPU, "boot": ["ready"]}),
            hb_interval=0.05, hb_timeout=5.0, logger=Capture(verbose=0))
        try:
            await eng.start()
            return eng.device, eng.stats.spawns
        finally:
            await eng.close()

    device, spawns = asyncio.run(go())
    assert device == TPU and spawns == 1
    # the parent's build info now names the CHILD's device
    assert perf.build_info()["backend"] == "tpu"
    # and the ready line is machine-readable (chip_smoke.py parses it)
    ready = [ln for ln in lines if "ready on device" in ln]
    assert ready and json.loads(ready[0].split("device ", 1)[1]) == TPU


def test_host_without_a_device_reports_none(perf_state):
    async def go():
        eng = SupervisedEngine(
            _fakehost_cmd({"boot": ["ready"]}),
            hb_interval=0.05, hb_timeout=5.0, logger=Logger(verbose=0))
        try:
            await eng.start()
            return eng.device
        finally:
            await eng.close()

    assert asyncio.run(go()) is None
    assert perf.build_info()["backend"] == ""


# ------------------------------------------------------- no silent CPU


@pytest.mark.parametrize("platform,env,refused", [
    ("cpu", None, True),
    ("cpu", "", True),
    ("cpu", "cpu", False),
    ("cpu", "cpu,tpu", False),
    ("cpu", "tpu,cpu", True),
    ("tpu", None, False),
    ("tpu", "cpu", False),
])
def test_require_accelerator(monkeypatch, platform, env, refused):
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    if refused:
        with pytest.raises(NoAcceleratorError, match="JAX_PLATFORMS=cpu"):
            require_accelerator(platform)
    else:
        require_accelerator(platform)


def test_host_refuses_an_unasked_for_cpu_backend(monkeypatch, perf_state):
    """`--backend tpu` where JAX came up on the CPU by itself: the boot
    fails before anything is built — no mesh, no table, no scheduler."""
    from fishnet_tpu.engine import tpu as tpu_mod

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        perf, "claim_device",
        lambda: {"platform": "cpu", "kind": "cpu", "count": 1})

    def built(*a, **kw):
        raise AssertionError("the engine was built on the refused backend")

    monkeypatch.setattr(tpu_mod, "LaneScheduler", built)
    monkeypatch.setattr("fishnet_tpu.parallel.mesh.make_mesh", built)
    monkeypatch.setattr("fishnet_tpu.ops.tt.make_table", built)
    args = type("Args", (), dict(
        backend="tpu", weights=None, weights_small=None, depth=2,
        helpers=None, refill=None, mesh_refill=None, skip_warmup=True))()
    with pytest.raises(NoAcceleratorError):
        engine_host._build_engine(args, lambda msg: None)
    # asked for, the same boot goes through to the (stubbed) build
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(AssertionError, match="refused backend"):
        engine_host._build_engine(args, lambda msg: None)


def _exit_cmd(status: int, last_words: str):
    code = (
        "import sys\n"
        "from fishnet_tpu.engine.frames import write_frame\n"
        f"write_frame(sys.stdout.buffer, {{'t': 'log', 'msg': {last_words!r}}})\n"
        f"sys.exit({status})\n"
    )
    return [sys.executable, "-c", code]


@pytest.mark.parametrize("status,exc", [
    (EXIT_NO_ACCELERATOR, NoAcceleratorError),
    (1, EngineError),
])
def test_refused_boot_surfaces_as_its_own_error(status, exc):
    """The host's exit status tells a refused boot (no retry cures it)
    from a fault that may pass, and the error carries the child's last
    words so the client can say which error it was."""
    async def go():
        eng = SupervisedEngine(
            _exit_cmd(status, "boot refused: no accelerator here"),
            hb_interval=0.05, hb_timeout=5.0, logger=Logger(verbose=0))
        try:
            await eng.start()
        finally:
            await eng.close()

    with pytest.raises(EngineError) as err:
        asyncio.run(go())
    assert type(err.value) is exc
    assert f"status {status}" in str(err.value)
    assert "no accelerator here" in str(err.value)


def test_client_exits_nonzero_when_the_host_refuses_the_boot(monkeypatch):
    """client/app.py: a refused boot is not retried and is not "a cold
    engine" — the client says which error it was and returns non-zero
    instead of serving from the breaker's CPU engine."""
    from fishnet_tpu.client import app
    from fishnet_tpu.client.configure import Config

    starts = []

    class Refusing:
        async def start(self):
            starts.append(1)
            raise NoAcceleratorError("backend 'tpu' found no accelerator")

        async def close(self):
            pass

    def factory_for(cfg, logger, stats=None):
        eng = Refusing()

        def factory(flavor):
            return eng

        factory.peek_tpu = lambda: eng
        return factory

    errors = []
    monkeypatch.setattr(app, "make_engine_factory", factory_for)
    monkeypatch.setattr(Logger, "error", lambda self, t: errors.append(t))
    cfg = Config(endpoint="http://127.0.0.1:9/fishnet", key="k",
                 backend="tpu", cores=1, no_stats_file=True,
                 auto_update=False)
    assert asyncio.run(app.run(cfg)) == 1
    assert starts == [1], "a refused boot must not be retried"
    assert errors and "no accelerator" in errors[-1]


def test_bench_fails_without_a_device(monkeypatch, capsys):
    """bench.py: no CPU fallback stages and no zero-valued success line —
    no device, or no stage that ran, is a non-zero exit with the reason
    on stderr and nothing on stdout."""
    import types

    import bench

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        bench.subprocess, "run",
        lambda *a, **kw: types.SimpleNamespace(
            returncode=0, stdout="cpu\n", stderr=""))
    assert "no accelerator" in bench.device_preflight()
    stages = []
    monkeypatch.setattr(
        bench, "run_stage", lambda *a, **kw: stages.append(a))
    with pytest.raises(SystemExit) as no_device:
        bench.main()
    assert "no device to measure" in str(no_device.value.code)
    assert stages == []
    # the CPU, where it was asked for, is a device like any other ...
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.device_preflight() == ""
    # ... but stages that all die are not a result
    with pytest.raises(SystemExit) as no_stage:
        bench.main()
    assert "no device stage produced a result" in str(no_stage.value.code)
    assert stages, "the ramp should have been tried"
    assert capsys.readouterr().out == ""


# ------------------------------------------------------- compile cache


@pytest.fixture
def cache_calls(monkeypatch):
    """enable_compile_cache() from a clean module state, with every
    jax.config.update recorded instead of applied (the suite's own
    cache settings stay as conftest made them)."""
    import jax

    calls = {}
    monkeypatch.setattr(compile_cache, "_enabled_path", None)
    monkeypatch.setattr(compile_cache, "_force_disabled", False)
    monkeypatch.setattr(compile_cache, "_reset_cache_memo", lambda: None)
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv("FISHNET_TPU_NO_COMPILE_CACHE", raising=False)
    return calls


def test_cache_placed_from_outside_sets_no_directory(monkeypatch, tmp_path,
                                                     cache_calls):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    assert compile_cache.enable_compile_cache() == tmp_path / "xla"
    assert "jax_compilation_cache_dir" not in cache_calls
    # no sub-directory is appended, and nothing is created in code
    assert not (tmp_path / "xla").exists()
    # it may still lower the two persistence thresholds
    assert cache_calls == {
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }


def test_cache_defaults_to_one_fixed_path_in_the_checkout(monkeypatch,
                                                          cache_calls):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == REPO / ".cache" / "xla" == compile_cache.DEFAULT_CACHE_DIR
    assert cache_calls["jax_compilation_cache_dir"] == str(got)
    # idempotent, and the same path every time: it is part of jax's key
    assert compile_cache.enable_compile_cache() == got


def test_cache_off_switch(monkeypatch, cache_calls):
    monkeypatch.setenv("FISHNET_TPU_NO_COMPILE_CACHE", "1")
    assert compile_cache.enable_compile_cache() is None
    assert cache_calls == {}


# ----------------------------------------------- chip_smoke.py's last line


def test_chip_smoke_last_line():
    import chip_smoke

    ok = chip_smoke.last_line(True, dict(TPU, extra="dropped"))
    assert ok == ('{"ok": true, "device": {"platform": "tpu", '
                  '"kind": "TPU v5 lite", "count": 1}}')
    bad = json.loads(chip_smoke.last_line(False, None, ["host_ready"]))
    assert bad == {"ok": False, "device": None, "failed": ["host_ready"]}
    cpu = json.loads(chip_smoke.last_line(
        False, {"platform": "cpu", "kind": "cpu", "count": 1},
        ["device_is_tpu"]))
    assert cpu["ok"] is False and cpu["device"]["platform"] == "cpu"


def test_chip_smoke_refuses_a_cpu_environment():
    """Run as the driver runs it, in an environment held to the CPU
    (this sandbox's): non-zero exit, "ok": false, nothing started."""
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], text=True,
        capture_output=True, timeout=60,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"] is None


def test_cpu_asked_for_reads_the_standard_variable(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", " CPU ")
    assert engine_base.cpu_asked_for()
    for names in ("tpu", "tpu,cpu", ""):
        monkeypatch.setenv("JAX_PLATFORMS", names)
        assert not engine_base.cpu_asked_for()
