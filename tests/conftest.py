"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-run-compiles the
multichip path via __graft_entry__.dryrun_multichip). JAX_PLATFORMS=cpu,
set here before the first jax import, keeps every test on XLA:CPU.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# small engine search stack + one warmup bucket: the MAX_PLY=32 production
# program takes minutes to compile on XLA:CPU; engine tests search depth ≤3
os.environ.setdefault("FISHNET_TPU_MAX_PLY", "8")
os.environ.setdefault("FISHNET_TPU_WARMUP_BUCKETS", "16")
# Lazy-SMP helpers off by default under pytest: the production default
# (K=4) widens every engine dispatch ~4x, which XLA:CPU pays in both
# compile and step time across dozens of engine tests. Helper-lane
# behavior is covered explicitly in tests/test_helper_lanes.py, which
# constructs TpuEngine(helper_lanes=...) itself.
os.environ.setdefault("FISHNET_TPU_HELPERS", "1")
# FISHNET_TPU_REFILL is NOT pinned: single-pv analysis chunks run through
# the LaneScheduler, as in every deployment path. A test that means the
# chunk-serial path says so: TpuEngine(refill=False).

# make the package importable regardless of how pytest was invoked; the
# settings registry (pure stdlib, safe before jax) is the single source
# of truth for FISHNET_TPU_* reads — including the one below
import sys as _sys

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from fishnet_tpu.utils import settings  # noqa: E402

# persistent XLA compile cache for the whole suite (VERDICT r4 weak #7:
# the fast tier outgrew its box — XLA:CPU compiles of unchanged search
# programs dominated its wall clock). JAX reads the standard
# JAX_COMPILATION_CACHE_DIR itself and engine subprocesses inherit it, so
# unchanged programs compile once per code change, not once per run. The
# home-directory default keeps the suite's hundreds of XLA:CPU programs
# out of the checkout (utils/compile_cache.py's own default is inside it).
if not settings.get_bool("FISHNET_TPU_NO_COMPILE_CACHE"):
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "fishnet-tpu", "xla"),
    )
    from fishnet_tpu.utils import enable_compile_cache

    enable_compile_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running tests (deep perft, big batches)")
    config.addinivalue_line("markers", "tpu: tests that require a real TPU device")
    config.addinivalue_line(
        "markers",
        "mesh: sharded-scheduler tests that require the 8-device "
        "virtual CPU mesh (XLA_FLAGS=--xla_force_host_platform_"
        "device_count=8, which conftest forces anyway)")
    config.addinivalue_line(
        "markers",
        "subproc: subprocess-heavy integration suites (spawned fakehost/"
        "serve/full-app children); excluded from the fast tier and run "
        "in their own per-commit CI step")


def pytest_collection_modifyitems(config, items):
    # subproc implies slow so BOTH exclusion spellings drop the tier:
    # pytest.ini's addopts (-m "not slow and not tpu") and the roadmap's
    # tier-1 command, which passes -m 'not slow' on the CLI and thereby
    # REPLACES addopts' -m — a bare `-m "... and not subproc"` edit to
    # the ini would not survive that override.
    for item in items:
        if "subproc" in item.keywords:
            item.add_marker(pytest.mark.slow)


class EngineHostPool:
    """Session-scoped pool of supervised fake-engine hosts.

    Every fakehost-backed test pays a fresh interpreter boot per
    SupervisedEngine spawn, and the subproc tier spawns dozens. Tests
    whose script carries no cross-chunk fault state (plain "ok" serving)
    can share one long-lived child instead: the pool owns a private
    event loop on a background thread — SupervisedEngine's reader task
    is bound to the loop it spawned on, so a pooled engine cannot hop
    between the per-test asyncio.run() loops — and caches one engine per
    host command line. `run()` submits a coroutine to the pool loop and
    blocks for its result.

    Tests that assert spawn/death/kill counters or script specific
    faults must keep constructing their own SupervisedEngine: pooled
    stats accumulate across tests by design.
    """

    def __init__(self):
        import asyncio
        import threading

        self._loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self._loop.run_forever, name="engine-host-pool",
            daemon=True)
        self._thread.start()
        self._engines = {}

    def run(self, coro, timeout=120.0):
        import asyncio

        return asyncio.run_coroutine_threadsafe(
            coro, self._loop).result(timeout)

    def get(self, cmd, **kw):
        """Get-or-spawn the pooled SupervisedEngine for a host command
        line. Construction kwargs apply on first use only — callers
        sharing a command line share one incarnation and its settings.
        """
        key = tuple(cmd)
        eng = self._engines.get(key)
        if eng is None:
            from fishnet_tpu.client.logger import Logger
            from fishnet_tpu.engine.supervisor import SupervisedEngine

            kw.setdefault("hb_interval", 0.05)
            kw.setdefault("hb_timeout", 0.6)
            kw.setdefault("deadline_margin", 0.15)
            kw.setdefault("logger", Logger(verbose=0))
            eng = self._engines[key] = SupervisedEngine(list(cmd), **kw)
        return eng

    def close(self):
        async def _close_all():
            for eng in self._engines.values():
                await eng.close()

        self.run(_close_all())
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10.0)


@pytest.fixture(scope="session")
def engine_host_pool():
    pool = EngineHostPool()
    yield pool
    pool.close()
