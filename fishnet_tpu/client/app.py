"""Application core: wires config, queue, workers, engines, and signals.

Parity with the reference's orchestrator (reference: src/main.rs:44-261):
N workers, graceful SIGINT (second SIGINT aborts), SIGTERM immediate, the
120 s summary line, background auto-update every 5 h, CPU priority, and
abort-on-shutdown of pending batches.
"""
from __future__ import annotations

import asyncio
import json
import os
import signal
from dataclasses import asdict
from pathlib import Path
from typing import Optional, Set

from ..engine.base import NoAcceleratorError
from ..engine.pyengine import PyEngine
from ..obs import metrics as obs_metrics
from ..obs import perf as obs_perf
from ..obs import trace as obs_trace
from ..utils import settings
from .api import ApiClient, ApiError, Endpoint
from .configure import Config
from .logger import Logger
from .queue import BacklogOpt, Queue
from .stats import StatsRecorder
from .update import auto_update, restart_process
from .wire import EngineFlavor
from .workers import worker

SUMMARY_INTERVAL_S = 120.0  # reference: src/main.rs:202-214
UPDATE_INTERVAL_S = 5 * 3600.0  # reference: src/main.rs:180-200


async def _http_get(url: str) -> bytes:
    import urllib.request

    def fetch() -> bytes:
        with urllib.request.urlopen(url, timeout=30.0) as r:
            return r.read()

    return await asyncio.to_thread(fetch)


def tpu_variants_for(cfg: Config) -> Optional[Set[str]]:
    if cfg.backend != "tpu":
        return None
    # all seven lichess variants run on device
    # (engine/tpu.py DEVICE_VARIANTS; ops/ variant static flags)
    return {
        "standard", "chess960", "fromPosition", "threeCheck", "crazyhouse",
        "antichess", "atomic", "horde", "kingOfTheHill", "racingKings",
    }


def make_engine_factory(cfg: Config, logger: Logger, stats=None):
    tpu_engine = None

    def factory(flavor: EngineFlavor):
        nonlocal tpu_engine
        if cfg.fleet:
            # fleet mode: every flavor feeds the one coordinator
            # (fishnet_tpu/fleet/) — it spreads position work over N
            # members (supervised host children here, or remote serve
            # endpoints) behind the same Engine protocol, so workers,
            # serve and bench need no other change
            if tpu_engine is None:
                from ..fleet import FleetCoordinator
                from ..fleet.member import (
                    make_local_member,
                    members_from_specs,
                )

                # the engine host child speaks --backend tpu|py; the
                # CLI's "python" backend maps to its "py"
                backend = (
                    "py" if cfg.backend == "python"
                    else "tpu"
                )

                def local_factory(name: str):
                    return make_local_member(
                        name,
                        backend=backend,
                        weights_path=cfg.tpu_weights,
                        weights_small_path=cfg.tpu_weights_small,
                        max_depth=cfg.tpu_depth,
                        helper_lanes=cfg.tpu_helpers,
                        refill=cfg.tpu_refill,
                        mesh_refill=cfg.tpu_mesh_refill,
                        logger=logger,
                        stats_recorder=stats,
                    )

                tpu_engine = FleetCoordinator(
                    members_from_specs(
                        cfg.fleet_members,
                        local_factory=local_factory,
                        logger=logger,
                    ),
                    logger=logger,
                    # runtime membership (POST /fleet/members, fleet-ctl):
                    # an added 'local' member builds through the same
                    # Config-closed factory as the boot-time ones
                    local_factory=local_factory,
                )
            return tpu_engine
        if flavor is EngineFlavor.TPU:
            if tpu_engine is None:
                if cfg.supervisor:
                    # device work runs in a killable child process behind
                    # the supervisor proxy (engine/supervisor.py): a wedged
                    # device gets SIGKILLed and respawned instead of
                    # wedging this process's executor threads forever
                    from ..engine.supervisor import SupervisedEngine

                    tpu_engine = SupervisedEngine(
                        backend="tpu",
                        weights_path=cfg.tpu_weights,
                        weights_small_path=cfg.tpu_weights_small,
                        max_depth=cfg.tpu_depth,
                        helper_lanes=cfg.tpu_helpers,
                        refill=cfg.tpu_refill,
                        mesh_refill=cfg.tpu_mesh_refill,
                        logger=logger,
                        replay=cfg.tpu_replay,
                        bisect_max=cfg.tpu_bisect_max,
                        quarantine=cfg.tpu_quarantine,
                        stats_recorder=stats,
                    )
                else:
                    from ..engine.tpu import TpuEngine

                    tpu_engine = TpuEngine(
                        weights_path=cfg.tpu_weights,
                        weights_small_path=cfg.tpu_weights_small,
                        max_depth=cfg.tpu_depth,
                        helper_lanes=cfg.tpu_helpers,
                        refill=cfg.tpu_refill,
                        mesh_refill=cfg.tpu_mesh_refill,
                        logger=logger,
                    )
            # one device program (or supervised child) shared by all
            # workers; workers close() it on drop — SupervisedEngine
            # stays reusable across close(), preserving breaker state
            return tpu_engine
        if cfg.backend == "subprocess" or cfg.engine_path or cfg.variant_engine_path:
            from ..engine.uci import UciEngine

            path = (
                cfg.engine_path
                if flavor is EngineFlavor.OFFICIAL
                else (cfg.variant_engine_path or cfg.engine_path)
            )
            if path:
                return UciEngine(path, logger=logger, flavor=flavor)
        return PyEngine()

    # non-creating accessor: the summary loop exports SupervisorStats
    # without forcing an engine (and its warmup) into existence
    factory.peek_tpu = lambda: tpu_engine
    return factory


async def run(cfg: Config) -> int:
    logger = Logger(verbose=cfg.verbose)
    logger.headline(f"fishnet-tpu starting ({cfg.cores} cores, backend={cfg.backend})")

    bucket_url = settings.get_str("FISHNET_TPU_UPDATE_URL")
    if cfg.auto_update:
        # startup check (reference: src/main.rs:50-68): update THEN exec a
        # fresh process so work starts on the new version
        try:
            new_version = await auto_update(_http_get, bucket_url, logger)
        except Exception as e:
            logger.warn(f"Auto-update check failed: {e}")
            new_version = None
        if new_version:
            logger.headline(f"Updated to {new_version}; restarting ...")
            restart_process()

    if cfg.cpu_priority == "min":
        try:
            os.nice(19)  # reference: src/main.rs:163-171
        except OSError:
            pass

    api = ApiClient(
        Endpoint(cfg.endpoint),
        cfg.resolved_key(),
        logger=logger,
        max_backoff_s=cfg.max_backoff,
    )
    stats = StatsRecorder(
        stats_file=Path(cfg.stats_file) if cfg.stats_file else None,
        no_stats_file=cfg.no_stats_file,
        db_file=Path("stats.db") if not cfg.no_stats_file else None,
        cores=cfg.cores,
    )
    # observability opt-ins: the client-side trace ring (the supervisor
    # merges the engine host's spans into it and dumps it as the flight
    # recorder) and the Prometheus text endpoint on loopback
    if obs_trace.RECORDER is None:
        obs_trace.install_from_settings("client")
    metrics_server = obs_metrics.serve_from_settings()
    if metrics_server is not None:
        logger.info(
            "Serving metrics at "
            f"http://127.0.0.1:{metrics_server.server_address[1]}/metrics"
        )
    queue = Queue(
        api,
        cores=cfg.cores,
        backlog=BacklogOpt(user=cfg.user_backlog, system=cfg.system_backlog),
        stats=stats,
        logger=logger,
        tpu_variants=tpu_variants_for(cfg),
        # play jobs ride the TPU engine too (skill semantics in
        # engine/tpu.py _move_job; reference runs them on the bundled
        # MultiVariant engine, src/queue.rs:562-568)
        tpu_moves=cfg.backend == "tpu",
        max_backoff_s=cfg.max_backoff,
    )

    loop = asyncio.get_running_loop()
    sigint_count = 0
    hard_stop = asyncio.Event()

    def on_sigint():
        nonlocal sigint_count
        sigint_count += 1
        if sigint_count == 1:
            logger.headline("Stopping after pending batches (press ^C again to abort)")
            queue.stop_acquiring()
        else:
            logger.headline("Aborting pending batches ...")
            hard_stop.set()

    def on_sigterm():
        hard_stop.set()

    # install handlers BEFORE the (slow) warmup: ^C during the first XLA
    # compile must not dump a KeyboardInterrupt traceback
    try:
        loop.add_signal_handler(signal.SIGINT, on_sigint)
        loop.add_signal_handler(signal.SIGTERM, on_sigterm)
    except NotImplementedError:
        pass  # non-unix

    factory = make_engine_factory(cfg, logger, stats=stats)

    def mirror_supervisor() -> None:
        """SupervisorStats → the SQLite sink and the metrics registry
        (fishnet_supervisor_*): one interface over the scattered
        counter piles, so quarantines/replays are visible next to
        occupancy (tools/occupancy_report.py --stats-db)."""
        eng = factory.peek_tpu()
        if eng is not None and hasattr(eng, "stats"):
            sup = asdict(eng.stats)
            stats.record_supervisor(sup)
            obs_metrics.REGISTRY.absorb_totals("fishnet_supervisor", sup)

    if cfg.backend == "tpu":
        # pay the XLA compile cost now, before any chunk deadline ticks.
        # Three attempts stand for faults that can pass (a flaky device
        # at start-up); an engine that never comes up is fatal — going
        # on would serve every chunk from the supervisor's CPU fallback
        # under the TPU's name
        logger.info("Warming up TPU engine (compiling search program) ...")
        boot_error: Optional[Exception] = None
        for attempt in range(3):
            try:
                engine = factory(EngineFlavor.TPU)
                if cfg.fleet:
                    # members spawn concurrently; one that fails to come
                    # up cools down instead of failing the fleet
                    await engine.start()
                    logger.info("Fleet coordinator ready.")
                elif cfg.supervisor:
                    # the child owns the device: its warmup (and the
                    # background variant compiles, engine/host.py) runs
                    # under heartbeat watch rather than a fixed timeout
                    await engine.start()
                    logger.info("Supervised TPU engine host ready.")
                else:
                    await asyncio.to_thread(engine.warmup, None, logger.info)
                    logger.info("TPU engine ready (all lane buckets compiled).")
                    from ..aot import registry as aot_registry

                    if aot_registry.warm_covers("variants"):
                        # same skip as engine/host.py: compiling would
                        # silently mask AOT bundle misses
                        logger.info(
                            "Variant programs preloaded from AOT bundle."
                        )
                    else:
                        # variant programs compile in the background;
                        # dispatches interleave behind the engine lock, so
                        # standard chunks flow immediately while variant
                        # chunks stop racing their deadlines within the
                        # first few minutes
                        asyncio.ensure_future(
                            asyncio.to_thread(
                                engine.warmup_variants, logger.info
                            )
                        )
                boot_error = None
                break
            except NoAcceleratorError as e:
                boot_error = e  # no retry cures a missing accelerator
                break
            except Exception as e:
                boot_error = e
                logger.warn(f"TPU warmup attempt {attempt + 1} failed: {e}")
                if attempt < 2:
                    await asyncio.sleep(5.0)
        if boot_error is not None:
            logger.error(f"TPU engine did not come up: {boot_error}")
            eng = factory.peek_tpu()
            if eng is not None:
                await eng.close()
            stats.close()
            return 1
    # after the engine is up: the device fields come from the host's
    # ready frame (or the in-process engine), never from JAX here
    obs_perf.register_build_info()
    mirror_supervisor()
    tasks = [
        asyncio.ensure_future(worker(i, queue, factory, logger))
        for i in range(cfg.cores)
    ]

    async def summary_loop():
        while True:
            await asyncio.sleep(SUMMARY_INTERVAL_S)
            logger.info(queue.stats_summary())
            # recovery counters ride the same cadence
            mirror_supervisor()
            # fold the registry into the sqlite time series on the same
            # cadence as the summary line
            stats.record_metrics(obs_metrics.REGISTRY.snapshot())

    summary = asyncio.ensure_future(summary_loop())

    restart_after_drain = False

    async def update_loop():
        # 5-hourly background check (reference: src/main.rs:180-200): on a
        # new release, stop acquiring, let pending batches drain, restart
        nonlocal restart_after_drain
        while True:
            await asyncio.sleep(UPDATE_INTERVAL_S)
            if not cfg.auto_update:
                continue
            try:
                new_version = await auto_update(_http_get, bucket_url, logger)
            except Exception as e:
                logger.warn(f"Auto-update check failed: {e}")
                continue
            if new_version:
                logger.headline(
                    f"Updated to {new_version}; finishing pending batches "
                    "before restart ..."
                )
                restart_after_drain = True
                queue.stop_acquiring()
                return

    updater = asyncio.ensure_future(update_loop())

    stopper = asyncio.ensure_future(hard_stop.wait())
    done, _ = await asyncio.wait(
        tasks + [stopper], return_when=asyncio.FIRST_COMPLETED
    )
    if stopper in done:
        await queue.shutdown()
    await asyncio.gather(*tasks, return_exceptions=True)
    stopper.cancel()
    summary.cancel()
    updater.cancel()
    await queue.shutdown()
    await queue.drain_submissions()
    # once more, so a run shorter than one summary interval still
    # leaves its final counters behind
    mirror_supervisor()
    sup = stats.last_supervisor
    if sup:
        logger.info(f"Supervisor counters: {json.dumps(sup, sort_keys=True)}")
    eng = factory.peek_tpu()
    if eng is not None:
        # workers close the engine they used; one that was started and
        # never used would otherwise be left to die with the event loop
        await eng.close()
    stats.close()
    if restart_after_drain:
        logger.headline("Restarting into the updated version ...")
        restart_process()  # exec: replaces this process (src/main.rs:399-425)
    logger.headline("Bye.")
    return 0


def _sync_check_key(endpoint: str, key: str) -> bool:
    """Online key validation for the first-run dialog (reference:
    src/configure.rs:487-498 spawns an ApiActor just for check_key)."""
    try:
        api = ApiClient(Endpoint(endpoint), key, logger=Logger(verbose=0))
        return asyncio.run(api.check_key())
    except (ApiError, OSError):
        return True  # network trouble: accept and let `run` find out


def run_inflight(cfg: Config) -> int:
    """`fishnet-tpu inflight`: one-shot view of what a running serve
    process is doing RIGHT NOW — GET /debug/requests rendered as a
    table (stage, lanes, age, deadline slack per in-flight request)."""
    import json
    import urllib.error
    import urllib.request

    host = cfg.serve_host or settings.get_str("FISHNET_TPU_SERVE_HOST")
    port = (
        cfg.serve_port if cfg.serve_port is not None
        else settings.get_int("FISHNET_TPU_SERVE_PORT")
    )
    url = f"http://{host}:{port}/debug/requests"
    try:
        with urllib.request.urlopen(url, timeout=5.0) as r:
            payload = json.loads(r.read().decode("utf-8"))
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"inflight: cannot reach {url}: {e}")
        return 1
    reqs = payload.get("requests") or []
    print(f"{len(reqs)} request(s) in flight at {host}:{port}")
    if not reqs:
        return 0
    cols = ("trace_id", "id", "tenant", "kind", "stage", "pos", "lanes",
            "age_ms", "slack_ms")
    rows = []
    for e in reqs:
        done = sum(
            1 for p in (e.get("positions") or {}).values()
            if p.get("stage") in ("delivered", "done")
        )
        slack = e.get("slack_ms")
        rows.append((
            str(e.get("trace_id", "")), str(e.get("id", "")),
            str(e.get("tenant", "")), str(e.get("kind", "")),
            str(e.get("stage", "")),
            f"{done}/{e.get('n_positions', 0)}",
            ",".join(str(x) for x in e.get("lanes") or []) or "-",
            str(e.get("age_ms", "")),
            str(slack) if slack is not None else "-",
        ))
    widths = [
        max(len(c), *(len(r[i]) for r in rows))
        for i, c in enumerate(cols)
    ]
    print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for r in rows:
        print("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    return 0


def run_perf(cfg: Config) -> int:
    """`fishnet-tpu perf`: the performance surface in one screen — GET
    /debug/perf from a running serve process (build info, program cost
    table, perf counters, last ledger baseline), falling back to this
    process's own view when no server is up (build info + the local
    ledger; program costs need a live process that compiled
    something)."""
    import json
    import urllib.error
    import urllib.request

    host = cfg.serve_host or settings.get_str("FISHNET_TPU_SERVE_HOST")
    port = (
        cfg.serve_port if cfg.serve_port is not None
        else settings.get_int("FISHNET_TPU_SERVE_PORT")
    )
    url = f"http://{host}:{port}/debug/perf"
    source = url
    try:
        with urllib.request.urlopen(url, timeout=5.0) as r:
            snap = json.loads(r.read().decode("utf-8"))
    except (urllib.error.URLError, OSError, ValueError):
        source = "local (no serve process reachable)"
        snap = obs_perf.live_snapshot()

    print(f"perf: {source}")
    build = snap.get("build") or {}
    if build:
        print("build: " + " ".join(
            f"{k}={build[k]}" for k in sorted(build)))
    fp = snap.get("fingerprint")
    print(f"env fingerprint: {fp or '(no AOT store fingerprint)'}")

    programs = snap.get("programs") or {}
    if programs:
        print("\nprogram cost (cost_analysis/memory_analysis at compile):")
        cols = ("program", "flops", "bytes_accessed", "peak_bytes")
        rows = [
            (name,
             *(f"{costs[c]:.3e}" if c in costs else "-"
               for c in cols[1:]))
            for name, costs in sorted(programs.items())
        ]
        widths = [
            max(len(c), *(len(r[i]) for r in rows))
            for i, c in enumerate(cols)
        ]
        print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
        for r in rows:
            print("  ".join(v.ljust(w) for v, w in zip(r, widths)))

    metrics = snap.get("metrics") or {}
    if metrics:
        print("\ncounters:")
        for name in sorted(metrics):
            print(f"  {name} = {metrics[name]:g}")
    ratio = snap.get("cache_hit_ratio")
    if ratio is not None:
        print(f"  cache hit ratio = {ratio:.2%}")

    baseline = snap.get("baseline")
    if baseline:
        print(
            f"\nledger baseline: run {baseline.get('run_id')} "
            f"(seq {baseline.get('seq')}, source "
            f"{baseline.get('source')}, sha {baseline.get('git_sha')}, "
            f"fingerprint {baseline.get('fingerprint') or '-'})"
        )
        for bench_row, metrics_row in sorted(
                (baseline.get("rows") or {}).items()):
            for metric, value in sorted(metrics_row.items()):
                print(f"  {bench_row}.{metric} = {value:g}")
    else:
        print("\nledger baseline: (empty — run bench.py to seed it)")
    return 0


def run_fleet_ctl(cfg: Config) -> int:
    """`fishnet-tpu fleet-ctl [list | add SPEC | drain NAME | remove
    NAME]`: runtime membership against a running fleet front-end's
    /fleet/members admin surface (--serve-host/--serve-port pick the
    target). `drain` + `remove` + `add` is a zero-loss rolling restart
    (docs/fleet.md). `--json` makes `list` print the raw health payload
    (machine-readable; scripts and the autoscaling runbook use it)."""
    import json
    import urllib.error
    import urllib.request

    host = cfg.serve_host or settings.get_str("FISHNET_TPU_SERVE_HOST")
    port = (
        cfg.serve_port if cfg.serve_port is not None
        else settings.get_int("FISHNET_TPU_SERVE_PORT")
    )
    url = f"http://{host}:{port}/fleet/members"
    sub = list(cfg.extra_args) or ["list"]
    action, operand = sub[0], (sub[1] if len(sub) > 1 else None)
    if action in ("add", "drain", "remove") and operand is None:
        print(f"fleet-ctl: {action} needs an argument "
              "(add SPEC / drain NAME / remove NAME)")
        return 2
    try:
        if action == "list":
            req = urllib.request.Request(url, method="GET")
        elif action in ("add", "drain", "remove"):
            body = {"action": action}
            body["spec" if action == "add" else "member"] = operand
            req = urllib.request.Request(
                url, method="POST", data=json.dumps(body).encode("utf-8"),
                headers={"Content-Type": "application/json"},
            )
        else:
            print(f"fleet-ctl: unknown action {action!r} "
                  "(use list / add / drain / remove)")
            return 2
        with urllib.request.urlopen(req, timeout=30.0) as r:
            payload = json.loads(r.read().decode("utf-8"))
    except urllib.error.HTTPError as e:
        try:
            detail = json.loads(e.read().decode("utf-8")).get("error", "")
        except (ValueError, OSError):
            detail = ""
        print(f"fleet-ctl: {url} answered HTTP {e.code}: {detail}")
        return 1
    except (urllib.error.URLError, OSError, ValueError) as e:
        print(f"fleet-ctl: cannot reach {url}: {e}")
        return 1
    if action != "list":
        print(json.dumps(payload, indent=2))
        return 0
    if cfg.json_output:
        print(json.dumps(payload, indent=2, sort_keys=True))
        return 0
    members = payload.get("members") or []
    print(
        f"{len(members)} member(s), {payload.get('members_live', 0)} "
        f"live; losses={payload.get('losses', 0)} "
        f"readmissions={payload.get('readmissions', 0)} "
        f"hedges={payload.get('hedges', 0)}"
    )
    cols = ("name", "kind", "state", "backlog", "inflight", "losses",
            "cooldown_s")
    rows = [
        tuple(str(m.get(c, "")) for c in cols) for m in members
    ]
    widths = [
        max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
        for i, c in enumerate(cols)
    ]
    print("  ".join(c.ljust(w) for c, w in zip(cols, widths)))
    for r in rows:
        print("  ".join(v.ljust(w) for v, w in zip(r, widths)))
    return 0


def main(argv=None) -> int:
    from .configure import parse_and_configure
    from .systemd import system_unit, user_unit

    cfg = parse_and_configure(argv, check_key=_sync_check_key)
    if cfg.command == "license":
        print("fishnet-tpu is free software distributed under GPLv3+ terms,")
        print("matching the licensing of the fishnet protocol ecosystem.")
        return 0
    if cfg.command == "systemd":
        print(system_unit(cfg))
        return 0
    if cfg.command == "systemd-user":
        print(user_unit(cfg))
        return 0
    if cfg.command == "bench":
        import runpy
        import sys as _sys

        runpy.run_path(
            str(Path(__file__).resolve().parents[2] / "bench.py"),
            run_name="__main__",
        )
        return 0
    if cfg.command in ("pack", "warm"):
        # AOT program assets (fishnet_tpu/aot/): `pack` compiles and
        # serializes every hot search program into a bundle; `warm`
        # installs a bundle so the next boot loads instead of compiling
        from ..aot.pack import main_pack, main_warm

        return main_pack(cfg) if cfg.command == "pack" else main_warm(cfg)
    if cfg.command == "fleet-ctl":
        # runtime fleet membership against a running front-end
        # (fleet/coordinator.py + serve /fleet/members admin surface)
        return run_fleet_ctl(cfg)
    if cfg.command == "inflight":
        # live in-flight introspection against a running serve process
        # (obs/inflight.py; --serve-host/--serve-port pick the target)
        return run_inflight(cfg)
    if cfg.command == "perf":
        # build info, program cost table, and the perf-ledger baseline
        # (obs/perf.py; reaches a serve process's /debug/perf if up)
        return run_perf(cfg)
    if cfg.command in ("serve", "fleet"):
        # the analysis-serving front-end (fishnet_tpu/serve/): many
        # concurrent HTTP tenants multiplex into the same lane pool the
        # lichess client feeds. `fleet` is serve with the coordinator
        # forced on (cfg.fleet, set by parse): one HTTP front door over
        # N engine hosts
        from ..serve.server import run_serve

        return asyncio.run(run_serve(cfg))
    if cfg.command == "configure":
        return 0  # parse_and_configure already ran the dialog
    return asyncio.run(run(cfg))
