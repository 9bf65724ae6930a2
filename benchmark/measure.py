"""The harness's contact with the program and with the device.

Everything the benchmark takes from the program is taken here: the entry
(``TpuEngine.go_multiple``), the delivery hook, the occupancy counters, the
program's own spans, and the work types a chunk is made of. The load
generator and the comparison speak only to the adapter below, so a test
can put a stand-in in the program's place.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, List, Optional

import numpy as np


class NoDevice(Exception):
    pass


# CPU rehearsal only: the shrinkers the repo's own tests use
_REHEARSAL_ENV = {
    "JAX_PLATFORMS": "cpu",
    "FISHNET_TPU_MAX_PLY": "8",
    "FISHNET_TPU_WARMUP_VARIANTS": "none",
}


def prepare_environment(rehearsal: Optional[dict],
                        control: Optional[str]) -> None:
    """Set what must be set before the program is imported."""
    # the control is asked for on the command line, never inherited
    os.environ.pop("FISHNET_TPU_DTYPE", None)
    if control == "bf16":
        os.environ["FISHNET_TPU_DTYPE"] = "bf16"
    if rehearsal is not None:
        os.environ.update(_REHEARSAL_ENV)
        os.environ.update(rehearsal.get("env", {}))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def claim_device(chips: int, rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    platform = str(devs[0].platform)
    if platform == "cpu" and not rehearsal:
        raise NoDevice("JAX found no accelerator (platform cpu); a cell is "
                       "measured on the chip or not at all")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX reports {len(devs)}")
    return {"platform": platform, "kind": str(devs[0].device_kind),
            "count": len(devs)}


class CompileCounter:
    """Programs built or loaded, as JAX's monitoring events report them."""

    def __init__(self):
        import jax.monitoring as mon

        self.built = 0  # backend compiles, cache loads included
        self.cache_hits = 0
        self.last_at = time.monotonic()
        self.seconds = 0.0

        def on_duration(event, duration, **_kw):
            if event.endswith("backend_compile_duration"):
                self.built += 1
                self.seconds += float(duration)
                self.last_at = time.monotonic()

        def on_event(event, **_kw):
            if event.endswith("compilation_cache/cache_hits"):
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return {"built": self.built, "cache_hits": self.cache_hits,
                "seconds": self.seconds}


class BoundarySampler(threading.Thread):
    """Notes every change of the scheduler's segment count with the time it
    was seen (to ~1 ms): the boundaries as the host ran them."""

    def __init__(self, counters, period_s: float = 0.001):
        super().__init__(daemon=True)
        self._counters = counters
        self._period = period_s
        self._stop = threading.Event()
        self.records: List[tuple] = []  # (t, segments, steps)

    def read(self, t: Optional[float] = None) -> tuple:
        c = self._counters()
        return (time.monotonic() if t is None else t, c["segments"], c["steps"])

    def run(self):
        last = -1
        while not self._stop.is_set():
            rec = self.read()
            if rec[1] != last:
                last = rec[1]
                self.records.append(rec)
            time.sleep(self._period)

    def stop(self):
        self._stop.set()

    def wait_boundaries(self, n: int, timeout_s: float) -> int:
        start = len(self.records)
        t_end = time.monotonic() + timeout_s
        while len(self.records) < start + n and time.monotonic() < t_end:
            time.sleep(0.002)
        return len(self.records) - start


class DeviceTracer:
    """One profiler slice, taken right after the window closed while the
    loop still runs, so that tracing costs the window's counters nothing.
    A drive session and the gap to the next take seconds, so a slice at a
    fixed time can fall wholly between two sessions: this one starts when
    the scheduler has just passed a boundary (a session is running, and as
    a rule the device waits for the boundary's host work).

    What a slice costs is the device ops it holds (`stop_trace` takes one
    to two minutes a million), and the profiler keeps next to nothing from
    before `start_trace` returned, so the slice is cut by device work: it
    ends at the first boundary at which it holds a whole boundary interval
    and the scheduler has accounted `steps` steps in those it holds, or
    after `max_s`. Where the trace began and every boundary seen inside it
    are its `marks`: `trace_reduce` holds the device's busy time between
    them against what the scheduler logged for the segments between them
    (`segment_log(since, until)` → (segment, steps, blocked ms))."""

    def __init__(self, trace_dir: str, sampler: BoundarySampler, cfg: dict,
                 segment_log: Callable[[int, int], List[tuple]]):
        self.dir = trace_dir
        self.sampler = sampler
        self.cfg = cfg
        self.segment_log = segment_log
        self.captured = threading.Event()  # the slice is over; stop_trace may still run
        self.anchor_mono = None
        self.anchor_mark = None  # the sampler's reading where the trace began
        self.in_flight = None  # was a segment program running when it began?
        self.stop_s = None
        self.slice_s = None
        self.cut = None  # how the slice ended
        self.error = None

    def start_profiler(self) -> float:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.anchor"):
            return time.monotonic()

    def stop_profiler(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def take(self):
        try:
            self.sampler.wait_boundaries(1, 8.0)
            self.anchor_mono = self.start_profiler()
            self.anchor_mark = self.sampler.read(self.anchor_mono)
            t_end = self.anchor_mono + self.cfg["max_s"]
            while True:
                marks = self.marks()
                ran = marks[-1][2] - marks[0][2] if marks else 0
                if len(marks) >= 2 and ran >= self.cfg["steps"]:
                    ended = "work"
                    break
                if time.monotonic() >= t_end:
                    ended = "max_s"
                    break
                time.sleep(0.002)
            self.slice_s = time.monotonic() - self.anchor_mono
            self.cut = {"intervals": max(len(marks) - 1, 0), "steps": ran,
                        "ended_on": ended, "in_flight_at_anchor": self.in_flight}
            self.captured.set()
            t1 = time.monotonic()
            self.stop_profiler()
            self.stop_s = time.monotonic() - t1
        except Exception as e:  # a failed trace fails the metrics, not the run
            self.error = repr(e)
        finally:
            self.captured.set()

    def marks(self) -> List[tuple]:
        """(t, segments, steps) where the trace began and at every boundary
        seen inside the slice: the instants between which whole boundary
        intervals lie. Not the first, where a segment program was in flight
        when the trace began (a speculative one that found live lanes, or
        admissions quicker than the profiler's start): part of it ran
        before, and the wait logged for it is the whole one. The log shows
        that: a wait longer than the trace had run began before it. (One
        launched in the few milliseconds before the anchor passes for
        inside; so much of its run is missed.)"""
        end = float("inf") if self.slice_s is None else self.anchor_mono + self.slice_s
        seen = [r for r in self.sampler.records if self.anchor_mono < r[0] <= end]
        if seen and self.in_flight is None:
            rows = self.segment_log(self.anchor_mark[1], seen[0][1])
            if len(rows) < seen[0][1] - self.anchor_mark[1]:
                return []  # the scheduler has counted the segment and not yet logged it
            blocked_s = sum(ms for _seg, _steps, ms in rows) / 1e3
            self.in_flight = blocked_s > seen[0][0] - self.anchor_mono
        return seen if self.in_flight else [self.anchor_mark] + seen


def program_engine_factory(cell: dict, rehearsal: Optional[dict]):
    """→ make_engine() building the program's engine as the configuration
    runs it. Importing the program happens here, so a checkout that holds
    only the benchmark fails before anything is measured."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.client.ipc import Chunk, WorkPosition
    from fishnet_tpu.client.wire import AnalysisWork, EngineFlavor, NodeLimit
    from fishnet_tpu.engine.tpu import TpuEngine
    from fishnet_tpu.obs import trace as obs_trace

    cfg = dict(cell["config"]["engine"])
    if rehearsal is not None:
        cfg.update(rehearsal.get("engine", {}))
    evaluator = cell["evaluator"]
    weights = evaluator.load_weights(cfg, cell["root"])

    class ProgramAdapter:
        name = "TpuEngine"

        def __init__(self):
            self.engine = TpuEngine(
                params=evaluator.program_params(weights),
                max_depth=cfg["max_depth"],
                tt_size_log2=cfg["tt_size_log2"], max_lanes=cfg["max_lanes"],
                helper_lanes=cfg["helper_lanes"], refill=cfg["refill"],
            )
            self.recorder = None

        def new_chunk(self, work_id, variant, nodes, timeout_s, deadline,
                      root_fen, positions):
            work = AnalysisWork(
                id=work_id, nodes=NodeLimit(sf16=nodes["sf16"],
                                            classical=nodes["classical"]),
                timeout_s=timeout_s, depth=None, multipv=None)
            wps = [WorkPosition(work=work, position_index=idx, url=url,
                                skip=False, root_fen=root_fen, moves=list(mv))
                   for idx, url, mv in positions]
            return Chunk(work=work, deadline=deadline, variant=variant,
                         flavor=EngineFlavor.TPU, positions=wps)

        async def go(self, chunk):
            return await self.engine.go_multiple(chunk)

        def set_deliver_hook(self, fn):
            self.engine.on_deliver = (
                None if fn is None else
                lambda chunk, wp, resp: fn(chunk, wp.position_index, wp.url, resp))

        @staticmethod
        def plain(resp) -> dict:
            def rows(matrix):
                row = matrix.matrix[0] if matrix.matrix else []
                return {d: v for d, v in enumerate(row) if v is not None}
            return {
                "position_index": resp.position_index, "url": resp.url,
                "depth": resp.depth, "nodes": resp.nodes,
                "best_move": resp.best_move,
                "scores": {d: (s.kind, s.value) for d, s in rows(resp.scores).items()},
                "pvs": {d: list(pv) for d, pv in rows(resp.pvs).items()},
            }

        def counters(self) -> dict:
            return self.engine.occupancy_totals

        def widths(self, since_segment: int, until_segment=None) -> List[int]:
            return [r["width"] for r in list(self.engine.occupancy_log)
                    if r["segment"] > since_segment
                    and (until_segment is None or r["segment"] <= until_segment)]

        def by_width(self, since_segment: int, until_segment: int) -> dict:
            out: dict = {}
            for r in list(self.engine.occupancy_log):
                if not since_segment < r["segment"] <= until_segment:
                    continue
                v = out.setdefault(r["width"], {
                    "segments": 0, "steps": 0, "device_ms": 0.0, "host_ms": 0.0,
                    "live": 0.0, "helper": 0.0, "refilled": 0})
                v["segments"] += 1
                v["steps"] += r["steps"]
                v["device_ms"] += r["device_ms"]
                v["host_ms"] += r["host_ms"]
                v["live"] += r["steps"] * r["live"]
                v["helper"] += r["steps"] * r["helpers"]
                v["refilled"] += r["refilled"]
            for w, v in out.items():
                lanes = max(v["steps"] * w, 1)
                v["live"] = 100.0 * v["live"] / lanes
                v["helper"] = 100.0 * v["helper"] / lanes
            return out

        def segment_log(self, since_segment: int, until_segment: int) -> List[tuple]:
            """(segment, steps, blocked ms) as the scheduler logged each
            counted segment's own boundary interval: its admissions, its
            dispatch, the wait for it. The boundary of a speculative
            program that ran no step is in the totals and not here."""
            return [(r["segment"], r["steps"], r["device_ms"])
                    for r in list(self.engine.occupancy_log)
                    if since_segment < r["segment"] <= until_segment]

        def warm_shapes(self, width: int, counts: int, variant: str,
                        root_fen: str) -> int:
            """Build or load the small programs whose shapes follow the
            number of lanes refilled at one boundary (1..width), by making
            the scheduler's own refill call on a scratch state. The program
            compiles one set per count, so without this a window meets
            counts it has not seen and compiles inside itself. Reaches into
            the program (ops/search.py's refill path); PERF.md lists it as
            the program's to mend. `counts`: the most lanes one boundary
            of this traffic can refill (positions in flight times the
            helper factor). A cold cache costs about a second a program."""
            import jax.numpy as jnp

            from fishnet_tpu.chess.variants import from_fen
            from fishnet_tpu.engine.tpu import DEVICE_VARIANTS, MAX_PLY
            from fishnet_tpu.ops import search as so
            from fishnet_tpu.ops.board import from_position, stack_boards

            eng = self.engine
            dv = DEVICE_VARIANTS.get(variant, "standard")
            pos = from_fen(root_fen, variant)
            board = from_position(pos)
            B, H = width, so.MAX_HIST

            def hist(n):
                return dict(
                    hist_hash=np.zeros((n, H, 2), np.uint32),
                    hist_halfmove=np.full((n, H), so.HIST_HM_SENTINEL, np.int32),
                    root_alpha=np.full((n,), -so.INF, np.int32),
                    root_beta=np.full((n,), so.INF, np.int32),
                    order_jitter=np.zeros((n,), np.int32),
                    group=np.zeros((n,), np.int32))

            state = so._init_state_jit(
                eng.params, stack_boards([board] * B), jnp.zeros(B, jnp.int32),
                jnp.zeros(B, jnp.int32), MAX_PLY, dv,
                **{k: jnp.asarray(v) for k, v in hist(B).items()})
            top = min(counts, B)
            for n in range(1, top + 1):
                state = so.refill_lanes(
                    eng.params, state, stack_boards([board] * n), list(range(n)),
                    np.ones(n, np.int32), np.ones(n, np.int32), variant=dv,
                    **hist(n))
                rows = jnp.asarray(np.arange(n, dtype=np.int64))
                np.asarray(jnp.take(state.pv[:, 0], rows, axis=0))
                np.asarray(jnp.take(state.nt[:, 0, so.NT_PVLEN], rows, axis=0))
            return top

        def queued(self) -> int:
            """Positions waiting for a session (read for warm-up only)."""
            sched = getattr(self.engine, "_scheduler", None)
            return len(getattr(sched, "_pending", ()))

        @contextlib.contextmanager
        def hold(self):
            """Keep the scheduler from starting a session until released,
            so a warm-up session sees all of its chunks at once (what a
            move job holding the engine does to queued analysis)."""
            lock = getattr(self.engine, "_lock", None)
            if lock is None:
                yield
                return
            with lock:
                yield

        def record_spans(self, on: bool):
            if on:
                self.recorder = obs_trace.install(
                    obs_trace.TraceRecorder(capacity=400_000))
            else:
                obs_trace.uninstall()

        def host_spans(self):
            if self.recorder is None:
                return []
            out = []
            for ev in self.recorder.snapshot():
                if ev.get("ph") != "X":
                    continue
                name = ev["name"]
                if name == "fetch":
                    name = "fetch:" + str((ev.get("args") or {}).get("label", ""))
                out.append((name, ev["ts"] / 1e6, ev["dur"] / 1e6))
            return out

        def memory_peak_bytes(self) -> int:
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.local_devices()]
            return int(max(peaks)) if peaks else 0

        def table_fill(self):
            """(rows written, rows) of the engine's table, counted on the
            device; None where the engine keeps no plain table."""
            tt = self.engine.tt
            data = getattr(tt, "data", None)
            if data is None or data.ndim != 2:
                return None
            written = jax.jit(lambda d: jnp.sum(jnp.any(d != 0, axis=-1)))(data)
            return int(written), int(data.shape[0])

        def release(self):
            """Free the program's device state before the reference runs."""
            self.engine.on_deliver = None
            self.engine.tt = None
            self.engine = None

    return ProgramAdapter


def percentile_nearest(values, q: float):
    """q-th percentile as the nearest-rank order statistic."""
    if not len(values):
        return None
    s = sorted(values)
    k = min(len(s) - 1, max(0, int(np.ceil(q / 100.0 * len(s))) - 1))
    return float(s[k])
