"""The asyncio HTTP/1.1 front-end: many tenants, one lane pool.

Stdlib only — `asyncio.start_server` plus a hand-rolled HTTP/1.1 layer
(request line, headers, Content-Length bodies, keep-alive). Endpoints:

    POST /analyse    batch analysis  (protocol.py body shape)
    POST /bestmove   play-speed move requests
    GET  /healthz    JSON liveness/occupancy summary
    GET  /fleet/members   fleet health table   (fleet front-ends only)
    POST /fleet/members   runtime membership: add / drain / remove

Every accepted request is stamped with a deadline (its own timeout_ms
clamped by FISHNET_TPU_SERVE_TIMEOUT_MS), passes the admission
controller (429 + Retry-After on saturation, admission.py), and is
expanded into `PositionRequest`s submitted through one shared
`EngineSession` — against the TPU engine all tenants' positions merge
into the LaneScheduler's hardest-deadline-first pending queue.

Graceful drain: SIGTERM/SIGINT closes the listener, in-flight requests
finish (bounded by FISHNET_TPU_SERVE_DRAIN_S), per-tenant totals are
flushed to the log and the metrics registry snapshot, then the process
exits. New requests during the drain get 503 + Connection: close.
"""
from __future__ import annotations

import asyncio
import json
import signal
import time
from typing import Dict, Optional, Tuple

from ..cache.keys import keys_for_requests
from ..cache.store import AnalysisCache
from ..client.ipc import response_to_wire
from ..client.logger import Logger
from ..client.wire import EngineFlavor
from ..engine.base import EngineError
from ..engine.session import EngineSession
from ..obs import inflight as obs_inflight
from ..obs import metrics as obs_metrics
from ..obs import perf as obs_perf
from ..obs import trace as obs_trace
from ..utils import settings
from .admission import AdmissionController, Shed
from .protocol import (
    ProtocolError,
    parse_request,
    results_to_json,
    shed_to_json,
    to_position_requests,
)

# HTTP header carrying an upstream trace id into the serve edge (the
# body field "trace_id" wins when both are present).
TRACE_HEADER = "x-fishnet-trace"

MAX_HEADER_BYTES = 32768
MAX_BODY_BYTES = 4 * 1024 * 1024
# keep-alive idle cutoff: a silent client must not pin a connection
# handler forever
IDLE_TIMEOUT_S = 75.0

_REASONS = {
    200: "OK",
    400: "Bad Request",
    404: "Not Found",
    405: "Method Not Allowed",
    409: "Conflict",
    413: "Payload Too Large",
    429: "Too Many Requests",
    500: "Internal Server Error",
    503: "Service Unavailable",
}

_ENDPOINTS = {"/analyse": "analysis", "/bestmove": "bestmove"}


class _BadRequest(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


class ServeApp:
    """One server instance: listener + admission + shared session."""

    def __init__(
        self,
        session: EngineSession,
        max_inflight: Optional[int] = None,
        max_queue: Optional[int] = None,
        default_timeout_ms: Optional[int] = None,
        drain_s: Optional[float] = None,
        logger: Optional[Logger] = None,
        registry: Optional[obs_metrics.MetricsRegistry] = None,
        fleet=None,
        cache: Optional[AnalysisCache] = None,
    ):
        self.session = session
        # the FleetCoordinator behind this front-end, when there is one:
        # enables the /fleet/members runtime-membership admin surface
        self.fleet = fleet
        # the analysis-result cache (fishnet_tpu/cache/), consulted
        # BEFORE admission: a hit costs microseconds and sheds no
        # capacity; only cold positions pay for an admission ticket
        self.cache = cache
        self.logger = logger or Logger()
        if max_inflight is None:
            max_inflight = settings.get_int("FISHNET_TPU_SERVE_MAX_INFLIGHT")
        if max_queue is None:
            max_queue = settings.get_int("FISHNET_TPU_SERVE_MAX_QUEUE")
        if default_timeout_ms is None:
            default_timeout_ms = settings.get_int("FISHNET_TPU_SERVE_TIMEOUT_MS")
        if drain_s is None:
            drain_s = float(settings.get_int("FISHNET_TPU_SERVE_DRAIN_S"))
        self.default_timeout_ms = default_timeout_ms
        self.drain_s = drain_s
        self.registry = registry if registry is not None else obs_metrics.REGISTRY
        self.admission = AdmissionController(
            max_inflight, max_queue, registry=self.registry
        )
        self.slo = obs_metrics.SloRecorder(self.registry)
        self.inflight = obs_inflight.REGISTRY
        self._server: Optional[asyncio.AbstractServer] = None
        self._draining = False
        self._open_requests = 0
        self._drained = asyncio.Event()

    # ------------------------------------------------------------ lifecycle

    async def start(self, host: str, port: int) -> Tuple[str, int]:
        self._server = await asyncio.start_server(
            self._handle_conn, host=host, port=port
        )
        bound = self._server.sockets[0].getsockname()
        return bound[0], bound[1]

    def begin_drain(self) -> None:
        """Stop accepting; in-flight requests run to completion."""
        if self._draining:
            return
        self._draining = True
        self.logger.headline("serve: draining (no new requests)")
        if self._server is not None:
            self._server.close()
        if self._open_requests == 0:
            self._drained.set()

    async def drain_and_stop(self) -> None:
        """Wait for in-flight work (bounded by drain_s), then stop."""
        self.begin_drain()
        try:
            await asyncio.wait_for(self._drained.wait(), timeout=self.drain_s)
        except asyncio.TimeoutError:
            self.logger.warn(
                f"serve: drain grace period ({self.drain_s:.0f}s) expired "
                f"with {self._open_requests} request(s) still open"
            )
        if self._server is not None:
            await self._server.wait_closed()
        self._flush_stats()

    def _flush_stats(self) -> None:
        snap = self.registry.snapshot()
        served = {
            k: v for k, v in sorted(snap.items())
            if k.startswith("fishnet_serve_") and not k.endswith("_sum")
        }
        parts = ", ".join(f"{k.removeprefix('fishnet_serve_')}={int(v)}"
                          for k, v in served.items())
        self.logger.headline(f"serve: final stats: {parts or 'no requests'}")

    # ------------------------------------------------------------ transport

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        try:
            while True:
                try:
                    request = await asyncio.wait_for(
                        self._read_request(reader), timeout=IDLE_TIMEOUT_S
                    )
                except asyncio.TimeoutError:
                    break
                if request is None:  # clean EOF between requests
                    break
                method, path, headers, body = request
                want_close = (
                    headers.get("connection", "").lower() == "close"
                    or self._draining
                )
                status, payload, extra = await self._dispatch(
                    method, path, headers, body
                )
                await self._write_response(
                    writer, status, payload, extra, close=want_close
                )
                if want_close:
                    break
        except _BadRequest as e:
            # malformed transport framing: answer once and hang up
            try:
                await self._write_response(
                    writer, e.status, {"error": e.message}, {}, close=True
                )
            except (ConnectionError, OSError):
                pass  # peer already gone; nothing to answer
        except (ConnectionError, asyncio.IncompleteReadError, OSError) as e:
            self.logger.debug(f"serve: connection dropped: {e}")
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass  # close raced the peer's reset; already closed

    async def _read_request(
        self, reader: asyncio.StreamReader
    ) -> Optional[Tuple[str, str, Dict[str, str], bytes]]:
        line = await reader.readline()
        if not line:
            return None
        try:
            parts = line.decode("latin-1").split()
            method, target, _version = parts[0], parts[1], parts[2]
        except (IndexError, UnicodeDecodeError):
            raise _BadRequest(400, "malformed request line") from None
        headers: Dict[str, str] = {}
        total = len(line)
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            total += len(h)
            if total > MAX_HEADER_BYTES:
                raise _BadRequest(400, "headers too large")
            name, sep, value = h.decode("latin-1").partition(":")
            if not sep:
                raise _BadRequest(400, "malformed header")
            headers[name.strip().lower()] = value.strip()
        try:
            length = int(headers.get("content-length", "0"))
        except ValueError:
            raise _BadRequest(400, "bad Content-Length") from None
        if length > MAX_BODY_BYTES:
            raise _BadRequest(413, "body too large")
        body = await reader.readexactly(length) if length > 0 else b""
        return method, target.split("?", 1)[0], headers, body

    async def _write_response(
        self,
        writer: asyncio.StreamWriter,
        status: int,
        payload: dict,
        extra_headers: Dict[str, str],
        close: bool,
    ) -> None:
        body = json.dumps(payload).encode("utf-8")
        lines = [
            f"HTTP/1.1 {status} {_REASONS.get(status, 'Unknown')}",
            "Content-Type: application/json",
            f"Content-Length: {len(body)}",
            f"Connection: {'close' if close else 'keep-alive'}",
        ]
        lines.extend(f"{k}: {v}" for k, v in extra_headers.items())
        writer.write(("\r\n".join(lines) + "\r\n\r\n").encode("latin-1") + body)
        await writer.drain()

    # ------------------------------------------------------------ handlers

    async def _dispatch(
        self, method: str, path: str, headers: Dict[str, str], body: bytes
    ) -> Tuple[int, dict, Dict[str, str]]:
        if path == "/healthz":
            if method != "GET":
                return 405, {"error": "use GET"}, {}
            inflight, queued = self.admission.occupancy()
            return 200, {
                "status": "draining" if self._draining else "ok",
                "inflight": inflight,
                "queued": queued,
                "drain_rate_pos_per_s": round(self.admission.drain_rate(), 3),
                "cache": (
                    self.cache.counters() if self.cache is not None else None
                ),
                # the device the engine runs on, as the process that
                # owns it reported (supervised host's ready frame, or
                # the in-process TpuEngine); None for host engines
                "device": getattr(
                    getattr(self.session, "engine", None), "device", None
                ),
            }, {}
        if path == "/debug/requests":
            if method != "GET":
                return 405, {"error": "use GET"}, {}
            reqs = self.inflight.snapshot()
            return 200, {"inflight": len(reqs), "requests": reqs}, {}
        if path == "/debug/perf":
            if method != "GET":
                return 405, {"error": "use GET"}, {}
            # current perf snapshot next to the last ledger baseline
            # (obs/perf.py, docs/perf.md); `python -m fishnet_tpu perf`
            # renders this payload as a table
            from ..obs import perf as obs_perf

            return 200, obs_perf.live_snapshot(), {}
        if path == "/fleet/members":
            return await self._fleet_members(method, body)
        kind = _ENDPOINTS.get(path)
        if kind is None:
            return 404, {"error": f"no such endpoint {path}"}, {}
        if method != "POST":
            return 405, {"error": "use POST"}, {}
        if self._draining:
            return 503, {"error": "draining"}, {"Retry-After": "5"}
        try:
            obj = json.loads(body.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            return 400, {"error": "body is not valid JSON"}, {}
        try:
            sreq = parse_request(kind, obj)
        except ProtocolError as e:
            return 400, {"error": str(e)}, {}
        return await self._serve_request(
            sreq, upstream_trace=headers.get(TRACE_HEADER, "")
        )

    async def _fleet_members(
        self, method: str, body: bytes
    ) -> Tuple[int, dict, Dict[str, str]]:
        """Runtime membership (docs/fleet.md rolling restarts): GET is
        the coordinator's health table; POST takes {"action": "add",
        "spec": ...} | {"action": "drain"|"remove", "member": ...}.
        State conflicts (undrained removal, duplicate add) answer 409."""
        if self.fleet is None:
            return 404, {"error": "not a fleet front-end"}, {}
        if method == "GET":
            return 200, self.fleet.health(), {}
        if method != "POST":
            return 405, {"error": "use GET or POST"}, {}
        try:
            obj = json.loads(body.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            return 400, {"error": "body is not valid JSON"}, {}
        if not isinstance(obj, dict):
            return 400, {"error": "body must be a JSON object"}, {}
        action = obj.get("action")
        try:
            if action == "add":
                row = await self.fleet.add_member(
                    str(obj.get("spec") or "")
                )
                return 200, {"ok": True, "member": row}, {}
            if action == "drain":
                out = self.fleet.drain_member(
                    str(obj.get("member") or "")
                )
                return 200, {"ok": True, **out}, {}
            if action == "remove":
                row = await self.fleet.remove_member(
                    str(obj.get("member") or ""),
                    force=bool(obj.get("force")),
                )
                return 200, {"ok": True, "member": row}, {}
        except EngineError as e:
            return 409, {"error": str(e)}, {}
        return 400, {
            "error": f"unknown action {action!r} "
                     "(use add / drain / remove)"
        }, {}

    async def _serve_request(
        self, sreq, upstream_trace: str = ""
    ) -> Tuple[int, dict, Dict[str, str]]:
        timeout_ms = min(
            sreq.timeout_ms or self.default_timeout_ms, self.default_timeout_ms
        )
        t0 = time.monotonic()
        deadline = t0 + timeout_ms / 1000.0
        # The edge stamp: every request gets a context (the in-flight
        # registry and SLO accounting key on it even with tracing off);
        # spans/flow links are additionally gated on the recorder and
        # the deterministic sampling verdict for this trace_id.
        ctx = obs_trace.make_ctx(
            sreq.tenant, sreq.kind, deadline_ms=timeout_ms,
            trace_id=sreq.trace_id or upstream_trace or None,
        )
        tid = ctx["trace_id"]
        rec = obs_trace.RECORDER
        traced = rec is not None and obs_trace.sampled(tid)
        self.inflight.begin(
            tid, sreq.id, sreq.tenant, sreq.kind,
            deadline_mono_s=deadline, n_positions=len(sreq.positions),
        )
        self._open_requests += 1
        try:
            with (rec.span("http.request", "serve",
                           **obs_trace.ctx_args(ctx, id=sreq.id,
                                                n=len(sreq.positions)))
                  if traced else obs_trace.NULL_SPAN):
                if traced:
                    rec.flow("request", tid, "s")
                preqs = to_position_requests(sreq, deadline, ctx=ctx)
                n = len(preqs)
                cache = self.cache
                # cache consult (docs/caching.md): classify every
                # position as hit (served from store), join (an
                # identical search is already in flight — one search,
                # N deliveries) or lead (cold; we search and fill)
                hydrated: Dict[int, object] = {}
                joins: Dict[int, "asyncio.Future"] = {}
                leases: Dict[int, object] = {}
                keys = None
                if cache is not None:
                    flavor = getattr(self.session, "flavor", EngineFlavor.TPU)
                    keys = keys_for_requests(preqs, cache.net, flavor=flavor)
                    for i, (key, depth) in enumerate(keys):
                        state, val = cache.lease(key, depth)
                        if state == "hit":
                            hydrated[i] = AnalysisCache.hydrate(val, i)
                        elif state == "join":
                            joins[i] = val
                        else:
                            leases[i] = val
                        if traced:
                            rec.instant(
                                "cache.hit" if state != "lead"
                                else "cache.miss",
                                "serve",
                                **obs_trace.ctx_args(
                                    ctx, position_index=i,
                                    coalesced=state == "join",
                                ))
                cold = sorted(leases) if cache is not None else list(range(n))
                fallback: list = []
                try:
                    ticket = None
                    if cold or cache is None:
                        # only cold positions pay for admission: an
                        # all-hit request never touches the waiting room
                        try:
                            with (rec.span("serve.admission", "serve",
                                           **obs_trace.ctx_args(ctx))
                                  if traced else obs_trace.NULL_SPAN):
                                ticket = await self.admission.admit(
                                    sreq.tenant,
                                    len(cold) if cache is not None else n,
                                    deadline, sreq.priority,
                                )
                        except Shed as e:
                            self.slo.shed(sreq.tenant, sreq.kind)
                            return 429, shed_to_json(
                                e.retry_after, e.reason
                            ), {"Retry-After": str(e.retry_after)}
                    self.inflight.stage(tid, "admitted")
                    queue_ms = (time.monotonic() - t0) * 1000.0
                    ok = False
                    try:
                        self.inflight.stage(tid, "dispatched")
                        searched = (
                            await self.session.submit_many(
                                [preqs[i] for i in cold]
                            ) if cold else []
                        )
                        ok = True
                    except EngineError as e:
                        self.logger.error(f"serve: engine error: {e}")
                        return 500, {"error": f"engine error: {e}"}, {}
                    finally:
                        if ticket is not None:
                            self.admission.release(ticket, ok=ok)
                    for i, resp in zip(cold, searched):
                        hydrated[i] = resp
                        if keys is not None:
                            # fill + settle: followers coalesced onto
                            # this search get the same wire result
                            # (store() is idempotent — the engine-side
                            # delivery hook may have filled already)
                            wire = response_to_wire(resp)
                            key, depth = keys[i]
                            cache.store(key, depth, wire)
                            leases[i].settle(dict(wire))
                    for i, fut in joins.items():
                        try:
                            wire = await asyncio.wait_for(
                                asyncio.shield(fut),
                                timeout=max(
                                    0.0, deadline - time.monotonic()
                                ),
                            )
                        except (asyncio.TimeoutError,
                                asyncio.CancelledError):
                            wire = None
                        if wire is None:
                            # the leader's search failed or outran our
                            # deadline: fall back to our own search
                            fallback.append(i)
                        else:
                            hydrated[i] = AnalysisCache.hydrate(wire, i)
                    if fallback:
                        try:
                            fb = await self.session.submit_many(
                                [preqs[i] for i in fallback]
                            )
                        except EngineError as e:
                            self.logger.error(f"serve: engine error: {e}")
                            return 500, {"error": f"engine error: {e}"}, {}
                        for i, resp in zip(fallback, fb):
                            hydrated[i] = resp
                finally:
                    if cache is not None:
                        for lease in leases.values():
                            # no-op for settled leases; an error path
                            # resolves followers to None (search-your-
                            # own) instead of wedging them
                            lease.settle(None)
                responses = [hydrated[i] for i in range(n)]
                now = time.monotonic()
                total_ms = (now - t0) * 1000.0
                device_ms = max(
                    (r.time_s for r in responses), default=0.0
                ) * 1000.0
                self.slo.observe(
                    sreq.tenant, sreq.kind, total_ms,
                    queue_ms=queue_ms,
                    device_ms=device_ms,
                    deadline_missed=now > deadline,
                )
                if traced:
                    # the histogram observation rides the dump so
                    # trace_report --request can crosscheck the
                    # reconstructed waterfall against what the SLO
                    # accounting actually recorded (same idiom as the
                    # segment spans carrying their SyncStats args)
                    rec.instant(
                        "slo.observe", "serve",
                        **obs_trace.ctx_args(
                            ctx, total_ms=total_ms, queue_ms=queue_ms,
                            device_ms=device_ms,
                            deadline_missed=now > deadline,
                        ))
                    rec.flow("request", tid, "f")
                extra: Dict[str, str] = {}
                if cache is not None:
                    served = n - len(cold) - len(fallback)
                    extra["X-Fishnet-Cache"] = (
                        "hit" if n and served == n
                        else "partial" if served else "miss"
                    )
                    cache.observe_request(sreq.tenant, served, n)
                    cache.export_metrics()
                return 200, results_to_json(sreq, responses, now - t0), extra
        finally:
            self.inflight.end(tid)
            self._open_requests -= 1
            if self._draining and self._open_requests == 0:
                self._drained.set()


async def run_serve(cfg) -> int:
    """`python -m fishnet_tpu serve` entry: build the engine for the
    configured backend, share it through one EngineSession, serve until
    SIGTERM/SIGINT, drain, exit."""
    from ..client.app import make_engine_factory
    from ..client.wire import EngineFlavor

    logger = Logger(verbose=cfg.verbose)
    if obs_trace.RECORDER is None:
        # serve is its own trace edge: the request-scoped http/admission
        # spans and the flow chain start here (no-op without TRACE_DIR)
        obs_trace.install_from_settings("serve")
    host = cfg.serve_host or settings.get_str("FISHNET_TPU_SERVE_HOST")
    port = (
        cfg.serve_port
        if cfg.serve_port is not None
        else settings.get_int("FISHNET_TPU_SERVE_PORT")
    )

    factory = make_engine_factory(cfg, logger)
    flavor = (
        EngineFlavor.TPU if cfg.backend == "tpu" else EngineFlavor.OFFICIAL
    )
    engine = factory(flavor)
    if getattr(cfg, "fleet", False):
        # fleet front door: the coordinator spawns its local members
        # (remote ones need no warmup) before the listener opens
        logger.info("serve: starting fleet members ...")
        await engine.start()
        logger.info("serve: fleet coordinator ready.")
    elif cfg.backend == "tpu":
        logger.info("serve: warming up TPU engine (compiling search program) ...")
        if cfg.supervisor:
            await engine.start()
            logger.info("serve: supervised TPU engine host ready.")
        else:
            await asyncio.to_thread(engine.warmup, None, logger.info)
            logger.info("serve: TPU engine ready.")
        # autoscaling cold-start signal (docs/aot.md): a replica booted
        # from an AOT bundle reached this point without compiling, so
        # it can accept traffic the moment the listener opens
        if cfg.supervisor:
            # the host child's own report (its ready frame): this
            # process leaves jax, and the AOT registry with it, alone
            rep = getattr(engine, "aot_report", None) or {}
        else:
            from ..aot import registry as aot_registry

            rep = aot_registry.boot_report()
        if rep.get("enabled"):
            logger.info(
                f"serve: AOT assets — {rep.get('programs', 0)} programs "
                f"(bundle {rep.get('fingerprint', '?')}, covers "
                f"{','.join(rep.get('covers') or []) or 'none'})"
            )

    session = EngineSession(engine, flavor=flavor)
    cache = None
    if getattr(cfg, "cache", True):
        from ..cache import attach_ttwarm, cache_from_settings
        from ..cache import attach_engine as cache_attach_engine

        if getattr(cfg, "fleet", False):
            # the coordinator object carries no net of its own: pin the
            # identity inputs from the config its members are built with
            # so the fingerprint tracks netswaps (cache/keys.py)
            if getattr(engine, "weights_path", None) is None:
                engine.weights_path = cfg.tpu_weights
            if getattr(engine, "max_depth", None) is None:
                engine.max_depth = cfg.tpu_depth
        cache = cache_from_settings(
            engine, flavor, logger=logger,
            directory=getattr(cfg, "cache_dir", None),
        )
    if cache is not None:
        logger.info(
            f"serve: analysis cache on (identity {cache.net}, "
            f"{'persisted' if cache.recorder is not None else 'memory-only'})"
        )
        if getattr(cfg, "fleet", False):
            # fleet: consult + fill at the coordinator so N members
            # share one hit set (exactly-once via the ack journal path)
            engine.attach_cache(cache)
        else:
            # direct engine: fill from the exactly-once delivery hook
            cache_attach_engine(engine, cache)
            if attach_ttwarm(engine, logger=logger) is not None:
                logger.info(
                    "serve: TT warm slices on "
                    f"(prefix {engine.tt_warm_prefix} plies)"
                )
    app = ServeApp(
        session, logger=logger,
        fleet=engine if getattr(cfg, "fleet", False) else None,
        cache=cache,
    )
    bound_host, bound_port = await app.start(host, port)
    # the smoke client and bench parse this exact line to find an
    # ephemeral port (FISHNET_TPU_SERVE_PORT=0)
    logger.headline(f"serve: listening on {bound_host}:{bound_port}")

    # the engine is up: the device fields come from the supervised
    # host's ready frame (or the in-process engine), never from JAX here
    obs_perf.register_build_info()
    metrics_server = obs_metrics.serve_from_settings()
    if metrics_server is not None:
        logger.info(
            "serve: metrics at "
            f"http://127.0.0.1:{metrics_server.server_address[1]}/metrics"
        )

    # elastic capacity (fleet/autoscaler.py): only meaningful with a
    # fleet engine — the control loop drives the coordinator's runtime
    # membership off this app's admission/SLO signals. Starts after the
    # listener opens (the floor fleet is already warm) and stops before
    # drain so no membership change races the shutdown.
    autoscaler = None
    autoscale_on = (
        cfg.autoscale if getattr(cfg, "autoscale", None) is not None
        else settings.get_bool("FISHNET_TPU_AUTOSCALE")
    )
    if autoscale_on and getattr(cfg, "fleet", False):
        from ..fleet.autoscaler import AutoscaleConfig, Autoscaler

        as_cfg = AutoscaleConfig.from_settings()
        if getattr(cfg, "autoscale_min", None) is not None or \
                getattr(cfg, "autoscale_max", None) is not None:
            from dataclasses import replace as _dc_replace

            kw = {}
            if getattr(cfg, "autoscale_min", None) is not None:
                kw["min_members"] = cfg.autoscale_min
            if getattr(cfg, "autoscale_max", None) is not None:
                kw["max_members"] = cfg.autoscale_max
            as_cfg = _dc_replace(as_cfg, **kw)
        autoscaler = Autoscaler(
            engine, app.admission, config=as_cfg, logger=logger,
        )
        autoscaler.start()
        logger.info(
            f"serve: autoscaler on (members {as_cfg.min_members}.."
            f"{as_cfg.max_members}, tick {as_cfg.interval_s:g}s)"
        )
    elif autoscale_on:
        logger.info("serve: autoscale requested without --fleet; off.")

    loop = asyncio.get_running_loop()
    stop = asyncio.Event()
    try:
        loop.add_signal_handler(signal.SIGTERM, stop.set)
        loop.add_signal_handler(signal.SIGINT, stop.set)
    except NotImplementedError:
        pass  # non-unix
    await stop.wait()  # fishnet-lint: disable=conc-no-timeout
    if autoscaler is not None:
        await autoscaler.stop()
    await app.drain_and_stop()
    await session.close()
    await engine.close()
    rec = obs_trace.RECORDER
    trace_dir = settings.get_str("FISHNET_TPU_TRACE_DIR")
    if rec is not None and trace_dir:
        # the serve ring holds the merged timeline (supervised members'
        # events were absorbed as they streamed); one dump at drain is
        # the whole request waterfall, edge to lane
        path = rec.flight_dump(trace_dir, "serve-final")
        logger.info(f"serve: trace dumped to {path}")
    logger.headline("serve: bye.")
    return 0
