"""Serving front-end tests: protocol serde, admission policy, HTTP
backpressure, graceful drain, and the bit-identity contract (an answer
through the HTTP layer equals the same chunk through go_multiple).

All async tests drive a real asyncio server on an ephemeral loopback
port through asyncio.run — no external HTTP client, no extra deps.
"""
import asyncio
import json
import time

import pytest

from fishnet_tpu.client.ipc import Matrix, PositionResponse
from fishnet_tpu.client.wire import EngineFlavor, Score
from fishnet_tpu.engine.pyengine import PyEngine
from fishnet_tpu.engine.session import (
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    EngineSession,
    PositionRequest,
    requests_to_chunks,
)
from fishnet_tpu.obs.metrics import MetricsRegistry
from fishnet_tpu.serve.admission import AdmissionController, Shed
from fishnet_tpu.serve.protocol import (
    ProtocolError,
    ServeRequest,
    parse_request,
    request_to_json,
)
from fishnet_tpu.serve.server import ServeApp

STARTPOS = "rnbqkbnr/pppppppp/8/8/8/8/PPPPPPPP/RNBQKBNR w KQkq - 0 1"


# ------------------------------------------------------------ protocol


def test_request_round_trip():
    reqs = [
        ServeRequest(
            kind="analysis",
            positions=((STARTPOS, ("e2e4", "e7e5")), (STARTPOS, ())),
            id="req-1",
            tenant="team-a",
            depth=6,
            multipv=3,
            nodes=250_000,
            timeout_ms=4000,
        ),
        ServeRequest(
            kind="bestmove",
            positions=((STARTPOS, ()),),
            id="bm-9",
            tenant="bot-x",
            level=5,
            priority=PRIORITY_INTERACTIVE,
        ),
        ServeRequest(kind="analysis", positions=((STARTPOS, ()),)),
    ]
    for req in reqs:
        assert parse_request(req.kind, request_to_json(req)) == req


def test_parse_request_defaults():
    req = parse_request("analysis", {"positions": [{"fen": STARTPOS}]})
    assert req.tenant == "default"
    assert req.priority == PRIORITY_BATCH
    # bestmove defaults to the interactive tier
    req = parse_request("bestmove", {"positions": [{"fen": STARTPOS}]})
    assert req.priority == PRIORITY_INTERACTIVE


@pytest.mark.parametrize(
    "body",
    [
        {},  # no positions
        {"positions": []},
        {"positions": [{"fen": ""}]},
        {"positions": [{"fen": STARTPOS, "moves": [1, 2]}]},
        {"positions": [{"fen": STARTPOS}], "depth": 0},
        {"positions": [{"fen": STARTPOS}], "multipv": 6},
        {"positions": [{"fen": STARTPOS}], "priority": "urgent"},
        {"positions": [{"fen": STARTPOS}], "level": 9},
        {"positions": [{"fen": STARTPOS}], "tenant": ""},
        "not an object",
    ],
)
def test_parse_request_rejects(body):
    with pytest.raises(ProtocolError):
        parse_request("analysis", body)


# ------------------------------------------------------------ admission


def test_admission_hardest_deadline_first_across_tenants():
    """Waiters drain in (priority tier, deadline) order regardless of
    arrival order or tenant."""

    async def scenario():
        adm = AdmissionController(
            max_inflight=1, max_queue=10, registry=MetricsRegistry()
        )
        now = time.monotonic()
        blocker = await adm.admit("seed", 1, now + 30.0, PRIORITY_BATCH)

        order = []

        async def waiter(tag, deadline, priority):
            ticket = await adm.admit(tag, 1, deadline, priority)
            order.append(tag)
            await asyncio.sleep(0)  # let the next grant interleave
            adm.release(ticket)

        # arrival order deliberately scrambled vs expected service order
        tasks = []
        for tag, dl, prio in [
            ("batch-late", now + 20.0, PRIORITY_BATCH),
            ("interactive-late", now + 15.0, PRIORITY_INTERACTIVE),
            ("batch-soon", now + 6.0, PRIORITY_BATCH),
            ("interactive-soon", now + 5.0, PRIORITY_INTERACTIVE),
        ]:
            tasks.append(asyncio.ensure_future(waiter(tag, dl, prio)))
            await asyncio.sleep(0)  # enqueue in this order

        assert adm.occupancy() == (1, 4)
        adm.release(blocker)
        await asyncio.wait_for(asyncio.gather(*tasks), timeout=5.0)
        # interactive tier first; hardest deadline first within a tier
        assert order == [
            "interactive-soon",
            "interactive-late",
            "batch-soon",
            "batch-late",
        ]
        assert adm.occupancy() == (0, 0)

    asyncio.run(scenario())


def test_admission_sheds_when_room_full():
    async def scenario():
        registry = MetricsRegistry()
        adm = AdmissionController(
            max_inflight=1, max_queue=0, registry=registry
        )
        now = time.monotonic()
        ticket = await adm.admit("a", 1, now + 30.0, PRIORITY_BATCH)
        with pytest.raises(Shed) as exc:
            await adm.admit("b", 1, now + 30.0, PRIORITY_BATCH)
        assert 1 <= exc.value.retry_after <= 60
        snap = registry.snapshot()
        assert snap["fishnet_serve_shed_total_b"] == 1
        adm.release(ticket)

    asyncio.run(scenario())


def test_retry_after_with_no_drain_history_is_the_cap():
    # a cold saturated server has no completion history to extrapolate
    # from: the only honest Retry-After is the pessimistic cap
    adm = AdmissionController(
        max_inflight=4, max_queue=4, registry=MetricsRegistry()
    )
    assert adm.drain_rate() == 0.0
    assert adm.retry_after() == 60
    assert adm.retry_after(extra_positions=1) == 60


def test_retry_after_zero_drain_stall_is_the_cap():
    # a measured-then-collapsed drain rate (stall) must behave like no
    # history at all — dividing by ~0 must not leak a huge number out
    adm = AdmissionController(
        max_inflight=8, max_queue=8, registry=MetricsRegistry()
    )
    adm._drain_rate = 0.0
    assert adm.retry_after(extra_positions=100) == 60


def test_retry_after_clamped_to_one_second_floor():
    # backlog drains in well under a second: the header still says 1,
    # never 0 (a 0 would invite an immediate retry storm)
    adm = AdmissionController(
        max_inflight=8, max_queue=8, registry=MetricsRegistry()
    )
    adm._drain_rate = 1000.0
    assert adm.retry_after(extra_positions=1) == 1


def test_retry_after_clamped_to_sixty_second_cap():
    adm = AdmissionController(
        max_inflight=8, max_queue=8, registry=MetricsRegistry()
    )
    adm._drain_rate = 0.5
    assert adm.retry_after(extra_positions=10_000) == 60


def test_retry_after_interior_estimate():
    # 10 queued positions at 2 positions/s -> ~5s, +1 for the partial
    adm = AdmissionController(
        max_inflight=8, max_queue=8, registry=MetricsRegistry()
    )
    adm._drain_rate = 2.0
    assert adm.retry_after(extra_positions=10) == 6


def test_release_establishes_drain_rate():
    async def scenario():
        adm = AdmissionController(
            max_inflight=4, max_queue=4, registry=MetricsRegistry()
        )
        ticket = await adm.admit(
            "a", 2, time.monotonic() + 30.0, PRIORITY_BATCH)
        await asyncio.sleep(0.01)
        adm.release(ticket, ok=True)
        assert adm.drain_rate() > 0.0
        assert 1 <= adm.retry_after(extra_positions=4) <= 60

    asyncio.run(scenario())


def test_admission_sheds_expired_deadline():
    async def scenario():
        adm = AdmissionController(
            max_inflight=4, max_queue=4, registry=MetricsRegistry()
        )
        with pytest.raises(Shed):
            await adm.admit("a", 1, time.monotonic() - 0.1, PRIORITY_BATCH)

    asyncio.run(scenario())


# ------------------------------------------------------------ HTTP layer


async def _http(host, port, method, path, obj=None):
    """Minimal one-shot HTTP/1.1 client over asyncio streams."""
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps(obj).encode("utf-8") if obj is not None else b""
    head = (
        f"{method} {path} HTTP/1.1\r\nHost: {host}\r\n"
        f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
    )
    writer.write(head.encode("latin-1") + body)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head_raw, _, payload = raw.partition(b"\r\n\r\n")
    lines = head_raw.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        name, _, value = ln.partition(":")
        headers[name.strip().lower()] = value.strip()
    return status, headers, json.loads(payload) if payload else {}


def _fake_response():
    scores = Matrix()
    scores.set(1, 2, Score.cp(13))
    pvs = Matrix()
    pvs.set(1, 2, ["e2e4"])
    return PositionResponse(
        work=None,
        position_index=0,
        url=None,
        scores=scores,
        pvs=pvs,
        best_move="e2e4",
        depth=2,
        nodes=100,
        time_s=0.01,
        nps=10_000,
    )


class GatedSession:
    """Stub EngineSession: submit_many parks on a gate so tests control
    exactly when in-flight work completes."""

    def __init__(self):
        self.gate = asyncio.Event()
        self.calls = 0

    async def submit_many(self, requests):
        self.calls += 1
        await asyncio.wait_for(self.gate.wait(), timeout=30.0)
        return [_fake_response() for _ in requests]


def _analysis_body(rid, tenant="default"):
    return {
        "id": rid,
        "tenant": tenant,
        "positions": [{"fen": STARTPOS, "moves": ["e2e4"]}],
        "depth": 2,
    }


def test_http_backpressure_429_and_shed_metrics():
    """At the in-flight cap with no waiting room, the second request is
    shed with 429 + Retry-After and the tenant's shed counter moves."""

    async def scenario():
        registry = MetricsRegistry()
        session = GatedSession()
        app = ServeApp(
            session,
            max_inflight=1,
            max_queue=0,
            default_timeout_ms=8000,
            drain_s=5.0,
            registry=registry,
        )
        host, port = await app.start("127.0.0.1", 0)
        try:
            first = asyncio.ensure_future(
                _http(host, port, "POST", "/analyse", _analysis_body("r1"))
            )
            for _ in range(50):
                await asyncio.sleep(0.01)
                if app.admission.occupancy()[0] == 1:
                    break
            assert app.admission.occupancy()[0] == 1

            status, headers, payload = await _http(
                host, port, "POST", "/analyse",
                _analysis_body("r2", tenant="team-b"),
            )
            assert status == 429
            assert "retry-after" in headers
            assert int(headers["retry-after"]) >= 1
            assert payload["retry_after"] == int(headers["retry-after"])
            assert "error" in payload
            # registry sanitizes metric names: tenant "team-b" -> team_b
            assert registry.snapshot()["fishnet_serve_shed_total_team_b"] == 1

            session.gate.set()
            status, _, payload = await asyncio.wait_for(first, timeout=10.0)
            assert status == 200
            assert payload["id"] == "r1"
        finally:
            session.gate.set()
            await app.drain_and_stop()

    asyncio.run(scenario())


def test_http_graceful_drain_completes_inflight():
    """begin_drain() mid-request: the in-flight request still answers
    200 and drain_and_stop returns once it does."""

    async def scenario():
        session = GatedSession()
        app = ServeApp(
            session,
            max_inflight=4,
            max_queue=4,
            default_timeout_ms=8000,
            drain_s=10.0,
            registry=MetricsRegistry(),
        )
        host, port = await app.start("127.0.0.1", 0)
        inflight = asyncio.ensure_future(
            _http(host, port, "POST", "/analyse", _analysis_body("d1"))
        )
        for _ in range(50):
            await asyncio.sleep(0.01)
            if session.calls == 1:
                break
        assert session.calls == 1

        app.begin_drain()
        drainer = asyncio.ensure_future(app.drain_and_stop())
        await asyncio.sleep(0.05)
        assert not drainer.done()  # still waiting on the in-flight request

        session.gate.set()
        status, _, payload = await asyncio.wait_for(inflight, timeout=10.0)
        assert status == 200
        assert payload["id"] == "d1"
        await asyncio.wait_for(drainer, timeout=10.0)

    asyncio.run(scenario())


def test_http_rejects_and_healthz():
    async def scenario():
        session = GatedSession()
        app = ServeApp(
            session, max_inflight=4, max_queue=4,
            default_timeout_ms=8000, drain_s=5.0, registry=MetricsRegistry(),
        )
        host, port = await app.start("127.0.0.1", 0)
        try:
            status, _, payload = await _http(host, port, "GET", "/healthz")
            assert status == 200
            assert payload["status"] == "ok"
            assert payload["inflight"] == 0
            # no engine behind this session, so no device to report
            assert payload["device"] is None
            session.engine = type("Eng", (), {"device": {
                "platform": "tpu", "kind": "TPU v5 lite", "count": 1}})()
            _, _, payload = await _http(host, port, "GET", "/healthz")
            assert payload["device"]["platform"] == "tpu"

            status, _, _ = await _http(host, port, "POST", "/nope", {})
            assert status == 404
            status, _, _ = await _http(host, port, "GET", "/analyse")
            assert status == 405
            status, _, payload = await _http(
                host, port, "POST", "/analyse", {"positions": []}
            )
            assert status == 400
            assert "error" in payload
        finally:
            session.gate.set()
            await app.drain_and_stop()

    asyncio.run(scenario())


# ------------------------------------------------------------ bit-identity


def _comparable(wire: dict) -> dict:
    """The deterministic result fields; time_s/nps are wall-clock."""
    return {
        k: wire[k] for k in ("scores", "pvs", "best_move", "depth", "nodes")
    }


def test_http_bit_identical_to_direct_go_multiple():
    """An /analyse answer equals the same positions pushed straight
    through Engine.go_multiple — the HTTP layer adds no search-visible
    state."""

    async def scenario():
        engine = PyEngine(max_depth=2)
        app = ServeApp(
            EngineSession(engine, flavor=EngineFlavor.OFFICIAL),
            max_inflight=8,
            max_queue=4,
            default_timeout_ms=8000,
            drain_s=5.0,
            registry=MetricsRegistry(),
        )
        host, port = await app.start("127.0.0.1", 0)
        try:
            body = {
                "id": "bit-1",
                "positions": [
                    {"fen": STARTPOS, "moves": ["e2e4"]},
                    {"fen": STARTPOS, "moves": []},
                ],
                "depth": 2,
                "nodes": 300_000,
            }
            status, _, payload = await _http(
                host, port, "POST", "/analyse", body
            )
            assert status == 200
            assert payload["id"] == "bit-1"
            assert len(payload["results"]) == 2

            direct_engine = PyEngine(max_depth=2)
            reqs = [
                PositionRequest(
                    fen=STARTPOS, moves=("e2e4",), depth=2, nodes=300_000,
                    deadline=time.monotonic() + 8.0,
                ),
                PositionRequest(
                    fen=STARTPOS, moves=(), depth=2, nodes=300_000,
                    deadline=time.monotonic() + 8.0,
                ),
            ]
            plan = requests_to_chunks(reqs, flavor=EngineFlavor.OFFICIAL)
            direct = [None, None]
            for chunk, indices in plan:
                responses = await direct_engine.go_multiple(chunk)
                for slot, i in enumerate(indices):
                    direct[i] = responses[slot]

            from fishnet_tpu.client.ipc import response_to_wire

            for http_res, direct_res in zip(payload["results"], direct):
                assert _comparable(http_res) == _comparable(
                    response_to_wire(direct_res)
                )
        finally:
            await app.drain_and_stop()

    asyncio.run(scenario())


# ----------------------------------------------------------- analysis cache


def _cache_app(cache):
    return ServeApp(
        EngineSession(PyEngine(max_depth=2), flavor=EngineFlavor.OFFICIAL),
        max_inflight=8,
        max_queue=4,
        default_timeout_ms=8000,
        drain_s=5.0,
        registry=MetricsRegistry(),
        cache=cache,
    )


def _searched(payload):
    """The search-determined part of a response body (wall-clock fields
    legitimately differ between a cached entry and a fresh search)."""
    return [
        {k: r.get(k) for k in ("scores", "pvs", "best_move", "depth",
                               "nodes")}
        for r in payload["results"]
    ]


def test_cache_header_miss_then_hit():
    """The same position twice: first response is X-Fishnet-Cache: miss,
    the repeat is a hit with an identical search payload — and the
    cached hit never reaches the session layer."""
    from fishnet_tpu.cache.store import AnalysisCache

    async def scenario():
        cache = AnalysisCache("serve-test-identity")
        app = _cache_app(cache)
        host, port = await app.start("127.0.0.1", 0)
        try:
            status, headers, first = await _http(
                host, port, "POST", "/analyse", _analysis_body("c-1")
            )
            assert status == 200
            assert headers["x-fishnet-cache"] == "miss"
            status, headers, second = await _http(
                host, port, "POST", "/analyse", _analysis_body("c-2")
            )
            assert status == 200
            assert headers["x-fishnet-cache"] == "hit"
            assert _searched(first) == _searched(second)
            assert cache.stats.hits == 1 and cache.stats.fills == 1
        finally:
            await app.drain_and_stop()

    asyncio.run(scenario())


def test_cache_header_partial_and_absent_when_off():
    """A request mixing one cached and one cold position answers
    `partial`; with the cache off the header is absent entirely."""
    from fishnet_tpu.cache.store import AnalysisCache

    async def scenario():
        cache = AnalysisCache("serve-test-identity")
        app = _cache_app(cache)
        host, port = await app.start("127.0.0.1", 0)
        try:
            await _http(host, port, "POST", "/analyse",
                        _analysis_body("p-1"))
            mixed = {
                "id": "p-2",
                "positions": [
                    {"fen": STARTPOS, "moves": ["e2e4"]},  # cached by p-1
                    {"fen": STARTPOS, "moves": []},  # cold
                ],
                "depth": 2,
            }
            status, headers, _ = await _http(
                host, port, "POST", "/analyse", mixed
            )
            assert status == 200
            assert headers["x-fishnet-cache"] == "partial"
        finally:
            await app.drain_and_stop()

        off = _cache_app(None)
        host, port = await off.start("127.0.0.1", 0)
        try:
            status, headers, _ = await _http(
                host, port, "POST", "/analyse", _analysis_body("p-3")
            )
            assert status == 200
            assert "x-fishnet-cache" not in headers
        finally:
            await off.drain_and_stop()

    asyncio.run(scenario())


def test_healthz_reports_cache_counters():
    """/healthz carries the live cache counters when the cache is on,
    and an explicit null when it is off."""
    from fishnet_tpu.cache.store import AnalysisCache

    async def scenario():
        cache = AnalysisCache("serve-test-identity")
        app = _cache_app(cache)
        host, port = await app.start("127.0.0.1", 0)
        try:
            await _http(host, port, "POST", "/analyse",
                        _analysis_body("h-1"))
            await _http(host, port, "POST", "/analyse",
                        _analysis_body("h-2"))
            status, _, health = await _http(host, port, "GET", "/healthz")
            assert status == 200
            c = health["cache"]
            assert c["hits"] == 1 and c["misses"] == 1
            assert c["fills"] == 1 and c["entries"] == 1
            assert c["hit_ratio"] == 0.5
        finally:
            await app.drain_and_stop()

        off = _cache_app(None)
        host, port = await off.start("127.0.0.1", 0)
        try:
            status, _, health = await _http(host, port, "GET", "/healthz")
            assert status == 200 and health["cache"] is None
        finally:
            await off.drain_and_stop()

    asyncio.run(scenario())
