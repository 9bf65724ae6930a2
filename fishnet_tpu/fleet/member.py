"""Fleet membership: the per-member ledger and the member-spec grammar.

A `FleetMember` wraps any Engine-protocol object with the coordinator's
bookkeeping: backlog (positions handed over, not yet answered), the
in-flight fingerprint set (what re-dispatches after a loss), the ack
journal (position fingerprint → wire response, fed by the supervisor's
`on_partial` hook for local members), and health (down-until cooldown,
drain flag, loss count).

Member specs are a comma-separated string (`FISHNET_TPU_FLEET_MEMBERS`
or `--fleet-members`):

    local            one SupervisedEngine-managed host child here
    local*4          four of them
    pod:2            one giant-B member spanning a 2-process
                     jax.distributed mesh (process 0 is the host child
                     here; workers join per the docs/mesh.md runbook)
    pod:2@h:1234     same, with an explicit coordinator address
    http://h:9670    a remote `fishnet-tpu serve` endpoint
    h:9670           same (bare host:port implies http)

Local members deliberately invert two supervisor defaults
(make_local_member): `bisect_max=0` so the recovery ladder escalates the
FIRST child death as an `EngineError` instead of respawn-and-bisect —
the fleet has survivors to re-dispatch to, which beats bisecting on a
possibly-sick host — and no fallback/quarantine, because masking a loss
inside the member would hide exactly the signal the coordinator's
exactly-once ledger is built on. Replay stays on: partial frames keep
streaming into the member journal, and `on_partial` mirrors each ack
into the fleet ledger so only genuinely un-acked positions re-run.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set

from ..client.ipc import WorkPosition
from ..client.logger import Logger
from ..utils import settings
from .remote import HttpEngine

# a local member's own breaker must never trip before the coordinator
# notices the loss — fleet health lives in the fleet ledger, not N
# private breakers with N private cooldowns
_MEMBER_BREAKER_THRESHOLD = 1_000_000


@dataclass
class FleetMember:
    """One engine plus the coordinator's ledger for it.

    Lifecycle (docs/fleet.md has the full diagram): an eligible member
    takes dispatches; a loss puts it in cooldown (`down_until`,
    escalating with `consecutive_losses`); when the cooldown expires it
    sits in *probation* — the coordinator must pass a healthz probe and
    one canary chunk through it (`probing` while that runs) before it
    is eligible again. A 429 shed parks it until `busy_until` without
    touching the loss ladder. `draining` excludes it from planning while
    in-flight work finishes, after which it can be removed.
    """

    name: str
    engine: object  # Engine protocol (go_multiple/close)
    kind: str = "local"  # "local" | "remote"
    backlog: int = 0  # positions dispatched, not yet answered
    inflight: Dict[str, WorkPosition] = field(default_factory=dict)
    acked: Dict[str, dict] = field(default_factory=dict)  # fp -> wire
    down_until: float = 0.0  # monotonic; loss cooldown
    busy_until: float = 0.0  # monotonic; 429 Retry-After backpressure
    draining: bool = False
    probation: bool = False  # must pass healthz + canary to re-enter
    probing: bool = False  # a probe is in flight right now
    losses: int = 0
    consecutive_losses: int = 0  # resets on a served sub-chunk
    canaries_ok: int = 0
    dispatched_positions: int = 0

    def available(self, now: Optional[float] = None) -> bool:
        """Eligible for new work: not draining, not in loss cooldown or
        probation, not shedding (429), breaker (if any) not open."""
        if self.draining:
            return False
        if now is None:
            now = time.monotonic()
        if now < self.down_until:
            return False
        if self.probation:
            return False
        if now < self.busy_until:
            return False
        if getattr(self.engine, "breaker_open", False):
            return False
        return True

    def probe_due(self, now: Optional[float] = None) -> bool:
        """Cooldown over, probation pending, no probe running yet."""
        if not self.probation or self.probing or self.draining:
            return False
        if now is None:
            now = time.monotonic()
        return now >= self.down_until

    def state(self, now: Optional[float] = None) -> str:
        """One-word lifecycle state for health tables and fleet-ctl."""
        if now is None:
            now = time.monotonic()
        if self.draining:
            return "draining"
        if now < self.down_until:
            return "cooldown"
        if self.probing:
            return "probing"
        if self.probation:
            return "probation"
        if now < self.busy_until:
            return "busy"
        if getattr(self.engine, "breaker_open", False):
            return "breaker-open"
        return "eligible"

    def health(self, now: Optional[float] = None) -> dict:
        """Flat health snapshot (docs/fleet.md: autoscaling signals)."""
        if now is None:
            now = time.monotonic()
        hb = getattr(self.engine, "heartbeat_age", None)
        # local members relay their host child's AOT boot report
        # (engine/host.py ready frame → supervisor.aot_report): an
        # autoscaler reading fleet health can tell warm boots (bundle
        # preloaded, seconds to first dispatch) from cold ones (minutes
        # of XLA compiles) and scale accordingly
        aot = getattr(self.engine, "aot_report", None)
        mesh = getattr(self.engine, "mesh_report", None)
        return {
            "name": self.name,
            "kind": self.kind,
            "mesh": mesh,
            "state": self.state(now),
            "available": self.available(now),
            "backlog": self.backlog,
            "inflight": len(self.inflight),
            "losses": self.losses,
            "consecutive_losses": self.consecutive_losses,
            "canaries_ok": self.canaries_ok,
            "draining": self.draining,
            "cooldown_s": max(self.down_until - now, 0.0),
            "busy_s": max(self.busy_until - now, 0.0),
            "heartbeat_age_s": hb,
            "aot": aot,
        }


def make_local_member(
    name: str,
    *,
    host_cmd: Optional[List[str]] = None,
    backend: str = "tpu",
    weights_path: Optional[str] = None,
    weights_small_path: Optional[str] = None,
    max_depth: Optional[int] = None,
    helper_lanes: Optional[int] = None,
    refill: Optional[bool] = None,
    mesh_refill: Optional[bool] = None,
    logger: Optional[Logger] = None,
    hb_interval: float = 1.0,
    hb_timeout: Optional[float] = None,
    backoff=None,
    env: Optional[dict] = None,
    stats_recorder=None,
) -> FleetMember:
    """A SupervisedEngine-backed member with loss-escalation policy.

    bisect_max=0 / quarantine=False / giant breaker threshold: the first
    child death raises out of `go_multiple` as the member-loss event the
    coordinator re-dispatches on (module docstring has the why). The
    member's partial journal still streams (replay=True) and every
    accepted ack is mirrored into `member.acked` via `on_partial`.

    AOT program assets need no member-level wiring: the FISHNET_TPU_AOT*
    settings are engine-affecting, so the supervisor's engine_env
    overlay forwards them into the host child, the child's TpuEngine
    preloads the bundle, and its ready-frame boot report surfaces here
    as `health()["aot"]` — a scale-out member on a warmed machine boots
    in seconds instead of recompiling every program.
    """
    from ..engine.supervisor import SupervisedEngine

    engine = SupervisedEngine(
        host_cmd,
        backend=backend,
        weights_path=weights_path,
        weights_small_path=weights_small_path,
        max_depth=max_depth,
        helper_lanes=helper_lanes,
        refill=refill,
        mesh_refill=mesh_refill,
        logger=logger,
        hb_interval=hb_interval,
        hb_timeout=hb_timeout,
        breaker_threshold=_MEMBER_BREAKER_THRESHOLD,
        fallback_factory=None,
        backoff=backoff,
        env=env,
        replay=True,
        bisect_max=0,
        quarantine=False,
        stats_recorder=stats_recorder,
    )
    member = FleetMember(name=name, engine=engine, kind="local")
    engine.on_partial = (
        lambda fp, wire: member.acked.__setitem__(fp, wire)
    )
    return member


# default jax.distributed coordinator for `pod:` members without an
# explicit @host:port (the host-level boundary exchange rides one port
# above it — parallel/distributed.py)
_POD_DEFAULT_COORDINATOR = "127.0.0.1:9791"


def parse_pod_spec(token: str) -> tuple:
    """'pod:N[@host:port]' → (hosts, coordinator address).

    N is the jax.distributed process count of the pod member's ONE
    logical engine; the member's host child runs as process 0 (it hosts
    the coordinator), workers N>0 are launched out-of-band per the
    docs/mesh.md runbook."""
    body = token[len("pod:"):]
    addr = _POD_DEFAULT_COORDINATOR
    if "@" in body:
        body, addr = body.split("@", 1)
    try:
        hosts = int(body)
    except ValueError:
        raise ValueError(
            f"fleet member spec {token!r}: host count after 'pod:' "
            "must be an integer"
        ) from None
    if hosts < 1:
        raise ValueError(
            f"fleet member spec {token!r}: host count must be >= 1"
        )
    host, _, port = addr.rpartition(":")
    if not host or not port.isdigit():
        raise ValueError(
            f"fleet member spec {token!r}: coordinator must be host:port"
        )
    return hosts, addr


def pod_member_env(hosts: int, coordinator: str) -> Dict[str, str]:
    """The engine-env overlay that turns a host child into pod process 0
    (engine/tpu.py calls parallel.distributed.ensure_initialized from
    these settings before first device use)."""
    return {
        "FISHNET_TPU_MESH_HOSTS": str(hosts),
        "FISHNET_TPU_MESH_COORDINATOR": coordinator,
        "FISHNET_TPU_MESH_PROCESS_ID": "0",
    }


def members_from_specs(
    spec: Optional[str] = None,
    *,
    local_factory: Optional[Callable[[str], FleetMember]] = None,
    pod_factory: Optional[Callable[[str, Dict[str, str]], FleetMember]] = None,
    logger: Optional[Logger] = None,
) -> List[FleetMember]:
    """Parse the member-spec grammar into live FleetMembers.

    `local_factory(name)` builds local members (callers close over their
    Config — app.py — or a fakehost command line — tests/chaos/bench);
    it defaults to a bare `make_local_member(name)` from registry
    settings. `pod_factory(name, env)` builds pod members — local
    members whose host child boots as process 0 of a multi-host mesh via
    the given engine-env overlay (pod_member_env). Remote specs become
    `HttpEngine` members directly.
    """
    if spec is None:
        spec = settings.get_str("FISHNET_TPU_FLEET_MEMBERS")
    log = logger or Logger()
    if local_factory is None:
        local_factory = lambda name: make_local_member(name)  # noqa: E731
    if pod_factory is None:
        pod_factory = (  # noqa: E731
            lambda name, env: make_local_member(name, env=env)
        )
    members: List[FleetMember] = []
    seen: Set[str] = set()
    locals_made = 0
    pods_made = 0
    for raw in spec.split(","):
        token = raw.strip()
        if not token:
            continue
        if token.startswith("pod:"):
            hosts, coord = parse_pod_spec(token)
            name = f"pod{pods_made}"
            pods_made += 1
            member = pod_factory(name, pod_member_env(hosts, coord))
            members.append(member)
        elif token == "local" or token.startswith("local*"):
            count = 1
            if "*" in token:
                try:
                    count = int(token.split("*", 1)[1])
                except ValueError:
                    raise ValueError(
                        f"fleet member spec {token!r}: count after "
                        "'local*' must be an integer"
                    ) from None
            if count < 1:
                raise ValueError(
                    f"fleet member spec {token!r}: count must be >= 1"
                )
            for _ in range(count):
                name = f"local{locals_made}"
                locals_made += 1
                members.append(local_factory(name))
        else:
            engine = HttpEngine(token)  # validates host:port
            name = f"{engine.host}:{engine.port}"
            if name in seen:
                raise ValueError(
                    f"fleet member spec lists {name} twice"
                )
            members.append(
                FleetMember(name=name, engine=engine, kind="remote")
            )
        seen.add(members[-1].name)
    if not members:
        raise ValueError(
            "fleet member spec is empty — set FISHNET_TPU_FLEET_MEMBERS "
            "or pass --fleet-members (e.g. 'local*2,http://host:9670')"
        )
    log.info(
        "fleet: %d member(s): %s"
        % (len(members), ", ".join(m.name for m in members))
    )
    return members
