"""Single source of truth for every FISHNET_TPU_* environment variable.

The first rounds hand-threaded engine config through five layers and
sprinkled 14 env vars across ~40 scattered `os.environ` read sites.
This registry pins each variable once — name, type, default, doc line,
and whether it is *engine-affecting* (changes search results or engine
behavior, so it must reach the supervised engine host child process) —
and every read in the codebase goes through the typed accessors below.

The registry is enforced statically by `python -m fishnet_tpu.lint`
(config-coherence rule family): a direct `os.environ` read of a
FISHNET_TPU_* name anywhere else, an unregistered name, a stale
docs/config.md table, or a supervisor spawn path that stops forwarding
the engine-affecting vars all fail the gate. Keep this module pure
stdlib — the linter and conftest import it before JAX exists.

IMPORTANT for the linter: the SETTINGS tuple below must stay a literal
(string/bool literals only, no computed values) — the lint extracts it
by AST, without importing arbitrary project code.

Boolean grammar (normalized; the pre-registry sites disagreed on "0" vs
"" vs "1"): unset or empty string means "use the default"; "0", "false",
"no", "off" (case-insensitive) mean False; anything else means True.

Generate the docs table with:  python -m fishnet_tpu.utils.settings
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

PREFIX = "FISHNET_TPU_"

_FALSE_WORDS = ("0", "false", "no", "off")


@dataclass(frozen=True)
class Setting:
    """One registered environment variable.

    kind: "bool" | "int" | "str" | "csv-int" — drives the typed accessor
    and the generated docs table. default is stored in string form ("":
    no default / unset means None for str and csv-int kinds).
    engine: True when the variable changes engine behavior or search
    results and therefore must be forwarded to the supervised engine
    host child (engine/supervisor.py applies engine_env() on spawn).
    """

    name: str
    kind: str
    default: str
    doc: str
    engine: bool = False


# ---------------------------------------------------------------- registry
#
# PURE LITERALS ONLY in this tuple — the lint reads it via AST.

SETTINGS: Tuple[Setting, ...] = (
    Setting(
        name="FISHNET_TPU_MAX_PLY",
        kind="int",
        default="32",
        doc="Static search stack depth; compile cost scales with it. "
            "Tests/CPU smoke runs set a small value.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_HELPERS",
        kind="int",
        default="4",
        doc="Lazy-SMP helper lanes per analysed position "
            "(engine/tpu.py); 1 disables helpers entirely.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_MAX_LANES",
        kind="int",
        default="1024",
        doc="Per-dispatch lane ceiling (v5e VMEM cliff at ~1024 lanes, "
            "docs/tpu-hang.md round 5).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_REFILL",
        kind="bool",
        default="1",
        doc="Continuous lane refill: the engine keeps the compiled step "
            "at full width by splicing queued positions into DONE lanes "
            "at segment boundaries (engine/tpu.py LaneScheduler); 0 "
            "restores strict chunk-serial dispatch.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_MESH_REFILL",
        kind="bool",
        default="1",
        doc="Continuous lane refill on MESH hosts: the LaneScheduler "
            "drives the shard_map'd segment/refill callables "
            "(parallel/mesh.py) so each device resplices its own lanes "
            "locally; 0 pins meshed engines back to strict chunk-serial "
            "dispatch. No effect on single-device hosts or with "
            "FISHNET_TPU_REFILL=0.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_MESH_HOSTS",
        kind="int",
        default="1",
        doc="Number of jax.distributed processes forming ONE logical "
            "engine over a multi-host mesh (parallel/distributed.py). "
            "1 (default) keeps the single-process mesh path; > 1 makes "
            "the engine call jax.distributed.initialize before first "
            "device use and build its mesh over the GLOBAL device set. "
            "Requires FISHNET_TPU_MESH_COORDINATOR; see docs/mesh.md.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_MESH_COORDINATOR",
        kind="str",
        default="",
        doc="host:port of the jax.distributed coordinator (process 0) "
            "when FISHNET_TPU_MESH_HOSTS > 1. The host-level boundary "
            "exchange (parallel/distributed.py HostExchange) rides one "
            "port above this.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_MESH_PROCESS_ID",
        kind="int",
        default="0",
        doc="This process's id in [0, FISHNET_TPU_MESH_HOSTS) for "
            "jax.distributed.initialize. Process 0 hosts the "
            "coordinator and (in a pod: fleet member) sits inside the "
            "fleet coordinator; workers run the same dispatch sequence "
            "(docs/mesh.md runbook).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_NARROW_FLOOR",
        kind="int",
        default="64",
        doc="search_batch_resumable power-of-two narrowing floor: live "
            "batches never narrow below this width (each width is a "
            "separate XLA program).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_SEGMENT",
        kind="int",
        default="20000",
        doc="Cap on device steps per resumable segment between host "
            "checks (deadline / narrowing / refill boundaries). A "
            "scheduler session's segment also ends on the step its last "
            "primary lane parks.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_REPLAY",
        kind="bool",
        default="1",
        doc="Crash-safe session recovery (engine/supervisor.py): the "
            "host streams per-position results as partial frames into "
            "the supervisor's session journal, and after a kill the "
            "respawned child is handed only the unfinished suffix of "
            "the chunk (with bisection/quarantine for repeat offenders); "
            "0 restores whole-chunk retry semantics.",
    ),
    Setting(
        name="FISHNET_TPU_BISECT_MAX",
        kind="int",
        default="12",
        doc="Child-death budget per chunk for the supervisor's recovery "
            "ladder (replay retries + bisection splits + quarantine "
            "probes); isolating one poison position in a 6-position "
            "chunk costs up to 7 deaths.",
    ),
    Setting(
        name="FISHNET_TPU_QUARANTINE",
        kind="bool",
        default="1",
        doc="Route bisection-isolated poison positions to the CPU "
            "fallback individually while the rest of the chunk stays on "
            "the TPU path (engine/supervisor.py quarantine list); 0 "
            "lets repeat offenders fail the chunk instead.",
    ),
    Setting(
        name="FISHNET_TPU_ASPIRATION",
        kind="csv-int",
        default="",
        doc="Override aspiration window half-width schedule, e.g. "
            "\"15,120\" (docs/depth.md: measured default).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_SELECT_UPDATES",
        kind="bool",
        default="1",
        doc="Per-lane dynamic row writes as one-hot masked selects "
            "(default) instead of scatter (docs/tpu-hang.md device "
            "fault + 20x step cost; the modes are bit-identical).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_NO_PRUNING",
        kind="bool",
        default="0",
        doc="Disable null-move pruning, LMR and futility pruning "
            "(debug/A-B lever; the oracle mirrors the active mode).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_DTYPE",
        kind="str",
        default="",
        doc="Quantize NNUE weights, of a float params type (board768, a "
            "float StockfishNet or a two-net DualNet; accumulators stay "
            "float32): "
            "\"bf16\" for MXU-native inputs; \"int8\" is experimental, "
            "board768 only and additionally gated. A .nnue file's integer "
            "nets refuse \"bf16\" (the engine does not start).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_EXPERIMENTAL_INT8",
        kind="bool",
        default="0",
        doc="Unlock the int8 fixed-point ladder (measured a NET LOSS "
            "vs f32 at production shapes, round-5 bench).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_WARMUP_BUCKETS",
        kind="csv-int",
        default="",
        doc="Trim the warmup lane-bucket set, e.g. \"16\" for CPU "
            "smoke runs where each extra compile costs minutes.",
    ),
    Setting(
        name="FISHNET_TPU_WARMUP_VARIANTS",
        kind="str",
        default="auto",
        doc="Variant programs to precompile: comma list, \"all\", "
            "\"none\", or \"auto\" (all on accelerators, none on CPU).",
    ),
    Setting(
        name="FISHNET_TPU_TRACE_DIR",
        kind="str",
        default="",
        doc="Enable the trace timeline (obs/trace.py) and write flight-"
            "recorder dumps into this directory on child death, progress "
            "stall, or breaker trip; unset keeps tracing off (the "
            "default: one attribute check per site, zero events).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_TRACE_SAMPLE",
        kind="str",
        default="1.0",
        doc="Fraction of requests that get per-request lifecycle "
            "tracing (request-scoped spans + flow links, obs/trace.py "
            "sampled()): a float in [0, 1]. The decision hashes the "
            "trace_id, so every process traces the same subset of "
            "requests. Only meaningful with FISHNET_TPU_TRACE_DIR set.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_TRACE_BUF",
        kind="int",
        default="65536",
        doc="Trace ring-buffer capacity in events (obs/trace.py); the "
            "ring keeps the most recent events, so this bounds how far "
            "back a flight-recorder dump can see.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_METRICS_PORT",
        kind="int",
        default="0",
        doc="Serve the metrics registry (obs/metrics.py) as Prometheus "
            "text on this loopback port; 0 (default) disables the "
            "endpoint.",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_HOST",
        kind="str",
        default="127.0.0.1",
        doc="Bind address for the analysis-serving endpoint "
            "(`fishnet-tpu serve`, fishnet_tpu/serve/). The default is "
            "loopback; bind a routable address only behind your own "
            "auth/TLS front proxy.",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_PORT",
        kind="int",
        default="9670",
        doc="TCP port for the analysis-serving endpoint; 0 binds an "
            "OS-assigned ephemeral port (smoke tests parse the "
            "\"listening on\" line).",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_MAX_INFLIGHT",
        kind="int",
        default="768",
        doc="Admission controller: maximum positions admitted into the "
            "engine concurrently across all tenants (fishnet_tpu/serve/"
            "admission.py); sized to the lane pool.",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_MAX_QUEUE",
        kind="int",
        default="256",
        doc="Admission controller: positions allowed to wait for a free "
            "in-flight slot before new requests are shed with HTTP 429 "
            "(bounded waiting room, hardest-deadline-first admission).",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_TIMEOUT_MS",
        kind="int",
        default="8000",
        doc="Default and maximum per-request deadline for served "
            "analysis/bestmove requests; a request's own timeout_ms is "
            "clamped to this.",
    ),
    Setting(
        name="FISHNET_TPU_SERVE_DRAIN_S",
        kind="int",
        default="20",
        doc="Graceful-drain grace period on SIGTERM/SIGINT: the server "
            "stops accepting, finishes in-flight requests for up to this "
            "many seconds, flushes stats, then exits.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_MEMBERS",
        kind="str",
        default="local*1",
        doc="Fleet member specs, comma-separated (fishnet_tpu/fleet/): "
            "'local' or 'local*N' for SupervisedEngine-managed host "
            "children on this machine, 'http://HOST:PORT' (or bare "
            "HOST:PORT) for a remote `fishnet-tpu serve` endpoint. "
            "Used when the coordinator is started without an explicit "
            "--fleet-members.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_REDISPATCH_MAX",
        kind="int",
        default="3",
        doc="Re-dispatch rounds the fleet coordinator may spend per "
            "chunk after member losses before the chunk fails; each "
            "round re-sends only the lost member's un-acked positions "
            "to survivors (exactly-once ledger, fleet/coordinator.py).",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_LOSS_WINDOW",
        kind="int",
        default="30",
        doc="Seconds a lost fleet member sits out of admission after a "
            "member-loss event before the least-backlog planner "
            "considers it again (its supervisor's own respawn backoff "
            "still applies underneath).",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_RETRY_MAX",
        kind="int",
        default="4",
        doc="In-dispatch retry attempts for transient remote faults "
            "(connect refused, timeout before the request was written) "
            "before the dispatch escalates to a member-loss event "
            "(fleet/faults.py taxonomy). Retries use jittered "
            "exponential backoff bounded by the chunk's deadline slack.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_COOLDOWN_MAX",
        kind="int",
        default="600",
        doc="Cap in seconds on the fleet's escalating loss cooldown: "
            "each consecutive loss doubles the member's cooldown from "
            "FISHNET_TPU_FLEET_LOSS_WINDOW up to this bound, so a "
            "permanently-dead member costs only periodic probes.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_PROBATION",
        kind="bool",
        default="1",
        doc="Probed readmission: after its cooldown a lost member "
            "enters probation and must pass a healthz probe plus one "
            "canary chunk before the planner gives it real work again. "
            "0 restores blind readmission at cooldown expiry.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_HEDGE",
        kind="bool",
        default="0",
        doc="Hedged dispatch: when a dispatched sub-chunk's deadline "
            "slack drops below FISHNET_TPU_FLEET_HEDGE_SLACK_MS and a "
            "healthy member has free capacity, duplicate the unfinished "
            "positions to it; first answer wins via the exactly-once "
            "fingerprint ledger, the loser is discarded and counted. "
            "Results are bit-identical with hedging on or off.",
    ),
    Setting(
        name="FISHNET_TPU_FLEET_HEDGE_SLACK_MS",
        kind="int",
        default="1500",
        doc="Deadline slack threshold for hedged dispatch: a sub-chunk "
            "still unanswered when this many milliseconds remain before "
            "its chunk deadline is duplicated to a free member (only "
            "with FISHNET_TPU_FLEET_HEDGE=1).",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE",
        kind="bool",
        default="0",
        doc="Elastic capacity (fleet/autoscaler.py): run the autoscaling "
            "control loop next to `serve --fleet`, adding local members "
            "under admission-queue pressure or deadline misses and "
            "draining them back to the floor when idle. Capacity changes "
            "never alter search results.",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_MIN",
        kind="int",
        default="1",
        doc="Autoscaler member-count floor: the loop never drains below "
            "this many members, and only ever drains members it added "
            "itself (the configured fleet is the floor).",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_MAX",
        kind="int",
        default="4",
        doc="Autoscaler member-count ceiling: scale-up stops here no "
            "matter the backlog (the cost clamp).",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_INTERVAL_MS",
        kind="int",
        default="1000",
        doc="Autoscaler control-loop tick interval in milliseconds; "
            "hysteresis counts ticks, so the up/down reaction times are "
            "UP_TICKS x this and DOWN_TICKS x this.",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_UP_QUEUE",
        kind="int",
        default="1",
        doc="Admission-queue depth (queued positions) that counts as "
            "scale-up pressure for a tick; a deadline miss recorded "
            "during the tick counts as pressure regardless.",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_UP_TICKS",
        kind="int",
        default="2",
        doc="Consecutive pressure ticks before the autoscaler adds a "
            "member (scale-up hysteresis).",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_DOWN_TICKS",
        kind="int",
        default="5",
        doc="Consecutive fully-idle ticks (no queue, no in-flight, no "
            "member backlog) before the autoscaler drains a member "
            "(scale-down hysteresis; deliberately slower than scale-up "
            "so one burst costs at most one up/down reversal).",
    ),
    Setting(
        name="FISHNET_TPU_AUTOSCALE_LOSS_COOLDOWN_S",
        kind="int",
        default="30",
        doc="Scale-down veto window after a member-loss event: the loop "
            "never drains while any member is in cooldown/probing/"
            "probation or within this many seconds of the last loss "
            "(never shrink mid-recovery-ladder).",
    ),
    Setting(
        name="FISHNET_TPU_AOT",
        kind="bool",
        default="1",
        doc="AOT program assets (fishnet_tpu/aot/): preload serialized "
            "compiled search programs from the registry instead of "
            "JIT-compiling at warmup; misses fall back to JIT with a "
            "warning. 0 disables the registry entirely.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_AOT_DIR",
        kind="str",
        default="",
        doc="AOT program store root "
            "(default ~/.cache/fishnet-tpu/aot). `python -m fishnet_tpu "
            "pack` writes here, engines read at boot.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_AOT_EXPORT",
        kind="bool",
        default="0",
        doc="Background re-export: on an AOT miss, serialize the "
            "JIT-compiled executable back into the store so the next "
            "boot hits (pack sets this implicitly).",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_NO_COMPILE_CACHE",
        kind="bool",
        default="0",
        doc="Disable the persistent XLA compile cache entirely "
            "(e.g. read-only filesystems). Its place is the standard "
            "JAX_COMPILATION_CACHE_DIR, or <checkout>/.cache/xla.",
    ),
    Setting(
        name="FISHNET_TPU_UPDATE_URL",
        kind="str",
        default="https://fishnet-tpu-releases.s3.amazonaws.com/",
        doc="Release bucket for the auto-updater "
            "(tests point it at a local fixture).",
    ),
    Setting(
        name="FISHNET_TPU_CACHE",
        kind="bool",
        default="1",
        doc="Fleet-wide analysis memoization (fishnet_tpu/cache/, "
            "docs/caching.md): memoize search results keyed on position "
            "content + search shape + engine identity, consulted at "
            "serve admission and the fleet coordinator. Cold positions "
            "are bit-identical to cache-off; hits return an "
            "at-least-as-deep stored result.",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_DIR",
        kind="str",
        default="",
        doc="Analysis-cache root "
            "(default ~/.cache/fishnet-tpu/cache): the sqlite index "
            "and per-entry payload files that let hits survive "
            "restarts (FISHNET_TPU_CACHE_PERSIST=0 skips the tier "
            "entirely).",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_PERSIST",
        kind="bool",
        default="1",
        doc="Persist analysis-cache entries to FISHNET_TPU_CACHE_DIR "
            "(0: the bounded in-memory LRU only; nothing survives a "
            "restart).",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_MAX_ENTRIES",
        kind="int",
        default="4096",
        doc="In-memory LRU bound on cached analysis results (entries); "
            "evictions never touch the persisted tier.",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_MAX_MB",
        kind="int",
        default="32",
        doc="In-memory LRU bound on cached analysis results "
            "(payload megabytes); whichever of the entry/byte bounds "
            "trips first evicts.",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_DISK_MAX_ENTRIES",
        kind="int",
        default="65536",
        doc="Persisted-tier bound: oldest index rows (and their payload "
            "files) are dropped beyond this count.",
    ),
    Setting(
        name="FISHNET_TPU_CACHE_TT",
        kind="bool",
        default="0",
        doc="TT warm slices (cache/ttwarm.py): persist the "
            "transposition-table rows a search earned around each "
            "position, keyed by opening-prefix fingerprint, and splice "
            "them back in when a chunk starts on the same prefix. "
            "Warm-started searches may return better-informed answers "
            "than cold ones, so this sits OUTSIDE the cache's "
            "bit-identity guarantee — off by default.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_CACHE_TT_PREFIX",
        kind="int",
        default="8",
        doc="Opening-prefix length (plies) for TT warm-slice keys: "
            "positions sharing this many first moves share a slice.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_SANITIZE",
        kind="bool",
        default="0",
        doc="Runtime invariant sanitizer (utils/sanitize.py, "
            "docs/sanitizer.md): poison donated jit inputs so "
            "use-after-donate raises on CPU too, assert the "
            "exactly-once delivery ledgers never double-deliver, "
            "reject unknown in-flight stage labels, and verify "
            "sampled TT warm rows decode to storable entries. "
            "Captured at import/construction — flipping it needs a "
            "fresh process. Off (default) adds zero overhead.",
        engine=True,
    ),
    Setting(
        name="FISHNET_TPU_PERF_LEDGER",
        kind="str",
        default="",
        doc="Path of the perf-ledger sqlite file (obs/perf.py, "
            "docs/perf.md). Empty (default) resolves to perf_ledger.db "
            "at the checkout root, falling back to "
            "~/.cache/fishnet-tpu/perf_ledger.db for installed "
            "packages. bench.py appends every RESULT row here; "
            "tools/perf_report.py reads the history back for the "
            "regression gate.",
    ),
    Setting(
        name="FISHNET_TPU_PERF_WINDOW",
        kind="int",
        default="5",
        doc="Rolling-baseline window for the perf regression detector: "
            "how many prior same-fingerprint ledger runs average into "
            "the baseline each metric is compared against.",
    ),
    Setting(
        name="FISHNET_TPU_PERF_BAND",
        kind="str",
        default="0.02",
        doc="Minimum relative noise band (fraction) for deterministic "
            "counter metrics in tools/perf_report.py --check; the "
            "band widens automatically to 2x the baseline's relative "
            "stddev when history is noisier than this floor. "
            "Wall-clock metrics use a fixed 15% band and never gate.",
    ),
    Setting(
        name="FISHNET_TPU_PERF_PROGRAMS",
        kind="bool",
        default="1",
        doc="Program cost accounting (obs/perf.py): read "
            "cost_analysis()/memory_analysis() off AOT-compiled "
            "executables wherever a Compiled object already exists "
            "(bench precompile, AOT registry export) and export "
            "fishnet_program_* gauges. Capture never triggers an "
            "extra compile; off skips even the cheap reads.",
    ),
)

_BY_NAME: Dict[str, Setting] = {s.name: s for s in SETTINGS}


class UnregisteredSetting(KeyError):
    """A FISHNET_TPU_* name was used without a registry entry."""


def lookup(name: str) -> Setting:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise UnregisteredSetting(
            f"{name} is not registered in fishnet_tpu/utils/settings.py"
        ) from None


def raw(name: str) -> Optional[str]:
    """The raw environment value, or the registered default when unset
    or empty. Returns None when there is no default either. Reads the
    environment on every call — tests mutate it between imports."""
    s = lookup(name)
    value = os.environ.get(name)
    if value is None or value == "":
        value = s.default
    return value if value != "" else None


def get_bool(name: str) -> bool:
    s = lookup(name)
    if s.kind != "bool":
        raise TypeError(f"{name} is registered as {s.kind}, not bool")
    value = raw(name)
    if value is None:
        return False
    return value.strip().lower() not in _FALSE_WORDS


def get_int(name: str) -> int:
    s = lookup(name)
    if s.kind != "int":
        raise TypeError(f"{name} is registered as {s.kind}, not int")
    value = raw(name)
    assert value is not None, f"{name} registered as int must have a default"
    return int(value)


def get_str(name: str) -> Optional[str]:
    s = lookup(name)
    if s.kind != "str":
        raise TypeError(f"{name} is registered as {s.kind}, not str")
    return raw(name)


def get_csv_int(name: str) -> Optional[Tuple[int, ...]]:
    """Comma-separated ints, or None when unset (callers keep their own
    built-in fallback schedule)."""
    s = lookup(name)
    if s.kind != "csv-int":
        raise TypeError(f"{name} is registered as {s.kind}, not csv-int")
    value = raw(name)
    if value is None:
        return None
    return tuple(int(x) for x in value.split(",") if x)


def is_set(name: str) -> bool:
    """True when the variable is explicitly present and non-empty in the
    environment (regardless of defaults)."""
    lookup(name)
    return bool(os.environ.get(name))


def engine_settings() -> Tuple[Setting, ...]:
    return tuple(s for s in SETTINGS if s.engine)


def engine_env() -> Dict[str, str]:
    """Environment overlay carrying every engine-affecting variable that
    is explicitly set, for the supervised engine host child. The child
    would inherit the parent environment anyway; applying this overlay
    explicitly makes the invariant visible — and statically checkable
    (lint rule config-engine-wire) — so a future sanitized-env spawn
    can't silently strand engine config on the parent side."""
    out: Dict[str, str] = {}
    for s in engine_settings():
        value = os.environ.get(s.name)
        if value:
            out[s.name] = value
    return out


# ------------------------------------------------------------ docs table


def render_rows(rows: List[tuple]) -> str:
    """Render the docs/config.md table from (name, kind, default, doc,
    engine) tuples. Shared by the runtime generator below and the lint's
    AST-extracted staleness check, so the two can never disagree."""
    lines = [
        "# Configuration reference",
        "",
        "Every `FISHNET_TPU_*` environment variable, generated from the",
        "single registry in `fishnet_tpu/utils/settings.py` — do not edit",
        "by hand; regenerate with:",
        "",
        "```",
        "python -m fishnet_tpu.utils.settings > docs/config.md",
        "```",
        "",
        "Boolean grammar: unset/empty uses the default; `0`, `false`,",
        "`no`, `off` (case-insensitive) mean false; anything else true.",
        "Engine-affecting variables are forwarded to the supervised",
        "engine host child on spawn (`settings.engine_env()`).",
        "",
        "| Variable | Type | Default | Engine-affecting | Description |",
        "|---|---|---|---|---|",
    ]
    for name, kind, default, doc, engine in rows:
        default_cell = f"`{default}`" if default != "" else "*(unset)*"
        lines.append(
            f"| `{name}` | {kind} | {default_cell} | "
            f"{'yes' if engine else 'no'} | {doc} |"
        )
    return "\n".join(lines + NET_SETTINGS_MD) + "\n"


# the nets are chosen by client settings (fishnet.ini / the `run` flags,
# client/configure.py), not by environment variables; listed here so that
# one page names every knob a deployment sets
NET_SETTINGS_MD = [
    "",
    "## Nets (client settings, not environment variables)",
    "",
    "| Setting | Flag | Description |",
    "|---|---|---|",
    "| `tpu_weights` | `--tpu-weights` (engine host: `--weights`) | NNUE "
    "weights: this program's .npz, or a Stockfish .nnue file, searched in "
    "the integers the file holds (int16 accumulator rows, int8 layer "
    "weights, Stockfish's integer ladder; models/nnue_import.py "
    "StockfishNetInt). |",
    "| `tpu_weights_small` | `--tpu-weights-small` (engine host: "
    "`--weights-small`) | The small Stockfish .nnue net beside a .nnue "
    "`tpu_weights` (upstream's EvalFileSmall): both nets resident, every "
    "eval chooses between them by Stockfish's material rule and hands an "
    "unsure small value back to the big net, both in their files' "
    "integers (models/nnue_import.py DualNetInt). |",
]


def render_config_md() -> str:
    return render_rows(
        [(s.name, s.kind, s.default, s.doc, s.engine) for s in SETTINGS]
    )


if __name__ == "__main__":
    import sys

    sys.stdout.write(render_config_md())
