"""fishnet-perf: the persistent performance ledger + program cost accounting.

Point-in-time observability (the trace timeline, the SLO histograms)
answers "where did this run spend its time"; this module answers the
longitudinal question — "is this build faster or slower than the last
twenty" — which nothing in the repo could answer before: BENCH_rNN.json
artifacts were written by the bench driver and never compared.

Three pieces:

- **PerfLedger** — a sqlite ``perf_ledger`` table (one row per
  (run, bench row, metric)) keyed on git sha + the AOT store
  fingerprint digest (aot/keys.py), so values measured under different
  jax/backend/topology/settings envelopes are never gated against each
  other. The schema/insert helpers are shared with the client's
  stats.db sink (client/stats.py ensure_perf_table/record_perf) so one
  sqlite file can carry both time series. ``backfill()`` ingests the
  checked-in ``BENCH_r*.json`` + ``MULTICHIP_r*.json`` artifacts
  (idempotently — stable run ids + INSERT OR REPLACE), so trend history
  starts populated; ``emit_bench_round()`` writes the next
  ``BENCH_rNN.json`` from the ledger instead of by hand.

- **Program cost accounting** — ``program_cost(compiled)`` reads
  ``cost_analysis()`` FLOPs/bytes-accessed and ``memory_analysis()``
  sizes off an AOT-compiled executable; ``record_program_cost`` exports
  them as ``fishnet_program_*`` gauges. Capture sites are the places a
  Compiled object already exists (bench.py's precompile, the AOT
  registry's export path) — never an extra compile.

- **build_info()** — git sha + jax/jaxlib versions + backend + device
  kind/count, registered as the ``fishnet_build_info`` gauge (value 1,
  fields in the HELP line — the registry has no label system), stamped
  into every ledger row and into trace dump metadata: the join key for
  cross-host comparison.

Pure stdlib at module scope (same constraint as obs/metrics.py and
obs/trace.py). Only claim_device() and what follows it touch JAX: a
supervised client or serve parent must leave the chip to its engine
host child, so everything else here works from recorded facts.
tools/perf_report.py holds the direction table and the regression
detector that reads this ledger; docs/perf.md is the contract.
"""
from __future__ import annotations

import json
import os
import re
import sqlite3
import subprocess
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "PERF_TABLE_SQL",
    "PerfLedger",
    "backfill_rows_from_artifacts",
    "build_info",
    "default_ledger_path",
    "claim_device",
    "ensure_perf_table",
    "env_fingerprint",
    "flatten_result",
    "insert_perf_rows",
    "program_cost",
    "record_program_cost",
    "register_build_info",
    "split_mesh_rows",
    "live_snapshot",
    "note_device",
]

# One row per (run, bench row, metric). `seq` orders runs within one
# ledger (assigned at insert: max+1); the UNIQUE key + INSERT OR
# REPLACE make re-ingesting the same run id (backfill re-runs) a no-op
# rather than a duplicate series.
PERF_TABLE_SQL = (
    "CREATE TABLE IF NOT EXISTS perf_ledger ("
    " id INTEGER PRIMARY KEY AUTOINCREMENT,"
    " run_id TEXT NOT NULL,"
    " seq INTEGER NOT NULL,"
    " timestamp INTEGER NOT NULL,"
    " git_sha TEXT NOT NULL DEFAULT '',"
    " fingerprint TEXT NOT NULL DEFAULT '',"
    " build_info TEXT NOT NULL DEFAULT '{}',"
    " source TEXT NOT NULL DEFAULT 'bench',"
    " bench_row TEXT NOT NULL,"
    " metric TEXT NOT NULL,"
    " value REAL NOT NULL,"
    " UNIQUE (run_id, bench_row, metric))"
)

_BENCH_ARTIFACT_RE = re.compile(r"^BENCH_r(\d+)\.json$")
_MULTICHIP_ARTIFACT_RE = re.compile(r"^MULTICHIP_r(\d+)\.json$")
_CONFIG_LINE_RE = re.compile(r"^bench config ([A-Za-z0-9_.\-]+): (\{.*)$")
_SEARCH_NODES_RE = re.compile(r"search nodes (\d+)")

_build_info_cache: Optional[Dict[str, Any]] = None
_device_report: Optional[Dict[str, Any]] = None
_owns_device = False  # claim_device() ran: this process may ask JAX


# --------------------------------------------------------------- build info


def repo_root() -> Optional[str]:
    """The checkout root (the directory holding bench.py), or None when
    running from an installed/zipped package."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if os.path.isfile(os.path.join(root, "bench.py")):
        return root
    return None


def git_sha(short: int = 12) -> str:
    root = repo_root()
    if root is None:
        return ""
    try:
        out = subprocess.run(
            ["git", "-C", root, "rev-parse", f"--short={short}", "HEAD"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, timeout=10.0,
        )
    except (OSError, subprocess.SubprocessError):
        return ""
    return out.stdout.strip() if out.returncode == 0 else ""


def claim_device() -> Dict[str, Any]:
    """{platform, kind, count} of the device as JAX reports it, recorded
    for build_info(). This INITIALISES the JAX backend, so only the one
    process that owns the engine may call it (TpuEngine does): a local
    chip belongs to the first process that touches it, and a client
    parent that asked would lock its own engine host child out."""
    global _owns_device
    import jax

    devs = jax.devices()
    _owns_device = True
    report = {
        "platform": str(devs[0].platform),
        "kind": str(devs[0].device_kind),
        "count": len(devs),
    }
    note_device(report)
    return report


def note_device(report: Dict[str, Any]) -> None:
    """Record the device the engine runs on, for build_info(): a
    client/serve parent passes its supervised host's `ready` frame."""
    global _device_report, _build_info_cache
    _device_report = dict(report)
    _build_info_cache = None


def _dist_version(name: str) -> str:
    from importlib import metadata

    try:
        return metadata.version(name)
    except metadata.PackageNotFoundError:
        return ""


def build_info(refresh: bool = False) -> Dict[str, Any]:
    """git sha + jax/jaxlib versions + backend + device kind/count.
    Never imports JAX: versions come from the installed distributions'
    metadata, and the device is what claim_device()/note_device()
    recorded — blank until the engine, or its host's ready frame, has
    reported one."""
    global _build_info_cache
    if _build_info_cache is not None and not refresh:
        return dict(_build_info_cache)
    dev = _device_report or {}
    info: Dict[str, Any] = {
        "git_sha": git_sha(),
        "jax": _dist_version("jax"),
        "jaxlib": _dist_version("jaxlib"),
        "backend": str(dev.get("platform", "")),
        "device_kind": str(dev.get("kind", "")),
        "device_count": int(dev.get("count", 0)),
    }
    _build_info_cache = dict(info)
    return info


def register_build_info(registry=None) -> Dict[str, Any]:
    """Register the ``fishnet_build_info`` gauge (value 1; the
    identifying fields ride in the HELP line — standard Prometheus
    build-info practice, minus the label system this registry doesn't
    have). Returns the info dict."""
    info = build_info()
    from . import metrics as obs_metrics  # lazy: avoid cycles
    obs_metrics.set_build_info(info, registry=registry)
    return info


def env_fingerprint() -> str:
    """The AOT store fingerprint digest (aot/keys.py) truncated to 12
    hex chars — the env compatibility envelope a ledger row was
    measured under. It asks JAX for its devices, so it is empty in a
    process that has not claimed the device (rows without a fingerprint
    are compared report-only, never gated)."""
    if not _owns_device:
        return ""
    from ..aot import keys

    return keys.fingerprint_digest(keys.store_fingerprint())[:12]


# ----------------------------------------------------------------- flatten


def flatten_result(result: Dict[str, Any],
                   prefix: str = "") -> Dict[str, float]:
    """One bench RESULT dict → flat metric→value rows. Nested dicts
    (occupancy summaries, per-ndev tables) flatten to dotted names;
    strings and lists are skipped (a list's aggregate belongs in the
    RESULT row itself, e.g. mean_live_occupancy next to
    shard_live_occupancy)."""
    out: Dict[str, float] = {}
    for k, v in result.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            out[key] = 1.0 if v else 0.0
        elif isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict):
            out.update(flatten_result(v, prefix=key + "."))
    return out


# ------------------------------------------------------------------ ledger


def default_ledger_path() -> str:
    """FISHNET_TPU_PERF_LEDGER if set; else perf_ledger.db at the
    checkout root; else under ~/.cache/fishnet-tpu."""
    try:
        from ..utils import settings

        configured = settings.get_str("FISHNET_TPU_PERF_LEDGER")
    except Exception:
        configured = ""
    if configured:
        return configured
    root = repo_root()
    if root is not None:
        return os.path.join(root, "perf_ledger.db")
    return os.path.join(
        os.path.expanduser("~"), ".cache", "fishnet-tpu", "perf_ledger.db"
    )


def ensure_perf_table(db: sqlite3.Connection) -> None:
    db.execute(PERF_TABLE_SQL)


def insert_perf_rows(
    db: sqlite3.Connection,
    run_id: str,
    rows: Dict[str, Dict[str, float]],
    *,
    source: str = "bench",
    sha: Optional[str] = None,
    fingerprint: Optional[str] = None,
    info: Optional[Dict[str, Any]] = None,
    timestamp: Optional[int] = None,
) -> int:
    """Shared insert used by PerfLedger and the client's StatsRecorder
    sink. `rows` maps bench_row → {metric: value}. Returns rows
    written. Re-inserting an existing run_id replaces its values and
    keeps its seq (idempotent backfill)."""
    ensure_perf_table(db)
    cur = db.execute(
        "SELECT seq FROM perf_ledger WHERE run_id = ? LIMIT 1", (run_id,)
    ).fetchone()
    if cur is not None:
        seq = int(cur[0])
    else:
        top = db.execute("SELECT MAX(seq) FROM perf_ledger").fetchone()
        seq = (int(top[0]) + 1) if top and top[0] is not None else 1
    if sha is None:
        sha = git_sha()
    if fingerprint is None:
        fingerprint = env_fingerprint()
    info_json = json.dumps(info or {}, sort_keys=True)
    if timestamp is None:
        # report timestamp correlated with external logs — wall clock
        # is the sanctioned form here (same idiom as client/stats.py)
        timestamp = int(time.time())  # fishnet-lint: disable=obs-wall-clock
    n = 0
    for bench_row, metrics in rows.items():
        for metric, value in sorted(metrics.items()):
            db.execute(
                "INSERT OR REPLACE INTO perf_ledger"
                " (run_id, seq, timestamp, git_sha, fingerprint,"
                "  build_info, source, bench_row, metric, value)"
                " VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?, ?)",
                (run_id, seq, timestamp, sha, fingerprint, info_json,
                 source, bench_row, metric, float(value)),
            )
            n += 1
    db.commit()
    return n


class PerfLedger:
    """One sqlite perf ledger. All readers/writers go through here (or
    through the same helpers on the client's stats.db connection)."""

    def __init__(self, db: sqlite3.Connection, path: str = "") -> None:
        self.db = db
        self.path = path
        ensure_perf_table(db)
        db.commit()

    @classmethod
    def open(cls, path: Optional[str] = None) -> "PerfLedger":
        """Open (creating if needed) the ledger at `path` / the default
        path; falls back to an in-memory ledger when the path is
        unwritable (a read-only checkout must never crash bench)."""
        p = path or default_ledger_path()
        try:
            os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
            db = sqlite3.connect(p)
            return cls(db, p)
        except (OSError, sqlite3.Error):
            return cls(sqlite3.connect(":memory:"), ":memory:")

    def close(self) -> None:
        try:
            self.db.close()
        except sqlite3.Error:
            pass

    # ------------------------------------------------------------ write

    def ingest_run(self, run_id: str, rows: Dict[str, Dict[str, float]],
                   **kw: Any) -> int:
        return insert_perf_rows(self.db, run_id, rows, **kw)

    def ingest_results(self, run_id: str, results: Dict[str, Any],
                       **kw: Any) -> int:
        """Raw bench RESULT dicts (bench_row → RESULT json) → one
        ledger run: per-ndev tables split into their own rows, nested
        summaries flattened to dotted metric names."""
        rows: Dict[str, Dict[str, float]] = {}
        for name, res in results.items():
            if not isinstance(res, dict):
                continue
            rest = split_mesh_rows(rows, name, res)
            flat = flatten_result(rest)
            if flat:
                rows[name] = flat
        if not rows:
            return 0
        return self.ingest_run(run_id, rows, **kw)

    def backfill(self, root: Optional[str] = None) -> int:
        """Ingest the checked-in BENCH_r*.json + MULTICHIP_r*.json
        artifacts. Stable run ids (`backfill:BENCH_r03`) + REPLACE
        semantics make this idempotent. Backfilled rows carry no env
        fingerprint — the detector compares them report-only."""
        root = root or repo_root()
        if root is None:
            return 0
        n = 0
        for name, rows in backfill_rows_from_artifacts(root):
            n += self.ingest_run(
                f"backfill:{name}", rows, source="backfill",
                sha="", fingerprint="", info={"artifact": name},
            )
        return n

    # ------------------------------------------------------------- read

    def runs(self) -> List[Dict[str, Any]]:
        """Every run, ordered by seq: run_id/seq/timestamp/git_sha/
        fingerprint/source plus its row count."""
        try:
            cur = self.db.execute(
                "SELECT run_id, seq, MIN(timestamp), MIN(git_sha),"
                " MIN(fingerprint), MIN(source), COUNT(*)"
                " FROM perf_ledger GROUP BY run_id, seq ORDER BY seq"
            )
        except sqlite3.Error:
            return []
        return [
            {"run_id": r[0], "seq": int(r[1]), "timestamp": int(r[2]),
             "git_sha": r[3], "fingerprint": r[4], "source": r[5],
             "metrics": int(r[6])}
            for r in cur.fetchall()
        ]

    def latest_run(self) -> Optional[Dict[str, Any]]:
        runs = self.runs()
        return runs[-1] if runs else None

    def run_metrics(self, run_id: str) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        try:
            cur = self.db.execute(
                "SELECT bench_row, metric, value FROM perf_ledger"
                " WHERE run_id = ? ORDER BY bench_row, metric", (run_id,)
            )
        except sqlite3.Error:
            return out
        for bench_row, metric, value in cur.fetchall():
            out.setdefault(bench_row, {})[metric] = float(value)
        return out

    def history(self, bench_row: str, metric: str, *,
                fingerprint: Optional[str] = None,
                before_seq: Optional[int] = None,
                limit: int = 20) -> List[Tuple[int, float]]:
        """(seq, value) series for one metric, oldest first — the
        rolling-baseline input. With `fingerprint`, only runs measured
        under that exact env envelope count."""
        q = ("SELECT seq, value FROM perf_ledger"
             " WHERE bench_row = ? AND metric = ?")
        args: List[Any] = [bench_row, metric]
        if fingerprint is not None:
            q += " AND fingerprint = ?"
            args.append(fingerprint)
        if before_seq is not None:
            q += " AND seq < ?"
            args.append(before_seq)
        q += " ORDER BY seq DESC LIMIT ?"
        args.append(limit)
        try:
            rows = self.db.execute(q, args).fetchall()
        except sqlite3.Error:
            return []
        return [(int(s), float(v)) for s, v in reversed(rows)]

    # ----------------------------------------------------- BENCH emission

    def next_round(self, root: Optional[str] = None) -> int:
        root = root or repo_root() or "."
        top = 0
        try:
            names = os.listdir(root)
        except OSError:
            names = []
        for name in names:
            m = _BENCH_ARTIFACT_RE.match(name)
            if m:
                top = max(top, int(m.group(1)))
        return top + 1

    def emit_bench_round(self, run_id: str,
                         root: Optional[str] = None) -> Optional[str]:
        """Write the next BENCH_rNN.json from this ledger run: the same
        artifact shape the bench driver recorded by hand in rounds 1–5
        (n/rc/tail/parsed), plus build-info + env fingerprint and the
        full per-row metric table."""
        root = root or repo_root()
        if root is None:
            return None
        rows = self.run_metrics(run_id)
        if not rows:
            return None
        meta = next(
            (r for r in self.runs() if r["run_id"] == run_id), None)
        headline = rows.get("headline", {})
        tail_lines = [
            f"bench config {name}: {json.dumps(metrics, sort_keys=True)}"
            for name, metrics in sorted(rows.items()) if name != "headline"
        ]
        parsed = {
            "metric": "batched alpha-beta+NNUE nodes/sec/chip",
            "value": headline.get("value", 0.0),
            "unit": "nodes/sec",
            "vs_baseline": headline.get("vs_baseline", 0.0),
        } if headline else None
        if parsed is not None:
            tail_lines.append(json.dumps(parsed))
        n = self.next_round(root)
        artifact = {
            "n": n,
            "cmd": "perf-ledger",
            "rc": 0,
            "run_id": run_id,
            "git_sha": (meta or {}).get("git_sha", ""),
            "fingerprint": (meta or {}).get("fingerprint", ""),
            "build_info": build_info(),
            "rows": rows,
            "tail": "\n".join(tail_lines) + "\n",
            "parsed": parsed,
        }
        path = os.path.join(root, f"BENCH_r{n:02d}.json")
        try:
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(artifact, fh, indent=1)
            os.replace(tmp, path)
        except OSError:
            return None
        return path


# ---------------------------------------------------------------- backfill


def backfill_rows_from_artifacts(
        root: str) -> List[Tuple[str, Dict[str, Dict[str, float]]]]:
    """(artifact name, bench_row → metrics) per checked-in artifact,
    in round order — BENCH_r*.json first, then MULTICHIP_r*.json."""
    out: List[Tuple[str, Dict[str, Dict[str, float]]]] = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    bench = sorted(
        (int(m.group(1)), n) for n in names
        if (m := _BENCH_ARTIFACT_RE.match(n))
    )
    multi = sorted(
        (int(m.group(1)), n) for n in names
        if (m := _MULTICHIP_ARTIFACT_RE.match(n))
    )
    for _, name in bench:
        rows = _parse_bench_artifact(os.path.join(root, name))
        if rows:
            out.append((os.path.splitext(name)[0], rows))
    for _, name in multi:
        rows = _parse_multichip_artifact(os.path.join(root, name))
        if rows:
            out.append((os.path.splitext(name)[0], rows))
    return out


def _load_json(path: str) -> Optional[dict]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            obj = json.load(fh)
    except (OSError, ValueError):
        return None
    return obj if isinstance(obj, dict) else None


def _parse_bench_artifact(path: str) -> Dict[str, Dict[str, float]]:
    """One driver BENCH_rNN.json → bench rows. The tail text holds
    `bench config NAME: {json}` lines (one per matrix row) and the
    final stdout headline JSON; `parsed` (when the driver captured it)
    holds the same headline. Ledger-emitted artifacts (this module's
    own emission) carry an explicit `rows` table and are read directly."""
    obj = _load_json(path)
    if obj is None:
        return {}
    rows: Dict[str, Dict[str, float]] = {}
    if isinstance(obj.get("rows"), dict):
        for name, metrics in obj["rows"].items():
            if isinstance(metrics, dict):
                flat = flatten_result(metrics)
                if flat:
                    rows[str(name)] = flat
        return rows
    tail = obj.get("tail") or ""
    for line in str(tail).splitlines():
        m = _CONFIG_LINE_RE.match(line.strip())
        if m:
            try:
                res = json.loads(m.group(2))
            except ValueError:
                continue
            if isinstance(res, dict):
                flat = flatten_result(split_mesh_rows(rows, m.group(1),
                                                      res))
                if flat:
                    rows[m.group(1)] = flat
            continue
        stripped = line.strip()
        if stripped.startswith("{") and '"metric"' in stripped:
            try:
                head = json.loads(stripped)
            except ValueError:
                continue
            if isinstance(head, dict) and "value" in head:
                rows["headline"] = flatten_result(
                    {k: head[k] for k in ("value", "vs_baseline")
                     if k in head})
    parsed = obj.get("parsed")
    if "headline" not in rows and isinstance(parsed, dict) \
            and "value" in parsed:
        rows["headline"] = flatten_result(
            {k: parsed[k] for k in ("value", "vs_baseline") if k in parsed})
    if not rows and "rc" in obj:
        # a failed/timed-out round still ingests: its exit code is the
        # whole story
        rows["artifact"] = {"rc": float(obj.get("rc") or 0)}
    return rows


def split_mesh_rows(rows: Dict[str, Dict[str, float]], name: str,
                    res: dict) -> dict:
    """A mesh-scaling-shaped result (its "ndev" key maps device count →
    per-count RESULT row) becomes one bench row per device count — the
    deterministic scaling gate wants per-ndev series, not dotted names.
    Everything else passes through untouched. (A stage's own RESULT
    carries "ndev" as an int, which this deliberately ignores.)"""
    ndev = res.get("ndev")
    if isinstance(ndev, dict):
        for count, row in ndev.items():
            if isinstance(row, dict):
                flat = flatten_result(row)
                if flat:
                    rows[f"{name}_ndev{count}"] = flat
        return {k: v for k, v in res.items() if k != "ndev"}
    return res


def _parse_multichip_artifact(path: str) -> Dict[str, Dict[str, float]]:
    """One MULTICHIP_rNN.json ({n_devices, rc, ok, skipped, tail}) →
    a single row: ok flag + dry-run search nodes when present."""
    obj = _load_json(path)
    if obj is None or obj.get("skipped"):
        return {}
    metrics: Dict[str, float] = {
        "ok": 1.0 if obj.get("ok") else 0.0,
        "rc": float(obj.get("rc") or 0),
    }
    m = _SEARCH_NODES_RE.search(str(obj.get("tail") or ""))
    if m:
        metrics["nodes"] = float(m.group(1))
    ndev = obj.get("n_devices") or 0
    return {f"multichip_ndev{ndev}": metrics}


# -------------------------------------------------------- program costs


def program_cost(compiled: Any) -> Dict[str, float]:
    """FLOPs / bytes-accessed / memory sizes off one jax Compiled
    object. Tolerates every historical cost_analysis() return shape
    (dict, or a one-element list of dicts) and missing analyses
    (backends without implementations return {} fields)."""
    out: Dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = ca.get("flops")
            if isinstance(flops, (int, float)):
                out["flops"] = float(flops)
            nbytes = ca.get("bytes accessed")
            if isinstance(nbytes, (int, float)):
                out["bytes_accessed"] = float(nbytes)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for metric, attr in (
            ("peak_bytes", "temp_size_in_bytes"),
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("code_bytes", "generated_code_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if isinstance(v, (int, float)):
                out[metric] = float(v)
    except Exception:
        pass
    return out


def _program_slug(name: str) -> str:
    slug = re.sub(r"[^a-z0-9_]", "_", name.lower()).strip("_")
    return slug or "unnamed"


def record_program_cost(name: str, compiled: Any,
                        registry=None) -> Dict[str, float]:
    """Export one program's cost/memory analysis as fishnet_program_*
    gauges (name-embedded program label, the registry's idiom) and
    return the cost dict for ledger ingestion. Never raises."""
    cost = program_cost(compiled)
    if not cost:
        return cost
    try:
        if registry is None:
            from .metrics import REGISTRY as registry
        slug = _program_slug(name)
        for metric, value in cost.items():
            registry.gauge(
                f"fishnet_program_{metric}_{slug}",
                f"cost_analysis/memory_analysis {metric} for "
                f"program {name}",
            ).set(value)
    except Exception:
        pass
    return cost


# ------------------------------------------------------------ live surface


_SNAPSHOT_PREFIXES = (
    "fishnet_occupancy", "fishnet_lanes", "fishnet_queue",
    "fishnet_boundary", "fishnet_cache", "fishnet_serve_inflight",
    "fishnet_serve_queued", "fishnet_fleet_members", "fishnet_compile",
    "fishnet_autoscale_members",
)


def live_snapshot(registry=None,
                  ledger_path: Optional[str] = None) -> Dict[str, Any]:
    """The /debug/perf payload: build info, the per-program cost table,
    the perf-relevant slice of the metrics registry, and the last
    ledger run as the baseline column."""
    if registry is None:
        from .metrics import REGISTRY as registry
    snap = registry.snapshot()
    programs: Dict[str, Dict[str, float]] = {}
    metrics: Dict[str, float] = {}
    for name, value in sorted(snap.items()):
        if name.startswith("fishnet_program_"):
            rest = name[len("fishnet_program_"):]
            for metric in ("flops", "bytes_accessed", "peak_bytes",
                           "argument_bytes", "output_bytes", "code_bytes"):
                if rest.startswith(metric + "_"):
                    prog = rest[len(metric) + 1:]
                    programs.setdefault(prog, {})[metric] = value
                    break
        elif name.startswith(_SNAPSHOT_PREFIXES):
            metrics[name] = value
    cache_hits = snap.get("fishnet_cache_hits", 0.0)
    cache_misses = snap.get("fishnet_cache_misses", 0.0)
    looked = cache_hits + cache_misses
    baseline: Optional[Dict[str, Any]] = None
    path: Optional[str] = ledger_path or default_ledger_path()
    if path != ":memory:" and not os.path.exists(path):
        path = None  # a debug read must not create the ledger
    try:
        ledger = PerfLedger.open(path) if path is not None else None
        if ledger is None:
            raise OSError("no ledger")
        try:
            last = ledger.latest_run()
            if last is not None:
                baseline = dict(last)
                baseline["rows"] = ledger.run_metrics(last["run_id"])
        finally:
            ledger.close()
    except Exception:
        baseline = None
    return {
        "build": build_info(),
        "fingerprint": env_fingerprint(),
        "programs": programs,
        "metrics": metrics,
        "cache_hit_ratio": round(cache_hits / looked, 4) if looked else None,
        "baseline": baseline,
    }
