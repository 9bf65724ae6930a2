"""Content-addressed store of serialized compiled search programs.

The executable artifact is ``jax.experimental.serialize_executable``
output — a pickled (payload, in_tree, out_tree) triple, zlib-compressed
— because deserialize_and_load restores a *Compiled* object that runs
with zero recompilation. (jax.export round-trips StableHLO, which
recompiles on first call — useless for warmup-free boot.)

Store layout (one directory per store fingerprint, so incompatible
jax/config combinations never collide)::

    <root>/<fingerprint12>/manifest.json
    <root>/<fingerprint12>/blobs/<program-key>.bin

The fallback ladder, in order, for every wrapped call:

1. in-memory compiled executable → call it (zero host overhead after
   first load);
2. on-disk artifact → sha256-verify, deserialize, cache, call;
3. corrupted/unloadable artifact → quarantine (rename ``.bad``), warn,
   fall through;
4. miss → **plain JIT**, with a one-time warning per program key and an
   ``aot.miss`` trace instant. A miss is never an error: the engine
   degrades to exactly the pre-AOT behaviour.

In export mode (``pack``, or FISHNET_TPU_AOT_EXPORT=1 for background
re-export on a live host) a miss additionally lowers + compiles through
the wrapper and serializes the executable to the store from a
background thread, so the next boot hits.

All serialize/deserialize calls live in THIS module — fishnet-lint's
``aot-unkeyed-export`` rule rejects them anywhere else, which is what
keeps every artifact behind the fingerprint key.

Security note: artifacts are pickles and a bundle is trusted exactly
like the code that loads it — ship bundles over the same channel as the
wheel/zipapp, never from untrusted input.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
import pickle
import sys
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..obs import trace
from ..utils import settings, syncstats
from . import keys

try:  # pragma: no cover - exercised implicitly on every import
    from jax.experimental import serialize_executable as _serialize_executable
except Exception:  # pragma: no cover - jax builds without the module
    _serialize_executable = None

# 2: blobs carry the ids of the devices the program was compiled for
MANIFEST_VERSION = 2
MANIFEST_NAME = "manifest.json"

# Sentinel cached after a key already missed: later calls skip the disk
# probe and go straight to jit (whose own executable cache is warm by
# then — the JIT fallback pays the compile exactly once).
_MISS = object()

REGISTRY: Optional["Registry"] = None

_install_lock = threading.Lock()
_monitoring_installed = False
_compile_count = 0
_compile_current = threading.local()


def _on_compile_duration(event: str, duration: float, **kw: Any) -> None:
    # jax.monitoring fires this for every XLA backend compile, including
    # ~10ms eager-op compiles; mirror each one into the trace timeline
    # (retroactively — the compile just ended) so tools/aot_smoke.py can
    # assert a warmed boot ran no big compiles.
    if "backend_compile" not in event:
        return
    global _compile_count
    _compile_count += 1
    try:
        # the perf layer's compile-duration stream (docs/perf.md):
        # count + cumulative seconds, next to the trace mirror below
        from ..obs.metrics import REGISTRY

        REGISTRY.counter(
            "fishnet_compiles_total",
            "XLA backend compiles observed via jax.monitoring",
        ).inc()
        REGISTRY.counter(
            "fishnet_compile_seconds_total",
            "Cumulative XLA backend compile wall time",
        ).inc(float(duration))
    except (ImportError, TypeError, ValueError):
        pass  # metrics are best-effort; the trace mirror still runs
    # which part of the program asked for it: the listener fires in the
    # compiling thread, and a thread serving an engine's chunk carries
    # that engine's counters and the phase or submit step it is in
    totals, where = syncstats.where()
    if totals is not None:
        key = "compiles_" + where
        totals[key if key in totals else "compiles_other"] += 1
        totals["compile_ms"] += float(duration) * 1000.0
    rec = trace.RECORDER
    if rec is not None:
        dur_us = float(duration) * 1e6
        rec.complete(
            "xla_backend_compile",
            trace.now_us() - dur_us,
            dur_us,
            cat="compile",
            args={
                "event": event,
                "program": getattr(_compile_current, "program", ""),
                "where": where if totals is not None else None,
            },
        )


def _install_monitoring() -> None:
    global _monitoring_installed
    with _install_lock:
        if _monitoring_installed:
            return
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(
                _on_compile_duration
            )
            _monitoring_installed = True
        except Exception:
            _monitoring_installed = True  # no monitoring API: stay quiet


def compile_count() -> int:
    """Backend compiles observed process-wide since install."""
    return _compile_count


def default_dir() -> str:
    return os.path.join(
        os.path.expanduser("~"), ".cache", "fishnet-tpu", "aot"
    )


class Registry:
    """One process's view of an on-disk program store."""

    def __init__(self, root: str, export: bool = False,
                 logger: Optional[Callable[[str], None]] = None):
        self.root = os.path.abspath(os.path.expanduser(root))
        self.export = bool(export)
        self._log = logger
        if self.export:
            # serialize() of an executable that was LOADED from the XLA
            # persistent compile cache yields an incomplete payload that
            # fails at deserialize ("Symbols not found", observed on
            # XLA:CPU) — an exporter must compile for real, so the
            # tier-2 cache goes off for this whole process
            from ..utils.compile_cache import disable_compile_cache

            disable_compile_cache()
        self.fingerprint = keys.store_fingerprint()
        self.digest = keys.fingerprint_digest(self.fingerprint)
        self.dir = os.path.join(self.root, self.digest[:12])
        self.blob_dir = os.path.join(self.dir, "blobs")
        self._lock = threading.Lock()
        self._warned: set = set()
        self._pending: List[threading.Thread] = []
        self.stats = {
            "hits": 0, "misses": 0, "loads": 0,
            "errors": 0, "exports": 0,
        }
        self.manifest = self._read_manifest()
        # A registry over an empty store in read-only mode has nothing
        # to offer: deactivate so the wrappers are pure passthrough.
        self.active = self.export or bool(self.manifest["programs"])
        if not self.manifest["programs"] and not self.export:
            self._note_rejections()

    # -- store I/O ---------------------------------------------------

    def _read_manifest(self) -> Dict[str, Any]:
        path = os.path.join(self.dir, MANIFEST_NAME)
        try:
            with open(path, "r", encoding="utf-8") as f:
                man = json.load(f)
            if man.get("version") != MANIFEST_VERSION:
                self._warn(
                    f"aot: manifest version {man.get('version')!r} != "
                    f"{MANIFEST_VERSION}; ignoring store {self.dir}"
                )
                raise ValueError("version skew")
            man.setdefault("programs", {})
            man.setdefault("covers", [])
            return man
        except (OSError, ValueError, KeyError):
            return {
                "version": MANIFEST_VERSION,
                "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
                "fingerprint": self.fingerprint,
                "covers": [],
                "programs": {},
            }

    def _note_rejections(self) -> None:
        # The explicit compat-rejection path: name WHY sibling stores
        # (other fingerprints under the same root) don't apply here,
        # instead of silently booting cold.
        try:
            entries = os.listdir(self.root)
        except OSError:
            return
        for d in entries:
            if d == self.digest[:12]:
                continue
            mpath = os.path.join(self.root, d, MANIFEST_NAME)
            if not os.path.isfile(mpath):
                continue
            try:
                with open(mpath, "r", encoding="utf-8") as f:
                    theirs = json.load(f).get("fingerprint") or {}
            except (OSError, ValueError, AttributeError):
                continue
            diff = keys.diff_fingerprints(self.fingerprint, theirs)
            self._warn(
                f"aot: store {d} is incompatible with this process "
                f"({'; '.join(diff) or 'fingerprint digest mismatch'}) "
                f"— booting cold (JIT)"
            )

    def _write_manifest_locked(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        path = os.path.join(self.dir, MANIFEST_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(self.manifest, f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    def set_covers(self, covers: List[str]) -> None:
        with self._lock:
            self.manifest["covers"] = sorted(set(covers))
            self._write_manifest_locked()

    def flush(self) -> None:
        """Join pending export threads (pack calls this before exit)."""
        while True:
            with self._lock:
                if not self._pending:
                    return
                t = self._pending.pop()
            # serialization of one executable is seconds; a wedged
            # thread must not hang pack (the bundle just won't cover
            # that program — the boot-side ladder degrades to JIT)
            t.join(timeout=120.0)
            if t.is_alive():
                self._warn(f"aot: export thread {t.name} still running "
                           f"after 120s; leaving it behind")

    # -- logging -----------------------------------------------------

    def _warn(self, msg: str, once_key: Optional[str] = None) -> None:
        if once_key is not None:
            with self._lock:
                if once_key in self._warned:
                    return
                self._warned.add(once_key)
        if self._log is not None:
            try:
                self._log(msg)
                return
            except Exception:
                # broken logger sink: fall through to stderr so the
                # warning is never swallowed
                print(f"W: {msg}", file=sys.stderr, flush=True)
                return
        print(f"W: {msg}", file=sys.stderr, flush=True)

    # -- call path ---------------------------------------------------

    def call(self, prog: "AotProgram", args: tuple, kwargs: dict) -> Any:
        try:
            bound = prog.signature.bind(*args, **kwargs)
            bound.apply_defaults()
            ordered = list(bound.arguments.items())
            statics = {n: v for n, v in ordered if n in prog.static_names}
            dynamics = tuple(
                v for n, v in ordered if n not in prog.static_names
            )
            key, meta = keys.program_key(
                prog.name, statics, prog.extra_static, dynamics
            )
        except Exception as e:
            self._warn(
                f"aot: {prog.name}: cannot canonicalize call ({e!r}); "
                f"falling back to JIT", once_key=f"canon:{prog.name}",
            )
            return self._jit_call(prog, args, kwargs)

        cached = prog.cache.get(key)
        if cached is _MISS:
            return self._jit_call(prog, args, kwargs)
        if cached is not None:
            try:
                out = cached(*dynamics)
                self.stats["hits"] += 1
                return out
            except Exception as e:
                # Never let a stale artifact break a dispatch: evict and
                # degrade this key to JIT for the rest of the process.
                self.stats["errors"] += 1
                prog.cache[key] = _MISS
                self._warn(
                    f"aot: {prog.name}: preloaded executable rejected the "
                    f"call ({e!r}); evicted, falling back to JIT",
                    once_key=f"callerr:{key}",
                )
                return self._jit_call(prog, args, kwargs)

        entry = self.manifest["programs"].get(key)
        if entry is not None:
            compiled = self._load(key, entry)
            if compiled is not None:
                prog.cache[key] = compiled
                self.stats["loads"] += 1
                trace.instant(
                    "aot.load", "aot", program=prog.name, key=key[:12]
                )
                try:
                    out = compiled(*dynamics)
                    self.stats["hits"] += 1
                    return out
                except Exception as e:
                    self.stats["errors"] += 1
                    prog.cache[key] = _MISS
                    self._warn(
                        f"aot: {prog.name}: loaded executable rejected the "
                        f"call ({e!r}); falling back to JIT",
                        once_key=f"callerr:{key}",
                    )
                    return self._jit_call(prog, args, kwargs)

        return self._miss(prog, key, meta, ordered, dynamics, args, kwargs)

    def _jit_call(self, prog: "AotProgram", args: tuple,
                  kwargs: dict) -> Any:
        _compile_current.program = prog.name
        try:
            return prog.jit(*args, **kwargs)
        finally:
            _compile_current.program = ""

    def _miss(self, prog: "AotProgram", key: str, meta: Dict[str, str],
              ordered: List[Tuple[str, Any]], dynamics: tuple,
              args: tuple, kwargs: dict) -> Any:
        self.stats["misses"] += 1
        trace.instant("aot.miss", "aot", program=prog.name, key=key[:12])
        self._warn(
            f"aot: miss for {prog.name} [{key[:12]}] "
            f"(statics {meta['statics']}); compiling via JIT",
            once_key=f"miss:{key}",
        )
        if not (self.export and _serialize_executable is not None):
            prog.cache[key] = _MISS
            return self._jit_call(prog, args, kwargs)
        # Export mode: compile through lower() so we hold the Compiled
        # object to serialize, then answer the call with it.
        _compile_current.program = prog.name
        try:
            compiled = prog.jit.lower(*[v for _, v in ordered]).compile()
        except Exception as e:
            self._warn(
                f"aot: {prog.name}: lower/compile for export failed "
                f"({e!r}); serving the call via plain JIT",
                once_key=f"lower:{key}",
            )
            prog.cache[key] = _MISS
            return self._jit_call(prog, args, kwargs)
        finally:
            _compile_current.program = ""
        prog.cache[key] = compiled
        # program cost accounting (obs/perf.py): pack time is the one
        # moment every search jit and mesh callable passes through here
        # as a Compiled object, so the FLOPs/bytes/memory read is free
        try:
            if settings.get_bool("FISHNET_TPU_PERF_PROGRAMS"):
                from ..obs import perf as _perf

                _perf.record_program_cost(prog.name, compiled)
        except (ImportError, TypeError, ValueError):
            pass  # accounting is best-effort; the export still runs
        t = threading.Thread(
            target=self._export_one, args=(prog.name, key, meta, compiled),
            daemon=True, name=f"aot-export-{key[:8]}",
        )
        with self._lock:
            self._pending.append(t)
        t.start()
        return compiled(*dynamics)

    # -- artifacts ---------------------------------------------------

    def _load(self, key: str, entry: Dict[str, Any]) -> Optional[Any]:
        path = os.path.join(self.blob_dir, key + ".bin")
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError as e:
            self._warn(
                f"aot: artifact {key[:12]} listed in manifest but "
                f"unreadable ({e!r})", once_key=f"noblob:{key}",
            )
            return None
        if hashlib.sha256(blob).hexdigest() != entry.get("sha256"):
            self._quarantine(path, key, "sha256 mismatch")
            return None
        try:
            payload, in_tree, out_tree, device_ids = pickle.loads(
                zlib.decompress(blob)
            )
            # load onto the devices the program was compiled for: left
            # to its default, deserialize_and_load spreads a one-device
            # executable over every device of the backend and the first
            # call is rejected for its shard count
            by_id = {d.id: d for d in jax.devices()}
            with trace.span("aot.deserialize", "aot",
                            program=entry.get("entry", "?"), key=key[:12]):
                return _serialize_executable.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in device_ids],
                )
        except Exception as e:
            self._quarantine(path, key, repr(e))
            return None

    def _quarantine(self, path: str, key: str, why: str) -> None:
        self.stats["errors"] += 1
        try:
            os.replace(path, path + ".bad")
        except OSError:
            pass
        self._warn(
            f"aot: artifact {key[:12]} corrupt ({why}); quarantined as "
            f"{os.path.basename(path)}.bad, falling back to JIT",
            once_key=f"quarantine:{key}",
        )

    def _export_one(self, name: str, key: str, meta: Dict[str, str],
                    compiled: Any) -> None:
        try:
            payload, in_tree, out_tree = _serialize_executable.serialize(
                compiled
            )
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
            blob = zlib.compress(
                pickle.dumps((payload, in_tree, out_tree, device_ids)), 6
            )
        except Exception as e:
            # shard_map/unsupported executables may refuse serialization;
            # the program still runs (compiled is cached in memory).
            self._warn(
                f"aot: {name} [{key[:12]}] is not serializable ({e!r}); "
                f"bundle will not cover it", once_key=f"ser:{key}",
            )
            return
        try:
            os.makedirs(self.blob_dir, exist_ok=True)
            path = os.path.join(self.blob_dir, key + ".bin")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            with self._lock:
                self.manifest["programs"][key] = dict(
                    meta,
                    sha256=hashlib.sha256(blob).hexdigest(),
                    size=len(blob),
                )
                self._write_manifest_locked()
            self.stats["exports"] += 1
            trace.instant("aot.export", "aot", program=name, key=key[:12])
        except Exception as e:
            self._warn(f"aot: export of {name} [{key[:12]}] failed ({e!r})")

    # -- reporting ---------------------------------------------------

    def covers(self) -> set:
        return set(self.manifest.get("covers") or [])

    def report(self) -> Dict[str, Any]:
        return {
            "enabled": True,
            "export": self.export,
            "fingerprint": self.digest[:12],
            "dir": self.dir,
            "programs": len(self.manifest["programs"]),
            "covers": sorted(self.covers()),
            **self.stats,
        }


class AotProgram:
    """Transparent wrapper around one jitted entry point.

    Callable exactly like the jit it wraps (same signature, donation and
    static handling included). With no active registry it IS the jit
    plus one global check; with one, calls route through the fallback
    ladder above. Keep the module-level variable names of wrapped jits
    unchanged (`_run_segment_jit` etc.) — fishnet-lint's conc-host-sync
    device-producer list matches on those names.
    """

    __slots__ = ("name", "jit", "signature", "static_names",
                 "extra_static", "cache", "_plain")

    def __init__(self, name: str, jit_fn: Any, fun: Callable,
                 static_names: tuple = (),
                 extra_static: Optional[Dict[str, Any]] = None):
        self.name = name
        self.jit = jit_fn
        self.signature = inspect.signature(fun)
        self.static_names = frozenset(static_names)
        self.extra_static = dict(extra_static or {})
        self.cache: Dict[str, Any] = {}
        # *args/**kwargs signatures cannot be canonicalized to a stable
        # positional form — such programs stay plain JIT forever.
        self._plain = any(
            p.kind in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
            for p in self.signature.parameters.values()
        )

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        reg = REGISTRY
        if reg is None or not reg.active or self._plain:
            return self.jit(*args, **kwargs)
        return reg.call(self, args, kwargs)

    def lower(self, *args: Any, **kwargs: Any) -> Any:
        return self.jit.lower(*args, **kwargs)


def wrap(name: str, jit_fn: Any, fun: Callable, static_names: tuple = (),
         extra_static: Optional[Dict[str, Any]] = None) -> AotProgram:
    """Wrap a jitted entry point for AOT load/export."""
    return AotProgram(name, jit_fn, fun, static_names, extra_static)


def install(root: str, export: bool = False,
            logger: Optional[Callable[[str], None]] = None) -> Registry:
    """Install a registry at an explicit root (pack / tests)."""
    global REGISTRY
    _install_monitoring()
    with _install_lock:
        REGISTRY = Registry(root, export=export, logger=logger)
        return REGISTRY


def uninstall() -> None:
    global REGISTRY
    with _install_lock:
        REGISTRY = None


def install_from_settings(
    logger: Optional[Callable[[str], None]] = None,
) -> Optional[Registry]:
    """Install the process registry from FISHNET_TPU_AOT* settings.

    Idempotent; called from the TpuEngine constructor so every
    deployment shape (host child, in-process client, serve, fleet,
    bench) gets the same behaviour. Returns None when AOT is disabled
    or the serialize API is unavailable.
    """
    global REGISTRY
    _install_monitoring()
    with _install_lock:
        if REGISTRY is not None:
            return REGISTRY
        if _serialize_executable is None:
            return None
        if not settings.get_bool("FISHNET_TPU_AOT"):
            return None
        root = settings.get_str("FISHNET_TPU_AOT_DIR") or default_dir()
        export = settings.get_bool("FISHNET_TPU_AOT_EXPORT")
        REGISTRY = Registry(root, export=export, logger=logger)
        return REGISTRY


def boot_report() -> Dict[str, Any]:
    """Small JSON-safe summary for ready frames and logs."""
    reg = REGISTRY
    if reg is None or not reg.active:
        return {"enabled": False, "programs": 0, "covers": []}
    return reg.report()


def warm_covers(*need: str) -> bool:
    """True iff a non-exporting registry's bundle covers `need`.

    The warmup early-outs key on this: an exporting registry must never
    skip warmup (pack IS the warmup), and an empty store covers nothing.
    """
    reg = REGISTRY
    if reg is None or not reg.active or reg.export:
        return False
    if not reg.manifest["programs"]:
        return False
    return set(need) <= reg.covers()
