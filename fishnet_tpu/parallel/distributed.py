"""Multi-host mesh lowering: jax.distributed boot, global-mesh helpers,
and addressable-shard-aware host I/O.

One logical engine spanning a pod slice means the Mesh covers devices on
SEVERAL processes. Device-side nothing changes — the segment/merge
callables (parallel/mesh.py) are collective-free by design, so each
process advances its addressable shards locally under the same compiled
program. What DOES change is every host touch point:

  * placement — `jax.device_put` cannot build a non-addressable global
    array from host data; `put_global` switches to
    `jax.make_array_from_callback`, where each process supplies only the
    shards it can see (every process holds the same host-side values, so
    the global array is consistent by construction).
  * fetches — `np.asarray` on a non-fully-addressable array raises. The
    streaming loops' per-boundary reads route through `fetch_summary` /
    `gather_rows`: ONE SyncStats-accounted fetch of the process's local
    shards (the one-fetch-per-boundary property from the pipelined
    scheduler holds per host), then a host-level allgather of the tiny
    payload over `HostExchange` — a plain TCP star on
    coordinator-port+1, no device collectives, so CPU meshes need no
    gloo/MPI build.

Every process must drive the SAME dispatch sequence (SPMD discipline);
the exchange gives every host an identical global boundary picture, so
identical code makes identical decisions. tools/mesh_smoke.py is the
2-process CI proof; docs/mesh.md has the topology matrix and runbook.
"""
from __future__ import annotations

import functools
import pickle
import socket
import struct
import threading
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import settings
from . import partition as _partition

_EXCHANGE: Optional["HostExchange"] = None
_INITIALIZED = False


def ensure_initialized(logger=None) -> bool:
    """Boot jax.distributed from the FISHNET_TPU_MESH_* settings.

    No-op (returns False) unless FISHNET_TPU_MESH_HOSTS > 1. Otherwise
    connects this process to the coordinator
    (FISHNET_TPU_MESH_COORDINATOR host:port, process id
    FISHNET_TPU_MESH_PROCESS_ID), starts the host-level boundary
    exchange one port above the coordinator, and returns True.
    Idempotent — callers sprinkle it before first device use."""
    global _INITIALIZED
    n = settings.get_int("FISHNET_TPU_MESH_HOSTS")
    if n <= 1:
        return False
    if _INITIALIZED:
        return True
    coord = settings.get_str("FISHNET_TPU_MESH_COORDINATOR")
    pid = settings.get_int("FISHNET_TPU_MESH_PROCESS_ID")
    if not coord or ":" not in coord:
        raise ValueError(
            "FISHNET_TPU_MESH_HOSTS > 1 requires "
            "FISHNET_TPU_MESH_COORDINATOR as host:port"
        )
    import jax

    # the XLA:CPU client refuses ANY computation spanning processes
    # unless a CPU collectives backend is configured — even though the
    # segment/merge callables are collective-free; gloo ships in jaxlib
    # and only coordinates the runtime here (TPU meshes ignore this)
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # older jaxlib without the knob; TPU pods don't need it
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=n, process_id=pid,
    )
    _INITIALIZED = True
    host, port = coord.rsplit(":", 1)
    _start_exchange(host, int(port) + 1, n, pid)
    if logger is not None:
        logger.info(
            "mesh: jax.distributed up — process %d/%d, coordinator %s"
            % (pid, n, coord)
        )
    return True


def host_exchange() -> "HostExchange":
    """The process-global boundary exchange; raises if the process is not
    a distributed-mesh participant."""
    if _EXCHANGE is None:
        raise RuntimeError(
            "no host exchange — multi-host paths require "
            "distributed.ensure_initialized() (FISHNET_TPU_MESH_HOSTS)"
        )
    return _EXCHANGE


def _start_exchange(host: str, port: int, num: int, pid: int) -> None:
    global _EXCHANGE
    _EXCHANGE = HostExchange(host, port, num, pid)


@functools.lru_cache(maxsize=None)
def spans_processes(mesh) -> bool:
    """True when the mesh's devices live on more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


@functools.lru_cache(maxsize=None)
def addressable_shards(mesh) -> Tuple[int, ...]:
    """Global shard indices (mesh device order) this process can see.

    Single-process meshes address everything; under jax.distributed the
    LaneScheduler admits new work only into these shards while its free
    lists keep GLOBAL shard indexing (engine/tpu.py)."""
    import jax

    me = jax.process_index()
    return tuple(
        i for i, d in enumerate(mesh.devices.flat) if d.process_index == me
    )


def global_mesh(axis: str = "dp"):
    """The Mesh over every device of every participating process —
    make_mesh already enumerates jax.devices(), which is global once
    jax.distributed is up."""
    from .mesh import make_mesh

    return make_mesh(axis=axis)


# -------------------------------------------------------------- placement


def put_global(mesh, x, spec):
    """Place host/local data as a (possibly multi-host) global array.

    Single-process: a plain device_put. Multi-process: every process
    holds the same full-size host value and contributes its addressable
    shards via jax.make_array_from_callback — no cross-host transfer."""
    sharding = _partition.named_sharding(mesh, spec)
    if not spans_processes(mesh):
        import jax

        return jax.device_put(x, sharding)
    import jax

    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def replicate_tree(mesh, tree):
    """Every leaf placed fully replicated on the global mesh (NNUE
    params before the first sharded dispatch)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: put_global(mesh, x, _partition.replicated_spec()), tree
    )


# ---------------------------------------------------------------- fetches


def fetch_summary(mesh, p_summ, stats, label: str = "summary"):
    """The stacked (ndev, local + SUM_TAIL, SUM_W) boundary summary
    (ops/search.py), on every host.

    Single-process: the usual one SyncStats fetch. Multi-process: ONE
    fetch of this process's addressable summary rows (keeping the
    one-fetch-per-boundary invariant per host), then a host-level
    allgather reassembles the global block identically everywhere."""
    if not spans_processes(mesh):
        return stats.fetch(p_summ, label)
    import jax.numpy as jnp

    shards = sorted(
        p_summ.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    rows = [s.index[0].start or 0 for s in shards]
    local = stats.fetch(
        jnp.concatenate([s.data for s in shards], axis=0), label
    )
    out = np.zeros(p_summ.shape, p_summ.dtype)
    seen = np.zeros(p_summ.shape[0], bool)
    for blob in host_exchange().allgather(pickle.dumps((rows, local))):
        peer_rows, peer_local = pickle.loads(blob)
        for j, r in enumerate(peer_rows):
            n = peer_local.shape[0] // len(peer_rows)
            out[r:r + n] = peer_local[j * n:(j + 1) * n]
            seen[r:r + n] = True
    if not seen.all():
        raise RuntimeError(
            "boundary exchange left summary shards unfilled: "
            f"{np.nonzero(~seen)[0].tolist()}"
        )
    return out


def gather_rows(mesh, x, rows, stats, label: str = "",
                pick: Optional[Callable[[Any], Any]] = None,
                tail: Tuple[int, ...] = (), dtype=np.int32) -> np.ndarray:
    """Global rows of a lane-sharded array, assembled on every host.

    `pick` maps a (local, ...) shard block to the slice actually wanted
    (e.g. lambda a: a[:, 0] for PV rows) BEFORE the device→host copy, so
    the fetch stays as small as the single-process jnp.take path. Each
    process fetches only rows its addressable shards own (one
    SyncStats-accounted fetch), then the host exchange fills in the
    rest. Returns (len(rows),) + tail, identical on every process."""
    import jax.numpy as jnp

    rows = np.asarray(rows, np.int64).reshape(-1)
    pick = pick if pick is not None else (lambda a: a)
    if not spans_processes(mesh):
        taken = jnp.take(pick(x), jnp.asarray(rows), axis=0)
        return np.asarray(stats.fetch(taken, label), dtype)
    owned_pos: List[np.ndarray] = []
    owned_vals = []
    for s in x.addressable_shards:
        start = s.index[0].start or 0
        stop = s.index[0].stop
        stop = start + s.data.shape[0] if stop is None else stop
        sel = np.nonzero((rows >= start) & (rows < stop))[0]
        if sel.size:
            owned_pos.append(sel)
            owned_vals.append(
                jnp.take(pick(s.data), jnp.asarray(rows[sel] - start),
                         axis=0)
            )
    if owned_vals:
        local = np.asarray(
            stats.fetch(jnp.concatenate(owned_vals, axis=0), label), dtype
        )
        pos = np.concatenate(owned_pos)
    else:
        local = np.zeros((0,) + tail, dtype)
        pos = np.zeros(0, np.int64)
    out = np.zeros((len(rows),) + tail, dtype)
    filled = np.zeros(len(rows), bool)
    for blob in host_exchange().allgather(pickle.dumps((pos, local))):
        peer_pos, peer_vals = pickle.loads(blob)
        out[peer_pos] = peer_vals
        filled[peer_pos] = True
    if not filled.all():
        raise RuntimeError(
            f"boundary exchange left rows unfilled: "
            f"{rows[~filled].tolist()}"
        )
    return out


# --------------------------------------------------------- host exchange


class HostExchange:
    """Tiny TCP-star allgather for per-boundary host payloads.

    Process 0 binds `port`; every worker keeps one persistent connection.
    `allgather(payload)` is a collective: every process contributes its
    bytes and receives the full pid-ordered list. Payloads are boundary
    summaries and finished-lane rows — hundreds of bytes — so a
    sequential star is plenty, and staying off the device interconnect
    means CPU test meshes need no collectives backend at all."""

    def __init__(self, host: str, port: int, num: int, pid: int,
                 timeout: float = 60.0) -> None:
        self.num = num
        self.pid = pid
        self._lock = threading.Lock()
        if pid == 0:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            # workers may sit on other machines: bind all interfaces
            srv.bind(("", port))
            srv.listen(num)
            srv.settimeout(timeout)
            self._peers: dict[int, socket.socket] = {}
            deadline = time.monotonic() + timeout
            while len(self._peers) < num - 1:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"host exchange: {len(self._peers)}/{num - 1} "
                        "workers connected before timeout"
                    )
                conn, _ = srv.accept()
                conn.settimeout(timeout)
                (peer,) = struct.unpack("<I", _read_exact(conn, 4))
                self._peers[peer] = conn
            srv.close()
        else:
            deadline = time.monotonic() + timeout
            last_err: Optional[Exception] = None
            while True:
                try:
                    conn = socket.create_connection((host, port), timeout=5)
                    break
                except OSError as e:
                    last_err = e
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"host exchange: cannot reach coordinator "
                            f"{host}:{port}: {last_err}"
                        ) from e
                    time.sleep(0.05)
            conn.settimeout(timeout)
            conn.sendall(struct.pack("<I", pid))
            self._conn = conn

    def allgather(self, payload: bytes) -> List[bytes]:
        """All processes' payloads, ordered by process id. Collective:
        every participant must call once per boundary, in lockstep."""
        with self._lock:
            if self.pid == 0:
                parts: List[bytes] = [b""] * self.num
                parts[0] = payload
                for peer, conn in self._peers.items():
                    (n,) = struct.unpack("<I", _read_exact(conn, 4))
                    parts[peer] = _read_exact(conn, n)
                blob = struct.pack("<I", self.num) + b"".join(
                    struct.pack("<I", len(p)) + p for p in parts
                )
                for conn in self._peers.values():
                    conn.sendall(blob)
                return parts
            self._conn.sendall(
                struct.pack("<I", len(payload)) + payload
            )
            (num,) = struct.unpack("<I", _read_exact(self._conn, 4))
            parts = []
            for _ in range(num):
                (n,) = struct.unpack("<I", _read_exact(self._conn, 4))
                parts.append(_read_exact(self._conn, n))
            return parts

    def close(self) -> None:
        if self.pid == 0:
            for conn in self._peers.values():
                conn.close()
        else:
            self._conn.close()


def _read_exact(conn: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("host exchange peer closed mid-frame")
        buf += chunk
    return buf
