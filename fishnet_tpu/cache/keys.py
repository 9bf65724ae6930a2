"""Canonical analysis-cache keys and the satisfaction rule.

Every cache write and every lookup goes through the builders here —
`cache-unkeyed-store` (lint/cache_rules.py) flags raw store calls
anywhere else. The key must capture EVERYTHING that changes the answer
and NOTHING that doesn't:

* **fp** — content-only position fingerprint: sha256 over root FEN and
  the move list. Deliberately NOT client/ipc.py `position_fingerprint`,
  which folds in the chunk slot index (exactly-once bookkeeping): the
  same board reached in slot 0 of one request and slot 5 of another is
  the same position.
* **kind / variant / level** — the request class. level only shapes
  bestmove searches (SkillLevel table), so analysis keys pin it to 0.
* **multipv** — kept raw (None stays -1): multipv=None and multipv=1
  run the same search but answer with different matrix shapes
  (AnalysisWork.matrix_wanted), and a hit must be bit-identical to the
  search it replaces.
* **nodes** — the EFFECTIVE per-position budget the engine sees
  (NodeLimit.get after the chunk-overlap scaling), not the raw request
  field: an explicit budget and a default budget that resolve to the
  same number run the same search and must share an entry.
* **net** — the engine identity fingerprint: net weights + search
  depth cap + the search-visible settings (aot/keys.py
  AOT_KEY_SETTINGS). A netswap or settings flip changes every answer,
  so it changes every key; `AnalysisCache` additionally persists it
  and invalidates the store on mismatch (docs/caching.md).

The **depth axis rides beside the key, not inside it**: a cached
depth-20 result satisfies a depth-12 request of the same shape (deeper
analysis strictly dominates), never the reverse, and the default-depth
marker (-1) only matches itself — what "default" resolves to lives in
the engine, not here. `satisfies` is the whole rule.
"""
from __future__ import annotations

import hashlib
import json
from typing import List, NamedTuple, Optional, Sequence, Tuple

from ..client.ipc import Chunk, WorkPosition
from ..client.wire import AnalysisWork, EngineFlavor, MoveWork

# depth axis value for "engine default depth" requests: matches only
# itself (the resolved default depends on the engine's max_depth, which
# is folded into the identity fingerprint instead)
DEPTH_DEFAULT = -1


class CacheKey(NamedTuple):
    """The exact-match shape key (depth is the satisfaction axis)."""

    fp: str  # content-only position fingerprint
    kind: str  # "analysis" | "bestmove"
    variant: str
    multipv: int  # raw request multipv; -1 for None
    nodes: int  # effective per-position budget; -1 for bestmove
    level: int  # skill level for bestmove; 0 for analysis
    net: str  # engine identity fingerprint

    def row_id(self) -> str:
        """Stable filename/sqlite identity for this key."""
        return hashlib.sha256(
            "\x00".join(str(f) for f in self).encode("utf-8")
        ).hexdigest()[:24]


def content_fingerprint(fen: str, moves: Sequence[str]) -> str:
    """Position identity by content alone (no chunk slot index)."""
    h = hashlib.sha256()
    h.update(fen.encode("utf-8"))
    h.update(b"\x00")
    h.update(" ".join(moves).encode("utf-8"))
    return h.hexdigest()[:16]


def satisfies(cached_depth: int, wanted_depth: int) -> bool:
    """The at-least-as-deep rule, on the normalized depth axis: a
    deeper (or equal) cached search answers a shallower request;
    default-depth only matches default-depth."""
    if wanted_depth == DEPTH_DEFAULT or cached_depth == DEPTH_DEFAULT:
        return cached_depth == wanted_depth
    return cached_depth >= wanted_depth


def key_for_chunk_position(
    chunk: Chunk, wp: WorkPosition, net: str
) -> Tuple[CacheKey, int]:
    """(shape key, depth axis) for one chunk slot — the primitive
    builder; the serve-side helper routes through it so the two layers
    can never disagree on normalization."""
    work = chunk.work
    fp = content_fingerprint(wp.root_fen, wp.moves)
    if isinstance(work, MoveWork):
        key = CacheKey(
            fp=fp, kind="bestmove", variant=chunk.variant,
            multipv=-1, nodes=-1, level=work.level.level, net=net,
        )
        return key, DEPTH_DEFAULT
    assert isinstance(work, AnalysisWork)
    key = CacheKey(
        fp=fp, kind="analysis", variant=chunk.variant,
        multipv=work.multipv if work.multipv is not None else -1,
        nodes=work.nodes.get(chunk.flavor.eval_flavor()),
        level=0, net=net,
    )
    depth = work.depth if work.depth is not None else DEPTH_DEFAULT
    return key, depth


def keys_for_requests(
    requests: Sequence, net: str,
    flavor: EngineFlavor = EngineFlavor.TPU,
) -> List[Tuple[CacheKey, int]]:
    """(shape key, depth axis) per PositionRequest, in request order.

    Normalization by construction: the requests run through the SAME
    requests_to_chunks grouping the session uses, and each resulting
    chunk slot goes through key_for_chunk_position — so a serve-layer
    consult and a coordinator-layer fill of the same request literally
    cannot produce different keys."""
    from ..engine.session import requests_to_chunks

    out: List[Optional[Tuple[CacheKey, int]]] = [None] * len(requests)
    for chunk, indices in requests_to_chunks(
        list(requests), flavor=flavor, id_prefix="cachekey"
    ):
        for wp, idx in zip(chunk.positions, indices):
            out[idx] = key_for_chunk_position(chunk, wp, net)
    assert all(k is not None for k in out)
    return out  # type: ignore[return-value]


def key_for_request(
    request, net: str, flavor: EngineFlavor = EngineFlavor.TPU
) -> Tuple[CacheKey, int]:
    """Single-request convenience over keys_for_requests."""
    return keys_for_requests([request], net, flavor=flavor)[0]


def engine_identity(engine, flavor: EngineFlavor = EngineFlavor.TPU) -> str:
    """The net/settings fingerprint folded into every key.

    Captures what changes answers without re-keying per request: the
    net weights identity (both nets of a two-net deployment, and the
    integer arithmetic a .nnue file is searched in), the engine's depth cap (resolves default-
    depth requests), the engine class, the eval flavor, and the
    search-visible settings (the same registry slice that keys AOT
    bundles — aot/keys.py AOT_KEY_SETTINGS)."""
    from ..aot.keys import AOT_KEY_SETTINGS
    from ..utils import settings

    ident = {
        "class": type(engine).__name__,
        "net": (
            getattr(engine, "net_id", None)
            or getattr(engine, "weights_path", None)
            or "builtin"
        ),
        "max_depth": getattr(engine, "max_depth", None),
        "flavor": flavor.value,
        "settings": {
            name: settings.raw(name) or "" for name in AOT_KEY_SETTINGS
        },
    }
    # a deployment's second net (weights_small_path) chooses answers too;
    # an engine of one net keeps the fingerprint it had
    small = getattr(engine, "weights_small_path", None)
    if small:
        ident["net_small"] = small
    # a .nnue file searched in its own integers answers otherwise than
    # the same file divided into floats; a float engine keeps its
    # fingerprint
    if getattr(engine, "arithmetic", None) == "int":
        ident["arithmetic"] = "int"
    blob = json.dumps(ident, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]
