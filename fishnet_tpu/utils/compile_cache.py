"""Persistent XLA compilation cache — tier 2 of the warm-boot ladder.

The reference spends build time on PGO so shipped engine binaries start
fast (reference: build.rs:249-261). The TPU analog of that cost is XLA
compilation: the search program takes 20-40 s to compile per lane-bucket
shape. Persisting compiled executables to disk makes every restart after
the first start warm — the same "pay once, run fast forever" trade.

Since the AOT asset registry landed (fishnet_tpu/aot/, docs/aot.md)
this cache is the SECOND tier, not the first: a packed bundle loads
serialized executables with zero XLA involvement at all; this cache
only softens the compiles that still happen — AOT misses, export runs
(`pack` itself compiles through it), and programs the bundle doesn't
cover. It stays on by default because the tiers compose: a miss that
falls back to JIT hits this cache before it hits the compiler.

Where it lives is decided outside the program: JAX reads the standard
JAX_COMPILATION_CACHE_DIR itself, and when that is set nothing here sets
a directory (an engine host child inherits the variable through its
environment). Unset, the cache is at one fixed path inside the checkout
(`.cache/xla`, git-ignored) — the path is part of JAX's cache key, so a
directory that moves between runs never hits.

Disabled with FISHNET_TPU_NO_COMPILE_CACHE=1 (e.g. read-only filesystems).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

from . import settings

# <checkout>/.cache/xla: fixed, so restarts (and a second process of
# the same run) find what the first one compiled
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "xla"

_enabled_path: Optional[Path] = None
_force_disabled = False


def _reset_cache_memo() -> None:
    # jax memoizes "is the persistent cache used" at the first compile,
    # so a config change mid-process is ignored unless that is reset too
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def disable_compile_cache() -> None:
    """Turn the persistent cache off for the rest of this process.

    AOT export (``pack``) requires it: serializing an executable that was
    a persistent-cache HIT yields an incomplete payload that fails at
    deserialize time with "Symbols not found" — exported programs must be
    compiled for real. Later enable_compile_cache() calls become no-ops."""
    global _enabled_path, _force_disabled
    _force_disabled = True
    _enabled_path = None
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    _reset_cache_memo()


def enable_compile_cache() -> Optional[Path]:
    """Turn JAX's persistent compilation cache on for this process.

    Idempotent; returns the cache dir, or None when disabled. Must be
    called before the first compilation to benefit it."""
    global _enabled_path
    if _force_disabled:
        return None
    if settings.get_bool("FISHNET_TPU_NO_COMPILE_CACHE"):
        return None
    if _enabled_path is not None:
        return _enabled_path
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        # placed from outside: jax.config already holds it
        p = Path(placed)
    else:
        p = DEFAULT_CACHE_DIR
        try:
            p.mkdir(parents=True, exist_ok=True)
        except OSError:
            return None  # read-only checkout: run without the cache
        jax.config.update("jax_compilation_cache_dir", str(p))
    # default thresholds skip small programs; cache everything — even
    # the small host-callback programs add up across restarts
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _reset_cache_memo()
    _enabled_path = p
    return p
