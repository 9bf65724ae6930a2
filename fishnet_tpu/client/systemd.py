"""systemd unit generators (reference: src/systemd.rs:11-189).

Prints hardened service units reproducing the current invocation's flags,
for `fishnet-tpu systemd` (system unit) and `systemd-user`.
"""
from __future__ import annotations

import shlex
import sys
from pathlib import Path

from .configure import Config


def exec_start(cfg: Config) -> str:
    """Rebuild the command line from the effective config (reference:
    src/systemd.rs:117-189)."""
    parts = [sys.executable, "-m", "fishnet_tpu", "run", "--no-conf"]
    if cfg.endpoint != "https://lichess.org/fishnet":
        parts += ["--endpoint", cfg.endpoint]
    if cfg.key_file:
        parts += ["--key-file", cfg.key_file]
    elif cfg.key:
        parts += ["--key", cfg.key]
    parts += ["--cores", str(cfg.cores)]
    if cfg.backend != "tpu":
        parts += ["--backend", cfg.backend]
    if cfg.engine_path:
        parts += ["--engine-path", cfg.engine_path]
    if cfg.variant_engine_path:
        parts += ["--variant-engine-path", cfg.variant_engine_path]
    if cfg.tpu_weights:
        parts += ["--tpu-weights", cfg.tpu_weights]
    if cfg.tpu_weights_small:
        parts += ["--tpu-weights-small", cfg.tpu_weights_small]
    if cfg.user_backlog is not None:
        parts += ["--user-backlog", f"{int(cfg.user_backlog)}s"]
    if cfg.system_backlog is not None:
        parts += ["--system-backlog", f"{int(cfg.system_backlog)}s"]
    if cfg.max_backoff != 30.0:
        parts += ["--max-backoff", f"{int(cfg.max_backoff)}s"]
    if cfg.cpu_priority:
        parts += ["--cpu-priority", cfg.cpu_priority]
    if cfg.stats_file:
        parts += ["--stats-file", cfg.stats_file]
    if cfg.no_stats_file:
        parts += ["--no-stats-file"]
    if cfg.auto_update:
        parts += ["--auto-update"]
    return " ".join(shlex.quote(p) for p in parts)


def system_unit(cfg: Config, user: str = "fishnet") -> str:
    """Hardened system service (reference: src/systemd.rs:11-54)."""
    return f"""[Unit]
Description=Fishnet TPU client
After=network-online.target
Wants=network-online.target

[Service]
ExecStart={exec_start(cfg)}
WorkingDirectory={Path.cwd()}
User={user}
Nice=5
CapabilityBoundingSet=
PrivateTmp=true
PrivateDevices=false
DevicePolicy=closed
DeviceAllow=char-accel rw
ProtectSystem=strict
NoNewPrivileges=true
Restart=on-failure

[Install]
WantedBy=multi-user.target
"""


def user_unit(cfg: Config) -> str:
    """User-level service (reference: src/systemd.rs:56-93)."""
    return f"""[Unit]
Description=Fishnet TPU client
After=network-online.target
Wants=network-online.target

[Service]
ExecStart={exec_start(cfg)}
WorkingDirectory={Path.cwd()}
Nice=5
PrivateTmp=true
DevicePolicy=closed
DeviceAllow=char-accel rw
NoNewPrivileges=true
Restart=on-failure

[Install]
WantedBy=default.target
"""
