"""Config: CLI flags, fishnet.ini, interactive dialog; precedence CLI > ini.

Parity with the reference's three-layer config system (reference:
src/configure.rs:20-643): same flags, same ini file format (default section
"Fishnet"), same subcommands (run | configure | systemd | systemd-user |
license), same human duration parsing (d/h/m/s/ms), same Cores/Backlog
semantics — plus the TPU backend's own knobs (backend selection, weight
file, engine paths for the subprocess fallback).
"""
from __future__ import annotations

import argparse
import configparser
import os
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

_DURATION_RE = re.compile(r"^\s*(\d+)\s*(d|h|m|s|ms)?\s*$")


def parse_duration(text: str) -> float:
    """'90s', '2m', '1h', '1d', '500ms', bare seconds → seconds
    (reference: src/configure.rs:340-355)."""
    m = _DURATION_RE.match(text)
    if not m:
        raise ValueError(f"invalid duration: {text!r}")
    value = int(m.group(1))
    unit = m.group(2) or "s"
    scale = {"d": 86400, "h": 3600, "m": 60, "s": 1, "ms": 0.001}[unit]
    return value * scale


def parse_cores(text: Optional[str]) -> int:
    """'auto' = n-1, 'all'/'max' = n, or an explicit number
    (reference: src/configure.rs:177-219)."""
    n = os.cpu_count() or 1
    if text is None or text == "auto":
        return max(n - 1, 1)
    if text in ("all", "max"):
        return n
    value = int(text)
    if value < 1:
        raise ValueError("cores must be >= 1")
    return min(value, n)


def parse_backlog(text: Optional[str]) -> Optional[float]:
    """'short' = 30s, 'long' = 1h, duration, or None
    (reference: src/configure.rs:244-289)."""
    if text is None or text == "":
        return None
    if text == "short":
        return 30.0
    if text == "long":
        return 3600.0
    return parse_duration(text)


def validate_key(key: str) -> str:
    key = key.strip()
    if not key:
        return key
    if not key.isalnum():
        raise ValueError("fishnet key must be alphanumeric")
    return key


@dataclass
class Config:
    command: str = "run"
    endpoint: str = "https://lichess.org/fishnet"
    key: Optional[str] = None
    key_file: Optional[str] = None
    cores: int = 1
    backend: str = "tpu"  # tpu | subprocess | python
    engine_path: Optional[str] = None  # external Stockfish (Official flavor)
    variant_engine_path: Optional[str] = None  # external Fairy-Stockfish
    tpu_weights: Optional[str] = None
    # the small net of a Stockfish deployment beside a .nnue tpu_weights
    # (upstream's EvalFileSmall); every eval chooses between the two
    tpu_weights_small: Optional[str] = None
    # analysis depth cap. Deepening is ALSO governed per position by the
    # server node budget and the chunk deadline (engine/tpu.py stops
    # iterating when either runs out), so this cap only binds when budget
    # remains — raised 6 → 8 → 12 in round 4 as the pruning stack grew
    # (NMP + LMR, then frontier futility). The measured node table
    # (docs/depth.md, tools/depth_table.py: EBF ≈ 2.8 with the full
    # stack + TT) puts the reference's own per-position budgets
    # (api.rs:214-233, ×6/7 overlap scaling) at budget-emergent depth
    # ~9-10 (sf16) / ~10-11 (classical), so 12 lets the BUDGET bind —
    # matching the reference, whose depth is likewise budget-emergent —
    # while the deadline race still cuts off any iteration a slow
    # backend can't afford.
    tpu_depth: int = 12
    # Lazy-SMP helper lanes per analysed position (engine/tpu.py): spare
    # batch lanes re-search the hardest roots with perturbed ordering and
    # share results through the TT. 1 disables helpers entirely.
    tpu_helpers: int = 4
    # continuous lane refill (engine/tpu.py LaneScheduler): finished
    # lanes are respliced with queued positions at segment boundaries
    # instead of narrowing and draining chunks serially; --no-tpu-refill
    # restores strict chunk-serial dispatch
    tpu_refill: bool = True
    # shard-aware refill on multi-chip hosts (parallel/mesh.py sharded
    # segment/refill callables driven by the same LaneScheduler);
    # --no-tpu-mesh-refill pins meshed engines back to chunk-serial
    # dispatch without touching single-device refill
    tpu_mesh_refill: bool = True
    # host the TPU engine in a supervised child process (engine/supervisor.py)
    # so a wedged device can be hard-killed and respawned; --no-supervisor
    # reverts to the in-process engine (debugging, single-process profiling)
    supervisor: bool = True
    # session recovery (engine/supervisor.py recovery ladder). None defers
    # to the FISHNET_TPU_REPLAY / _BISECT_MAX / _QUARANTINE registry
    # settings so env-var config keeps working without CLI/ini mirrors.
    tpu_replay: Optional[bool] = None
    tpu_bisect_max: Optional[int] = None
    tpu_quarantine: Optional[bool] = None
    user_backlog: Optional[float] = None
    system_backlog: Optional[float] = None
    max_backoff: float = 30.0
    cpu_priority: Optional[str] = None
    stats_file: Optional[str] = None
    no_stats_file: bool = False
    auto_update: bool = False
    # `serve` subcommand (fishnet_tpu/serve/): bind overrides; None
    # defers to the FISHNET_TPU_SERVE_HOST/_PORT registry settings
    serve_host: Optional[str] = None
    serve_port: Optional[int] = None
    # fleet coordinator (fishnet_tpu/fleet/): --fleet swaps the engine
    # factory's TPU path for a FleetCoordinator over --fleet-members
    # (None defers to FISHNET_TPU_FLEET_MEMBERS); the `fleet` command
    # is `serve` with the coordinator forced on
    fleet: bool = False
    fleet_members: Optional[str] = None
    # elastic capacity (fishnet_tpu/fleet/autoscaler.py): tri-state —
    # unset (None) defers to the FISHNET_TPU_AUTOSCALE registry setting;
    # min/max override the FISHNET_TPU_AUTOSCALE_MIN/_MAX clamp
    autoscale: Optional[bool] = None
    autoscale_min: Optional[int] = None
    autoscale_max: Optional[int] = None
    # analysis-result cache (fishnet_tpu/cache/): --no-cache forces it
    # off regardless of FISHNET_TPU_CACHE; cache_dir overrides
    # FISHNET_TPU_CACHE_DIR for the persisted tier
    cache: bool = True
    cache_dir: Optional[str] = None
    # fleet-ctl: machine-readable output (`fleet-ctl --json list`)
    json_output: bool = False
    # AOT program assets (fishnet_tpu/aot/): `pack` builds a bundle,
    # `warm` installs one. aot_bundle = pack output / warm source;
    # aot_dir = warm's install target. Engines read the store root from
    # FISHNET_TPU_AOT_DIR only — these flags never touch the environment
    aot_bundle: Optional[str] = None
    aot_dir: Optional[str] = None
    conf: Optional[str] = None
    no_conf: bool = False
    verbose: int = 0
    extra_args: List[str] = field(default_factory=list)

    def resolved_key(self) -> Optional[str]:
        if self.key:
            return self.key
        if self.key_file:
            return Path(self.key_file).read_text().strip()
        return None


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="fishnet-tpu",
        description="Distributed analysis client for lichess.org with a TPU engine",
    )
    p.add_argument("command", nargs="?", default="run",
                   choices=["run", "configure", "systemd", "systemd-user",
                            "license", "bench", "serve", "fleet",
                            "pack", "warm", "inflight", "fleet-ctl",
                            "perf"])
    p.add_argument("subargs", nargs="*", default=[],
                   help="subcommand arguments (fleet-ctl: list | "
                        "add SPEC | drain NAME | remove NAME)")
    p.add_argument("--verbose", "-v", action="count", default=0)
    p.add_argument("--auto-update", action="store_true")
    p.add_argument("--conf", help="path to fishnet.ini")
    p.add_argument("--no-conf", action="store_true")
    p.add_argument("--key", help="fishnet key")
    p.add_argument("--key-file", help="file containing the fishnet key")
    p.add_argument("--endpoint", help="API endpoint")
    p.add_argument("--cores", help="number of workers: auto, all, or a number")
    p.add_argument("--backend", choices=["tpu", "subprocess", "python"],
                   help="analysis backend (default tpu)")
    p.add_argument("--engine-path", help="external Stockfish binary (subprocess backend)")
    p.add_argument("--variant-engine-path", help="external Fairy-Stockfish binary")
    p.add_argument("--tpu-weights",
                   help="NNUE weights: our .npz or a Stockfish .nnue file")
    p.add_argument("--tpu-weights-small",
                   help="the small Stockfish .nnue net beside a .nnue "
                        "--tpu-weights: both resident, chosen per eval by "
                        "Stockfish's rule")
    p.add_argument("--tpu-depth", type=int, help="max search depth for the TPU engine")
    p.add_argument("--tpu-helpers", type=int,
                   help="Lazy-SMP helper lanes per position (1 disables)")
    p.add_argument("--no-tpu-refill", action="store_true",
                   help="disable continuous lane refill (strict "
                        "chunk-serial engine dispatch)")
    p.add_argument("--no-tpu-mesh-refill", action="store_true",
                   help="disable shard-aware lane refill on multi-chip "
                        "hosts (meshed engines fall back to chunk-serial "
                        "dispatch; single-device refill is unaffected)")
    p.add_argument("--no-supervisor", action="store_true",
                   help="run the TPU engine in-process instead of in a "
                        "supervised child process")
    p.add_argument("--no-tpu-replay", action="store_true",
                   help="disable partial-progress replay after an engine "
                        "host death (whole-chunk retry semantics)")
    p.add_argument("--tpu-bisect-max", type=int,
                   help="child-death budget for the per-chunk recovery "
                        "ladder (replay/bisect/quarantine)")
    p.add_argument("--no-tpu-quarantine", action="store_true",
                   help="never quarantine isolated poison positions to "
                        "the CPU fallback")
    p.add_argument("--serve-host",
                   help="serve subcommand: bind address (default "
                        "FISHNET_TPU_SERVE_HOST, loopback)")
    p.add_argument("--serve-port", type=int,
                   help="serve subcommand: TCP port; 0 binds an ephemeral "
                        "port (default FISHNET_TPU_SERVE_PORT)")
    p.add_argument("--fleet", action="store_true",
                   help="dispatch work through the fleet coordinator "
                        "(fishnet_tpu/fleet/) instead of one engine; "
                        "implied by the `fleet` command")
    p.add_argument("--fleet-members",
                   help="comma-separated member specs: 'local', 'local*N' "
                        "(supervised host children here) or "
                        "'http://HOST:PORT' (remote serve endpoints); "
                        "default FISHNET_TPU_FLEET_MEMBERS")
    p.add_argument("--autoscale", action="store_true",
                   help="run the elastic-capacity control loop on the "
                        "fleet coordinator (fishnet_tpu/fleet/"
                        "autoscaler.py); requires --fleet")
    p.add_argument("--no-autoscale", action="store_true",
                   help="force the autoscaler off even when "
                        "FISHNET_TPU_AUTOSCALE is set")
    p.add_argument("--autoscale-min", type=int,
                   help="autoscaler member floor (default "
                        "FISHNET_TPU_AUTOSCALE_MIN)")
    p.add_argument("--autoscale-max", type=int,
                   help="autoscaler member ceiling (default "
                        "FISHNET_TPU_AUTOSCALE_MAX)")
    p.add_argument("--no-cache", action="store_true",
                   help="serve subcommand: disable the analysis-result "
                        "cache (fishnet_tpu/cache/) even when "
                        "FISHNET_TPU_CACHE is set")
    p.add_argument("--cache-dir",
                   help="serve subcommand: persisted-cache directory "
                        "(default FISHNET_TPU_CACHE_DIR, "
                        "~/.cache/fishnet-tpu/cache)")
    p.add_argument("--json", action="store_true", dest="json_output",
                   help="fleet-ctl list: print the raw health payload as "
                        "JSON instead of the human table")
    p.add_argument("--aot-bundle",
                   help="pack subcommand: output directory for the AOT "
                        "program bundle (default: the live store); warm "
                        "subcommand: bundle directory to install")
    p.add_argument("--aot-dir",
                   help="warm subcommand: store root to install the bundle "
                        "into (default FISHNET_TPU_AOT_DIR, "
                        "~/.cache/fishnet-tpu/aot); engines read the store "
                        "root from FISHNET_TPU_AOT_DIR at boot")
    p.add_argument("--user-backlog", help="short, long, or duration")
    p.add_argument("--system-backlog", help="short, long, or duration")
    p.add_argument("--max-backoff", help="maximum backoff duration")
    p.add_argument("--cpu-priority", choices=["min", "normal"])
    p.add_argument("--stats-file")
    p.add_argument("--no-stats-file", action="store_true")
    return p


INI_SECTION = "Fishnet"  # reference: src/configure.rs:421


def read_ini(path: Path) -> dict:
    parser = configparser.ConfigParser()
    try:
        parser.read(path)
        if parser.has_section(INI_SECTION):
            return dict(parser.items(INI_SECTION))
    except configparser.Error:
        pass
    # tolerate files without a section header
    try:
        with open(path) as f:
            content = f"[{INI_SECTION}]\n" + f.read()
        parser = configparser.ConfigParser()
        parser.read_string(content)
        return dict(parser.items(INI_SECTION))
    except (OSError, configparser.Error):
        return {}


def write_ini(path: Path, values: dict) -> None:
    parser = configparser.ConfigParser()
    parser[INI_SECTION] = {k: str(v) for k, v in values.items() if v is not None}
    with open(path, "w") as f:
        parser.write(f)


def merge(args: argparse.Namespace, ini: dict) -> Config:
    """CLI wins over ini (reference: src/configure.rs:602-627)."""

    def pick(cli_value, ini_key, default=None):
        if cli_value is not None and cli_value is not False:
            return cli_value
        if ini_key in ini and ini[ini_key] != "":
            return ini[ini_key]
        return default

    cfg = Config()
    cfg.command = args.command
    cfg.verbose = args.verbose
    cfg.auto_update = bool(pick(args.auto_update or None, "auto_update", False))
    cfg.endpoint = str(pick(args.endpoint, "endpoint", cfg.endpoint)).rstrip("/")
    key = pick(args.key, "key")
    cfg.key = validate_key(str(key)) if key else None
    cfg.key_file = pick(args.key_file, "key_file")
    cfg.cores = parse_cores(pick(args.cores, "cores"))
    cfg.backend = str(pick(args.backend, "backend", "tpu"))
    cfg.engine_path = pick(args.engine_path, "engine_path")
    cfg.variant_engine_path = pick(args.variant_engine_path, "variant_engine_path")
    cfg.tpu_weights = pick(args.tpu_weights, "tpu_weights")
    cfg.tpu_weights_small = pick(args.tpu_weights_small, "tpu_weights_small")
    cfg.tpu_depth = int(pick(args.tpu_depth, "tpu_depth", Config.tpu_depth))
    cfg.tpu_helpers = int(pick(args.tpu_helpers, "tpu_helpers", Config.tpu_helpers))
    refill_ini = str(ini.get("tpu_refill", "")).strip().lower()
    cfg.tpu_refill = not (
        args.no_tpu_refill or refill_ini in ("0", "false", "no", "off")
    )
    mesh_refill_ini = str(ini.get("tpu_mesh_refill", "")).strip().lower()
    cfg.tpu_mesh_refill = not (
        args.no_tpu_mesh_refill
        or mesh_refill_ini in ("0", "false", "no", "off")
    )
    supervisor_ini = str(ini.get("supervisor", "")).strip().lower()
    cfg.supervisor = not (
        args.no_supervisor or supervisor_ini in ("0", "false", "no", "off")
    )
    # tri-state recovery knobs: unset (None) defers to the settings
    # registry, so FISHNET_TPU_REPLAY=0 et al. keep working
    replay_ini = str(ini.get("tpu_replay", "")).strip().lower()
    if args.no_tpu_replay or replay_ini in ("0", "false", "no", "off"):
        cfg.tpu_replay = False
    elif replay_ini:
        cfg.tpu_replay = True
    quarantine_ini = str(ini.get("tpu_quarantine", "")).strip().lower()
    if args.no_tpu_quarantine or quarantine_ini in ("0", "false", "no", "off"):
        cfg.tpu_quarantine = False
    elif quarantine_ini:
        cfg.tpu_quarantine = True
    bisect_max = pick(args.tpu_bisect_max, "tpu_bisect_max")
    cfg.tpu_bisect_max = int(bisect_max) if bisect_max is not None else None
    cfg.serve_host = pick(args.serve_host, "serve_host")
    serve_port = pick(args.serve_port, "serve_port")
    cfg.serve_port = int(serve_port) if serve_port is not None else None
    cfg.fleet = bool(args.fleet) or args.command == "fleet" or \
        str(ini.get("fleet", "")).strip().lower() in ("1", "true", "yes", "on")
    cfg.fleet_members = pick(args.fleet_members, "fleet_members")
    # tri-state autoscale: unset (None) defers to FISHNET_TPU_AUTOSCALE
    autoscale_ini = str(ini.get("autoscale", "")).strip().lower()
    if args.no_autoscale or autoscale_ini in ("0", "false", "no", "off"):
        cfg.autoscale = False
    elif args.autoscale or autoscale_ini:
        cfg.autoscale = True
    autoscale_min = pick(args.autoscale_min, "autoscale_min")
    cfg.autoscale_min = int(autoscale_min) if autoscale_min is not None else None
    autoscale_max = pick(args.autoscale_max, "autoscale_max")
    cfg.autoscale_max = int(autoscale_max) if autoscale_max is not None else None
    cache_ini = str(ini.get("cache", "")).strip().lower()
    cfg.cache = not (
        args.no_cache or cache_ini in ("0", "false", "no", "off")
    )
    cfg.cache_dir = pick(args.cache_dir, "cache_dir")
    cfg.json_output = bool(args.json_output)
    cfg.aot_bundle = pick(args.aot_bundle, "aot_bundle")
    cfg.aot_dir = pick(args.aot_dir, "aot_dir")
    cfg.user_backlog = parse_backlog(pick(args.user_backlog, "user_backlog"))
    cfg.system_backlog = parse_backlog(pick(args.system_backlog, "system_backlog"))
    cfg.max_backoff = parse_duration(str(pick(args.max_backoff, "max_backoff", "30s")))
    cfg.cpu_priority = pick(args.cpu_priority, "cpu_priority")
    cfg.stats_file = pick(args.stats_file, "stats_file")
    cfg.no_stats_file = bool(args.no_stats_file)
    cfg.conf = args.conf
    cfg.no_conf = args.no_conf
    cfg.extra_args = list(args.subargs)
    return cfg


def interactive_dialog(cfg: Config, check_key=None, stream=sys.stdout) -> Config:
    """The reference's 5-step first-run dialog (reference:
    src/configure.rs:433-600): endpoint, key (with optional online
    validation), cores, backlog, write fishnet.ini."""

    def ask(prompt: str, default: str = "") -> str:
        suffix = f" ({default})" if default else ""
        stream.write(f"{prompt}{suffix}: ")
        stream.flush()
        line = input().strip()
        return line or default

    endpoint = ask("Endpoint", cfg.endpoint).rstrip("/")
    key = ask("Personal fishnet key (https://lichess.org/get-fishnet)", cfg.key or "")
    key = validate_key(key)
    if key and check_key is not None and not check_key(endpoint, key):
        raise ValueError("key rejected by server")
    cores = ask("Number of logical cores to use", "auto")
    backlog = ask(
        "Analysis backlog: short (user games), long (system), or duration", ""
    )
    cfg.endpoint = endpoint
    cfg.key = key or None
    cfg.cores = parse_cores(cores if cores != "auto" else None)
    cfg.user_backlog = parse_backlog(backlog or None)
    target = ask("Write configuration to", str(Path("fishnet.ini").absolute()))
    write_ini(
        Path(target),
        {
            "endpoint": cfg.endpoint,
            "key": cfg.key,
            "cores": cfg.cores,
            "user_backlog": backlog or None,
        },
    )
    return cfg


def parse_and_configure(argv: Optional[List[str]] = None, interactive: bool = True,
                        check_key=None) -> Config:
    args = build_parser().parse_args(argv)
    ini: dict = {}
    conf_path = Path(args.conf) if args.conf else Path("fishnet.ini")
    if not args.no_conf and conf_path.exists():
        ini = read_ini(conf_path)
    cfg = merge(args, ini)
    needs_dialog = args.command == "configure" or (
        interactive
        and not args.no_conf
        and not conf_path.exists()
        and args.command == "run"
        and sys.stdin.isatty()
    )
    if needs_dialog:
        cfg = interactive_dialog(cfg, check_key=check_key)
    return cfg
