"""One cell, one process, one last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The process owns the chip and calls ``TpuEngine.go_multiple`` in-process.
Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one evaluator is a file found by name (the first three
by the names in BENCHMARK.json, the evaluator by the name the configuration
gives); this file holds only what is common to all cells.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import cells, loadgen, measure  # noqa: E402

EXIT_NO_DEVICE = 2
EXIT_NO_PROGRAM = 3
EXIT_BAD_CELL = 4


def say(msg: str) -> None:
    print(msg, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on XLA:CPU; prints no metric")
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="run the program's lower-precision path "
                         "(the control of `correct`; never a benchmark run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv if argv is not None else sys.argv[1:])
    try:
        cell = cells.load_cell(ROOT, args.workload)
    except cells.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_BAD_CELL
    rehearsal = cells.load_json(HERE / "rehearsal.json") if args.rehearse_cpu else None
    measure.prepare_environment(rehearsal, args.control)
    try:
        device = measure.claim_device(cell["chips"], bool(rehearsal))
    except measure.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    try:
        make_engine = measure.program_engine_factory(cell, rehearsal)
    except ImportError as e:
        print(f"benchmark: the program under test is not here: {e}",
              file=sys.stderr)
        return EXIT_NO_PROGRAM
    # a cell's first run in a checkout builds its programs, and is allowed
    # the time for it: the marker says that a run got through before
    ran_before = ROOT / ".cache" / "bench_ran" / args.workload
    result = loadgen.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        make_engine=make_engine, device=device, t_start=T_PROCESS_START,
        rehearsal=rehearsal, control=args.control, say=say,
        trace_dir=str(ROOT / ".cache" / "bench_trace"),
        first_run=not ran_before.exists(),
    )
    if rehearsal is None:
        ran_before.parent.mkdir(parents=True, exist_ok=True)
        ran_before.touch()
    for name, c in result["checks"].items():
        flag = "" if (c["value"] is not None and c["value"] <= c["limit"]) else "  <-- FAILS"
        print(f"check: {name} = {c['value']} (limit {c['limit']}){flag}",
              file=sys.stderr, flush=True)
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # worker threads may still sit in the executor; everything owed has
    # been waited for, so leave without joining them
    os._exit(code)
