"""Profile the lockstep search step on the current device.

Times run_segment per-step wall clock at a given shape, then captures a
jax.profiler trace of a short segment and aggregates device time per op
and per `jax.named_scope` of the step (ops/search.py: step.rules,
step.eval, step.movegen, step.order, ...; the small net's forward under
--net halfka_dual is step.eval_small), so the hot spots are
attributable (VERDICT r4 weak #6: perf claims need a committed artifact).
With --trace the program is compiled here, past the persistent compile
cache: an executable loaded from it carries the names of the tree that
built it.

Usage:
  python tools/profile_step.py [B] [depth] [max_ply] [--trace] [--tt]
                               [--variant crazyhouse] [--net halfka3072]
                               [--net halfka_dual]
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("B", nargs="?", type=int, default=64)
    ap.add_argument("depth", nargs="?", type=int, default=3)
    ap.add_argument("max_ply", nargs="?", type=int, default=None)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--tt", action="store_true",
                    help="shared 2^21-slot table (production config)")
    ap.add_argument("--variant", default="standard",
                    help="the statically compiled program to profile "
                         "(engine/tpu.py DEVICE_VARIANTS' values)")
    ap.add_argument("--net", default="board768",
                    help="board768 (64 wide), or halfka<L1> for the "
                         "king-relative net of that width with seeded "
                         "weights (halfka3072 is the benchmark's), or "
                         "halfka_dual for the 3,072- and 128-wide pair "
                         "Stockfish chooses between")
    opts = ap.parse_args()
    B, depth, variant = opts.B, opts.depth, opts.variant
    max_ply = opts.max_ply if opts.max_ply is not None else depth + 1
    do_trace, use_tt = opts.trace, opts.tt
    steps = int(os.environ.get("PROFILE_STEPS", "200"))

    import jax
    import jax.numpy as jnp
    import numpy as np

    if do_trace:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        from fishnet_tpu.utils import enable_compile_cache

        enable_compile_cache()
    print(f"devices={jax.devices()} platform={jax.default_backend()}",
          file=sys.stderr)

    from fishnet_tpu.models import nnue
    from fishnet_tpu.ops import search as S

    from bench import FENS_VARIANT, _roots_for

    roots = _roots_for(
        B, variant, "variant" if variant in FENS_VARIANT else "standard")
    if opts.net == "halfka_dual":
        from fishnet_tpu.models import nnue_import as ni

        params = ni.DualNet.from_nets(
            seeded_halfka(3072), seeded_halfka(128, seed=1)).as_device()
    elif opts.net.startswith("halfka"):
        params = seeded_halfka(int(opts.net[len("halfka"):]))
    else:
        params = nnue.init_params(jax.random.PRNGKey(0), l1=64, feature_set="board768")
    depth_arr = jnp.full((B,), depth, jnp.int32)
    budget_arr = jnp.full((B,), 10_000_000, jnp.int32)

    tt_mod = None
    if use_tt:
        from fishnet_tpu.ops import tt as tt_mod

    def fresh_inputs():
        # _run_segment_jit DONATES the state and table (ops/search.py),
        # so every dispatch needs its own copies — rebuilding also keeps
        # the step counts comparable across the timed runs
        st = S._init_state_jit(params, roots, depth_arr, budget_arr,
                               max_ply, variant)
        t = tt_mod.make_table(21) if use_tt else None
        jax.block_until_ready(st.bt)
        return st, t

    state, tt0 = fresh_inputs()
    t0 = time.perf_counter()
    compiled = S._run_segment_jit.lower(params, state, tt0, steps,
                                        variant, False).compile()
    print(f"compile run_segment({steps}) for {variant}: "
          f"{time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    # warmup + timed: same fresh state each time so step counts match
    for tag in ("warmup", "timed1", "timed2", "timed3"):
        state, tt0 = fresh_inputs()
        t0 = time.perf_counter()
        out, _, n, _summ = S._run_segment_jit(params, state, tt0, steps,
                                              variant, False)
        jax.block_until_ready(out.lane)
        dt = time.perf_counter() - t0
        n = int(n)
        nodes = int(np.asarray(out.lane[:, S.LN_NODES]).sum())
        print(f"{tag}: {n} steps in {dt*1e3:.1f}ms -> {dt/max(n,1)*1e6:.0f}"
              f" us/step, {nodes} nodes, {nodes/dt:.0f} nps", file=sys.stderr)

    if not do_trace:
        return

    state, tt0 = fresh_inputs()
    trace_dir = os.environ.get("PROFILE_TRACE_DIR", "/tmp/fishnet-trace")
    with jax.profiler.trace(trace_dir):
        out, _, n, _summ = S._run_segment_jit(params, state, tt0, steps,
                                              variant, False)
        jax.block_until_ready(out.lane)
    print(f"trace written to {trace_dir}", file=sys.stderr)
    report(trace_dir, scope_of_instruction(compiled.as_text()), steps)


def seeded_halfka(l1: int, seed: int = 0):
    """A StockfishNet of width l1 drawn on the device, at scales that keep
    the accumulators around the clip's 0 and 1 (no file is read: times do
    not depend on the values)."""
    import jax
    import jax.numpy as jnp

    from fishnet_tpu.models import nnue_import as ni

    shapes = {
        "ft_w": ((ni.NUM_FEATURES, l1), 0.1), "ft_b": ((l1,), 0.5),
        "psqt_w": ((ni.NUM_FEATURES, ni.NUM_PSQT_BUCKETS), 0.02),
        "fc0_w": ((ni.NUM_STACKS, ni.FC0_OUT, l1), l1 ** -0.5),
        "fc0_b": ((ni.NUM_STACKS, ni.FC0_OUT), 0.1),
        "fc1_w": ((ni.NUM_STACKS, ni.FC1_OUT, ni.FC1_IN), ni.FC1_IN ** -0.5),
        "fc1_b": ((ni.NUM_STACKS, ni.FC1_OUT), 0.1),
        "fc2_w": ((ni.NUM_STACKS, 1, ni.FC1_OUT), 0.05),
        "fc2_b": ((ni.NUM_STACKS, 1), 0.02),
    }
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return ni.StockfishNet(**{
        name: jax.random.normal(k, shape, jnp.float32) * scale
        for k, (name, (shape, scale)) in zip(keys, shapes.items())
    })


SCOPE = re.compile(r"\b((?:step|refill)\.[a-z_]+)")
NO_SCOPE = "(no scope)"
# `%while.3 = (s32[..], pred[..]) while((..) %tuple), condition=..`: the
# op kind follows the result type, which for a loop is a tuple
CONTAINER = re.compile(r"^(?:\(.*?\)|\S+) (?:while|conditional|call)\(")


def scope_of_op_name(op_name: str) -> str:
    """`jit(_run_segment)/while/body/vmap(step.order)/sort` → step.order:
    the innermost jax.named_scope of ops/search.py the op was traced
    under."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else NO_SCOPE


def scope_of_instruction(hlo_text: str) -> dict:
    """instruction name → scope, from the `op_name` XLA keeps in each
    instruction's metadata (a fusion carries its root's)."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = ", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = scope_of_op_name(op.group(1)) if op else NO_SCOPE
    return out


def report(trace_dir: str, scope_by_instr: dict, steps: int) -> None:
    """Device time by op and by named scope, from the newest xplane under
    `trace_dir`. An event names its scope through its own stats where
    the profiler kept the op's metadata there, else through the compiled
    program's text."""
    from jax.profiler import ProfileData

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        print("no .xplane.pb found", file=sys.stderr)
        return
    by_name: dict[str, float] = defaultdict(float)
    cnt: dict[str, int] = defaultdict(int)
    by_scope: dict[str, float] = defaultdict(float)
    from_stats = 0
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                instr, _sep, rest = e.name.partition(" = ")
                if CONTAINER.search(rest):
                    continue  # one event over its whole body, gaps and all
                scope = NO_SCOPE
                for _key, value in e.stats:
                    if isinstance(value, str) and SCOPE.search(value):
                        scope = scope_of_op_name(value)
                        from_stats += 1
                        break
                else:
                    scope = scope_by_instr.get(instr.lstrip("%"), NO_SCOPE)
                by_name[e.name] += e.duration_ns
                cnt[e.name] += 1
                by_scope[scope] += e.duration_ns
    total = sum(by_name.values())
    print(f"total device-op time: {total / 1e6:.1f}ms over {steps} steps "
          f"({from_stats} events named their scope themselves)")
    print("by named scope (ops/search.py):")
    for scope, dur in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print(f"{dur / 1e6:9.2f}ms {100 * dur / max(total, 1e-9):5.1f}% "
              f"{dur / 1e3 / max(steps, 1):8.1f} us/step  {scope}")
    print("by op:")
    for name, dur in sorted(by_name.items(), key=lambda kv: -kv[1])[:40]:
        print(f"{dur / 1e6:9.2f}ms {100 * dur / max(total, 1e-9):5.1f}% "
              f"x{cnt[name]:<6} {name[:110]}")


if __name__ == "__main__":
    main()
