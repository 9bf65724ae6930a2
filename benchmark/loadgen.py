"""The one general traffic generator, the measured window, the result line.

A traffic file describes a closed loop: so many workers, each holding one
chunk of an analysis job and taking the next the moment its chunk is
answered, from a queue of whole games (the traffic file's pool, dealt out
in an order drawn from the seed). The window is
exactly ``seconds`` long by the host clock, opens once the loop is in steady
state and closes on the clock; what is in flight at the close is not
counted.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import gc
import random
import shutil
import statistics
import threading
import time
from collections import Counter, deque
from typing import Callable, Dict, List, Optional

from . import cells, games, measure, reference, rules, trace_reduce, work_count

DRAIN_S = 3.0  # in flight at the close is not counted: nothing is owed


class ClosedLoop:
    def __init__(self, adapter, cell, traffic, game_list, seed):
        self.adapter = adapter
        self.cfg = cell["config"]
        self.traffic = traffic
        self.variant = self.cfg["variant"]
        self.root_fen = rules.start_fen(self.variant)
        self.nodes = self.cfg["work"]["nodes"]
        self.timeout_s = self.cfg["work"]["timeout"] / 1000.0
        self.games = game_list
        self.seed = seed
        self.queue: deque = deque()
        self.next_game = 0
        self.rounds = 0
        self.records: Dict[int, dict] = {}
        self.deliveries: List[tuple] = []  # appended from the driving thread
        self.stopping = False
        self._lock = threading.Lock()

    # ------------------------------------------------------------ chunks

    def _acquire_game(self):
        """What the client does when its queue runs dry: take a game, plan
        it into chunks, one deadline for the batch (upstream queue.rs
        589-610: timeout per ply times the batch's positions)."""
        if self.next_game >= len(self.games):
            self.next_game = 0
            self.rounds += 1  # the games come round again, under new ids
        g = self.next_game
        self.next_game += 1
        moves = self.games[g]
        work_id = f"s{self.seed}r{self.rounds}g{g}"
        deadline = time.monotonic() + self.timeout_s * (len(moves) + 1)
        for spec in games.tile(len(moves)):
            positions = [
                (idx, f"bench://{work_id}#{idx}" if idx is not None else None,
                 moves[:played])
                for idx, played in spec
            ]
            self.queue.append((work_id, deadline, positions))

    def next_chunk(self):
        with self._lock:
            if not self.queue:
                self._acquire_game()
            work_id, deadline, positions = self.queue.popleft()
        return self.make_chunk(work_id, deadline, positions)

    def make_chunk(self, work_id, deadline, positions, warm=False):
        # a warm-up session is there for its shapes, not its answers
        nodes = self.traffic["warm_nodes"] if warm else self.nodes
        chunk = self.adapter.new_chunk(
            work_id, self.variant, nodes, self.timeout_s, deadline,
            self.root_fen, positions)
        rec = {"work": work_id, "deadline": deadline, "positions": positions,
               "t_sub": None, "t_done": None, "error": None,
               "responses": None, "warm": warm}
        self.records[id(chunk)] = rec
        rec["chunk"] = chunk  # keeps id(chunk) unique for the run
        return chunk, rec

    def on_deliver(self, chunk, index, url, resp):
        self.deliveries.append((time.monotonic(), id(chunk), index, url, resp))

    async def submit(self, chunk, rec):
        rec["t_sub"] = time.monotonic()
        try:
            rec["responses"] = await self.adapter.go(chunk)
        except Exception as e:  # a failed chunk fails its positions
            rec["error"] = repr(e)
        rec["t_done"] = time.monotonic()

    async def worker(self):
        while not self.stopping:
            chunk, rec = self.next_chunk()
            await self.submit(chunk, rec)


def _field(resp, name):
    """A response's field: the program's object, or a stand-in's dict."""
    return resp[name] if isinstance(resp, dict) else getattr(resp, name)


def _cut(positions, size):
    return [positions[i:i + size] for i in range(0, len(positions), size)]


async def _warm_sessions(loop_: ClosedLoop, warm_games, sessions, say):
    """One session per width the cell's traffic can meet, each started with
    all of its positions queued (so its width is what the file says)."""
    pool = [moves[:k] for moves in warm_games for k in range(len(moves) + 1)]
    random.Random(loop_.seed).shuffle(pool)
    for sess in sessions:
        n = sess["positions"]
        # indexed, so that each is owed and delivered like any other
        take = [(i, f"bench://warm#{i}", pool[i % len(pool)]) for i in range(n)]
        pool = pool[n % len(pool):] + pool[:n % len(pool)]
        t0 = time.monotonic()
        seg0 = loop_.adapter.counters()["segments"]
        deadline = time.monotonic() + 600.0
        tasks = []
        with loop_.adapter.hold():
            for part in _cut(take, games.MAX_CHUNK_POSITIONS):
                chunk, rec = loop_.make_chunk("warm", deadline, part, warm=True)
                tasks.append(asyncio.ensure_future(loop_.submit(chunk, rec)))
            # submitting replays every game prefix on the host; the session
            # may start only when all of them are queued
            t_q = time.monotonic()
            while (loop_.adapter.queued() < n
                   and time.monotonic() - t_q < 0.2 * n + 5.0):
                await asyncio.sleep(0.02)
        await asyncio.gather(*tasks)
        widths = sorted(set(loop_.adapter.widths(seg0)))
        say(f"setup: warm session of {n} positions in {len(tasks)} chunks "
            f"{time.monotonic() - t0:.1f} s; widths {widths} (wanted {sess['width']})")


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             make_engine: Callable, device: dict, t_start: float,
             rehearsal: Optional[dict], control: Optional[str],
             say: Callable[[str], None], trace_dir: str,
             tracer_factory: Callable = measure.DeviceTracer,
             first_run: bool = False) -> dict:
    """first_run: no run of this cell has got as far as its window in this
    checkout yet, so this one builds its programs and has the allowance of
    a run that compiles."""
    traffic = dict(cell["traffic"])
    cfg = cell["config"]
    if rehearsal is not None:
        traffic.update(rehearsal.get("traffic", {}))
        cfg = dict(cfg)
        cfg["work"] = dict(cfg["work"], **rehearsal.get("work", {}))
        cfg["assumed"] = dict(cfg["assumed"], **rehearsal.get("assumed", {}))
        cell = dict(cell, config=cfg)
    variant = cfg["variant"]
    plies = cfg["assumed"]["plies_per_game"]
    tcfg = cell["limits"]["trace"]
    evaluator = cell["evaluator"]
    weights = evaluator.load_weights(cfg["engine"], cell["root"])
    compiles = measure.CompileCounter()

    t0 = time.monotonic()
    # every --seed gets the traffic file's games, dealt out in another
    # order: the same work in every run
    pool_seed = traffic["pool_seed"]
    pool = games.make_games(weights, evaluator, variant, traffic["games"],
                            plies, pool_seed)
    game_list = [pool[g] for g in games.deal(len(pool), seed)]
    sessions = traffic.get("warm_sessions", [])
    need = max([s["positions"] for s in sessions] + [0])
    warm_games = games.make_games(
        weights, evaluator, variant, -(-need // (plies + 1)) if need else 0,
        plies, pool_seed ^ 0x5BD1E995)
    t_games = time.monotonic() - t0
    t0 = time.monotonic()
    adapter = make_engine()
    t_engine = time.monotonic() - t0
    loop_ = ClosedLoop(adapter, cell, traffic, game_list, seed)
    adapter.set_deliver_hook(loop_.on_deliver)
    sampler = measure.BoundarySampler(adapter.counters)
    state: dict = {}

    async def conduct():
        aio = asyncio.get_running_loop()
        aio.set_default_executor(concurrent.futures.ThreadPoolExecutor(
            max_workers=max(traffic["workers"], need // games.MAX_CHUNK_POSITIONS + 1) + 4))
        t_w = time.monotonic()
        for shape in traffic.get("warm_shapes", []):
            t_s, b0 = time.monotonic(), compiles.built
            # no catch: shapes left cold would compile inside the window
            adapter.warm_shapes(shape["width"], shape["counts"], variant,
                                loop_.root_fen)
            say(f"setup: refill shapes 1..{shape['counts']} of width "
                f"{shape['width']} warmed in {time.monotonic() - t_s:.1f} s, "
                f"{compiles.built - b0} programs built or loaded")
        await _warm_sessions(loop_, warm_games, sessions, say)
        state["t_warm"] = time.monotonic() - t_w
        state["built_warm"] = compiles.built
        # ---- the loop starts: worker 0 alone, the rest once it is driving
        seg0 = adapter.counters()["segments"]
        t_loop = time.monotonic()
        workers = [asyncio.ensure_future(loop_.worker())]
        while (adapter.counters()["segments"] == seg0
               and time.monotonic() - t_loop < 5.0):
            await asyncio.sleep(0.005)
        workers += [asyncio.ensure_future(loop_.worker())
                    for _ in range(traffic["workers"] - 1)]
        # ---- steady state: no program built or loaded for a while
        while True:
            await asyncio.sleep(0.05)
            el = time.monotonic() - t_loop
            quiet = time.monotonic() - max(compiles.last_at, t_loop)
            home = sum(1 for r in loop_.records.values()
                       if not r["warm"] and r["t_done"] is not None)
            # steady: more than one full latency has passed (two chunks per
            # worker are home) and nothing was built or loaded for a while
            if el >= traffic["preroll_max_s"] or (
                    el >= traffic["preroll_min_s"]
                    and home >= 2 * traffic["workers"]
                    and quiet >= traffic["preroll_quiet_s"]):
                break
        t_open = time.monotonic()
        t_close = t_open + seconds
        state.update(
            t_open=t_open, t_close=t_close, t_preroll=t_open - t_loop,
            occ_open=dict(adapter.counters()), built_open=compiles.snapshot())
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        state["occ_close"] = dict(adapter.counters())
        state["built_close"] = compiles.snapshot()
        state["close_late_s"] = time.monotonic() - t_close
        if trace:
            # the slice comes after the close, the loop still running: the
            # same steady state, and the window's counters pay nothing
            sampler.start()
            adapter.record_spans(True)
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer = tracer_factory(trace_dir, sampler, tcfg, adapter.segment_log)
            th = threading.Thread(target=tracer.take, daemon=True)
            th.start()
            state["tracer"], state["tracer_thread"] = tracer, th
            while not tracer.captured.is_set():
                await asyncio.sleep(0.02)
        loop_.stopping = True
        # a closed loop owes nothing past the close: what is in flight is
        # not counted, so it is not waited for either
        _done, pending = await asyncio.wait(workers, timeout=DRAIN_S)
        state["undrained"] = len(pending)
        for w in pending:
            w.cancel()

    asyncio.run(conduct())
    sampler.stop()
    t_open, t_close = state["t_open"], state["t_close"]
    setup_s = t_open - t_start

    # ------------------------------------------------- what the window saw
    answered, late, latencies, nodes = [], 0, [], []
    for t, key, index, url, resp in loop_.deliveries:
        rec = loop_.records.get(key)
        if rec is None or rec["warm"] or index is None:
            continue
        if not (t_open <= t < t_close):
            continue
        if not any(idx == index and u == url for idx, u, _m in rec["positions"]):
            continue  # nobody asked for it: a delivery fault, not an answer
        if t > rec["deadline"]:
            late += 1
            continue
        answered.append((t, key, index, url, resp, t - rec["t_sub"]))
        latencies.append(t - rec["t_sub"])
        nodes.append(_field(resp, "nodes"))
    raised = 0
    for rec in loop_.records.values():
        if rec["error"] and rec["t_done"] and t_open <= rec["t_done"] < t_close:
            raised += sum(1 for idx, _u, _m in rec["positions"] if idx is not None)
    failed = late + raised
    delivery_faults = _delivery_faults(loop_, adapter)
    widths = adapter.widths(state["occ_open"]["segments"],
                            state["occ_close"]["segments"])
    occ = {k: state["occ_close"][k] - state["occ_open"][k]
           for k in state["occ_close"]
           if isinstance(state["occ_close"][k], (int, float))}
    built_inside = state["built_close"]["built"] - state["built_open"]["built"]
    hits_inside = state["built_close"]["cache_hits"] - state["built_open"]["cache_hits"]

    say(f"setup: games {t_games:.1f} s, engine {t_engine:.1f} s, warm sessions "
        f"{state['t_warm']:.1f} s, loop until open {state['t_preroll']:.1f} s; "
        f"setup_s {setup_s:.3f}; {state['built_warm']} programs built or loaded "
        f"in the warm sessions, {state['built_open']['built']} by the time the "
        f"window opened ({state['built_open']['cache_hits']} from the cache)")
    say(f"window: {seconds} s, closed {state['close_late_s'] * 1e3:.1f} ms after the "
        f"clock; {len(answered)} answers, {late} late, {raised} of raised chunks, "
        f"{state['undrained']} workers not drained; programs built or loaded "
        f"inside: {built_inside} ({hits_inside} from the cache)")
    say(f"window: session widths by segment {dict(Counter(widths))}; "
        f"{occ.get('segments', 0)} segments, {occ.get('steps', 0)} steps, "
        f"blocked on the device {occ.get('device_ms', 0) / 1e3:.2f} s, host "
        f"{occ.get('host_ms', 0) / 1e3:.2f} s; lane-steps live/helper/idle "
        f"{occ.get('live_lane_steps', 0)}/{occ.get('helper_lane_steps', 0)}/"
        f"{occ.get('idle_lane_steps', 0)}")
    by_width = adapter.by_width(state["occ_open"]["segments"],
                                state["occ_close"]["segments"])
    blocked_s = sum(ms for _seg, _steps, ms in adapter.segment_log(
        state["occ_open"]["segments"], state["occ_close"]["segments"])) / 1e3
    if by_width:
        say("window: by width " + "; ".join(
            f"{w}: {v['segments']} seg {v['steps']} steps "
            f"{v['device_ms'] / 1e3:.1f}+{v['host_ms'] / 1e3:.1f} s "
            f"live {v['live']:.0f}% helper {v['helper']:.0f}% refilled {v['refilled']}"
            for w, v in sorted(by_width.items())))
    if nodes:
        say(f"work: {sum(nodes)} nodes over {len(nodes)} answered positions, "
            f"{sum(nodes) / len(nodes):.1f} per position (min {min(nodes)}, "
            f"median {statistics.median(nodes)}, max {max(nodes)}); budget "
            f"{cfg['work']['nodes']}")
        say(f"latency: p50 {measure.percentile_nearest(latencies, 50):.3f} s, p95 "
            f"{measure.percentile_nearest(latencies, 95):.3f} s, max {max(latencies):.3f} s "
            f"over {len(latencies)} answers")

    # ------------------------------- the device's numbers, then free it
    peak_bytes = adapter.memory_peak_bytes()
    fill = adapter.table_fill()  # after the peak is read: it adds nothing to it
    if fill is not None:
        say(f"table: {fill[0]} of {fill[1]} rows written by the time the window "
            f"closed ({100.0 * fill[0] / fill[1]:.3f} %); peak {peak_bytes} bytes")
    tr = None
    tracer = state.get("tracer")
    trace_failed = None
    if tracer is not None:
        trace_failed = _wait_for_tracer(
            tracer, state["tracer_thread"], tcfg, t_start, first_run)
        spans = adapter.host_spans()
        adapter.record_spans(False)
        if trace_failed is None:
            marks = tracer.marks() or [tracer.anchor_mark]
            slice_widths = dict(Counter(adapter.widths(marks[0][1], marks[-1][1])))
            marks = trace_reduce.log_marks(
                marks, adapter.segment_log(marks[0][1], marks[-1][1]))
    sample = _sample(answered, loop_, adapter, variant, seed,
                     cell["limits"]["sample"])
    adapter.set_deliver_hook(None)
    adapter.release()
    gc.collect()
    if tracer is not None and trace_failed is None:
        t1 = time.monotonic()
        raw = trace_reduce.load_xplane(trace_dir)
        tr = trace_reduce.reduce_trace(
            raw["ops"], raw["modules"], marks, spans,
            raw["anchor_ns"], tracer.anchor_mono)
        say(f"trace: slice {tracer.slice_s:.2f} s, stop_trace {tracer.stop_s:.1f} s, "
            f"read in {time.monotonic() - t1:.1f} s; {len(raw['ops'])} device ops; "
            f"{tr['intervals']} whole boundary intervals of {tr['window_s']} s: "
            f"{tr['segment_programs']} segment programs, {tr['segment_device_s']} s, "
            f"{tr['steps']} steps; device busy {tr['busy_s']} s, host blocked "
            f"{tr['wait_s']} s, busy per blocked second {tr['busy_per_wait']}")
        say(f"trace: cut {_slice_cut(raw, tracer)}; session widths by segment "
            f"{slice_widths}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif tracer is not None:
        # the profiler may still be writing: its directory is left alone
        say(f"trace: failed: {trace_failed}")

    # ------------------------------------------ the comparison, last
    t1 = time.monotonic()
    correct, checks, detail = reference.compare(
        reference.Reference(weights, evaluator), sample,
        {"delivery": delivery_faults, "programs_inside": built_inside},
        cell["limits"]["checks"])
    say(f"check: {detail['answers']} answers against the reference in "
        f"{time.monotonic() - t1:.1f} s; depth-1 compared {detail['d1_compared']}, "
        f"lines {detail['lines_compared']} (median gap {detail['leaf_gap_median']}, "
        f"p90 gap {detail['leaf_gap_p90']}), mean d1 gap {detail['d1_gap_mean']}, "
        f"worst d1 {detail['worst_d1']}; bad {detail['bad']}")

    window_s = float(seconds)
    # the slice holds a segment or two of the window's two hundred. What is
    # read as the device's busy time has to be the window's: the slice's
    # busy seconds per blocked second carried over the seconds the window's
    # log shows the host blocked
    busy_s = trace_reduce.window_busy_s(blocked_s, tr)
    e2e = {
        "positions_per_s": len(answered) / window_s,
        "setup_s": setup_s,
    }
    peak = cell["peaks"]["devices"].get(device["kind"])
    metrics: Dict[str, dict] = {}
    notes: dict = {}
    if rehearsal is not None:
        metrics = {}  # a rehearsal never prints a metric
    elif trace:
        if peak is None:
            raise SystemExit(f"benchmark: no peaks for device kind {device['kind']!r}")
        ctx = {
            "occupancy": occ, "window_s": window_s, "trace": tr,
            "busy_s": busy_s,
            "slice": _slice_steps(tr), "nodes": sum(nodes), "peak_bytes": peak_bytes,
            "peak": peak, "notes": notes,
            "latency": {"p95_s": measure.percentile_nearest(latencies, 95),
                        "answers": len(latencies)},
            "per_node": work_count.per_node(
                evaluator.net_work(cfg["net_shapes"]), cfg["max_moves"]),
            "config": cfg,
        }
        metrics = cells.read_per_layer(cell, ctx)
    else:
        for m in cell["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=peak_bytes)
    result = {
        "correct": bool(correct), "attempted": len(answered) + failed,
        "failed": failed, "metrics": metrics, "device": dev,
    }
    if trace and tr is not None and tr["busy_s"] is not None and rehearsal is None:
        if busy_s is not None:
            dev["busy_s"], dev["window_s"] = busy_s, window_s
        else:  # no whole boundary interval: the traced span as it is
            dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        notes["slice"] = dict({k: tr[k] for k in (
            "busy_s", "window_s", "intervals", "wait_s", "busy_per_wait")},
            session_widths=slice_widths)
        notes["blocked_s"] = blocked_s
    result["window"] = {
        "seconds": window_s, "answers": len(answered),
        "nodes_per_position": (sum(nodes) / len(nodes)) if nodes else None,
        "programs_inside": built_inside, "cache_hits_inside": hits_inside,
        "session_widths": dict(Counter(widths)),
        "rehearsal": rehearsal is not None, "control": control,
        "table_rows": list(fill) if fill is not None else None,
        "notes": notes,
    }
    result["checks"] = checks
    return result


def _delivery_faults(loop_: ClosedLoop, adapter) -> int:
    """Positions of finished chunks not answered exactly once under their
    own index and url, at the delivery point and in the returned list."""
    seen = Counter((key, index, url) for _t, key, index, url, _r in loop_.deliveries)
    faults = 0
    owed = set()
    for key, rec in loop_.records.items():
        if rec["t_done"] is None or rec["error"]:
            continue
        for idx, url, _mv in rec["positions"]:
            owed.add((key, idx, url))
            if seen.get((key, idx, url), 0) != 1:
                faults += 1
        got = rec["responses"] or []
        want = [(idx, url) for idx, url, _mv in rec["positions"]]
        have = [(_field(r, "position_index"), _field(r, "url")) for r in got]
        if have != want:
            faults += 1
    for key, index, url in seen:
        rec = loop_.records.get(key)
        if rec is not None and rec["t_done"] is not None and not rec["error"] \
                and (key, index, url) not in owed:
            faults += 1  # an answer nobody asked for
    return faults


def _sample(answered, loop_, adapter, variant, seed, size) -> List[dict]:
    """Answers to hold against the reference: drawn from the seed, the one
    that waited longest always among them."""
    if not answered:
        return []
    rng = random.Random(seed)
    longest = max(range(len(answered)), key=lambda i: answered[i][5])
    picks = set(rng.sample(range(len(answered)), min(size, len(answered))))
    picks.add(longest)
    out = []
    for i in sorted(picks):
        _t, key, index, url, resp, _lat = answered[i]
        rec = loop_.records[key]
        moves = next(mv for idx, _u, mv in rec["positions"] if idx == index)
        plain = resp if isinstance(resp, dict) else adapter.plain(resp)
        out.append(dict(plain, id=url, variant=variant, moves=list(moves)))
    return out


def _wait_for_tracer(tracer, thread, tcfg: dict, t_start: float,
                     first_run: bool) -> Optional[str]:
    """Wait for `stop_trace` as long as the run's own allowance permits:
    the contract's seconds from process start (`compiling` for a cell's
    first run in its checkout), less `reserve_s` for what is still to do
    after the wait. → why there is no trace to read, or None."""
    allowed = tcfg["run_allowance_s"]["compiling" if first_run else "warm"]
    t_wait = time.monotonic()
    thread.join(timeout=max(0.0, t_start + allowed - tcfg["reserve_s"] - t_wait))
    if tracer.error is not None:
        return tracer.error
    if thread.is_alive():
        return (f"the profiler had not returned {time.monotonic() - t_wait:.0f} s "
                f"after the slice ended, {time.monotonic() - t_start:.0f} s into a "
                f"run that is allowed {allowed} s ({tcfg['reserve_s']} s of them "
                f"kept for reading the trace and the comparison)")
    if tracer.slice_s is None or tracer.stop_s is None:
        return "the tracer ended without a slice"
    return None


def _slice_cut(raw, tracer) -> dict:
    """How the slice was cut, and what the profiler kept around it: the
    device ops that started before the anchor (while the profiler started)
    and after the slice's end (while it stopped)."""
    out = dict(getattr(tracer, "cut", None) or {})
    if raw["anchor_ns"] is not None:
        a0 = raw["anchor_ns"]
        a1 = a0 + int(tracer.slice_s * 1e9)
        starts = [o[1] for o in raw["ops"]]
        out["ops_before_anchor"] = sum(1 for t in starts if t < a0)
        out["ops_after_slice"] = sum(1 for t in starts if t >= a1)
    return out


def _slice_steps(tr: Optional[dict]) -> Optional[dict]:
    """Device time of the segment programs inside the slice's whole
    boundary intervals and the steps the scheduler accounted in them."""
    if not tr or not tr["intervals"] or not tr["segment_programs"]:
        return None
    return {"steps": tr["steps"], "device_s": tr["segment_device_s"],
            "segments": tr["segment_programs"]}
