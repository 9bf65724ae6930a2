"""A stand-in for the program, for tests of the harness: it answers every
position with what the benchmark's own reference gives (depth 1 with its
quiescence line, reported again as depth 2), through the same adapter
surface the real engine is wrapped in. Faults are planted by name."""
from __future__ import annotations

import asyncio
import contextlib
import json
import shutil

import numpy as np

from benchmark import cells, reference, rules

INF = reference.INF


def cell_weights(root, workload="standard.trickle"):
    """→ (weights, evaluator) of the cell's configuration, as the harness
    loads them."""
    cell = cells.load_cell(root, workload)
    evaluator = cell["evaluator"]
    return evaluator.load_weights(cell["config"]["engine"], root), evaluator


def qs_line(ref, p, alpha, beta, ply):
    """reference.Reference.qs, returning the line it backs up as well."""
    if p.halfmove >= 100:
        return 0, []
    stand = ref.eval(p)
    if ply >= reference.STACK_PLIES or stand >= beta:
        return stand, []
    best, line = stand, []
    alpha = max(alpha, stand)
    for mv in rules.pseudo_moves(p, captures_only=True):
        if alpha >= beta:
            break
        child = rules.make(p, mv)
        if rules.mover_left_king_en_prise(child):
            continue
        v, sub = qs_line(ref, child, -beta, -alpha, ply + 1)
        if -v > best:
            best, line = -v, [rules.uci(mv)] + sub
        alpha = max(alpha, best)
    return best, line


def bf16_weights(w):
    """Weights rounded to bfloat16 (round to nearest even on the top 16
    bits), as the program's FISHNET_TPU_DTYPE=bf16 stores them."""
    out = {}
    for k, a in w.items():
        u = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
        u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
        out[k] = u.astype(np.uint32).view(np.float32)
    return out


class FakeAdapter:
    name = "fake"

    def __init__(self, weights, evaluator, fault=None, latency_s=0.0):
        self.ref = reference.Reference(weights, evaluator)
        self.fault = fault
        self.latency_s = latency_s
        self.hook = None
        self.totals = {"segments": 0, "steps": 0, "lane_steps": 0,
                       "live_lane_steps": 0, "helper_lane_steps": 0,
                       "idle_lane_steps": 0, "host_ms": 0.0, "device_ms": 0.0}
        self.log = []  # (segment, steps, blocked ms), as the totals count them
        self.answered = 0

    def new_chunk(self, work_id, variant, nodes, timeout_s, deadline,
                  root_fen, positions):
        return {"work": work_id, "variant": variant, "nodes": nodes,
                "deadline": deadline, "positions": positions}

    def answer(self, variant, moves):
        root = reference.replay(variant, moves)
        best, line = -INF, []
        for mv in rules.pseudo_moves(root):
            child = rules.make(root, mv)
            if rules.mover_left_king_en_prise(child):
                continue
            v, sub = qs_line(self.ref, child, -INF, INF, 1)
            if -v > best:
                best, line = -v, [rules.uci(mv)] + sub
        return best, line, root

    async def go(self, chunk):
        out = []
        for idx, url, moves in chunk["positions"]:
            await asyncio.sleep(self.latency_s)
            score, line, root = self.answer(chunk["variant"], moves)
            self.answered += 1
            hit = self.fault is not None and self.answered % 3 == 0
            if hit and self.fault == "score":
                score += 7  # an answer altered where it is produced
            if hit and self.fault == "move":
                other = [rules.uci(m) for m in rules.legal_moves(root)
                         if rules.uci(m) != line[0]]
                line = [other[0]]
            if hit and self.fault == "illegal_line":
                line = line[:1] + ["a1a1"]
            # the deep search's bookkeeping gone wrong on every answer:
            # depth 1 stays as it is, the deeper score leaves its leaf
            deep = score + 7 if self.fault == "deep_score" else score
            resp = {"position_index": idx, "url": url, "depth": 2,
                    "nodes": chunk["nodes"]["sf16"], "best_move": line[0],
                    "scores": {1: ("cp", score), 2: ("cp", deep)},
                    "pvs": {1: list(line), 2: list(line)}}
            if hit and self.fault == "best_move":
                resp["best_move"] = "a1a1"
            if hit and self.fault == "wrong_index":
                resp["position_index"] = (idx or 0) + 100
            self.totals["segments"] += 1
            self.totals["steps"] += 10
            self.totals["lane_steps"] += 160
            self.totals["live_lane_steps"] += 40
            self.totals["device_ms"] += 1.0
            self.totals["host_ms"] += 0.5
            self.log.append((self.totals["segments"], 10, 1.0))
            if self.hook is not None:
                self.hook(chunk, resp["position_index"], url, resp)
                if hit and self.fault == "twice":
                    self.hook(chunk, idx, url, resp)
            if not (hit and self.fault == "dropped"):
                out.append(resp)
        return out

    def set_deliver_hook(self, fn):
        self.hook = fn

    @staticmethod
    def plain(resp):
        return resp

    def counters(self):
        return self.totals

    def widths(self, since_segment, until_segment=None):
        return []

    def by_width(self, since_segment, until_segment):
        return {}

    def segment_log(self, since_segment, until_segment):
        return [r for r in self.log if since_segment < r[0] <= until_segment]

    def queued(self):
        return 10 ** 9

    def warm_shapes(self, width, counts, variant, root_fen):
        if self.fault == "cold_shapes":
            raise RuntimeError("the program's refill path moved")
        return width

    @contextlib.contextmanager
    def hold(self):
        yield

    def record_spans(self, on):
        pass

    def host_spans(self):
        return []

    def memory_peak_bytes(self):
        return 0

    def table_fill(self):
        return None

    def release(self):
        pass


def tree_with_new_evaluator(tmp_path, root, name, source, engine, net_shapes,
                            **config):
    """A later PR's addition, made in a copy of the tree by new files and
    new entries alone: evaluator `name` (the text of its file is `source`),
    a configuration `name` that names it (standard.json's with `engine`,
    `net_shapes` and `config` laid over it, and no `net` file) and the cell
    `<name>.trickle`. → (the copy's root, the bytes of every file that was
    there before)."""
    new_root = tmp_path / "repo"
    bdir = new_root / "benchmark"
    shutil.copytree(root / "benchmark", bdir,
                    ignore=shutil.ignore_patterns("__pycache__", "weights"))
    before = {p: p.read_bytes() for p in bdir.rglob("*") if p.is_file()}
    (bdir / "evaluators" / f"{name}.py").write_text(source)
    cfg = json.load(open(bdir / "configs/standard.json"))
    kept = {k: v for k, v in cfg["engine"].items() if k != "net"}
    cfg.update(config, name=name, net_shapes=net_shapes,
               engine=dict(kept, evaluator=name, **engine))
    (bdir / f"configs/{name}.json").write_text(json.dumps(cfg))
    bench = json.load(open(root / "BENCHMARK.json"))
    bench["configs"].append({"name": name, "source": "x", "reduced": [],
                             "file": f"benchmark/configs/{name}.json", "why": "y"})
    bench["workloads"].append({"name": f"{name}.trickle", "config": name,
                               "traffic": "trickle", "chips": 1, "why": "z"})
    (new_root / "BENCHMARK.json").write_text(json.dumps(bench))
    return new_root, before


def toy_cell(root, workload="standard.trickle", variant=None, bench_dir=cells.HERE,
             **traffic):
    cell = cells.load_cell(root, workload, bench_dir=bench_dir)
    cell["traffic"] = dict(cell["traffic"], games=2, preroll_min_s=0.2,
                           preroll_quiet_s=0.0, preroll_max_s=0.5,
                           warm_sessions=[], **traffic)
    cfg = dict(cell["config"])
    cfg["assumed"] = dict(cfg["assumed"], plies_per_game=6)
    if variant is not None:  # a configuration a later PR would add as a file
        cfg["variant"] = variant
    cell["config"] = cfg
    cell["limits"] = dict(cell["limits"], sample=24)
    return cell
