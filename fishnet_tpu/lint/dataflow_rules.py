"""Per-function dataflow rules: donated-buffer lifetimes and async
shared-state mutation ordering.

Two rules, one engine each:

`jit-donate-use-after` — the PR-5/PR-8 bug class as a lint error. The
segment/merge/init jits donate operands (`donate_argnums` /
`donate_argnames` in ops/search.py and parallel/mesh.py), so the input
handles are dead the moment the call is issued and every caller must
rebind to the outputs. XLA:CPU only *warns* when donation is unusable,
so a use-after-donate passes the CPU test tier silently and corrupts
on the TPU. The rule runs a forward def-use pass over every function:
a name passed in a donated position becomes *dead*; any later read of
it is a finding unless an assignment rebound the name first.

The pass is deliberately may-miss, never may-false-positive, because
the pipelined scheduler loops donate speculatively on one branch and
read the same name only on the mutually-exclusive other branch:

- at an `if` join the dead set is the INTERSECTION of the branches
  (a name donated on only one path is considered live after the join);
- loop bodies get two passes so a donation at the tail of iteration i
  is seen by a read at the head of iteration i+1;
- a bare-name alias (`cur = p_state`) propagates deadness without
  itself counting as a read — the alias copies the handle, it does not
  touch the buffer;
- nested `def`s are analyzed as their own functions (a closure body
  runs at call time, not at definition time).

`conc-await-shared-mutate` — check-then-act races in the asyncio
layer (the PR-12 plan-time admission bug). Inside an `async def` in
serve/, fleet/, or cache/, a read of `obj.attr` followed by an `await`
followed by a write to the same `obj.attr` means the written value was
computed from state another task may have changed during the
suspension. Exempt when both ends sit under one enclosing lock
`with`/`async with`, when the function carries a
`# fishnet-lint: single-writer` annotation (same line as the `async
def` or the line directly above), or when the write is an augmented
assignment (its own read does not straddle anything). Sync helpers are
out of scope — they run under `to_thread`/executors or atomically
between suspension points.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import Finding, Project, SourceFile, dotted, register_family

# ------------------------------------------------- jit-donate-use-after

# The known donating entry points (ops/search.py, parallel/mesh.py) and
# their donated positions: {callee-name: (argnums, argnames)}. These
# apply everywhere in scope — the names are unambiguous.
DONATING_CALLS: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {
    "_run_segment_jit": ((1, 2), ()),      # state, tt
    "_splice_lanes_jit": ((1,), ()),       # state (after params)
    "_init_state_jit": ((), ("hist_hash", "hist_halfmove")),
    "run_segment_sharded": ((2, 3), ()),   # state, ttab (after mesh, params)
    "refill_lanes_sharded": ((2,), ()),    # state
    "refill_lanes": ((1,), ()),            # state
}

# Local closure wrappers over the donating jits inside search_stream.
# The names are generic, so they only register there. (The engine's
# _Session holds its state and table as attributes, each rebound in the
# statement that donates it: nothing for a flow over names to follow.)
WRAPPER_SCOPE = ("fishnet_tpu/ops/search.py",)
WRAPPER_DONATING_CALLS: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {
    "dispatch": ((0, 1), ()),              # st, table
    "do_refill": ((0,), ()),               # st
}

# tests/ deliberately poke donated handles (the is_deleted regression
# tests in test_pipeline.py / test_mesh_refill.py assert the read
# RAISES); the package, drivers and bench carry the rebind discipline.
DONATE_SCOPE = ("fishnet_tpu/", "tools/", "bench.py")


def _int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """A literal int or tuple-of-ints, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, ast.Tuple):
        out: List[int] = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, ast.Tuple):
        out: List[str] = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, str)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _module_jit_donations(
    tree: ast.Module,
) -> Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    """Names bound (at any nesting) to an expression containing a
    `jax.jit(..., donate_argnums=...)` call: `_my_jit = jax.jit(fn,
    donate_argnums=(1,))` or `_my_jit = registry.wrap("k", jax.jit(fn,
    donate_argnums=(1, 2)), ...)`."""
    found: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        donation = None
        for call in ast.walk(node.value):
            if not isinstance(call, ast.Call):
                continue
            if not dotted(call.func).endswith("jit"):
                continue
            nums: Tuple[int, ...] = ()
            names: Tuple[str, ...] = ()
            for kw in call.keywords:
                if kw.arg == "donate_argnums":
                    nums = _int_tuple(kw.value) or ()
                elif kw.arg == "donate_argnames":
                    names = _str_tuple(kw.value) or ()
            if nums or names:
                donation = (nums, names)
                break
        if donation is None:
            continue
        for target in node.targets:
            if isinstance(target, ast.Name):
                found[target.id] = donation
    return found


class _DeadSet:
    """Names whose device buffers were donated: name -> donating site
    description (for the finding message)."""

    def __init__(self, entries: Optional[Dict[str, str]] = None) -> None:
        self.entries: Dict[str, str] = dict(entries or {})

    def copy(self) -> "_DeadSet":
        return _DeadSet(self.entries)

    @staticmethod
    def intersect(sets: Sequence["_DeadSet"]) -> "_DeadSet":
        if not sets:
            return _DeadSet()
        keys = set(sets[0].entries)
        for s in sets[1:]:
            keys &= set(s.entries)
        return _DeadSet({k: sets[0].entries[k] for k in keys})


class _DonateFlow:
    """Forward flow over one function body."""

    def __init__(
        self,
        src: SourceFile,
        registry: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]],
    ) -> None:
        self.src = src
        self.registry = registry
        # findings dedup across the two loop passes: (line, col, name)
        self.findings: Dict[Tuple[int, int, str], Finding] = {}

    # -- entry point

    def run(self, fn: ast.AST) -> List[Finding]:
        self._block(getattr(fn, "body", []), _DeadSet())
        return [self.findings[k] for k in sorted(self.findings)]

    # -- statement flow

    def _block(self, stmts: Iterable[ast.stmt], dead: _DeadSet) -> _DeadSet:
        for stmt in stmts:
            dead = self._stmt(stmt, dead)
        return dead

    def _stmt(self, stmt: ast.stmt, dead: _DeadSet) -> _DeadSet:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def runs later; its body is its own function.
            # Binding the name kills nothing.
            return dead
        if isinstance(stmt, ast.If):
            self._expr(stmt.test, dead)
            body = self._block(stmt.body, dead.copy())
            orelse = self._block(stmt.orelse, dead.copy())
            return _DeadSet.intersect([body, orelse])
        if isinstance(stmt, (ast.While, ast.For, ast.AsyncFor)):
            if isinstance(stmt, ast.While):
                self._expr(stmt.test, dead)
            else:
                self._expr(stmt.iter, dead)
                self._bind(stmt.target, dead)
            # two passes: a donation at the body's tail reaches a read
            # at its head on the next iteration
            once = self._block(stmt.body, dead.copy())
            twice = self._block(stmt.body, once.copy())
            after = _DeadSet.intersect([dead, once, twice])
            return self._block(stmt.orelse, after)
        if isinstance(stmt, ast.Try):
            body = self._block(stmt.body, dead.copy())
            outs = [body]
            for handler in stmt.handlers:
                h = _DeadSet.intersect([dead, body])
                if handler.name:
                    h.entries.pop(handler.name, None)
                outs.append(self._block(handler.body, h))
            merged = _DeadSet.intersect(outs)
            merged = self._block(stmt.orelse, merged)
            return self._block(stmt.finalbody, merged)
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr, dead)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, dead)
            return self._block(stmt.body, dead)
        if isinstance(stmt, ast.Assign):
            alias = self._alias_source(stmt.value, dead)
            if alias is None:
                self._expr(stmt.value, dead)
            for target in stmt.targets:
                self._bind(target, dead)
                if alias is not None and isinstance(target, ast.Name):
                    dead.entries[target.id] = alias
            return dead
        if isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            if stmt.value is not None:
                self._expr(stmt.value, dead)
            if isinstance(stmt, ast.AugAssign):
                # x += v reads x
                self._expr(stmt.target, dead, store_ok=False)
            self._bind(stmt.target, dead)
            return dead
        if isinstance(stmt, ast.Delete):
            for target in stmt.targets:
                if isinstance(target, ast.Name):
                    dead.entries.pop(target.id, None)
                else:
                    self._expr(target, dead)
            return dead
        if isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                self._expr(stmt.value, dead)
            return dead
        if isinstance(stmt, ast.ClassDef):
            return dead
        # Raise, Assert, Global, Import, Pass, Break, Continue, ...
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._expr(child, dead)
        return dead

    # -- expression flow

    def _alias_source(
        self, value: ast.expr, dead: _DeadSet
    ) -> Optional[str]:
        """`a = b` where b is a dead bare name: the alias copies the
        handle without touching the buffer — propagate, don't flag."""
        if isinstance(value, ast.Name) and value.id in dead.entries:
            return dead.entries[value.id]
        return None

    def _bind(self, target: ast.expr, dead: _DeadSet) -> None:
        """An assignment target rebinds names: they hold live handles
        again (the rebind-to-outputs discipline)."""
        if isinstance(target, ast.Name):
            dead.entries.pop(target.id, None)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, dead)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, dead)
        else:
            # obj.attr = v / obj[k] = v: the base expression is read
            self._expr(target, dead, store_ok=True)

    def _expr(self, node: ast.expr, dead: _DeadSet,
              store_ok: bool = False) -> None:
        """Walk an expression: flag reads of dead names, then apply any
        donations its calls perform."""
        if isinstance(node, ast.Call):
            self._call(node, dead)
            return
        if isinstance(node, ast.Name):
            if node.id in dead.entries:
                self._flag(node, dead)
            return
        if isinstance(node, (ast.Lambda, ast.GeneratorExp, ast.ListComp,
                             ast.SetComp, ast.DictComp)):
            # deferred/scoped bodies: comprehension iterables evaluate
            # now, the rest is its own scope — only walk the first iter
            gens = getattr(node, "generators", [])
            if gens:
                self._expr(gens[0].iter, dead)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child, dead)

    def _call(self, call: ast.Call, dead: _DeadSet) -> None:
        # callee expression and every argument are reads first: passing
        # an already-dead name anywhere (donated position or not) is a
        # use-after-donate
        self._expr(call.func, dead)
        for arg in call.args:
            self._expr(arg, dead)
        for kw in call.keywords:
            self._expr(kw.value, dead)

        name = dotted(call.func)
        short = name.rsplit(".", 1)[-1] if name else ""
        donation = self.registry.get(short)
        if donation is None:
            return
        argnums, argnames = donation
        site = f"{short}() at line {call.lineno}"
        for i in argnums:
            if i < len(call.args) and isinstance(call.args[i], ast.Name):
                dead.entries[call.args[i].id] = site
        for kw in call.keywords:
            if (kw.arg in argnames and isinstance(kw.value, ast.Name)):
                dead.entries[kw.value.id] = site

    def _flag(self, node: ast.Name, dead: _DeadSet) -> None:
        site = dead.entries.pop(node.id)  # one finding per donation
        key = (node.lineno, node.col_offset, node.id)
        if key not in self.findings:
            self.findings[key] = self.src.finding(
                "jit-donate-use-after", node,
                f"'{node.id}' was donated into {site} and its device "
                f"buffer is dead; rebind the name from the call's "
                f"outputs before reading it (donation is only a "
                f"warning on CPU — this corrupts on TPU)",
            )


def _check_donate_use_after(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for src in project.in_dirs(*DONATE_SCOPE):
        registry = dict(DONATING_CALLS)
        if src.rel in WRAPPER_SCOPE:
            registry.update(WRAPPER_DONATING_CALLS)
        registry.update(_module_jit_donations(src.tree))
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(_DonateFlow(src, registry).run(node))
    return findings


# --------------------------------------------- conc-await-shared-mutate

AWAIT_MUTATE_SCOPE = (
    "fishnet_tpu/serve",
    "fishnet_tpu/fleet",
    "fishnet_tpu/cache",
)

_SINGLE_WRITER_MARK = "fishnet-lint: single-writer"


def _attr_path(node: ast.expr) -> str:
    """Dotted path of an attribute chain rooted at a bare name
    ('self.stats.chunks_ok', 'member.busy_until'); '' otherwise."""
    return dotted(node)


def _is_lock_name(name: str) -> bool:
    return "lock" in name.lower()


class _AsyncEvents(ast.NodeVisitor):
    """Ordered reads/writes/awaits of one async def's own statements
    (nested defs excluded — they run under to_thread or later)."""

    def __init__(self) -> None:
        self.awaits: List[Tuple[int, int]] = []
        # key -> [(pos, node, lock-ids)]
        self.reads: Dict[str, List[Tuple[Tuple[int, int], ast.AST,
                                         frozenset]]] = {}
        self.writes: Dict[str, List[Tuple[Tuple[int, int], ast.AST,
                                          frozenset]]] = {}
        self._locks: List[int] = []

    def _pos(self, node: ast.AST) -> Tuple[int, int]:
        return (getattr(node, "lineno", 0), getattr(node, "col_offset", 0))

    def _record(self, table, key: str, node: ast.AST) -> None:
        table.setdefault(key, []).append(
            (self._pos(node), node, frozenset(self._locks)))

    # nested functions are their own analysis units
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        pass

    def visit_Lambda(self, node: ast.Lambda) -> None:
        pass

    def visit_Await(self, node: ast.Await) -> None:
        self.awaits.append(self._pos(node))
        self.generic_visit(node)

    def _visit_with(self, node) -> None:
        def ctx_name(expr: ast.expr) -> str:
            if isinstance(expr, ast.Call):
                return dotted(expr.func)
            return dotted(expr)

        locked = any(
            _is_lock_name(ctx_name(item.context_expr))
            for item in node.items
        )
        for item in node.items:
            self.visit(item.context_expr)
        if locked:
            self._locks.append(id(node))
        for stmt in node.body:
            self.visit(stmt)
        if locked:
            self._locks.pop()

    def visit_With(self, node: ast.With) -> None:
        self._visit_with(node)

    def visit_AsyncWith(self, node: ast.AsyncWith) -> None:
        self._visit_with(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        for target in node.targets:
            self._target(target)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self.visit(node.value)
        self._target(node.target)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        # atomic read-modify-write between suspension points: the
        # embedded read never straddles an await; the write still can
        self.visit(node.value)
        self._target(node.target, aug=True)

    def visit_Delete(self, node: ast.Delete) -> None:
        for target in node.targets:
            self._target(target)

    def _target(self, target: ast.expr, aug: bool = False) -> None:
        # an augmented target reads and writes at ONE point between
        # suspension points — its value never depends on a pre-await
        # read, so it does not participate in straddle checks
        if isinstance(target, ast.Attribute):
            key = _attr_path(target)
            if key:
                if not aug:
                    self._record(self.writes, key, target)
                return
        if isinstance(target, ast.Subscript):
            key = _attr_path(target.value)
            if key:
                # obj.attr[k] = v mutates the container held by the
                # attribute (the ledger/journal shape)
                if not aug:
                    self._record(self.writes, key, target)
                self.visit(target.slice)
                return
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._target(elt)
            return
        if isinstance(target, ast.Starred):
            self._target(target.value)
            return
        if not isinstance(target, ast.Name):
            self.visit(target)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        if isinstance(node.ctx, ast.Load):
            key = _attr_path(node)
            if key:
                self._record(self.reads, key, node)
                return  # the inner chain is part of this read
        self.generic_visit(node)


def _single_writer_annotated(src: SourceFile, fn: ast.AST) -> bool:
    line = getattr(fn, "lineno", 1)
    for deco in getattr(fn, "decorator_list", []):
        line = min(line, getattr(deco, "lineno", line))
    for i in (line - 1, line):  # line above the def, and the def line
        if 1 <= i <= len(src.lines) and _SINGLE_WRITER_MARK in src.lines[i - 1]:
            return True
    return False


def _check_await_shared_mutate(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for src in project.in_dirs(*AWAIT_MUTATE_SCOPE):
        for fn in ast.walk(src.tree):
            if not isinstance(fn, ast.AsyncFunctionDef):
                continue
            if _single_writer_annotated(src, fn):
                continue
            events = _AsyncEvents()
            for stmt in fn.body:
                events.visit(stmt)
            if not events.awaits:
                continue
            for key, writes in events.writes.items():
                reads = events.reads.get(key, [])
                if not reads:
                    continue
                for w_pos, w_node, w_locks in writes:
                    straddles = any(
                        r_pos < a_pos < w_pos
                        and not (r_locks & w_locks)
                        for r_pos, _r, r_locks in reads
                        for a_pos in events.awaits
                    )
                    if straddles:
                        findings.append(src.finding(
                            "conc-await-shared-mutate", w_node,
                            f"'{key}' is read before an await and "
                            f"written after it: the written value was "
                            f"computed from state another task may "
                            f"have changed during the suspension. "
                            f"Guard both ends with one lock, move the "
                            f"check next to the write, or annotate "
                            f"the function '# {_SINGLE_WRITER_MARK}' "
                            f"if only this task ever writes it",
                        ))
                        break  # one finding per write site
    return findings


@register_family("dataflow")
def dataflow_rules(project: Project) -> List[Finding]:
    """Donated-buffer lifetime tracking and async check-then-act races."""
    findings = _check_donate_use_after(project)
    findings.extend(_check_await_shared_mutate(project))
    return findings
