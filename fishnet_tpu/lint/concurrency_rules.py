"""Concurrency rules for the supervisor/worker/queue machinery.

The supervisor exists because a wedged device must never wedge the
client (docs/tpu-hang.md); these rules keep the discipline that makes
that true:

  conc-no-timeout      .join()/.get()/.wait()/.recv() with no timeout
                       and no surrounding asyncio.wait_for — an
                       unbounded block on a peer that may be wedged
  conc-block-in-lock   a known-blocking call inside `with <lock>:` —
                       one stalled peer stalls every lock waiter
  conc-bare-except     `except:` catches SystemExit/KeyboardInterrupt
  conc-swallow-base    `except BaseException:` without a re-raise
  conc-silent-except   a broad handler (Exception/BaseException/bare)
                       whose body neither logs nor raises — failures
                       vanish without a trace
  conc-host-sync       a blocking host sync (int(), np.asarray(),
                       .block_until_ready(), jax.device_get) applied to
                       a device-resident value inside the scheduler
                       loop — every such sync stalls the pipeline and
                       escapes the SyncStats transfer accounting
  conc-journal-writer  the supervisor's session journal
                       (self._journal / self._journal_expect) mutated
                       outside its delivery path — the recovery ladder
                       trusts exactly-once journal contents, so the
                       single-writer invariant allows mutation only in
                       _journal_record/_journal_reset/__init__
  conc-sock-in-loop    a known-blocking socket/IO call (socket.*,
                       time.sleep, urllib, http.client) inside an
                       `async def` of the serving package — one blocked
                       handler freezes every connection the event loop
                       owns; use asyncio streams / asyncio.sleep /
                       run_in_executor instead
  conc-unbounded-retry an unbounded loop (`while True`, for-over-
                       itertools.count) that awaits a network call and
                       catches transport-level failures back into the
                       next iteration — a dead peer spins the retry
                       forever; bound it with an attempt cap
                       (`for attempt in range(N)`) or a deadline guard
                       that breaks/raises (fleet/remote.py's in-dispatch
                       retry is the canonical shape)

Scopes: the timeout/lock rules run on the process-boundary modules
(supervisor, host, uci, workers, queue), on fishnet_tpu/serve/ (the
HTTP front-end is a process boundary too), on fishnet_tpu/fleet/
(the coordinator fans out across N member processes/machines), and on
fishnet_tpu/aot/ (registry export threads and flush() joins sit on the
engine boot path); the except rules run on all of client/, engine/,
serve/, fleet/ and aot/ (kernels and utils keep their own idioms —
e.g. compile_cache deliberately degrades to "no cache" on any error).
The sock-in-loop rule runs on serve/ and fleet/ — the packages whose
code lives inside a single shared event loop.
Narrow handlers (`except OSError: pass` around best-effort logging) are
deliberately not flagged — the rules target *broad* swallowing.

The host-sync rule runs on the scheduler-loop modules (engine/tpu.py's
LaneScheduler and ops/search.py's stream/batch loops): values that
flow from the segment dispatch jits (`_run_segment_jit`,
`_init_state_jit`, `_splice_lanes_jit`, `refill_lanes`,
`extract_results`, the shard_map'd mesh callables
`run_segment_sharded`/`refill_lanes_sharded`, or a local
`dispatch` wrapper) are device-resident, and the only
sanctioned way to materialize one on the host inside a `while` loop is
`SyncStats.fetch`, which counts the transfer and measures the blocked
time (utils/syncstats.py).
`stats.fetch(x)` is naturally absolved — the rule tracks the names, and
a fetch result is a host value, not a device one.
"""
from __future__ import annotations

import ast
from typing import List

from .core import (
    Finding,
    Project,
    dotted,
    register_family,
)

# modules where an unbounded block is a liveness bug. fishnet_tpu/aot
# is in scope: the registry's export threads and flush() joins sit on
# the engine boot path, and an unbounded wait there wedges warmup.
# fishnet_tpu/fleet covers the autoscaler (fleet/autoscaler.py) by
# prefix; tools/loadgen.py is named explicitly — its open-loop firing
# task shares the client event loop, so the same liveness rules apply
BLOCK_SCOPE = (
    "fishnet_tpu/engine/supervisor.py",
    "fishnet_tpu/engine/host.py",
    "fishnet_tpu/engine/uci.py",
    "fishnet_tpu/client/workers.py",
    "fishnet_tpu/client/queue.py",
    "fishnet_tpu/serve",
    "fishnet_tpu/fleet",
    "fishnet_tpu/aot",
    "fishnet_tpu/cache",
    "tools/loadgen.py",
)

# modules where a swallowed exception hides an operational failure
EXCEPT_SCOPE = ("fishnet_tpu/client", "fishnet_tpu/engine",
                "fishnet_tpu/serve", "fishnet_tpu/fleet",
                "fishnet_tpu/aot", "fishnet_tpu/cache",
                "tools/loadgen.py")

# these packages run inside ONE shared event loop: a blocking socket
# call in an async def stalls every tenant (serve), every member
# dispatch (fleet — the autoscaler control loop rides the same loop),
# or every open-loop arrival (tools/loadgen.py) at once
SERVE_ASYNC_SCOPE = ("fishnet_tpu/serve", "fishnet_tpu/fleet",
                     "fishnet_tpu/cache", "tools/loadgen.py")

# call targets that block the thread: raw socket ops, sync HTTP
# clients, and the sleep that should have been asyncio.sleep. Matched
# against the dotted call name: exact for the module-level forms,
# attribute-tail for the socket-object methods (asyncio stream APIs —
# read/readline/readexactly/write/drain — are deliberately absent)
_BLOCKING_IN_LOOP_EXACT = ("time.sleep", "socket.socket",
                           "socket.create_connection", "socket.getaddrinfo",
                           "urllib.request.urlopen")
_BLOCKING_IN_LOOP_TAILS = ("accept", "connect", "recv", "recv_into",
                           "sendall", "makefile", "urlopen",
                           "HTTPConnection", "HTTPSConnection")

# modules that talk to peers over the wire: an unbounded retry loop
# here turns one dead peer into a coroutine that spins forever.
# tools/loadgen.py is open-loop BY CONTRACT — a retry loop there would
# silently convert it to closed-loop — so the same rule polices it
RETRY_SCOPE = ("fishnet_tpu/fleet", "fishnet_tpu/serve",
               "fishnet_tpu/client", "fishnet_tpu/cache",
               "tools/loadgen.py")

# awaited call tails that reach the network. Deliberately narrow:
# `acquire`/`go_multiple` are absent so the work queue's long-poll
# (client/queue.py) and the worker dispatch loop (client/workers.py)
# stay clean — their loops are exit-condition driven, not retry loops
_RETRY_NET_TAILS = ("open_connection", "open_unix_connection",
                    "readline", "readexactly", "readuntil", "drain",
                    "sendall", "urlopen", "getresponse",
                    "_round_trip", "_round_trip_inner", "_attempt",
                    "healthz")

# transport-level exception tails: catching one of these and looping
# again is a retry. Application errors (ApiError, ShuttingDown) are
# excluded — handlers for those encode protocol flow, not redial
_RETRY_EXC_TAILS = ("OSError", "ConnectionError", "ConnectionRefusedError",
                    "ConnectionResetError", "ConnectionAbortedError",
                    "BrokenPipeError", "TimeoutError",
                    "IncompleteReadError", "EngineError", "MemberFault",
                    "MemberBusy")

# for-loop iterables that never run dry
_RETRY_INFINITE_ITERS = ("count", "cycle", "repeat")

# the scheduler loops: blocking host syncs here stall the segment
# pipeline — engine/tpu.py holds the LaneScheduler, ops/search.py the
# stream/batch segment loops (both dispatch the sharded mesh callables)
HOST_SYNC_SCOPE = (
    "fishnet_tpu/engine/tpu.py",
    "fishnet_tpu/ops/search.py",
)

# the session journal lives in the supervisor; its single-writer
# invariant is what lets the recovery ladder trust exactly-once contents
JOURNAL_SCOPE = ("fishnet_tpu/engine/supervisor.py",)
_JOURNAL_ATTRS = ("_journal", "_journal_expect")
_JOURNAL_WRITERS = ("_journal_record", "_journal_reset", "__init__")
_MUT_METHODS = ("update", "pop", "clear", "setdefault", "popitem",
                "add", "discard", "remove")

# calls whose results are device arrays (or tuples of them); a local
# `dispatch` wrapping the segment jit counts too, as do the shard_map'd
# mesh callables (parallel/mesh.py) the sharded scheduler drives
_DEVICE_PRODUCERS = ("_run_segment_jit", "_init_state_jit",
                     "_splice_lanes_jit", "refill_lanes", "extract_results",
                     "dispatch",
                     "run_segment_sharded", "refill_lanes_sharded")

# attribute calls that block the caller until a peer acts
_WAITING_ATTRS = ("join", "get", "wait", "recv")

# calls that block; write_frame is excluded deliberately — host.py's
# `with wlock: write_frame(...)` is the intended frame-stream serializer
_BLOCKING_IN_LOCK = ("join", "get", "wait", "recv", "sleep", "read_frame",
                     "acquire")

_BROAD = ("Exception", "BaseException")

_LOG_ATTRS = ("debug", "info", "warn", "warning", "error", "exception",
              "log", "headline", "progress")


def _parents(tree: ast.AST) -> dict:
    out = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def _inside_wait_for(node: ast.AST, parents: dict) -> bool:
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, ast.Call) and \
                dotted(cur.func).split(".")[-1] == "wait_for":
            return True
        cur = parents.get(cur)
    return False


def _handler_type_names(handler: ast.ExceptHandler) -> List[str]:
    t = handler.type
    if t is None:
        return []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    return [dotted(e).split(".")[-1] for e in elts]


def _body_raises(body: List[ast.stmt]) -> bool:
    return any(isinstance(n, ast.Raise) for n in ast.walk(ast.Module(
        body=body, type_ignores=[])))


def _body_logs(body: List[ast.stmt]) -> bool:
    for stmt in body:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            target = dotted(node.func)
            tail = target.split(".")[-1]
            if tail in _LOG_ATTRS or target in ("print", "log"):
                return True
    return False


def _body_trivial(body: List[ast.stmt]) -> bool:
    """pass/continue/break/`return <constant>`/docstring only — the
    handler observably does nothing with the failure."""
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            continue
        if isinstance(stmt, ast.Return) and (
            stmt.value is None or isinstance(stmt.value, ast.Constant)
        ):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant):
            continue
        return False
    return True


def _assign_targets(node: ast.Assign) -> List[str]:
    out: List[str] = []
    for t in node.targets:
        elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
        for e in elts:
            if isinstance(e, ast.Name):
                out.append(e.id)
    return out


def _sync_sink(call: ast.Call, device: set) -> str:
    """Name of the device-resident value this call blocks on, or ''."""
    target = dotted(call.func)
    tail = target.split(".")[-1]
    arg = call.args[0] if call.args else None
    if target == "int" or tail in ("asarray", "device_get",
                                   "block_until_ready"):
        if isinstance(arg, ast.Name) and arg.id in device:
            return arg.id
    # method form: state.block_until_ready()
    if isinstance(call.func, ast.Attribute) and \
            call.func.attr == "block_until_ready" and \
            isinstance(call.func.value, ast.Name) and \
            call.func.value.id in device:
        return call.func.value.id
    return ""


def _check_host_sync(src, findings: List[Finding]) -> None:
    """Forward flow per function: names fed from the segment-dispatch
    jits are device-resident until rebound; materializing one inside a
    `while` loop other than via SyncStats.fetch is a finding."""
    parents = _parents(src.tree)

    def in_while(node: ast.AST) -> bool:
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.While):
                return True
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            cur = parents.get(cur)
        return False

    for fn in ast.walk(src.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        device: set = set()
        stmts = sorted(
            (n for n in ast.walk(fn)
             if isinstance(n, (ast.Assign, ast.Expr, ast.AugAssign))),
            key=lambda n: (n.lineno, n.col_offset),
        )
        for stmt in stmts:
            # sinks first: the RHS evaluates before the rebind
            for node in ast.walk(stmt):
                if isinstance(node, ast.Call) and in_while(node):
                    name = _sync_sink(node, device)
                    if name:
                        findings.append(src.finding(
                            "conc-host-sync", node,
                            f"blocking host sync on device value "
                            f"'{name}' inside the scheduler loop; route "
                            "it through SyncStats.fetch so the transfer "
                            "is counted and the blocked time measured",
                        ))
            if not isinstance(stmt, ast.Assign):
                continue
            val = stmt.value
            is_device = False
            if isinstance(val, ast.Call):
                tail = dotted(val.func).split(".")[-1]
                is_device = tail in _DEVICE_PRODUCERS
            elif isinstance(val, ast.Name):
                is_device = val.id in device
            elif isinstance(val, ast.Subscript) and \
                    isinstance(val.value, ast.Name):
                # tt = pend[1]: slicing a device tuple stays on device
                is_device = val.value.id in device
            for name in _assign_targets(stmt):
                if is_device:
                    device.add(name)
                else:
                    device.discard(name)


def _journal_attr(node: ast.AST) -> str:
    """'_journal'/'_journal_expect' if node is (a subscript of) that
    attribute on self, else ''."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and node.attr in _JOURNAL_ATTRS and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return ""


def _check_journal_writer(src, findings: List[Finding]) -> None:
    """Single-writer invariant for the supervisor's session journal:
    any rebind, item write, delete, or mutating method call on
    self._journal / self._journal_expect outside the sanctioned delivery
    path is a finding."""
    parents = _parents(src.tree)

    def enclosing_fn(node: ast.AST) -> str:
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur.name
            cur = parents.get(cur)
        return ""

    for node in ast.walk(src.tree):
        name = ""
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                name = name or _journal_attr(t)
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                name = name or _journal_attr(t)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in _MUT_METHODS:
            name = _journal_attr(node.func.value)
        if name and enclosing_fn(node) not in _JOURNAL_WRITERS:
            findings.append(src.finding(
                "conc-journal-writer", node,
                f"self.{name} mutated outside the supervisor's delivery "
                "path; the session journal is single-writer so the "
                "recovery ladder can trust exactly-once contents — "
                "route the write through _journal_record/_journal_reset",
            ))


def _check_sock_in_loop(src, findings: List[Finding]) -> None:
    """Blocking socket/IO calls inside an `async def`: the serving
    package's handlers all share one event loop, so a single blocking
    call freezes every connection. Sync helpers nested inside the async
    function are skipped — they run under to_thread/run_in_executor by
    construction (that's the sanctioned escape hatch)."""

    def async_body_calls(fn: ast.AsyncFunctionDef):
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # sync helper / inner coroutine (walked on its own)
            if isinstance(node, ast.Call):
                yield node
            stack.extend(ast.iter_child_nodes(node))

    for fn in ast.walk(src.tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for call in async_body_calls(fn):
            target = dotted(call.func)
            tail = target.split(".")[-1]
            if target in _BLOCKING_IN_LOOP_EXACT or \
                    tail in _BLOCKING_IN_LOOP_TAILS:
                findings.append(src.finding(
                    "conc-sock-in-loop", call,
                    f"blocking call {target}() inside an async handler "
                    "stalls the shared event loop — every tenant freezes "
                    "together; use asyncio streams / asyncio.sleep, or "
                    "push it through run_in_executor",
                ))


def _loop_unbounded(loop: ast.AST) -> bool:
    """True for loops with no intrinsic iteration cap: `while True`
    (or any constant-true test) and `for _ in itertools.count()`-style
    infinite iterables. A `while` over a real condition or a `for`
    over range()/a collection bounds itself."""
    if isinstance(loop, ast.While):
        return isinstance(loop.test, ast.Constant) and bool(loop.test.value)
    if isinstance(loop, ast.For):
        it = loop.iter
        return isinstance(it, ast.Call) and \
            dotted(it.func).split(".")[-1] in _RETRY_INFINITE_ITERS
    return False


def _walk_loop_body(loop: ast.AST):
    """Walk a loop body, skipping nested function defs (their loops are
    judged on their own) but descending into nested loops/try/if."""
    stack = list(loop.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _deadline_guarded(loop: ast.AST) -> bool:
    """A loop escapes the retry rule if its body carries a deadline
    guard: an `if` whose test consults a deadline/monotonic clock and
    whose body leaves the loop (break/return/raise)."""
    for node in _walk_loop_body(loop):
        if not isinstance(node, ast.If):
            continue
        mentions_clock = False
        for sub in ast.walk(node.test):
            name = ""
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            low = name.lower()
            if "deadline" in low or "monotonic" in low or "slack" in low:
                mentions_clock = True
                break
        if not mentions_clock:
            continue
        for sub in ast.walk(ast.Module(body=node.body, type_ignores=[])):
            if isinstance(sub, (ast.Break, ast.Return, ast.Raise)):
                return True
    return False


def _handler_reiterates(handler: ast.ExceptHandler) -> bool:
    """A handler permits another lap unless its last statement
    unconditionally leaves the loop."""
    if not handler.body:
        return True
    return not isinstance(handler.body[-1], (ast.Raise, ast.Break,
                                             ast.Return))


def _check_unbounded_retry(src, findings: List[Finding]) -> None:
    """Unbounded retry around an awaited network call: a `while True`
    (or infinite `for`) whose try-body awaits the wire and whose
    handler catches a transport fault back into the next iteration.
    Against a dead peer this coroutine spins forever — cap it with
    `for attempt in range(N)` or a deadline check that breaks/raises
    (fleet/remote.py's in-dispatch retry is the canonical shape)."""
    for loop in ast.walk(src.tree):
        if not isinstance(loop, (ast.While, ast.For)):
            continue
        if not _loop_unbounded(loop) or _deadline_guarded(loop):
            continue
        for node in _walk_loop_body(loop):
            if not isinstance(node, ast.Try):
                continue
            awaits_net = any(
                isinstance(sub, ast.Await) and
                isinstance(sub.value, ast.Call) and
                dotted(sub.value.func).split(".")[-1] in _RETRY_NET_TAILS
                for stmt in node.body for sub in ast.walk(stmt)
            )
            if not awaits_net:
                continue
            retries = next(
                (h for h in node.handlers
                 if (h.type is None or
                     any(n in _RETRY_EXC_TAILS
                         for n in _handler_type_names(h))) and
                 _handler_reiterates(h)),
                None)
            if retries is None:
                continue
            findings.append(src.finding(
                "conc-unbounded-retry", retries,
                "transport fault caught back into an unbounded loop "
                "around an awaited network call; a dead peer spins "
                "this retry forever — bound it with an attempt cap "
                "(for attempt in range(N)) or a deadline guard that "
                "breaks/raises",
            ))


@register_family("concurrency")
def check_concurrency(project: Project) -> List[Finding]:
    findings: List[Finding] = []

    for src in project.in_dirs(*HOST_SYNC_SCOPE):
        _check_host_sync(src, findings)

    for src in project.in_dirs(*JOURNAL_SCOPE):
        _check_journal_writer(src, findings)

    for src in project.in_dirs(*SERVE_ASYNC_SCOPE):
        _check_sock_in_loop(src, findings)

    for src in project.in_dirs(*RETRY_SCOPE):
        _check_unbounded_retry(src, findings)

    for src in project.in_dirs(*BLOCK_SCOPE):
        parents = _parents(src.tree)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            attr = node.func.attr

            if attr in _WAITING_ATTRS and not node.args and \
                    not any(kw.arg == "timeout" for kw in node.keywords) and \
                    not _inside_wait_for(node, parents):
                findings.append(src.finding(
                    "conc-no-timeout", node,
                    f".{attr}() with no timeout blocks forever if the "
                    "peer is wedged; pass timeout= or wrap in "
                    "asyncio.wait_for",
                ))

        # blocking calls under a held (sync) lock; async locks are
        # legitimately held across awaits, so only ast.With is scanned
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.With):
                continue
            held_lock = any(
                "lock" in dotted(item.context_expr.func
                                 if isinstance(item.context_expr, ast.Call)
                                 else item.context_expr).lower()
                for item in node.items
            )
            if not held_lock:
                continue
            for sub in ast.walk(ast.Module(body=node.body, type_ignores=[])):
                if isinstance(sub, ast.Call):
                    tail = dotted(sub.func).split(".")[-1]
                    if tail in _BLOCKING_IN_LOCK:
                        findings.append(src.finding(
                            "conc-block-in-lock", sub,
                            f"{tail}() while holding a lock; every other "
                            "waiter stalls behind a wedged peer — move "
                            "the blocking call outside the critical "
                            "section",
                        ))

    for src in project.in_dirs(*EXCEPT_SCOPE):
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            names = _handler_type_names(node)
            if node.type is None:
                findings.append(src.finding(
                    "conc-bare-except", node,
                    "bare except also catches KeyboardInterrupt and "
                    "SystemExit; catch Exception (or narrower)",
                ))
            if "BaseException" in names and not _body_raises(node.body):
                findings.append(src.finding(
                    "conc-swallow-base", node,
                    "except BaseException without re-raise swallows "
                    "KeyboardInterrupt/SystemExit; re-raise or narrow",
                ))
            broad = node.type is None or any(n in _BROAD for n in names)
            if broad and _body_trivial(node.body) and \
                    not _body_logs(node.body):
                findings.append(src.finding(
                    "conc-silent-except", node,
                    "broad exception handler that neither logs nor "
                    "raises; failures vanish without a trace — log the "
                    "exception or narrow the type",
                ))

    return findings
