"""resource-lifecycle: interprocedural resource acquire/release analysis.

The elasticity story (relaunch + task recovery, no checkpoints) only
works if processes that die violently come back clean — which in turn
requires that every resource the framework acquires (shm segments,
AF_UNIX sockets, worker/shard subprocesses, drain threads, rendezvous
files, manual lock acquisitions) is released on EVERY path out of its
owning scope, including the exception edges chaos faults exercise.
rpc/transport.py alone has ~25 acquisition sites; the migration plane
added lease threads and standby servers. This family tracks each
acquisition through an interprocedural escape analysis built on
analysis/callgraph.py:

- a resource that stays local to one function must be released (or
  ownership-transferred: returned, passed to a callee) on every path,
  with ``with``/``try-finally``/``contextlib.closing`` recognized as
  exception-safe release;
- a resource that escapes to ``self`` (direct assignment, container
  append/setitem, or THROUGH a callee whose parameter escapes — the
  pooled-connection idiom) obligates the owning class to release it
  somewhere in the closure of its close-like methods
  (``close``/``stop``/``shutdown``/``__exit__``/...), where "release"
  includes handing the attribute to a function that releases its
  parameter (the ``stop_shard_processes(self._procs)`` idiom) and
  container drains (``for t in self._threads: t.join()``).

Checks:

- ``leak-on-raise-path``   a call that can raise sits between the
                           acquisition and its release with no
                           try/finally (or except-handler) releasing
                           the resource; in ``__init__`` this includes
                           calls after a self-escape — a failed ctor
                           leaks the resource because the caller never
                           gets an object to ``close()``
- ``unreleased-escape``    a resource escapes to ``self`` but no
                           close-like method of the owning class ever
                           releases it
- ``start-without-join-or-daemon``  a non-daemon thread is started but
                           neither joined in its function nor (for
                           self-escaped threads) joined by any
                           close-like method — process exit hangs
- ``acquire-without-finally``  a bare ``lock.acquire()`` statement not
                           paired with a ``finally: release()`` — an
                           exception parks every waiter forever

Findings carry the interprocedural escape chain in ``Finding.chain``
(rendered in ``--format json``), e.g. ``("UdsTransport.call",
"UdsTransport._checkin", "self._pool")`` for a socket that reaches the
pool attribute through a helper's parameter. Suppress deliberate
lifetimes at the acquisition site::

    self._t = threading.Thread(
        target=loop
    )  # edl-lint: disable=resource-lifecycle -- reaped by the supervisor

Like every verify family this runs on the AST alone and resolves calls
conservatively: an unresolvable call transfers ownership (no finding)
rather than guessing.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from elasticdl_tpu.analysis import callgraph as cg
from elasticdl_tpu.analysis.core import AnalysisContext, Finding

RULE = "resource-lifecycle"

#: syntactic constructor name -> resource kind (``open`` handled apart:
#: only the bare builtin counts, not ``webbrowser.open`` etc.)
CTOR_KINDS = {
    "SharedMemory": "shm",
    "socket": "socket",
    "Popen": "process",
    "Thread": "thread",
}

#: receiver methods that release (or reap) each kind
RELEASE_OPS: Dict[str, Tuple[str, ...]] = {
    "shm": ("close", "unlink"),
    "socket": ("close", "detach"),
    "process": ("wait", "kill", "terminate", "communicate"),
    "file": ("close",),
    "thread": ("join",),
}
ALL_RELEASE_OPS = frozenset(
    op for ops in RELEASE_OPS.values() for op in ops
)
#: close-shaped receiver calls accepted as releasing ANY kind when the
#: static kind is unknown (e.g. draining a mixed pool)
GENERIC_RELEASE_OPS = ALL_RELEASE_OPS | {"stop", "shutdown", "destroy"}

#: a class is "closeable" through these; escaped resources must be
#: released in their call closure
CLOSE_LIKE = (
    "close", "stop", "shutdown", "__exit__", "__del__",
    "terminate", "destroy", "release", "abort",
)

#: calls treated as non-raising for the acquire..release window (pure
#: lookups, container ops, logging); everything else is a raise point
_SAFE_NAME_CALLS = frozenset({
    "len", "str", "int", "float", "bool", "list", "dict", "tuple",
    "set", "frozenset", "sorted", "min", "max", "isinstance",
    "issubclass", "getattr", "hasattr", "id", "repr", "print",
    "range", "enumerate", "zip", "iter", "abs", "round", "type",
    # non-raising constructors (threading primitives, views, containers)
    "Lock", "RLock", "Condition", "Event", "Semaphore", "Barrier",
    "Queue", "deque", "memoryview", "bytearray", "OrderedDict",
    "defaultdict", "Counter",
})
_SAFE_ATTR_CALLS = frozenset({
    "append", "add", "extend", "insert", "discard", "get", "items",
    "keys", "values", "pop", "popleft", "setdefault", "clear",
    "copy", "update", "info", "debug", "warning", "error",
    "exception", "log", "format", "join", "split", "strip",
    "startswith", "endswith", "encode", "decode", "lower", "upper",
    "replace", "record", "hex", "count", "index", "isoformat",
    "keys", "fileno", "getsockname", "setsockopt", "setblocking",
    "settimeout", "setdefault",
})


def _call_name(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _ctor_kind(expr: ast.expr) -> Optional[str]:
    if not isinstance(expr, ast.Call):
        return None
    f = expr.func
    if isinstance(f, ast.Name) and f.id == "open":
        return "file"
    name = _call_name(expr)
    return CTOR_KINDS.get(name or "")


def _thread_daemon_kw(expr: ast.expr) -> bool:
    if not isinstance(expr, ast.Call):
        return False
    for kw in expr.keywords:
        if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
            return bool(kw.value.value)
    return False


def _stmts_in_order(body) -> Iterator[ast.stmt]:
    """Depth-first statements in source order, NOT descending into
    nested function/class definitions (separate scopes)."""
    for stmt in body:
        yield stmt
        if isinstance(
            stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            continue
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if sub:
                yield from _stmts_in_order(sub)
        for handler in getattr(stmt, "handlers", []) or []:
            yield from _stmts_in_order(handler.body)


def _own_exprs(stmt: ast.stmt) -> Iterator[ast.expr]:
    """The statement's OWN expression children (test, iter, value,
    targets, with-items...), excluding nested statement bodies — those
    are visited as statements in their own right."""
    for field, value in ast.iter_fields(stmt):
        if field in ("body", "orelse", "finalbody", "handlers"):
            continue
        if isinstance(value, ast.expr):
            yield value
        elif isinstance(value, list):
            for v in value:
                if isinstance(v, ast.expr):
                    yield v
                elif isinstance(v, ast.withitem):
                    yield v.context_expr


def _releases_target(
    tree_nodes, target_repr: str, ops: frozenset = GENERIC_RELEASE_OPS
) -> bool:
    """Does any node in `tree_nodes` call a release op on `target_repr`
    (the ast.dump of the receiver expression)?"""
    for node in tree_nodes:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in ops
                and ast.dump(f.value) == target_repr
            ):
                return True
    return False


#: the only op that undoes a manual .acquire()
_LOCK_RELEASE_OPS = frozenset({"release"})


class _Protected:
    """try-blocks of one function whose handler/finalbody releases a
    given receiver: line ranges where a raise does NOT leak it."""

    def __init__(self, func_node: ast.AST):
        self.ranges: List[Tuple[int, int, ast.Try]] = []
        for stmt in _stmts_in_order(
            getattr(func_node, "body", [])
        ):
            if isinstance(stmt, ast.Try) and stmt.body:
                end = stmt.body[-1].end_lineno or stmt.body[-1].lineno
                self.ranges.append((stmt.body[0].lineno, end, stmt))
                # the handler bodies too: a release-then-re-raise
                # handler is the recommended cleanup shape, so risky
                # statements inside it (including the bare `raise`)
                # are covered by the handler's own release
                for h in stmt.handlers:
                    if h.body:
                        hend = (
                            h.body[-1].end_lineno or h.body[-1].lineno
                        )
                        self.ranges.append(
                            (h.body[0].lineno, hend, stmt)
                        )

    def covers(self, line: int, target_repr: str) -> bool:
        for start, end, t in self.ranges:
            if not (start <= line <= end):
                continue
            cleanup: List[ast.AST] = list(t.finalbody)
            for h in t.handlers:
                cleanup.extend(h.body)
            if _releases_target(cleanup, target_repr):
                return True
        return False


def _risky_call(node: ast.Call) -> bool:
    name = _call_name(node)
    if name is None:
        return True
    if name in _SAFE_NAME_CALLS:  # covers threading.Lock() etc. too
        return False
    if isinstance(node.func, ast.Name):
        return _ctor_kind(node) is None
    if name in _SAFE_ATTR_CALLS or name in GENERIC_RELEASE_OPS:
        return False
    return _ctor_kind(node) is None


class _Local:
    """One tracked local resource inside a single function."""

    __slots__ = (
        "name", "kind", "line", "daemon", "released_line",
        "transferred_line", "escaped", "start_line", "joined",
    )

    def __init__(self, name: str, kind: str, line: int, daemon: bool):
        self.name = name
        self.kind = kind
        self.line = line
        self.daemon = daemon
        self.released_line: Optional[int] = None
        self.transferred_line: Optional[int] = None
        self.escaped: Optional[str] = None  # attr it escaped to
        self.start_line: Optional[int] = None
        self.joined = False

    def note_release(self, line: int) -> None:
        if self.released_line is None:
            self.released_line = line

    def note_transfer(self, line: int) -> None:
        if self.transferred_line is None:
            self.transferred_line = line

    @property
    def endpoint(self) -> Optional[int]:
        ends = [
            ln
            for ln in (self.released_line, self.transferred_line)
            if ln is not None
        ]
        return min(ends) if ends else None


class _Escape:
    """A resource that reached a ``self`` attribute."""

    __slots__ = ("cls", "attr", "kind", "path", "line", "chain", "daemon")

    def __init__(self, cls, attr, kind, path, line, chain, daemon=False):
        self.cls = cls  # (path, class name)
        self.attr = attr
        self.kind = kind
        self.path = path
        self.line = line
        self.chain = chain
        self.daemon = daemon


class Analysis:
    """The interprocedural pass: per-function summaries to a fixpoint,
    then escape/leak extraction. Exposed (not underscored) so the test
    suite can pin release chains of known-good teardown paths."""

    def __init__(self, ctx: AnalysisContext, g: Optional[cg.CallGraph] = None):
        self.ctx = ctx
        self.g = g if g is not None else cg.CallGraph(ctx)
        #: function -> resource kind its return value carries
        self.returns_kind: Dict[cg.FuncKey, str] = {}
        #: function -> {positional param index: self attr it escapes to}
        self.param_escapes: Dict[cg.FuncKey, Dict[int, str]] = {}
        #: function -> positional param indices it releases
        self.param_releases: Dict[cg.FuncKey, Set[int]] = {}
        self._released_memo: Dict[Tuple[str, str], Set[str]] = {}
        self._summaries_fixpoint()

    # -- summaries -----------------------------------------------------------

    def _params(self, key: cg.FuncKey) -> Dict[str, int]:
        node = self.g.functions[key].node
        args = getattr(node, "args", None)
        if args is None:
            return {}
        names = [a.arg for a in args.posonlyargs + args.args]
        if key[1] is not None and names and names[0] == "self":
            names = names[1:]
        return {n: i for i, n in enumerate(names)}

    def _resolve(self, key: cg.FuncKey, call: ast.Call) -> Optional[cg.FuncKey]:
        path, cls_name, _ = key
        cls = self.g.classes.get((path, cls_name)) if cls_name else None
        return self.g._resolve_call(key, call, cls, {})

    def _expr_kind(
        self, key: cg.FuncKey, expr: ast.expr, kinds: Dict[str, str]
    ) -> Optional[str]:
        k = _ctor_kind(expr)
        if k is not None:
            return k
        if isinstance(expr, ast.Name):
            return kinds.get(expr.id)
        if isinstance(expr, ast.Call):
            callee = self._resolve(key, expr)
            if callee is not None:
                return self.returns_kind.get(callee)
        return None

    def _summaries_fixpoint(self) -> None:
        for _ in range(10):
            changed = False
            for key in self.g.functions:
                ret, esc, rel = self._scan_summaries(key)
                if ret is not None and self.returns_kind.get(key) != ret:
                    self.returns_kind[key] = ret
                    changed = True
                if esc and self.param_escapes.get(key) != esc:
                    self.param_escapes[key] = esc
                    changed = True
                if rel and self.param_releases.get(key) != rel:
                    self.param_releases[key] = rel
                    changed = True
            if not changed:
                return

    def _scan_summaries(self, key: cg.FuncKey):
        node = self.g.functions[key].node
        params = self._params(key)
        kinds: Dict[str, str] = {}
        ret: Optional[str] = None
        p_esc: Dict[int, str] = dict(self.param_escapes.get(key, {}))
        p_rel: Set[int] = set(self.param_releases.get(key, set()))
        for stmt in _stmts_in_order(getattr(node, "body", [])):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                t = stmt.targets[0]
                k = self._expr_kind(key, stmt.value, kinds)
                if isinstance(t, ast.Name):
                    if k is not None:
                        kinds[t.id] = k
                    else:
                        kinds.pop(t.id, None)
                else:
                    attr = cg._self_attr(t)
                    if (
                        attr
                        and isinstance(stmt.value, ast.Name)
                        and stmt.value.id in params
                    ):
                        p_esc[params[stmt.value.id]] = attr
            elif isinstance(stmt, ast.Expr) and isinstance(
                stmt.value, ast.Call
            ):
                call = stmt.value
                f = call.func
                if isinstance(f, ast.Attribute):
                    recv_attr = cg._self_attr(f.value)
                    if f.attr in ("append", "add", "insert") and recv_attr:
                        for a in call.args:
                            if isinstance(a, ast.Name) and a.id in params:
                                p_esc[params[a.id]] = recv_attr
                    if f.attr in ALL_RELEASE_OPS and isinstance(
                        f.value, ast.Name
                    ):
                        if f.value.id in params:
                            p_rel.add(params[f.value.id])
                callee = self._resolve(key, call)
                if callee is not None:
                    crel = self.param_releases.get(callee, set())
                    cesc = self.param_escapes.get(callee, {})
                    for i, a in enumerate(call.args):
                        if isinstance(a, ast.Name) and a.id in params:
                            if i in crel:
                                p_rel.add(params[a.id])
                            if i in cesc:
                                p_esc[params[a.id]] = cesc[i]
            elif isinstance(stmt, ast.For):
                if (
                    isinstance(stmt.iter, ast.Name)
                    and stmt.iter.id in params
                    and isinstance(stmt.target, ast.Name)
                ):
                    loop_var = ast.dump(stmt.target)
                    # normalize the Store ctx to the Load the call uses
                    loop_var = loop_var.replace("Store()", "Load()")
                    if _releases_target(stmt.body, loop_var):
                        p_rel.add(params[stmt.iter.id])
            elif isinstance(stmt, ast.Return) and stmt.value is not None:
                k = self._expr_kind(key, stmt.value, kinds)
                if k is not None:
                    ret = k
        return ret, p_esc, p_rel

    # -- class teardown ------------------------------------------------------

    def close_like_closure(self, cls: Tuple[str, str]) -> List[cg.FuncKey]:
        """Methods reachable from the class's close-like methods via
        resolved same-class calls, in BFS order."""
        path, cname = cls
        info = self.g.classes.get(cls)
        if info is None:
            return []
        queue = [
            (path, cname, m) for m in CLOSE_LIKE if m in info.methods
        ]
        seen = list(queue)
        while queue:
            cur = queue.pop(0)
            for edge in self.g.edges.get(cur, []):
                cal = edge.callee
                if cal[:2] == (path, cname) and cal not in seen:
                    seen.append(cal)
                    queue.append(cal)
        return seen

    def released_attrs(self, cls: Tuple[str, str]) -> Set[str]:
        """Attributes of `cls` released somewhere in the closure of its
        close-like methods (direct release op, pop-drain, for-loop
        drain, or handing the attr to a param-releasing function)."""
        if cls in self._released_memo:
            return self._released_memo[cls]
        released: Set[str] = set()
        self._released_memo[cls] = released  # cycle guard
        for key in self.close_like_closure(cls):
            node = self.g.functions[key].node
            for stmt in _stmts_in_order(getattr(node, "body", [])):
                released |= self._stmt_released_attrs(key, stmt)
        return released

    def _stmt_released_attrs(
        self, key: cg.FuncKey, stmt: ast.stmt
    ) -> Set[str]:
        out: Set[str] = set()
        if isinstance(stmt, ast.For):
            # for v in self.attr: v.close()   (also over list(self.attr))
            it = stmt.iter
            if isinstance(it, ast.Call) and _call_name(it) == "list":
                it = it.args[0] if it.args else it
            attr = cg._self_attr(it)
            if attr and isinstance(stmt.target, ast.Name):
                loop_var = ast.dump(stmt.target).replace("Store()", "Load()")
                if _releases_target(stmt.body, loop_var):
                    out.add(attr)
                else:
                    for sub in _stmts_in_order(stmt.body):
                        if not (
                            isinstance(sub, ast.Expr)
                            and isinstance(sub.value, ast.Call)
                        ):
                            continue
                        callee = self._resolve(key, sub.value)
                        if callee is None:
                            continue
                        crel = self.param_releases.get(callee, set())
                        for i, a in enumerate(sub.value.args):
                            if (
                                i in crel
                                and isinstance(a, ast.Name)
                                and a.id == stmt.target.id
                            ):
                                out.add(attr)
            return out
        for sub in ast.walk(stmt):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            if isinstance(f, ast.Attribute) and f.attr in GENERIC_RELEASE_OPS:
                attr = cg._self_attr(f.value)
                if attr:
                    out.add(attr)
                    continue
                # self.attr.pop().close() — pool drain
                v = f.value
                if (
                    isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Attribute)
                    and v.func.attr == "pop"
                ):
                    attr = cg._self_attr(v.func.value)
                    if attr:
                        out.add(attr)
                continue
            callee = self._resolve(key, sub)
            if callee is None:
                continue
            crel = self.param_releases.get(callee, set())
            for i, a in enumerate(sub.args):
                if i not in crel:
                    continue
                if isinstance(a, ast.Call) and _call_name(a) == "list":
                    a = a.args[0] if a.args else a
                attr = cg._self_attr(a)
                if attr:
                    out.add(attr)
        return out

    def release_chain(
        self, cls: Tuple[str, str], attr: str
    ) -> Optional[Tuple[str, ...]]:
        """The close-like call chain that releases `cls`.`attr`, or
        None: ('UdsServer.close', 'self._sock'). Used by findings and
        pinned by the repo cross-check tests."""
        path, cname = cls
        info = self.g.classes.get(cls)
        if info is None:
            return None
        for key in self.close_like_closure(cls):
            node = self.g.functions[key].node
            for stmt in _stmts_in_order(getattr(node, "body", [])):
                if attr in self._stmt_released_attrs(key, stmt):
                    qual = self.g.functions[key].qualname
                    roots = [
                        f"{cname}.{m}"
                        for m in CLOSE_LIKE
                        if m in info.methods
                    ]
                    head = roots[0] if roots else qual
                    if head != qual:
                        return (head, qual, f"self.{attr}")
                    return (qual, f"self.{attr}")
        return None


# -- per-function extraction --------------------------------------------------


def _scan_function(
    an: Analysis, key: cg.FuncKey
) -> Tuple[List[_Local], List[_Escape], List[Finding]]:
    """Track local resources, record escapes, and emit the local-scope
    findings (leak-on-raise-path, local start-without-join)."""
    g = an.g
    func = g.functions[key]
    node = func.node
    path, cls_name, fname = key
    locals_: Dict[str, _Local] = {}
    escapes: List[_Escape] = []
    findings: List[Finding] = []
    protected = _Protected(node)
    risky: List[Tuple[int, str]] = []  # (line, what)

    def tracked(name_node: ast.expr) -> Optional[_Local]:
        if isinstance(name_node, ast.Name):
            return locals_.get(name_node.id)
        return None

    def transfer_names_in(call: ast.Call, line: int) -> None:
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            r = tracked(a)
            if r is not None:
                r.note_transfer(line)

    for stmt in _stmts_in_order(getattr(node, "body", [])):
        line = stmt.lineno
        # risky operations (can raise, leaking anything live)
        if isinstance(stmt, (ast.Raise, ast.Assert)):
            risky.append((line, "raise"))
        if not isinstance(
            stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            for expr in _own_exprs(stmt):
                for sub in ast.walk(expr):
                    if isinstance(sub, ast.Call) and _risky_call(sub):
                        risky.append(
                            (sub.lineno, _call_name(sub) or "call")
                        )

        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                ce = item.context_expr
                r = tracked(ce)
                if r is not None:
                    r.note_release(line)  # `with sock:` closes on exit
                if isinstance(ce, ast.Call) and _call_name(ce) == "closing":
                    for a in ce.args:
                        r = tracked(a)
                        if r is not None:
                            r.note_release(line)
            continue

        if isinstance(stmt, ast.Assign) and len(stmt.targets) > 1:
            # a = b = tracked — the alias owns it now; conservatively
            # treat as a transfer (the alias may be closed instead)
            r = tracked(stmt.value)
            if r is not None:
                r.note_transfer(line)
            continue
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            t = stmt.targets[0]
            value = stmt.value
            kind = an._expr_kind(key, value, {
                n: loc.kind for n, loc in locals_.items()
            })
            # x.daemon = True after construction
            if (
                isinstance(t, ast.Attribute)
                and t.attr == "daemon"
                and isinstance(t.value, ast.Name)
            ):
                r = locals_.get(t.value.id)
                if r is not None and isinstance(value, ast.Constant):
                    r.daemon = bool(value.value)
                continue
            if not isinstance(value, ast.Name):
                # tracked name stored NESTED in the value (wrapped in
                # an entry object, a container literal, ...): the new
                # owner is responsible now — transfer
                for sub in ast.walk(value):
                    r = tracked(sub)
                    if r is not None:
                        r.note_transfer(line)
            if isinstance(t, ast.Name):
                src = tracked(value)
                if src is not None:
                    src.note_transfer(line)  # aliased: stop tracking
                if kind is not None and not (
                    isinstance(value, ast.Name)
                ):
                    locals_[t.id] = _Local(
                        t.id, kind, line, _thread_daemon_kw(value)
                    )
                elif t.id in locals_ and src is None:
                    del locals_[t.id]  # rebound to something else
                continue
            attr = cg._self_attr(t)
            if attr is None and isinstance(t, ast.Subscript):
                attr = cg._self_attr(t.value)
            if attr is not None:
                src = tracked(value)
                if src is not None:
                    src.escaped = attr
                    src.note_transfer(line)
                    escapes.append(_Escape(
                        (path, cls_name), attr, src.kind, path, line,
                        (func.qualname, f"self.{attr}"), src.daemon,
                    ))
                elif kind is not None:
                    escapes.append(_Escape(
                        (path, cls_name), attr, kind, path, line,
                        (func.qualname, f"self.{attr}"),
                        _thread_daemon_kw(value),
                    ))
            continue

        if isinstance(stmt, ast.Return) and stmt.value is not None:
            r = tracked(stmt.value)
            if r is not None:
                r.note_transfer(line)
            continue

        if not (isinstance(stmt, ast.Expr) and isinstance(
            stmt.value, ast.Call
        )):
            continue
        call = stmt.value
        f = call.func
        if isinstance(f, ast.Attribute):
            recv = f.value
            r = tracked(recv)
            if r is not None:
                if f.attr in RELEASE_OPS.get(r.kind, ()):
                    r.note_release(line)
                    if f.attr == "join":
                        r.joined = True
                    continue
                if f.attr == "start" and r.kind == "thread":
                    r.start_line = line
                    continue
            recv_attr = cg._self_attr(recv)
            if recv_attr and f.attr in ("append", "add", "insert"):
                for a in call.args:
                    ra = tracked(a)
                    if ra is not None:
                        ra.escaped = recv_attr
                        ra.note_transfer(call.lineno)
                        escapes.append(_Escape(
                            (path, cls_name), recv_attr, ra.kind, path,
                            call.lineno,
                            (func.qualname, f"self.{recv_attr}"),
                            ra.daemon,
                        ))
                    ck = _ctor_kind(a)
                    if ck is not None:
                        escapes.append(_Escape(
                            (path, cls_name), recv_attr, ck, path,
                            call.lineno,
                            (func.qualname, f"self.{recv_attr}"),
                            _thread_daemon_kw(a),
                        ))
                continue
        # plain call: tracked args either release (param summary),
        # escape through the callee, or transfer ownership
        callee = an._resolve(key, call)
        if callee is None:
            transfer_names_in(call, call.lineno)
            continue
        crel = an.param_releases.get(callee, set())
        cesc = an.param_escapes.get(callee, {})
        callee_func = g.functions.get(callee)
        for i, a in enumerate(call.args):
            r = tracked(a)
            if r is None:
                continue
            # escape beats release: a callee that conditionally pools
            # AND conditionally closes (the _checkin idiom) may leave
            # the resource alive, so the owning class inherits the
            # release obligation
            if i in cesc and callee_func is not None:
                esc_attr = cesc[i]
                r.escaped = esc_attr
                r.note_transfer(call.lineno)
                escapes.append(_Escape(
                    (callee[0], callee[1]), esc_attr, r.kind,
                    callee[0], call.lineno,
                    (
                        func.qualname,
                        callee_func.qualname,
                        f"self.{esc_attr}",
                    ),
                    r.daemon,
                ))
            elif i in crel:
                r.note_release(call.lineno)
            else:
                r.note_transfer(call.lineno)
        for kw in call.keywords:
            r = tracked(kw.value)
            if r is not None:
                r.note_transfer(call.lineno)

    # -- local findings
    for r in locals_.values():
        if r.kind == "thread":
            if (
                r.start_line is not None
                and not r.daemon
                and not r.joined
                and r.escaped is None
                and r.transferred_line is None
            ):
                findings.append(Finding(
                    RULE, "start-without-join-or-daemon", path,
                    r.start_line,
                    f"{func.qualname} starts non-daemon thread "
                    f"'{r.name}' but neither joins it nor hands it "
                    "off — a hung target wedges process exit; join "
                    "it, store it for a close-like join, or mark it "
                    "daemon",
                    chain=(func.qualname, r.name),
                ))
            continue
        endpoint = r.endpoint
        if endpoint is None and r.escaped is None:
            findings.append(Finding(
                RULE, "leak-on-raise-path", path, r.line,
                f"{func.qualname} acquires {r.kind} '{r.name}' and "
                "releases it on no path out of the function — close "
                "it, return it, or hand it to an owner",
                chain=(func.qualname, r.name),
            ))
            continue
        if endpoint is None:
            continue
        target_repr = ast.dump(ast.parse(r.name, mode="eval").body)
        for rl, what in risky:
            if r.line < rl < endpoint and not protected.covers(
                rl, target_repr
            ):
                findings.append(Finding(
                    RULE, "leak-on-raise-path", path, rl,
                    f"{func.qualname}: '{what}' between acquiring "
                    f"{r.kind} '{r.name}' and its release can raise "
                    "and leak it — wrap the window in try/finally "
                    "(or release in an except handler)",
                    chain=(func.qualname, r.name, what),
                ))
                break

    # -- __init__ escape-then-raise: the caller never gets the object,
    # so the class's close() cannot run
    if fname == "__init__":
        end_line = node.body[-1].end_lineno or node.body[-1].lineno
        for esc in escapes:
            if esc.kind == "thread" or esc.cls != (path, cls_name):
                continue
            target_repr = ast.dump(
                ast.parse(f"self.{esc.attr}", mode="eval").body
            )
            for rl, what in risky:
                if esc.line < rl <= end_line and not protected.covers(
                    rl, target_repr
                ):
                    findings.append(Finding(
                        RULE, "leak-on-raise-path", path, rl,
                        f"{func.qualname}: '{what}' after "
                        f"self.{esc.attr} holds a {esc.kind} can "
                        "raise — the caller gets no object, so "
                        "close() can never release it; catch, "
                        f"release self.{esc.attr}, and re-raise",
                        chain=(
                            func.qualname, f"self.{esc.attr}", what
                        ),
                    ))
                    break
    return list(locals_.values()), escapes, findings


def _acquire_without_finally(
    ctx: AnalysisContext, g: cg.CallGraph
) -> List[Finding]:
    findings: List[Finding] = []
    for key, func in g.functions.items():
        if key[2] == "__enter__" or key[2].endswith(".__enter__"):
            continue
        node = func.node
        body = getattr(node, "body", [])
        # try-blocks whose finally releases a receiver
        release_ranges: List[Tuple[int, int, ast.Try]] = []
        for stmt in _stmts_in_order(body):
            if isinstance(stmt, ast.Try) and stmt.body:
                end = stmt.body[-1].end_lineno or stmt.body[-1].lineno
                release_ranges.append(
                    (stmt.body[0].lineno, end, stmt)
                )

        def in_released_try(line: int, target_repr: str) -> bool:
            for start, end, t in release_ranges:
                if start <= line <= end and _releases_target(
                    t.finalbody, target_repr, _LOCK_RELEASE_OPS
                ):
                    return True
            return False

        def walk(stmts) -> None:
            for i, stmt in enumerate(stmts):
                if isinstance(
                    stmt,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    continue
                if (
                    isinstance(stmt, ast.Expr)
                    and isinstance(stmt.value, ast.Call)
                    and isinstance(stmt.value.func, ast.Attribute)
                    and stmt.value.func.attr == "acquire"
                ):
                    target_repr = ast.dump(stmt.value.func.value)
                    nxt = stmts[i + 1] if i + 1 < len(stmts) else None
                    safe = isinstance(nxt, ast.Try) and _releases_target(
                        nxt.finalbody, target_repr, _LOCK_RELEASE_OPS
                    )
                    if not safe:
                        safe = in_released_try(stmt.lineno, target_repr)
                    if not safe:
                        findings.append(Finding(
                            RULE, "acquire-without-finally", func.path,
                            stmt.lineno,
                            f"{func.qualname} calls .acquire() with "
                            "no try/finally release — an exception "
                            "before the release parks every waiter "
                            "forever; use `with`, or follow the "
                            "acquire with try/finally",
                            chain=(func.qualname,),
                        ))
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, field, None)
                    if sub:
                        walk(sub)
                for h in getattr(stmt, "handlers", []) or []:
                    walk(h.body)

        walk(body)
    return findings


def run(ctx: AnalysisContext) -> List[Finding]:
    g = cg.CallGraph(ctx)
    an = Analysis(ctx, g)
    findings: List[Finding] = []
    all_escapes: List[_Escape] = []
    for key in sorted(
        g.functions, key=lambda k: (k[0], k[1] or "", k[2])
    ):
        _locals, escapes, local_findings = _scan_function(an, key)
        all_escapes.extend(escapes)
        findings.extend(local_findings)

    # -- class obligations: every escaped resource must be released by
    # the owning class's close-like closure
    seen: Set[Tuple[str, str, str, str]] = set()
    for esc in all_escapes:
        if esc.cls[1] is None:
            continue
        dedup = (esc.cls[0], esc.cls[1] or "", esc.attr, esc.kind)
        if dedup in seen:
            continue
        seen.add(dedup)
        released = an.released_attrs(esc.cls)
        if esc.attr in released:
            continue
        cname = esc.cls[1]
        if esc.kind == "thread":
            if esc.daemon:
                continue
            # flagged only if some method actually starts it
            if not _class_starts_attr(g, esc.cls, esc.attr):
                continue
            findings.append(Finding(
                RULE, "start-without-join-or-daemon", esc.path,
                esc.line,
                f"{cname}.{esc.attr} holds a started non-daemon "
                "thread no close-like method "
                f"({'/'.join(CLOSE_LIKE[:3])}/...) ever joins — "
                "shutdown hangs on interpreter exit; join it in the "
                "class teardown or mark it daemon",
                chain=esc.chain,
            ))
        else:
            findings.append(Finding(
                RULE, "unreleased-escape", esc.path, esc.line,
                f"{cname}.{esc.attr} holds a {esc.kind} (escape "
                f"chain: {' -> '.join(esc.chain)}) but no close-like "
                "method of the class releases it — add it to the "
                "teardown path",
                chain=esc.chain,
            ))

    findings.extend(_acquire_without_finally(ctx, g))
    return findings


def _class_starts_attr(
    g: cg.CallGraph, cls: Tuple[str, str], attr: str
) -> bool:
    info = g.classes.get(cls)
    if info is None:
        return False
    for m in info.methods.values():
        for sub in ast.walk(m):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "start"
                and cg._self_attr(sub.func.value) == attr
            ):
                return True
    return False
