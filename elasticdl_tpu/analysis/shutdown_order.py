"""shutdown-order: teardown ordering of close-like methods.

A violent death (SIGKILL chaos fault, spot reclaim) is survivable
because `_reclaim_stale` sweeps and scenario replays re-run the close
paths — which makes the ORDER inside those close paths load-bearing.
This family derives, for every class with a close-like method
(``close``/``stop``/``shutdown``/``__exit__``/...), the linear teardown
sequence (inlining same-class helper calls) and checks it against the
thread/lock structure the callgraph already knows:

- ``join-under-lock``      a thread is joined while the join site holds
                           a lock the thread's target may acquire — the
                           target blocks on the lock, the join blocks on
                           the target: deadlock. Unlike lock-discipline
                           (which only sees ``with``), this walk also
                           tracks manual ``acquire()``/``release()``
                           pairs, the one place hand-rolled locking is
                           common in teardown code.
- ``close-order-inversion``  a transport attribute is closed BEFORE
                           joining the thread that still uses it. The
                           wake-the-reader idiom is exempt: when the
                           thread only ever performs blocking reads
                           (``accept``/``recv``/``get``/...) on the
                           attribute, closing it first is exactly how
                           you unblock the loop (UdsServer does this
                           deliberately). Anything else — sends,
                           dispatches, state updates — races the close.
- ``double-close-unsafe``  a close path unlinks a file/segment with no
                           guard (``try/except``, ``missing_ok=True``,
                           an existence check, or a method-level
                           idempotency early-return) — the second close
                           that `_reclaim_stale` and SIGKILL replays
                           guarantee will raise mid-teardown and leak
                           everything after it.

Suppress a deliberate ordering at the site::

    # edl-lint: disable=shutdown-order -- poll-based reader, close is the wakeup
    self._sock.close()

Findings carry the chain (close method, attribute, thread target, the
racing use) in ``Finding.chain``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from elasticdl_tpu.analysis import callgraph as cg
from elasticdl_tpu.analysis.core import AnalysisContext, Finding
from elasticdl_tpu.analysis.resource_lifecycle import (
    CLOSE_LIKE,
    _stmts_in_order,
)

RULE = "shutdown-order"

#: blocking-read receivers: closing the attribute WAKES a thread parked
#: in one of these, so close-before-join is the correct order
UNBLOCK_READS = frozenset({
    "accept", "recv", "recv_into", "recvfrom", "recvmsg", "get",
    "read", "readline", "readinto", "poll", "select", "wait",
})

#: receiver calls that count as "closing" an attribute in a teardown
_CLOSING_OPS = frozenset({
    "close", "stop", "shutdown", "unlink", "detach", "terminate",
    "kill", "destroy",
})


def _class_of(g: cg.CallGraph, key: cg.FuncKey) -> Optional[cg._ClassInfo]:
    if key[1] is None:
        return None
    return g.classes.get((key[0], key[1]))


def _thread_target_kw(expr: ast.expr) -> Optional[ast.expr]:
    if not (
        isinstance(expr, ast.Call)
        and isinstance(
            expr.func, (ast.Name, ast.Attribute)
        )
        and (
            expr.func.id if isinstance(expr.func, ast.Name)
            else expr.func.attr
        ) == "Thread"
    ):
        return None
    for kw in expr.keywords:
        if kw.arg == "target":
            return kw.value
    return None


def _thread_attr_targets(
    g: cg.CallGraph,
) -> Dict[Tuple[str, str], Dict[str, cg.FuncKey]]:
    """Per class: {attr name: resolved thread-target FuncKey} for every
    ``self.attr`` that holds (or collects) a Thread — direct assignment,
    via a local, or appended into a container attribute."""
    out: Dict[Tuple[str, str], Dict[str, cg.FuncKey]] = {}
    for (path, cname), info in g.classes.items():
        amap: Dict[str, cg.FuncKey] = {}
        for mname in info.methods:
            key = (path, cname, mname)
            func = g.functions.get(key)
            if func is None:
                continue
            local_threads: Dict[str, cg.FuncKey] = {}
            for stmt in _stmts_in_order(getattr(func.node, "body", [])):
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                    t = stmt.targets[0]
                    tgt_expr = _thread_target_kw(stmt.value)
                    ref = (
                        g._resolve_ref(key, tgt_expr, info, {})
                        if tgt_expr is not None
                        else None
                    )
                    if isinstance(t, ast.Name):
                        if ref is not None:
                            local_threads[t.id] = ref
                        continue
                    attr = cg._self_attr(t)
                    if attr is None:
                        continue
                    if ref is not None:
                        amap[attr] = ref
                    elif (
                        isinstance(stmt.value, ast.Name)
                        and stmt.value.id in local_threads
                    ):
                        amap[attr] = local_threads[stmt.value.id]
                elif (
                    isinstance(stmt, ast.Expr)
                    and isinstance(stmt.value, ast.Call)
                    and isinstance(stmt.value.func, ast.Attribute)
                    and stmt.value.func.attr in ("append", "add")
                ):
                    attr = cg._self_attr(stmt.value.func.value)
                    if attr is None:
                        continue
                    for a in stmt.value.args:
                        if (
                            isinstance(a, ast.Name)
                            and a.id in local_threads
                        ):
                            amap[attr] = local_threads[a.id]
                        else:
                            tgt_expr = _thread_target_kw(a)
                            if tgt_expr is not None:
                                ref = g._resolve_ref(key, tgt_expr, info, {})
                                if ref is not None:
                                    amap[attr] = ref
        if amap:
            out[(path, cname)] = amap
    return out


# -- join-under-lock ----------------------------------------------------------


def _join_under_lock(
    g: cg.CallGraph,
    tmap: Dict[Tuple[str, str], Dict[str, cg.FuncKey]],
) -> List[Finding]:
    findings: List[Finding] = []
    entry_held = g.entry_held()
    for key, func in g.functions.items():
        path, cname, _ = key
        cls = _class_of(g, key)
        amap = tmap.get((path, cname), {}) if cname else {}
        # locals holding threads (t = Thread(target=...))
        local_threads: Dict[str, cg.FuncKey] = {}
        manual_held: Set[cg.LockId] = set()
        entry = set(entry_held.get(key, frozenset()))

        def join_target(recv: ast.expr) -> Optional[Tuple[str, cg.FuncKey]]:
            attr = cg._self_attr(recv)
            if attr is not None and attr in amap:
                return (f"self.{attr}", amap[attr])
            if isinstance(recv, ast.Name) and recv.id in local_threads:
                return (recv.id, local_threads[recv.id])
            return None

        def check_join(
            recv: ast.expr, line: int, held: Set[cg.LockId]
        ) -> None:
            hit = join_target(recv)
            if hit is None:
                return
            what, target = hit
            inter = held & g.transitive_acquires(target)
            if not inter:
                return
            lock = sorted(g.lock_name(lk) for lk in inter)[0]
            tname = g.functions[target].qualname
            findings.append(Finding(
                RULE, "join-under-lock", path, line,
                f"{func.qualname} joins {what} while holding "
                f"'{lock}', which the thread target {tname} may "
                "acquire — the target blocks on the lock, the join "
                "blocks on the target; release before joining",
                chain=(func.qualname, f"{what}.join", tname, lock),
            ))

        def walk(stmts, with_held: Set[cg.LockId]) -> None:
            for stmt in stmts:
                if isinstance(
                    stmt,
                    (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
                ):
                    continue
                if isinstance(stmt, (ast.With, ast.AsyncWith)):
                    inner = set(with_held)
                    for item in stmt.items:
                        lk = g._lock_of(item.context_expr, cls, path)
                        if lk is not None:
                            inner.add(lk)
                    walk(stmt.body, inner)
                    continue
                if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
                    t = stmt.targets[0]
                    tgt_expr = _thread_target_kw(stmt.value)
                    if isinstance(t, ast.Name) and tgt_expr is not None:
                        ref = g._resolve_ref(key, tgt_expr, cls, {})
                        if ref is not None:
                            local_threads[t.id] = ref
                elif isinstance(stmt, ast.Expr) and isinstance(
                    stmt.value, ast.Call
                ):
                    f = stmt.value.func
                    if isinstance(f, ast.Attribute):
                        if f.attr == "acquire":
                            lk = g._lock_of(f.value, cls, path)
                            if lk is not None:
                                manual_held.add(lk)
                        elif f.attr == "release":
                            lk = g._lock_of(f.value, cls, path)
                            if lk is not None:
                                manual_held.discard(lk)
                        elif f.attr == "join":
                            check_join(
                                f.value,
                                stmt.lineno,
                                entry | with_held | manual_held,
                            )
                elif isinstance(stmt, ast.For):
                    # for t in self._threads: t.join()
                    it = stmt.iter
                    if isinstance(it, ast.Call) and isinstance(
                        it.func, ast.Name
                    ) and it.func.id == "list" and it.args:
                        it = it.args[0]
                    attr = cg._self_attr(it)
                    if (
                        attr is not None
                        and attr in amap
                        and isinstance(stmt.target, ast.Name)
                    ):
                        local_threads[stmt.target.id] = amap[attr]
                for field in ("body", "orelse", "finalbody"):
                    if isinstance(stmt, (ast.With, ast.AsyncWith)):
                        break
                    sub = getattr(stmt, field, None)
                    if sub:
                        walk(sub, with_held)
                for handler in getattr(stmt, "handlers", []) or []:
                    walk(handler.body, with_held)

        walk(getattr(func.node, "body", []), set())
    return findings


# -- close-order-inversion ----------------------------------------------------


def _close_closure(
    g: cg.CallGraph, cls: Tuple[str, str]
) -> List[cg.FuncKey]:
    path, cname = cls
    info = g.classes.get(cls)
    if info is None:
        return []
    queue = [(path, cname, m) for m in CLOSE_LIKE if m in info.methods]
    seen = list(queue)
    while queue:
        cur = queue.pop(0)
        for edge in g.edges.get(cur, []):
            cal = edge.callee
            if cal[:2] == (path, cname) and cal not in seen:
                seen.append(cal)
                queue.append(cal)
    return seen


def _teardown_events(
    g: cg.CallGraph,
    key: cg.FuncKey,
    amap: Dict[str, cg.FuncKey],
    _depth: int = 0,
    _seen: Optional[Set[cg.FuncKey]] = None,
) -> List[Tuple[int, str, str]]:
    """Linear (line, kind, attr) events of a close method with
    same-class helper calls inlined: kind is 'close' or 'join'."""
    if _seen is None:
        _seen = set()
    if key in _seen or _depth > 4:
        return []
    _seen.add(key)
    func = g.functions.get(key)
    if func is None:
        return []
    path, cname, _ = key
    cls = _class_of(g, key)
    events: List[Tuple[int, str, str]] = []
    for stmt in _stmts_in_order(getattr(func.node, "body", [])):
        if isinstance(stmt, ast.For):
            it = stmt.iter
            if isinstance(it, ast.Call) and isinstance(
                it.func, ast.Name
            ) and it.func.id == "list" and it.args:
                it = it.args[0]
            attr = cg._self_attr(it)
            if attr is not None and attr in amap:
                for sub in ast.walk(stmt):
                    if (
                        isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "join"
                    ):
                        events.append((stmt.lineno, "join", attr))
                        break
            continue
        if not (
            isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call)
        ):
            continue
        call = stmt.value
        f = call.func
        if isinstance(f, ast.Attribute):
            attr = cg._self_attr(f.value)
            if attr is not None:
                if f.attr == "join" and attr in amap:
                    events.append((stmt.lineno, "join", attr))
                    continue
                if f.attr in _CLOSING_OPS:
                    events.append((stmt.lineno, "close", attr))
                    continue
        callee = g._resolve_call(key, call, cls, {})
        if callee is not None and callee[:2] == (path, cname):
            events.extend(
                _teardown_events(g, callee, amap, _depth + 1, _seen)
            )
    return events


def _racing_use(
    g: cg.CallGraph,
    cls: Tuple[str, str],
    target: cg.FuncKey,
    attr: str,
) -> Optional[Tuple[str, str]]:
    """A non-read, non-close use of ``self.attr`` reachable from the
    thread target within the owning class: (qualname, method called)."""
    path, cname = cls
    queue, seen = [target], {target}
    while queue:
        cur = queue.pop(0)
        if cur[:2] == (path, cname):
            func = g.functions.get(cur)
            if func is not None:
                for sub in ast.walk(func.node):
                    if (
                        isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and cg._self_attr(sub.func.value) == attr
                        and sub.func.attr not in UNBLOCK_READS
                        and sub.func.attr not in _CLOSING_OPS
                    ):
                        return (func.qualname, sub.func.attr)
        for edge in g.edges.get(cur, []):
            if edge.callee not in seen:
                seen.add(edge.callee)
                queue.append(edge.callee)
    return None


def _close_order_inversion(
    g: cg.CallGraph,
    tmap: Dict[Tuple[str, str], Dict[str, cg.FuncKey]],
) -> List[Finding]:
    findings: List[Finding] = []
    for cls, amap in sorted(tmap.items()):
        path, cname = cls
        info = g.classes.get(cls)
        if info is None:
            continue
        for m in CLOSE_LIKE:
            if m not in info.methods:
                continue
            key = (path, cname, m)
            events = _teardown_events(g, key, amap)
            reported: Set[Tuple[str, str]] = set()
            for i, (l1, kind1, closed) in enumerate(events):
                if kind1 != "close" or closed in amap:
                    continue
                for l2, kind2, tattr in events[i + 1:]:
                    if kind2 != "join" or (closed, tattr) in reported:
                        continue
                    target = amap.get(tattr)
                    if target is None:
                        continue
                    use = _racing_use(g, cls, target, closed)
                    if use is None:
                        continue
                    uq, um = use
                    reported.add((closed, tattr))
                    findings.append(Finding(
                        RULE, "close-order-inversion", path, l1,
                        f"{cname}.{m} closes self.{closed} before "
                        f"joining self.{tattr}, whose target {uq} "
                        f"still calls self.{closed}.{um}() — the "
                        "drain races the close; join the thread "
                        "first (blocking reads would be exempt: "
                        "closing to wake a reader is fine)",
                        chain=(
                            f"{cname}.{m}", f"self.{closed}",
                            f"self.{tattr}", f"{uq}:self.{closed}.{um}",
                        ),
                    ))
    return findings


# -- double-close-unsafe ------------------------------------------------------


def _test_is_existence_guard(test: ast.expr) -> bool:
    for sub in ast.walk(test):
        if isinstance(sub, ast.Call):
            f = sub.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else ""
            )
            if name in ("exists", "is_file", "is_dir", "lexists"):
                return True
        if cg._self_attr(sub) is not None:
            return True
    return False


def _method_has_idempotency_guard(node: ast.AST) -> bool:
    for stmt in getattr(node, "body", []):
        if not isinstance(stmt, ast.If):
            continue
        has_self = any(
            cg._self_attr(s) is not None for s in ast.walk(stmt.test)
        )
        has_return = any(
            isinstance(s, ast.Return) for s in ast.walk(stmt)
        )
        if has_self and has_return:
            return True
    return False


def _unlink_call(node: ast.Call) -> Optional[str]:
    """Receiver description if this call re-raises on a second close:
    ``x.unlink()`` without missing_ok=True, ``os.unlink``/``os.remove``."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    if f.attr == "unlink":
        for kw in node.keywords:
            if kw.arg == "missing_ok" and isinstance(
                kw.value, ast.Constant
            ) and kw.value.value:
                return None
        return ast.unparse(f.value) if hasattr(ast, "unparse") else "receiver"
    if f.attr == "remove" and isinstance(f.value, ast.Name) and (
        f.value.id == "os"
    ):
        return "os.remove target"
    return None


def _double_close_unsafe(g: cg.CallGraph) -> List[Finding]:
    findings: List[Finding] = []
    scanned: Set[cg.FuncKey] = set()
    for cls in sorted(g.classes, key=lambda c: (c[0], c[1])):
        for key in _close_closure(g, cls):
            if key in scanned:
                continue
            scanned.add(key)
            func = g.functions.get(key)
            if func is None:
                continue
            if _method_has_idempotency_guard(func.node):
                continue

            def walk(stmts, protected: bool) -> None:
                for stmt in stmts:
                    if isinstance(
                        stmt,
                        (
                            ast.FunctionDef,
                            ast.AsyncFunctionDef,
                            ast.ClassDef,
                        ),
                    ):
                        continue
                    if isinstance(stmt, ast.Try):
                        walk(stmt.body, protected or bool(stmt.handlers))
                        for h in stmt.handlers:
                            walk(h.body, protected)
                        walk(stmt.orelse, protected or bool(stmt.handlers))
                        walk(stmt.finalbody, protected)
                        continue
                    if isinstance(stmt, ast.If):
                        walk(
                            stmt.body,
                            protected
                            or _test_is_existence_guard(stmt.test),
                        )
                        walk(stmt.orelse, protected)
                        continue
                    if isinstance(
                        stmt,
                        (
                            ast.With,
                            ast.AsyncWith,
                            ast.For,
                            ast.AsyncFor,
                            ast.While,
                        ),
                    ):
                        walk(stmt.body, protected)
                        walk(getattr(stmt, "orelse", []) or [], protected)
                        continue
                    if not protected:
                        for sub in ast.walk(stmt):
                            if not isinstance(sub, ast.Call):
                                continue
                            recv = _unlink_call(sub)
                            if recv is not None:
                                findings.append(Finding(
                                    RULE, "double-close-unsafe",
                                    func.path, sub.lineno,
                                    f"{func.qualname} unlinks "
                                    f"'{recv}' with no guard — the "
                                    "second close that SIGKILL "
                                    "replays and _reclaim_stale "
                                    "guarantee raises mid-teardown; "
                                    "use try/except, missing_ok="
                                    "True, an existence check, or "
                                    "an idempotency flag",
                                    chain=(func.qualname, recv),
                                ))

            walk(getattr(func.node, "body", []), False)
    return findings


def run(ctx: AnalysisContext) -> List[Finding]:
    g = cg.CallGraph(ctx)
    tmap = _thread_attr_targets(g)
    findings: List[Finding] = []
    findings.extend(_join_under_lock(g, tmap))
    findings.extend(_close_order_inversion(g, tmap))
    findings.extend(_double_close_unsafe(g))
    return findings
