"""Host-concurrency analyzer: PICO-C001..C004.

Targets the threaded host subsystems (``tools/serve.py``, the
``checkpoint.py`` mirror worker, ``resilience/cluster.py``,
``resilience/preemption.py``, ``inference/batcher.py`` under the serve
front end).  Per class, the analyzer:

1. identifies **locks** (attributes assigned ``threading.Lock()`` /
   ``RLock`` / ``Condition`` / ``Semaphore``, module-level equivalents,
   plus name-pattern fallbacks like ``_mu``/``*_lock``) and walks every
   method tracking the *held set* through ``with lock:`` nesting and the
   ``acquire(timeout=...)`` / ``release()`` idiom;
2. builds a **lock-acquisition graph** — an edge A→B wherever B is
   acquired (directly or through a same-class/module call) while A is
   held — and reports cycles (PICO-C001);
3. reports **blocking calls under a lock** (PICO-C002): ``time.sleep``,
   ``.join()``, subprocess/os.system, file I/O (``open``, ``shutil.*``,
   ``os.rename``...), network clients, timeout-less ``.wait()``, and
   timeout-less queue ``.get()``;
4. tracks **attribute mutations vs the held set**: an attribute mutated
   under a lock in one place and without it in another is PICO-C003; an
   attribute mutated both by background-thread methods
   (``threading.Thread(target=self.m)`` closure) and by foreground
   methods with no lock at all is PICO-C004.

Thread-safe channel objects (``queue.Queue``, ``threading.Event``,
locks themselves) are exempt from the mutation rules — they are the
sanctioned way to share state.  Construction in ``__init__`` and the
thread-starting method are exempt too (happens-before ``Thread.start``).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Optional

from picotron_tpu.analysis.callgraph import (
    ModuleInfo, Project, dotted_name)
from picotron_tpu.analysis.findings import Finding

LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
THREADSAFE_CTORS = {"Queue", "SimpleQueue", "LifoQueue", "PriorityQueue",
                    "Event", "deque"} | LOCK_CTORS
_LOCKISH_NAME = re.compile(r"(^|_)(mu|mutex|lock|cond|sem)\d*$")
# collection methods that mutate their receiver
MUTATORS = {"append", "appendleft", "extend", "insert", "pop", "popleft",
            "remove", "clear", "update", "add", "discard", "setdefault"}
_QUEUEISH_NAME = re.compile(r"(^|_)(q|queue|events|inbox|outbox)\d*$",
                            re.IGNORECASE)


def _ctor_name(value: ast.expr) -> Optional[str]:
    if isinstance(value, ast.Call):
        parts = dotted_name(value.func)
        if parts:
            return parts[-1]
    return None


@dataclass
class MethodSummary:
    name: str
    acquires: list = field(default_factory=list)  # (lock, held_before, line)
    blocking: list = field(default_factory=list)  # (desc, held, line)
    mutations: list = field(default_factory=list)  # (attr, held, line)
    calls: list = field(default_factory=list)  # (callee_name, held, line)
    thread_targets: list = field(default_factory=list)  # self-method names


class _MethodWalker:
    """Walk one method body tracking the held-lock set statement by
    statement.  Deliberately linear: loops are walked once, ``try`` bodies
    with their entry held set, ``finally`` releases applied in order."""

    def __init__(self, owner: "_ClassScan", method: str):
        self.o = owner
        self.sum = MethodSummary(method)

    # -- lock identity ------------------------------------------------------ #

    def _lock_id(self, expr: ast.expr) -> Optional[str]:
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and expr.value.id == "self":
            name = expr.attr
            if name in self.o.lock_attrs or _LOCKISH_NAME.search(name):
                return f"{self.o.class_name}.{name}"
        elif isinstance(expr, ast.Name):
            name = expr.id
            if name in self.o.module_locks or _LOCKISH_NAME.search(name):
                return f"<module>.{name}"
        return None

    # -- statement walk ----------------------------------------------------- #

    def walk(self, stmts: list, held: frozenset) -> frozenset:
        for stmt in stmts:
            held = self._stmt(stmt, held)
        return held

    def _stmt(self, stmt: ast.stmt, held: frozenset) -> frozenset:
        if isinstance(stmt, ast.With):
            return self._with(stmt, held)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a nested def runs when called, not here; scan it with a
            # fresh held set under the same method context
            self.walk(stmt.body, frozenset())
            return held
        if isinstance(stmt, ast.If):
            return self._if(stmt, held)
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr_events(stmt.iter, held)
            self.walk(stmt.body, held)
            self.walk(stmt.orelse, held)
            return held
        if isinstance(stmt, ast.While):
            self._expr_events(stmt.test, held)
            self.walk(stmt.body, held)
            acq = self._acquire_in(stmt.test)
            if acq is not None and acq[1]:
                # `while not X.acquire(timeout=...): <shed, or ask again>`
                # — a bounded acquire in slices: the loop ends when one
                # succeeds, so the lock is held from the statement after it
                lid = acq[0]
                self.sum.acquires.append((lid, held, stmt.test.lineno))
                held = held | {lid}
            self.walk(stmt.orelse, held)
            return held
        if isinstance(stmt, ast.Try):
            inner = self.walk(stmt.body, held)
            for h in stmt.handlers:
                self.walk(h.body, held)
            inner = self.walk(stmt.orelse, inner)
            return self.walk(stmt.finalbody, inner)
        if isinstance(stmt, (ast.ClassDef,)):
            return held
        # simple statement: record events, then apply acquire/release
        self._expr_events(stmt, held)
        return self._apply_acq_rel(stmt, held)

    def _with(self, stmt: ast.With, held: frozenset) -> frozenset:
        locks = []
        for item in stmt.items:
            lid = self._lock_id(item.context_expr)
            if lid is not None:
                locks.append(lid)
                self.sum.acquires.append((lid, held | frozenset(locks[:-1]),
                                          item.context_expr.lineno))
            else:
                self._expr_events(item.context_expr, held)
        inner = held | frozenset(locks)
        # a lock the body takes with acquire() outlives the block (a timed
        # span around a bounded acquire); the block's own locks do not
        return self.walk(stmt.body, inner) - (frozenset(locks) - held)

    def _if(self, stmt: ast.If, held: frozenset) -> frozenset:
        self._expr_events(stmt.test, held)
        acq = self._acquire_in(stmt.test)
        if acq is not None:
            lid, negated = acq
            self.sum.acquires.append((lid, held, stmt.test.lineno))
            if negated:
                # `if not X.acquire(...): <shed/raise>` — the lock is held
                # from the statement AFTER the if on the success path
                self.walk(stmt.body, held)
                self.walk(stmt.orelse, held | {lid})
                return held | {lid}
            # `if X.acquire(...): <locked body>`
            self.walk(stmt.body, held | {lid})
            self.walk(stmt.orelse, held)
            return held
        self.walk(stmt.body, held)
        self.walk(stmt.orelse, held)
        return held

    def _acquire_in(self, test: ast.expr) -> Optional[tuple]:
        negated = False
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            negated, test = True, test.operand
        if isinstance(test, ast.Call) and \
                isinstance(test.func, ast.Attribute) \
                and test.func.attr == "acquire":
            lid = self._lock_id(test.func.value)
            if lid is not None:
                return lid, negated
        return None

    def _apply_acq_rel(self, stmt: ast.stmt, held: frozenset) -> frozenset:
        for node in ast.walk(stmt):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            lid = self._lock_id(node.func.value)
            if lid is None:
                continue
            if node.func.attr == "acquire":
                self.sum.acquires.append((lid, held, node.lineno))
                held = held | {lid}
            elif node.func.attr == "release":
                held = held - {lid}
        return held

    # -- events inside one statement/expression ----------------------------- #

    def _expr_events(self, node: ast.AST, held: frozenset) -> None:
        self._record_mutations(node, held)
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            if isinstance(sub, ast.Call):
                self._record_call(sub, held)

    def _record_mutations(self, node: ast.AST, held: frozenset) -> None:
        def attr_of_target(t: ast.expr) -> Optional[str]:
            while isinstance(t, ast.Subscript):
                t = t.value
            if isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self":
                return t.attr
            return None

        targets: list = []
        if isinstance(node, ast.Assign):
            for t in node.targets:
                targets.extend(t.elts if isinstance(t, (ast.Tuple, ast.List))
                               else [t])
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets.append(node.target)
        for t in targets:
            attr = attr_of_target(t)
            if attr is not None and not self.o.is_threadsafe_attr(attr):
                self.sum.mutations.append((attr, held, t.lineno))
        # mutating method calls: self.X.append(...) etc.
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in MUTATORS:
                recv = sub.func.value
                if isinstance(recv, ast.Attribute) and \
                        isinstance(recv.value, ast.Name) \
                        and recv.value.id == "self" \
                        and not self.o.is_threadsafe_attr(recv.attr):
                    self.sum.mutations.append((recv.attr, held, sub.lineno))

    def _record_call(self, call: ast.Call, held: frozenset) -> None:
        func = call.func
        # threading.Thread(target=self.m) — remember the thread entry
        parts = dotted_name(func)
        if parts and parts[-1] == "Thread":
            for kw in call.keywords:
                if kw.arg == "target" and isinstance(kw.value, ast.Attribute)\
                        and isinstance(kw.value.value, ast.Name) \
                        and kw.value.value.id == "self":
                    self.sum.thread_targets.append(kw.value.attr)
        # same-class call for the lock/blocking propagation
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and func.value.id == "self":
            self.sum.calls.append((func.attr, held, call.lineno))
        desc = self._blocking_desc(call, parts)
        if desc is not None:
            # recorded with an empty held set too: the one-hop propagation
            # needs to see a lock-free callee's blocking calls
            self.sum.blocking.append((desc, held, call.lineno))

    def _blocking_desc(self, call: ast.Call,
                       parts: Optional[list]) -> Optional[str]:
        kwargs = {kw.arg for kw in call.keywords}
        if parts:
            root, leaf = parts[0], parts[-1]
            if root == "time" and leaf == "sleep":
                return "time.sleep"
            if root == "subprocess" or (root, leaf) == ("os", "system"):
                return ".".join(parts)
            if root == "shutil":
                return ".".join(parts)
            if root == "os" and leaf in ("rename", "replace", "remove",
                                         "unlink", "makedirs", "rmdir",
                                         "listdir", "getmtime", "stat"):
                return ".".join(parts)
            if root in ("requests", "urllib", "socket"):
                return ".".join(parts)
            if len(parts) == 1 and leaf == "open":
                return "open()"
        func = call.func
        if isinstance(func, ast.Attribute):
            recv = func.value
            recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                         else recv.id if isinstance(recv, ast.Name) else "")
            # thread/queue joins take no positional arg (or one numeric
            # timeout); str.join always takes exactly one iterable —
            # `sep.join(parts)` under a lock is string building, not a
            # blocking wait
            threadish_args = (not call.args or (
                len(call.args) == 1
                and isinstance(call.args[0], ast.Constant)
                and isinstance(call.args[0].value, (int, float))))
            if func.attr == "join" and threadish_args \
                    and not isinstance(recv, ast.Constant) \
                    and not (parts and len(parts) >= 2
                             and parts[-2] == "path"):
                return f"{recv_name or '<expr>'}.join"
            if func.attr == "wait" and "timeout" not in kwargs \
                    and not call.args and self._lock_id(recv) is None:
                return f"{recv_name or '<expr>'}.wait() without timeout"
            if func.attr == "get" and not call.args \
                    and "timeout" not in kwargs \
                    and _QUEUEISH_NAME.search(recv_name or ""):
                return f"{recv_name}.get() without timeout"
        return None


@dataclass
class _ClassScan:
    module: ModuleInfo
    class_name: str
    node: ast.ClassDef
    lock_attrs: set = field(default_factory=set)
    threadsafe_attrs: set = field(default_factory=set)
    module_locks: set = field(default_factory=set)
    methods: dict = field(default_factory=dict)  # name -> MethodSummary

    def is_threadsafe_attr(self, attr: str) -> bool:
        return attr in self.threadsafe_attrs or attr in self.lock_attrs \
            or bool(_LOCKISH_NAME.search(attr))

    def scan(self) -> None:
        # pass 1: classify attributes from `self.X = <ctor>()` assignments
        for sub in ast.walk(self.node):
            if not isinstance(sub, ast.Assign):
                continue
            ctor = _ctor_name(sub.value)
            if ctor is None:
                continue
            for t in sub.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and t.value.id == "self":
                    if ctor in LOCK_CTORS:
                        self.lock_attrs.add(t.attr)
                    if ctor in THREADSAFE_CTORS:
                        self.threadsafe_attrs.add(t.attr)
        # pass 2: walk each direct method
        for item in self.node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                w = _MethodWalker(self, item.name)
                w.walk(item.body, frozenset())
                self.methods[item.name] = w.sum

    # -- derived facts ------------------------------------------------------ #

    def locks_acquired_transitively(self, method: str,
                                    _seen: Optional[set] = None) -> set:
        _seen = _seen if _seen is not None else set()
        if method in _seen or method not in self.methods:
            return set()
        _seen.add(method)
        out = {lock for lock, _, _ in self.methods[method].acquires}
        for callee, _, _ in self.methods[method].calls:
            out |= self.locks_acquired_transitively(callee, _seen)
        return out

    def reachable_from(self, entries: list) -> set:
        seen: set = set()
        work = list(entries)
        while work:
            m = work.pop()
            if m in seen or m not in self.methods:
                continue
            seen.add(m)
            work.extend(c for c, _, _ in self.methods[m].calls)
        return seen


def _scan_module(mod: ModuleInfo) -> list:
    """All class scans for one module (module-level locks attached)."""
    module_locks = set()
    for stmt in mod.tree.body:
        if isinstance(stmt, ast.Assign) and \
                _ctor_name(stmt.value) in LOCK_CTORS:
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    module_locks.add(t.id)
    scans = []
    for stmt in ast.walk(mod.tree):
        if isinstance(stmt, ast.ClassDef):
            s = _ClassScan(mod, stmt.name, stmt, module_locks=module_locks)
            s.scan()
            scans.append(s)
    return scans


# --------------------------------------------------------------------------- #
# rules over the per-class summaries
# --------------------------------------------------------------------------- #


def _finding(mod: ModuleInfo, rule: str, line: int, context: str,
             message: str) -> Finding:
    return Finding(rule=rule, path=mod.rel, line=line, context=context,
                   snippet=mod.snippet(line), message=message)


def _lock_order(scan: _ClassScan, findings: list) -> None:
    """PICO-C001: cycles in the acquired-while-holding graph."""
    edges: dict = {}  # (A, B) -> (line, method)
    for name, summ in scan.methods.items():
        for lock, held, line in summ.acquires:
            for h in held:
                if h != lock:
                    edges.setdefault((h, lock), (line, name))
        for callee, held, line in summ.calls:
            if not held:
                continue
            for lock in scan.locks_acquired_transitively(callee):
                for h in held:
                    if h != lock:
                        edges.setdefault((h, lock),
                                         (line, f"{name} -> {callee}"))
    graph: dict = {}
    for (a, b) in edges:
        graph.setdefault(a, set()).add(b)

    def on_cycle(a: str, b: str) -> bool:
        """Whether edge a->b closes a cycle (i.e. b reaches back to a)."""
        seen, work = set(), [b]
        while work:
            n = work.pop()
            if n == a:
                return True
            if n in seen:
                continue
            seen.add(n)
            work.extend(graph.get(n, ()))
        return False

    reported: set = set()
    for (a, b), (line, where) in sorted(edges.items(),
                                        key=lambda kv: kv[1][0]):
        if frozenset((a, b)) in reported:
            continue
        if on_cycle(a, b):
            reported.add(frozenset((a, b)))
            findings.append(_finding(
                scan.module, "PICO-C001", line,
                f"{scan.class_name}.{where.split(' ')[0]}",
                f"lock-order inversion: {b} acquired while holding {a} "
                f"here, but another path acquires them in the opposite "
                f"order — the two paths deadlock when they interleave"))


def _blocking_under_lock(scan: _ClassScan, findings: list) -> None:
    """PICO-C002: direct blocking calls, plus one-hop propagation (a
    callee that blocks, called while the caller holds a lock)."""
    for name, summ in scan.methods.items():
        for desc, held, line in summ.blocking:
            if not held:
                continue
            findings.append(_finding(
                scan.module, "PICO-C002", line,
                f"{scan.class_name}.{name}",
                f"blocking call ({desc}) while holding "
                f"{', '.join(sorted(held))} — every thread contending for "
                f"the lock stalls behind it"))
        for callee, held, line in summ.calls:
            if not held or callee not in scan.methods:
                continue
            # a callee that blocks while itself holding a lock is already
            # reported at its own site; here we catch the lock-free callee
            # whose blocking call only becomes a hazard under OUR lock
            for desc, _inner_held, bline in [
                    (d, h, ln) for d, h, ln in scan.methods[callee].blocking
                    if not h]:
                findings.append(_finding(
                    scan.module, "PICO-C002", line,
                    f"{scan.class_name}.{name}",
                    f"call to self.{callee}() while holding "
                    f"{', '.join(sorted(held))} reaches a blocking "
                    f"{desc} (at line {bline})"))


def _guarded_mutations(scan: _ClassScan, findings: list) -> None:
    """PICO-C003: attr mutated under a lock somewhere, without it
    elsewhere."""
    if not scan.lock_attrs and not scan.module_locks:
        return
    # like C004: the thread-starting method's writes happen-before
    # Thread.start, so they need no lock (module docstring contract)
    exempt = {"__init__"} | {name for name, summ in scan.methods.items()
                             if summ.thread_targets}
    by_attr: dict = {}
    for name, summ in scan.methods.items():
        if name in exempt:
            continue
        for attr, held, line in summ.mutations:
            by_attr.setdefault(attr, []).append((held, name, line))
    for attr, sites in sorted(by_attr.items()):
        guarded = sorted({lock for held, _, _ in sites for lock in held})
        if not guarded:
            continue
        for held, name, line in sites:
            if held:
                continue
            findings.append(_finding(
                scan.module, "PICO-C003", line,
                f"{scan.class_name}.{name}",
                f"self.{attr} is mutated under {', '.join(guarded)} "
                f"elsewhere but without any lock here — concurrent "
                f"threads lose updates or tear reads"))


def _cross_thread_mutations(scan: _ClassScan, findings: list) -> None:
    """PICO-C004: attr mutated by background-thread methods AND by
    foreground methods, no lock on either side."""
    entries, starters = [], set()
    for name, summ in scan.methods.items():
        if summ.thread_targets:
            starters.add(name)
            entries.extend(summ.thread_targets)
    if not entries:
        return
    reachable = scan.reachable_from(entries)
    exempt = starters | {"__init__"}
    bg_sites: dict = {}
    fg_sites: dict = {}
    for name, summ in scan.methods.items():
        if name in exempt:
            continue
        bucket = bg_sites if name in reachable else fg_sites
        for attr, held, line in summ.mutations:
            if not held:
                bucket.setdefault(attr, []).append((name, line))
    for attr in sorted(set(bg_sites) & set(fg_sites)):
        bgm, bgl = bg_sites[attr][0]
        fgm, _ = fg_sites[attr][0]
        findings.append(_finding(
            scan.module, "PICO-C004", bgl, f"{scan.class_name}.{bgm}",
            f"self.{attr} is mutated by background-thread code here AND "
            f"by {scan.class_name}.{fgm} with no lock on either side — "
            f"there is no ordering between the threads at all"))


def analyze(project: Project) -> list:
    findings: list = []
    for mod in project.modules.values():
        for scan in _scan_module(mod):
            _lock_order(scan, findings)
            _blocking_under_lock(scan, findings)
            _guarded_mutations(scan, findings)
            _cross_thread_mutations(scan, findings)
    return findings
