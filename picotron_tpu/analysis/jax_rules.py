"""JAX hot-path analyzer: PICO-J001..J005.

Entry points are discovered syntactically — functions decorated with or
passed to ``jax.jit`` / ``jax.pmap`` / ``pl.pallas_call`` / ``shard_map``
(including the ``utils.shard_map`` compat wrapper and
``functools.partial(kernel, ...)`` indirection), plus bodies handed to
``lax.fori_loop`` / ``while_loop`` / ``scan`` / ``cond`` (those trace even
outside jit).  From each entry the intra-project call graph is walked
(``callgraph.Project``), and every reachable function is analyzed as
*traced code*:

- **PICO-J001** — host-sync operations on traced values.  A light taint
  pass marks the function's parameters and anything assigned from them or
  from ``jnp``/``jax``/``lax`` call results; ``float()``/``int()``/
  ``bool()``/``.item()``/``.tolist()``/``np.asarray``/``np.array``/
  ``jax.device_get``/``.block_until_ready()`` applied to a tainted value
  is a finding, as is an ``if``/``while`` test that coerces an
  array-derived value.  Shape/dtype reads (``x.shape``, ``x.ndim``,
  ``x.dtype``, ``len(x)``) are static under trace and stop the taint.
- **PICO-J002** — host nondeterminism under trace (``time.*``,
  ``random.*``, ``np.random.*``, ``os.urandom``, ``uuid.*``,
  ``datetime.now``): evaluated once at trace time, baked into the
  compiled program.
- **PICO-J003** — ``pl.program_id`` (or any ``*.program_id``) read inside
  a function passed as a ``fori_loop``/``while_loop``/``scan`` body: the
  Pallas interpreter (jax 0.9.0 too) cannot lower it in the sub-jaxpr (see
  ``ops/pallas/decode_attention.py``).  Scanned everywhere, traced or
  not — the trap fires at kernel runtime.
- **PICO-J004** — ``jax.jit``/``jax.pmap``/``pl.pallas_call`` evaluated
  lexically inside a ``for``/``while`` loop: a fresh callable per
  iteration means a recompile per iteration unless cached outside.
- **PICO-J005** — ``pltpu.make_async_copy`` started with no matching
  ``.wait()`` in the enclosing function, or started per-iteration inside
  a ``fori_loop``/``while_loop``/``scan`` body whose every wait sits
  outside the loop: the DMA is still in flight when its buffer is read
  (or the semaphore imbalances) — the exact hazard the double-buffered
  decode kernel (``ops/pallas/decode_attention.py``) must discipline.
- **PICO-J006** — a compiled model program called around ``_dispatch``.
  In any class defining ``_dispatch`` (the retry / flash-fallback fault
  wrapper), a call to a ``self._*_jit`` / ``self._*_prog`` attribute
  whose first operand is ``params`` (the model-program signature —
  housekeeping programs take the cache or nothing first) must sit inside
  a ``self._dispatch(...)`` argument; a direct call silently opts the
  program family out of the engine's fault semantics.  Builder calls
  (``self._make_*``) construct rather than dispatch and are exempt.
"""

from __future__ import annotations

import ast
from typing import Optional

from picotron_tpu.analysis.callgraph import (
    FuncInfo, ModuleInfo, Project, dotted_name, enclosing_qualname)
from picotron_tpu.analysis.findings import Finding

# attribute reads that yield static (trace-time Python) values: reading
# them off a tracer does not sync, and values derived from them are static
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                "itemsize", "nbytes"}
# calls whose results are static regardless of argument taint
STATIC_CALLS = {"len", "isinstance", "type", "getattr", "hasattr", "range"}
# names whose attributes produce traced arrays (taint sources / "derived")
ARRAY_NAMESPACES = {"jnp", "lax", "jax", "pl", "pltpu"}
HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
HOST_SYNC_CASTS = {"float", "int", "bool", "complex"}
# wrappers whose function-valued args enter trace
JIT_WRAPPERS = {("jax", "jit"), ("jax", "pmap"), ("jax", "vmap"),
                ("jax", "grad"), ("jax", "value_and_grad"),
                ("jax", "checkpoint"), ("jax", "remat"),
                ("jax", "shard_map")}
LOOP_BODY_WRAPPERS = {"fori_loop", "while_loop", "scan", "cond"}


def _callee_parts(call: ast.Call) -> Optional[list]:
    return dotted_name(call.func)


def _is_jit_wrapper(parts: list, mod: ModuleInfo) -> bool:
    """Whether a dotted callee name is a jit-like wrapper call."""
    if len(parts) >= 2 and (parts[-2], parts[-1]) in JIT_WRAPPERS:
        return True
    if parts[-1] in ("pallas_call",):
        return True
    if parts[-1] in ("shard_map", "shard_map_compat"):
        return True
    if len(parts) == 1 and parts[0] in ("jit", "pmap"):
        src = mod.from_imports.get(parts[0])
        return src is not None and src[0].startswith("jax")
    return False


def _unwrap_partial(node: ast.expr) -> ast.expr:
    """``functools.partial(f, ...)`` / ``partial(f, ...)`` -> ``f``."""
    if isinstance(node, ast.Call):
        parts = dotted_name(node.func)
        if parts and parts[-1] == "partial" and node.args:
            return node.args[0]
    return node


def _func_args_of_call(call: ast.Call, parts: list) -> list:
    """The positional args of a wrapper call that are traced callables."""
    if parts[-1] in LOOP_BODY_WRAPPERS:
        if parts[-1] == "fori_loop":
            return call.args[2:3]
        if parts[-1] == "while_loop":
            return call.args[0:2]
        if parts[-1] == "scan":
            return call.args[0:1]
        if parts[-1] == "cond":
            return call.args[1:3]
    return call.args[0:1]  # jit/pmap/pallas_call/shard_map: first arg


class _EntryCollector(ast.NodeVisitor):
    """Find every function that enters trace in one module."""

    def __init__(self, project: Project, mod: ModuleInfo):
        self.project = project
        self.mod = mod
        self.entries: list = []  # FuncInfo
        self.lambda_entries: list = []  # (ast.Lambda, context qualname)
        self._scope: list = []  # qualname prefix stack
        self._class: Optional[str] = None

    # -- scope tracking ---------------------------------------------------- #

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        prev, self._class = self._class, node.name
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()
        self._class = prev

    def _visit_func(self, node) -> None:
        self._check_decorators(node)
        self._scope.append(node.name)
        self._scope.append("<locals>")
        self.generic_visit(node)
        self._scope.pop()
        self._scope.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- entry forms ------------------------------------------------------- #

    def _check_decorators(self, node) -> None:
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            target = _unwrap_partial(target) if isinstance(dec, ast.Call) \
                else target
            parts = dotted_name(target)
            if parts and _is_jit_wrapper(parts, self.mod):
                fi = self._resolve_local(node.name)
                if fi is not None:
                    self.entries.append(fi)
            # @partial(jax.jit, static_argnames=...) — the partial's first
            # arg is the wrapper, the decorated function is the entry
            if isinstance(dec, ast.Call):
                inner = dotted_name(dec.func)
                if inner and inner[-1] == "partial" and dec.args:
                    wparts = dotted_name(dec.args[0])
                    if wparts and _is_jit_wrapper(wparts, self.mod):
                        fi = self._resolve_local(node.name)
                        if fi is not None:
                            self.entries.append(fi)

    def visit_Call(self, node: ast.Call) -> None:
        parts = _callee_parts(node)
        if parts and (_is_jit_wrapper(parts, self.mod)
                      or parts[-1] in LOOP_BODY_WRAPPERS):
            for arg in _func_args_of_call(node, parts):
                self._add_entry(_unwrap_partial(arg))
        self.generic_visit(node)

    def _add_entry(self, expr: ast.expr) -> None:
        if isinstance(expr, ast.Lambda):
            self.lambda_entries.append((expr, ".".join(
                [p for p in self._scope if p != "<locals>"]) or "<module>"))
            return
        fi = None
        if isinstance(expr, ast.Name):
            fi = self._resolve_local(expr.id)
        elif isinstance(expr, ast.Attribute):
            if (isinstance(expr.value, ast.Name) and expr.value.id == "self"
                    and self._class):
                fi = self.mod.functions.get(f"{self._class}.{expr.attr}")
            else:
                fi = self.project.resolve_callee_expr(self.mod, expr,
                                                      self._class)
        if fi is not None:
            self.entries.append(fi)

    def _resolve_local(self, name: str) -> Optional[FuncInfo]:
        """A bare name in the current scope: innermost nested def first,
        then module level, then project imports."""
        prefix = list(self._scope)
        while prefix:
            fi = self.mod.functions.get(".".join(prefix + [name]))
            if fi is not None:
                return fi
            prefix.pop()
        return self.project.resolve_name(self.mod, name)


def traced_functions(project: Project) -> tuple:
    """``(reachable, direct)``: qualname keys ``(modname, qualname)`` of
    every function reachable from a jit/pallas/control-flow entry point,
    and the subset that IS such an entry (decorated with / passed to a
    wrapper).  Direct entries have definitely-traced parameters; a
    transitively-reached helper may take any mix of tracers and static
    Python values, so its params must not be presumed traced (the
    ``is_entry`` contract in ``_TracedFuncChecker``)."""
    entries: list = []
    for mod in project.modules.values():
        col = _EntryCollector(project, mod)
        col.visit(mod.tree)
        entries.extend(col.entries)
    direct = {(fi.module.modname, fi.qualname) for fi in entries}
    seen: set = set()
    work = list(entries)
    while work:
        fi = work.pop()
        key = (fi.module.modname, fi.qualname)
        if key in seen:
            continue
        seen.add(key)
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            callee = project.resolve_call(fi.module, node, fi.class_name)
            if callee is not None:
                work.append(callee)
        # nested defs run under the same trace
        for qual, sub in fi.module.functions.items():
            if qual.startswith(fi.qualname + ".<locals>."):
                work.append(sub)
    return seen, direct


# --------------------------------------------------------------------------- #
# J001/J002: taint + nondeterminism inside traced functions
# --------------------------------------------------------------------------- #


def _names_in(node: ast.expr, stop_static: bool = True) -> set:
    """Names referenced in ``node``, optionally pruning subtrees under
    static attribute reads / static calls (``x.shape``, ``len(x)``)."""
    out: set = set()

    def walk(n: ast.AST) -> None:
        if stop_static and isinstance(n, ast.Attribute) \
                and n.attr in STATIC_ATTRS:
            return
        if stop_static and isinstance(n, ast.Call):
            parts = dotted_name(n.func)
            if parts and parts[-1] in STATIC_CALLS:
                return
        if isinstance(n, ast.Name):
            out.add(n.id)
        for c in ast.iter_child_nodes(n):
            walk(c)

    walk(node)
    return out


# jax/jnp functions whose results are static host values, not tracers —
# shape/type/topology introspection and trace-time-only utilities
JAX_STATIC_FUNCS = {"eval_shape", "ShapeDtypeStruct", "typeof",
                    "device_count", "local_device_count", "process_index",
                    "process_count", "devices", "local_devices",
                    "named_scope", "dtype", "result_type"}


def _call_is_array(call: ast.Call, mod: ModuleInfo) -> bool:
    """Whether this one call's RESULT is a traced array (jnp/lax/... and
    not a static introspection helper)."""
    parts = dotted_name(call.func)
    if not parts:
        return False
    if parts[:2] in (["jax", "tree"], ["jax", "tree_util"]):
        return False  # containers of leaves; coercion on them is host-side
    if parts[-1] in JAX_STATIC_FUNCS:
        return False
    if parts[0] in ARRAY_NAMESPACES:
        return True
    if len(parts) == 1:
        src = mod.from_imports.get(parts[0])
        return src is not None and src[0].split(".")[0] == "jax" \
            and parts[0] not in JAX_STATIC_FUNCS
    return False


def _is_array_call(node: ast.expr, mod: ModuleInfo) -> bool:
    """Whether ``node`` contains an array-producing call, pruning
    subtrees under static attribute reads (``jnp.sum(x).dtype`` is a
    static value, not a tracer)."""

    def walk(n: ast.AST) -> bool:
        if isinstance(n, ast.Attribute) and n.attr in STATIC_ATTRS:
            return False
        if isinstance(n, ast.Call):
            parts = dotted_name(n.func)
            if parts and parts[-1] in STATIC_CALLS:
                return False
            if _call_is_array(n, mod):
                return True
        return any(walk(c) for c in ast.iter_child_nodes(n))

    return walk(node)


def _numpy_aliases(mod: ModuleInfo) -> set:
    """Local names bound to HOST numpy.  The bare names ``np``/``numpy``
    count only when the module doesn't rebind them to something else —
    ``import jax.numpy as np`` makes ``np.asarray`` a traced no-sync op,
    not a host sync."""
    out = set()
    for local, target in mod.module_aliases.items():
        if target in ("numpy", "np"):
            out.add(local)
    for name in ("np", "numpy"):
        if name not in mod.module_aliases and name not in mod.from_imports:
            out.add(name)
    return out


def _nondet_call(parts: list, mod: ModuleInfo,
                 np_aliases: set) -> Optional[str]:
    """A human message when the dotted callee is a trace-time
    nondeterminism source, else None."""
    root = parts[0]
    if root == "time" and parts[-1] in ("time", "monotonic", "perf_counter",
                                        "time_ns", "monotonic_ns", "sleep"):
        return f"time.{parts[-1]}() is evaluated once at trace time"
    if root == "random":
        # `from jax import random` shadows the stdlib module — not host RNG
        src = mod.from_imports.get("random")
        if src is None or not src[0].startswith("jax"):
            return f"stdlib random.{parts[-1]}() draws host RNG under trace"
    if root in np_aliases and len(parts) >= 2 \
            and parts[1] == "random":
        return "np.random under trace bakes one draw into the program"
    if root == "os" and parts[-1] == "urandom":
        return "os.urandom under trace bakes one draw into the program"
    if root == "uuid":
        return "uuid under trace bakes one value into the program"
    if root == "datetime" and parts[-1] in ("now", "utcnow", "today"):
        return "datetime.now() is evaluated once at trace time"
    if root == "secrets":
        return "secrets under trace bakes one draw into the program"
    return None


def _scalar_annotated(node) -> set:
    """Param names annotated with a host scalar type (``eps: float``) —
    those are static under jit and never tainted."""
    out = set()
    a = node.args
    for p in getattr(a, "posonlyargs", []) + a.args + a.kwonlyargs:
        ann = p.annotation
        if isinstance(ann, ast.Name) and ann.id in ("float", "int", "bool",
                                                    "str", "bytes"):
            out.add(p.arg)
        elif isinstance(ann, ast.Constant) and isinstance(ann.value, str) \
                and ann.value in ("float", "int", "bool", "str"):
            out.add(p.arg)
    return out


class _TracedFuncChecker(ast.NodeVisitor):
    """J001 + J002 over one traced function (nested defs included).

    ``is_entry`` — whether this function is a DIRECT jit/pallas entry: its
    parameters are definitely traced arrays, so host syncs on them fire.
    Transitively-reached helpers often take a mix of tracers and static
    Python values (a ``scale: float``, a config), so there only values
    *derived from array calls* inside the function are flagged — precision
    over recall, the contract that keeps the shipped tree's baseline
    empty of real code."""

    def __init__(self, fi: FuncInfo, findings: list, is_entry: bool = True,
                 static_params: frozenset = frozenset()):
        self.fi = fi
        self.mod = fi.module
        self.findings = findings
        self.np_aliases = _numpy_aliases(self.mod)
        # taint: parameters + anything assigned from tainted/array exprs
        if is_entry:
            self.tainted = (set(fi.params) - {"self", "cls"}
                            - set(static_params)
                            - _scalar_annotated(fi.node))
        else:
            self.tainted = set()
        # derived: definitely-array values (results of jnp/lax/jax calls)
        self.derived: set = set()

    def run(self) -> None:
        node = self.fi.node
        body = node.body if hasattr(node, "body") else []
        if isinstance(body, list):
            for stmt in body:
                self.visit(stmt)

    # -- taint propagation -------------------------------------------------- #

    def _expr_tainted(self, expr: ast.expr) -> bool:
        return bool(_names_in(expr) & (self.tainted | self.derived))

    def _expr_derived(self, expr: ast.expr) -> bool:
        return bool(_names_in(expr) & self.derived) \
            or _is_array_call(expr, self.mod)

    def _bind(self, target: ast.expr, tainted: bool, derived: bool) -> None:
        # structural, NOT ast.walk: `out[i] = jnp.sum(a)` taints the
        # container `out`, never the index `i` (a host loop variable),
        # and `self.x = ...` taints neither `self` nor the chain
        if isinstance(target, ast.Name):
            if tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
            if derived:
                self.derived.add(target.id)
            elif not tainted:
                self.derived.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind(elt, tainted, derived)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted, derived)
        elif isinstance(target, ast.Subscript):
            base = target.value
            while isinstance(base, (ast.Subscript, ast.Attribute)):
                base = base.value
            # a store into one slot never CLEARS the container's taint
            if isinstance(base, ast.Name):
                if tainted:
                    self.tainted.add(base.id)
                if derived:
                    self.derived.add(base.id)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.visit(node.value)
        t, d = self._expr_tainted(node.value), self._expr_derived(node.value)
        for target in node.targets:
            self._bind(target, t or d, d)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self.visit(node.value)
        if self._expr_tainted(node.value) or self._expr_derived(node.value):
            self._bind(node.target, True, self._expr_derived(node.value))

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self.visit(node.value)
            t = self._expr_tainted(node.value)
            d = self._expr_derived(node.value)
            self._bind(node.target, t or d, d)

    def visit_For(self, node: ast.For) -> None:
        if self._expr_tainted(node.iter) or self._expr_derived(node.iter):
            self._bind(node.target, True, self._expr_derived(node.iter))
        self.generic_visit(node)

    def _visit_nested(self, node) -> None:
        # nested defs trace with the parent; their params are fresh taints
        a = node.args
        for p in getattr(a, "posonlyargs", []) + a.args + a.kwonlyargs:
            self.tainted.add(p.arg)
        self.generic_visit(node)

    visit_FunctionDef = _visit_nested
    visit_AsyncFunctionDef = _visit_nested

    # -- checks ------------------------------------------------------------- #

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        self.findings.append(Finding(
            rule=rule, path=self.mod.rel, line=node.lineno,
            context=self.fi.qualname, snippet=self.mod.snippet(node.lineno),
            message=message))

    def visit_Call(self, node: ast.Call) -> None:
        parts = dotted_name(node.func)
        if parts is not None:
            self._check_host_sync(node, parts)
            msg = _nondet_call(parts, self.mod, self.np_aliases)
            if msg is not None:
                self._emit("PICO-J002", node,
                           f"host nondeterminism under trace: {msg}")
        elif isinstance(node.func, ast.Attribute):
            self._check_method_sync(node, node.func)
        self.generic_visit(node)

    def _sync_arg_hit(self, a: ast.expr) -> bool:
        """Whether a host-sync call's argument is a traced value.  When
        the argument is itself a call, only that call's own result type
        counts — ``bool(typeof_vma(lax.axis_index(...)))`` coerces the
        (static) helper result, not the tracer buried inside it."""
        if isinstance(a, ast.Call):
            return _call_is_array(a, self.mod)
        return self._expr_tainted(a) or self._expr_derived(a)

    def _check_host_sync(self, node: ast.Call, parts: list) -> None:
        name = parts[-1]
        arg_hit = any(self._sync_arg_hit(a) for a in node.args)
        if len(parts) == 1 and name in HOST_SYNC_CASTS and arg_hit:
            self._emit("PICO-J001", node,
                       f"{name}() on a traced value forces a host sync "
                       f"(ConcretizationTypeError under jit)")
        elif len(parts) >= 2 and parts[0] in self.np_aliases \
                and name in ("asarray", "array", "copy") and arg_hit:
            self._emit("PICO-J001", node,
                       f"np.{name}() on a traced value forces a host sync")
        elif len(parts) >= 2 and parts[-2] == "jax" \
                and name == "device_get" and (arg_hit or node.args):
            self._emit("PICO-J001", node,
                       "jax.device_get inside traced code is a host sync")
        elif name in HOST_SYNC_METHODS and len(parts) >= 2:
            recv = {parts[0]}
            if recv & (self.tainted | self.derived):
                self._emit("PICO-J001", node,
                           f".{name}() on a traced value is a host sync")

    def _check_method_sync(self, node: ast.Call, func: ast.Attribute) -> None:
        if func.attr in HOST_SYNC_METHODS and self._expr_tainted(func.value):
            self._emit("PICO-J001", node,
                       f".{func.attr}() on a traced value is a host sync")

    def _check_bool_coercion(self, test: ast.expr, node: ast.AST,
                             kind: str) -> None:
        # identity tests are static under trace (`if cache is not None:`
        # is how optional-arg plumbing looks inside every jitted program)
        if isinstance(test, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
            return
        # only definitely-array values: `if cfg.use_flash:` on a static
        # Python config must not fire, `if jnp.any(bad):` must
        if _names_in(test) & self.derived or _is_array_call(test, self.mod):
            self._emit("PICO-J001", node,
                       f"bool coercion of a traced value in `{kind}` "
                       f"(data-dependent Python control flow under trace)")

    def visit_If(self, node: ast.If) -> None:
        self.visit(node.test)
        self._check_bool_coercion(node.test, node, "if")
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_While(self, node: ast.While) -> None:
        self.visit(node.test)
        self._check_bool_coercion(node.test, node, "while")
        for stmt in node.body + node.orelse:
            self.visit(stmt)


# --------------------------------------------------------------------------- #
# J003: program_id inside loop bodies; J004: jit built in a loop
# --------------------------------------------------------------------------- #


def _loop_body_functions(mod: ModuleInfo) -> list:
    """(body FuncInfo | Lambda, wrapper name) for every function passed as
    a fori_loop/while_loop/scan body in ``mod``."""
    out: list = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        parts = dotted_name(node.func)
        if not parts or parts[-1] not in ("fori_loop", "while_loop", "scan"):
            continue
        for arg in _func_args_of_call(node, parts):
            arg = _unwrap_partial(arg)
            if isinstance(arg, ast.Lambda):
                out.append((arg, parts[-1]))
            elif isinstance(arg, ast.Name):
                qual = enclosing_qualname(mod, node)
                prefix = [] if qual == "<module>" else qual.split(".")
                while True:
                    fi = mod.functions.get(".".join(
                        prefix + ["<locals>", arg.id]) if prefix
                        else arg.id)
                    if fi is None and prefix:
                        fi = mod.functions.get(
                            ".".join(prefix[:-1] + [arg.id]))
                    if fi is not None or not prefix:
                        break
                    prefix = prefix[:-2] if prefix[-1] == "<locals>" \
                        else prefix[:-1]
                if fi is not None:
                    out.append((fi.node, parts[-1]))
    return out


def _check_program_id(project: Project, mod: ModuleInfo,
                      findings: list) -> None:
    for body, wrapper in _loop_body_functions(mod):
        for node in ast.walk(body):
            if not isinstance(node, ast.Call):
                continue
            parts = dotted_name(node.func)
            if parts and parts[-1] == "program_id":
                findings.append(Finding(
                    rule="PICO-J003", path=mod.rel, line=node.lineno,
                    context=enclosing_qualname(mod, node),
                    snippet=mod.snippet(node.lineno),
                    message=f"pl.program_id read inside a {wrapper} body: "
                            f"the Pallas interpreter cannot lower "
                            f"it in the sub-jaxpr — read grid ids once, "
                            f"before the loop (docs/ANALYSIS.md#pico-j003)"))


def _check_jit_in_loop(mod: ModuleInfo, findings: list) -> None:
    RECOMPILERS = {("jax", "jit"), ("jax", "pmap")}

    def scan(node: ast.AST, in_loop: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            for child in ast.iter_child_nodes(node):
                scan(child, False)  # a def inside a loop runs per CALL
            return
        if in_loop and isinstance(node, ast.Call):
            parts = dotted_name(node.func)
            hit = parts and (
                (len(parts) >= 2 and (parts[-2], parts[-1])
                 in RECOMPILERS)
                or parts[-1] == "pallas_call")
            if hit:
                findings.append(Finding(
                    rule="PICO-J004", path=mod.rel, line=node.lineno,
                    context=enclosing_qualname(mod, node),
                    snippet=mod.snippet(node.lineno),
                    message=f"{'.'.join(parts)}(...) inside a loop "
                            f"builds a fresh callable per iteration — "
                            f"every call recompiles; hoist and cache "
                            f"it outside the loop"))
        if isinstance(node, (ast.For, ast.AsyncFor)):
            # the iterator expression runs ONCE at loop setup; only the
            # body repeats (and for-else runs once, after)
            scan(node.iter, in_loop)
            scan(node.target, in_loop)
            for stmt in node.body:
                scan(stmt, True)
            for stmt in node.orelse:
                scan(stmt, in_loop)
            return
        if isinstance(node, ast.While):
            scan(node.test, True)  # the test re-evaluates every pass
            for stmt in node.body:
                scan(stmt, True)
            for stmt in node.orelse:
                scan(stmt, in_loop)
            return
        for child in ast.iter_child_nodes(node):
            scan(child, in_loop)

    scan(mod.tree, False)


# --------------------------------------------------------------------------- #
# J005: make_async_copy started without a reachable wait
# --------------------------------------------------------------------------- #


def _outermost_functions(tree: ast.AST) -> list:
    """Module-level functions and class methods, NOT nested defs — a DMA
    kernel's start/wait pairing is analyzed over the whole outermost
    function (helper closures included), so the double-buffer idiom of a
    ``_start`` helper next to a ``_wait`` helper reads as paired."""
    funcs: list = []

    def walk(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.append(child)
            else:
                walk(child)

    walk(tree)
    return funcs


def _dma_starts_waits(root: ast.AST) -> tuple:
    """``(start_calls, wait_calls)`` on make_async_copy values inside one
    subtree: ``.start()``/``.wait()`` chained directly onto a
    ``make_async_copy(...)`` call, or on a name the subtree binds to one.
    Receiver-typed on purpose — ``thread.start()`` / ``event.wait()`` /
    helper-returned descriptors never match (precision over recall, the
    empty-baseline contract)."""
    names: set = set()
    for node in ast.walk(root):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            parts = dotted_name(node.value.func)
            if parts and parts[-1] == "make_async_copy":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    starts: list = []
    waits: list = []
    for node in ast.walk(root):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("start", "wait")):
            continue
        recv = node.func.value
        hit = isinstance(recv, ast.Name) and recv.id in names
        if not hit and isinstance(recv, ast.Call):
            parts = dotted_name(recv.func)
            hit = bool(parts) and parts[-1] == "make_async_copy"
        if hit:
            (starts if node.func.attr == "start" else waits).append(node)
    return starts, waits


def _check_dma_waits(mod: ModuleInfo, findings: list) -> None:
    """PICO-J005, two layers:

    (a) an outermost function whose subtree starts DMAs but never waits
        on any — the copy is still in flight when its buffer is read;
    (b) a ``fori_loop``/``while_loop``/``scan`` body that starts DMAs
        per iteration while every wait sits OUTSIDE the loop path — N
        starts against the wait discipline of 1, the semaphore-imbalance
        hazard double buffering introduces (a warm-up start outside the
        loop with the waits inside is the CORRECT pipelined shape and
        stays silent).
    """
    flagged: set = set()

    def emit(node: ast.AST, detail: str) -> None:
        if id(node) in flagged:
            return
        flagged.add(id(node))
        findings.append(Finding(
            rule="PICO-J005", path=mod.rel, line=node.lineno,
            context=enclosing_qualname(mod, node),
            snippet=mod.snippet(node.lineno),
            message=f"make_async_copy started {detail} — pair every "
                    f"start with a wait built from the same (src, dst, "
                    f"sem) triple on the same control path "
                    f"(docs/ANALYSIS.md#pico-j005)"))

    for fn in _outermost_functions(mod.tree):
        starts, waits = _dma_starts_waits(fn)
        if starts and not waits:
            for s in starts:
                emit(s, "with no .wait() anywhere in the enclosing "
                        "function: the DMA may still be in flight when "
                        "its destination buffer is read")
    for body, wrapper in _loop_body_functions(mod):
        bstarts, bwaits = _dma_starts_waits(body)
        if bstarts and not bwaits:
            for s in bstarts:
                emit(s, f"inside a {wrapper} body whose every .wait() "
                        f"sits outside the loop: one wait cannot "
                        f"discharge N per-iteration starts")


_PROGRAM_ATTR_SUFFIXES = ("_jit", "_prog")


def _is_program_call(call: ast.Call) -> bool:
    """``self._<family>_jit(params, ...)`` / ``self._<family>_prog(
    params, ...)`` — a compiled MODEL program dispatch.  The ``params``
    first operand is the discriminator: housekeeping programs
    (``_set_length_jit``, ``_release_jit``, ...) take the cache (or
    nothing) first and may run outside the fault wrapper.  ``_make_*``
    builders construct programs rather than dispatch them."""
    f = call.func
    if not (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id == "self"):
        return False
    name = f.attr
    if not name.startswith("_") or name.startswith("_make_"):
        return False
    if not name.endswith(_PROGRAM_ATTR_SUFFIXES):
        return False
    if not call.args:
        return False
    first = call.args[0]
    return (isinstance(first, ast.Name)
            and (first.id == "params" or first.id.endswith("_params")))


def _check_dispatch_routing(mod: ModuleInfo, findings: list) -> None:
    """PICO-J006: in a class that defines ``_dispatch`` (the retry /
    flash-fallback fault wrapper), every compiled model-program call
    (``self._*_jit(params, ...)``) must occur inside an argument of a
    ``self._dispatch(...)`` call — usually ``self._dispatch(lambda:
    self._x_jit(params, ...))``.  A direct call opts that program family
    out of the engine's fault semantics; nothing else re-dispatches it
    after a flash->dense rebuild."""
    for cls in ast.walk(mod.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = [n for n in cls.body if isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        if not any(m.name == "_dispatch" for m in methods):
            continue
        routed: set = set()
        for node in ast.walk(cls):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "_dispatch"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"):
                for arg in list(node.args) + [k.value for k in
                                              node.keywords]:
                    routed.update(id(n) for n in ast.walk(arg))
        for m in methods:
            if m.name == "_dispatch":
                continue  # the wrapper itself runs the routed callable
            for node in ast.walk(m):
                if (isinstance(node, ast.Call) and _is_program_call(node)
                        and id(node) not in routed):
                    findings.append(Finding(
                        rule="PICO-J006", path=mod.rel, line=node.lineno,
                        context=enclosing_qualname(mod, node),
                        snippet=mod.snippet(node.lineno),
                        message=f"compiled model program "
                                f"self.{node.func.attr}(params, ...) "
                                f"called outside self._dispatch — wrap "
                                f"it as self._dispatch(lambda: ...) so "
                                f"the family inherits retry/fallback "
                                f"fault semantics "
                                f"(docs/ANALYSIS.md#pico-j006)"))


# --------------------------------------------------------------------------- #
# driver
# --------------------------------------------------------------------------- #


def analyze(project: Project) -> list:
    findings: list = []
    traced, direct = traced_functions(project)
    analyzed: set = set()
    for modname, qual in sorted(traced):
        mod = project.modules[modname]
        fi = mod.functions.get(qual)
        if fi is None:
            continue
        # nested defs are visited by their parent's checker; don't run a
        # second, parent-less pass over them
        parent = qual.split(".<locals>.")[0]
        if parent != qual and (modname, parent) in traced:
            continue
        if (modname, qual) in analyzed:
            continue
        analyzed.add((modname, qual))
        _TracedFuncChecker(fi, findings,
                           is_entry=(modname, qual) in direct).run()
    for mod in project.modules.values():
        _check_program_id(project, mod, findings)
        _check_jit_in_loop(mod, findings)
        _check_dma_waits(mod, findings)
        _check_dispatch_routing(mod, findings)
    return findings
