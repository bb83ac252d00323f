"""GM1xx — JAX tracing safety.

Finds host impurity and recompile hazards inside functions that run
under a trace: anything wrapped by ``jax.jit`` / ``shard_map`` /
``pl.pallas_call``, anything returned by a builder passed to the
engine's ``get_kernel``/``schedule_kernel`` kernel cache (the project's
jit funnel — every solver kernel reaches XLA through it), and anything
those functions call in the same module (taint-propagated through
direct calls, callbacks like ``jax.lax.scan`` bodies, and lambdas).

Within a traced function its parameters are *traced values* (minus
declared static args); locals derived from them are traced too, except
through the static accessors (``.shape``/``.dtype``/``.ndim``/
``.size``, ``len()``) which produce Python values at trace time.

| id | finding |
|---|---|
| GM101 | host clock call (``time.time``/``perf_counter``/...) under trace |
| GM102 | Python/numpy RNG call under trace (untraced randomness) |
| GM103 | host sync of a traced value (``int()``/``float()``/``bool()``/``.item()``/``.tolist()``) |
| GM104 | Python control flow on a traced value (``if``/``while``/``assert``/iteration) |
| GM105 | ``np.*`` host call applied to a traced value |
| GM106 | static arg with a non-hashable (list/dict/set) default — recompile/TypeError hazard |

The analysis is intra-module and name-based: it never imports the code
under test, so it is safe to run on kernel code whose import would grab
an accelerator.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from gamesmanmpi_tpu.analysis.diagnostics import Diagnostic
from gamesmanmpi_tpu.analysis.project import (
    Project,
    SourceFile,
    attr_chain,
    call_name,
)

#: Attribute reads that yield *static* (trace-time Python) values.
SANITIZER_ATTRS = {
    "shape", "dtype", "ndim", "size", "itemsize", "nbytes", "aval",
    "sharding", "weak_type",
}

#: builtins that force a concrete value out of a tracer.
HOST_CASTS = {"int", "float", "bool", "complex"}
HOST_SYNC_METHODS = {"item", "tolist", "__index__"}

CLOCK_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.time_ns", "time.sleep",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}

#: Wrappers whose first argument runs under a trace.
_JIT_NAMES = {"jit"}
_TRACE_WRAPPERS = {"shard_map", "pallas_call", "checkpoint", "remat",
                   "vmap", "pmap", "grad"}
#: The project's kernel-cache funnel: builder(game) returns the function
#: that gets jitted (solve/engine.get_kernel / schedule_kernel).
_BUILDER_FUNNELS = {"get_kernel", "schedule_kernel"}

#: Host-callback funnels: a function passed into these from traced code
#: runs on the HOST with concrete numpy arrays, not tracers — its numpy
#: calls, branches and host syncs are the whole point (compat/shim's
#: scalar-game lifts). Without this exemption the callback rule below
#: would re-enqueue those bodies as traced and flag every np.* call in
#: them (GM105 false positives).
_HOST_CALLBACK_FUNNELS = {"pure_callback", "io_callback", "debug_callback"}

#: Per-module cap on (function, taint-set) walks — a loop breaker, set
#: far above what any real module needs.
_MAX_WALKS = 4000


def _last(name: str) -> str:
    return name.rsplit(".", 1)[-1]


class _Scopes(ast.NodeVisitor):
    """function node -> {local def name: node}, plus parent links for
    lexical resolution and a module-level table."""

    def __init__(self, tree: ast.AST):
        self.locals: Dict[ast.AST, Dict[str, ast.AST]] = {tree: {}}
        self.parent: Dict[ast.AST, ast.AST] = {}
        self._stack: List[ast.AST] = [tree]
        self.visit(tree)

    def _handle_def(self, node):
        self.locals[self._stack[-1]][node.name] = node
        self.parent[node] = self._stack[-1]
        self.locals[node] = {}
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _handle_def
    visit_AsyncFunctionDef = _handle_def

    def visit_ClassDef(self, node):
        # Methods resolve through the class body scope; treat the class
        # as a scope node so nested helpers stay findable.
        self.parent[node] = self._stack[-1]
        self.locals[node] = {}
        self._stack.append(node)
        self.generic_visit(node)
        self._stack.pop()

    def resolve(self, scope: ast.AST, name: str) -> Optional[ast.AST]:
        node: Optional[ast.AST] = scope
        while node is not None:
            fn = self.locals.get(node, {}).get(name)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return fn
            node = self.parent.get(node)
        return None


def _numpy_aliases(tree: ast.AST) -> Tuple[Set[str], Set[str]]:
    """(numpy module aliases, python-random module aliases)."""
    np_alias, rng_alias = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    np_alias.add(a.asname or "numpy")
                elif a.name == "random":
                    rng_alias.add(a.asname or "random")
                elif a.name == "numpy.random":
                    rng_alias.add(a.asname or "numpy")
    return np_alias, rng_alias


def _param_names(fn) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _static_params(fn, keywords) -> Set[str]:
    """Params excluded from tracing by static_argnums/static_argnames."""
    a = fn.args
    positional = [p.arg for p in a.posonlyargs + a.args]
    out: Set[str] = set()
    for kw in keywords:
        if kw.arg == "static_argnums":
            for v in _const_list(kw.value):
                if isinstance(v, int) and 0 <= v < len(positional):
                    out.add(positional[v])
        elif kw.arg == "static_argnames":
            for v in _const_list(kw.value):
                if isinstance(v, str):
                    out.add(v)
    return out


def _const_list(node) -> list:
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [
            e.value for e in node.elts if isinstance(e, ast.Constant)
        ]
    return []


def _mutable_default_params(fn) -> Dict[str, int]:
    """{param name: default's line} for list/dict/set-literal defaults."""
    a = fn.args
    out: Dict[str, int] = {}
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if isinstance(d, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)):
            out[p.arg] = d.lineno
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None and isinstance(
            d, (ast.List, ast.Dict, ast.Set)
        ):
            out[p.arg] = d.lineno
    return out


class _ModuleChecker:
    def __init__(self, src: SourceFile):
        self.src = src
        self.tree = src.tree
        self.scopes = _Scopes(self.tree)
        self.np_aliases, self.rng_aliases = _numpy_aliases(self.tree)
        self.diags: List[Diagnostic] = []
        self._seen_diag: Set[Tuple[str, int, str]] = set()
        self._queue: List[Tuple[ast.AST, FrozenSet[str]]] = []
        self._visited: Set[Tuple[int, FrozenSet[str]]] = set()
        self._walks = 0

    # ------------------------------------------------------------- reporting

    def report(self, id_: str, node: ast.AST, msg: str) -> None:
        key = (id_, node.lineno, msg)
        if key not in self._seen_diag:
            self._seen_diag.add(key)
            self.diags.append(
                Diagnostic(self.src.rel, node.lineno, id_, msg)
            )

    # ---------------------------------------------------------------- roots

    def find_roots(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._roots_from_decorators(node)
            elif isinstance(node, ast.Call):
                self._roots_from_call(node)

    def _jit_wrapper_kind(self, func_expr) -> Optional[str]:
        """'jit'/'wrapper' when ``func_expr`` is a tracing wrapper
        (possibly through functools.partial(jax.jit, ...))."""
        chain = attr_chain(func_expr)
        if chain:
            last = chain[-1]
            if last in _JIT_NAMES:
                return "jit"
            if last in _TRACE_WRAPPERS:
                return "wrapper"
        if isinstance(func_expr, ast.Call):
            inner = call_name(func_expr)
            if _last(inner) == "partial" and func_expr.args:
                return self._jit_wrapper_kind(func_expr.args[0])
        return None

    def _roots_from_decorators(self, fn) -> None:
        for dec in fn.decorator_list:
            if isinstance(dec, ast.Call):
                kind = self._jit_wrapper_kind(dec.func)
                if kind is None and self._jit_wrapper_kind(dec):
                    # @partial(jax.jit, static_argnums=...) arrives here
                    # as a Call whose func is partial.
                    kind = "jit"
                keywords = dec.keywords
            else:
                kind = self._jit_wrapper_kind(dec)
                keywords = []
            if kind is not None:
                self._enqueue_root(fn, keywords)

    def _roots_from_call(self, call: ast.Call) -> None:
        name = _last(call_name(call))
        scope = self._enclosing_scope(call)
        if name in _JIT_NAMES or name in _TRACE_WRAPPERS:
            if call.args:
                fn = self._resolve_arg(scope, call.args[0])
                if fn is not None:
                    self._enqueue_root(fn, call.keywords)
        elif name in _BUILDER_FUNNELS:
            builder_expr = None
            if len(call.args) >= 4:
                builder_expr = call.args[3]
            for kw in call.keywords:
                if kw.arg == "builder":
                    builder_expr = kw.value
            builder = self._resolve_arg(scope, builder_expr)
            if builder is not None:
                # The builder itself runs on host with static args (the
                # game); every function defined inside it is the traced
                # kernel it returns.
                for sub in self.scopes.locals.get(builder, {}).values():
                    if isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        self._enqueue_root(sub, [])

    def _resolve_arg(self, scope, expr) -> Optional[ast.AST]:
        if isinstance(expr, ast.Name):
            return self.scopes.resolve(scope, expr.id)
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
        ):
            # Method builders (`self._fwdp_builder` handed to get_kernel):
            # one name means one method across the module's classes — the
            # repo convention the whole lock checker also leans on.
            for owner, members in self.scopes.locals.items():
                if isinstance(owner, ast.ClassDef):
                    fn = members.get(expr.attr)
                    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        return fn
        return None

    def _enclosing_scope(self, node) -> ast.AST:
        # Cheap but exact: find the innermost function whose span holds
        # the node's position.
        best = self.tree
        for fn, _ in self.scopes.locals.items():
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if (
                    fn.lineno <= node.lineno
                    and node.lineno <= (fn.end_lineno or fn.lineno)
                ):
                    if (
                        best is self.tree
                        or fn.lineno >= best.lineno
                    ):
                        best = fn
        return best

    def _enqueue_root(self, fn, jit_keywords) -> None:
        static = _static_params(fn, jit_keywords)
        mutable = _mutable_default_params(fn)
        for p in sorted(static & set(mutable)):
            self.report(
                "GM106", fn,
                f"static arg {p!r} of {fn.name!r} has a non-hashable "
                "(list/dict/set) default — every call re-hashes it and "
                "fails or recompiles",
            )
        tainted = frozenset(set(_param_names(fn)) - static)
        self.enqueue(fn, tainted)

    # -------------------------------------------------------------- worklist

    def enqueue(self, fn, tainted: FrozenSet[str]) -> None:
        key = (id(fn), tainted)
        if key not in self._visited and self._walks < _MAX_WALKS:
            self._visited.add(key)
            self._walks += 1
            self._queue.append((fn, tainted))

    def run(self) -> List[Diagnostic]:
        if self.tree is None:
            return []
        self.find_roots()
        while self._queue:
            fn, tainted = self._queue.pop()
            _TaintWalker(self, fn, set(tainted)).walk()
        return self.diags


class _TaintWalker:
    """One traced function body: propagate taint, report impurity."""

    def __init__(self, mod: _ModuleChecker, fn, tainted: Set[str]):
        self.mod = mod
        self.fn = fn
        self.env = tainted

    def walk(self) -> None:
        for stmt in self.fn.body:
            self.stmt(stmt)

    # ------------------------------------------------------------ statements

    def stmt(self, node) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # walked when reached via a call/callback
        if isinstance(node, ast.Assign):
            t = self.tainted(node.value)
            for target in node.targets:
                self.assign(target, t)
        elif isinstance(node, ast.AnnAssign):
            if node.value is not None:
                self.assign(node.target, self.tainted(node.value))
        elif isinstance(node, ast.AugAssign):
            t = self.tainted(node.value) or self.tainted(node.target)
            self.assign(node.target, t)
        elif isinstance(node, (ast.If, ast.While)):
            if self.tainted(node.test):
                self.mod.report(
                    "GM104", node,
                    "Python branch on a traced value — under jit this "
                    "raises TracerBoolConversionError or bakes in one "
                    "path; use jnp.where/lax.cond",
                )
            for s in node.body + node.orelse:
                self.stmt(s)
        elif isinstance(node, ast.Assert):
            if self.tainted(node.test):
                self.mod.report(
                    "GM104", node,
                    "assert on a traced value — hosts a bool() sync; "
                    "use checkify or debug_assert",
                )
        elif isinstance(node, ast.For):
            if self.tainted(node.iter):
                self.mod.report(
                    "GM104", node,
                    "Python iteration over a traced value — unrolls or "
                    "fails under jit; use lax.scan/fori_loop",
                )
                self.assign(node.target, True)
            else:
                self.tainted(node.iter)
                self.assign(node.target, False)
            for s in node.body + node.orelse:
                self.stmt(s)
        elif isinstance(node, ast.With):
            for item in node.items:
                self.tainted(item.context_expr)
                if item.optional_vars is not None:
                    self.assign(item.optional_vars, False)
            for s in node.body:
                self.stmt(s)
        elif isinstance(node, ast.Try):
            for s in (
                node.body
                + [h_s for h in node.handlers for h_s in h.body]
                + node.orelse
                + node.finalbody
            ):
                self.stmt(s)
        elif isinstance(node, (ast.Return, ast.Expr)):
            if node.value is not None:
                self.tainted(node.value)
        elif isinstance(node, (ast.Raise,)):
            if node.exc is not None:
                self.tainted(node.exc)
        elif isinstance(node, ast.Delete):
            pass
        else:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.expr):
                    self.tainted(child)
                elif isinstance(child, ast.stmt):
                    self.stmt(child)

    def assign(self, target, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.env.add(target.id)
            else:
                self.env.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self.assign(e, tainted)
        elif isinstance(target, ast.Starred):
            self.assign(target.value, tainted)
        elif isinstance(target, (ast.Subscript, ast.Attribute)):
            self.tainted(target.value)

    # ----------------------------------------------------------- expressions

    def tainted(self, node) -> bool:
        """Evaluate an expression: report findings, return taintedness."""
        if node is None or isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self.env
        if isinstance(node, ast.Attribute):
            base = self.tainted(node.value)
            if node.attr in SANITIZER_ATTRS:
                return False
            return base
        if isinstance(node, ast.Call):
            return self.call(node)
        if isinstance(node, ast.Subscript):
            return self.tainted(node.value) or self.tainted(node.slice)
        if isinstance(node, (ast.BinOp,)):
            left = self.tainted(node.left)
            return self.tainted(node.right) or left
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any([self.tainted(v) for v in node.values])
        if isinstance(node, ast.Compare):
            t = self.tainted(node.left)
            for c in node.comparators:
                t = self.tainted(c) or t
            return t
        if isinstance(node, ast.IfExp):
            if self.tainted(node.test):
                self.mod.report(
                    "GM104", node,
                    "conditional expression on a traced value — use "
                    "jnp.where/lax.select",
                )
            a = self.tainted(node.body)
            return self.tainted(node.orelse) or a
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any([self.tainted(e) for e in node.elts])
        if isinstance(node, ast.Dict):
            t = any([self.tainted(k) for k in node.keys if k is not None])
            return any([self.tainted(v) for v in node.values]) or t
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        if isinstance(node, ast.Slice):
            return any(
                self.tainted(p)
                for p in (node.lower, node.upper, node.step)
                if p is not None
            )
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                if isinstance(v, ast.FormattedValue):
                    self.tainted(v.value)
            return False
        if isinstance(
            node, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)
        ):
            return self.comprehension(node)
        if isinstance(node, ast.Lambda):
            return False  # walked where it's passed as a callback
        if isinstance(node, ast.NamedExpr):
            t = self.tainted(node.value)
            self.assign(node.target, t)
            return t
        if isinstance(node, ast.Await):
            return self.tainted(node.value)
        return False

    def comprehension(self, node) -> bool:
        child = _TaintWalker(self.mod, self.fn, set(self.env))
        t = False
        for gen in node.generators:
            it = child.tainted(gen.iter)
            if it:
                self.mod.report(
                    "GM104", node,
                    "comprehension over a traced value — Python "
                    "iteration under jit",
                )
            child.assign(gen.target, it)
            t = t or it
            for cond in gen.ifs:
                child.tainted(cond)
        if isinstance(node, ast.DictComp):
            t = child.tainted(node.key) or t
            t = child.tainted(node.value) or t
        else:
            t = child.tainted(node.elt) or t
        return t

    # ----------------------------------------------------------------- calls

    def call(self, node: ast.Call) -> bool:
        name = call_name(node)
        last = _last(name)
        chain = attr_chain(node.func) or []
        arg_taints = [self.tainted(a) for a in node.args]
        kw_taints = {
            kw.arg: self.tainted(kw.value) for kw in node.keywords
        }
        any_tainted = any(arg_taints) or any(kw_taints.values())

        # --- impurity findings -------------------------------------------
        if name in CLOCK_CALLS or (
            chain[:1] == ["time"] and len(chain) == 2
        ):
            self.mod.report(
                "GM101", node,
                f"host clock call {name}() inside traced code — the "
                "value freezes at trace time (and differs per recompile)",
            )
            return False
        if chain and (
            chain[0] in self.mod.rng_aliases
            and (len(chain) == 2 or chain[1:2] == ["random"])
            or (chain[0] in self.mod.np_aliases and chain[1:2] == ["random"])
        ):
            self.mod.report(
                "GM102", node,
                f"untraced RNG call {name}() inside traced code — "
                "freezes at trace time; thread a jax.random key instead",
            )
            return False
        if last in HOST_CASTS and len(chain) == 1 and any_tainted:
            self.mod.report(
                "GM103", node,
                f"{last}() applied to a traced value — forces a host "
                "sync / ConcretizationTypeError under jit",
            )
            return False
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in HOST_SYNC_METHODS
            and self.tainted(node.func.value)
        ):
            self.mod.report(
                "GM103", node,
                f".{node.func.attr}() on a traced value — forces a "
                "host sync under jit",
            )
            return False
        if (
            chain
            and chain[0] in self.mod.np_aliases
            and len(chain) > 1
            and any_tainted
        ):
            self.mod.report(
                "GM105", node,
                f"numpy host call {name}() on a traced value — "
                "silently syncs (or fails) under jit; use jnp",
            )
            return True
        if last == "len" and len(chain) == 1:
            return False

        # --- propagation into local functions ----------------------------
        scope = self.fn
        is_funnel = last in _BUILDER_FUNNELS \
            or last in _HOST_CALLBACK_FUNNELS
        if isinstance(node.func, ast.Name):
            target = self.mod.scopes.resolve(scope, node.func.id)
            if target is not None:
                params = _param_names(target)
                tainted_params = set()
                offset = 1 if params[:1] == ["self"] else 0
                for i, t in enumerate(arg_taints):
                    if t and i + offset < len(params):
                        tainted_params.add(params[i + offset])
                for k, t in kw_taints.items():
                    if t and k in params:
                        tainted_params.add(k)
                self.mod.enqueue(target, frozenset(tainted_params))
        if not is_funnel:
            # Callback rule: a local function passed BY NAME into any
            # call inside traced code will be invoked with traced
            # operands (scan/while/cond bodies, custom combinators).
            for a in node.args:
                if isinstance(a, ast.Name) and a is not node.func:
                    cb = self.mod.scopes.resolve(scope, a.id)
                    if cb is not None:
                        self.mod.enqueue(
                            cb, frozenset(_param_names(cb))
                        )
                elif isinstance(a, ast.Lambda):
                    child = _TaintWalker(self.mod, self.fn, set(self.env))
                    for p in _param_names(a):
                        child.env.add(p)
                    child.tainted(a.body)

        # Taint of the call's result: conservative — tainted operands
        # (or a method on a tainted object) yield a tainted result.
        recv_tainted = isinstance(
            node.func, ast.Attribute
        ) and self.tainted(node.func.value)
        return any_tainted or recv_tainted


def check(project: Project) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for src in project.files:
        if src.tree is not None:
            diags.extend(_ModuleChecker(src).run())
    return diags
