"""Trace-safety linter: host-side sins inside (or around) jitted code.

JAX tracing is our codegen layer (the reference's sql/gen/ bytecode
discipline): a traced function must be a pure shape-polymorphic program.
Four rule families, each a silent-wrongness class no unit test catches
until the shapes change:

- ``tracer-branch`` — host control flow on a traced value inside a
  jitted function (``if``/``while`` on a function arg, ``bool()``/
  ``int()``/``float()``/``.item()`` of one). Under trace these either
  throw ConcretizationTypeError at sf=10's first novel shape bucket or,
  worse, bake one batch's data into the executable.
- ``raw-jit`` — a ``jax.jit``/``pjit`` call site that is not wrapped in
  an ``ops/jitcache._TimedEntry``. Raw entries are invisible to the
  PR 6 profiler (no compile seconds, no device-time attribution, absent
  from system.runtime.executables), their recompiles are uncapped and
  unobservable, and XLA, the device trace and the compile cache know
  them as ``jit_run`` or ``jit__lambda_``. The engine jits through
  ``ops/jitcache.timed_entry``/``named_jit``, which name the program.
- ``nondeterminism`` — ``time.*`` / ``random.*`` / ``np.random*``
  calls inside a traced body: they run ONCE at trace time and freeze
  their value into the executable, so "random" is constant per shape
  bucket and replays differ from first runs.
- ``unbracketed-sync`` — ``jax.device_get`` / ``.block_until_ready``
  in engine code: a read goes through ``obs/trace.device_sync(what,
  value)``, which makes both calls itself inside the ``device-sync``
  span and its counters; a ``TRACER.span("device-sync")`` or profiler
  scope is accepted too.
  Async dispatch makes an unbracketed sync a stall nobody can see in
  the trace viewer; the engine's rule since PR 1 is that every
  deliberate device round-trip is a span.
- ``param-bound-read`` — reading ``ir.Param.bound`` (or calling
  ``expr/params.consult``) inside a jitted body. ``.bound`` is the
  BUILD-time literal the template was planned against; under trace it
  bakes that one binding's value into the shared executable, so every
  later binding silently reuses it (the exact staleness the
  parameter-generic plan cache exists to avoid). Dispatch-scope reads
  are the trace-safe channel: ``params.traced_val``/``current_args``
  deliver the LIVE binding as a traced operand — their results are
  tainted like any traced value, so host-branching on them still trips
  ``tracer-branch``; ``consult`` is planner-only (it records template
  reuse guards and must never run under trace).

Taint model (deliberately intraprocedural): the parameters of a jitted
function are traced; names assigned from traced expressions become
traced; structure/shape reads (``is None``, ``len``, ``.shape``,
``.dtype``, ``.ndim``, ``isinstance``) are static under jit and do not
propagate taint. Functions reached only by call from a jitted body are
NOT walked — that keeps false positives near zero at the cost of
missing deep flows, which is the right trade for a gate that must stay
green on every commit.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set

from .base import (Finding, add_parents, ancestors, dotted,
                   enclosing_symbol, parse_file, rel, str_const, walk_py)

CHECKER = "tracing"

#: scope of the walk (ISSUE 7 tentpole) — the traced/offload seams;
#: exec/local.py rides along because its unnest kernel was this
#: checker's first raw-jit catch and the line must hold
SCOPE = ("presto_tpu/ops", "presto_tpu/parallel",
         "presto_tpu/exec/fused.py", "presto_tpu/exec/distributed.py",
         "presto_tpu/exec/local.py", "presto_tpu/exec/local_exchange.py")

#: the one module allowed to call jax.jit directly: it IS the cache
RAW_JIT_ALLOWED_FILES = ("presto_tpu/ops/jitcache.py",)

#: attribute reads that are static under jit (structure, not value)
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "aval", "sharding",
                 "weak_type", "columns", "schema", "types", "names"}

#: cast calls that concretize a tracer
_CONCRETIZING_CASTS = {"bool", "int", "float"}

#: nondeterministic call prefixes (host-evaluated at trace time)
_NONDET_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.")

#: expr/params.py dispatch-scope reads: their RESULT is a traced value
#: (the live binding as a jit operand), so taint flows through them
_PARAM_TRACED_CALLS = {"traced_val", "current_args"}


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted(node.func)
    return name in ("jax.jit", "pjit", "jax.pjit",
                    "jax.experimental.pjit.pjit")


def _is_named_jit_call(node: ast.Call) -> bool:
    """``named_jit(name, fn)`` / ``timed_entry(name, fn, ...)``
    (ops/jitcache): the engine's own way to a jitted program. Not a
    raw jit, but what it is handed IS traced."""
    name = dotted(node.func) or ""
    return name.split(".")[-1] in ("named_jit", "timed_entry")


def _is_partial_jit(node: ast.Call) -> bool:
    """functools.partial(jax.jit, ...) used as a decorator."""
    name = dotted(node.func)
    if name not in ("functools.partial", "partial"):
        return False
    return bool(node.args) and dotted(node.args[0]) == "jax.jit"


def _jit_static_names(call: ast.Call, fn: ast.FunctionDef) -> Set[str]:
    """Parameter names excluded from tracing by static_argnums/names."""
    out: Set[str] = set()
    params = [a.arg for a in fn.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            vals = (kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                s = str_const(v)
                if s:
                    out.add(s)
        elif kw.arg == "static_argnums":
            vals = (kw.value.elts
                    if isinstance(kw.value, (ast.Tuple, ast.List))
                    else [kw.value])
            for v in vals:
                if isinstance(v, ast.Constant) \
                        and isinstance(v.value, int) \
                        and 0 <= v.value < len(params):
                    out.add(params[v.value])
    return out


def _find_jitted_functions(tree: ast.Module
                           ) -> List[tuple]:
    """[(FunctionDef/Lambda, static_param_names)] for every function the
    module jits: @jax.jit / @functools.partial(jax.jit, ...) decorated
    defs, defs whose name is later passed to jax.jit(...), and lambdas
    appearing directly inside a jax.jit(...) call."""
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            defs.setdefault(node.name, node)

    out: List[tuple] = []
    seen: Set[int] = set()

    def add(fn, statics: Set[str]) -> None:
        if id(fn) not in seen:
            seen.add(id(fn))
            out.append((fn, statics))

    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if dotted(dec) == "jax.jit":
                    add(node, set())
                elif isinstance(dec, ast.Call) and (
                        _is_jit_call(dec) or _is_partial_jit(dec)):
                    add(node, _jit_static_names(dec, node))
        elif isinstance(node, ast.Call) and (
                _is_jit_call(node) or _is_named_jit_call(node)):
            for arg in node.args:
                if isinstance(arg, ast.Name) and arg.id in defs:
                    fn = defs[arg.id]
                    add(fn, _jit_static_names(node, fn))
                elif isinstance(arg, ast.Lambda):
                    add(arg, set())
    return out


class _TaintWalk:
    """Intraprocedural traced-value taint over one jitted body."""

    def __init__(self, fn, statics: Set[str]):
        self.fn = fn
        args = fn.args
        params = [a.arg for a in args.args + args.kwonlyargs]
        if args.vararg:
            params.append(args.vararg.arg)
        self.tainted: Set[str] = {p for p in params if p not in statics}

    # -- taint queries --------------------------------------------------------
    def _expr_tainted(self, node: ast.expr) -> bool:
        """Does evaluating ``node`` yield a traced VALUE (not just
        structure)? Static reads break the chain."""
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self._expr_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self._expr_tainted(node.value)
        if isinstance(node, (ast.BinOp,)):
            return (self._expr_tainted(node.left)
                    or self._expr_tainted(node.right))
        if isinstance(node, ast.UnaryOp):
            return self._expr_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self._expr_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a structure test (pytree
            # arity), static under jit — any other comparison of a
            # traced value is a traced bool
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in node.ops):
                return False
            return (self._expr_tainted(node.left)
                    or any(self._expr_tainted(c)
                           for c in node.comparators))
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in ("len", "isinstance", "type", "getattr",
                        "hasattr"):
                return False
            if name and name.split(".")[-1] in _PARAM_TRACED_CALLS:
                # params.traced_val/current_args deliver the live
                # binding as a traced operand regardless of arg taint
                return True
            # conservative: a call over traced args returns traced
            return any(self._expr_tainted(a) for a in node.args)
        if isinstance(node, (ast.Tuple, ast.List)):
            # a Python container OF tracers is not itself traced: its
            # truthiness/len is static structure. (Cost: taint doesn't
            # flow through tuple-pack/unpack — acceptable for a gate
            # that must stay green.)
            return False
        if isinstance(node, ast.IfExp):
            return (self._expr_tainted(node.test)
                    or self._expr_tainted(node.body)
                    or self._expr_tainted(node.orelse))
        if isinstance(node, ast.Starred):
            return self._expr_tainted(node.value)
        return False

    def _propagate(self, body: Sequence[ast.stmt]) -> None:
        """One forward pass seeding assigned names (loops in kernels are
        rare; a single pass plus the param seed is enough in practice)."""
        for node in ast.walk(ast.Module(body=list(body),
                                        type_ignores=[])):
            if isinstance(node, ast.Assign) \
                    and self._expr_tainted(node.value):
                for tgt in node.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            self.tainted.add(n.id)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)) \
                    and node.value is not None \
                    and self._expr_tainted(node.value) \
                    and isinstance(node.target, ast.Name):
                self.tainted.add(node.target.id)

    # -- rule application -----------------------------------------------------
    def findings(self, path: str, symbol: str) -> List[Finding]:
        body = (self.fn.body if isinstance(self.fn, ast.FunctionDef)
                else [ast.Expr(value=self.fn.body)])
        self._propagate(body)
        out: List[Finding] = []

        def emit(rule: str, node: ast.AST, msg: str,
                 token: str = "") -> None:
            out.append(Finding(
                CHECKER, rule, path, node.lineno,
                f"{symbol}.{token}" if token else symbol, msg))

        for node in ast.walk(ast.Module(body=list(body),
                                        type_ignores=[])):
            # NOTE: ident tokens carry no line numbers (the baseline
            # contract — see base.py): a suppression covers every
            # same-kind finding on the symbol, which is the right
            # granularity for accepted-by-design code
            if isinstance(node, (ast.If, ast.While)):
                if self._expr_tainted(node.test):
                    kw = "while" if isinstance(node, ast.While) else "if"
                    emit("tracer-branch", node,
                         f"host `{kw}` on a traced value inside jitted "
                         f"function {symbol!r} — use jnp.where/"
                         f"lax.cond, or hoist the decision out of the "
                         f"traced region", kw)
            elif isinstance(node, ast.Call):
                name = dotted(node.func)
                if name in _CONCRETIZING_CASTS and node.args \
                        and self._expr_tainted(node.args[0]):
                    emit("tracer-branch", node,
                         f"{name}() concretizes a traced value inside "
                         f"jitted function {symbol!r}", name)
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" \
                        and self._expr_tainted(node.func.value):
                    emit("tracer-branch", node,
                         f".item() concretizes a traced value inside "
                         f"jitted function {symbol!r}", "item")
                elif name and name.startswith(_NONDET_PREFIXES):
                    emit("nondeterminism", node,
                         f"{name}() inside jitted function {symbol!r} "
                         f"runs once at trace time and freezes into "
                         f"the executable", name)
                elif name and name.split(".")[-1] == "consult":
                    emit("param-bound-read", node,
                         f"params.consult() inside jitted function "
                         f"{symbol!r} — consult is planner-only (it "
                         f"records template reuse guards); kernels "
                         f"must take the binding as a traced operand "
                         f"via traced_val/current_args", "consult")
            elif isinstance(node, ast.Attribute) \
                    and node.attr == "bound" \
                    and isinstance(node.ctx, ast.Load) \
                    and not (isinstance(getattr(node, "parent", None),
                                        ast.Call)
                             and node.parent.func is node):
                # `.bound` VALUE read (a `.bound(...)` method call is
                # the params.bound binding scope, a different thing)
                emit("param-bound-read", node,
                     f".bound read inside jitted function {symbol!r} "
                     f"bakes the BUILD-time binding into the shared "
                     f"executable — every later binding of this "
                     f"template would silently reuse it; read the "
                     f"live value via traced_val/current_args",
                     "bound")
        return out


# -- raw-jit + unbracketed-sync (whole-file rules) ---------------------------

def _inside_timed_entry(node: ast.AST) -> bool:
    for anc in ancestors(node):
        if isinstance(anc, ast.Call):
            name = dotted(anc.func)
            if name and name.split(".")[-1] == "_TimedEntry":
                return True
    return False


def _inside_sync_span(node: ast.AST) -> bool:
    """Lexically under ``with TRACER.span("device-sync", ...)`` (the
    span ``obs/trace.device_sync`` opens around its own two calls), or
    any ``with`` whose context manager comes from the profiler
    (obs.profiler brackets its own syncs)."""
    for anc in ancestors(node):
        if not isinstance(anc, ast.With):
            continue
        for item in anc.items:
            ctx = item.context_expr
            if not isinstance(ctx, ast.Call):
                continue
            name = dotted(ctx.func) or ""
            if name.endswith(".span") and ctx.args:
                s = str_const(ctx.args[0])
                if s and s.startswith("device-sync"):
                    return True
            if "_prof" in name or "profiler" in name:
                return True
    return False


def _file_findings(path: str, rpath: str,
                   raw_jit_exempt: bool) -> List[Finding]:
    tree = parse_file(path)
    if tree is None:
        return [Finding(CHECKER, "parse-error", rpath, 1, "<module>",
                        "file does not parse")]
    add_parents(tree)
    out: List[Finding] = []

    # rule: raw-jit
    if not raw_jit_exempt:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                    _is_jit_call(node) or _is_partial_jit(node)):
                if _inside_timed_entry(node):
                    continue
                sym = enclosing_symbol(node)
                out.append(Finding(
                    CHECKER, "raw-jit", rpath, node.lineno, sym,
                    f"direct {dotted(node.func)} call bypasses "
                    f"ops/jitcache — build it with timed_entry (or an "
                    f"_entry_cache) so the program has a name and "
                    f"compiles/invocations/device time are profiled"))
            elif isinstance(node, ast.Attribute) \
                    and dotted(node) == "jax.jit" \
                    and isinstance(getattr(node, "parent", None),
                                   (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                # bare @jax.jit decorator (non-call form)
                sym = node.parent.name  # type: ignore[attr-defined]
                out.append(Finding(
                    CHECKER, "raw-jit", rpath, node.lineno, sym,
                    "bare @jax.jit decorator bypasses ops/jitcache — "
                    "build it with timed_entry"))

    # rule: unbracketed-sync
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func) or ""
        is_sync = (name in ("jax.device_get",)
                   or name.endswith(".block_until_ready"))
        if is_sync and not _inside_sync_span(node):
            sym = enclosing_symbol(node)
            what = ("jax.device_get" if name == "jax.device_get"
                    else "block_until_ready")
            out.append(Finding(
                CHECKER, "unbracketed-sync", rpath, node.lineno,
                f"{sym}.{what}",
                f"{what} outside a TRACER.span(\"device-sync\") "
                f"scope — deliberate device round-trips must be "
                f"observable stalls"))

    # rules: tracer-branch / nondeterminism (per jitted function)
    for fn, statics in _find_jitted_functions(tree):
        symbol = (fn.name if isinstance(fn, ast.FunctionDef)
                  else f"<lambda>:{fn.lineno}")
        out.extend(_TaintWalk(fn, statics).findings(rpath, symbol))
    return out


def check_paths(paths: Sequence[str], root: str,
                raw_jit_allowed: Sequence[str] = RAW_JIT_ALLOWED_FILES
                ) -> List[Finding]:
    out: List[Finding] = []
    for p in paths:
        rpath = rel(p, root)
        out.extend(_file_findings(p, rpath,
                                  raw_jit_exempt=rpath in raw_jit_allowed))
    return out


def check(root: str, scope: Sequence[str] = SCOPE) -> List[Finding]:
    return check_paths(sorted(set(walk_py(root, scope))), root)
