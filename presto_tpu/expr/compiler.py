"""Expression kernel compiler: IR -> jitted XLA kernels with a compile cache.

The TPU analogue of Presto's runtime bytecode generation (reference
presto-main/.../sql/gen/ExpressionCompiler.java:93 and
PageFunctionCompiler.java:98 with its guava caches at :121-136): instead of
emitting JVM bytecode per (expression, input types), we trace the IR into an
XLA program per (expression, schema, batch capacity) and cache the jitted
callable. XLA fuses the whole projection/filter into a handful of VPU loops —
the same fusion PageProcessor gets from generated bytecode, but done by the
compiler.

Three-valued logic (AND/OR/IN/COALESCE/IF) follows ANSI SQL semantics,
mirroring Presto's SpecialForm handling in sql/gen/AndCodeGenerator etc.

Invariant: a program's constants are ``Literal`` (or ``Param``) leaves
compared or combined WITH a column. No ``Call``, ``Cast`` or
``SpecialForm`` all of whose leaves are literals or params is traced
into a device program: the device's arithmetic is not the host's (the
TPU v5e's emulated f64 division is not correctly rounded, so
``cast(0.05 as double)`` computed there is 0.04999999999999982 and a
row whose value EQUALS a bound of BETWEEN falls out). The planner folds
such subtrees on the host (planner/fold.py, through :func:`host_value`
below); a plan template punches no literal under arithmetic
(serving/template.py) and a parameter is coerced by retyping it
(sql/analyzer.coerce), its value converted on the host when a binding
is bound (expr/params.current_args). What reaches :func:`eval_expr` all the
same (a fold that declined: the evaluation raised, or the value has no
literal form) is counted in ``expr_device_constant_total`` and logged.
"""
from __future__ import annotations

import hashlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..batch import Batch, Column, Schema
from ..obs import profiler as _prof
from ..obs.log import LOG
from ..obs.metrics import REGISTRY
from ..obs.trace import TRACER, device_drained, note_launch
from . import functions as F
from . import array_functions  # noqa: F401  (registers array fns)
from .functions import Val, cast_val, merge_err
from . import ir as _ir
from .ir import (
    Call, Cast, Expr, Form, InputRef, LambdaExpr, LambdaRef, Literal,
    SpecialForm,
)
from .rewrite import constant_subtrees, node_name

#: functions whose lambda arguments are evaluated over array elements
HIGHER_ORDER = frozenset(
    ["transform", "filter", "any_match", "all_match", "none_match",
     "reduce"])

# lambda parameter bindings for the expression currently being traced
_LAMBDA_STACK: List[List[Val]] = []


def _masked_err(cond, err):
    """err where cond else 0 (None-transparent)."""
    if err is None:
        return None
    return jnp.where(cond, err, jnp.int32(0))


_DEVICE_CONSTANTS = REGISTRY.counter("expr_device_constant_total")


def eval_expr(expr: Expr, inputs: List[Val]) -> Val:
    """Evaluate an expression against input column Vals (traceable):
    the entry every device program traces through. Constant subtrees
    that the planner's fold left in ``expr`` are counted here, at trace
    time (the module's invariant)."""
    for sub in constant_subtrees(expr):
        _DEVICE_CONSTANTS.inc()
        LOG.log("expr_device_constant", node=node_name(sub),
                expr=repr(sub)[:200])
    return _eval(expr, inputs)


def host_value(expr: Expr):
    """The python value of a constant expression (no column below it),
    evaluated ON THE HOST: the engine's own semantics (decimal precision
    and scale, dates, NULLs, three-valued logic) through :func:`_eval`
    over one row, pinned to the CPU device, whose f64 is IEEE's. Raises
    what the evaluation raises, and ``QueryError`` for a row error
    (division by zero, a cast out of range). Arrays come back as
    tuples."""
    from ..errors import QueryError
    # no CPU backend (JAX_PLATFORMS names the accelerator alone) is a
    # RuntimeError here, and the query's: nothing may take it for a
    # fold that declined and send the literals back to the device
    with jax.default_device(jax.devices("cpu")[0]):
        v = _eval(expr, [])
        if v.err is not None:
            code = int(jnp.max(v.err))
            if code:
                raise QueryError(code)
        b = Batch(Schema([("c", expr.type)]),
                  [Column(expr.type, v.data, v.valid, v.dictionary)],
                  jnp.ones(v.valid.shape[0], dtype=bool))
        out = b.to_pylist()[0][0]
    return tuple(out) if isinstance(out, list) else out


def _eval(expr: Expr, inputs: List[Val]) -> Val:
    if isinstance(expr, InputRef):
        return inputs[expr.index]
    if isinstance(expr, Literal):
        # valid is always the [capacity] row mask, even for composite
        # columns whose data is a tuple of arrays
        n = inputs[0].valid.shape[0] if inputs else 1
        return Val.constant(expr.value, expr.type, n)
    if isinstance(expr, _ir.Param):
        # plan-template parameter: a traced scalar jit operand, NOT a
        # baked constant — one executable serves every binding
        from .params import traced_val
        n = inputs[0].valid.shape[0] if inputs else 1
        return traced_val(expr, n)
    if isinstance(expr, Cast):
        arg = _eval(expr.arg, inputs)
        out = cast_val(arg, expr.type)
        out.err = merge_err(out.err, arg.err)
        return out
    if isinstance(expr, LambdaRef):
        return _LAMBDA_STACK[expr.level][expr.index]
    if isinstance(expr, Call):
        if expr.name in HIGHER_ORDER:
            return _eval_higher_order(expr, inputs)
        args = [_eval(a, inputs) for a in expr.args]
        out = F.lookup(expr.name)(args, expr.type)
        # strict calls: an argument's row error poisons the call's row
        # (the argument was evaluated to produce the error, like Java
        # evaluating operands before the invoke throws)
        out.err = merge_err(out.err, *[a.err for a in args])
        return out
    if isinstance(expr, SpecialForm):
        return _eval_special(expr, inputs)
    raise TypeError(f"unknown expr node {type(expr).__name__}")


def _repeat_val(v: Val, L: int) -> Val:
    """Lift an outer-row Val to element granularity (row i -> L copies)."""
    data = jax.tree_util.tree_map(
        lambda a: jnp.repeat(a, L, axis=0), v.data)
    return Val(data, jnp.repeat(v.valid, L, axis=0), v.type, v.dictionary,
               err=None if v.err is None else jnp.repeat(v.err, L, axis=0))


def _eval_higher_order(expr: Call, inputs: List[Val]) -> Val:
    """Array higher-order functions: the lambda body is traced once over
    the FLATTENED element array [cap*L] (captured outer columns repeat per
    element), then results fold back to [cap, L] — one fused elementwise
    kernel, the TPU answer to the reference's per-element lambda invokes
    (reference sql/gen/LambdaBytecodeGenerator.java)."""
    from .array_functions import _compact_rows, in_length

    name = expr.name
    arr = _eval(expr.args[0], inputs)
    values, lengths, elem_valid = arr.data
    cap, L = values.shape
    inl = in_length(values, lengths)
    et = expr.args[0].type.element

    if name == "reduce":
        # reduce(arr, init, (s, x) -> step, s -> out): sequential fold over
        # the static width L — the loop unrolls at trace time
        init = _eval(expr.args[1], inputs)
        step_lam: LambdaExpr = expr.args[2]
        out_lam: LambdaExpr = expr.args[3]
        state = init
        for j in range(L):
            xj = Val(values[:, j], (elem_valid & inl)[:, j], et,
                     arr.dictionary)
            _LAMBDA_STACK.append([state, xj])
            try:
                new_state = _eval(step_lam.body, inputs)
            finally:
                _LAMBDA_STACK.pop()
            active = inl[:, j]
            state = Val(jnp.where(active, new_state.data, state.data),
                        jnp.where(active, new_state.valid, state.valid),
                        new_state.type, new_state.dictionary,
                        err=merge_err(state.err, _masked_err(
                            active, new_state.err)))
        _LAMBDA_STACK.append([state])
        try:
            out = _eval(out_lam.body, inputs)
        finally:
            _LAMBDA_STACK.pop()
        out.valid = out.valid & arr.valid
        out.err = merge_err(out.err, arr.err)
        return out

    lam: LambdaExpr = expr.args[1]
    elem = Val(values.reshape(-1), (elem_valid & inl).reshape(-1), et,
               arr.dictionary)
    rep_inputs = [_repeat_val(v, L) for v in inputs]
    # nested lambdas: enclosing frames lift to the new element granularity
    # so outer parameters stay shape-compatible inside this body
    old_stack = list(_LAMBDA_STACK)
    _LAMBDA_STACK[:] = [[_repeat_val(v, L) for v in frame]
                        for frame in old_stack] + [[elem]]
    try:
        body = _eval(lam.body, rep_inputs)
    finally:
        _LAMBDA_STACK[:] = old_stack
    body_err = None
    if body.err is not None:
        body_err = jnp.max(
            jnp.where(inl, body.err.reshape(cap, L), jnp.int32(0)), axis=1)
    if name == "transform":
        new_vals = body.data.reshape(cap, L)
        new_ev = body.valid.reshape(cap, L) & inl
        return Val((new_vals, lengths, new_ev), arr.valid, expr.type,
                   dictionary=body.dictionary,
                   err=merge_err(arr.err, body_err))
    if name == "filter":
        keep = (body.data & body.valid).reshape(cap, L) & inl
        out = _compact_rows(values, elem_valid, keep, arr, expr.type)
        out.err = merge_err(out.err, body_err)
        return out
    # any/all/none_match: ANSI 3-valued fold over elements
    b = body.data.reshape(cap, L)
    bv = body.valid.reshape(cap, L)
    any_true = jnp.any(b & bv & inl, axis=1)
    any_null = jnp.any(~bv & inl, axis=1)
    if name == "all_match":
        any_false = jnp.any(~b & bv & inl, axis=1)
        data = ~any_false
        valid = any_false | ~any_null
    else:
        data = any_true if name == "any_match" else ~any_true
        valid = any_true | ~any_null
    return Val(data, valid & arr.valid, T.BOOLEAN,
               err=merge_err(arr.err, body_err))


def _eval_special(expr: SpecialForm, inputs: List[Val]) -> Val:
    form = expr.form
    if form == Form.AND or form == Form.OR:
        vals = [_eval(a, inputs) for a in expr.args]
        return _logical(vals, is_and=(form == Form.AND))
    if form == Form.IS_NULL:
        v = _eval(expr.args[0], inputs)
        return Val(~v.valid, jnp.ones_like(v.valid), T.BOOLEAN, err=v.err)
    if form == Form.IF:
        cond = _eval(expr.args[0], inputs)
        then = _eval(expr.args[1], inputs)
        els = _eval(expr.args[2], inputs)
        take_then = cond.valid & cond.data
        out = _merge_branch(take_then, then, els, expr.type)
        out.err = merge_err(out.err, cond.err)
        return out
    if form == Form.SWITCH:
        # SWITCH(c1, v1, c2, v2, ..., default)
        default = _eval(expr.args[-1], inputs)
        out = default
        # fold right-to-left so earlier WHENs win; an earlier match also
        # masks later branches'/conditions' errors (they are unevaluated
        # in the row-at-a-time reference)
        pairs = list(zip(expr.args[:-1:2], expr.args[1::2]))
        for cond_e, val_e in reversed(pairs):
            cond = _eval(cond_e, inputs)
            val = _eval(val_e, inputs)
            out = _merge_branch(cond.valid & cond.data, val, out, expr.type)
            out.err = merge_err(out.err, cond.err)
        return out
    if form == Form.COALESCE:
        vals = [_eval(a, inputs) for a in expr.args]
        out = vals[-1]
        for v in reversed(vals[:-1]):
            nxt = _merge_branch(v.valid, v, out, expr.type)
            # v's own errors always surface (v made itself NULL by
            # erroring); later args' errors only where v was NULL
            nxt.err = merge_err(v.err, _masked_err(~v.valid, out.err))
            out = nxt
        return out
    if form == Form.BETWEEN:
        v, lo, hi = (_eval(a, inputs) for a in expr.args)
        ge = F.lookup("ge")([v, lo], T.BOOLEAN)
        le = F.lookup("le")([v, hi], T.BOOLEAN)
        out = _logical([ge, le], is_and=True)
        out.err = merge_err(out.err, v.err, lo.err, hi.err)
        return out
    if form == Form.IN:
        return _eval_in(expr, inputs)
    if form == Form.NULL_IF:
        a = _eval(expr.args[0], inputs)
        b = _eval(expr.args[1], inputs)
        eq = F.lookup("eq")([a, b], T.BOOLEAN)
        return Val(a.data, a.valid & ~(eq.valid & eq.data), a.type,
                   a.dictionary, err=merge_err(a.err, b.err))
    if form == Form.TRY:
        v = _eval(expr.args[0], inputs)
        if v.err is None:
            return v
        return Val(v.data, v.valid & (v.err == 0), v.type, v.dictionary)
    raise NotImplementedError(f"special form {form}")


def _logical(vals: List[Val], is_and: bool) -> Val:
    """ANSI AND/OR over any number of boolean Vals.

    Errors honor left-to-right short-circuit evaluation (reference
    sql/gen/AndCodeGenerator): a row error in arg i is suppressed where an
    earlier arg already decided the result (known-false for AND,
    known-true for OR) — `x <> 0 AND 1/x > 0` never raises.
    """
    err = None
    decided = jnp.zeros_like(vals[0].valid)
    for v in vals:
        if v.err is not None:
            err = merge_err(err, _masked_err(~decided, v.err))
        decided = decided | (v.valid & (~v.data if is_and else v.data))
    if is_and:
        known_false = jnp.zeros_like(vals[0].valid)
        all_valid = jnp.ones_like(vals[0].valid)
        for v in vals:
            known_false = known_false | (v.valid & ~v.data)
            all_valid = all_valid & v.valid
        data = ~known_false & all_valid
        # data only meaningful where valid; AND is false wherever known_false
        return Val(jnp.where(known_false, False, data),
                   all_valid | known_false, T.BOOLEAN, err=err)
    known_true = jnp.zeros_like(vals[0].valid)
    all_valid = jnp.ones_like(vals[0].valid)
    for v in vals:
        known_true = known_true | (v.valid & v.data)
        all_valid = all_valid & v.valid
    return Val(known_true, all_valid | known_true, T.BOOLEAN, err=err)


def _merge_branch(take_a: jnp.ndarray, a: Val, b: Val, out_type: T.Type) -> Val:
    """where(take_a, a, b) with validity merge and dictionary unification.
    Row errors follow the taken branch (the untaken branch is never
    evaluated in the row-at-a-time reference)."""
    if a.err is None and b.err is None:
        err = None
    else:
        zeros = jnp.zeros(take_a.shape, dtype=jnp.int32)
        err = jnp.where(take_a,
                        a.err if a.err is not None else zeros,
                        b.err if b.err is not None else zeros)
    if out_type.is_string:
        da, db = a.dictionary or (), b.dictionary or ()
        if da == db:
            vocab = da
            data = jnp.where(take_a, a.data, b.data)
        else:
            vocab_list = list(da)
            lookup = {s: i for i, s in enumerate(vocab_list)}
            remap_b = np.empty(len(db) + 1, dtype=np.int32)
            remap_b[-1] = -1
            for i, s in enumerate(db):
                if s not in lookup:
                    lookup[s] = len(vocab_list)
                    vocab_list.append(s)
                remap_b[i] = lookup[s]
            vocab = tuple(vocab_list)
            tbl = jnp.asarray(remap_b)
            b_codes = jnp.take(tbl, jnp.where(b.data >= 0, b.data, len(db)), axis=0)
            data = jnp.where(take_a, a.data, b_codes)
        valid = jnp.where(take_a, a.valid, b.valid)
        return Val(data, valid, out_type, vocab, err=err)
    a = cast_val(a, out_type)
    b = cast_val(b, out_type)
    cond = take_a[:, None] if getattr(a.data, "ndim", 1) == 2 else take_a
    return Val(
        jnp.where(cond, a.data, b.data),
        jnp.where(take_a, a.valid, b.valid),
        out_type,
        err=err,
    )


def _eval_in(expr: SpecialForm, inputs: List[Val]) -> Val:
    v = _eval(expr.args[0], inputs)
    items = [_eval(a, inputs) for a in expr.args[1:]]
    if v.type.is_string and v.dictionary is not None:
        targets = set()
        any_null = False
        for it in items:
            s = F._string_literal_of(it)
            if s is None:
                any_null = True  # non-constant string in IN list: unsupported
                raise NotImplementedError("IN with non-constant string items")
            targets.add(F._str_padded(v, s))
        table = F.vocab_table(v.dictionary, lambda s: s in targets, np.bool_)
        data = F._code_gather(table, v.data)
        return Val(data, v.valid, T.BOOLEAN, err=v.err)
    # numeric: fold to OR of equalities (ANSI null semantics come along)
    eqs = [F.lookup("eq")([v, it], T.BOOLEAN) for it in items]
    out = _logical(eqs, is_and=False)
    out.err = merge_err(out.err, v.err, *[it.err for it in items])
    return out


# ---------------------------------------------------------------------------
# Compiled entry points
# ---------------------------------------------------------------------------

import contextlib


@contextlib.contextmanager
def _param_trace(exprs, pargs):
    """Trace scope mapping each Param slot to its traced jit operand
    (slot-order matches _ExprProgram/current_args). No-op for the
    common parameter-free kernel."""
    if not len(pargs):
        yield
        return
    from .params import collect_params, trace_scope
    slots = collect_params(exprs)
    with trace_scope({p.slot: a for p, a in zip(slots, pargs)}):
        yield


_EXPR_INVOCATIONS = REGISTRY.counter("expr_program_invocations_total")


class _ExprProgram:
    """One compiled expression program: the jitted kernel under its own
    name, ``expr_<kind>_<digest>`` (``kind`` the entry point, ``digest``
    six hex digits of a SHA-1 of the expressions' text and the input
    schema's: the same in every process, whatever ``PYTHONHASHSEED``),
    with a ledger in ``obs.profiler.EXECUTABLES`` whose static key is
    that text (``system.runtime.executables`` turns a digest seen in a
    device trace back into SQL). Launches count in
    ``expr_program_invocations_total`` and, while the tracer is on, are
    ``dispatch`` spans. Parameterized expressions (plan templates) get
    their bindings as an extra traced-operand tuple fetched from the
    query's binding scope at call time, so the cached executable is
    shared by every binding (value changes re-dispatch, never
    re-trace)."""

    __slots__ = ("program", "text", "fn", "slots", "bindings", "record")

    def __init__(self, kind: str, key, run, exprs):
        from ..ops.jitcache import named_jit
        from .params import collect_params, current_args
        self.text = repr(key)
        name = (f"expr_{kind}_"
                + hashlib.sha1(self.text.encode()).hexdigest()[:6])
        self.program = "jit_" + name
        self.slots = collect_params(exprs)
        self.bindings = current_args
        self.fn = named_jit(name, run)
        self.record = _prof.EXECUTABLES.register(name, self.text,
                                                 program=name)

    def __call__(self, batch: Batch):
        _prof.note_launch(self.record)
        _EXPR_INVOCATIONS.inc()
        if TRACER.enabled:
            with TRACER.span("dispatch", program=self.program,
                             expr=self.text[:200],
                             starved=device_drained()):
                return note_launch(self._launch(batch))
        return self._launch(batch)

    def _launch(self, batch: Batch):
        if self.slots:
            return self.fn(batch, self.bindings(self.slots))
        return self.fn(batch)


class ExprCompiler:
    """Compile cache keyed by (exprs, input schema) — capacity specializes
    inside jit automatically (shape is part of the XLA signature)."""

    def __init__(self):
        self._cache: Dict[object, Callable] = {}

    def projection(
        self, exprs: Sequence[Expr], out_names: Sequence[str], schema: Schema,
        errors: bool = False,
    ) -> Callable[[Batch], Batch]:
        """Compile exprs into fn(batch)->batch with columns named out_names.

        With ``errors=True`` the kernel returns ``(batch, err_code)`` where
        err_code is a device int32 scalar: the max row error code over live
        rows (0 = ok). The executor stacks these and checks once per query.
        """
        key = ("proj", tuple(exprs), tuple(out_names), schema, errors)
        if key in self._cache:
            return self._cache[key]
        out_schema = Schema(
            [(n, e.type) for n, e in zip(out_names, exprs)]
        )

        def run(batch: Batch, pargs=()):
            with _param_trace(exprs, pargs):
                inputs = [
                    Val(c.data, c.validity, c.type, c.dictionary)
                    for c in batch.columns
                ]
                if not inputs:
                    # zero-column input (SELECT without FROM): shape
                    # carrier so literal projections broadcast to the
                    # batch capacity
                    inputs = [Val(batch.row_mask, batch.row_mask,
                                  T.BOOLEAN)]
                outs = [eval_expr(e, inputs) for e in exprs]
            cols = [
                Column(o.type, o.data, o.valid & batch.row_mask, o.dictionary)
                for o in outs
            ]
            result = Batch(out_schema, cols, batch.row_mask)
            if not errors:
                return result
            return result, _err_scalar([o.err for o in outs], batch.row_mask)

        fn = self._cache[key] = _ExprProgram("project", key, run, exprs)
        return fn

    def filter(self, pred: Expr, schema: Schema,
               errors: bool = False) -> Callable[[Batch], Batch]:
        """Compile a predicate into fn(batch)->batch with narrowed row_mask.

        NULL predicate = row dropped (ANSI WHERE semantics). With
        ``errors=True`` returns ``(batch, err_code)`` like projection().
        """
        key = ("filter", pred, schema, errors)
        if key in self._cache:
            return self._cache[key]

        def run(batch: Batch, pargs=()):
            with _param_trace([pred], pargs):
                inputs = [
                    Val(c.data, c.validity, c.type, c.dictionary)
                    for c in batch.columns
                ]
                p = eval_expr(pred, inputs)
            keep = batch.row_mask & p.valid & p.data
            result = Batch(batch.schema, batch.columns, keep)
            if not errors:
                return result
            return result, _err_scalar([p.err], batch.row_mask)

        fn = self._cache[key] = _ExprProgram("filter", key, run, [pred])
        return fn


def _err_scalar(errs: List[Optional[jnp.ndarray]], row_mask):
    """Reduce per-row error codes over live rows to one device scalar —
    or None when the expression is statically error-free (known at trace
    time), so the common no-error path costs nothing."""
    merged = merge_err(*errs)
    if merged is None:
        return None
    return jnp.max(jnp.where(row_mask, merged, jnp.int32(0)))


_DEFAULT = ExprCompiler()


def compile_projection(exprs, out_names, schema, errors: bool = False):
    return _DEFAULT.projection(exprs, out_names, schema, errors=errors)


def compile_filter(pred, schema, errors: bool = False):
    return _DEFAULT.filter(pred, schema, errors=errors)
