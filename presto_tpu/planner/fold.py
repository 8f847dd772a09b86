"""Constant folding: literal expressions are evaluated on the host at
plan time.

The role of the reference's ExpressionInterpreter behind
SimplifyExpressions (reference sql/planner/ExpressionInterpreter.java,
iterative/rule/SimplifyExpressions.java), for a reason the reference
does not have: the device's arithmetic is not the host's. The TPU v5e
emulates f64 and its division is not correctly rounded, so
``cast(0.06 - 0.01 as double)`` traced into a filter program is
0.04999999999999982 there and a row whose ``l_discount`` EQUALS the
bound falls out of BETWEEN (TPC-H Q6). Folded here the subtraction is
exact decimal arithmetic and the conversion to DOUBLE happens once, in
IEEE arithmetic.

Every maximal subtree of a plan expression that reads no row (no
column, no lambda parameter, no non-deterministic call, no init-plan
placeholder) is evaluated through ``expr/compiler.host_value``, the
engine's own evaluation pinned to the CPU device, and becomes one
``ir.Literal`` of the subtree's type. A plan-template parameter
(``ir.Param``, serving/template.py) is no literal and nothing above it
folds, here or once its binding is known: the template walk punches no
literal that sits under arithmetic, and the analyzer's coercion retypes
a parameter and casts none (sql/analyzer.coerce), so no subtree over
parameters alone reaches a plan.

A subtree whose evaluation raises (``1/0``, a cast out of range) or
whose value has no literal form (arrays, maps, NaN) is left as it is:
the error stays a row error at run time, so a query over zero rows
still succeeds. ``plan_literals_folded_total`` and
``plan_fold_declined_total`` count both outcomes; what was left is
counted again where it is traced (``expr_device_constant_total``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator

from .. import types as T
from ..expr import ir
from ..expr.rewrite import is_constant, rewrite
from ..obs.metrics import REGISTRY
from .plan import PlanNode
from .planner import InitPlanRef

_FOLDED = REGISTRY.counter("plan_literals_folded_total")
_DECLINED = REGISTRY.counter("plan_fold_declined_total")

#: the types whose values an ``ir.Literal`` holds (expr/functions
#: ``Val.constant``): composite values have no literal form
_LITERAL_TYPES = (T.BooleanType, T.IntegerLikeType, T.BigintType,
                  T.DoubleType, T.RealType, T.DecimalType, T.DateType,
                  T.TimestampType, T.VarcharType, T.CharType)

def _literal_value(t: T.Type, value):
    """``value`` as the analyzer would have written it into a literal
    of type ``t``: dates and timestamps as ISO text, numbers as python
    numbers."""
    if value is None:
        return None
    if isinstance(t, T.DateType):
        return value.isoformat()
    if isinstance(t, T.TimestampType):
        return value.isoformat(sep=" ")
    if isinstance(t, (T.DoubleType, T.RealType)):
        if math.isnan(value):
            raise ValueError("NaN is equal to no literal, itself included")
        return float(value)
    if isinstance(t, T.BooleanType):
        return bool(value)
    if isinstance(t, (T.IntegerLikeType, T.BigintType)):
        return int(value)
    return value


def _leaves(e: ir.Expr) -> Iterator[ir.Expr]:
    kids = e.children()
    if not kids:
        yield e
    for c in kids:
        yield from _leaves(c)


class Folder:
    """One pass over one plan: a subtree it declines is counted once."""

    def __init__(self):
        self._left = set()

    def expr(self, e: ir.Expr) -> ir.Expr:
        if not e.children() or e in self._left:
            return e
        constant = (not isinstance(e, ir.LambdaExpr)
                    and is_constant(e)
                    and not any(isinstance(x, ir.Literal)
                                and isinstance(x.value, InitPlanRef)
                                for x in _leaves(e)))
        if constant and isinstance(e.type, _LITERAL_TYPES):
            folded = self._value(e)
            if folded is not None:
                _FOLDED.inc()
                return folded
        # a subtree that reads rows, or one that would not fold whole:
        # fold what folds below it
        before = len(self._left)
        out = self._below(e)
        if constant and len(self._left) == before:
            self._left.add(out)     # the innermost subtree left
            _DECLINED.inc()
        return out

    def _value(self, e: ir.Expr):
        """The literal that ``e`` folds to; None where its evaluation
        raises for the row (the subtree stays, and raises at run time)
        or its value has no literal form."""
        from ..errors import QueryError
        from ..expr.compiler import host_value
        try:
            value = _literal_value(e.type, host_value(e))
        except (QueryError, ArithmeticError, ValueError,
                NotImplementedError):
            return None
        return ir.Literal(type=e.type, value=value)

    def _below(self, e: ir.Expr) -> ir.Expr:
        if isinstance(e, ir.Cast):
            arg = self.expr(e.arg)
            return e if arg is e.arg else ir.Cast(type=e.type, arg=arg)
        if isinstance(e, ir.LambdaExpr):
            body = self.expr(e.body)
            return e if body is e.body else dataclasses.replace(e, body=body)
        args = tuple(self.expr(a) for a in e.args)
        if all(a is b for a, b in zip(args, e.args)):
            return e
        return dataclasses.replace(e, args=args)

    def plan(self, node: PlanNode) -> PlanNode:
        """``node`` with every expression of every plan node folded:
        filter predicates, projections, join residuals, unnest
        arguments (aggregate arguments are projections below the
        aggregation)."""
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, ir.Expr):
                nv = self.expr(v)
            elif (isinstance(v, tuple) and v
                  and all(isinstance(x, ir.Expr) for x in v)):
                nv = tuple(self.expr(x) for x in v)
                nv = v if all(a is b for a, b in zip(nv, v)) else nv
            else:
                continue
            if nv is not v:
                changes[f.name] = nv
        if changes:
            node = dataclasses.replace(node, **changes)
        kids = [self.plan(c) for c in node.children]
        if any(a is not b for a, b in zip(kids, node.children)):
            node = node.with_children(kids)
        return node


def fold_expr(e: ir.Expr) -> ir.Expr:
    """One expression folded (the executor's, once an init plan's
    value has replaced its placeholder)."""
    return Folder().expr(e)
