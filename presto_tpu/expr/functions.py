"""Scalar function implementations over (data, validity) column pairs.

The analogue of Presto's FunctionRegistry + operator/scalar/* (reference
presto-main/.../metadata/FunctionRegistry.java:350 and operator/scalar/): each
function is a pure jnp transform over storage arrays plus explicit SQL
three-valued-logic validity handling. String functions operate on dictionary
codes with host-side vocabulary precomputation at trace time — the vocab is
static under jit, so LIKE/substr/comparison tables bake into the compiled
kernel as constants (the TPU answer to Presto's per-invocation Joni regex).

Error semantics (reference spi/StandardErrorCode.java): kernels record a
per-row int32 error code on the Val (``err``; 0/None = ok) instead of
raising — integer/decimal division by zero sets DIVISION_BY_ZERO exactly
like Presto's BigintOperators.divide, while double division follows IEEE
(Infinity/NaN, no error) like DoubleOperators. The compiler propagates the
codes with branch masking (IF/CASE/AND-OR short circuits) and the executor
raises QueryError after the batch is produced; TRY() clears them to NULL.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import math
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from .. import errors as E
from ..types import Type


@dataclasses.dataclass
class Val:
    """Evaluation-time column value: storage data + validity (+ vocab)."""

    data: jnp.ndarray
    valid: jnp.ndarray
    type: Type
    dictionary: Optional[Tuple[str, ...]] = None
    #: static python value when this Val is a compile-time constant —
    #: lets string/positional args (substr offsets, LIKE patterns) stay
    #: static under jit, like constant folding in the reference codegen
    literal: Optional[object] = None
    #: per-row int32 error code (0 = ok); None = statically error-free
    err: Optional[jnp.ndarray] = None

    @staticmethod
    def constant(value, typ: Type, n: int) -> "Val":
        if value is None:
            if isinstance(typ, T.ArrayType):
                return Val(
                    (jnp.zeros((n, 1), dtype=typ.storage_dtype),
                     jnp.zeros(n, dtype=jnp.int32),
                     jnp.zeros((n, 1), dtype=bool)),
                    jnp.zeros(n, dtype=bool), typ,
                    dictionary=() if typ.element.is_string else None,
                )
            width = getattr(typ, "storage_width", None)
            shape = (n,) if width is None else (n, width)
            return Val(
                jnp.zeros(shape, dtype=typ.storage_dtype),
                jnp.zeros(n, dtype=bool), typ, literal=None,
            )
        if typ.is_string:
            s = value
            if isinstance(typ, T.CharType):
                s = str(s).ljust(typ.length)
            return Val(
                jnp.zeros(n, dtype=jnp.int32),
                jnp.ones(n, dtype=bool), typ, dictionary=(s,), literal=s,
            )
        storage = typ.to_storage(value)
        if getattr(typ, "storage_width", None):
            data = jnp.tile(
                jnp.asarray(storage, dtype=typ.storage_dtype)[None, :],
                (n, 1))
        else:
            data = jnp.full(n, storage, dtype=typ.storage_dtype)
        return Val(data, jnp.ones(n, dtype=bool), typ, literal=value)


def _all_valid(args: Sequence[Val]) -> jnp.ndarray:
    v = args[0].valid
    for a in args[1:]:
        v = v & a.valid
    return v


def merge_err(*errs: Optional[jnp.ndarray]) -> Optional[jnp.ndarray]:
    """Combine per-row error codes; the max code wins on a row."""
    present = [e for e in errs if e is not None]
    if not present:
        return None
    out = present[0]
    for e in present[1:]:
        out = jnp.maximum(out, e)
    return out


def flag_err(cond: jnp.ndarray, code: int) -> jnp.ndarray:
    return jnp.where(cond, jnp.int32(code), jnp.int32(0))


# -- decimal helpers ---------------------------------------------------------

def _is_long_dec(t) -> bool:
    return isinstance(t, T.DecimalType) and t.is_long


def _dec_limbs(v: Val, to_scale: int):
    """Numeric Val -> ([n, 2] limb tile at to_scale, overflow rows).
    Decimal inputs rescale from their own scale; integrals from 0
    (ops/int128.py; reference UnscaledDecimal128Arithmetic.rescale)."""
    from ..ops import int128 as I
    t = v.type
    if isinstance(t, T.DecimalType):
        x = v.data if t.is_long else I.from_i64(v.data)
        return I.rescale(x, to_scale - t.scale)
    if T.is_integral(t) or isinstance(t, T.BigintType):
        return I.rescale(I.from_i64(v.data.astype(jnp.int64)), to_scale)
    raise NotImplementedError(
        f"cannot take decimal limbs of {t.display()}")


def rescale_decimal(data: jnp.ndarray, from_scale: int, to_scale: int) -> jnp.ndarray:
    """Rescale int64 decimal storage, rounding half-up away from zero."""
    if to_scale == from_scale:
        return data
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    div = 10 ** (from_scale - to_scale)
    half = div // 2
    sign = jnp.sign(data)
    return sign * ((jnp.abs(data) + half) // div)


def _divisor(scale: int, dtype=jnp.float64):
    """``10**scale`` as ``cast(decimal as double)`` divides by it: behind
    an optimization barrier, or XLA sees a division by a constant and
    multiplies by the reciprocal instead, which is not the quotient
    (``35 * 0.01`` is 0.35000000000000003: a column's 0.35 then differs
    from the literal 0.35 on every backend). The CPU's division is
    IEEE's; the TPU v5e's is not correctly rounded (README.md, DOUBLE
    on the chip)."""
    return jax.lax.optimization_barrier(jnp.asarray(10.0 ** scale, dtype))


def _cast_long_decimal(v: Val, to: Type) -> Val:
    """Casts where the source or target is a long decimal (p > 18):
    limb rescales with range checks (reference DecimalCasts.java +
    UnscaledDecimal128Arithmetic). Out-of-range rows error with
    NUMERIC_VALUE_OUT_OF_RANGE like the reference's throw."""
    from ..ops import int128 as I
    f = v.type
    if isinstance(to, T.DecimalType):
        if isinstance(f, T.DecimalType) or T.is_integral(f) \
                or isinstance(f, T.BigintType):
            x, ovf = _dec_limbs(v, to.scale)
        elif T.is_floating(f):
            bound = 10.0 ** (to.precision - to.scale)
            scaled = v.data.astype(jnp.float64) * (10.0 ** to.scale)
            half_up = jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5)
            x = I.from_f64(half_up)
            ovf = ~(jnp.abs(v.data.astype(jnp.float64)) < bound)
        else:
            raise NotImplementedError(
                f"cast {f.display()} -> {to.display()}")
        fits = I.fits_decimal(x, to.precision) & ~ovf
        err = flag_err(v.valid & ~fits, E.NUMERIC_VALUE_OUT_OF_RANGE)
        if to.is_long:
            return Val(x, v.valid & fits, to, err=err)
        return Val(I.lo(x), v.valid & fits, to, err=err)
    # source is long decimal
    if isinstance(to, T.DoubleType) or isinstance(to, T.RealType):
        out = (I.to_f64(v.data) / _divisor(f.scale)).astype(to.storage_dtype)
        return Val(out, v.valid, to)
    if T.is_integral(to) or isinstance(to, T.BigintType):
        x, _ = I.rescale(v.data, -f.scale)
        fits = I.hi(x) == (I.lo(x) >> 63)       # value fits one limb
        narrow = I.lo(x)
        if not isinstance(to, T.BigintType):
            info = jnp.iinfo(to.storage_dtype)
            fits = fits & (narrow >= info.min) & (narrow <= info.max)
        err = flag_err(v.valid & ~fits, E.NUMERIC_VALUE_OUT_OF_RANGE)
        return Val(narrow.astype(to.storage_dtype), v.valid & fits, to,
                   err=err)
    if isinstance(to, T.BooleanType):
        return Val(~I.is_zero(v.data), v.valid, to)
    raise NotImplementedError(f"cast {f.display()} -> {to.display()}")


def _unify_numeric(a: Val, b: Val) -> Tuple[Val, Val, Type]:
    """Coerce two numeric Vals to a common type (planner usually pre-casts;
    this is the defensive fallback)."""
    t = T.common_super_type(a.type, b.type)
    if t is None:
        raise TypeError(f"cannot unify {a.type} and {b.type}")
    return cast_val(a, t), cast_val(b, t), t


def cast_val(v: Val, to: Type) -> Val:
    """CAST implementation (reference operator/scalar casts per type)."""
    f = v.type
    if f == to:
        return v
    if isinstance(f, T.UnknownType):
        # typed NULL: all-invalid storage of the target type
        n = v.data.shape[0]
        if isinstance(to, T.ArrayType):
            return Val((jnp.zeros((n, 1), dtype=to.storage_dtype),
                        jnp.zeros(n, dtype=jnp.int32),
                        jnp.zeros((n, 1), dtype=bool)),
                       jnp.zeros(n, dtype=bool), to,
                       dictionary=() if to.element.is_string else None,
                       err=v.err)
        return Val(jnp.zeros(n, dtype=to.storage_dtype),
                   jnp.zeros(n, dtype=bool), to,
                   dictionary=() if to.is_string else None, err=v.err)
    data = v.data
    if _is_long_dec(f) or _is_long_dec(to):
        return _cast_long_decimal(v, to)
    if isinstance(f, T.DecimalType) and isinstance(to, T.DecimalType):
        return Val(rescale_decimal(data, f.scale, to.scale), v.valid, to)
    if isinstance(to, T.DoubleType) or isinstance(to, T.RealType):
        if isinstance(f, T.DecimalType):
            out = data.astype(to.storage_dtype) / _divisor(
                f.scale, to.storage_dtype)
        else:
            out = data.astype(to.storage_dtype)
        return Val(out, v.valid, to)
    if isinstance(to, T.DecimalType):
        if T.is_integral(f):
            return Val(data.astype(jnp.int64) * (10 ** to.scale), v.valid, to)
        if T.is_floating(f):
            scaled = data * (10.0 ** to.scale)
            out = jnp.sign(scaled) * jnp.floor(jnp.abs(scaled) + 0.5)
            return Val(out.astype(jnp.int64), v.valid, to)
    if T.is_integral(to) or isinstance(to, T.BigintType):
        if T.is_floating(f):
            # Presto DoubleOperators.castToLong: Math.round = half-up
            out = jnp.floor(data + 0.5).astype(to.storage_dtype)
            return Val(out, v.valid, to)
        if isinstance(f, T.DecimalType):
            return Val(
                rescale_decimal(data, f.scale, 0).astype(to.storage_dtype),
                v.valid, to,
            )
        if T.is_integral(f) or isinstance(f, T.BooleanType):
            return Val(data.astype(to.storage_dtype), v.valid, to)
    if isinstance(to, T.BooleanType) and T.is_numeric(f):
        return Val(data != 0, v.valid, to)
    if isinstance(to, T.VarcharType) and f.is_string \
            and not isinstance(f, T.VarbinaryType):
        return Val(data, v.valid, to, v.dictionary)
    if isinstance(to, T.TimestampType) and isinstance(f, T.DateType):
        return Val(data.astype(jnp.int64) * 86_400_000_000, v.valid, to)
    if isinstance(to, T.DateType) and isinstance(f, T.TimestampType):
        return Val((data // 86_400_000_000).astype(jnp.int32), v.valid, to)
    if isinstance(to, T.DateType) and f.is_string \
            and isinstance(v.dictionary, tuple):
        # dictionary-string -> date: parse each distinct VALUE host-side
        # (the vocabulary is static at trace time), then one device
        # gather maps codes to epoch days. Unparseable values raise the
        # row-error channel like the reference's failing DATE cast
        # (reference operator/scalar/DateTimeFunctions castToDate).
        import datetime as _dt
        from ..errors import INVALID_FUNCTION_ARGUMENT
        days, ok = [], []
        for s in v.dictionary:
            try:
                # lenient y-m-d split like the reference's date parse:
                # '2002-2-01' is a valid DATE literal (unpadded fields)
                y, m, d = (int(p) for p in s.strip().split("-"))
                days.append((_dt.date(y, m, d)
                             - _dt.date(1970, 1, 1)).days)
                ok.append(True)
            except (ValueError, TypeError):
                days.append(0)
                ok.append(False)
        table = jnp.asarray(days + [0], dtype=jnp.int32)
        okt = jnp.asarray(ok + [False])
        codes = jnp.clip(data.astype(jnp.int32), 0, len(days))
        parsed_ok = jnp.take(okt, codes, axis=0)
        err = jnp.where(v.valid & ~parsed_ok,
                        jnp.int32(INVALID_FUNCTION_ARGUMENT),
                        jnp.int32(0))
        return Val(jnp.take(table, codes, axis=0),
                   v.valid & parsed_ok, to,
                   err=merge_err(v.err, err))
    raise NotImplementedError(f"cast {f.display()} -> {to.display()}")


# -- date math (branch-free civil calendar, VPU-friendly) --------------------

def _civil_from_days(days: jnp.ndarray):
    """days since 1970-01-01 -> (year, month, day). Howard Hinnant's
    branch-free algorithm, exact for the whole int32 range."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097                                # [0, 146096]
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)       # [0, 365]
    mp = (5 * doy + 2) // 153                             # [0, 11]
    d = doy - (153 * mp + 2) // 5 + 1                     # [1, 31]
    m = jnp.where(mp < 10, mp + 3, mp - 9)                # [1, 12]
    year = jnp.where(m <= 2, y + 1, y)
    return year, m, d


def _days_from_civil(y: jnp.ndarray, m: jnp.ndarray, d: jnp.ndarray):
    y = y.astype(jnp.int64)
    yy = jnp.where(m <= 2, y - 1, y)
    era = jnp.floor_divide(yy, 400)
    yoe = yy - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = 365 * yoe + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# -- string helpers (host-side over static vocab) ----------------------------

def _like_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    esc = escape
    while i < len(pattern):
        c = pattern[i]
        if esc is not None and c == esc and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return "".join(out)


def vocab_table(vocab: Tuple[str, ...], fn: Callable[[str], object], dtype) -> jnp.ndarray:
    """Evaluate a host predicate/transform over the vocab -> device table.
    Appends a slot for the -1 (null) code at the end."""
    vals = [fn(s) for s in vocab]
    vals.append(fn("") if dtype != np.bool_ else False)
    return jnp.asarray(np.asarray(vals, dtype=dtype))


def _code_gather(table: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    idx = jnp.where(codes >= 0, codes, table.shape[0] - 1)
    return jnp.take(table, idx, axis=0)


def _string_literal_of(v: Val) -> Optional[str]:
    if v.dictionary is not None and len(v.dictionary) == 1 and v.data.ndim >= 1:
        # constant produced by Val.constant
        return v.dictionary[0]
    return None


def _str_padded(v: Val, s: str) -> str:
    return s.ljust(v.type.length) if isinstance(v.type, T.CharType) else s


def _string_compare(a: Val, b: Val, op: str) -> Val:
    """Comparison on dictionary-coded strings."""
    lit_b = _string_literal_of(b)
    lit_a = _string_literal_of(a)
    valid = a.valid & b.valid
    if a.dictionary is not None and lit_b is not None:
        target = _str_padded(a, lit_b)
        if op in ("eq", "ne"):
            code = a.dictionary.index(target) if target in a.dictionary else -2
            d = a.data == code
            return Val(d if op == "eq" else ~d, valid, T.BOOLEAN)
        table = vocab_table(
            a.dictionary,
            {"lt": lambda s: s < target, "le": lambda s: s <= target,
             "gt": lambda s: s > target, "ge": lambda s: s >= target}[op],
            np.bool_,
        )
        return Val(_code_gather(table, a.data), valid, T.BOOLEAN)
    if lit_a is not None and b.dictionary is not None:
        flipped = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                   "eq": "eq", "ne": "ne"}[op]
        return _string_compare(b, a, flipped)
    if a.dictionary is not None and b.dictionary is not None:
        if a.dictionary == b.dictionary:
            if op in ("eq", "ne"):
                d = a.data == b.data
                return Val(d if op == "eq" else ~d, valid, T.BOOLEAN)
            rank = vocab_table(
                a.dictionary,
                lambda s, order=sorted(a.dictionary): (
                    order.index(s) if s in order else -1),
                np.int32,
            )
            ra, rb = _code_gather(rank, a.data), _code_gather(rank, b.data)
            d = {"lt": ra < rb, "le": ra <= rb, "gt": ra > rb, "ge": ra >= rb}[op]
            return Val(d, valid, T.BOOLEAN)
        # different vocabularies: build a shared ordering at trace time
        # (the -1 sentinel slot probes with "", which need not be a
        # member — rank -1 compares like nothing real but the slot is
        # masked by validity anyway)
        merged = sorted(set(a.dictionary) | set(b.dictionary))
        order = {s: i for i, s in enumerate(merged)}
        ta = vocab_table(a.dictionary, lambda s: order.get(s, -1),
                         np.int64)
        tb = vocab_table(b.dictionary, lambda s: order.get(s, -1),
                         np.int64)
        ra, rb = _code_gather(ta, a.data), _code_gather(tb, b.data)
        d = {"eq": ra == rb, "ne": ra != rb, "lt": ra < rb,
             "le": ra <= rb, "gt": ra > rb, "ge": ra >= rb}[op]
        return Val(d, valid, T.BOOLEAN)
    raise NotImplementedError("string comparison without dictionaries")


# -- function registry -------------------------------------------------------

FunctionImpl = Callable[[List[Val], Type], Val]
_REGISTRY: Dict[str, FunctionImpl] = {}
#: plugin-provided return-type inference, name -> (arg_types) -> Type
#: (the Plugin.getFunctions surface; reference spi/Plugin.java:33-78 +
#: metadata/FunctionRegistry registration)
_EXTERNAL_SIGNATURES: Dict[str, Callable[[List[Type]], Type]] = {}


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn
    return deco


def register_external(name: str, impl: FunctionImpl,
                      infer: Callable[[List[Type]], Type]) -> None:
    """Register a plugin scalar function: device kernel + return-type
    inference. The kernel receives (args: List[Val], out_type) and must
    be jax-traceable like every builtin."""
    key = name.lower()
    _REGISTRY[key] = impl
    _EXTERNAL_SIGNATURES[key] = infer


def lookup(name: str) -> FunctionImpl:
    if name not in _REGISTRY:
        raise KeyError(f"unknown function {name!r}")
    return _REGISTRY[name]


def _long_decimal_arith(op: str, a: Val, b: Val, out, valid) -> Val:
    """Decimal arithmetic through int128 limb kernels (reference
    DecimalOperators.java long-decimal paths over Int128). add/sub/mul
    are exact with NUMERIC_VALUE_OUT_OF_RANGE on 38-digit overflow;
    division supports divisors whose unscaled value fits 31 bits
    (precision <= 9 — the short-division kernel's bound), which covers
    constants and typical scaled divisors."""
    from ..ops import int128 as I
    s_out = out.scale
    sa = a.type.scale if isinstance(a.type, T.DecimalType) else 0
    sb = b.type.scale if isinstance(b.type, T.DecimalType) else 0
    if op in ("add", "sub"):
        xa, oa = _dec_limbs(a, s_out)
        xb, ob = _dec_limbs(b, s_out)
        res = I.add(xa, xb) if op == "add" else I.sub(xa, xb)
        rhs = xb if op == "add" else I.neg(xb)
        wrap = I.add_overflows(xa, rhs, res)
        fits = I.fits_decimal(res, out.precision) & ~(oa | ob | wrap)
    elif op == "mul":
        xa, oa = _dec_limbs(a, sa)
        xb, ob = _dec_limbs(b, sb)
        prod, om = I.mul(xa, xb)
        res, orr = I.rescale(prod, s_out - (sa + sb))
        fits = I.fits_decimal(res, out.precision) & ~(oa | ob | om | orr)
    elif op == "div":
        # general int128/int128 division (float-estimate + exact
        # correction, ops/int128.py divmod_abs); the base-2^32 short
        # kernel stays for small divisors where it's cheaper
        num, on = _dec_limbs(a, s_out + sb)
        small_type = (isinstance(b.type, T.DecimalType)
                      and not b.type.is_long and b.type.precision <= 9) \
            or (T.is_integral(b.type)
                and not isinstance(b.type, T.BigintType))
        if small_type:
            db = b.data.astype(jnp.int64)
            zero = db == 0
            q = I.div_round_half_up(num, jnp.abs(jnp.where(zero, 1, db)))
            q = I.where(db < 0, I.neg(q), q)
        else:
            den, od = _dec_limbs(b, sb)
            on = on | od
            zero = I.is_zero(den)
            safe = I.where(zero, I.from_i64(
                jnp.ones(num.shape[:-1], dtype=jnp.int64)), den)
            q = I.div_round_half_up_wide(num, safe)
        err = flag_err(valid & zero, E.DIVISION_BY_ZERO)
        fits = I.fits_decimal(q, out.precision) & ~on & ~zero
        err = err | flag_err(valid & ~zero & ~fits,
                             E.NUMERIC_VALUE_OUT_OF_RANGE)
        data = q if out.is_long else I.lo(q)
        return Val(data, valid & fits, out, err=err)
    else:
        raise NotImplementedError(f"long decimal {op} is not supported")
    err = flag_err(valid & ~fits, E.NUMERIC_VALUE_OUT_OF_RANGE)
    data = res if out.is_long else I.lo(res)
    return Val(data, valid & fits, out, err=err)


def _arith(op):
    def impl(args: List[Val], out: Type) -> Val:
        a, b = args
        valid = a.valid & b.valid
        if isinstance(out, T.DecimalType) and (
                out.is_long or _is_long_dec(a.type) or _is_long_dec(b.type)):
            return _long_decimal_arith(op, a, b, out, valid)
        if isinstance(out, T.DecimalType):
            s_out = out.scale
            sa = a.type.scale if isinstance(a.type, T.DecimalType) else 0
            sb = b.type.scale if isinstance(b.type, T.DecimalType) else 0
            da = a.data.astype(jnp.int64)
            db = b.data.astype(jnp.int64)
            if op == "mul":
                data = rescale_decimal(da * db, sa + sb, s_out)
            elif op == "div":
                # scale numerator to s_out + sb, integer divide, round half-up
                num = rescale_decimal(da, sa, s_out + sb)
                den = jnp.where(db == 0, 1, db)
                q = num / den
                data = (jnp.sign(q) * jnp.floor(jnp.abs(num) / jnp.abs(den) + 0.5)).astype(jnp.int64)
                err = flag_err(valid & (db == 0), E.DIVISION_BY_ZERO)
                valid = valid & (db != 0)
                return Val(data, valid, out, err=err)
            elif op == "mod":
                sc = max(sa, sb)
                da2, db2 = rescale_decimal(da, sa, sc), rescale_decimal(db, sb, sc)
                den = jnp.where(db2 == 0, 1, db2)
                data = jnp.sign(da2) * (jnp.abs(da2) % jnp.abs(den))
                err = flag_err(valid & (db2 == 0), E.DIVISION_BY_ZERO)
                valid = valid & (db2 != 0)
                return Val(data, valid, out, err=err)
            else:
                sc = s_out
                da2, db2 = rescale_decimal(da, sa, sc), rescale_decimal(db, sb, sc)
                data = da2 + db2 if op == "add" else da2 - db2
            return Val(data, valid, out)
        a2, b2 = cast_val(a, out), cast_val(b, out)
        da, db = a2.data, b2.data
        if op == "add":
            data = da + db
        elif op == "sub":
            data = da - db
        elif op == "mul":
            data = da * db
        elif op == "div":
            if T.is_integral(out):
                den = jnp.where(db == 0, 1, db)
                # SQL integer division truncates toward zero
                data = (jnp.sign(da) * jnp.sign(den)) * (jnp.abs(da) // jnp.abs(den))
                err = flag_err(valid & (db == 0), E.DIVISION_BY_ZERO)
                valid = valid & (db != 0)
                return Val(data, valid, out, err=err)
            # double/real: IEEE semantics like Java (DoubleOperators.divide):
            # x/0 = ±Infinity, 0/0 = NaN — no error, no NULL
            data = da / db
        elif op == "mod":
            if T.is_integral(out):
                den = jnp.where(db == 0, 1, db)
                data = jnp.sign(da) * (jnp.abs(da) % jnp.abs(den))
                err = flag_err(valid & (db == 0), E.DIVISION_BY_ZERO)
                valid = valid & (db != 0)
                return Val(data, valid, out, err=err)
            # double % 0 = NaN (Java remainder semantics)
            den = jnp.where(db == 0.0, jnp.nan, db)
            data = jnp.sign(da) * (jnp.abs(da) % jnp.abs(den))
        else:
            raise AssertionError(op)
        return Val(data, valid, out)
    return impl


for _name, _op in [("add", "add"), ("subtract", "sub"), ("multiply", "mul"),
                   ("divide", "div"), ("modulus", "mod")]:
    register(_name)(_arith(_op))


@register("negate")
def _negate(args, out):
    (a,) = args
    if _is_long_dec(a.type):
        from ..ops import int128 as I
        return Val(I.neg(a.data), a.valid, out)
    return Val(-a.data, a.valid, out)


def _long_dec_compare(a: Val, b: Val, op: str) -> Val:
    """Compare when either side is a long decimal and both are exact
    numerics: rescale to the wider scale, limb compare. When the
    rescale would exceed 38 digits (extreme scale gap), fall back to
    f64 compare (beyond-38-digit distinctions round away, documented)."""
    from ..ops import int128 as I
    sa = a.type.scale if isinstance(a.type, T.DecimalType) else 0
    sb = b.type.scale if isinstance(b.type, T.DecimalType) else 0
    pa = a.type.precision if isinstance(a.type, T.DecimalType) else 19
    pb = b.type.precision if isinstance(b.type, T.DecimalType) else 19
    s = max(sa, sb)
    valid = a.valid & b.valid
    if max(pa + s - sa, pb + s - sb) > 38:
        fa = cast_val(a, T.DOUBLE).data
        fb = cast_val(b, T.DOUBLE).data
        data = {"eq": fa == fb, "ne": fa != fb, "lt": fa < fb,
                "le": fa <= fb, "gt": fa > fb, "ge": fa >= fb}[op]
        return Val(data, valid, T.BOOLEAN)
    xa, _ = _dec_limbs(a, s)
    xb, _ = _dec_limbs(b, s)
    data = {"eq": I.eq(xa, xb), "ne": ~I.eq(xa, xb),
            "lt": I.lt(xa, xb), "le": I.le(xa, xb),
            "gt": I.lt(xb, xa), "ge": I.le(xb, xa)}[op]
    return Val(data, valid, T.BOOLEAN)


def _cmp(op):
    def impl(args: List[Val], out: Type) -> Val:
        a, b = args
        if a.type.is_string or b.type.is_string:
            return _string_compare(a, b, op)
        if (_is_long_dec(a.type) or _is_long_dec(b.type)) \
                and not (T.is_floating(a.type) or T.is_floating(b.type)):
            return _long_dec_compare(a, b, op)
        if a.type != b.type:
            a, b, _ = _unify_numeric(a, b)
        valid = a.valid & b.valid
        da, db = a.data, b.data
        data = {"eq": da == db, "ne": da != db, "lt": da < db,
                "le": da <= db, "gt": da > db, "ge": da >= db}[op]
        return Val(data, valid, T.BOOLEAN)
    return impl


for _name in ["eq", "ne", "lt", "le", "gt", "ge"]:
    register(_name)(_cmp(_name))


@register("not")
def _not(args, out):
    (a,) = args
    return Val(~a.data, a.valid, T.BOOLEAN)


@register("abs")
def _abs(args, out):
    (a,) = args
    if _is_long_dec(a.type):
        from ..ops import int128 as I
        return Val(I.abs_(a.data), a.valid, out)
    return Val(jnp.abs(a.data), a.valid, out)


def _dbl_fn(fn):
    def impl(args, out):
        (a,) = args
        a = cast_val(a, T.DOUBLE)
        return Val(fn(a.data), a.valid, out)
    return impl


register("sqrt")(_dbl_fn(jnp.sqrt))
register("ln")(_dbl_fn(jnp.log))
register("exp")(_dbl_fn(jnp.exp))


@register("floor")
def _floor(args, out):
    (a,) = args
    if _is_long_dec(a.type):
        return Val(_long_dec_floor_ceil(a, ceil=False), a.valid, out)
    if isinstance(a.type, T.DecimalType):
        div = 10 ** a.type.scale
        return Val(jnp.floor_divide(a.data, div) * div, a.valid, out)
    if T.is_integral(a.type):
        return Val(a.data, a.valid, out)
    return Val(jnp.floor(a.data), a.valid, out)


def _long_dec_floor_ceil(a: Val, ceil: bool) -> jnp.ndarray:
    """Exact floor/ceil to integer multiples of 10**scale for long
    decimals: truncate the fraction digits by digit division, then bump
    toward -inf (floor of negatives) / +inf (ceil of positives) when
    any fraction digit was nonzero."""
    from ..ops import int128 as I
    s = a.type.scale
    m = I.abs_(a.data)
    k = s
    rem_any = jnp.zeros(a.data.shape[:-1], dtype=bool)
    while k > 0:
        step = min(k, 9)
        m, rr = I.divmod_small_abs(m, 10 ** step)
        rem_any = rem_any | (rr != 0)
        k -= step
    neg_in = I.is_neg(a.data)
    bump_rows = rem_any & (neg_in != ceil)   # floor: negatives; ceil: positives
    bump = bump_rows.astype(jnp.int64)
    m = I.add(m, I.pack(jnp.zeros_like(bump), bump))
    signed = I.where(neg_in, I.neg(m), m)
    back, _ = I.rescale(signed, s)
    return back


@register("ceil")
def _ceil(args, out):
    (a,) = args
    if _is_long_dec(a.type):
        return Val(_long_dec_floor_ceil(a, ceil=True), a.valid, out)
    if isinstance(a.type, T.DecimalType):
        div = 10 ** a.type.scale
        return Val(-(jnp.floor_divide(-a.data, div)) * div, a.valid, out)
    if T.is_integral(a.type):
        return Val(a.data, a.valid, out)
    return Val(jnp.ceil(a.data), a.valid, out)


@register("round")
def _round(args, out):
    a = args[0]
    digits = 0
    if len(args) > 1:
        # digits must be a compile-time constant (Literal-backed)
        if args[1].literal is not None:
            digits = int(args[1].literal)
        else:
            try:
                digits = int(np.asarray(args[1].data)[0])
            except Exception as e:
                raise NotImplementedError(
                    "round() with non-constant digits") from e
    if _is_long_dec(a.type):
        if digits >= a.type.scale:
            return Val(a.data, a.valid, out)   # nothing to round away
        from ..ops import int128 as I
        x, _ = I.rescale(a.data, digits - a.type.scale)  # half-up here
        x, _ = I.rescale(x, a.type.scale - digits)
        return Val(x, a.valid, out)
    if isinstance(a.type, T.DecimalType):
        if digits >= a.type.scale:
            return Val(a.data, a.valid, out)   # nothing to round away
        data = rescale_decimal(a.data, a.type.scale, digits)
        data = rescale_decimal(data, digits, a.type.scale)
        return Val(data, a.valid, out)
    scale = 10.0 ** digits
    x = a.data * scale
    data = jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5) / scale
    return Val(data, a.valid, out)


@register("power")
def _power(args, out):
    a, b = (cast_val(x, T.DOUBLE) for x in args)
    return Val(jnp.power(a.data, b.data), a.valid & b.valid, out)


# -- datetime ----------------------------------------------------------------

def _date_part(part):
    def impl(args, out):
        (a,) = args
        days = a.data if isinstance(a.type, T.DateType) else a.data // 86_400_000_000
        y, m, d = _civil_from_days(days)
        val = {"year": y, "month": m, "day": d, "quarter": (m + 2) // 3}[part]
        return Val(val.astype(jnp.int64), a.valid, out)
    return impl


for _p in ["year", "month", "day", "quarter"]:
    register(_p)(_date_part(_p))


@register("date_add_days")
def _date_add_days(args, out):
    a, n = args
    return Val(a.data + n.data.astype(a.data.dtype), a.valid & n.valid, out)


@register("date_add_months")
def _date_add_months(args, out):
    a, n = args
    y, m, d = _civil_from_days(a.data)
    months = y * 12 + (m - 1) + n.data.astype(jnp.int64)
    ny, nm = jnp.floor_divide(months, 12), months % 12 + 1
    # clamp day to end of target month
    dim_table = jnp.asarray([31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31])
    leap = ((ny % 4 == 0) & (ny % 100 != 0)) | (ny % 400 == 0)
    dim = jnp.take(dim_table, nm - 1) + jnp.where(leap & (nm == 2), 1, 0)
    nd = jnp.minimum(d, dim)
    return Val(_days_from_civil(ny, nm, nd).astype(a.data.dtype), a.valid & n.valid, out)


@register("date_add_years")
def _date_add_years(args, out):
    a, n = args
    months = Val(n.data * 12, n.valid, n.type)
    return _date_add_months([a, months], out)


# -- strings -----------------------------------------------------------------

@register("like")
def _like(args, out):
    a, pat = args[0], args[1]
    pattern = _string_literal_of(pat)
    if pattern is None:
        raise NotImplementedError("LIKE with non-constant pattern")
    escape = None
    if len(args) > 2:
        escape = _string_literal_of(args[2])
    if a.dictionary is None:
        raise NotImplementedError("LIKE on non-dictionary column")
    rx = re.compile(_like_to_regex(pattern, escape), re.DOTALL)
    table = vocab_table(a.dictionary, lambda s: rx.fullmatch(s) is not None, np.bool_)
    return Val(_code_gather(table, a.data), a.valid, T.BOOLEAN)


def _vocab_transform(fn):
    """String->string function: transform the vocab, keep the codes."""
    def impl(args, out):
        a = args[0]
        if a.dictionary is None:
            raise NotImplementedError("string fn on non-dictionary column")
        extra = []
        for x in args[1:]:
            if x.type.is_string:
                extra.append(_string_literal_of(x))
            elif x.literal is not None:
                extra.append(int(x.literal))
            else:
                raise NotImplementedError(
                    "string function positional args must be constants")
        entries = [fn(s, *extra) for s in a.dictionary]
        # dedupe the transformed vocab and remap codes: distinct inputs can
        # map to one output (substr prefixes), and equal strings MUST share
        # one code — grouping/joins compare codes
        lookup: dict = {}
        vocab: list = []
        remap = np.empty(len(entries) + 1, dtype=np.int32)
        for i, s in enumerate(entries):
            code = lookup.get(s)
            if code is None:
                code = lookup[s] = len(vocab)
                vocab.append(s)
            remap[i] = code
        remap[-1] = -1
        if len(vocab) == len(entries):
            return Val(a.data, a.valid, out, dictionary=tuple(entries))
        codes = _code_gather(jnp.asarray(remap), a.data)
        return Val(codes, a.valid, out, dictionary=tuple(vocab))
    return impl


register("lower")(_vocab_transform(lambda s: s.lower()))
# varbinary bridge (reference operator/scalar/VarbinaryFunctions.java):
# the dictionary plan carries bytes vocabularies the same way as strings
register("to_utf8")(_vocab_transform(
    lambda s: s.encode("utf-8") if isinstance(s, str) else s))
register("from_utf8")(_vocab_transform(
    lambda s: s.decode("utf-8", "replace")
    if isinstance(s, (bytes, bytearray)) else s))
register("upper")(_vocab_transform(lambda s: s.upper()))
register("trim")(_vocab_transform(lambda s: s.strip()))
# SQL substr is 1-based
register("substr")(_vocab_transform(
    lambda s, start, length=None: s[start - 1: start - 1 + length]
    if length is not None else s[start - 1:]))


@register("length")
def _length(args, out):
    (a,) = args
    if a.dictionary is None:
        raise NotImplementedError("length on non-dictionary column")
    table = vocab_table(a.dictionary, len, np.int64)
    return Val(_code_gather(table, a.data), a.valid, out)


@register("concat")
def _concat(args, out):
    lits = [_string_literal_of(v) for v in args]
    dyn = [i for i, l in enumerate(lits) if l is None]
    if len(dyn) == 0:
        return Val.constant("".join(lits), out, args[0].data.shape[0])
    if len(dyn) == 1:
        i = dyn[0]
        a = args[i]
        if a.dictionary is None:
            raise NotImplementedError("concat on non-dictionary column")
        prefix = "".join(lits[:i])
        suffix = "".join(lits[i + 1:])
        vocab = tuple(prefix + s + suffix for s in a.dictionary)
        return Val(a.data, jnp.stack([v.valid for v in args]).all(0), out, vocab)
    raise NotImplementedError("concat of multiple non-constant strings")


# -- widened math surface (reference operator/scalar/MathFunctions.java) -----

for _name, _jfn in [
        ("sin", jnp.sin), ("cos", jnp.cos), ("tan", jnp.tan),
        ("asin", jnp.arcsin), ("acos", jnp.arccos), ("atan", jnp.arctan),
        ("sinh", jnp.sinh), ("cosh", jnp.cosh), ("tanh", jnp.tanh),
        ("log10", jnp.log10), ("cbrt", jnp.cbrt),
        ("degrees", jnp.degrees), ("radians", jnp.radians)]:
    register(_name)(_dbl_fn(_jfn))


def _log2(x):
    """``ln(x) / ln(2)``, a true division as the reference's
    (MathFunctions.log2): ``jnp.log2`` multiplies by a reciprocal where
    its operand is not a constant of the program, so ``log2(8e0)`` was
    3.0 as a literal (XLA folded it) and 2.9999999999999996 from a
    column, or from the planner's fold on the host."""
    return jnp.log(x) / jax.lax.optimization_barrier(
        jnp.asarray(math.log(2.0), x.dtype))


register("log2")(_dbl_fn(_log2))


@register("atan2")
def _atan2(args, out):
    a, b = (cast_val(x, T.DOUBLE) for x in args)
    return Val(jnp.arctan2(a.data, b.data), a.valid & b.valid, out)


@register("log")
def _log(args, out):
    # log(b, x): base-b logarithm of x (reference MathFunctions.log)
    b, x = (cast_val(v, T.DOUBLE) for v in args)
    return Val(jnp.log(x.data) / jnp.log(b.data), b.valid & x.valid, out)


@register("sign")
def _sign(args, out):
    (a,) = args
    if _is_long_dec(a.type):
        from ..ops import int128 as I
        return Val(I.sign(a.data).astype(out.storage_dtype), a.valid, out)
    # decimal input: out is decimal(1,0), so the raw -1/0/1 is already
    # correctly scaled; double/bigint keep their type
    return Val(jnp.sign(a.data).astype(out.storage_dtype), a.valid, out)


@register("truncate")
def _truncate(args, out):
    a = cast_val(args[0], T.DOUBLE)
    if len(args) == 1:
        return Val(jnp.trunc(a.data), a.valid, out)
    if args[1].literal is None:
        raise NotImplementedError("truncate() scale must be a constant")
    scale = 10.0 ** int(args[1].literal)
    return Val(jnp.trunc(a.data * scale) / scale, _all_valid(args), out)


@register("width_bucket")
def _width_bucket(args, out):
    x, lo, hi, n = (cast_val(v, T.DOUBLE) for v in args)
    frac = (x.data - lo.data) / (hi.data - lo.data)
    b = jnp.floor(frac * n.data).astype(jnp.int64) + 1
    b = jnp.clip(b, 0, n.data.astype(jnp.int64) + 1)
    return Val(b, _all_valid(args), out)


@register("is_nan")
def _is_nan(args, out):
    a = cast_val(args[0], T.DOUBLE)
    return Val(jnp.isnan(a.data), a.valid, T.BOOLEAN)


@register("is_finite")
def _is_finite(args, out):
    a = cast_val(args[0], T.DOUBLE)
    return Val(jnp.isfinite(a.data), a.valid, T.BOOLEAN)


@register("is_infinite")
def _is_infinite(args, out):
    a = cast_val(args[0], T.DOUBLE)
    return Val(jnp.isinf(a.data), a.valid, T.BOOLEAN)


def _variadic_extreme(is_max):
    def impl(args, out):
        # NULL if any argument is NULL (reference GreatestFunction)
        if out.is_string:
            # dictionary codes are insertion-ordered, not lexicographic
            raise NotImplementedError("greatest/least on varchar")
        vals = [cast_val(a, out) for a in args]
        data = vals[0].data
        for v in vals[1:]:
            data = jnp.maximum(data, v.data) if is_max else jnp.minimum(data, v.data)
        return Val(data, _all_valid(vals), out)
    return impl


register("greatest")(_variadic_extreme(True))
register("least")(_variadic_extreme(False))


# -- bitwise (reference operator/scalar/BitwiseFunctions.java) ---------------

def _bitwise(fn):
    def impl(args, out):
        vals = [cast_val(a, T.BIGINT) for a in args]
        return Val(fn(*[v.data for v in vals]), _all_valid(vals), out)
    return impl


register("bitwise_and")(_bitwise(jnp.bitwise_and))
register("bitwise_or")(_bitwise(jnp.bitwise_or))
register("bitwise_xor")(_bitwise(jnp.bitwise_xor))
register("bitwise_not")(_bitwise(jnp.bitwise_not))
register("bitwise_left_shift")(_bitwise(lambda a, n: a << n))
register("bitwise_right_shift")(
    _bitwise(lambda a, n: ((a.astype(jnp.uint64)) >> n.astype(jnp.uint64))
             .astype(jnp.int64)))
register("bitwise_arithmetic_shift_right")(_bitwise(lambda a, n: a >> n))


@register("bit_count")
def _bit_count(args, out):
    import jax.lax as lax
    a = cast_val(args[0], T.BIGINT)
    bits = 64
    if len(args) > 1:
        if args[1].literal is None:
            raise NotImplementedError("bit_count() bits must be a constant")
        bits = int(args[1].literal)
    data = a.data if bits == 64 else a.data & ((1 << bits) - 1)
    return Val(lax.population_count(data.astype(jnp.uint64)).astype(jnp.int64),
               a.valid, out)


# -- widened strings (reference operator/scalar/StringFunctions.java) --------

register("replace")(_vocab_transform(
    lambda s, find, repl="": s.replace(find, repl)))
register("reverse")(_vocab_transform(lambda s: s[::-1]))
register("lpad")(_vocab_transform(
    lambda s, n, pad=" ": s[:n] if len(s) >= n
    else ((pad * n)[: n - len(s)] + s if pad else s)))
register("rpad")(_vocab_transform(
    lambda s, n, pad=" ": s[:n] if len(s) >= n
    else (s + (pad * n)[: n - len(s)] if pad else s)))
register("ltrim")(_vocab_transform(lambda s: s.lstrip()))
register("rtrim")(_vocab_transform(lambda s: s.rstrip()))
def _vocab_transform_nullable(fn):
    """Like _vocab_transform but fn may return None (SQL NULL): the null
    slots clear validity and the output vocab is deduplicated so equal
    strings share one code (required by code-comparing joins/grouping)."""
    def impl(args, out):
        a = args[0]
        if a.dictionary is None:
            raise NotImplementedError("string fn on non-dictionary column")
        extra = []
        for x in args[1:]:
            lit = _string_literal_of(x) if x.type.is_string else x.literal
            if lit is None:
                raise NotImplementedError(
                    "string function positional args must be constants")
            extra.append(lit)
        entries = [fn(s, *extra) for s in a.dictionary]
        lookup: dict = {}
        vocab: list = []
        remap = np.empty(len(entries) + 1, dtype=np.int32)
        for i, s in enumerate(entries):
            if s is None:
                remap[i] = -1
                continue
            code = lookup.get(s)
            if code is None:
                code = lookup[s] = len(vocab)
                vocab.append(s)
            remap[i] = code
        remap[-1] = -1
        codes = _code_gather(jnp.asarray(remap), a.data)
        return Val(codes, a.valid & (codes >= 0), out,
                   dictionary=tuple(vocab))
    return impl


def _split_part(s: str, delim: str, idx: int) -> Optional[str]:
    if idx <= 0:
        # constant index: raised at trace time like Presto's
        # INVALID_FUNCTION_ARGUMENT for non-positive indexes
        from ..errors import INVALID_FUNCTION_ARGUMENT, QueryError
        raise QueryError(INVALID_FUNCTION_ARGUMENT,
                         "split_part index must be greater than zero")
    if not delim:
        return s if idx == 1 else None
    parts = s.split(delim)
    return parts[idx - 1] if idx <= len(parts) else None


register("split_part")(_vocab_transform_nullable(_split_part))


def _vocab_int_fn(fn):
    """String->bigint function via a host-computed vocab table."""
    def impl(args, out):
        a = args[0]
        if a.dictionary is None:
            raise NotImplementedError("string fn on non-dictionary column")
        extra = []
        for x in args[1:]:
            lit = _string_literal_of(x) if x.type.is_string else x.literal
            if lit is None:
                raise NotImplementedError(
                    "string function positional args must be constants")
            extra.append(lit)
        table = vocab_table(a.dictionary, lambda s: fn(s, *extra), np.int64)
        return Val(_code_gather(table, a.data), a.valid, out)
    return impl


register("strpos")(_vocab_int_fn(lambda s, sub: s.find(sub) + 1))
register("codepoint")(_vocab_int_fn(lambda s: ord(s[0]) if s else 0))
register("levenshtein_distance")(_vocab_int_fn(
    lambda s, t: _levenshtein(s, t)))


def _levenshtein(s: str, t: str) -> int:
    if len(s) < len(t):
        s, t = t, s
    prev = list(range(len(t) + 1))
    for i, cs in enumerate(s, 1):
        cur = [i]
        for j, ct in enumerate(t, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (cs != ct)))
        prev = cur
    return prev[-1]


def _vocab_bool_fn(fn):
    def impl(args, out):
        a = args[0]
        if a.dictionary is None:
            raise NotImplementedError("string fn on non-dictionary column")
        extra = []
        for x in args[1:]:
            lit = _string_literal_of(x) if x.type.is_string else x.literal
            if lit is None:
                raise NotImplementedError(
                    "string function positional args must be constants")
            extra.append(lit)
        table = vocab_table(a.dictionary, lambda s: fn(s, *extra), np.bool_)
        return Val(_code_gather(table, a.data), a.valid, T.BOOLEAN)
    return impl


register("starts_with")(_vocab_bool_fn(lambda s, p: s.startswith(p)))
register("ends_with")(_vocab_bool_fn(lambda s, p: s.endswith(p)))
# reference operator/scalar/StringFunctions.java translate(): chars in
# `from` map positionally to `to`; unmatched positions delete
register("translate")(_vocab_transform(
    lambda s, frm, to: s.translate(
        {ord(c): (to[i] if i < len(to) else None)
         for i, c in enumerate(frm)})))
# deviation: the reference raises for unequal lengths
# (StringFunctions.hammingDistance); the vocab-table evaluation path has
# no per-entry error channel, so unequal lengths count their difference
register("hamming_distance")(_vocab_int_fn(
    lambda s, t: sum(a != b for a, b in zip(s, t))
    + abs(len(s) - len(t))))


def _presto_replacement(repl: str) -> str:
    """Presto/Java replacement syntax -> Python re.sub template:
    $n / ${name} are group refs, \\$ is a literal dollar."""
    out = []
    i = 0
    while i < len(repl):
        c = repl[i]
        if c == "\\" and i + 1 < len(repl):
            nxt = repl[i + 1]
            out.append(nxt if nxt in ("$", "\\") else "\\" + nxt)
            i += 2
        elif c == "$" and i + 1 < len(repl):
            j = i + 1
            if repl[j] == "{":
                end = repl.index("}", j)
                out.append(f"\\g<{repl[j + 1:end]}>")
                i = end + 1
            elif repl[j].isdigit():
                while j < len(repl) and repl[j].isdigit():
                    j += 1
                out.append(f"\\g<{repl[i + 1:j]}>")
                i = j
            else:
                out.append("$")
                i += 1
        else:
            out.append("\\\\" if c == "\\" else c)
            i += 1
    return "".join(out)


# regex: host-compiled over the static vocab — the TPU answer to Joni/RE2J
# (reference operator/scalar/JoniRegexpFunctions.java); patterns must be
# constants, which they virtually always are in SQL
register("regexp_like")(_vocab_bool_fn(
    lambda s, pat: re.search(pat, s) is not None))
register("regexp_extract")(_vocab_transform_nullable(
    lambda s, pat, group=0: (
        (lambda m: m.group(group) if m else None)(re.search(pat, s)))))
register("regexp_replace")(_vocab_transform(
    lambda s, pat, repl="": re.sub(pat, _presto_replacement(repl), s)))


def _json_extract_scalar(doc: str, path: str):
    """Tiny JSONPath: $.key / [idx] steps only (the common Presto usage)."""
    import json as _json
    try:
        v = _json.loads(doc)
    except Exception:
        return None
    if not path.startswith("$"):
        return None
    i = 1
    while i < len(path):
        if path[i] == ".":
            j = i + 1
            while j < len(path) and path[j] not in ".[":
                j += 1
            key = path[i + 1: j]
            if not isinstance(v, dict) or key not in v:
                return None
            v = v[key]
            i = j
        elif path[i] == "[":
            j = path.index("]", i)
            token = path[i + 1: j].strip("\"'")
            if isinstance(v, list):
                try:
                    v = v[int(token)]
                except (ValueError, IndexError):
                    return None
            elif isinstance(v, dict):
                if token not in v:
                    return None
                v = v[token]
            else:
                return None
            i = j + 1
        else:
            return None
    if isinstance(v, (dict, list)):
        return None      # scalar extraction only
    if isinstance(v, bool):
        return "true" if v else "false"
    if v is None:
        return None
    return str(v)


register("json_extract_scalar")(
    _vocab_transform_nullable(_json_extract_scalar))


# -- URL functions (reference operator/scalar/UrlFunctions.java) -------------

def _url_part(part):
    from urllib.parse import urlparse

    def get(s: str) -> str:
        try:
            u = urlparse(s)
        except Exception:
            return ""
        return {"protocol": u.scheme, "host": u.hostname or "",
                "path": u.path, "query": u.query,
                "fragment": u.fragment}[part]
    return get


for _p in ["protocol", "host", "path", "query", "fragment"]:
    register(f"url_extract_{_p}")(_vocab_transform(_url_part(_p)))


@register("url_extract_port")
def _url_extract_port(args, out):
    from urllib.parse import urlparse
    a = args[0]
    if a.dictionary is None:
        raise NotImplementedError("url fn on non-dictionary column")

    def port(s):
        try:
            p = urlparse(s).port
        except Exception:
            p = None
        return -1 if p is None else p
    table = vocab_table(a.dictionary, port, np.int64)
    vals = _code_gather(table, a.data)
    return Val(vals, a.valid & (vals >= 0), out)


# -- widened datetime (reference operator/scalar/DateTimeFunctions.java) -----

_US_PER = {"millisecond": 1_000, "second": 1_000_000,
           "minute": 60_000_000, "hour": 3_600_000_000,
           "day": 86_400_000_000, "week": 7 * 86_400_000_000}


def _to_micros(v: Val) -> jnp.ndarray:
    if isinstance(v.type, T.DateType):
        return v.data.astype(jnp.int64) * 86_400_000_000
    return v.data.astype(jnp.int64)


@register("day_of_week")
def _day_of_week(args, out):
    (a,) = args
    days = a.data if isinstance(a.type, T.DateType) else a.data // 86_400_000_000
    # ISO: Monday=1..Sunday=7; 1970-01-01 was a Thursday (=4)
    dow = (days.astype(jnp.int64) + 3) % 7 + 1
    return Val(dow, a.valid, out)


@register("day_of_year")
def _day_of_year(args, out):
    (a,) = args
    days = a.data if isinstance(a.type, T.DateType) else a.data // 86_400_000_000
    y, _, _ = _civil_from_days(days)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return Val(days.astype(jnp.int64) - jan1 + 1, a.valid, out)


def _iso_week(days: jnp.ndarray):
    """ISO-8601 (week, week-year), branch-free."""
    days = days.astype(jnp.int64)
    y, _, _ = _civil_from_days(days)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    doy = days - jan1 + 1
    isodow = (days + 3) % 7 + 1

    def weeks_in(year):
        # 53-week years: Jan 1 is Thursday, or leap year starting Wednesday
        jan1d = _days_from_civil(year, jnp.ones_like(year),
                                 jnp.ones_like(year))
        dow1 = (jan1d + 3) % 7 + 1
        leap = ((year % 4 == 0) & (year % 100 != 0)) | (year % 400 == 0)
        return jnp.where((dow1 == 4) | (leap & (dow1 == 3)), 53, 52)

    w = (doy - isodow + 10) // 7
    week = jnp.where(w < 1, weeks_in(y - 1), jnp.where(w > weeks_in(y), 1, w))
    wyear = jnp.where(w < 1, y - 1, jnp.where(w > weeks_in(y), y + 1, y))
    return week, wyear


@register("week")
def _week(args, out):
    (a,) = args
    days = a.data if isinstance(a.type, T.DateType) else a.data // 86_400_000_000
    week, _ = _iso_week(days)
    return Val(week, a.valid, out)


@register("year_of_week")
def _year_of_week(args, out):
    (a,) = args
    days = a.data if isinstance(a.type, T.DateType) else a.data // 86_400_000_000
    _, wyear = _iso_week(days)
    return Val(wyear, a.valid, out)


def _time_part(part):
    div = {"hour": 3_600_000_000, "minute": 60_000_000,
           "second": 1_000_000, "millisecond": 1_000}[part]
    mod = {"hour": 24, "minute": 60, "second": 60, "millisecond": 1000}[part]

    def impl(args, out):
        (a,) = args
        us = _to_micros(a)
        return Val(jnp.floor_divide(us, div) % mod, a.valid, out)
    return impl


for _p in ["hour", "minute", "second", "millisecond"]:
    register(_p)(_time_part(_p))


@register("date_trunc")
def _date_trunc(args, out):
    unit_v, a = args
    unit = _string_literal_of(unit_v)
    if unit is None:
        raise NotImplementedError("date_trunc needs a constant unit")
    unit = unit.lower()
    is_date = isinstance(a.type, T.DateType)
    days = a.data.astype(jnp.int64) if is_date else a.data // 86_400_000_000
    if unit in ("millisecond", "second", "minute", "hour"):
        if is_date:
            return Val(a.data, a.valid, out)
        q = _US_PER[unit]
        return Val(jnp.floor_divide(a.data, q) * q, a.valid, out)
    if unit == "day":
        td = days
    elif unit == "week":
        td = days - ((days + 3) % 7)          # back to Monday
    elif unit in ("month", "quarter", "year"):
        y, m, _ = _civil_from_days(days)
        if unit == "month":
            tm = m
        elif unit == "quarter":
            tm = ((m - 1) // 3) * 3 + 1
        else:
            tm = jnp.ones_like(m)
        td = _days_from_civil(y, tm, jnp.ones_like(m))
    else:
        raise NotImplementedError(f"date_trunc({unit!r})")
    if is_date:
        return Val(td.astype(a.data.dtype), a.valid, out)
    return Val(td * 86_400_000_000, a.valid, out)


@register("date_diff")
def _date_diff(args, out):
    unit_v, a, b = args
    unit = _string_literal_of(unit_v)
    if unit is None:
        raise NotImplementedError("date_diff needs a constant unit")
    unit = unit.lower()
    valid = a.valid & b.valid
    if unit in _US_PER:
        delta = _to_micros(b) - _to_micros(a)
        q = _US_PER[unit]
        return Val(jnp.sign(delta) * (jnp.abs(delta) // q), valid, out)
    da = _to_micros(a) // 86_400_000_000
    db = _to_micros(b) // 86_400_000_000
    ya, ma, dda = _civil_from_days(da)
    yb, mb, ddb = _civil_from_days(db)
    months = (yb * 12 + mb) - (ya * 12 + ma)
    # complete months only (Joda monthsBetween semantics)
    months = months - jnp.where((months > 0) & (ddb < dda), 1, 0) \
        + jnp.where((months < 0) & (ddb > dda), 1, 0)
    if unit == "month":
        val = months
    elif unit == "quarter":
        val = jnp.sign(months) * (jnp.abs(months) // 3)
    elif unit == "year":
        val = jnp.sign(months) * (jnp.abs(months) // 12)
    else:
        raise NotImplementedError(f"date_diff({unit!r})")
    return Val(val, valid, out)


@register("date_add")
def _date_add(args, out):
    unit_v, n, a = args
    unit = _string_literal_of(unit_v)
    if unit is None:
        raise NotImplementedError("date_add needs a constant unit")
    unit = unit.lower()
    valid = a.valid & n.valid
    if unit in ("month", "quarter", "year"):
        mult = {"month": 1, "quarter": 3, "year": 12}[unit]
        is_date = isinstance(a.type, T.DateType)
        days = a.data.astype(jnp.int64) if is_date \
            else a.data // 86_400_000_000
        rem = jnp.zeros_like(days) if is_date else a.data % 86_400_000_000
        shifted = _date_add_months(
            [Val(days, a.valid, T.DATE),
             Val(n.data.astype(jnp.int64) * mult, n.valid, n.type)], T.DATE)
        if is_date:
            return Val(shifted.data.astype(a.data.dtype), valid, out)
        return Val(shifted.data * 86_400_000_000 + rem, valid, out)
    q = _US_PER.get(unit)
    if q is None:
        raise NotImplementedError(f"date_add({unit!r})")
    if isinstance(a.type, T.DateType):
        if unit in ("day", "week"):
            days = q // 86_400_000_000
            return Val(a.data + (n.data * days).astype(a.data.dtype),
                       valid, out)
        raise NotImplementedError("date_add of sub-day unit to a DATE")
    return Val(a.data + n.data.astype(jnp.int64) * q, valid, out)


@register("last_day_of_month")
def _last_day_of_month(args, out):
    (a,) = args
    is_date = isinstance(a.type, T.DateType)
    days = a.data.astype(jnp.int64) if is_date else a.data // 86_400_000_000
    y, m, _ = _civil_from_days(days)
    ny = jnp.where(m == 12, y + 1, y)
    nm = jnp.where(m == 12, 1, m + 1)
    td = _days_from_civil(ny, nm, jnp.ones_like(m)) - 1
    return Val(td.astype(jnp.int32), a.valid, out)


@register("from_unixtime")
def _from_unixtime(args, out):
    a = cast_val(args[0], T.DOUBLE)
    return Val((a.data * 1_000_000.0).astype(jnp.int64), a.valid, out)


@register("to_unixtime")
def _to_unixtime(args, out):
    (a,) = args
    return Val(_to_micros(a).astype(jnp.float64) / 1_000_000.0, a.valid, out)


def infer_call_type(name: str, arg_types: List[Type]) -> Type:
    """Return type inference for scalar calls (used by the analyzer).

    Mirrors the signature-resolution role of FunctionRegistry.resolveFunction
    (reference metadata/FunctionRegistry.java) for the engine's builtins.
    """
    if name in ("eq", "ne", "lt", "le", "gt", "ge", "not", "like"):
        return T.BOOLEAN
    if name in ("add", "subtract", "multiply", "divide", "modulus"):
        a, b = arg_types
        if isinstance(a, T.DecimalType) or isinstance(b, T.DecimalType):
            # Presto's decimal operator signatures (reference
            # type/DecimalOperators.java), precision saturating at the
            # Int128-backed MAX_PRECISION 38
            sa = a.scale if isinstance(a, T.DecimalType) else 0
            pa = a.precision if isinstance(a, T.DecimalType) else 19
            sb = b.scale if isinstance(b, T.DecimalType) else 0
            pb = b.precision if isinstance(b, T.DecimalType) else 19
            if T.is_floating(a) or T.is_floating(b):
                return T.DOUBLE
            if name == "multiply":
                return T.DecimalType(min(38, pa + pb), min(38, sa + sb))
            if name == "divide":
                s = max(sa, sb)
                p = min(38, pa + sb + max(0, sb - sa))
                return T.DecimalType(max(p, s), s)
            s = max(sa, sb)
            p = min(38, max(pa - sa, pb - sb) + s + 1)
            return T.DecimalType(p, s)
        t = T.common_super_type(a, b)
        if t is None:
            raise TypeError(f"{name}({a.display()}, {b.display()})")
        return t
    if name == "negate" or name == "abs":
        return arg_types[0]
    if name == "sign":
        # sign(decimal) -> decimal(1,0) (reference MathFunctions.signDecimal)
        if isinstance(arg_types[0], T.DecimalType):
            return T.DecimalType(1, 0)
        return arg_types[0]
    if name in ("sqrt", "ln", "exp", "power", "sin", "cos", "tan", "asin",
                "acos", "atan", "atan2", "sinh", "cosh", "tanh", "log2",
                "log10", "log", "cbrt", "degrees", "radians", "truncate",
                "to_unixtime"):
        return T.DOUBLE
    if name in ("floor", "ceil", "round"):
        return arg_types[0]
    if name in ("year", "month", "day", "quarter", "day_of_week",
                "day_of_year", "week", "year_of_week", "hour", "minute",
                "second", "millisecond", "date_diff", "width_bucket",
                "strpos", "codepoint", "levenshtein_distance",
                "hamming_distance", "bit_count",
                "url_extract_port", "bitwise_and", "bitwise_or",
                "bitwise_xor", "bitwise_not", "bitwise_left_shift",
                "bitwise_right_shift", "bitwise_arithmetic_shift_right"):
        return T.BIGINT
    if name in ("is_nan", "is_finite", "is_infinite", "starts_with",
                "ends_with", "regexp_like"):
        return T.BOOLEAN
    if name in ("greatest", "least"):
        out = arg_types[0]
        for t in arg_types[1:]:
            nxt = T.common_super_type(out, t)
            if nxt is None:
                raise TypeError(f"{name} args have incompatible types")
            out = nxt
        return out
    if name in ("date_add_days", "date_add_months", "date_add_years"):
        return arg_types[0]
    if name == "date_trunc":
        return arg_types[1]
    if name == "date_add":
        return arg_types[2]
    if name == "last_day_of_month":
        return T.DATE
    if name == "from_unixtime":
        return T.TIMESTAMP
    if name in ("lower", "upper", "trim", "ltrim", "rtrim", "substr",
                "translate",
                "concat", "replace", "reverse", "lpad", "rpad", "split_part",
                "regexp_extract", "regexp_replace", "json_extract_scalar",
                "url_extract_protocol", "url_extract_host",
                "url_extract_path", "url_extract_query",
                "url_extract_fragment"):
        return T.VARCHAR
    if name == "length":
        return T.BIGINT
    if name == "to_utf8":
        return T.VARBINARY
    if name == "from_utf8":
        return T.VARCHAR
    if name in _EXTERNAL_SIGNATURES:
        return _EXTERNAL_SIGNATURES[name](list(arg_types))
    raise KeyError(f"unknown function {name!r}")
