"""Predicate expressions: comparisons, boolean logic, null tests.

Ref: org/apache/spark/sql/rapids/predicates.scala and GpuOverrides rules
(EqualTo, LessThan, And, Or, Not, IsNull, IsNotNull, IsNaN, In, InSet,
EqualNullSafe).

Spark semantics implemented here:
  * three-valued AND/OR (FALSE AND NULL = FALSE, TRUE OR NULL = TRUE);
  * NaN equals NaN and sorts greater than every other double (Spark's
    total order), unlike IEEE;
  * string comparisons via the byte-tensor kernels in ops/strings.py.
"""

from __future__ import annotations

import numpy as np

from .. import types as t
from ..ops import strings as sops
from .arithmetic import cast_data, promote
from .core import (ColumnValue, EvalContext, Expression, ScalarValue, Value,
                   and_validity, data_of, evaluator, make_column, validity_of)


def scalar_string_keys(s: bytes):
    """Host-side prefix words + rolling hashes of a constant string, matching
    ops/strings.py kernels bit-for-bit."""
    mod = 1 << 64
    h = []
    for base in (int(sops._HASH_BASE_1), int(sops._HASH_BASE_2)):
        acc, p = 0, 1
        for c in s:
            acc = (acc + (c + 1) * p) % mod
            p = (p * base) % mod
        h.append(np.uint64(acc))
    padded = s[:sops.PREFIX_BYTES].ljust(sops.PREFIX_BYTES, b"\0")
    words = [np.uint64(int.from_bytes(padded[i * 8:(i + 1) * 8], "big"))
             for i in range(sops.PREFIX_BYTES // 8)]
    return words, h[0], h[1], np.int32(len(s))


class BinaryComparison(Expression):
    symbol = "?"

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    @property
    def left(self):
        return self.children[0]

    @property
    def right(self):
        return self.children[1]

    def data_type(self):
        return t.BOOLEAN

    def sql(self):
        return f"({self.left.sql()} {self.symbol} {self.right.sql()})"


class EqualTo(BinaryComparison):
    symbol = "="


class EqualNullSafe(BinaryComparison):
    symbol = "<=>"

    @property
    def nullable(self):
        return False


class LessThan(BinaryComparison):
    symbol = "<"


class LessThanOrEqual(BinaryComparison):
    symbol = "<="


class GreaterThan(BinaryComparison):
    symbol = ">"


class GreaterThanOrEqual(BinaryComparison):
    symbol = ">="


def _is_string(dt):
    return isinstance(dt, (t.StringType, t.BinaryType))


def _cmp_inputs(e: BinaryComparison, ctx: EvalContext):
    lv, rv = e.left.eval(ctx), e.right.eval(ctx)
    lt, rt = e.left.data_type(), e.right.data_type()
    if _is_string(lt) or _is_string(rt):
        return lv, rv, None
    common = promote(lt, rt)
    ld = cast_data(ctx, data_of(lv, ctx), lt, common)
    rd = cast_data(ctx, data_of(rv, ctx), rt, common)
    v = and_validity(ctx, validity_of(lv, ctx), validity_of(rv, ctx))
    return (ld, rd, common), v, lv  # tuple marker


def _float_like(dt):
    return isinstance(dt, (t.FloatType, t.DoubleType))


def _is_param_string(value) -> bool:
    """A hoisted string parameter: traced chars (expr/params.py)."""
    from .params import StringParam
    return isinstance(value, StringParam) or hasattr(value, "shape")


def _param_chars(xp, value):
    """A hoisted string parameter as a 1-string column: (offsets
    [0, len], chars, len).  Under a comparison the chars come padded to
    their length bucket with the length traced beside them
    (`params.StringParam`); else the length is the array's."""
    from .params import StringParam
    if isinstance(value, StringParam):
        arr = xp.asarray(value.chars, dtype=xp.uint8)
        ln = xp.asarray(value.length, dtype=xp.int32)
        return xp.stack([xp.zeros((), xp.int32), ln]), arr, ln
    arr = xp.asarray(value, dtype=xp.uint8)
    ln = np.int32(int(arr.shape[0]))
    return xp.asarray(np.array([0, ln], dtype=np.int32)), arr, ln


def _string_eq_data(ctx: EvalContext, lv: Value, rv: Value):
    xp = ctx.xp
    if isinstance(lv, ColumnValue) and isinstance(rv, ColumnValue):
        return sops.string_eq(xp, lv.col.offsets, lv.col.data,
                              rv.col.offsets, rv.col.data)
    col, scalar = (lv, rv) if isinstance(lv, ColumnValue) else (rv, lv)
    c1, c2 = sops.string_hashes(xp, col.col.offsets, col.col.data)
    lens = sops.lengths(xp, col.col.offsets)
    if _is_param_string(scalar.value):
        # ParamLiteral string: chars are a traced array, so the hashes
        # must come from the device kernel, not host-side key derivation
        offs, arr, ln = _param_chars(xp, scalar.value)
        s1, s2 = sops.string_hashes(xp, offs, arr)
        return (lens == ln) & (c1 == s1[0]) & (c2 == s2[0])
    sval = scalar.value if isinstance(scalar.value, bytes) else \
        (scalar.value or b"")
    _, h1, h2, ln = scalar_string_keys(sval)
    return (lens == ln) & (c1 == h1) & (c2 == h2)


def _string_order_lt(ctx: EvalContext, lv: Value, rv: Value, or_equal: bool):
    """a < b (or <=) via prefix-word lexicographic compare."""
    xp = ctx.xp

    def keys(v):
        if isinstance(v, ColumnValue):
            cols = sops.order_keys(xp, v.col.offsets, v.col.data)
            return cols
        if _is_param_string(v.value):  # ParamLiteral string (traced)
            offs, arr, _ = _param_chars(xp, v.value)
            cols = sops.order_keys(xp, offs, arr)
            return [xp.broadcast_to(c, (ctx.capacity,)) for c in cols]
        words, _, _, ln = scalar_string_keys(
            v.value if isinstance(v.value, bytes) else b"")
        return [xp.full((ctx.capacity,), w, dtype=xp.uint64) for w in words] + \
            [xp.full((ctx.capacity,), np.uint64(int(ln)), dtype=xp.uint64)]

    ka, kb = keys(lv), keys(rv)
    lt = xp.zeros((ctx.capacity,), dtype=bool)
    eq = xp.ones((ctx.capacity,), dtype=bool)
    for a, b in zip(ka, kb):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return (lt | eq) if or_equal else lt


@evaluator(EqualTo)
def _eval_eq(e: EqualTo, ctx: EvalContext):
    lt, rt = e.left.data_type(), e.right.data_type()
    if _is_string(lt) or _is_string(rt):
        lv, rv = e.left.eval(ctx), e.right.eval(ctx)
        data = _string_eq_data(ctx, lv, rv)
        v = and_validity(ctx, validity_of(lv, ctx), validity_of(rv, ctx))
        return make_column(ctx, t.BOOLEAN, data, v)
    (ld, rd, common), v, _ = _cmp_inputs(e, ctx)
    xp = ctx.xp
    data = ld == rd
    if _float_like(common):
        data = data | (xp.isnan(ld) & xp.isnan(rd))  # Spark: NaN = NaN
    return make_column(ctx, t.BOOLEAN, data, v)


@evaluator(EqualNullSafe)
def _eval_eq_ns(e: EqualNullSafe, ctx: EvalContext):
    xp = ctx.xp
    lv, rv = e.left.eval(ctx), e.right.eval(ctx)
    va = validity_of(lv, ctx)
    vb = validity_of(rv, ctx)

    def norm(v):
        if v is None:
            return xp.ones((ctx.capacity,), dtype=bool)
        if v is False:
            return xp.zeros((ctx.capacity,), dtype=bool)
        return v
    va, vb = norm(va), norm(vb)
    lt, rt = e.left.data_type(), e.right.data_type()
    if _is_string(lt) or _is_string(rt):
        eq = _string_eq_data(ctx, lv, rv)
    else:
        common = promote(lt, rt)
        ld = cast_data(ctx, data_of(lv, ctx), lt, common)
        rd = cast_data(ctx, data_of(rv, ctx), rt, common)
        eq = ld == rd
        if _float_like(common):
            eq = eq | (xp.isnan(ld) & xp.isnan(rd))
    data = (va & vb & eq) | (~va & ~vb)
    return make_column(ctx, t.BOOLEAN, data, None)


def _eval_ordering(e: BinaryComparison, ctx: EvalContext, flip: bool,
                   or_equal: bool):
    lt_, rt_ = e.left.data_type(), e.right.data_type()
    if _is_string(lt_) or _is_string(rt_):
        lv, rv = e.left.eval(ctx), e.right.eval(ctx)
        a, b = (rv, lv) if flip else (lv, rv)
        data = _string_order_lt(ctx, a, b, or_equal)
        v = and_validity(ctx, validity_of(lv, ctx), validity_of(rv, ctx))
        return make_column(ctx, t.BOOLEAN, data, v)
    (ld, rd, common), v, _ = _cmp_inputs(e, ctx)
    if flip:
        ld, rd = rd, ld
    xp = ctx.xp
    if _float_like(common):
        # Spark total order: NaN > everything, NaN == NaN
        a_nan, b_nan = xp.isnan(ld), xp.isnan(rd)
        lt = xp.where(a_nan, False, xp.where(b_nan, True, ld < rd))
        eqd = (ld == rd) | (a_nan & b_nan)
        data = (lt | eqd) if or_equal else lt
    else:
        data = (ld <= rd) if or_equal else (ld < rd)
    return make_column(ctx, t.BOOLEAN, data, v)


@evaluator(LessThan)
def _eval_lt(e, ctx):
    return _eval_ordering(e, ctx, flip=False, or_equal=False)


@evaluator(LessThanOrEqual)
def _eval_le(e, ctx):
    return _eval_ordering(e, ctx, flip=False, or_equal=True)


@evaluator(GreaterThan)
def _eval_gt(e, ctx):
    return _eval_ordering(e, ctx, flip=True, or_equal=False)


@evaluator(GreaterThanOrEqual)
def _eval_ge(e, ctx):
    return _eval_ordering(e, ctx, flip=True, or_equal=True)


# ---------------------------------------------------------------------------
# Boolean logic (three-valued)
# ---------------------------------------------------------------------------

class And(Expression):
    def __init__(self, left, right):
        self.children = (left, right)

    def data_type(self):
        return t.BOOLEAN

    def sql(self):
        return f"({self.children[0].sql()} AND {self.children[1].sql()})"


class Or(Expression):
    def __init__(self, left, right):
        self.children = (left, right)

    def data_type(self):
        return t.BOOLEAN

    def sql(self):
        return f"({self.children[0].sql()} OR {self.children[1].sql()})"


class Not(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self):
        return t.BOOLEAN

    def sql(self):
        return f"(NOT {self.children[0].sql()})"


def _bool_parts(ctx, v):
    xp = ctx.xp
    d = data_of(v, ctx)
    if not hasattr(d, "shape") or getattr(d, "shape", ()) == ():
        d = xp.full((ctx.capacity,), bool(d))
    val = validity_of(v, ctx)
    if val is None:
        val = xp.ones((ctx.capacity,), dtype=bool)
    elif val is False:
        val = xp.zeros((ctx.capacity,), dtype=bool)
    return d.astype(bool), val


@evaluator(And)
def _eval_and(e: And, ctx: EvalContext):
    da, va = _bool_parts(ctx, e.children[0].eval(ctx))
    db, vb = _bool_parts(ctx, e.children[1].eval(ctx))
    data = da & db & va & vb
    validity = (va & vb) | (va & ~da) | (vb & ~db)
    return make_column(ctx, t.BOOLEAN, data, validity)


@evaluator(Or)
def _eval_or(e: Or, ctx: EvalContext):
    da, va = _bool_parts(ctx, e.children[0].eval(ctx))
    db, vb = _bool_parts(ctx, e.children[1].eval(ctx))
    data = (da & va) | (db & vb)
    validity = (va & vb) | (va & da) | (vb & db)
    return make_column(ctx, t.BOOLEAN, data, validity)


@evaluator(Not)
def _eval_not(e: Not, ctx: EvalContext):
    d, v = _bool_parts(ctx, e.children[0].eval(ctx))
    return make_column(ctx, t.BOOLEAN, ~d & v, v)


# ---------------------------------------------------------------------------
# Null tests
# ---------------------------------------------------------------------------

class IsNull(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self):
        return t.BOOLEAN

    @property
    def nullable(self):
        return False

    def sql(self):
        return f"({self.children[0].sql()} IS NULL)"


class IsNotNull(IsNull):
    def sql(self):
        return f"({self.children[0].sql()} IS NOT NULL)"


class IsNaN(Expression):
    def __init__(self, child):
        self.children = (child,)

    def data_type(self):
        return t.BOOLEAN

    @property
    def nullable(self):
        return False


@evaluator(IsNull)
def _eval_isnull(e: IsNull, ctx: EvalContext):
    v = e.children[0].eval(ctx)
    val = validity_of(v, ctx)
    xp = ctx.xp
    if val is None:
        data = xp.zeros((ctx.capacity,), dtype=bool)
    elif val is False:
        data = xp.ones((ctx.capacity,), dtype=bool)
    else:
        data = ~val
    if type(e) is IsNotNull:
        data = ~data
    return make_column(ctx, t.BOOLEAN, data, None)


_EVAL_ISNOTNULL = _eval_isnull
from .core import _EVALUATORS  # noqa: E402
_EVALUATORS[IsNotNull] = _eval_isnull


@evaluator(IsNaN)
def _eval_isnan(e: IsNaN, ctx: EvalContext):
    v = e.children[0].eval(ctx)
    d = data_of(v, ctx)
    data = ctx.xp.isnan(d) if _float_like(e.children[0].data_type()) else \
        ctx.xp.zeros((ctx.capacity,), dtype=bool)
    val = validity_of(v, ctx)
    # Spark IsNaN(null) = false (non-nullable output)
    if val is not None and val is not False:
        data = data & val
    elif val is False:
        data = ctx.xp.zeros((ctx.capacity,), dtype=bool)
    return make_column(ctx, t.BOOLEAN, data, None)


# ---------------------------------------------------------------------------
# IN
# ---------------------------------------------------------------------------

class In(Expression):
    """value IN (literals...) — Spark null semantics: NULL if value is null,
    or if no match and the list contains a null."""

    def __init__(self, value: Expression, items):
        self.children = (value,)
        self.items = tuple(items)  # Literal expressions

    def data_type(self):
        return t.BOOLEAN

    def sql(self):
        return (f"({self.children[0].sql()} IN "
                f"({', '.join(i.sql() for i in self.items)}))")


@evaluator(In)
def _eval_in(e: In, ctx: EvalContext):
    from .core import Literal
    xp = ctx.xp
    v = e.children[0].eval(ctx)
    val = validity_of(v, ctx)
    has_null_item = any(i.value is None for i in e.items)
    matched = xp.zeros((ctx.capacity,), dtype=bool)
    dt = e.children[0].data_type()
    for item in e.items:
        if item.value is None:
            continue
        # eval (not .value): a ParamLiteral item resolves to the traced
        # call-time scalar when params are bound
        iv = item.eval(ctx)
        if _is_string(dt):
            eq = _string_eq_data(ctx, v, iv)
        else:
            common = promote(dt, item.dtype)
            ld = cast_data(ctx, data_of(v, ctx), dt, common)
            rd = cast_data(ctx, iv.value, item.dtype, common)
            eq = ld == rd
        matched = matched | eq
    if val is None:
        val = xp.ones((ctx.capacity,), dtype=bool)
    elif val is False:
        val = xp.zeros((ctx.capacity,), dtype=bool)
    validity = val & (matched | (xp.ones((ctx.capacity,), bool)
                                 if not has_null_item else matched))
    return make_column(ctx, t.BOOLEAN, matched & val, validity)
