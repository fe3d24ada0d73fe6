"""Literal parameterization: hoist constant scalars out of traced
closures so structurally identical queries share one compiled program.

Ref: the reference plugin amortizes kernel setup across queries through
its process-wide execution layer; here the analogous win is collapsing
the jit key space.  A bound expression tree like ``v > 5`` bakes the
``5`` into the traced computation, so ``v > 9999`` — the same program
shape — compiles a second XLA program.  `parameterize_exprs` rewrites
eligible ``Literal`` nodes into `ParamLiteral` slots whose values ride
into the kernel as *traced scalar arguments*; the jit key then carries
only (slot, dtype) and the two queries dispatch to one executable.

Safety rules (wrong sharing is silently wrong results, so the pass is
deliberately conservative):

* only literals under whitelisted parents (plain comparisons, +/-/*
  arithmetic, If/CaseWhen value arms and IN item lists) are hoisted —
  those evaluators are pure array math with no host-side branching on
  the scalar's VALUE.  Divide/Pmod and friends stay value-keyed
  (zero-divisor handling), as do decimal / boolean literals (scale
  logic and ``bool()`` coercion concretize the value).
* string literals hoist as traced uint8 chars; the string evaluators
  reachable from the whitelisted parents derive hashes / order keys /
  broadcast columns on DEVICE from them.  Under a comparison (=, <=>,
  <, <=, >, >=, IN) the chars ride padded to a length bucket
  (`string_pad_len`: 16, 32, 64, ... bytes) with the byte length beside
  them as a traced scalar (`StringParam`), so only the BUCKET is in the
  jit key: `s = 'BUILDING'` and `s = 'AUTOMOBILE'` dispatch to one
  executable, as a dashboard that walks a column's values needs (the
  hashes and prefix words read the chars through offsets [0, length],
  so the padding is never seen).  Under If / CaseWhen value arms the
  literal is tiled into a column, whose layout follows the length: there
  the exact byte length stays in the key and only same-length strings
  share a program.
* non-null values only: null literals flow through evaluator validity
  short-circuits that branch on ``is_null``.
* a parameterized tree may key a jit entry ONLY where the parameter
  values are actually threaded as call arguments — `ParamLiteral`'s
  evaluator falls back to the baked value when no params are bound, so
  host-path (numpy) evaluation needs no threading, but a traced closure
  built from a parameterized tree without passing params would bake the
  first query's constants under a shared key.  The exec-side helpers in
  exec/basic.py are the reference wiring.
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import numpy as np

from .. import types as t
from .core import (EvalContext, Expression, LeafExpression, Literal,
                   ScalarValue, evaluator)

# parents whose evaluators treat both operands as opaque array operands
# (promote + cast + xp op): safe to feed a traced scalar
from .arithmetic import Add, Multiply, Subtract
from .conditional import CaseWhen, If
from .predicates import (EqualNullSafe, EqualTo, GreaterThan,
                         GreaterThanOrEqual, In, LessThan,
                         LessThanOrEqual)

PARAM_PARENTS = (EqualTo, EqualNullSafe, LessThan, LessThanOrEqual,
                 GreaterThan, GreaterThanOrEqual,
                 Add, Subtract, Multiply,
                 # value arms blend via _value_parts / _string_select
                 # (xp.full / device gather — no host branching)
                 If, CaseWhen)

# parents that read a string operand through offsets and a length, so
# that a hoisted literal may ride padded (`StringParam`)
_STRING_PAD_PARENTS = (EqualTo, EqualNullSafe, LessThan, LessThanOrEqual,
                       GreaterThan, GreaterThanOrEqual, In)
_STRING_PAD_MIN = 16


def string_pad_len(n: int) -> int:
    """The length bucket of an n-byte hoisted string: the next power of
    two, 16 at the least."""
    bucket = _STRING_PAD_MIN
    while bucket < n:
        bucket *= 2
    return bucket


class StringParam(NamedTuple):
    """A hoisted string literal under a comparison, as it rides into the
    program: the utf-8 chars padded with zeros to their length bucket,
    and the byte length."""
    chars: object     # uint8[string_pad_len(length)]
    length: object    # int32 scalar


# value domains whose evaluators never concretize the scalar: fixed-
# width numerics and the day/microsecond integer encodings
_PARAM_DTYPES = (t.ByteType, t.ShortType, t.IntegerType, t.LongType,
                 t.FloatType, t.DoubleType, t.DateType, t.TimestampType)


class ParamLiteral(LeafExpression):
    """A literal hoisted to runtime-parameter slot `slot`.

    Keeps the original value so unparameterized evaluation (numpy host
    path, plan printing) behaves exactly like the `Literal` it
    replaced; the semantic signature deliberately EXCLUDES the value —
    that is the whole point."""

    def __init__(self, slot: int, dtype: t.DataType, value,
                 padded: bool = False):
        self.slot = slot
        self.dtype = dtype
        self.value = value
        #: a string under a comparison: rides as a `StringParam`
        self.padded = padded

    def data_type(self):
        return self.dtype

    @property
    def nullable(self):
        return False

    def _semantic_sig_(self):
        if isinstance(self.dtype, t.StringType):
            # the traced uint8 array's (static) shape stays in the key:
            # the length bucket where the chars ride padded, else the
            # byte length
            return ("ParamLiteral", self.slot, repr(self.dtype),
                    ("pad", string_pad_len(len(self.value)))
                    if self.padded else len(self.value))
        return ("ParamLiteral", self.slot, repr(self.dtype))

    def sql(self):
        return f"$param{self.slot}"


@evaluator(ParamLiteral)
def _eval_param_literal(e: ParamLiteral, ctx: EvalContext):
    params = getattr(ctx, "params", None)
    if params is not None:
        return ScalarValue(params[e.slot], e.dtype)
    return ScalarValue(e.value, e.dtype)


def _eligible(lit: Expression) -> bool:
    if type(lit) is not Literal or lit.value is None:
        return False
    if isinstance(lit.dtype, _PARAM_DTYPES):
        return True
    # strings hoist as char arrays (empty strings stay baked: a
    # zero-length traced operand buys nothing and the string kernels
    # assume at least one char of backing data)
    return isinstance(lit.dtype, t.StringType) and len(lit.value) > 0


def _np_param(lit, padded: bool = False):
    """The slot's call-time value: an np scalar typed from the literal's
    DataType (strings: the utf-8 chars as a uint8 array, or padded with
    their length as a `StringParam`) so the jit dispatch signature is
    value-independent."""
    if isinstance(lit.dtype, t.StringType):
        chars = np.frombuffer(lit.value, dtype=np.uint8)
        if not padded:
            return chars
        room = np.zeros(string_pad_len(len(chars)), np.uint8)
        room[:len(chars)] = chars
        return StringParam(room, np.int32(len(chars)))
    return np.dtype(t.to_np_dtype(lit.dtype)).type(lit.value)


def _rewrite(e: Expression, values: List) -> Expression:
    new_children = []
    changed = False
    hoist = isinstance(e, PARAM_PARENTS)
    padded = isinstance(e, _STRING_PAD_PARENTS)
    for c in e.children:
        if hoist and _eligible(c):
            values.append(_np_param(c, padded))
            nc = ParamLiteral(len(values) - 1, c.dtype, c.value, padded)
        else:
            nc = _rewrite(c, values)
        changed |= nc is not c
        new_children.append(nc)
    node = e.with_children(new_children) if changed else e
    if isinstance(e, In):
        # item literals ride `items`, not `children` — _eval_in's per-
        # item compare is the same promote+cast array math as the
        # binary comparisons, so they hoist under the same rules
        new_items, items_changed = [], False
        for it in e.items:
            if _eligible(it):
                values.append(_np_param(it, True))
                new_items.append(ParamLiteral(len(values) - 1,
                                              it.dtype, it.value, True))
                items_changed = True
            else:
                new_items.append(it)
        if items_changed:
            if node is e:
                node = e.with_children(list(e.children))
            node.items = tuple(new_items)
    return node


def parameterize_exprs(bound: Sequence[Expression]
                       ) -> Tuple[List[Expression], Tuple]:
    """Rewrite eligible literals in already-BOUND expression trees.

    Returns (trees, params): `trees` with `ParamLiteral` slots in slot
    order across the whole sequence, and `params` the matching tuple of
    np-typed scalar values to pass at call time.  `params` is empty
    when nothing was eligible — callers then keep the original
    value-baked jit wiring (and its value-carrying key)."""
    values: List = []
    out = [_rewrite(b, values) for b in bound]
    if not values:
        return list(bound), ()
    return out, tuple(values)


def param_values(trees: Sequence[Expression]) -> Tuple:
    """Re-derive the call-time parameter tuple from rewritten trees
    (slot order is the collection order of `parameterize_exprs`)."""
    lits: List[ParamLiteral] = []

    def visit(e: Expression):
        if isinstance(e, ParamLiteral):
            lits.append(e)
        for c in e.children:
            visit(c)
        # In keeps its literal list OUTSIDE children
        for it in getattr(e, "items", ()):
            visit(it)

    for b in trees:
        visit(b)
    lits.sort(key=lambda p: p.slot)
    return tuple(_np_param(p, p.padded) for p in lits)
