"""Plan-rewrite engine: meta wrapping, tagging, TPU conversion, transitions.

TPU-native analog of the reference's core
(ref: GpuOverrides.scala:3476 apply / :3495 applyOverrides,
RapidsMeta.scala:70/543/911 meta hierarchy,
GpuTransitionOverrides.scala:44 transition insertion).

Flow:
  1. wrap the CPU physical plan into a Meta tree,
  2. tag every node: per-op enable confs, TypeSig checks on output schema,
     expression-level checks (each expression class has a rule + TypeSig,
     ref GpuOverrides.scala:727-3048 registry),
  3. convert untagged subtrees to TPU placement (aggregates become a
     Partial/Final TPU pair, ref aggregate.scala modes),
  4. insert HostToDevice/DeviceToHost transitions at placement boundaries,
  5. produce reference-style explain output (spark.rapids.sql.explain).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Type

from .. import config as cfg
from .. import types as t
from ..exec import base as eb
from ..exec.aggregate import (CpuHashAggregateExec, TpuHashAggregateExec)
from ..exec.basic import (CoalesceBatchesExec, FilterExec, GlobalLimitExec,
                          LocalLimitExec, LocalScanExec, ProjectExec,
                          RangeExec, UnionExec)
from ..exec.gatherpart import GatherPartitionsExec
from ..expr import aggregates as agg
from ..expr import arithmetic as ar
from ..expr import conditional as cond
from ..expr import mathexpr as mx
from ..expr import predicates as pred
from ..expr.cast import Cast, cast_supported_on_tpu
from ..expr.core import (Alias, AttributeReference, BoundReference,
                         Expression, Literal, bind_expression)
from ..types import T, TypeSig


# ---------------------------------------------------------------------------
# Expression rules (ref ExprRule, GpuOverrides.scala:206)
# ---------------------------------------------------------------------------

class ExprRule:
    def __init__(self, sig: TypeSig, desc: str = "",
                 tag_fn: Optional[Callable] = None):
        self.sig = sig
        self.desc = desc
        self.tag_fn = tag_fn


EXPR_RULES: Dict[Type[Expression], ExprRule] = {}


def expr_rule(cls, sig: TypeSig, desc: str = "", tag_fn=None):
    EXPR_RULES[cls] = ExprRule(sig, desc, tag_fn)


_num = T.numeric64
_common = T.common_scalar
_cmp = (T.numeric64 + T.BOOLEAN + T.DATE + T.TIMESTAMP + T.STRING + T.NULL)

def _tag_literal(meta: "ExprMeta"):
    e = meta.expr
    if isinstance(e.data_type(), t.DecimalType) and e.value is not None \
            and not (-(2**63) <= int(e.value) < 2**63):
        meta.will_not_work(
            "decimal literal beyond 64-bit unscaled range stays on CPU")


expr_rule(Literal, T.all_types, "literal values", _tag_literal)

from ..expr.params import ParamLiteral  # noqa: E402 (needs Literal)

expr_rule(ParamLiteral, _num + T.DATE + T.TIMESTAMP + T.STRING,
          "parameterized literal (hoisted out of the jit key so "
          "literal-only query twins share compiled programs)")
expr_rule(Alias, T.all_types.nested(), "named expression")
expr_rule(AttributeReference,
          (_common + T.ARRAY + T.STRUCT + T.MAP + T.BINARY).nested(),
          "column reference")
expr_rule(BoundReference,
          (_common + T.ARRAY + T.STRUCT + T.MAP + T.BINARY).nested(),
          "bound column reference")
for c in (ar.Add, ar.Subtract, ar.Multiply, ar.Divide, ar.IntegralDivide,
          ar.Remainder, ar.Pmod, ar.UnaryMinus, ar.UnaryPositive, ar.Abs,
          ar.Greatest, ar.Least):
    expr_rule(c, _num)
for c in (pred.EqualTo, pred.EqualNullSafe, pred.LessThan,
          pred.LessThanOrEqual, pred.GreaterThan, pred.GreaterThanOrEqual,
          pred.In):
    expr_rule(c, _cmp)
for c in (pred.And, pred.Or, pred.Not):
    expr_rule(c, T.BOOLEAN)
for c in (pred.IsNull, pred.IsNotNull, pred.IsNaN):
    expr_rule(c, _common)
for c in (cond.If, cond.CaseWhen, cond.Coalesce, cond.NullIf, cond.Nvl):
    expr_rule(c, _cmp)  # branch-select kernels move the low word only
for c in (mx.Sqrt, mx.Exp, mx.Expm1, mx.Sin, mx.Cos, mx.Tan, mx.Asin,
          mx.Acos, mx.Atan, mx.Sinh, mx.Cosh, mx.Tanh, mx.Cbrt, mx.Rint,
          mx.ToDegrees, mx.ToRadians, mx.Log, mx.Log2, mx.Log10, mx.Log1p,
          mx.Pow, mx.Atan2, mx.Signum, mx.Round, mx.BRound, mx.Floor,
          mx.Ceil, mx.Asinh, mx.Acosh, mx.Atanh, mx.Cot, mx.Logarithm):
    expr_rule(c, _num)

from ..expr import bitwise as bw

for c in (bw.BitwiseAnd, bw.BitwiseOr, bw.BitwiseXor, bw.BitwiseNot,
          bw.ShiftLeft, bw.ShiftRight, bw.ShiftRightUnsigned):
    expr_rule(c, T.integral)


from ..expr import datetime_expr as dte
from ..expr import hashfns as hf
from ..expr import strings as se

for c in (se.Upper, se.Lower, se.Substring, se.Concat, se.Trim, se.TrimLeft,
          se.TrimRight, se.StringReplace, se.StringRepeat, se.Reverse,
          se.StringLPad, se.StringRPad, se.InitCap):
    expr_rule(c, T.STRING)
for c in (se.Length, se.BitLength, se.StringLocate):
    expr_rule(c, T.INT)
for c in (se.Contains, se.StartsWith, se.EndsWith, se.Like):
    expr_rule(c, T.BOOLEAN)
expr_rule(se.Ascii, T.INT)


# host-evaluated string families run inside a CPU-placed operator
# (SURVEY hard-part #3: no regex engine on TPU) — registered with
# per-family reasons so generated docs and explain output state WHY,
# the way the reference documents its incompat/disabled ops
# (ref GpuOverrides.scala:97-100)
def _tag_host_only(reason: str):
    def tag(meta: "ExprMeta", _r=reason):
        meta.will_not_work(_r)
    return tag


from ..expr import json_expr as je
from ..expr import regex as rx

_regex_reason = ("regex evaluation runs on the host engine "
                 "(no TPU regex kernel; ref SURVEY hard-part #3)")
for c in (rx.RLike, rx.RegExpExtract, rx.RegExpReplace, rx.StringSplit):
    expr_rule(c, T.STRING, "host-evaluated regex",
              _tag_host_only(_regex_reason))
expr_rule(se.ConcatWs, T.STRING, "host-evaluated concat_ws",
          _tag_host_only("concat_ws's variadic null/separator semantics "
                         "evaluate on the host engine"))
expr_rule(je.GetJsonObject, T.STRING, "host-evaluated JSON path",
          _tag_host_only("JSON-path evaluation runs on the host engine "
                         "(no TPU JSON parser)"))
expr_rule(hf.Md5, T.STRING, "md5 hex digest (host digest loop)",
          _tag_host_only("md5 digests run on the host engine "
                         "(byte-serial digest)"))
for c in (dte.Year, dte.Month, dte.DayOfMonth, dte.Quarter, dte.DayOfWeek,
          dte.WeekDay, dte.DayOfYear, dte.Hour, dte.Minute, dte.Second,
          dte.DateDiff):
    expr_rule(c, T.INT)
for c in (dte.LastDay, dte.DateAdd, dte.DateSub, dte.AddMonths,
          dte.TruncDate):
    expr_rule(c, T.DATE)
expr_rule(dte.ToUnixTimestamp, T.LONG)
expr_rule(dte.FromUnixTime, T.TIMESTAMP)
expr_rule(dte.TimeAdd, T.TIMESTAMP)
expr_rule(hf.Murmur3Hash, T.INT)
expr_rule(hf.MonotonicallyIncreasingID, T.LONG,
          "(partition << 33) + row position, ref "
          "GpuMonotonicallyIncreasingID.scala")
expr_rule(hf.SparkPartitionID, T.INT, "ref GpuSparkPartitionID.scala")
expr_rule(hf.Rand, T.DOUBLE,
          "uniform [0,1); engine-deterministic but not bit-compatible "
          "with Spark's XORShift sequence (incompat, like the reference)")

from ..expr import collection as coll

# --- registry tail: the remaining reference rules -------------------------
# (ref GpuOverrides.scala:727-3048; each entry either lowers on TPU or is
# registered with an explicit host-fallback reason so explain/docs tell
# the truth about where it runs)
from ..expr import misc_tail as mt
from ..expr import higher_order as ho
from ..expr import window as win
from ..expr.subquery import ScalarSubquery
from ..udf.python_udf import PythonUDF

expr_rule(mt.NaNvl, T.DOUBLE + T.FLOAT)
expr_rule(mt.InSet, T.BOOLEAN)
expr_rule(mt.AtLeastNNonNulls, T.BOOLEAN)
expr_rule(mt.KnownNotNull, T.all_types.nested(), "optimizer marker")
expr_rule(mt.KnownFloatingPointNormalized, T.all_types.nested(),
          "optimizer marker")
expr_rule(mt.PromotePrecision, T.DECIMAL_64 + T.DECIMAL_128,
          "decimal precision marker")
expr_rule(mt.UnscaledValue, T.LONG,
          tag_fn=lambda m: m.will_not_work(
              "unscaledvalue of decimal128 needs both lanes")
          if isinstance(m.expr.children[0].data_type(), t.DecimalType)
          and not m.expr.children[0].data_type().is64 else None)
expr_rule(mt.MakeDecimal, T.DECIMAL_64 + T.DECIMAL_128)
expr_rule(mt.CheckOverflow, T.DECIMAL_64 + T.DECIMAL_128)
expr_rule(mt.PreciseTimestampConversion, T.TIMESTAMP + T.LONG)
expr_rule(hf.InputFileName, T.STRING,
          "current scan file path (forces the PERFILE reader, ref "
          "InputFileBlockRule.scala)",
          _tag_host_only("file-path strings materialize on the host "
                         "engine (task-context metadata, not device "
                         "data)"))
expr_rule(mt.InputFileBlockStart, T.LONG,
          "0 for whole-file PERFILE reads, ref GpuInputFileBlockStart")
expr_rule(mt.InputFileBlockLength, T.LONG,
          "file size for whole-file PERFILE reads")

# window machinery registered as expressions, like the reference
# (GpuOverrides windowing rules); evaluation lives in WindowExec
for c in (win.WindowExpression, win.RowNumber, win.Rank, win.DenseRank,
          win.PercentRank, win.CumeDist, win.NTile):
    expr_rule(c, T.common_scalar.nested())
for c in (win.Lead, win.Lag):
    expr_rule(c, (T.common_scalar + T.STRING).nested())
expr_rule(win.WindowSpec, T.common_scalar.nested(),
          "window spec definition (partition/order/frame; the analog of "
          "WindowSpecDefinition + SpecifiedWindowFrame + SortOrder)")

expr_rule(ScalarSubquery, T.common_scalar,
          "resolved driver-side to a literal before execution")
expr_rule(PythonUDF, T.all_types.nested(),
          "compiled to engine expressions when possible; otherwise "
          "evaluated out-of-process (ArrowEvalPython worker pool)")

expr_rule(coll.MapKeys, T.ARRAY.nested(T.common_scalar))
expr_rule(coll.MapValues, T.ARRAY.nested(T.common_scalar))
expr_rule(coll.MapEntries, T.ARRAY.nested(T.common_scalar + T.STRUCT))
expr_rule(coll.GetMapValue, T.common_scalar,
          tag_fn=lambda m: m.will_not_work(
              "string-keyed map element access needs a literal key "
              "(column-key byte comparison not lowered)")
          if isinstance(m.expr.children[0].data_type().key_type,
                        (t.StringType, t.BinaryType))
          and not isinstance(m.expr.children[1], Literal) else None)
def _tag_create_map(m):
    if any(isinstance(c.data_type(),
                      (t.StringType, t.BinaryType, t.ArrayType,
                       t.StructType, t.MapType))
           for c in m.expr.children):
        m.will_not_work("map() over variable-width children not supported")
        return
    # Spark RAISES on null map keys (and on duplicates under the default
    # EXCEPTION dedup policy); a jitted kernel cannot raise data-dependent
    # errors, so nullable keys stay on the host engine
    for kc in m.expr.children[0::2]:
        if getattr(kc, "nullable", True):
            m.will_not_work(
                "map() with nullable keys stays on CPU (Spark raises on "
                "null keys; device kernels cannot raise data-dependently)")
            return


expr_rule(coll.CreateMap, T.MAP.nested(T.common_scalar),
          "duplicate-key detection follows the host engine",
          _tag_create_map)
expr_rule(coll.ArrayMax, T.common_scalar,
          tag_fn=lambda m: m.will_not_work(
              "array_max/min over nested/string elements not supported")
          if isinstance(m.expr.children[0].data_type().element_type,
                        (t.StringType, t.BinaryType, t.ArrayType,
                         t.StructType, t.MapType)) else None)
expr_rule(coll.ArrayMin, T.common_scalar,
          tag_fn=EXPR_RULES[coll.ArrayMax].tag_fn)
expr_rule(ho.TransformKeys, T.MAP.nested(T.common_scalar))
expr_rule(ho.TransformValues, T.MAP.nested(T.common_scalar))

expr_rule(dte.UnixTimestamp, T.LONG)
expr_rule(dte.DateFormatClass, T.STRING, "host-evaluated date_format",
          _tag_host_only("strftime-style formatting runs on the host "
                         "engine (byte-serial pattern rendering)"))
expr_rule(dte.DateAddInterval, T.DATE, "host-evaluated interval add",
          _tag_host_only("the calendar-interval type is not modeled on "
                         "device; interval arithmetic runs on the host "
                         "engine"))
expr_rule(se.SubstringIndex, T.STRING,
          "single-byte delimiters lower on device",
          tag_fn=lambda m: m.will_not_work(
              "substring_index with a multi-byte or empty delimiter "
              "needs sequential non-overlapping search; host engine")
          if len(m.expr.delim_bytes()) != 1 else None)

expr_rule(coll.Size, T.INT)
expr_rule(coll.ArrayContains, T.BOOLEAN,
          tag_fn=lambda m: m.will_not_work(
              "array_contains over nested/string elements not supported")
          if isinstance(m.expr.children[0].data_type().element_type,
                        (t.StringType, t.BinaryType, t.ArrayType,
                         t.StructType, t.MapType)) else None)
expr_rule(coll.SortArray, T.ARRAY.nested(T.common_scalar),
          tag_fn=lambda m: m.will_not_work(
              "sort_array over nested/string elements not supported")
          if isinstance(m.expr.children[0].data_type().element_type,
                        (t.StringType, t.BinaryType, t.ArrayType,
                         t.StructType, t.MapType)) else None)
from ..expr import complextype as cx
from ..expr import higher_order as ho

_nested_common = (T.common_scalar + T.ARRAY + T.STRUCT + T.MAP +
                  T.BINARY).nested()
expr_rule(cx.GetStructField, _nested_common, "struct field extract")
expr_rule(cx.GetArrayItem, _nested_common, "array index extract")
expr_rule(cx.ElementAt, _nested_common, "element_at")
expr_rule(cx.CreateNamedStruct, T.STRUCT.nested(T.common_scalar),
          "named_struct")


def _tag_create_array(meta: "ExprMeta"):
    et = meta.expr.children[0].data_type() if meta.expr.children else None
    if isinstance(et, (t.StringType, t.BinaryType, t.ArrayType,
                       t.StructType, t.MapType)):
        meta.will_not_work(
            "array() over string/nested elements is not supported on TPU")


expr_rule(cx.CreateArray, T.ARRAY.nested(T.common_scalar), "array()",
          _tag_create_array)


def _tag_higher_order(meta: "ExprMeta"):
    e = meta.expr
    fn = e.fn
    if ho.references_outer_columns(fn.body,
                                   {a.name for a in fn.args}):
        meta.will_not_work(
            "lambda bodies may only reference lambda variables")


expr_rule(ho.LambdaFunction, T.all_types.nested(), "lambda function")
expr_rule(ho.NamedLambdaVariable, T.all_types.nested(), "lambda variable")
expr_rule(ho.ArrayTransform, T.ARRAY.nested(T.common_scalar), "transform",
          _tag_higher_order)
expr_rule(ho.ArrayFilter, T.ARRAY.nested(T.common_scalar), "filter",
          _tag_higher_order)
expr_rule(ho.ArrayExists, T.BOOLEAN, "exists", _tag_higher_order)
expr_rule(ho.ArrayForAll, T.BOOLEAN, "forall", _tag_higher_order)
# regex expressions intentionally have NO rule: no TPU regex engine, the
# operator stays on the CPU engine whose numpy path evaluates them via
# `re` (ref marks regex-dependent ops incompat the same way)

expr_rule(coll.Explode, (T.common_scalar + T.ARRAY + T.STRUCT).nested(),
          "explode generator")
expr_rule(coll.PosExplode, (T.common_scalar + T.ARRAY + T.STRUCT).nested(),
          "posexplode generator")


def _tag_string_literal_needle(meta: "ExprMeta"):
    from ..expr.strings import _literal_bytes
    e = meta.expr
    needle_child = e.children[1] if len(e.children) > 1 else None
    if needle_child is not None and \
            _literal_bytes(needle_child) is None and \
            not isinstance(needle_child, Literal):
        meta.will_not_work(
            f"{type(e).__name__} requires a literal search argument on TPU")


for c in (se.Contains, se.StartsWith, se.EndsWith, se.Like,
          se.StringReplace):
    EXPR_RULES[c].tag_fn = _tag_string_literal_needle


def _tag_cast(meta: "ExprMeta"):
    e = meta.expr
    src = e.child.data_type()
    if not cast_supported_on_tpu(src, e.to):
        meta.will_not_work(
            f"cast from {src.name} to {e.to.name} is not supported on TPU")


expr_rule(Cast, T.all_types, "type cast", _tag_cast)

# aggregate functions.  Sum accepts decimal64 inputs and produces exact
# 128-bit buffers (segment_sum128); Average's final divide is 64-bit so
# decimal averages stay on CPU; Min/Max carry both decimal words through
# the ordered gather so full decimal128 is fine.
expr_rule(agg.Sum, T.numeric)
expr_rule(agg.Average, T.integral + T.FLOAT + T.DOUBLE)
expr_rule(agg.Count, T.all_types)
expr_rule(agg.Min, T.numeric + T.DATE + T.TIMESTAMP + T.BOOLEAN + T.STRING)
expr_rule(agg.Max, T.numeric + T.DATE + T.TIMESTAMP + T.BOOLEAN + T.STRING)
expr_rule(agg.First, _common)
expr_rule(agg.Last, _common)
# collect over flat types: element ordering inside the collected array is
# sorted-row order (list) / value order (set), ref GpuCollectList/Set
_collect_elem = T.numeric + T.BOOLEAN + T.DATE + T.TIMESTAMP + T.STRING
expr_rule(agg.CollectList, (_collect_elem + T.ARRAY).nested(_collect_elem))
expr_rule(agg.CollectSet, (_collect_elem + T.ARRAY).nested(_collect_elem))
for c in (agg.StddevPop, agg.StddevSamp, agg.VariancePop, agg.VarianceSamp):
    expr_rule(c, _num)
# pivot_first: first value where the pivot column matches; the mask fuses
# into the update expression (ref GpuPivotFirst, GpuOverrides.scala:2034)
expr_rule(agg.PivotFirst, _common,
          "pivot aggregate (one instance per pivot value)")
expr_rule(agg.ApproximatePercentile, T.numeric64,
          "exact inverted-CDF percentile over collected groups "
          "(decimal128 would drop the high word in the rank gather)")
expr_rule(agg.AggregateExpression, T.all_types.nested())


def _tag_time_window(meta: "ExprMeta"):
    if not meta.expr.is_tumbling and meta.expr.copy_index is None:
        # lowered per-slide copies (copy_index set) are plain elementwise
        # math and run on TPU; only a bare un-lowered sliding window is
        # unsupported
        meta.will_not_work(
            "bare sliding time windows need the Expand lowering "
            "(window() through select/groupBy lowers automatically)")


from ..expr.datetime_expr import TimeWindow as _TimeWindow
from ..expr.mathexpr import NormalizeNaNAndZero as _NormNaN

expr_rule(_TimeWindow, T.STRUCT.nested(T.TIMESTAMP),
          "tumbling time window bucketing", _tag_time_window)
expr_rule(_NormNaN, T.FLOAT + T.DOUBLE,
          "canonicalize NaN/-0.0 for grouping and join keys")

# columnar native UDFs trace straight into the operator's XLA computation
# (ref GpuUserDefinedFunction + RapidsUDF.evaluateColumnar)
from ..udf.native import NativeUDFExpression

expr_rule(NativeUDFExpression, T.common_scalar + T.BINARY,
          "user-supplied columnar UDF")
# opaque PythonUDF has no rule: it keeps its operator on the CPU unless the
# planner extracted it into ArrowEvalPythonExec (ref GpuOverrides fallback)


# ---------------------------------------------------------------------------
# Meta hierarchy (ref RapidsMeta.scala)
# ---------------------------------------------------------------------------

class BaseMeta:
    def __init__(self, conf: cfg.RapidsConf):
        self.conf = conf
        self.reasons: List[str] = []

    def will_not_work(self, reason: str):
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_replace(self) -> bool:
        return not self.reasons


class ExprMeta(BaseMeta):
    """Wraps one expression node (ref BaseExprMeta, RapidsMeta.scala:911)."""

    def __init__(self, expr: Expression, conf, input_names, input_types):
        super().__init__(conf)
        self.expr = expr
        self.input_names = input_names
        self.input_types = input_types
        self.children = [ExprMeta(c, conf, input_names, input_types)
                         for c in expr.children]
        if isinstance(expr, agg.AggregateExpression):
            self.children = [ExprMeta(expr.func, conf, input_names,
                                      input_types)]
        if isinstance(expr, ho.ArrayHigherOrder):
            # retype the lambda variables from the (bound) array element
            # type so the body's meta tree type-checks
            try:
                bound = bind_expression(expr, input_names, input_types)
                self.children = [
                    ExprMeta(bound.arr, conf, input_names, input_types),
                    ExprMeta(bound._bind_lambda(), conf, input_names,
                             input_types)]
            except Exception:  # tpulint: allow[TPU-R011] tag() on the
                # unbound tree reports the bind failure as a
                # will-not-work reason — the sanctioned sink, one
                # phase later
                pass

    def tag(self):
        rule = EXPR_RULES.get(type(self.expr))
        if rule is None:
            self.will_not_work(
                f"expression {type(self.expr).__name__} is not supported on TPU")
        else:
            if not self.conf.is_op_enabled("expression",
                                           type(self.expr).__name__):
                self.will_not_work(
                    f"expression {type(self.expr).__name__} has been disabled")
            try:
                bound = bind_expression(self.expr, self.input_names,
                                        self.input_types)
                dt = bound.data_type()
                if not isinstance(dt, t.NullType) and \
                        not rule.sig.is_supported(dt):
                    for r in rule.sig.reasons_not_supported(dt):
                        self.will_not_work(
                            f"{type(self.expr).__name__} produces "
                            f"unsupported type: {r}")
            except Exception as ex:  # unresolvable -> cannot place
                self.will_not_work(
                    f"{type(self.expr).__name__}: {ex}")
            if rule.tag_fn is not None and not self.reasons:
                try:
                    bound = bind_expression(self.expr, self.input_names,
                                            self.input_types)
                    m2 = ExprMeta.__new__(ExprMeta)
                    m2.__dict__.update(self.__dict__)
                    m2.expr = bound
                    m2.reasons = self.reasons
                    rule.tag_fn(m2)
                except Exception as ex:
                    self.will_not_work(str(ex))
        for c in self.children:
            c.tag()

    @property
    def can_replace_tree(self) -> bool:
        return self.can_replace and all(c.can_replace_tree
                                        for c in self.children)

    def all_reasons(self) -> List[str]:
        out = list(self.reasons)
        for c in self.children:
            out += c.all_reasons()
        return out


class ExecMeta(BaseMeta):
    """Wraps one physical operator (ref SparkPlanMeta, RapidsMeta.scala:543)."""

    def __init__(self, exec_node: eb.Exec, conf):
        super().__init__(conf)
        self.exec = exec_node
        self.children = [ExecMeta(c, conf) for c in exec_node.children]

    # schema feeding this node's expressions
    def _input_schema(self):
        if self.exec.children:
            c = self.exec.children[0]
            return c.output_names, c.output_types
        return [], []

    def expressions(self) -> List[Expression]:
        e = self.exec
        if isinstance(e, ProjectExec):
            return list(e.exprs)
        if isinstance(e, FilterExec):
            return [e.condition]
        if isinstance(e, (CpuHashAggregateExec,)):
            return list(e.grouping) + list(e.aggregates)
        from ..exec.sort import SortExec as _SE
        if isinstance(e, _SE):
            return [o[0] for o in e.orders]
        from ..exec.expand import ExpandExec as _XE
        from ..exec.expand import GenerateExec as _GE
        if isinstance(e, _XE):
            return [x for proj in e.projections for x in proj]
        if isinstance(e, _GE):
            return [e.generator]
        return []

    def tag(self):
        e = self.exec
        name = type(e).__name__
        if getattr(e, "deliberate_cpu", False):
            # python-exchange operators run on CPU by design (the data
            # crosses into Python either way) — not an acceleration gap
            self.will_not_work(
                f"{name} runs on CPU by design (python data exchange)")
            for c in self.children:
                c.tag()
            self.expr_metas = []
            return
        if not self.conf.is_op_enabled("exec", name):
            self.will_not_work(f"{name} has been disabled by config")
        rule_sig = EXEC_SIGS.get(type(e))
        if rule_sig is None:
            self.will_not_work(f"{name} has no TPU implementation")
        else:
            for n, dt in zip(e.output_names, e.output_types):
                if isinstance(dt, t.NullType):
                    continue
                if not rule_sig.is_supported(dt):
                    for r in rule_sig.reasons_not_supported(dt):
                        self.will_not_work(f"output column {n}: {r}")
        names, dtypes = self._input_schema()
        self.expr_metas = [ExprMeta(x, self.conf, names, dtypes)
                           for x in self.expressions()]
        for em in self.expr_metas:
            em.tag()
            if not em.can_replace_tree:
                for r in em.all_reasons():
                    self.will_not_work(r)
        custom = EXEC_TAGS.get(type(e))
        if custom:
            custom(self)
        for c in self.children:
            c.tag()

    # ---- conversion -------------------------------------------------------
    def convert(self) -> eb.Exec:
        new_children = [c.convert() for c in self.children]
        e = self.exec.with_new_children(new_children)
        if not self.can_replace or not self.conf.sql_enabled:
            return e
        conv = EXEC_CONVERTS.get(type(e))
        if conv is not None:
            return conv(e, self.conf)
        import copy
        e.placement = eb.TPU
        return e

    # ---- explain ----------------------------------------------------------
    def explain_lines(self, level=0) -> List[str]:
        pad = "  " * level
        name = type(self.exec).__name__
        if self.can_replace:
            lines = [f"{pad}*Exec <{name}> will run on TPU"]
        else:
            lines = [f"{pad}!Exec <{name}> cannot run on TPU because "
                     + "; ".join(self.reasons[:4])]
        for c in self.children:
            lines += c.explain_lines(level + 1)
        return lines


# exec output-type signatures (ref ExecChecks, TypeChecks.scala:886)
_exec_common = (T.common_scalar + T.ARRAY + T.STRUCT + T.MAP + T.BINARY).nested()
EXEC_SIGS: Dict[Type[eb.Exec], TypeSig] = {
    LocalScanExec: _exec_common,
    RangeExec: T.LONG,
    ProjectExec: _exec_common,
    FilterExec: _exec_common,
    UnionExec: _exec_common,
    LocalLimitExec: _exec_common,
    GlobalLimitExec: _exec_common,
    CoalesceBatchesExec: _exec_common,
    GatherPartitionsExec: _exec_common,
    # struct keys group fine: key_words_for_column recurses children
    # (time-window bucketing groups by struct<start,end>)
    CpuHashAggregateExec: (T.common_scalar + T.ARRAY + T.STRUCT).nested(
        T.common_scalar),
}

from ..exec.broadcast import (BroadcastExchangeExec, BroadcastHashJoinExec,
                              BroadcastNestedLoopJoinExec)
from ..exec.join import (CpuJoinExec, HashJoinExec, NestedLoopJoinExec,
                         ShuffledHashJoinExec)
from ..exec.sort import SortExec

EXEC_SIGS[SortExec] = T.common_scalar.nested()
EXEC_SIGS[CpuJoinExec] = _exec_common
EXEC_SIGS[NestedLoopJoinExec] = _exec_common
EXEC_SIGS[HashJoinExec] = _exec_common
EXEC_SIGS[ShuffledHashJoinExec] = _exec_common
EXEC_SIGS[BroadcastExchangeExec] = _exec_common
EXEC_SIGS[BroadcastHashJoinExec] = _exec_common
EXEC_SIGS[BroadcastNestedLoopJoinExec] = _exec_common

EXEC_TAGS: Dict[Type[eb.Exec], Callable] = {}
EXEC_CONVERTS: Dict[Type[eb.Exec], Callable] = {}


def _fuse_single_chip(conf: cfg.RapidsConf) -> bool:
    """Collapse exchanges when this process drives exactly one chip.

    An N-partition exchange on a single device runs N per-partition
    programs SERIALLY — N dispatch/sync floors buying parallelism that
    does not exist (the multi-chip mesh path, parallel/ici_exec.py, is
    where partitions buy real concurrency).  Absorbing the exchange into
    its consumer turns the stage into ONE fused program, the single-chip
    mirror of the ICI stage fusion."""
    mode = conf.get(cfg.SINGLE_CHIP_FUSE)
    if mode == "off":
        return False
    if mode == "on":
        return True
    import jax
    return len(jax.devices()) == 1


def _strip_exchange(exchange: eb.Exec, coalesce: bool = False) -> eb.Exec:
    """Replace an exchange with a partition gather (+ optional device-side
    batch coalesce so streaming consumers see ONE batch instead of one
    per source partition — each probe batch costs its own sync)."""
    src = exchange.children[0]
    node = src
    if src.num_partitions > 1:
        node = GatherPartitionsExec(src)
        node.placement = src.placement
    if coalesce:
        node = CoalesceBatchesExec(node)
        node.placement = src.placement
    return node


def _convert_join(e: "CpuJoinExec", conf) -> eb.Exec:
    left, right = e.children
    colocated = getattr(e, "colocated", False)
    if _fuse_single_chip(conf):
        if colocated and \
                all(isinstance(c, ShuffleExchangeExec) for c in e.children):
            # shuffled hash join on one chip: the exchanges exist only to
            # co-locate keys, which a single chip already is — drop both
            # and run ONE count/sync/expand instead of one per partition
            left = _strip_exchange(left, coalesce=True)   # probe streams
            right = _strip_exchange(right)                # build concats
            colocated = False
        elif left.num_partitions > 1 and not colocated:
            # broadcast/plain join with a multi-partition probe: each
            # probe batch pays its own count->sync->expand round; one
            # chip gains nothing from the split, so funnel the probe
            # into a single device batch first
            g = GatherPartitionsExec(left)
            g.placement = left.placement
            left = CoalesceBatchesExec(g)
            left.placement = g.placement
    if isinstance(right, BroadcastExchangeExec):
        cls = BroadcastHashJoinExec
    elif colocated:
        # both sides hash-exchanged on the keys: the co-partitioned
        # spill-backed path (build = one catalog shard, not the table)
        cls = ShuffledHashJoinExec
    else:
        cls = HashJoinExec
    j = cls(e.left_keys, e.right_keys, e.how, e.condition,
            left, right, colocated=colocated)
    j.placement = eb.TPU
    return j


def _tag_join(meta: "ExecMeta"):
    e: CpuJoinExec = meta.exec
    if e.condition is not None and e.how not in ("inner", "left"):
        # inner post-filters; left repairs unmatched probe rows in the
        # expand kernel (right arrives pre-flipped to left)
        meta.will_not_work(
            f"conditional {e.how} join is not supported on TPU")
    # key types must be hash/equality-capable
    l, r = e.children
    for k in e.left_keys + e.right_keys:
        names = l.output_names + r.output_names
        dtypes = l.output_types + r.output_types
        try:
            b = bind_expression(k, l.output_names, l.output_types)
        except Exception:
            try:
                b = bind_expression(k, r.output_names, r.output_types)
            except Exception as ex:
                meta.will_not_work(str(ex))
                continue
        dt = b.data_type()
        if not (T.comparable + T.STRUCT).is_supported(dt):
            meta.will_not_work(f"join key type {dt.name} not supported")
    # payload sizing: the join size pass computes top-level child-row /
    # char totals for span columns, but a varlen type nested INSIDE
    # another type (array<string>, map<_, string>, struct<string> — the
    # struct gather branch forwards no char cap either) still defaults
    # its inner buffer to the source capacity — a duplicating gather
    # would silently truncate it, so those payloads stay on CPU until
    # the size pass learns to walk nested spans
    def nested_varlen(dt: t.DataType) -> bool:
        if isinstance(dt, t.ArrayType):
            return _has_varlen(dt.element_type)
        if isinstance(dt, t.MapType):
            return _has_varlen(dt.key_type) or _has_varlen(dt.value_type)
        if isinstance(dt, t.StructType):
            return any(_has_varlen(f.data_type) for f in dt.fields)
        return False

    def _has_varlen(dt: t.DataType) -> bool:
        if isinstance(dt, (t.StringType, t.BinaryType,
                           t.ArrayType, t.MapType)):
            return True
        if isinstance(dt, t.StructType):
            return any(_has_varlen(f.data_type) for f in dt.fields)
        return False

    for side in e.children:
        for dt in side.output_types:
            if nested_varlen(dt):
                meta.will_not_work(
                    f"join payload type {dt.name} (varlen nested in "
                    f"varlen) not sized for duplicating gathers")


def _convert_aggregate(e: CpuHashAggregateExec, conf) -> eb.Exec:
    """Replace the complete-mode CPU aggregate with a TPU Partial/Final
    pair (ref aggregate.scala partial/final mode pipeline).  When the
    planner put an exchange below the aggregate, the partial half moves
    BELOW the exchange (Spark's partial-aggregation pushdown) so only
    pre-aggregated groups cross the wire."""
    child = e.children[0]
    if isinstance(child, ShuffleExchangeExec):
        if _fuse_single_chip(conf):
            # one chip: partial-agg pushdown shrinks a wire that does not
            # exist; a single fused Complete program over the gathered
            # input replaces partial x N -> exchange -> final x N
            return TpuHashAggregateExec(
                e.grouping, e.aggregates, agg.COMPLETE,
                _strip_exchange(child, coalesce=True))
        from ..shuffle.partitioning import HashPartitioning
        source = child.children[0]
        partial = TpuHashAggregateExec(e.grouping, e.aggregates,
                                       agg.PARTIAL, source)
        part = HashPartitioning(
            [AttributeReference(n) for n in partial.output_names[
                :len(e.grouping)]],
            child.partitioning.num_partitions)
        exchange = ShuffleExchangeExec(part, partial)
        exchange.placement = eb.TPU
        final = TpuHashAggregateExec(e.grouping, partial.aggregates,
                                     agg.FINAL, exchange)
        return final
    # no exchange below: groups are already co-located, so a single
    # Complete-mode aggregate (update+evaluate, merge only for multi-batch
    # inputs) replaces the Partial/Final pair — one compiled program and
    # one device pass instead of two (Spark collapses the same way when
    # partial aggregation cannot help)
    return TpuHashAggregateExec(e.grouping, e.aggregates, agg.COMPLETE,
                                child)


EXEC_CONVERTS[CpuHashAggregateExec] = _convert_aggregate
EXEC_CONVERTS[CpuJoinExec] = _convert_join
EXEC_TAGS[CpuJoinExec] = _tag_join

from ..exec.window import WindowExec  # noqa: E402
from ..shuffle.exchange import ShuffleExchangeExec  # noqa: E402

EXEC_SIGS[WindowExec] = T.common_scalar.nested()
EXEC_SIGS[ShuffleExchangeExec] = _exec_common


def _convert_window(e: WindowExec, conf) -> eb.Exec:
    child = e.children[0]
    if _fuse_single_chip(conf) and isinstance(child, ShuffleExchangeExec):
        # window partitions need co-location only; one chip has it —
        # WindowExec concats its input and carry-sorts by (pkeys, okeys)
        e = WindowExec(e.window_exprs, _strip_exchange(child))
    e.placement = eb.TPU
    return e


def _convert_sort(e: SortExec, conf) -> eb.Exec:
    child = e.children[0]
    if e.is_global and _fuse_single_chip(conf) and \
            isinstance(child, ShuffleExchangeExec):
        # range exchange orders ranges ACROSS partitions; a single chip
        # sorts the gathered whole in one program instead
        e = SortExec(e.orders, _strip_exchange(child), is_global=True)
    e.placement = eb.TPU
    return e


EXEC_CONVERTS[WindowExec] = _convert_window
EXEC_CONVERTS[SortExec] = _convert_sort

from ..io.scan import FileScanExec  # noqa: E402

EXEC_SIGS[FileScanExec] = _exec_common

from ..exec.basic import SampleExec  # noqa: E402
from ..exec.expand import ExpandExec, GenerateExec  # noqa: E402

EXEC_SIGS[SampleExec] = _exec_common
EXEC_SIGS[ExpandExec] = _exec_common
EXEC_SIGS[GenerateExec] = _exec_common

from ..io.cached_batch import CachedScanExec, CacheWriteExec  # noqa: E402

EXEC_SIGS[CachedScanExec] = _exec_common
EXEC_SIGS[CacheWriteExec] = _exec_common


def _tag_file_scan(meta: "ExecMeta"):
    from .. import config as cfg
    e: FileScanExec = meta.exec
    key = {"parquet": cfg.PARQUET_ENABLED, "orc": cfg.ORC_ENABLED,
           "csv": cfg.CSV_ENABLED}.get(e.fmt)
    if key is not None and not meta.conf.get(key):
        meta.will_not_work(f"{e.fmt} scan disabled by config")


EXEC_TAGS[FileScanExec] = _tag_file_scan


def _tag_window(meta: ExecMeta):
    from ..expr import window as W
    from ..expr.aggregates import (AggregateFunction, Average, Count, First,
                                   Last, Max, Min, Sum)
    e: WindowExec = meta.exec
    cn = e.children[0].output_names
    ct = e.children[0].output_types
    for w in e.window_exprs:
        f = w.func
        if isinstance(f, AggregateFunction):
            if not isinstance(f, (Sum, Count, Average, Min, Max, First,
                                  Last)):
                meta.will_not_work(
                    f"window aggregate {type(f).__name__} not supported")
            kind, lo, hi = w.spec.effective_frame(False)
            bounded = not (lo == W.UNBOUNDED_PRECEDING and
                           hi in (W.CURRENT_ROW, W.UNBOUNDED_FOLLOWING))
            if kind == "range" and bounded:
                # bounded range frames need exactly one ascending flat
                # numeric/date/timestamp order key (binary-search bounds)
                orders = w.spec.order_by
                ok = len(orders) == 1 and orders[0][1]
                if ok:
                    try:
                        dt = bind_expression(orders[0][0], cn,
                                             ct).data_type()
                        ok = (t.is_numeric(dt) and not
                              isinstance(dt, t.DecimalType)) or \
                            isinstance(dt, (t.DateType, t.TimestampType))
                    except Exception:  # tpulint: allow[TPU-R011] the
                        # ok=False flag routes into the will_not_work
                        # call right below — reported, not swallowed
                        ok = False
                if not ok:
                    meta.will_not_work(
                        "bounded range frames need a single ascending "
                        "numeric/date/timestamp order key")
        elif not isinstance(f, (W.RowNumber, W.Rank, W.DenseRank, W.Lead,
                                W.Lag, W.NTile)):
            meta.will_not_work(
                f"window function {type(f).__name__} not supported")


EXEC_TAGS[WindowExec] = _tag_window


def _tag_aggregate(meta: ExecMeta):
    e: CpuHashAggregateExec = meta.exec
    cn, ct = e.children[0].output_names, e.children[0].output_types
    for ae in e.aggregates:
        fn = ae.func
        rule = EXPR_RULES.get(type(fn))
        if rule is None:
            meta.will_not_work(
                f"aggregate {type(fn).__name__} is not supported on TPU")
            continue
        if fn.children:
            try:
                b = bind_expression(fn.child, cn, ct)
                dt = b.data_type()
                if not rule.sig.is_supported(dt):
                    for r in rule.sig.reasons_not_supported(dt):
                        meta.will_not_work(
                            f"{type(fn).__name__} over unsupported input: {r}")
                if isinstance(fn, agg.Sum) and \
                        isinstance(dt, t.DecimalType) and not dt.is64:
                    # the update-stage cast reads the decimal low word; a
                    # >18-digit input would lose its high word before the
                    # exact 128-bit buffer accumulation starts
                    meta.will_not_work(
                        "sum over decimal(>18) inputs runs on CPU")
            except Exception as ex:
                meta.will_not_work(str(ex))


EXEC_TAGS[CpuHashAggregateExec] = _tag_aggregate


# ---------------------------------------------------------------------------
# Transitions (ref GpuTransitionOverrides)
# ---------------------------------------------------------------------------

def insert_transitions(root: eb.Exec) -> eb.Exec:
    def fix(node: eb.Exec) -> eb.Exec:
        new_children = []
        for c in node.children:
            c = fix(c)
            if node.placement == eb.TPU and c.placement == eb.CPU and \
                    not isinstance(c, eb.DeviceToHostExec):
                c = eb.HostToDeviceExec(c)
            elif node.placement == eb.CPU and c.placement == eb.TPU:
                c = eb.DeviceToHostExec(c)
            new_children.append(c)
        if new_children or node.children:
            node = node.with_new_children(new_children)
        return node

    root = fix(root)
    # fix() clones every node, and the num_partitions probe below can
    # EXECUTE the plan (an AQE reader materializes its map stage to size
    # its specs) — so replicated build readers must be re-pointed at the
    # cloned probe partner HERE, not only after insert_transitions
    # returns, or the stale partner shuffles the probe side a second
    # time and leaks every block it writes.
    from ..shuffle.aqe import relink_replicated_readers
    root = relink_replicated_readers(root)
    if root.placement == eb.TPU:
        # collect boundary: funnel every partition's device batches into
        # ONE device-side concat before crossing to host — each fetch
        # costs two round trips, so a 4-partition result fetched
        # per-batch pays 8 syncs where one coalesced batch pays 2 (the
        # coalesce-before-transition role of GpuCoalesceBatches)
        if root.num_partitions > 1:
            root = GatherPartitionsExec(root)
            root.placement = eb.TPU
        # NOT require_single_batch: a result bigger than the coalesce
        # target streams in bounded chunks instead of materializing one
        # giant device batch (device-OOM guard for huge collects)
        coal = CoalesceBatchesExec(root)
        coal.placement = eb.TPU
        root = eb.DeviceToHostExec(coal)
    # fuse DeviceToHost(HostToDevice(x)) -> x
    def fuse(node: eb.Exec) -> eb.Exec:
        if isinstance(node, eb.HostToDeviceExec) and \
                isinstance(node.children[0], eb.DeviceToHostExec):
            return node.children[0].children[0]
        if isinstance(node, eb.DeviceToHostExec) and \
                isinstance(node.children[0], eb.HostToDeviceExec):
            return node.children[0].children[0]
        return node
    return root.transform_up(fuse)


class TpuOverrides:
    """Entry point (ref GpuOverrides.apply, ColumnarOverrideRules)."""

    def __init__(self, conf: cfg.RapidsConf):
        self.conf = conf
        self.last_explain = ""
        self.last_lint = []

    def apply(self, plan: eb.Exec) -> eb.Exec:
        # external override providers contribute rules lazily (the
        # GpuHiveOverrides hook, ref GpuOverrides.scala:53)
        from .extensions import load_extension_rules
        load_extension_rules()
        if not self.conf.sql_enabled:
            self.last_explain = "(TPU acceleration disabled)"
            return plan
        meta = ExecMeta(plan, self.conf)
        meta.tag()
        if self.conf.get(cfg.OPTIMIZER_ENABLED):
            from .cost import CostBasedOptimizer
            CostBasedOptimizer(self.conf).optimize(meta)
        explain_mode = self.conf.explain
        lines = meta.explain_lines()
        self.last_explain = "\n".join(lines)
        if explain_mode == "ALL":
            print(self.last_explain)
        elif explain_mode == "NOT_ON_GPU":
            bad = [l for l in lines if l.lstrip().startswith("!")]
            if bad:
                print("\n".join(bad))
        converted = meta.convert()
        from ..parallel.ici_exec import install_ici_stages
        converted = install_ici_stages(converted, self.conf)
        if self.conf.get(cfg.LINT_ENABLED):
            # opt-in pre-flight: hazards the rewrite engine admitted but
            # the runtime would crash on (or quietly serve wrong/slow)
            # become structured diagnostics, and the subtrees with a
            # sound host fallback are downgraded instead of executed.
            # The lint runs flow-sensitively (spark.rapids.tpu.lint.infer,
            # on by default): the abstract interpreter's per-subtree
            # states decide the contract rules, so the downgrade set
            # includes violations only dataflow can see (TPU-L011 —
            # a contract broken BETWEEN its exchange and its consumer).
            from ..analysis.plan_lint import downgrade_hazards, lint_plan
            self.last_lint = lint_plan(converted, self.conf)
            if self.last_lint:
                converted = downgrade_hazards(converted, self.last_lint,
                                              self.conf)
                from ..analysis.diagnostics import format_diagnostics
                lint_text = "tpulint:\n" + \
                    format_diagnostics(self.last_lint)
                self.last_explain += "\n" + lint_text
                if explain_mode != "NONE":
                    print(lint_text, end="")
        from ..shuffle.aqe import (install_aqe_readers,
                                   relink_replicated_readers)
        converted = install_aqe_readers(converted, self.conf)
        # transition insertion clones nodes, so this must run LAST or a
        # replicated build reader keeps a stale pre-clone partner (which
        # re-shuffles the probe side and leaks the blocks)
        return relink_replicated_readers(insert_transitions(converted))
