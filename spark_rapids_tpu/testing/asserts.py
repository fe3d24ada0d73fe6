"""Differential-test assertions.

Re-design of the reference's primary correctness net
(ref: integration_tests/src/main/python/asserts.py:434
assert_gpu_and_cpu_are_equal_collect, :14-60 recursive value compare,
:357 assert_gpu_fallback_collect): run the same query on the CPU engine
(spark.rapids.sql.enabled=false) and the TPU engine, deep-compare results
with float tolerance; fallback assertions capture the executed plan and
check an operator actually stayed on CPU.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import pyarrow as pa

from ..api.session import TpuSession

_TPU_CONF = {"spark.rapids.sql.enabled": True}
_CPU_CONF = {"spark.rapids.sql.enabled": False}


def _mk(conf: Dict) -> TpuSession:
    b = TpuSession.builder()
    for k, v in conf.items():
        b.config(k, v)
    return b.get_or_create()


def with_cpu_session(fn: Callable[[TpuSession], object],
                     conf: Optional[Dict] = None):
    c = dict(conf or {})
    c.update(_CPU_CONF)
    return fn(_mk(c))


def with_tpu_session(fn: Callable[[TpuSession], object],
                     conf: Optional[Dict] = None):
    c = dict(conf or {})
    c.update(_TPU_CONF)
    return fn(_mk(c))


def _val_equal(a, b, approx: float) -> bool:
    if a is None and b is None:
        return True
    if a is None or b is None:
        return False
    if isinstance(a, float) or isinstance(b, float):
        fa, fb = float(a), float(b)
        if math.isnan(fa) and math.isnan(fb):
            return True
        if math.isinf(fa) or math.isinf(fb):
            return fa == fb
        if approx > 0:
            denom = max(abs(fa), abs(fb), 1e-12)
            return abs(fa - fb) <= approx * denom or abs(fa - fb) < 1e-11
        return fa == fb
    if isinstance(a, dict) and isinstance(b, dict):
        return (set(a) == set(b)
                and all(_val_equal(a[k], b[k], approx) for k in a))
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return (len(a) == len(b)
                and all(_val_equal(x, y, approx) for x, y in zip(a, b)))
    return a == b


def _sort_key(row):
    def k(v):
        if v is None:
            return (0, "")
        if isinstance(v, bool):
            return (1, str(int(v)))
        if isinstance(v, (int, float)):
            if isinstance(v, float) and math.isnan(v):
                return (3, "nan")
            return (2, f"{float(v):+040.12e}")
        if isinstance(v, (list, tuple, dict)):
            return (4, str(v))
        return (4, str(v))
    return tuple(k(v) for v in row)


def _flat(table: pa.Table) -> bool:
    """Only fixed-width scalar columns: what the column-at-a-time
    comparison below can hold to the row-at-a-time rules."""
    return all(pa.types.is_integer(f.type) or pa.types.is_floating(f.type)
               or pa.types.is_boolean(f.type) for f in table.schema)


def _assert_flat_tables_equal(cpu: pa.Table, tpu: pa.Table,
                              ignore_order: bool, approx: float):
    """`assert_tables_equal` for flat tables, a column at a time with
    Arrow and NumPy: the same rules as `_val_equal` (nulls match nulls,
    NaN matches NaN, infinities exactly, floats within `approx`), at a
    cost that lets a result of tens of millions of rows be checked."""
    import numpy as np
    import pyarrow.compute as pc
    if ignore_order:
        # exact columns first, so that floats which differ in their
        # last digits cannot reorder rows that the other columns tell
        # apart
        names = sorted(cpu.schema.names,
                       key=lambda n: pa.types.is_floating(
                           cpu.schema.field(n).type))
        keys = [(n, "ascending") for n in names]
        cpu = cpu.take(pc.sort_indices(cpu, sort_keys=keys))
        tpu = tpu.take(pc.sort_indices(tpu, sort_keys=keys))
    bad = np.zeros(cpu.num_rows, dtype=bool)
    for name in cpu.schema.names:
        a, b = (t.column(name).combine_chunks() for t in (cpu, tpu))
        a_null = np.asarray(a.is_null())
        bad |= a_null != np.asarray(b.is_null())
        is_float = pa.types.is_floating(a.type) or \
            pa.types.is_floating(b.type)
        fill = 0.0 if is_float else 0
        if pa.types.is_boolean(a.type):
            fill = False
        av = a.fill_null(fill).to_numpy(zero_copy_only=False)
        bv = b.fill_null(fill).to_numpy(zero_copy_only=False)
        if not is_float:
            bad |= (av != bv) & ~a_null
            continue
        av, bv = av.astype(np.float64), bv.astype(np.float64)
        with np.errstate(invalid="ignore"):
            diff = np.abs(av - bv)
            if approx > 0:
                denom = np.maximum(np.maximum(np.abs(av), np.abs(bv)),
                                   1e-12)
                close = (diff <= approx * denom) | (diff < 1e-11)
            else:
                close = av == bv
        same = np.where(np.isnan(av) | np.isnan(bv),
                        np.isnan(av) & np.isnan(bv),
                        np.where(np.isinf(av) | np.isinf(bv), av == bv,
                                 close))
        bad |= ~same & ~a_null
    if bad.any():
        i = int(np.flatnonzero(bad)[0])
        raise AssertionError(
            f"row {i} differs ({int(bad.sum())} rows differ):\n"
            f"  cpu: {tuple(cpu.slice(i, 1).to_pylist()[0].values())}\n"
            f"  tpu: {tuple(tpu.slice(i, 1).to_pylist()[0].values())}")


def assert_tables_equal(cpu: pa.Table, tpu: pa.Table,
                        ignore_order: bool = True,
                        approximate_float: float = 0.0):
    assert cpu.schema.names == tpu.schema.names, \
        f"schema mismatch: {cpu.schema.names} vs {tpu.schema.names}"
    assert cpu.num_rows == tpu.num_rows, \
        f"row count: cpu={cpu.num_rows} tpu={tpu.num_rows}"
    if _flat(cpu) and _flat(tpu):
        return _assert_flat_tables_equal(cpu, tpu, ignore_order,
                                         approximate_float)
    crows = [tuple(r.values()) for r in cpu.to_pylist()]
    trows = [tuple(r.values()) for r in tpu.to_pylist()]
    assert len(crows) == len(trows), \
        f"row count: cpu={len(crows)} tpu={len(trows)}"
    if ignore_order:
        crows = sorted(crows, key=_sort_key)
        trows = sorted(trows, key=_sort_key)
    for i, (cr, tr) in enumerate(zip(crows, trows)):
        if not _val_equal(list(cr), list(tr), approximate_float):
            raise AssertionError(
                f"row {i} differs:\n  cpu: {cr}\n  tpu: {tr}")


def assert_tpu_and_cpu_are_equal_collect(
        df_fn: Callable[[TpuSession], "object"],
        conf: Optional[Dict] = None,
        ignore_order: bool = True,
        approximate_float: float = 0.0):
    """Run the query builder against both engines and compare results
    (ref asserts.py:434)."""
    cpu = with_cpu_session(lambda s: df_fn(s).collect(), conf)
    tpu = with_tpu_session(lambda s: df_fn(s).collect(), conf)
    assert_tables_equal(cpu, tpu, ignore_order, approximate_float)
    return cpu, tpu


def assert_tpu_fallback_collect(
        df_fn: Callable[[TpuSession], "object"],
        cpu_exec_name: str,
        conf: Optional[Dict] = None,
        ignore_order: bool = True,
        approximate_float: float = 0.0):
    """Verify the op stayed on CPU *and* results match
    (ref asserts.py:357 + ExecutionPlanCaptureCallback)."""
    cpu = with_cpu_session(lambda s: df_fn(s).collect(), conf)

    c = dict(conf or {})
    c.update(_TPU_CONF)
    session = _mk(c)
    tpu = df_fn(session).collect()
    plan = session.last_plan
    found = []
    plan.foreach(lambda e: found.append(type(e).__name__))
    from ..exec.base import CPU as _CPU
    cpu_placed = []
    plan.foreach(lambda e: cpu_placed.append(type(e).__name__)
                 if e.placement == _CPU else None)
    assert any(cpu_exec_name in n for n in cpu_placed), \
        (f"expected {cpu_exec_name} to fall back to CPU; CPU-placed: "
         f"{cpu_placed}; all: {found}")
    assert_tables_equal(cpu, tpu, ignore_order, approximate_float)


def assert_tpu_and_cpu_error(df_fn, conf, error_message: str):
    """Both engines must raise with the message (ref asserts.py:495)."""
    for runner in (with_cpu_session, with_tpu_session):
        try:
            runner(lambda s: df_fn(s).collect(), conf)
            raise AssertionError(
                f"expected error '{error_message}' but query succeeded")
        except AssertionError:
            raise
        except Exception as ex:
            assert error_message in str(ex), \
                f"expected '{error_message}' in '{ex}'"
