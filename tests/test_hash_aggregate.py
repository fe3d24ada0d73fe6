"""Hash aggregate differential tests (model: integration_tests/
hash_aggregate_test.py — the reference's first-line aggregate coverage)."""

import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col, lit
from spark_rapids_tpu.testing.asserts import (
    assert_tpu_and_cpu_are_equal_collect)
from spark_rapids_tpu.testing.data_gen import (
    ByteGen, DoubleGen, FloatGen, IntegerGen, LongGen, ShortGen, StringGen,
    gen_df)

_int_key_gens = [ByteGen(), ShortGen(), IntegerGen(), LongGen()]


@pytest.mark.parametrize("key_gen", _int_key_gens,
                         ids=lambda g: type(g).__name__)
def test_group_by_sum_int_keys(key_gen):
    def q(spark):
        df = gen_df(spark, [("k", key_gen), ("v", LongGen())], length=512)
        return df.group_by(col("k")).agg(F.sum(col("v")).alias("s"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_group_by_sum_avg_count():
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen()), ("v", LongGen()),
                            ("f", DoubleGen(no_nans=True))], length=1024)
        return df.group_by(col("k")).agg(
            F.sum(col("v")).alias("sv"),
            F.avg(col("f")).alias("af"),
            F.count(col("v")).alias("cv"),
            F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-9)


def test_group_by_min_max():
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen()), ("v", LongGen()),
                            ("f", DoubleGen(no_nans=True))], length=1024)
        return df.group_by(col("k")).agg(
            F.min(col("v")).alias("mn"), F.max(col("v")).alias("mx"),
            F.min(col("f")).alias("fmn"), F.max(col("f")).alias("fmx"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-9)


def test_group_by_string_keys():
    def q(spark):
        df = gen_df(spark, [("k", StringGen(max_len=8)), ("v", LongGen())],
                    length=1024)
        return df.group_by(col("k")).agg(F.sum(col("v")).alias("s"),
                                         F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_global_aggregate():
    def q(spark):
        df = gen_df(spark, [("v", LongGen()), ("f", DoubleGen(no_nans=True))],
                    length=777)
        return df.agg(F.sum(col("v")).alias("s"),
                      F.count("*").alias("c"),
                      F.avg(col("f")).alias("a"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-9)


def test_global_aggregate_empty_input():
    def q(spark):
        df = gen_df(spark, [("v", LongGen())], length=64)
        return df.filter(lit(False)).agg(F.count("*").alias("c"),
                                         F.sum(col("v")).alias("s"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_group_by_with_nulls_in_keys():
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen(null_prob=0.5)),
                            ("v", LongGen())], length=512)
        return df.group_by(col("k")).agg(F.sum(col("v")).alias("s"),
                                         F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_group_by_multiple_keys():
    def q(spark):
        df = gen_df(spark, [("k1", IntegerGen()), ("k2", StringGen(max_len=4)),
                            ("k3", ByteGen()), ("v", LongGen())], length=2048)
        return df.group_by(col("k1"), col("k2"), col("k3")).agg(
            F.sum(col("v")).alias("s"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_first_last():
    # first/last are order-dependent: use a sorted single-partition input
    def q(spark):
        df = spark.create_dataframe({
            "k": [1, 1, 1, 2, 2, 3],
            "v": [10, None, 30, 40, 50, None]})
        return df.group_by(col("k")).agg(
            F.first(col("v")).alias("f"),
            F.last(col("v")).alias("l"),
            F.first(col("v"), ignorenulls=True).alias("fn"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_stddev_variance():
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen(lo=0, hi=20)),
                            ("v", DoubleGen(no_nans=True))], length=1024)
        df = df.filter(col("v").is_not_null() &
                       (F.abs(col("v")) < lit(1e6)))
        return df.group_by(col("k")).agg(
            F.stddev(col("v")).alias("sd"),
            F.var_pop(col("v")).alias("vp"),
            F.count("*").alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-6)


def test_avg_overflow_like_reference_config():
    """BASELINE.json config 1: single-partition GROUP BY SUM/AVG int/long."""
    def q(spark):
        df = gen_df(spark, [("k", LongGen()), ("i", IntegerGen()),
                            ("l", LongGen())], length=4096)
        return df.group_by(col("k")).agg(
            F.sum(col("i")).alias("si"), F.avg(col("i")).alias("ai"),
            F.sum(col("l")).alias("sl"), F.avg(col("l")).alias("al"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-9)


def test_group_by_min_max_strings():
    """Ordered reduce over variable-width values (regression: min/max on
    strings used to return the first value per group)."""
    def q(spark):
        df = gen_df(spark, [("k", IntegerGen(nullable=False)),
                            ("s", StringGen())], length=512)
        return df.group_by(col("k")).agg(
            F.min(col("s")).alias("mn"), F.max(col("s")).alias("mx"),
            F.count(col("s")).alias("c"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_global_min_max_strings():
    def q(spark):
        df = gen_df(spark, [("s", StringGen())], length=256)
        return df.agg(F.min(col("s")).alias("mn"),
                      F.max(col("s")).alias("mx"))
    assert_tpu_and_cpu_are_equal_collect(q)


def test_collect_list_and_set():
    """collect_list/collect_set (ref AggregateFunctions.scala
    GpuCollectList/GpuCollectSet): list keeps duplicates in row order
    within the engine's key-sorted layout, set dedupes; nulls dropped."""
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    rng = np.random.default_rng(5)
    n = 3000
    tb = pa.table({
        "k": pa.array(rng.integers(0, 40, n).astype(np.int64)),
        "v": pa.array([None if i % 7 == 0 else int(x) for i, x in
                       enumerate(rng.integers(0, 15, n))],
                      type=pa.int64()),
    })
    out = (s.create_dataframe(tb, num_partitions=3)
           .group_by(col("k"))
           .agg(F.collect_list(col("v")).alias("cl"),
                F.collect_set(col("v")).alias("cs"))
           .collect().sort_by("k"))
    placements = []
    s.last_plan.foreach(lambda e: placements.append(
        (type(e).__name__, e.placement)))
    assert any(n_ == "TpuHashAggregateExec" and p == "tpu"
               for n_, p in placements), placements
    # oracle
    want = {}
    for k, v in zip(tb.column("k").to_pylist(), tb.column("v").to_pylist()):
        want.setdefault(k, []).append(v)
    got_k = out.column("k").to_pylist()
    got_cl = out.column("cl").to_pylist()
    got_cs = out.column("cs").to_pylist()
    assert got_k == sorted(want)
    for k, cl, cs in zip(got_k, got_cl, got_cs):
        ref = [v for v in want[k] if v is not None]
        assert sorted(cl) == sorted(ref), (k, "list contents")
        assert sorted(cs) == sorted(set(ref)), (k, "set contents")


def test_collect_differential_cpu_vs_tpu():
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSession
    rng = np.random.default_rng(9)
    n = 1200
    tb = pa.table({
        "k": pa.array(rng.integers(0, 25, n).astype(np.int64)),
        "v": pa.array([None if i % 5 == 0 else int(x) for i, x in
                       enumerate(rng.integers(-8, 8, n))],
                      type=pa.int64()),
    })
    res = {}
    for enabled in (True, False):
        s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                        enabled).get_or_create()
        out = (s.create_dataframe(tb, num_partitions=2)
               .group_by(col("k"))
               .agg(F.collect_list(col("v")).alias("cl"),
                    F.collect_set(col("v")).alias("cs"))
               .collect().sort_by("k"))
        res[enabled] = (out.column("k").to_pylist(),
                        [sorted(x) for x in out.column("cl").to_pylist()],
                        [sorted(x) for x in out.column("cs").to_pylist()])
    assert res[True] == res[False]


def test_collect_list_strings():
    import pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    tb = pa.table({
        "k": pa.array([1, 1, 2, 2, 1, 2]),
        "s": pa.array(["a", "bb", "x", None, "a", "x"]),
    })
    out = (s.create_dataframe(tb).group_by(col("k"))
           .agg(F.collect_list(col("s")).alias("cl"),
                F.collect_set(col("s")).alias("cs"))
           .collect().sort_by("k"))
    cl = [sorted(x) for x in out.column("cl").to_pylist()]
    cs = [sorted(x) for x in out.column("cs").to_pylist()]
    assert cl == [["a", "a", "bb"], ["x", "x"]]
    assert cs == [["a", "bb"], ["x"]]


def test_pivot():
    """groupBy().pivot(col, values).agg(...) — each pivot value becomes a
    masked aggregate fused into one kernel pass (ref GpuPivotFirst in
    AggregateFunctions.scala)."""
    import numpy as np
    import pyarrow as pa
    import pandas as pd
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    rng = np.random.default_rng(3)
    n = 2000
    cats = ["red", "green", "blue"]
    tb = pa.table({
        "k": pa.array(rng.integers(0, 30, n).astype(np.int64)),
        "p": pa.array([cats[i] for i in rng.integers(0, 3, n)]),
        "v": pa.array(rng.integers(-100, 100, n).astype(np.int64)),
    })
    out = (s.create_dataframe(tb, num_partitions=2)
           .group_by(col("k")).pivot(col("p"), cats)
           .agg(F.sum(col("v")).alias("sv"))
           .collect().sort_by("k"))
    pdf = tb.to_pandas()
    want = pdf.pivot_table(index="k", columns="p", values="v",
                           aggfunc="sum")
    got_k = out.column("k").to_pylist()
    assert got_k == sorted(set(pdf.k))
    for c in cats:
        got = out.column(c).to_pylist()
        exp = [None if pd.isna(x) else int(x)
               for x in want[c].reindex(got_k)]
        assert got == exp, c


def test_pivot_inferred_values_multiple_aggs():
    import pyarrow as pa
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    tb = pa.table({
        "k": pa.array([1, 1, 2, 2, 2]),
        "p": pa.array(["a", "b", "a", "a", "b"]),
        "v": pa.array([10, 20, 30, 40, 50]),
    })
    out = (s.create_dataframe(tb).group_by(col("k"))
           .pivot(col("p"))
           .agg(F.sum(col("v")).alias("sv"),
                F.count(col("v")).alias("cv"))
           .collect().sort_by("k"))
    assert out.column("a_sv").to_pylist() == [10, 70]
    assert out.column("b_sv").to_pylist() == [20, 50]
    assert out.column("a_cv").to_pylist() == [1, 2]
    assert out.column("b_cv").to_pylist() == [1, 1]


def test_group_reduce_scale_and_skew_differential():
    import numpy as np
    import pyarrow as pa

    """Carry-sort group-by at 100k rows with skew, nulls, strings,
    decimals, and every reduction family — differential vs the CPU
    engine (the scale/skew case the small generator tests miss)."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession

    rng = np.random.default_rng(1234)
    n = 100_000
    hot = rng.random(n) < 0.35
    k = np.where(hot, 7, rng.integers(0, 500, n)).astype(np.int64)
    kmask = rng.random(n) < 0.02
    v = rng.integers(-(10**12), 10**12, n).astype(np.int64)
    vmask = rng.random(n) < 0.1
    f = rng.random(n) * rng.choice([1.0, 1e12], n)
    s_ = np.array([f"name_{int(x):03d}" for x in rng.integers(0, 97, n)],
                  dtype=object)
    tbl = pa.table({
        "k": pa.array(k, mask=kmask),
        "v": pa.array(v, mask=vmask),
        "f": pa.array(f),
        "s": pa.array(s_.tolist()),
        "d": pa.array((v % 10**10).tolist(),
                      type=pa.decimal128(12, 2)).cast(pa.decimal128(12, 2)),
    })

    def q(enabled):
        sess = (TpuSession.builder()
                .config("spark.rapids.sql.enabled", enabled)
                .get_or_create())
        df = sess.create_dataframe(tbl)
        return (df.group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv"),
                     F.avg(col("f")).alias("af"),
                     F.min(col("v")).alias("mv"),
                     F.max(col("f")).alias("xf"),
                     F.min(col("s")).alias("ms"),
                     F.sum(col("d")).alias("sd"),
                     F.count(col("v")).alias("cv"),
                     F.count("*").alias("c"))
                .collect().sort_by("k"))

    tpu, cpu = q(True), q(False)
    assert tpu.num_rows == cpu.num_rows
    for name in tpu.column_names:
        a, b = tpu.column(name).to_pylist(), cpu.column(name).to_pylist()
        for x, y in zip(a, b):
            if isinstance(x, float) and isinstance(y, float):
                assert x == y or abs(x - y) <= 1e-9 * max(1.0, abs(x),
                                                          abs(y)), name
            else:
                assert x == y, (name, x, y)


# -- the ungrouped aggregate: a reduction under the mask, no sort -------------
# (exec/aggregate._reduce_ungrouped; every case counts on its own)

def _ungrouped_inputs():
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(30)
    n = 700                                  # 324 padding rows of the bucket
    i = rng.integers(-(10**15), 10**15, n)
    f = rng.standard_normal(n) * 10.0 ** rng.integers(-3, 9, n)
    some = rng.random(n) < 0.3
    some[[0, n - 1]] = True                  # first and last rows null

    def table(fvals, mask):
        return pa.table({"i": pa.array(i, mask=mask),
                         "f": pa.array(fvals, mask=mask)})

    def with_at(values, where):
        out = f.copy()
        out[where] = values
        return out
    none = np.zeros(n, bool)
    return {
        "plain": table(f, none),
        "nulls": table(f, some),
        "all_null": table(f, np.ones(n, bool)),
        "pos_inf": table(with_at(np.inf, [5, 77]), some),
        "both_infs": table(with_at([np.inf, -np.inf], [5, 77]), some),
        "nan": table(with_at(np.nan, [3, 400]), some),
        "nan_and_inf": table(with_at([np.nan, np.inf], [3, 400]), none),
    }


_UNGROUPED_OPS = {
    "sum_int": lambda: F.sum(col("i")),
    "sum_float": lambda: F.sum(col("f")),
    "count": lambda: F.count(col("f")),
    "count_star": lambda: F.count("*"),
    "avg": lambda: F.avg(col("f")),
    "min_int": lambda: F.min(col("i")),
    "max_int": lambda: F.max(col("i")),
    "min_float": lambda: F.min(col("f")),
    "max_float": lambda: F.max(col("f")),
    "first": lambda: F.first(col("f")),
    "last": lambda: F.last(col("f")),
    "first_ignore_nulls": lambda: F.first(col("f"), ignorenulls=True),
    "last_ignore_nulls": lambda: F.last(col("i"), ignorenulls=True),
}
_UNGROUPED_INPUTS = ["plain", "nulls", "all_null", "empty", "pos_inf",
                     "both_infs", "nan", "nan_and_inf"]


@pytest.mark.parametrize("op", sorted(_UNGROUPED_OPS))
@pytest.mark.parametrize("data", _UNGROUPED_INPUTS)
def test_ungrouped_reduction_against_the_cpu_engine(data, op):
    tbl = _ungrouped_inputs()["plain" if data == "empty" else data]

    def q(spark):
        df = spark.create_dataframe(tbl, num_partitions=1)
        if data == "empty":
            df = df.filter(lit(False))
        return df.agg(_UNGROUPED_OPS[op]().alias("x"))
    if op == "max_float" and data in ("nan", "nan_and_inf"):
        # Spark orders NaN above every value; pyarrow's max, the CPU
        # engine's, skips it: hold the TPU path to Spark
        import math
        from spark_rapids_tpu.testing.asserts import with_tpu_session
        out = with_tpu_session(lambda s: q(s).collect())
        assert math.isnan(out.column("x")[0].as_py())
        return
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-12)


def _reduce_both_arms(data, monkeypatch):
    """Every reducible op over one input through `_group_reduce`: the
    masked arm under jit, the masked arm on numpy, and the sort arm under
    jit (the choice of arm held to "not reducible")."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.exec import aggregate as agg
    tbl = _ungrouped_inputs()[data]
    ops = ["sum", "sum", "countvalid", "min", "max", "min", "max", "first",
           "last", "first_any", "last_any"]
    names = ["i", "f", "f", "i", "i", "f", "f", "f", "i", "f", "i"]

    def run(xp, batch):
        live = xp.arange(batch.capacity, dtype=np.int32) < batch.num_rows
        cols = [batch.columns[batch.names.index(n)] for n in names]
        _, values, n_groups = agg._group_reduce(xp, [], cols, ops,
                                                batch.capacity, live, True)
        return values, n_groups
    host = batch_to_device(tbl.to_batches()[0], xp=np)
    dev = batch_to_device(tbl.to_batches()[0], xp=jnp)
    masked_np = run(np, host)
    masked = jax.jit(lambda b: run(jnp, b))(dev)
    monkeypatch.setattr(agg, "_ungrouped_reducible", lambda *_: False)
    sort_arm = jax.jit(lambda b: run(jnp, b))(dev)
    return ops, masked_np, masked, sort_arm


@pytest.mark.parametrize("data", [d for d in _UNGROUPED_INPUTS
                                  if d != "empty"])
def test_masked_arm_answers_as_the_sort_arm(data, monkeypatch):
    """Null, inf, nan and first/last semantics are the sort arm's to the
    letter: only a float sum may differ, in its last bits."""
    import numpy as np
    from spark_rapids_tpu.columnar.device import DEFAULT_ROW_BUCKETS
    ops, masked_np, masked, sort_arm = _reduce_both_arms(data, monkeypatch)
    assert int(masked[1]) == int(masked_np[1]) == int(sort_arm[1]) == 1
    for op, a, b, c in zip(ops, masked_np[0], masked[0], sort_arm[0]):
        assert a.capacity == b.capacity == DEFAULT_ROW_BUCKETS[0]
        assert list(np.asarray(a.validity)[1:]) == \
            list(np.asarray(b.validity)[1:]) == [False] * (a.capacity - 1)
        va, vb, vc = (bool(np.asarray(x.validity)[0]) for x in (a, b, c))
        assert va == vb == vc, op
        xa, xb, xc = (np.asarray(x.data)[0] for x in (a, b, c))
        if op == "sum" and xa.dtype.kind == "f" and np.isfinite(xc):
            scale = float(np.abs(np.nan_to_num(np.asarray(
                _ungrouped_inputs()[data].column("f")), posinf=0.0,
                neginf=0.0)).sum())
            assert abs(xa - xc) <= 1e-13 * scale, op
            assert abs(xb - xc) <= 1e-13 * scale, op
        else:
            assert xa.tobytes() == xb.tobytes() == xc.tobytes(), \
                (op, xa, xb, xc)


def test_ungrouped_mix_with_collect_list_takes_the_sort_arm():
    """One op that compacts values sends the whole call down the sort
    arm, which still answers."""
    from spark_rapids_tpu.ops import carry
    tbl = _ungrouped_inputs()["nulls"]

    def q(spark):
        df = spark.create_dataframe(tbl, num_partitions=1)
        return df.agg(F.sum(col("i")).alias("s"),
                      F.collect_list(col("i")).alias("l"),
                      F.max(col("f")).alias("m"))
    before = carry.lane_move_counts()
    _, tpu = assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-12)
    after = carry.lane_move_counts()
    assert after["ungrouped_reduced"] == before["ungrouped_reduced"]
    assert tpu.column("l")[0].as_py() == \
        [v for v in tbl.column("i").to_pylist() if v is not None]


@pytest.mark.parametrize("parts", [1, 3])
def test_ungrouped_multi_batch_partial_then_final(parts):
    """Several batches: each update leaves a one-row partial in the
    smallest bucket, and the merge reduces the handful of them."""
    import pyarrow as pa
    tbl = _ungrouped_inputs()["nulls"]
    tbl = pa.Table.from_batches(tbl.to_batches(max_chunksize=150))

    def q(spark):
        df = spark.create_dataframe(tbl, num_partitions=parts)
        return df.agg(F.sum(col("i")).alias("s"), F.sum(col("f")).alias("sf"),
                      F.avg(col("f")).alias("a"), F.count("*").alias("c"),
                      F.min(col("f")).alias("mn"), F.max(col("i")).alias("mx"))
    assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-12)


# -- a few groups of a bounded key: the dense arm, no sort --------------------
# (exec/aggregate._reduce_dense; every case counts on its own)

_DENSE_N, _DENSE_CAP = 700, 1024
_DENSE_OPS = ["sum", "sum", "countvalid", "min", "max", "min", "max",
              "first", "last", "first_any", "last_any"]
_DENSE_LANES = ["i", "f", "f", "i", "i", "f", "f", "f", "i", "f", "i"]
_DENSE_KEYS = ["two_char1", "bool_x_byte", "short", "char2_with_nulls"]
_DENSE_INPUTS = ["plain", "nulls", "inf_nan", "one_group", "empty",
                 "all_null_group", "padding_in_the_middle"]


def _dense_keys(kind, rng, one_group, null_keys):
    """Key columns of `kind` over the capacity, a few distinct values."""
    import numpy as np
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn

    def pick(values, dtype):
        values = np.asarray(values, dtype)
        return values[:1].repeat(_DENSE_CAP) if one_group else \
            rng.choice(values, _DENSE_CAP)

    def validity():
        if not null_keys:
            return np.ones(_DENSE_CAP, bool)
        return rng.random(_DENSE_CAP) > 0.15

    def string(words, dtype, width):
        valid = validity()
        return DeviceColumn.fixed_string(
            t.STRING, np.where(valid, pick(words, dtype), 0).astype(dtype),
            valid, width)

    def flat(dtype, values, np_dtype):
        valid = validity()
        return DeviceColumn(dtype, validity=valid, data=np.where(
            valid, pick(values, np_dtype), 0).astype(np_dtype))
    if kind == "two_char1":
        return [string([65, 78, 82], np.uint8, 1),
                string([70, 79], np.uint8, 1)]
    if kind == "bool_x_byte":
        return [flat(t.BOOLEAN, [True, False], bool),
                flat(t.BYTE, [-128, -3, 0, 5, 127], np.int8)]
    if kind == "short":
        return [flat(t.SHORT, [-32768, -1, 0, 1, 300, 32767], np.int16)]
    # a fixed-width string takes nulls only from an operator above the
    # scan: always some here
    valid = rng.random(_DENSE_CAP) > 0.2
    words = pick([0x4142, 0x4143, 0x5A5A, 0x0001, 0xFFFF], np.uint16)
    return [DeviceColumn.fixed_string(
        t.STRING, np.where(valid, words, 0).astype(np.uint16), valid, 2)]


def _dense_case(keys, data):
    """(key columns, value columns by lane name, live) on the host."""
    import numpy as np
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn
    rng = np.random.default_rng(
        32 + 7 * _DENSE_KEYS.index(keys) + _DENSE_INPUTS.index(data))
    key_cols = _dense_keys(keys, rng, one_group=(data == "one_group"),
                           null_keys=(data == "nulls"))
    i = rng.integers(-(10**15), 10**15, _DENSE_CAP)
    f = rng.standard_normal(_DENSE_CAP) * \
        10.0 ** rng.integers(-3, 9, _DENSE_CAP)
    valid = np.ones(_DENSE_CAP, bool)
    if data in ("nulls", "inf_nan"):
        valid = rng.random(_DENSE_CAP) > 0.3
    if data == "inf_nan":
        f[rng.choice(_DENSE_CAP, 40, replace=False)] = \
            rng.choice([np.inf, -np.inf, np.nan], 40)
    if data == "all_null_group":
        first = key_cols[0]
        lane = first.word if first.fixed_width is not None else first.data
        valid = lane != lane[0]
    live = np.arange(_DENSE_CAP) < _DENSE_N
    if data == "empty":
        live = np.zeros(_DENSE_CAP, bool)
    if data == "padding_in_the_middle":
        live = rng.random(_DENSE_CAP) > 0.4
    values = {"i": DeviceColumn(t.LONG, data=np.where(valid, i, 0),
                                validity=valid),
              "f": DeviceColumn(t.DOUBLE, data=np.where(valid, f, 0.0),
                                validity=valid)}
    return key_cols, values, live


def _group_reduce_three_ways(key_cols, value_cols, ops, live, monkeypatch):
    """`_group_reduce` as it chooses under jit, on the CPU engine, and
    with the dense arm's cap at no group at all (so that any row sends
    the conditional down its sort branch), under jit."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.exec import aggregate as agg

    def run(xp, keys, values, live):
        return agg._group_reduce(xp, keys, values, ops, _DENSE_CAP, live,
                                 False)
    dev = jax.tree_util.tree_map(jnp.asarray, (key_cols, value_cols, live))
    on_host = run(np, key_cols, value_cols, live)
    chosen = jax.jit(lambda a: run(jnp, *a))(dev)
    monkeypatch.setattr(agg, "_DENSE_GROUPS_MAX", 0)
    sort_arm = jax.jit(lambda a: run(jnp, *a))(dev)
    return on_host, chosen, sort_arm


def _assert_same_groups(ops, value_cols, live, results):
    """Group order, keys, validity, counts and integer results byte-equal
    over the groups; float sums within 1e-13 of the terms' absolute sum."""
    import numpy as np
    n = int(results[-1][2])
    assert [int(r[2]) for r in results] == [n] * len(results)
    want_keys, want_values, _ = results[-1]
    for keys, values, _ in results[:-1]:
        for a, b in zip(keys, want_keys):
            assert a.capacity == b.capacity and a.dtype == b.dtype
            lane = "word" if a.fixed_width is not None else "data"
            assert np.asarray(getattr(a, lane))[:n].tobytes() == \
                np.asarray(getattr(b, lane))[:n].tobytes()
            assert np.asarray(a.validity).tobytes() == \
                np.asarray(b.validity).tobytes()
        for op, vc, a, b in zip(ops, value_cols, values, want_values):
            assert a.capacity == b.capacity and a.dtype == b.dtype
            va, vb = np.asarray(a.validity), np.asarray(b.validity)
            assert va.tobytes() == vb.tobytes(), op
            xa = np.asarray(a.data)[:n][va[:n]]
            xb = np.asarray(b.data)[:n][va[:n]]
            if op == "sum" and xa.dtype.kind == "f":
                terms = np.asarray(vc.data)[live & np.asarray(vc.validity)]
                scale = float(np.abs(terms[np.isfinite(terms)]).sum())
                finite = np.isfinite(xb)
                assert xa[~finite].tobytes() == xb[~finite].tobytes(), op
                assert np.all(np.abs(xa[finite] - xb[finite])
                              <= 1e-13 * scale), op
            else:
                assert xa.tobytes() == xb.tobytes(), (op, xa, xb)


@pytest.mark.parametrize("data", _DENSE_INPUTS)
@pytest.mark.parametrize("keys", _DENSE_KEYS)
def test_dense_arm_answers_as_the_sort_arm(keys, data, monkeypatch):
    """A bounded key with a few groups: the dense arm (the groups found
    by masked mins, each reduced under its mask, its key read from its
    code) against the sort arm and the CPU engine."""
    import numpy as np
    from spark_rapids_tpu.ops import carry
    key_cols, lanes, live = _dense_case(keys, data)
    value_cols = [lanes[name] for name in _DENSE_LANES]
    before = carry.lane_move_counts()
    results = _group_reduce_three_ways(key_cols, value_cols, _DENSE_OPS,
                                       live, monkeypatch)
    after = carry.lane_move_counts()
    assert after["grouped_dense"] - before["grouped_dense"] == 3
    assert after["grouped_sorted"] == before["grouped_sorted"]
    _assert_same_groups(_DENSE_OPS, value_cols, live, results)
    n = int(results[0][2])
    assert (n == 0) == (data == "empty")
    # (the fixed-width string with nulls always has its null group)
    assert data != "one_group" or n == 1 + (keys == "char2_with_nulls")
    if data == "all_null_group":
        # the group is there, its count 0 and its sum null
        total, count = results[1][1][1], results[1][1][2]
        assert not np.asarray(total.validity)[:n].all()
        assert 0 in np.asarray(count.data)[:n].tolist()
        assert np.asarray(count.validity)[:n].all()


def test_more_groups_than_the_dense_arm_walks_take_the_sort_branch(
        monkeypatch):
    """A short bounds its groups at 65,537; one more distinct key than
    `_DENSE_GROUPS_MAX` and the same program answers from the sort arm
    (the CPU engine, whose count is concrete, calls it)."""
    import numpy as np
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn
    from spark_rapids_tpu.exec import aggregate as agg
    rng = np.random.default_rng(64)
    distinct = agg._DENSE_GROUPS_MAX + 1
    data = (rng.permutation(_DENSE_CAP) % distinct * 200 - 31000) \
        .astype(np.int16)
    live = np.ones(_DENSE_CAP, bool)
    key = DeviceColumn(t.SHORT, data=data, validity=live)
    _, lanes, _ = _dense_case("short", "nulls")
    value_cols = [lanes[name] for name in _DENSE_LANES]
    calls = []
    sort_segment = agg._sort_segment
    monkeypatch.setattr(agg, "_sort_segment", lambda xp, *a: (
        calls.append(xp), sort_segment(xp, *a))[1])
    results = _group_reduce_three_ways([key], value_cols, _DENSE_OPS, live,
                                       monkeypatch)
    assert calls[0] is np and len(calls) == 3
    assert int(results[1][2]) == distinct
    _assert_same_groups(_DENSE_OPS, value_cols, live, results)
    got = np.asarray(results[1][0][0].data)[:distinct]
    assert got.tolist() == sorted(set(data[live].tolist()))


def test_an_unbounded_key_lowers_without_a_conditional():
    """An int64 key is the sort arm alone, as it was: the choice is made
    from the key's type while the program is traced, and nothing of the
    dense arm is in the text.  A short key holds both."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn
    from spark_rapids_tpu.exec import aggregate as agg
    _, lanes, live = _dense_case("short", "plain")

    def text(key):
        args = jax.tree_util.tree_map(jnp.asarray, (key, lanes["f"], live))
        return jax.jit(lambda k, v, m: agg._group_reduce(
            jnp, [k], [v, v], ["sum", "countvalid"], _DENSE_CAP, m,
            False)).lower(*args).as_text()
    ones = np.ones(_DENSE_CAP, bool)
    unbounded = text(DeviceColumn(
        t.LONG, data=np.arange(_DENSE_CAP) % 5, validity=ones))
    bounded = text(DeviceColumn(
        t.SHORT, data=(np.arange(_DENSE_CAP) % 5).astype(np.int16),
        validity=ones))
    # (a `stablehlo.case` is also how a float64 lane's move asks which
    # platform it is lowered for: the sort arm has some of its own)
    assert "stablehlo.while" not in unbounded
    assert "stablehlo.sort" in unbounded and "stablehlo.sort" in bounded
    assert bounded.count("stablehlo.while") == 2
    assert bounded.count("stablehlo.case") > unbounded.count("stablehlo.case")


def _bounded_key_table():
    import numpy as np
    import pyarrow as pa
    rng = np.random.default_rng(132)
    n = 900
    return pa.table({
        "r": pa.array(rng.choice(["A", "N", "R"], n)),
        "s": pa.array(rng.choice(["F", "O"], n)),
        "i": pa.array(rng.integers(-(10**12), 10**12, n),
                      mask=rng.random(n) < 0.2),
        "f": pa.array(rng.standard_normal(n) * 1e4,
                      mask=rng.random(n) < 0.2)})


def test_grouped_mix_with_collect_list_takes_the_sort_arm():
    """One op that compacts values sends a bounded-key call down the sort
    arm alone, which still answers."""
    from spark_rapids_tpu.ops import carry
    tbl = _bounded_key_table()

    def q(spark):
        df = spark.create_dataframe(tbl, num_partitions=1)
        return df.group_by(col("r"), col("s")).agg(
            F.sum(col("i")).alias("s_i"),
            F.collect_list(col("i")).alias("l"),
            F.max(col("f")).alias("m")).order_by(col("r"), col("s"))
    before = carry.lane_move_counts()
    _, tpu = assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-12)
    after = carry.lane_move_counts()
    assert after["grouped_dense"] == before["grouped_dense"]
    assert after["grouped_sorted"] > before["grouped_sorted"]
    assert tpu.num_rows == 6


@pytest.mark.parametrize("parts", [1, 3])
def test_bounded_key_multi_batch_partial_then_final(parts):
    """Several batches: each update leaves its few groups as a partial,
    and the merge (sums of sums and counts, all reducible) takes the dense
    arm too."""
    import pyarrow as pa
    from spark_rapids_tpu.ops import carry
    tbl = _bounded_key_table()
    tbl = pa.Table.from_batches(tbl.to_batches(max_chunksize=200))

    def q(spark):
        df = spark.create_dataframe(tbl, num_partitions=parts)
        return df.group_by(col("r"), col("s")).agg(
            F.sum(col("i")).alias("s_i"), F.sum(col("f")).alias("s_f"),
            F.avg(col("f")).alias("a"), F.count("*").alias("c"),
            F.count(col("i")).alias("c_i"), F.min(col("f")).alias("mn"),
            F.max(col("i")).alias("mx")).order_by(col("r"), col("s"))
    before = carry.lane_move_counts()
    _, tpu = assert_tpu_and_cpu_are_equal_collect(q, approximate_float=1e-12)
    after = carry.lane_move_counts()
    assert after["grouped_dense"] > before["grouped_dense"]
    if parts == 1:
        # (across partitions the keys come back from the host shuffle in
        # the general string layout, which bounds nothing)
        assert after["grouped_sorted"] == before["grouped_sorted"]
    assert tpu.num_rows == 6
