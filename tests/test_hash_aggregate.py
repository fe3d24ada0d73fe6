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
