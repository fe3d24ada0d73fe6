"""Join differential tests (model: integration_tests/join_test.py)."""

import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col, lit
from spark_rapids_tpu.testing.asserts import (
    assert_tpu_and_cpu_are_equal_collect)
from spark_rapids_tpu.testing.data_gen import (
    IntegerGen, LongGen, StringGen, gen_df)

ALL_JOINS = ["inner", "left", "right", "full", "left_semi", "left_anti"]


def _sides(spark, key_gen, length=256):
    a = gen_df(spark, [("k", key_gen), ("va", LongGen())],
               length=length, seed=10)
    b = gen_df(spark, [("k2", key_gen), ("vb", LongGen())],
               length=length // 2, seed=20)
    return a, b


@pytest.mark.parametrize("how", ALL_JOINS)
def test_equi_join_int_keys(how):
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=50))
        return a.join(b, on=(col("k") == col("k2")), how=how)
    assert_tpu_and_cpu_are_equal_collect(q)


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_equi_join_string_keys(how):
    def q(spark):
        a = gen_df(spark, [("k", StringGen(max_len=4)), ("va", LongGen())],
                   length=256, seed=1)
        b = gen_df(spark, [("k2", StringGen(max_len=4)), ("vb", LongGen())],
                   length=128, seed=2)
        return a.join(b, on=(col("k") == col("k2")), how=how)
    assert_tpu_and_cpu_are_equal_collect(q)


@pytest.mark.parametrize("how", ALL_JOINS)
def test_join_null_keys(how):
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=5, null_prob=0.4), 64)
        return a.join(b, on=(col("k") == col("k2")), how=how)
    assert_tpu_and_cpu_are_equal_collect(q)


def test_using_join():
    def q(spark):
        a = gen_df(spark, [("k", IntegerGen(lo=0, hi=20)),
                           ("va", LongGen())], length=128, seed=3)
        b = gen_df(spark, [("k", IntegerGen(lo=0, hi=20)),
                           ("vb", LongGen())], length=64, seed=4)
        return a.join(b, on="k", how="inner")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_multi_key_join():
    def q(spark):
        a = gen_df(spark, [("k1", IntegerGen(lo=0, hi=8)),
                           ("k2", IntegerGen(lo=0, hi=8)),
                           ("va", LongGen())], length=256, seed=5)
        b = gen_df(spark, [("j1", IntegerGen(lo=0, hi=8)),
                           ("j2", IntegerGen(lo=0, hi=8)),
                           ("vb", LongGen())], length=128, seed=6)
        return a.join(b, on=(col("k1") == col("j1")) &
                      (col("k2") == col("j2")), how="inner")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_conditional_inner_join():
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=20), 128)
        return a.join(b, on=(col("k") == col("k2")) &
                      (col("va") > col("vb")), how="inner")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_cross_join():
    def q(spark):
        a = gen_df(spark, [("x", IntegerGen())], length=30, seed=7)
        b = gen_df(spark, [("y", IntegerGen())], length=20, seed=8)
        return a.join(b, how="cross")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_join_then_aggregate():
    """Join feeding aggregation (the TPC-DS bread-and-butter shape)."""
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=30), 512)
        return (a.join(b, on=(col("k") == col("k2")), how="inner")
                 .group_by(col("k"))
                 .agg(F.sum(col("va")).alias("sa"),
                      F.count("*").alias("c")))
    assert_tpu_and_cpu_are_equal_collect(q)


# ---------------------------------------------------------------------------
# Broadcast joins (ref GpuBroadcastHashJoinExec / GpuBroadcastNestedLoopJoin)
# ---------------------------------------------------------------------------

def _plan_exec_names(df_fn, conf=None):
    from spark_rapids_tpu.testing.asserts import _TPU_CONF, _mk
    c = dict(conf or {})
    c.update(_TPU_CONF)
    session = _mk(c)
    df_fn(session).collect()
    names = []
    session.last_plan.foreach(lambda e: names.append(type(e).__name__))
    return names


@pytest.mark.parametrize("how", ["inner", "left", "right", "left_semi",
                                 "left_anti"])
def test_broadcast_hash_join(how):
    """Small build side over a partitioned probe side must broadcast."""
    def q(spark):
        a = gen_df(spark, [("k", IntegerGen(lo=0, hi=40)),
                           ("va", LongGen())],
                   length=512, seed=30, num_partitions=4)
        b = gen_df(spark, [("k2", IntegerGen(lo=0, hi=40)),
                           ("vb", LongGen())], length=64, seed=31)
        return a.join(b, on=(col("k") == col("k2")), how=how)
    assert_tpu_and_cpu_are_equal_collect(q)
    names = _plan_exec_names(q)
    assert "BroadcastHashJoinExec" in names, names
    assert "BroadcastExchangeExec" in names, names
    assert "ShuffleExchangeExec" not in names, names


def test_broadcast_disabled_by_threshold():
    """threshold=-1 must fall back to shuffled hash join."""
    conf = {"spark.rapids.sql.autoBroadcastJoinThreshold": "-1"}
    def q(spark):
        a = gen_df(spark, [("k", IntegerGen(lo=0, hi=40)),
                           ("va", LongGen())],
                   length=512, seed=32, num_partitions=4)
        b = gen_df(spark, [("k2", IntegerGen(lo=0, hi=40)),
                           ("vb", LongGen())], length=64, seed=33)
        return a.join(b, on=(col("k") == col("k2")), how="inner")
    assert_tpu_and_cpu_are_equal_collect(q, conf=conf)
    names = _plan_exec_names(q, conf)
    assert "BroadcastHashJoinExec" not in names, names
    assert "ShuffleExchangeExec" in names, names


def test_broadcast_nested_loop_join():
    def q(spark):
        a = gen_df(spark, [("x", IntegerGen(lo=0, hi=100))],
                   length=64, seed=34, num_partitions=3)
        b = gen_df(spark, [("y", IntegerGen(lo=0, hi=100))],
                   length=16, seed=35)
        return a.join(b, on=(col("x") > col("y")), how="inner")
    assert_tpu_and_cpu_are_equal_collect(q)
    names = _plan_exec_names(q)
    assert "BroadcastNestedLoopJoinExec" in names, names


def test_inner_join_build_side_flip():
    """Inner join with the smaller side on the left should flip it to the
    build side and still produce left-first column order."""
    def q(spark):
        small = gen_df(spark, [("k", IntegerGen(lo=0, hi=10)),
                               ("vs", LongGen())], length=32, seed=36)
        big = gen_df(spark, [("k2", IntegerGen(lo=0, hi=10)),
                             ("vb", LongGen())],
                     length=512, seed=37, num_partitions=2)
        return small.join(big, on=(col("k") == col("k2")), how="inner")
    cpu, tpu = assert_tpu_and_cpu_are_equal_collect(q)
    assert cpu.schema.names == ["k", "vs", "k2", "vb"]


def test_full_join_never_broadcast():
    def q(spark):
        a = gen_df(spark, [("k", IntegerGen(lo=0, hi=20)),
                           ("va", LongGen())],
                   length=256, seed=38, num_partitions=3)
        b = gen_df(spark, [("k2", IntegerGen(lo=0, hi=20)),
                           ("vb", LongGen())], length=32, seed=39)
        return a.join(b, on=(col("k") == col("k2")), how="full")
    assert_tpu_and_cpu_are_equal_collect(q)
    names = _plan_exec_names(q)
    assert "BroadcastHashJoinExec" not in names, names


def test_conditional_left_join():
    """LEFT join with a residual condition: pairs failing the condition
    drop, probe rows with no passing pair emit once with the build side
    nulled (expand+repair kernel; ref GpuOverrides.scala:3352-3355)."""
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=20), 128)
        return a.join(b, on=(col("k") == col("k2")) &
                      (col("va") > col("vb")), how="left")
    assert_tpu_and_cpu_are_equal_collect(q)


def test_conditional_right_join_flips_to_left():
    def q(spark):
        a, b = _sides(spark, IntegerGen(lo=0, hi=12), 96)
        return a.join(b, on=(col("k") == col("k2")) &
                      (col("va") < col("vb")), how="right")
    assert_tpu_and_cpu_are_equal_collect(q)


# ---------------------------------------------------------------------------
# Equality by hash (ops/join_kernels.py): exact for ONE integer-typed key
# ---------------------------------------------------------------------------

def _int_keys(np_dtype):
    import numpy as np
    info = np.iinfo(np_dtype)
    rng = np.random.default_rng(7)
    # the extremes, the keys around zero and the word boundaries, dbgen's
    # sparse order keys (the first 8 of every 32) and their neighbours, a
    # random lot
    i = np.arange(0, 60_000, dtype=np.int64)
    sparse = (i >> 3 << 5) + (i & 7) + 1
    lot = [np.array([info.min, info.min + 1, -1, 0, 1, info.max - 1,
                     info.max], np.int64),
           sparse, sparse + 8, sparse - 1, sparse << 7,
           rng.integers(info.min, info.max, 60_000, dtype=np.int64,
                        endpoint=True)]
    if info.bits > 32:
        edges = np.array([2**31, 2**32, 2**33, 2**53, 2**62], np.int64)
        lot += [edges, edges - 1, -edges, edges + 1]
    keys = np.unique(np.clip(np.concatenate(lot), info.min, info.max))
    return keys.astype(np_dtype)


@pytest.mark.parametrize("np_dtype,sql_type", [
    ("int64", "LONG"), ("int32", "INT"), ("int32", "DATE"),
    ("int16", "SHORT"), ("int8", "BYTE")])
def test_one_integer_key_hashes_are_distinct(np_dtype, sql_type):
    """The combined hash of ONE integer-typed key is a composition of
    bijections of the 64-bit value: distinct keys give distinct hashes,
    so equality by hash is exact there."""
    import numpy as np
    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.columnar.device import DeviceColumn
    from spark_rapids_tpu.ops import join_kernels as jk
    keys = _int_keys(np.dtype(np_dtype))
    column = DeviceColumn(getattr(t, sql_type), data=keys,
                          validity=np.ones(len(keys), bool))
    hashes, any_null = jk.combined_key_hash(np, [column], len(keys))
    assert hashes.dtype == np.uint64 and not any_null.any()
    assert len(np.unique(hashes)) == len(keys)
    # and the mixer itself is a bijection: its inverse finds the key
    # whose mix is any given word (here: all ones, the old parking value)
    def unmix(h):
        mask = (1 << 64) - 1
        h ^= h >> 31 ^ h >> 62
        h = h * pow(int(jk._MIX2), -1, 1 << 64) & mask
        h ^= h >> 27 ^ h >> 54
        h = h * pow(int(jk._MIX), -1, 1 << 64) & mask
        return h ^ h >> 30 ^ h >> 60
    for word in ((1 << 64) - 1, 0, 0x9E3779B97F4A7C15):
        back = int(jk._mix64(np, np.array([unmix(word)], np.uint64))[0])
        assert back == word


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_no_hash_value_means_dead_or_null(xp_name):
    """A live key whose hash is all ones (where dead build rows used to be
    parked) matches its live twins and nothing else: rows that must not
    match are told apart by a flag beside the hash, not by its value."""
    import numpy as np
    from spark_rapids_tpu.ops import join_kernels as jk
    if xp_name == "jax":
        import jax.numpy as xp
    else:
        xp = np
    ones = np.uint64(0xFFFFFFFFFFFFFFFF)
    #          dead   live  live  dead  live  dead
    build_h = np.array([ones, ones, 5, 5, ones, 7], np.uint64)
    build_live = np.array([0, 1, 1, 0, 1, 0], bool)
    probe_h = np.array([ones, 5, 7, ones, 9], np.uint64)
    probe_live = np.array([1, 1, 1, 0, 1], bool)
    order, lo, counts = jk.count_matches(
        xp, xp.asarray(build_h), xp.asarray(build_live),
        xp.asarray(probe_h), xp.asarray(probe_live))
    order, lo, counts = (np.asarray(a) for a in (order, lo, counts))
    assert counts.tolist() == [2, 1, 0, 0, 0]
    assert sorted(order[lo[0]:lo[0] + 2].tolist()) == [1, 4]
    assert order[lo[1]] == 2
    # the live build rows are the order's prefix, in hash order
    assert sorted(order[:3].tolist()) == [1, 2, 4] and order[0] == 2


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_null_keys_match_nothing_whatever_their_hash(how):
    """Null keys on both sides, with the value lane under the nulls equal
    to live keys of the other side."""
    import numpy as np
    import pyarrow as pa

    def q(spark):
        k = pa.array([1, 2, None, 2, None, 5], pa.int64())
        k2 = pa.array([None, 2, 5, None, 1], pa.int64())
        a = spark.create_dataframe(pa.table(
            {"k": k, "va": pa.array(np.arange(6, dtype=np.int64))}))
        b = spark.create_dataframe(pa.table(
            {"k2": k2, "vb": pa.array(np.arange(5, dtype=np.int64))}))
        return a.join(b, on=(col("k") == col("k2")), how=how)
    assert_tpu_and_cpu_are_equal_collect(q)


# ---------------------------------------------------------------------------
# A join's output capacity follows the data: the sizing fetch, its spans
# and counters, and no program built for a warm join
# ---------------------------------------------------------------------------

def _counter(name, **labels):
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == name:
            return family.value(**labels) if labels else family.total()
    return 0


def _fact_and_dim(spark, payload, value="v"):
    import numpy as np
    import pyarrow as pa
    n = 6000
    fact = {"k": pa.array(np.arange(n, dtype=np.int64) % 500),
            value: pa.array(np.arange(n, dtype=np.int64))}
    dim = {"k2": pa.array(np.arange(400, dtype=np.int64)),
           "w": pa.array(np.arange(400, dtype=np.int64) * 3)}
    if payload == "string":
        dim["name"] = pa.array([f"dim-{i}" for i in range(400)])
    return (spark.create_dataframe(pa.table(fact)),
            spark.create_dataframe(pa.table(dim)))


def _rows(table):
    return sorted(map(tuple, zip(*(table.column(n).to_pylist()
                                   for n in table.column_names))),
                  key=repr)


def _sessions():
    from spark_rapids_tpu.api.session import TpuSession
    return [TpuSession.builder().config(
        "spark.rapids.sql.enabled", on).get_or_create()
        for on in (True, False)]


@pytest.mark.parametrize("how,payload", [
    ("inner", "flat"), ("left", "flat"), ("inner", "string"),
    ("full", "flat")])
def test_a_warm_join_builds_no_program(how, payload):
    """The same joined query at two parameter sets that leave the output
    in the same buckets: the second call runs the first's programs, asks
    for its sizes once like the first, and answers exactly."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    tpu, cpu = _sessions()

    def q(frames, cut):
        fact, dim = frames
        return fact.filter(col("v") < lit(cut)).join(
            dim, on=(col("k") == col("k2")), how=how).collect()
    frames = _fact_and_dim(tpu, payload)
    fetches0 = _counter("tpu_join_sizing_fetches_total")
    first = q(frames, 5000)
    assert _counter("tpu_join_sizing_fetches_total") == fetches0 + 1
    builds = CompileObservatory.get().snapshot()["builds"]
    sized0 = _counter("tpu_join_probe_batches_total", path="two_phase")
    second = q(frames, 4000)
    assert CompileObservatory.get().snapshot()["builds"] == builds
    assert _counter("tpu_join_sizing_fetches_total") == fetches0 + 2
    assert _counter("tpu_join_probe_batches_total",
                    path="two_phase") == sized0 + 1
    assert _rows(first) == _rows(q(_fact_and_dim(cpu, payload), 5000))
    assert _rows(second) == _rows(q(_fact_and_dim(cpu, payload), 4000))


def test_an_output_past_its_bucket_builds_one_expansion_and_never_retries():
    """A parameter set that moves the join's output to a larger bucket:
    the sizes say so before the expansion runs, so the query runs once
    (no guard, no re-execution) and builds the one program keyed by the
    new bucket; the count program is the first call's."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    tpu, _ = _sessions()
    # (compiled programs are shared process-wide by schema and key: a
    # column name of its own gives this test programs nobody built yet)
    fact, dim = _fact_and_dim(tpu, "flat", value="counted_here")

    def q(cut):
        return fact.filter(col("counted_here") < lit(cut)).join(
            dim, on=(col("k") == col("k2")), how="inner").collect()

    def join_programs():
        return sorted(p["key"] for p in
                      CompileObservatory.get().snapshot()["programs"]
                      if p["exec"] == "HashJoinExec")
    few = q(100)                 # 100 rows or fewer: the 1,024 bucket
    before = join_programs()
    retried0 = _counter("tpu_queries_retried_total")
    many = q(6000)               # 4,800 rows: past it
    assert _counter("tpu_queries_retried_total") == retried0
    after = join_programs()
    assert len(after) == len(before) + 1
    assert few.num_rows == 100 and many.num_rows == 4800
    assert sorted(many.column("counted_here").to_pylist()) == [
        v for v in range(6000) if v % 500 < 400]
    assert q(5390).num_rows == 4390
    assert join_programs() == after


def test_probe_batches_of_one_capacity_size_their_outputs_apart():
    """Two probe batches of one join at the same capacities whose outputs
    fall either side of a bucket boundary: each is sized by its own
    count, none by the other's, and nothing re-executes."""
    import numpy as np
    import pyarrow as pa
    tpu, cpu = _sessions()
    n = 4096                     # two partitions of 2,048 rows
    dup = np.where(np.arange(n) < n // 2, 0, 1)

    def q(spark):
        fact = spark.create_dataframe(pa.table({
            # the first partition's keys match one build row each, the
            # second's three: 2,048 rows out against 6,144
            "k": pa.array(dup.astype(np.int64)),
            "v": pa.array(np.arange(n, dtype=np.int64))}),
            num_partitions=2)
        dim = spark.create_dataframe(pa.table({
            "k2": pa.array(np.array([0, 1, 1, 1], np.int64)),
            "w": pa.array(np.arange(4, dtype=np.int64))}))
        return fact.join(dim, on=(col("k") == col("k2")),
                         how="inner").collect()
    retried0 = _counter("tpu_queries_retried_total")
    fetches0 = _counter("tpu_join_sizing_fetches_total")
    for _ in range(2):
        got = q(tpu)
        assert got.num_rows == n // 2 + 3 * (n // 2)
    assert _counter("tpu_queries_retried_total") == retried0
    assert _counter("tpu_join_sizing_fetches_total") >= fetches0 + 4
    assert _rows(got) == _rows(q(cpu))


def test_join_spans_say_how_the_output_was_sized():
    import pyarrow as pa
    import numpy as np
    from spark_rapids_tpu.api.session import TpuSession
    s = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.trace.enabled", True).get_or_create())
    fact = s.create_dataframe(pa.table(
        {"k": pa.array(np.arange(3000, dtype=np.int64) % 70),
         "x": pa.array(np.arange(3000, dtype=np.float64))}))
    dim = s.create_dataframe(pa.table(
        {"k2": pa.array(np.arange(64, dtype=np.int64)),
         "y": pa.array(np.arange(64, dtype=np.float64))}))

    def spans(frame):
        frame.collect()
        tr = s.last_query_trace()
        return {name: [sp for sp in tr.spans if sp.name == name]
                for name in ("join.build", "join.size", "join.probe")}
    for _ in range(2):           # a warm join reads the same
        got = spans(fact.join(dim, on=(col("k") == col("k2")),
                              how="inner"))
        assert got["join.build"][0].attrs["rows"] == 64
        assert got["join.build"][0].attrs["capacity"] == 1024
        size, = got["join.size"]
        assert size.attrs["out_capacity"] == 8192
        assert 2700 < size.attrs["total"] < 3000
        probe, = got["join.probe"]
        assert probe.attrs == {**probe.attrs, "how": "inner",
                               "path": "two_phase", "probe_capacity": 8192,
                               "build_capacity": 1024, "out_capacity": 8192}
        assert size.parent_id == probe.span_id
    semi = spans(fact.join(dim, on=(col("k") == col("k2")),
                           how="left_semi"))
    assert not semi["join.size"]
    assert semi["join.probe"][0].attrs["path"] == "count"
    # a semi join sizes nothing: its output lies at the probe's capacity
    # (PR 37 says so; the kept rows' count stays on the device)
    assert semi["join.probe"][0].attrs["out_capacity"] == 8192
    assert semi["join.probe"][0].attrs["sorted_slots"] == 8192 + 1024


def test_join_programs_count_the_columns_they_gather():
    """`gather_column` goes to ops/gather.py, which `lane_move_counts`
    does not see: the expansion counts its columns at build."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    tpu = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    fact, dim = _fact_and_dim(tpu, "string")
    fact.join(dim, on=(col("k") == col("k2")), how="left").collect()
    programs = [p for p in CompileObservatory.get().snapshot()["programs"]
                if p["exec"] == "HashJoinExec"]
    assert {p["join_cols_gathered"] for p in programs} >= {0, 5}
