"""Cross-query program sharing under bucket-canonical tracing.

The jit key space is meant to collapse to (exec kind, dtype layout,
capacity bucket): two structurally distinct queries that differ only in
literal constants and land in the same capacity buckets must run the
second query on the FIRST query's programs — zero new compilations.
ParamLiteral (expr/params.py) hoists eligible literals out of the
traced closures into traced arguments, and the semantic jit key
excludes their values, so this is exactly what the seam should deliver.

The anti-vacuity twin proves the test has teeth: changing a column's
DTYPE (not a literal) must fork the key space and compile new
programs — if it didn't, the sharing assertion above would be
vacuously green for the wrong reason (e.g. a disabled observatory).
"""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.exec.base as eb
import spark_rapids_tpu.obs.metrics as obs_metrics
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.obs.compileprof import CompileObservatory


@pytest.fixture
def obs():
    obs_metrics.MetricsRegistry.reset_for_tests()
    o = CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    yield o
    eb.clear_jit_cache()
    CompileObservatory.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()


def _session() -> TpuSession:
    return (TpuSession.builder()
            .config("spark.rapids.sql.enabled", True)
            .config("spark.rapids.tpu.singleChipFuse", "off")
            .get_or_create())


def _table(n=2000):
    # v = 0..n-1: the filter survivor counts for `v > 5` (1994) and
    # `v > 9` (1990) land in the SAME capacity bucket (2048), so even
    # the survivor-repack transfer programs are shared — a different
    # bucket would be an honest, wanted recompile, not sharing failure
    return pa.table({
        "k": pa.array((np.arange(n) % 7).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })


def _query(df, threshold: int, addend: int):
    return (df.filter(col("v") > threshold)
            .select(col("k"), (col("v") + addend).alias("x"))
            .collect())


def test_literal_twins_share_all_programs(obs):
    s = _session()
    df = s.create_dataframe(_table())

    out1 = _query(df, 5, 7)
    snap1 = obs.snapshot()
    assert snap1["builds"] > 0  # the cold query really compiled

    out2 = _query(df, 9, 11)
    snap2 = obs.snapshot()

    assert snap2["builds"] == snap1["builds"], (
        f"literal-only twin compiled "
        f"{snap2['builds'] - snap1['builds']} new program(s): "
        f"{snap2['by_cause']}")
    assert snap2["hits"] > snap1["hits"]

    # sharing must not bend correctness: both results are exact
    v = np.arange(2000, dtype=np.int64)
    np.testing.assert_array_equal(
        np.sort(out1.column("x").to_numpy()), np.sort(v[v > 5] + 7))
    np.testing.assert_array_equal(
        np.sort(out2.column("x").to_numpy()), np.sort(v[v > 9] + 11))


def test_dtype_change_must_compile(obs):
    s = _session()
    df = s.create_dataframe(_table())
    _query(df, 5, 7)
    snap1 = obs.snapshot()

    # same query shape over float64 — a dtype-layout change is a
    # genuinely different program family and MUST compile
    n = 2000
    ftbl = pa.table({
        "k": pa.array((np.arange(n) % 7).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.float64)),
    })
    fdf = s.create_dataframe(ftbl)
    out = (fdf.filter(col("v") > 5.0)
           .select(col("k"), (col("v") + 7.0).alias("x"))
           .collect())
    snap2 = obs.snapshot()

    assert snap2["builds"] > snap1["builds"], (
        "dtype change compiled nothing — the sharing test is vacuous")
    v = np.arange(n, dtype=np.float64)
    np.testing.assert_allclose(
        np.sort(out.column("x").to_numpy()), np.sort(v[v > 5.0] + 7.0))


def test_in_list_twins_share_programs(obs):
    """IN-list items hoist like comparison literals: twins that differ
    only in the listed values (same list LENGTH) share every program."""
    s = _session()
    df = s.create_dataframe(_table())
    out1 = df.filter(col("v").isin(3, 700, 1500)).collect()
    snap1 = obs.snapshot()
    assert snap1["builds"] > 0
    out2 = df.filter(col("v").isin(8, 901, 1999)).collect()
    snap2 = obs.snapshot()
    assert snap2["builds"] == snap1["builds"], snap2["by_cause"]
    assert sorted(out1.column("v").to_pylist()) == [3, 700, 1500]
    assert sorted(out2.column("v").to_pylist()) == [8, 901, 1999]


def test_case_arm_twins_share_programs(obs):
    """Numeric CASE value arms hoist: twins differing only in the arm
    constants (and the compared literal) share every program."""
    from spark_rapids_tpu.api.functions import when
    s = _session()
    df = s.create_dataframe(_table())

    def q(cut, a, b):
        return df.select(
            when(col("v") > cut, a).otherwise(b).alias("c")).collect()

    out1 = q(1000, 7, 3)
    snap1 = obs.snapshot()
    assert snap1["builds"] > 0
    out2 = q(500, 90, 40)
    snap2 = obs.snapshot()
    assert snap2["builds"] == snap1["builds"], snap2["by_cause"]
    v = np.arange(2000, dtype=np.int64)
    np.testing.assert_array_equal(out1.column("c").to_numpy(),
                                  np.where(v > 1000, 7, 3))
    np.testing.assert_array_equal(out2.column("c").to_numpy(),
                                  np.where(v > 500, 90, 40))


def _stable():
    n = 512
    vals = ["red", "blu", "grn", "yel"]
    return pa.table({
        "s": pa.array([vals[i % 4] for i in range(n)]),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })


def test_string_literal_twins_share_programs(obs):
    """Same-BYTE-LENGTH string literal twins share programs: the chars
    ride in as a traced uint8 array, equality hashes on device."""
    s = _session()
    df = s.create_dataframe(_stable())
    out1 = df.filter(col("s") == "red").collect()
    snap1 = obs.snapshot()
    assert snap1["builds"] > 0
    out2 = df.filter(col("s") == "grn").collect()
    snap2 = obs.snapshot()
    assert snap2["builds"] == snap1["builds"], snap2["by_cause"]
    assert set(out1.column("s").to_pylist()) == {"red"}
    assert set(out2.column("s").to_pylist()) == {"grn"}
    assert out1.num_rows == out2.num_rows == 128


def test_string_lengths_of_one_bucket_share_programs(obs):
    """Under a comparison the chars ride padded to a length bucket with
    the byte length traced beside them: literals of 3, 7 and 16 bytes
    dispatch to one executable and each finds its own rows (a dashboard
    that walks a column's values builds nothing after the first)."""
    s = _session()
    vals = ["red", "reddish", "red\x00", "a-sixteen-byte-s"]
    df = s.create_dataframe(pa.table({
        "s": pa.array([vals[i % 4] for i in range(512)]),
        "v": pa.array(np.arange(512, dtype=np.int64))}))
    def filters_built():
        return sum(1 for p in obs.snapshot()["programs"]
                   if p["exec"] == "FilterExec")
    first = df.filter(col("s") == "red").collect()
    assert filters_built() == 1
    outs = {v: df.filter(col("s") == v).collect() for v in vals}
    none = df.filter(col("s") == "re").collect()
    below = df.filter(col("s") < "red").collect()
    below_long = df.filter(col("s") < "reddish").collect()
    # (the ordering comparison is a program of its own, built once; an
    # answer of another shape may build a fetch program)
    assert filters_built() == 2
    assert first.num_rows == 128 and none.num_rows == 0
    for v, out in outs.items():
        assert set(out.column("s").to_pylist()) == {v}, v
        assert out.num_rows == 128
    assert set(below.column("s").to_pylist()) == {"a-sixteen-byte-s"}
    assert set(below_long.column("s").to_pylist()) == {
        "a-sixteen-byte-s", "red", "red\x00"}


def test_string_length_change_must_compile(obs):
    """Anti-vacuity: a byte length of ANOTHER bucket is a different traced
    shape and must fork the key space (honest recompile)."""
    s = _session()
    df = s.create_dataframe(_stable())
    df.filter(col("s") == "red").collect()
    snap1 = obs.snapshot()
    out = df.filter(col("s") == "reddish-beyond-sixteen").collect()
    snap2 = obs.snapshot()
    assert snap2["builds"] > snap1["builds"]
    assert out.num_rows == 0


def test_shared_program_ratio_gauge(obs):
    """tpu_jit_shared_program_ratio drops as calls reuse programs."""
    s = _session()
    df = s.create_dataframe(_table())
    _query(df, 5, 7)
    _query(df, 9, 11)
    ratio = obs_metrics.registry().gauge(
        "tpu_jit_shared_program_ratio").value()
    assert 0.0 < ratio < 1.0
