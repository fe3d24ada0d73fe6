"""A join's output is sized from the count program's sizes (exec/join.py):
whatever the expansion (none, 64x, a left join's null extension, string
payloads), the engine answers the CPU engine's rows and never surfaces a
truncated output."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.testing.asserts import assert_tables_equal


def _session(tpu: bool):
    return (TpuSession.builder()
            .config("spark.rapids.sql.enabled", tpu)
            .get_or_create())


def _sorted(t: pa.Table) -> pa.Table:
    return t.sort_by([(n, "ascending") for n in t.schema.names])


def test_fk_pk_join_keeps_every_probe_row():
    """Unique build keys: output rows == probe rows."""
    rng = np.random.default_rng(31)
    n = 5000
    probe = pa.table({
        "k": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        "v": pa.array(rng.integers(-50, 50, n).astype(np.int64))})
    build = pa.table({
        "k": pa.array(np.arange(100, dtype=np.int64)),
        "w": pa.array(np.arange(100, dtype=np.int64) * 7)})
    outs = []
    for tpu in (True, False):
        s = _session(tpu)
        outs.append(_sorted(
            s.create_dataframe(probe)
            .join(s.create_dataframe(build), on="k").collect()))
    assert outs[0].equals(outs[1])
    assert outs[0].num_rows == n


def test_expanding_join_is_exact_without_a_retry():
    """64x expansion blows past the probe's capacity: the output's bucket
    follows the count's sizes, so the first execution is the exact one."""
    n, dup = 5000, 64
    probe = pa.table({
        "k": pa.array((np.arange(n, dtype=np.int64) % 50)),
        "v": pa.array(np.arange(n, dtype=np.int64))})
    build = pa.table({
        "k": pa.array(np.repeat(np.arange(50, dtype=np.int64), dup)),
        "w": pa.array(np.arange(50 * dup, dtype=np.int64))})
    s = _session(True)
    retried0 = _retried()
    got = (s.create_dataframe(probe)
           .join(s.create_dataframe(build), on="k").collect())
    assert _retried() == retried0
    c = _session(False)
    want = (c.create_dataframe(probe)
            .join(c.create_dataframe(build), on="k").collect())
    assert got.num_rows == n * dup == want.num_rows
    assert _sorted(got).equals(_sorted(want))


def test_left_join_null_extension():
    rng = np.random.default_rng(33)
    probe = pa.table({
        "k": pa.array(np.arange(200, dtype=np.int64)),
        "v": pa.array(rng.integers(0, 9, 200).astype(np.int64))})
    build = pa.table({
        "k": pa.array(np.arange(0, 100, dtype=np.int64)),
        "w": pa.array(np.arange(100, dtype=np.int64))})
    outs = []
    for tpu in (True, False):
        s = _session(tpu)
        outs.append(_sorted(
            s.create_dataframe(probe)
            .join(s.create_dataframe(build), on="k", how="left")
            .collect()))
    assert outs[0].equals(outs[1])
    assert outs[0].num_rows == 200


def test_string_payloads_are_sized_by_their_bytes():
    """Span columns size their output by bytes as well as rows (the
    count's sizes carry both)."""
    probe = pa.table({
        "k": pa.array(np.arange(300, dtype=np.int64) % 40),
        "s": pa.array([f"row-{i}" for i in range(300)])})
    build = pa.table({
        "k": pa.array(np.arange(40, dtype=np.int64)),
        "t": pa.array([f"dim-{i}" for i in range(40)])})
    s = _session(True)
    got = _sorted(s.create_dataframe(probe)
                  .join(s.create_dataframe(build), on="k").collect())
    c = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    False).get_or_create()
    want = _sorted(c.create_dataframe(probe)
                   .join(c.create_dataframe(build), on="k").collect())
    assert got.equals(want)


@pytest.mark.parametrize("host_assisted", [False, True])
def test_three_key_sort_with_a_string_matches_the_cpu_engine(host_assisted):
    """The sort's passes and its span gather against the CPU engine
    (stability and the string included), over two partitions.  Above the
    host-assist threshold the collect runs a nested query for the row
    ids alone: it sorts by the same path."""
    rng = np.random.default_rng(34)
    n = 70_000
    tb = pa.table({
        "k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
        "v": pa.array(rng.integers(-9, 9, n).astype(np.int64)),
        "s": pa.array([f"x{int(i) % 13}" for i in rng.integers(0, 99, n)]),
    })

    def q(s):
        return (s.create_dataframe(tb, num_partitions=2)
                .sort(col("k"), col("v").desc(), col("s")).collect())
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.sql.collect.hostAssisted", host_assisted)
         .get_or_create())
    got = q(s)
    # the assisted collect's last plan is the nested one: the row id alone
    assert (s.last_plan.output_names == ["__rid__"]) == host_assisted
    want = q(TpuSession.builder().config("spark.rapids.sql.enabled",
                                         False).get_or_create())
    # by value: the assisted path answers the host copy's `string`, the
    # engines `large_string`
    assert_tables_equal(want, got, ignore_order=False)


def _arm_filters(monkeypatch, cap: int):
    """Every FilterExec planned from here on guesses that its survivors
    fit `cap` slots (what the TPU-L018 repair arms, here by hand)."""
    from spark_rapids_tpu.exec import basic
    init = basic.FilterExec.__init__

    def armed(self, condition, child):
        init(self, condition, child)
        self.rebucket_cap = cap
    monkeypatch.setattr(basic.FilterExec, "__init__", armed)


def _retried() -> int:
    from spark_rapids_tpu.obs import metrics
    return metrics.counter("tpu_queries_retried_total",
                           "speculation-miss exact re-executions").value()


def test_missed_capacity_guess_reexecutes_exactly(monkeypatch):
    """A filter's re-bucket guess that undershoots: the deferred guard
    rides the result fetch, trips, and the re-execution without
    speculation gives the exact rows."""
    _arm_filters(monkeypatch, 1024)
    n = 5000
    tb = pa.table({"v": pa.array(np.arange(n, dtype=np.int64))})
    s = _session(True)
    retried0 = _retried()
    few = s.create_dataframe(tb).filter(col("v") < 100).collect()
    assert _retried() == retried0          # 100 survivors fit the guess
    many = s.create_dataframe(tb).filter(col("v") >= 1000).collect()
    assert _retried() == retried0 + 1      # 4,000 do not
    assert few.column("v").to_pylist() == list(range(100))
    assert sorted(many.column("v").to_pylist()) == list(range(1000, n))


def test_missed_capacity_guess_does_not_poison_df_cache(monkeypatch):
    """A cache() materialization streamed during a mispredicted run must
    be discarded before re-execution — a truncated blob surviving into
    CachedScanExec would silently corrupt every later query."""
    _arm_filters(monkeypatch, 1024)
    n = 4000
    tb = pa.table({"v": pa.array(np.arange(n, dtype=np.int64))})
    s = _session(True)
    retried0 = _retried()
    df = s.create_dataframe(tb).filter(col("v") >= 500).cache()
    first = df.collect()           # miss -> re-execute -> cache rebuilt
    assert _retried() == retried0 + 1
    assert first.num_rows == n - 500
    second = df.collect()          # served from the cache
    assert second.num_rows == n - 500
    assert _sorted(first).equals(_sorted(second))
