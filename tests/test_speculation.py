"""Speculative join sizing (spark.rapids.tpu.join.speculativeSizing).

The join's count+expand fuse into one program at a guessed output
capacity; a deferred guard rides the result fetch and a miss re-executes
with exact sizing — results must be identical either way, and the
engine must never surface truncated output."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.testing.asserts import assert_tables_equal


def _session(spec: bool):
    return (TpuSession.builder()
            .config("spark.rapids.sql.enabled", True)
            .config("spark.rapids.tpu.join.speculativeSizing", spec)
            .get_or_create())


def _sorted(t: pa.Table) -> pa.Table:
    return t.sort_by([(n, "ascending") for n in t.schema.names])


def test_speculation_hit_fk_pk_join():
    """Unique build keys: output rows == probe rows, the guess holds."""
    rng = np.random.default_rng(31)
    n = 5000
    probe = pa.table({
        "k": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        "v": pa.array(rng.integers(-50, 50, n).astype(np.int64))})
    build = pa.table({
        "k": pa.array(np.arange(100, dtype=np.int64)),
        "w": pa.array(np.arange(100, dtype=np.int64) * 7)})
    outs = []
    for spec in (True, False):
        s = _session(spec)
        outs.append(_sorted(
            s.create_dataframe(probe)
            .join(s.create_dataframe(build), on="k").collect()))
    assert outs[0].equals(outs[1])
    assert outs[0].num_rows == n


def test_speculation_miss_reexecutes_exactly():
    """64x expansion blows past the probe-capacity guess; the deferred
    guard must trip and the re-execution must produce the exact rows."""
    n, dup = 5000, 64
    probe = pa.table({
        "k": pa.array((np.arange(n, dtype=np.int64) % 50)),
        "v": pa.array(np.arange(n, dtype=np.int64))})
    build = pa.table({
        "k": pa.array(np.repeat(np.arange(50, dtype=np.int64), dup)),
        "w": pa.array(np.arange(50 * dup, dtype=np.int64))})
    s = _session(True)
    got = (s.create_dataframe(probe)
           .join(s.create_dataframe(build), on="k").collect())
    c = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    False).get_or_create()
    want = (c.create_dataframe(probe)
            .join(c.create_dataframe(build), on="k").collect())
    assert got.num_rows == n * dup == want.num_rows
    assert _sorted(got).equals(_sorted(want))


def test_speculative_left_join_null_extension():
    rng = np.random.default_rng(33)
    probe = pa.table({
        "k": pa.array(np.arange(200, dtype=np.int64)),
        "v": pa.array(rng.integers(0, 9, 200).astype(np.int64))})
    build = pa.table({
        "k": pa.array(np.arange(0, 100, dtype=np.int64)),
        "w": pa.array(np.arange(100, dtype=np.int64))})
    outs = []
    for spec in (True, False):
        s = _session(spec)
        outs.append(_sorted(
            s.create_dataframe(probe)
            .join(s.create_dataframe(build), on="k", how="left")
            .collect()))
    assert outs[0].equals(outs[1])
    assert outs[0].num_rows == 200


def test_string_payloads_bypass_speculation():
    """Span schemas need char-cap guesses the spec program doesn't carry
    — they must take the exact-sizing path and still be correct."""
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


def test_speculation_miss_does_not_poison_df_cache():
    """A cache() materialization streamed during a mispredicted run must
    be discarded before re-execution — a truncated blob surviving into
    CachedScanExec would silently corrupt every later query."""
    n, dup = 4000, 64
    probe = pa.table({
        "k": pa.array((np.arange(n, dtype=np.int64) % 50)),
        "v": pa.array(np.arange(n, dtype=np.int64))})
    build = pa.table({
        "k": pa.array(np.repeat(np.arange(50, dtype=np.int64), dup)),
        "w": pa.array(np.arange(50 * dup, dtype=np.int64))})
    s = _session(True)
    df = (s.create_dataframe(probe)
          .join(s.create_dataframe(build), on="k").cache())
    first = df.collect()           # miss -> re-execute -> cache rebuilt
    assert first.num_rows == n * dup
    second = df.collect()          # served from the cache
    assert second.num_rows == n * dup
    assert _sorted(first).equals(_sorted(second))
