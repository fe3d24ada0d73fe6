"""A join with a few rows on one side compares, it does not sort: where one
side has at most `SMALL_SIDE_SLOTS` slots and the other at least
`NARROW_SHARE` times as many (`exec/join._compared_side`), the count holds
the large side's key hashes against the small side's live ones
(`join_kernels.match_flags`).  A semi or an anti join's small build: the
flags over the probe are the count.  An inner or a left join's small
probe: the flags pick the build rows it matches, which are gathered and
sorted with the probe (`HashJoinExec._flag_build`, `_count_gathered`).

The referees: the same plan with the rule switched off in the test (the
sort arm: the same batch, row for row and in order) and `CpuJoinExec`
over the NumPy engine (the pyarrow join)."""

import os
import sys

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spark_rapids_tpu.api.column import col, lit  # noqa: E402
from spark_rapids_tpu.columnar.interop import to_arrow_schema  # noqa: E402
from spark_rapids_tpu.exec import join as join_mod  # noqa: E402
from spark_rapids_tpu.exec.base import (CPU, TPU, ExecContext,  # noqa: E402
                                        to_host_batch)
from spark_rapids_tpu.exec.basic import FilterExec, LocalScanExec  # noqa: E402
from spark_rapids_tpu.exec.join import (NARROW_SHARE,  # noqa: E402
                                        SMALL_SIDE_SLOTS, CpuJoinExec,
                                        HashJoinExec, _compared_side)
from spark_rapids_tpu.obs import metrics, tracer  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402
from spark_rapids_tpu.testing.asserts import assert_tables_equal  # noqa: E402

#: the large side's slots: 65,536 >= 32 x the small side's 1,024
LARGE_CAP = 65536
KEYS = {"int": (["lk"], ["sk"]), "pair": (["lk", "lk2"], ["sk", "sk2"]),
        "string": (["ls"], ["ss"])}


def _tables(seed=42, n_small=60, fan_out=None):
    """(large, small).  The large side is orders and their lines: 9,000
    sparse order keys, 1-7 rows each (about 36,000 rows in 65,536 slots),
    a second key column that follows the first, the key as a string, a
    string payload of 3-20 bytes, some null keys.  The small side holds
    `n_small` rows: keys the large side holds (one of them twice), keys
    it does not, null keys, a second key column wrong in a few rows.
    `fan_out`: the large side's keys are 0..fan_out-1 instead, so that a
    few small rows match thousands of large ones."""
    rng = np.random.default_rng(seed)
    orders = np.arange(9000, dtype=np.int64) * 4 + 7
    lk = np.repeat(orders, rng.integers(1, 8, orders.size))
    if fan_out:
        lk = rng.integers(0, fan_out, lk.size).astype(np.int64)
        orders = np.arange(fan_out, dtype=np.int64)
    n = lk.size
    lnull = rng.random(n) < 0.03
    large = pa.table({
        "lk": pa.array(lk, mask=lnull),
        "lk2": pa.array((lk % 7).astype(np.int32)),
        "ls": pa.array([None if z else f"o{k}" for k, z in zip(lk, lnull)],
                       pa.string()),
        "lv": pa.array(np.arange(n, dtype=np.int64)),
        "lname": pa.array(["n" * (3 + i % 18) + str(i % 97)
                           for i in range(n)], pa.string()),
        "lq": pa.array(rng.integers(-100, 100, n).astype(np.int64))})
    sk = rng.choice(orders, n_small).astype(np.int64)
    sk[1] = sk[0]                                  # a key twice
    sk[2:7] = -5 - np.arange(5)                    # keys nobody holds
    snull = np.zeros(n_small, bool)
    snull[7:10] = True                             # null keys
    sk2 = (sk % 7).astype(np.int32)
    sk2[10:13] += 1                                # pair key that misses
    small = pa.table({
        "sk": pa.array(sk, mask=snull),
        "sk2": pa.array(sk2),
        "ss": pa.array([None if z else f"o{k}" for k, z in zip(sk, snull)],
                       pa.string()),
        "sv": pa.array(np.arange(n_small, dtype=np.int64)),
        "sname": pa.array([f"c{i}" * (1 + i % 4) for i in range(n_small)],
                          pa.string()),
        "sq": pa.array(rng.integers(-100, 100, n_small).astype(np.int64))})
    return large, small


def _plan(tables, how, key="int", mask="none", engine=TPU,
          join_cls=HashJoinExec, small_probe=None):
    """The small side is the probe under an expanding join and the build
    under a selecting one, unless `small_probe` says otherwise; `mask`:
    a filter directly under the large side, the small side or both,
    which hands up its flags."""
    large_t, small_t = tables
    large, small = LocalScanExec(large_t, 1), LocalScanExec(small_t, 1)
    if mask in ("large", "both"):
        large = FilterExec((col("lq") > lit(-60)).expr, large)
    if mask in ("small", "both"):
        small = FilterExec((col("sq") > lit(-80)).expr, small)
    lkeys, skeys = KEYS[key]
    if small_probe is None:
        small_probe = how in ("inner", "left", "right", "full")
    probe, build, pk, bk = (small, large, skeys, lkeys) if small_probe \
        else (large, small, lkeys, skeys)
    join = join_cls([col(k).expr for k in pk], [col(k).expr for k in bk],
                    how, None, probe, build)
    join.foreach(lambda e: setattr(e, "placement", engine))
    return join


def _collect(plan):
    """(the answer, the join's spans by name)."""
    trace = tracer.install(tracer.QueryTrace())
    try:
        ctx = ExecContext()
        batches = [to_host_batch(b, plan.output_names)
                   for pid in range(plan.num_partitions)
                   for b in plan.execute_partition(pid, ctx)]
    finally:
        tracer.uninstall()
    spans = {}
    for sp in trace.span_dicts():
        if sp["name"].startswith("join."):
            spans.setdefault(sp["name"], []).append(sp["attrs"])
    return pa.Table.from_batches(
        batches, to_arrow_schema(plan.output_names, plan.output_types)), \
        spans


def _counter(name, **labels):
    for family in metrics.registry().families():
        if family.name == name:
            return family.value(**labels) if labels else family.total()
    return 0


def _three_ways(monkeypatch, tables, how, **shape):
    """The answer, held to the sort arm's (same rows, same order) and to
    `CpuJoinExec`'s; returns it and the spans of both TPU runs."""
    got, spans = _collect(_plan(tables, how, **shape))
    with monkeypatch.context() as m:
        m.setattr(join_mod, "SMALL_SIDE_SLOTS", 0)
        sorted_, sorted_spans = _collect(_plan(tables, how, **shape))
    assert got.equals(sorted_), (got.num_rows, sorted_.num_rows)
    cpu, _ = _collect(_plan(tables, how, engine=CPU, join_cls=CpuJoinExec,
                            **shape))
    assert_tables_equal(cpu, got, ignore_order=True)
    return got, spans, sorted_spans


@pytest.mark.parametrize("mask", ["none", "both"])
@pytest.mark.parametrize("key", sorted(KEYS))
@pytest.mark.parametrize("how", ["inner", "left", "left_semi",
                                 "left_anti"])
def test_the_compare_gives_the_sort_arms_batch(monkeypatch, how, key, mask):
    """A small probe under an inner or a left join, a small build under a
    semi or an anti join: the count compares, and the answer is the sort
    arm's row for row, whatever the key, with null keys, dead slots, the
    filters' flags and keys held twice on either side."""
    side = "build" if how.startswith("left_") else "probe"
    before = _counter("tpu_join_compared_total", side=side)
    got, spans, sorted_spans = _three_ways(monkeypatch, _tables(), how,
                                           key=key, mask=mask)
    probe, = spans["join.probe"]
    assert probe["path"] == "compare"
    assert _counter("tpu_join_compared_total", side=side) == before + 1
    assert sorted_spans["join.probe"][0]["path"] in ("select", "two_phase")
    assert sorted_spans["join.probe"][0]["compared_slots"] == 0
    if side == "build":
        # the 60 small rows, fewer under the filter, lie in 1,024 slots:
        # one step of 128 comparands, and nothing sorted
        assert probe["compared_slots"] == LARGE_CAP * 128
        assert probe["sorted_slots"] == 0
        assert 0 < got.num_rows < 40000
    else:
        # the flagged build rows wait once more, and are sorted with the
        # probe in their own bucket
        flagged, sizes = spans["join.size"]
        assert flagged["out_capacity"] == 1024
        assert 0 < flagged["total"] <= 60 * 7        # 1-7 rows a key
        assert probe["compared_slots"] == LARGE_CAP * 1024
        assert probe["sorted_slots"] == 1024 + 1024
        assert sizes["total"] >= got.num_rows > 60


@pytest.mark.parametrize("how,small_probe", [
    ("inner", False), ("left_semi", True), ("left_anti", True),
    ("right", True), ("full", True)])
def test_other_pairings_keep_the_sort_arm(monkeypatch, how, small_probe):
    """A small build under an expanding join (its large probe's rows
    mostly stay), a small probe under a selecting one, and the right and
    full joins, which emit the unmatched build rows: all sort."""
    before = _counter("tpu_join_compared_total")
    tables = _tables()
    got, spans = _collect(_plan(tables, how, small_probe=small_probe))
    cpu, _ = _collect(_plan(tables, how, engine=CPU, join_cls=CpuJoinExec,
                            small_probe=small_probe))
    assert_tables_equal(cpu, got, ignore_order=True)
    probe, = spans["join.probe"]
    assert probe["path"] in ("select", "two_phase")
    assert probe["compared_slots"] == 0 and probe["sorted_slots"] > 0
    assert _counter("tpu_join_compared_total") == before


@pytest.mark.parametrize("how,probe_cap,build_cap,side", [
    # `tpch_q18_1chip`'s three joins
    ("left_semi", 8388608, 1024, "build"),
    ("inner", 1024, 1048576, "probe"),
    ("inner", 1024, 33554432, "probe"),
    # `tpch_q3_1chip`'s two: the smallest side is 1,048,576 slots
    ("inner", 8388608, 1048576, ""),
    ("inner", 33554432, 1048576, ""),
    # the edges of the rule
    ("left", SMALL_SIDE_SLOTS, NARROW_SHARE * SMALL_SIDE_SLOTS, "probe"),
    ("left", 2 * SMALL_SIDE_SLOTS, 1 << 30, ""),
    ("left_anti", NARROW_SHARE * 1024 - 1, 1024, ""),
    ("right", 1024, 33554432, ""),
    ("full", 1024, 33554432, ""),
    ("inner", 33554432, 1024, ""),
    ("left_semi", 1024, 33554432, ""),
])
def test_the_rule_reads_the_capacities(how, probe_cap, build_cap, side):
    assert _compared_side(how, probe_cap, build_cap) == side


def test_a_probe_whose_keys_fan_out_falls_back_to_the_sort(monkeypatch):
    """Six probe keys of 50 over 36,000 build rows flag about 4,000 of
    them: their bucket, 8,192, is over 1/32 of 65,536, so the join sorts
    the whole build as before, after one compare and one wait."""
    before = _counter("tpu_join_compared_total")
    tables = _tables(n_small=20, fan_out=50)
    got, spans, _ = _three_ways(monkeypatch, tables, "inner")
    flagged, sizes = spans["join.size"]
    assert flagged["out_capacity"] * NARROW_SHARE > LARGE_CAP
    probe, = spans["join.probe"]
    assert probe["path"] == "two_phase"
    assert probe["compared_slots"] == LARGE_CAP * 1024
    assert probe["sorted_slots"] == 1024 + LARGE_CAP
    assert _counter("tpu_join_compared_total") == before
    assert sizes["total"] == got.num_rows > 1000


def test_a_count_that_sorts_nothing_still_raises_the_sorted_slots():
    """A semi join that compares adds 0 to `tpu_join_sorted_slots_total`,
    and the family is there for the benchmark's reader to find."""
    tables = _tables(seed=7)
    _collect(_plan(tables, "left_semi"))
    before = _counter("tpu_join_sorted_slots_total")
    got, spans = _collect(_plan(tables, "left_semi"))
    assert spans["join.probe"][0]["path"] == "compare"
    assert "tpu_join_sorted_slots_total" in {
        f.name for f in metrics.registry().families()}
    assert _counter("tpu_join_sorted_slots_total") == before
    assert got.num_rows > 0


def test_live_counts_in_one_capacity_share_their_programs():
    """Thirty small rows or a hundred, in the same 1,024 slots: the
    programs are keyed by capacities and buckets, not by the live count
    (the compare's steps are a loop on the device), so the second query
    builds nothing."""
    obs = CompileObservatory.get()
    for how in ("inner", "left_semi"):
        _collect(_plan(_tables(seed=3, n_small=30), how))
        builds = obs.snapshot()["builds"]
        got, spans = _collect(_plan(_tables(seed=4, n_small=100), how))
        assert obs.snapshot()["builds"] == builds
        assert spans["join.probe"][0]["path"] == "compare"
