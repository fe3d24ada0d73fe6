"""A `FilterExec` directly under either side of a TPU `HashJoinExec`, or
under a bare selection there, hands up its keep flags and moves no lane:
the join `&`s them into the liveness that rides its sorts
(`HashJoinExec.masked_sources`, `FilterExec.execute_masked`,
`ProjectExec.execute_masked`).  Under anything else the filter compacts.

The referees: the same plan with the join's pairing switched off (the
filters compact, as they did before), and `CpuJoinExec` over the NumPy
engine's filters."""

import os
import sys

import numpy as np
import pyarrow as pa
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from spark_rapids_tpu.api import functions as F  # noqa: E402
from spark_rapids_tpu.api.column import col, lit  # noqa: E402
from spark_rapids_tpu.api.session import TpuSession  # noqa: E402
from spark_rapids_tpu.columnar.interop import to_arrow_schema  # noqa: E402
from spark_rapids_tpu.exec.base import (CPU, TPU, ExecContext,  # noqa: E402
                                        to_host_batch)
from spark_rapids_tpu.exec.basic import (FilterExec,  # noqa: E402
                                         LocalScanExec, ProjectExec)
from spark_rapids_tpu.exec.filter_common import MaskedBatch  # noqa: E402
from spark_rapids_tpu.exec.join import (CpuJoinExec,  # noqa: E402
                                        HashJoinExec, NestedLoopJoinExec,
                                        ShuffledHashJoinExec)
from spark_rapids_tpu.exec.sort import SortExec  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402
from spark_rapids_tpu.testing.asserts import assert_tables_equal  # noqa: E402

HOWS = ["inner", "left", "right", "full", "left_semi", "left_anti"]
MASKS = ["probe", "build", "both"]


def _counter(path):
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == "tpu_filter_batches_total":
            return family.value(path=path)
    return 0


# -- the data -----------------------------------------------------------------

def _tables(data: str):
    """(probe, build): keys 0..11 with duplicates on both sides and nulls
    among them, keys that only one side has, a string carried on each
    side, and the predicates' columns `p` and `q`, which know nothing of
    the keys: a dropped row's key matches as often as a kept one's.
    `mixed` drops about half of each side, `drops_all` every row,
    `drops_none` none."""
    rng = np.random.default_rng(36)
    n_p, n_b = 300, 120
    shift = {"mixed": 0, "drops_all": -10**6, "drops_none": 10**6}[data]

    def keys(n, lo, hi):
        return pa.array(rng.integers(lo, hi, n).astype(np.int64),
                        mask=rng.random(n) < 0.1)

    def words(n, tag):
        return pa.array([None if i % 11 == 0 else f"{tag}{i % 17:0{i % 5}d}"
                         for i in range(n)], pa.string())
    probe = pa.table({
        "k": keys(n_p, 0, 12), "va": pa.array(np.arange(n_p, dtype=np.int64)),
        "sa": words(n_p, "a"),
        "p": pa.array(rng.integers(-100, 100, n_p).astype(np.int64) + shift)})
    build = pa.table({
        "k2": keys(n_b, 4, 16),
        "vb": pa.array(np.arange(n_b, dtype=np.int64) * 7),
        "sb": words(n_b, "b"),
        "q": pa.array(rng.integers(-100, 100, n_b).astype(np.int64) + shift)})
    return probe, build


# -- the plans, made by hand: which side is which is the test's to say --------

def _plan(tables, how, mask, engine=TPU, join_cls=HashJoinExec,
          condition=None, probe_rows=None, build_rows=None,
          build_partitions=1):
    """scan -> filter (-> bare selection, on the build) -> join, every
    operator on `engine`.  `mask` says which side has the filter."""
    probe_table, build_table = tables
    probe = LocalScanExec(probe_table, 1, probe_rows)
    build = LocalScanExec(build_table, build_partitions, build_rows)
    if mask in ("probe", "both"):
        probe = FilterExec((col("p") > lit(0)).expr, probe)
    if mask in ("build", "both"):
        build = ProjectExec(
            [col("sb").expr, col("k2").expr, col("vb").alias("w").expr],
            FilterExec((col("q") > lit(0)).expr, build))
    join = join_cls([col("k").expr], [col("k2").expr], how, condition,
                    probe, build)
    join.foreach(lambda e: setattr(e, "placement", engine))
    return join


def _collect(plan) -> pa.Table:
    ctx = ExecContext()
    batches = [to_host_batch(b, plan.output_names)
               for pid in range(plan.num_partitions)
               for b in plan.execute_partition(pid, ctx)]
    return pa.Table.from_batches(
        batches, to_arrow_schema(plan.output_names, plan.output_types))


def _unpaired(monkeypatch):
    monkeypatch.setattr(HashJoinExec, "masked_sources",
                        lambda self: (None, None))


def _three_ways(monkeypatch, tables, how, mask, filter_batches, **shape):
    """The masked answer, held to the compacted one (same engine, same
    order) and to `CpuJoinExec`'s; the counter says which path ran."""
    masked0, compact0 = _counter("mask"), _counter("compact")
    plan = _plan(tables, how, mask, **shape)
    paired = plan.masked_sources()
    assert [s is not None for s in paired] == \
        [mask in ("probe", "both"), mask in ("build", "both")]
    masked = _collect(plan)
    assert _counter("mask") - masked0 == filter_batches
    assert _counter("compact") == compact0
    with monkeypatch.context() as m:
        _unpaired(m)
        compacted = _collect(_plan(tables, how, mask, **shape))
    assert _counter("mask") - masked0 == filter_batches
    assert _counter("compact") - compact0 == filter_batches
    assert_tables_equal(compacted, masked, ignore_order=False)
    cpu = _collect(_plan(tables, how, mask, engine=CPU,
                         join_cls=CpuJoinExec, **shape))
    assert_tables_equal(cpu, masked, ignore_order=True)
    return masked


@pytest.mark.parametrize("data", ["mixed", "drops_all", "drops_none"])
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("how", HOWS)
def test_masked_answers_equal_compacted_and_cpu_answers(how, mask, data,
                                                        monkeypatch):
    got = _three_ways(monkeypatch, _tables(data), how, mask,
                      filter_batches=2 if mask == "both" else 1)
    if data == "mixed":
        assert got.num_rows


@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("how", HOWS)
def test_a_dropped_row_whose_key_matches_joins_nothing(how, mask):
    """The case a lost `& keep` lets through: every key matches, and the
    filters drop all but one row a side."""
    probe = pa.table({"k": pa.array([1, 1, 1, 2], pa.int64()),
                      "va": pa.array([10, 11, 12, 13], pa.int64()),
                      "sa": pa.array(list("abcd")),
                      "p": pa.array([-1, 5, -1, -1], pa.int64())})
    build = pa.table({"k2": pa.array([1, 1, 2, 2], pa.int64()),
                      "vb": pa.array([20, 21, 22, 23], pa.int64()),
                      "sb": pa.array(list("wxyz")),
                      "q": pa.array([-1, -1, 7, -1], pa.int64())})
    got = _collect(_plan((probe, build), how, mask))
    rows = got.to_pylist()
    kept_p = [1] if mask != "build" else [0, 1, 2, 3]
    kept_b = [2] if mask != "probe" else [0, 1, 2, 3]
    pairs = [(i, j) for i in kept_p for j in kept_b
             if probe["k"][i] == build["k2"][j]]
    want = {"inner": len(pairs),
            "left": len(pairs) + sum(
                1 for i in kept_p if not any(a == i for a, _ in pairs)),
            "right": len(pairs) + sum(
                1 for j in kept_b if not any(b == j for _, b in pairs)),
            "left_semi": len({a for a, _ in pairs}),
            "left_anti": len(kept_p) - len({a for a, _ in pairs})}
    want["full"] = want["left"] + want["right"] - len(pairs)
    assert len(rows) == want[how]
    assert {r["va"] for r in rows} - {None} <= {10 + i for i in kept_p}
    if how not in ("left_semi", "left_anti"):
        vb = "vb" if mask == "probe" else "w"
        assert {r[vb] for r in rows} - {None} <= {20 + j for j in kept_b}


@pytest.mark.parametrize("mask", MASKS)
def test_a_left_join_with_a_residual_condition(mask, monkeypatch):
    """A dropped probe row emits no null-extended row; a kept one whose
    candidates all fail the condition emits exactly one."""
    vb = "w" if mask != "probe" else "vb"
    condition = (col("va") * lit(3) > col(vb)).expr
    got = _three_ways(monkeypatch, _tables("mixed"), "left", mask,
                      filter_batches=2 if mask == "both" else 1,
                      condition=condition)
    assert got.column(vb).null_count and \
        got.column(vb).null_count < got.num_rows


LAYOUTS = {
    "two_probe_batches": dict(probe_rows=150),
    "two_build_batches": dict(build_rows=60),
    "two_build_partitions": dict(build_partitions=2),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("how", HOWS)
def test_several_batches_a_side(how, layout, monkeypatch):
    """Probe batches each bring their own flags; several masked build
    batches are compacted each under its flags and concatenated (the
    filter still answers with its mask: the compaction is the join's)."""
    n_before = len(CompileObservatory.get().snapshot()["programs"])
    _three_ways(monkeypatch, _tables("mixed"), how, "both",
                filter_batches=3, **LAYOUTS[layout])
    built = [p for p in CompileObservatory.get().snapshot()["programs"]
             [n_before:] if p["exec"] == "FilterExec"]
    # (the first run of a layout builds; whatever it built as a mask
    # holds no pass)
    assert all(p["sort_passes"] == 0 for p in built
               if p["filters_masked"])


@pytest.mark.parametrize("bkeep", [False, True])
@pytest.mark.parametrize("pkeep", [False, True])
@pytest.mark.parametrize("how", ["inner", "full"])
def test_the_numpy_engine_takes_the_same_flags(how, pkeep, bkeep):
    """`_count` under `xp is np` (the oracle) and under jax agree on every
    probe row's match count and on the matched build rows, with flags on
    neither, either or both sides."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.device import batch_to_device
    probe_table, build_table = _tables("mixed")
    join = _plan((probe_table, build_table), how, "none")
    rng = np.random.default_rng(3)
    flags = {}
    got = {}
    for xp in (np, jnp):
        probe = batch_to_device(probe_table.to_batches()[0], xp=xp)
        build = batch_to_device(build_table.to_batches()[0], xp=xp)
        if not flags:                        # the same flags for both
            flags = {"p": rng.random(probe.capacity) < 0.5,
                     "b": rng.random(build.capacity) < 0.5}
        pk = xp.asarray(flags["p"]) if pkeep else None
        bk = xp.asarray(flags["b"]) if bkeep else None
        _, _, counts, sizes, matched = join._count(xp, build, probe, True,
                                                   pk, bk)
        got[xp.__name__] = (np.asarray(counts), np.asarray(sizes),
                            np.asarray(matched))
    for a, b in zip(got["numpy"], got["jax.numpy"]):
        assert np.array_equal(a, b)
    counts = got["numpy"][0]
    assert counts.sum() and counts[probe_table.num_rows:].sum() == 0


# -- the plan's shape decides -------------------------------------------------

def _session(enabled=True, **conf):
    b = TpuSession.builder().config("spark.rapids.sql.enabled", enabled)
    for k, v in conf.items():
        b = b.config(k, v)
    return b.get_or_create()


def _nodes(session):
    out = []
    session.last_plan.foreach(out.append)
    return out


def _fact(session, partitions=1):
    rng = np.random.default_rng(5)
    n = 900
    return session.create_dataframe(pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "x": pa.array(rng.integers(0, 1000, n).astype(np.int64))}),
        num_partitions=partitions)


def _dim(session, partitions=1):
    return session.create_dataframe(pa.table({
        "k2": pa.array(np.arange(9, dtype=np.int64)),
        "w": pa.array(np.arange(9, dtype=np.int64) * 3),
        "z": pa.array(np.arange(9, dtype=np.int64) % 2)}),
        num_partitions=partitions)


def _kept(s):
    return _fact(s).filter(col("x") > lit(100))


_NO_BROADCAST = {"spark.rapids.sql.autoBroadcastJoinThreshold": -1}

#: query -> (filter batches that go up as a mask, that are compacted)
SHAPES = {
    # (the "join" case of test_masked_filter's CONSUMERS until PR 36)
    "filter_under_the_probe": (
        lambda s: _kept(s).join(_dim(s), col("k") == col("k2")), (1, 0)),
    "filter_under_the_build": (
        lambda s: _fact(s).join(_dim(s).filter(col("z") == lit(0)),
                                col("k") == col("k2"), "left"), (1, 0)),
    "bare_selection_between": (
        lambda s: _fact(s).join(
            _dim(s).filter(col("z") == lit(0)).select(
                col("w").alias("w2"), col("k2")),
            col("k") == col("k2"), "left"), (1, 0)),
    "both_sides": (
        lambda s: _kept(s).join(
            _dim(s).filter(col("z") == lit(0)).select(col("k2")),
            col("k") == col("k2"), "left_semi"), (2, 0)),
    "computing_project_between": (
        lambda s: _fact(s).join(
            _dim(s).filter(col("z") == lit(0)).select(
                (col("w") + lit(1)).alias("w1"), col("k2")),
            col("k") == col("k2"), "left"), (0, 1)),
    "row_position_in_a_key": (
        lambda s: _kept(s).join(
            _dim(s), (col("k") + F.monotonically_increasing_id() * lit(0))
            == col("k2"), "left"), (0, 1)),
    "row_position_in_the_residual": (
        lambda s: _kept(s).join(
            _dim(s), (col("k") == col("k2")) &
            (F.monotonically_increasing_id() + col("w") >= lit(0)),
            "inner"), (0, 1)),
    "nested_loop_join": (
        lambda s: _kept(s).join(_dim(s), col("k") > col("k2") + lit(6),
                                "inner"), (0, 1)),
    "sort_between": (
        lambda s: _kept(s).order_by(col("x")).join(
            _dim(s), col("k") == col("k2"), "left"), (0, 1)),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_plans_shape_decides_what_the_filter_does(shape):
    query, (masked, compacted) = SHAPES[shape]
    session = _session()
    masked0, compact0 = _counter("mask"), _counter("compact")
    got = query(session).collect()
    assert any(isinstance(e, FilterExec) and e.placement == TPU
               for e in _nodes(session))
    assert (_counter("mask") - masked0,
            _counter("compact") - compact0) == (masked, compacted)
    assert_tables_equal(query(_session(False)).collect(), got)


def test_a_shuffled_hash_join_reads_its_exchanges():
    """Its children are exchanges, which pull `execute_partition` like
    every consumer that reads rows by position."""
    session = _session(**_NO_BROADCAST,
                       **{"spark.rapids.tpu.singleChipFuse": "off"})
    query = lambda s: (_fact(s, 2).filter(col("x") > lit(100))  # noqa: E731
                       .join(_dim(s, 2).filter(col("z") == lit(0)),
                             col("k") == col("k2"), "inner"))
    masked0, compact0 = _counter("mask"), _counter("compact")
    got = query(session).collect()
    (join,) = [e for e in _nodes(session) if isinstance(e, HashJoinExec)]
    assert isinstance(join, ShuffledHashJoinExec)
    assert join.masked_sources() == (None, None)
    assert _counter("mask") == masked0
    assert _counter("compact") == compact0 + 4
    assert_tables_equal(query(_session(False)).collect(), got)


def _join_and_filters(session):
    join = next(e for e in _nodes(session) if isinstance(e, HashJoinExec))
    return join, [e for e in _nodes(session) if isinstance(e, FilterExec)]


@pytest.mark.parametrize("what", ["armed_rebucket_cap", "filter_on_cpu",
                                  "join_on_cpu", "selection_on_cpu"])
def test_what_unpairs_a_side(what):
    session = _session()
    query, _ = SHAPES["both_sides"]
    want = query(session).collect()
    join, (probe_filter, build_filter) = _join_and_filters(session)
    selection = join.children[1]
    assert join.masked_sources() == (probe_filter, selection)
    assert selection.masked_sources() == (build_filter,)
    if what == "armed_rebucket_cap":
        # the L018 repair shrinks a COMPACTED output
        build_filter.rebucket_cap = 1024
        assert selection.masked_sources() == (None,)
        assert join.masked_sources() == (probe_filter, None)
        probe_filter.rebucket_cap = 1024
        assert join.masked_sources() == (None, None)
        masked0, compact0 = _counter("mask"), _counter("compact")
        out = list(join.execute_partition(0, ExecContext(session.conf)))
        assert (_counter("mask"), _counter("compact")) == \
            (masked0, compact0 + 2)
        assert sum(int(b.num_rows) for b in out) == want.num_rows
    elif what == "filter_on_cpu":
        probe_filter.placement = CPU
        assert join.masked_sources() == (None, selection)
    elif what == "selection_on_cpu":
        selection.placement = CPU
        assert join.masked_sources() == (probe_filter, None)
    else:
        join.placement = CPU
        assert join.masked_sources() == (None, None)


# -- a masked batch reaches nothing that does not read the mask ---------------

def test_only_the_paired_consumer_may_pull_execute_masked():
    session = _session()
    query, _ = SHAPES["both_sides"]
    query(session).collect()
    join, (probe_filter, build_filter) = _join_and_filters(session)
    selection = join.children[1]
    scan = probe_filter.children[0]
    ctx = ExecContext(session.conf)
    keys = ([col("k").expr], [col("k2").expr])
    strangers = [
        SortExec([(col("x").expr, True, True)], probe_filter),
        ProjectExec([(col("x") + lit(1)).expr], probe_filter),
        ProjectExec([col("x").expr], probe_filter),
        NestedLoopJoinExec("cross", None, probe_filter, selection),
        CpuJoinExec(*keys, "inner", None, probe_filter, selection),
        HashJoinExec(*keys, "inner", None, scan, selection),   # CPU engine
    ]
    for consumer in strangers:
        for source in (probe_filter, build_filter, selection):
            with pytest.raises(RuntimeError, match="is not paired with"):
                next(iter(source.execute_masked(0, ctx, consumer)))
    # the join reads the selection, not the filter under it
    with pytest.raises(RuntimeError, match="is not paired with"):
        next(iter(build_filter.execute_masked(0, ctx, join)))
    # armed after the pairing: refused too
    build_filter.rebucket_cap = 1024
    with pytest.raises(RuntimeError, match="is not paired with"):
        next(iter(selection.execute_masked(0, ctx, join)))
    build_filter.rebucket_cap = None
    (m,) = list(selection.execute_masked(0, ctx, join))
    assert isinstance(m, MaskedBatch)
    # the selected column went up as it lay: the scan's own array
    (scanned,) = list(build_filter.children[0].execute_partition(0, ctx))
    assert list(m.batch.names) == ["k2"] and len(m.batch.columns) == 1
    assert m.batch.columns[0].data is scanned.columns[0].data
    assert int(m.batch.num_rows) == 9
    keep = np.asarray(m.keep)
    assert keep[:9].tolist() == [i % 2 == 0 for i in range(9)]
    assert int(m.num_rows) == keep.sum() == 5


def test_a_computing_project_evaluates_nothing_on_a_dropped_row():
    """Why only a bare selection forwards: under ANSI a division by zero
    raises, and the filter removed the zeros."""
    session = _session(**{"spark.rapids.sql.ansi.enabled": True})
    dim = session.create_dataframe(pa.table({
        "k2": pa.array(np.arange(9, dtype=np.int64)),
        "d": pa.array(np.arange(9, dtype=np.int64) % 3)}))
    masked0, compact0 = _counter("mask"), _counter("compact")
    got = _fact(session).join(
        dim.filter(col("d") != lit(0)).select(
            col("k2"), (lit(12) / col("d")).alias("r")),
        col("k") == col("k2"), "inner").collect()
    assert got.num_rows and set(got.column("r").to_pylist()) == {12.0, 6.0}
    assert (_counter("mask"), _counter("compact")) == \
        (masked0, compact0 + 1)


# -- what the spans and the build counters say --------------------------------

def test_join_spans_say_which_side_came_up_masked():
    session = _session(**{"spark.rapids.tpu.trace.enabled": True})

    def spans(frame):
        frame.collect()
        trace = session.last_query_trace()
        return [next(sp for sp in trace.spans if sp.name == name).attrs
                for name in ("join.build", "join.probe")]
    query, _ = SHAPES["both_sides"]
    build, probe = spans(query(session))
    assert build["masked"] is True and "rows" not in build
    assert build["capacity"] == 1024
    assert probe["probe_masked"] is True
    build, probe = spans(_fact(session).join(
        _dim(session), col("k") == col("k2"), "left_semi"))
    assert build["masked"] is False and build["rows"] == 9
    assert probe["probe_masked"] is False


def test_the_joins_programs_say_which_flags_they_take():
    """One count program a combination of masked sides, one expansion
    for a masked probe and one for a plain one: the key says which."""
    from spark_rapids_tpu.exec.base import _JIT_CACHE
    tables = _tables("mixed")
    # a schema of this test's own, so that the programs are built here
    tables = (tables[0].append_column("only_here", tables[0]["va"]),
              tables[1])
    for mask in ("none",) + tuple(MASKS):
        _collect(_plan(tables, "inner", mask))
    sig = _plan(tables, "inner", "none")._jit_key[2]
    def roles_of(key):
        words = [p for p in key if isinstance(p, str)]
        return tuple(words[words.index("inner") + 1:])
    roles = sorted(roles_of(key) for key in _JIT_CACHE
                   if "HashJoinExec" in key and sig in key)
    counts = [r for r in roles if r[-1] == "count"]
    assert counts == [("build_masked", "count"), ("count",),
                      ("probe_masked", "build_masked", "count"),
                      ("probe_masked", "count")]
    assert {r for r in roles if r[-1] == "expand"} == \
        {("expand",), ("probe_masked", "expand")}
