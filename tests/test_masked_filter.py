"""A `FilterExec` directly under the update side of a TPU aggregate hands
up its keep flags and moves no lane (`FilterExec.execute_masked`,
`TpuHashAggregateExec.masked_source`); under a consumer that reads rows by
position it compacts.  (The other consumer that reads a mask, either side
of a `HashJoinExec`, is `tests/test_masked_join.py`'s.)

The referees: the same query with the pairing switched off (the filter
compacts, as it did before), and the NumPy engine
(`spark.rapids.sql.enabled=false`)."""

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
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec  # noqa: E402
from spark_rapids_tpu.exec.base import ExecContext  # noqa: E402
from spark_rapids_tpu.exec.basic import (FilterExec,  # noqa: E402
                                         LocalScanExec, ProjectExec)
from spark_rapids_tpu.exec.filter_common import MaskedBatch  # noqa: E402
from spark_rapids_tpu.expr.aggregates import (COMPLETE, FINAL,  # noqa: E402
                                              PARTIAL)
from spark_rapids_tpu.obs import compileprof  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402
from spark_rapids_tpu.testing.asserts import assert_tables_equal  # noqa: E402

N = 1500
BATCH_ROWS = 400


def _session(enabled=True, **conf):
    b = TpuSession.builder().config("spark.rapids.sql.enabled", enabled)
    for k, v in conf.items():
        b = b.config(k, v)
    return b.get_or_create()


def _nodes(session):
    out = []
    session.last_plan.foreach(out.append)
    return out


def _counter(path):
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == "tpu_filter_batches_total":
            return family.value(path=path)
    return 0


# -- the data -----------------------------------------------------------------

def _table(data: str) -> pa.Table:
    """`plain`: no null anywhere, the predicate keeps about half; `nulls`:
    null predicates, nulls in keys and values, inf and nan among the
    doubles of `g`; `drops_all`: the predicate keeps no row; `empty`: no
    row at all."""
    n = 0 if data == "empty" else N
    rng = np.random.default_rng(11)
    nulls = data == "nulls"

    def maybe_null(values, arrow_type, share=0.15):
        mask = rng.random(n) < share if nulls else None
        return pa.array(values, arrow_type, mask=mask)

    g = rng.normal(0, 1e3, n)
    if nulls and n:
        g[rng.integers(0, n, 12)] = np.inf
        g[rng.integers(0, n, 6)] = -np.inf
        g[rng.integers(0, n, 6)] = np.nan
    p = rng.integers(-100, 100, n) - (10**6 if data == "drops_all" else 0)
    return pa.table({
        "p": maybe_null(p.astype(np.int64), pa.int64()),
        "c1": pa.array([("A", "N", "R")[i] for i in rng.integers(0, 3, n)],
                       pa.string()),
        "c2": pa.array([("F", "O")[i] for i in rng.integers(0, 2, n)],
                       pa.string()),
        "b": maybe_null(rng.integers(0, 2, n).astype(bool), pa.bool_()),
        "k": maybe_null(rng.integers(0, 37, n).astype(np.int64) * 10**10,
                        pa.int64()),
        "i": maybe_null(rng.integers(-10**6, 10**6, n).astype(np.int64),
                        pa.int64()),
        "f": maybe_null(rng.random(n) * 1e4, pa.float64()),
        "g": maybe_null(g, pa.float64()),
    })


# -- the queries: one per arm of `_group_reduce` ------------------------------

#: float sums and what is made of them: which addends meet in the
#: two-level sum follows their positions, so these agree to 1e-12 and not
#: to the bit; every other column is positional-independent and exact
FLOAT_SUMS = ("sf", "af", "sg")

_AGGS = [F.count("*").alias("n"), F.count(col("i")).alias("ni"),
         F.sum(col("i")).alias("si"), F.min(col("i")).alias("mni"),
         F.max(col("f")).alias("mxf"), F.min(col("g")).alias("mng"),
         F.first(col("i")).alias("fi"),
         F.first(col("f"), ignorenulls=True).alias("ff"),
         F.last(col("i"), ignorenulls=True).alias("li"),
         F.last(col("f")).alias("lf"),
         F.sum(col("g")).alias("sg"),
         F.sum(col("f") * lit(0.5)).alias("sf"), F.avg(col("f")).alias("af")]

ARMS = {
    "ungrouped": lambda df: df.agg(*_AGGS),
    "dense_char1": lambda df: df.group_by(col("c1"), col("c2")).agg(*_AGGS),
    "dense_boolean": lambda df: df.group_by(col("b")).agg(*_AGGS),
    "sort_int64": lambda df: df.group_by(col("k")).agg(*_AGGS),
    "sort_collect_list": lambda df: df.group_by(col("k")).agg(
        F.collect_list(col("i")).alias("ci"), F.count("*").alias("n"),
        F.sum(col("f")).alias("sf")),
}

#: what each arm counts at build (`ops/carry.lane_move_counts`)
ARM_COUNT = {"ungrouped": "ungrouped_reduced", "dense_char1": "grouped_dense",
             "dense_boolean": "grouped_dense", "sort_int64": "grouped_sorted",
             "sort_collect_list": "grouped_sorted"}

LAYOUTS = {"one_batch": 1, "several_batches": 1, "partial_then_final": 4}


@pytest.fixture
def layout(request, monkeypatch):
    """`several_batches`: every scan cuts its partition into batches of
    `BATCH_ROWS` rows, so the aggregate updates batch by batch and merges."""
    if request.param == "several_batches":
        real = LocalScanExec.__init__

        def init(self, *args, **kwargs):
            real(self, *args, **kwargs)
            self.batch_rows = self.batch_rows or BATCH_ROWS
        monkeypatch.setattr(LocalScanExec, "__init__", init)
    return request.param


def _run(arm, table, partitions, enabled=True):
    session = _session(enabled)
    df = session.create_dataframe(table, num_partitions=partitions)
    return session, ARMS[arm](df.filter(col("p") > lit(0))).collect()


def _split(table):
    exact = [n for n in table.column_names if n not in FLOAT_SUMS]
    return table.select(exact), table.select(
        [n for n in table.column_names if n in FLOAT_SUMS])


def _lists_sorted(table):
    """A collected list by its values: another engine gathers a group's
    partials in another order."""
    if "ci" not in table.column_names:
        return table
    lists = [None if v is None else sorted(v)
             for v in table.column("ci").to_pylist()]
    return table.set_column(
        table.column_names.index("ci"), "ci",
        pa.array(lists, table.schema.field("ci").type))


@pytest.mark.parametrize("layout", list(LAYOUTS), indirect=True)
@pytest.mark.parametrize("data", ["plain", "nulls", "drops_all", "empty"])
@pytest.mark.parametrize("arm", list(ARMS))
def test_masked_answers_equal_compacted_answers(arm, data, layout,
                                                monkeypatch):
    table = _table(data)
    partitions = LAYOUTS[layout]
    masked0, compact0 = _counter("mask"), _counter("compact")
    session, masked = _run(arm, table, partitions)
    updates = [e for e in _nodes(session)
               if isinstance(e, TpuHashAggregateExec) and e.mode != FINAL]
    assert len(updates) == 1
    batches = -(-max(N // partitions, 1) // BATCH_ROWS) * partitions \
        if layout == "several_batches" and data != "empty" else partitions
    if arm == "ungrouped" and partitions > 1:
        # no exchange is planned under an ungrouped aggregate: a
        # GatherPartitionsExec lies between, and the filter compacts
        assert type(updates[0].children[0]).__name__ == \
            "GatherPartitionsExec"
        assert updates[0].masked_source() is None
        assert _counter("mask") == masked0
        return
    assert isinstance(updates[0].masked_source(), FilterExec)
    assert _counter("mask") - masked0 == batches
    assert _counter("compact") == compact0

    with monkeypatch.context() as m:
        m.setattr(TpuHashAggregateExec, "masked_source", lambda self: None)
        _, compacted = _run(arm, table, partitions)
    assert _counter("mask") - masked0 == batches
    assert _counter("compact") - compact0 == batches
    # same engine, same plan: the same groups in the same order, equal to
    # the bit where the arithmetic does not depend on the rows' positions
    exact_m, float_m = _split(masked)
    exact_c, float_c = _split(compacted)
    assert_tables_equal(exact_c, exact_m, ignore_order=False)
    assert_tables_equal(float_c, float_m, ignore_order=False,
                        approximate_float=1e-12)

    _, cpu = _run(arm, table, partitions, enabled=False)
    # first and last of a group spread over several partials follow the
    # order the merge folds them in (canonical on this engine, arrival on
    # the other), masked or compacted alike
    drop = [n for n in ("fi", "ff", "li", "lf")
            if layout != "one_batch" and n in cpu.column_names]
    assert_tables_equal(_lists_sorted(cpu.drop_columns(drop)),
                        _lists_sorted(masked.drop_columns(drop)),
                        ignore_order=True, approximate_float=1e-9)


@pytest.mark.parametrize("arm", list(ARMS))
def test_each_arm_is_the_arm_it_was_and_the_filter_sorts_nothing(arm):
    """The filter's mask program holds no sort pass and moves no lane;
    the aggregate above it takes the arm its keys and ops choose."""
    n_before = len(CompileObservatory.get().snapshot()["programs"])
    # a schema of this test's own, so that both programs are built here
    table = _table("nulls").append_column(
        "only_" + arm, pa.array(np.zeros(N, np.int8)))
    session, _ = _run(arm, table, 1)
    built = CompileObservatory.get().snapshot()["programs"][n_before:]
    (mask,) = [p for p in built if p["exec"] == "FilterExec"]
    assert mask["filters_masked"] == 1 and mask["filters_compacted"] == 0
    assert mask["sort_passes"] == 0 and mask["lane_moves_sorted"] == 0
    assert mask["lane_moves_gathered"] == 0
    (agg,) = [p for p in built if p["exec"] == "TpuHashAggregateExec"]
    assert agg[ARM_COUNT[arm]] == 1
    assert agg["filters_masked"] == 0 == agg["filters_compacted"]


# -- the plan's shape decides -------------------------------------------------

def _small(session, partitions=1):
    rng = np.random.default_rng(5)
    n = 900
    return session.create_dataframe(pa.table({
        "k": pa.array(rng.integers(0, 9, n).astype(np.int64)),
        "x": pa.array(rng.integers(0, 1000, n).astype(np.int64))}),
        num_partitions=partitions)


CONSUMERS = {
    "project": lambda s: _small(s).filter(col("x") > lit(100))
    .select((col("x") + lit(1)).alias("y")),
    "sort": lambda s: _small(s).filter(col("x") > lit(100))
    .order_by(col("x")),
    "limit": lambda s: _small(s).filter(col("x") > lit(100)).limit(5),
    "fetch": lambda s: _small(s).filter(col("x") > lit(100)),
    "project_under_aggregate": lambda s: _small(s)
    .filter(col("x") > lit(100)).select(col("k"), (col("x") * lit(2))
                                        .alias("y"))
    .group_by(col("k")).agg(F.sum(col("y")).alias("s")),
    "having": lambda s: _small(s).group_by(col("k"))
    .agg(F.sum(col("x")).alias("s")).filter(col("s") > lit(100)),
    "row_position_in_the_aggregate": lambda s: _small(s)
    .filter(col("x") > lit(100))
    .agg(F.max(F.monotonically_increasing_id()).alias("m")),
}


@pytest.mark.parametrize("consumer", list(CONSUMERS))
def test_under_any_other_consumer_the_filter_compacts(consumer):
    session = _session()
    masked0, compact0 = _counter("mask"), _counter("compact")
    got = CONSUMERS[consumer](session).collect()
    nodes = _nodes(session)
    assert any(isinstance(e, FilterExec) and e.placement == "tpu"
               for e in nodes)
    assert all(e.masked_source() is None for e in nodes
               if isinstance(e, TpuHashAggregateExec))
    assert _counter("mask") == masked0
    assert _counter("compact") == compact0 + 1
    cpu = CONSUMERS[consumer](_session(False)).collect()
    assert_tables_equal(cpu, got, ignore_order=consumer != "sort")


def test_under_a_mesh_aggregate_the_filter_compacts():
    """`parallel/ici_exec` replaces the aggregate by a mesh stage, which
    pulls `execute_partition` like every other consumer."""
    session = _session(**{"spark.rapids.shuffle.transport": "ici"})
    masked0, compact0 = _counter("mask"), _counter("compact")
    query = lambda s: (_small(s, partitions=4)  # noqa: E731
                       .filter(col("x") > lit(100)).group_by(col("k"))
                       .agg(F.sum(col("x")).alias("s")))
    got = query(session).collect()
    names = [type(e).__name__ for e in _nodes(session)]
    assert "IciAggregateExec" in names and "FilterExec" in names
    assert _counter("mask") == masked0
    assert _counter("compact") == compact0 + 4
    assert_tables_equal(query(_session(False)).collect(), got)


def _paired(session):
    """(aggregate, filter) of the plan the session ran last."""
    aggregate = next(e for e in _nodes(session)
                     if isinstance(e, TpuHashAggregateExec))
    return aggregate, aggregate.children[0]


def _filter_then_sum(session):
    return (_small(session).filter(col("x") > lit(100)).group_by(col("k"))
            .agg(F.sum(col("x")).alias("s")))


@pytest.mark.parametrize("selection", [
    lambda df: df.select(col("x"), col("k")),
    lambda df: df.select(col("k").alias("key"), col("x").alias("x"))
    .select(col("key").alias("k"), col("x"))])
def test_a_bare_selection_between_forwards_the_flags(selection):
    """What a planner's column pruning leaves between the two: the
    selection evaluates nothing, so it hands the filter's flags on (a
    project that computes does not: `CONSUMERS`)."""
    query = lambda s: (selection(  # noqa: E731
        _small(s).filter(col("x") > lit(100)))
        .group_by(col("k")).agg(F.sum(col("x")).alias("s")))
    session = _session()
    masked0, compact0 = _counter("mask"), _counter("compact")
    got = query(session).collect()
    aggregate, project = _paired(session)
    assert isinstance(project, ProjectExec)
    assert aggregate.masked_source() is project
    assert (_counter("mask"), _counter("compact")) == (masked0 + 1, compact0)
    assert_tables_equal(query(_session(False)).collect(), got)


def test_an_armed_rebucket_cap_compacts():
    """The L018 repair shrinks a COMPACTED output: with it armed the
    filter is no masked source, whatever lies above."""
    session = _session()
    want = _filter_then_sum(session).collect()
    aggregate, filt = _paired(session)
    assert aggregate.masked_source() is filt
    filt.rebucket_cap = 1024
    assert aggregate.masked_source() is None
    masked0, compact0 = _counter("mask"), _counter("compact")
    ctx = ExecContext(session.conf)
    out = list(aggregate.execute_partition(0, ctx))
    assert _counter("mask") == masked0
    assert _counter("compact") == compact0 + 1
    assert int(out[0].num_rows) == want.num_rows


@pytest.mark.parametrize("engines", ["filter_on_cpu", "aggregate_on_cpu"])
def test_both_must_be_on_the_tpu_engine(engines):
    session = _session()
    _filter_then_sum(session).collect()
    aggregate, filt = _paired(session)
    assert aggregate.masked_source() is filt
    (filt if engines == "filter_on_cpu" else aggregate).placement = "cpu"
    assert aggregate.masked_source() is None


def test_the_final_side_of_an_aggregate_pairs_with_nothing():
    session = _session()
    got = (_small(session, partitions=4).group_by(col("k"))
           .agg(F.sum(col("x")).alias("s")).collect())
    assert got.num_rows == 9
    modes = {e.mode: e for e in _nodes(session)
             if isinstance(e, TpuHashAggregateExec)}
    assert set(modes) == {PARTIAL, FINAL}
    assert modes[FINAL].masked_source() is None
    assert modes[PARTIAL].masked_source() is None     # over the scan


# -- a masked batch reaches nothing that does not read the mask ---------------

def test_only_the_paired_aggregate_may_pull_execute_masked():
    session = _session()
    _filter_then_sum(session).collect()
    aggregate, filt = _paired(session)
    ctx = ExecContext(session.conf)
    project = ProjectExec([col("x").expr], filt)
    other = TpuHashAggregateExec(aggregate.grouping, [], COMPLETE,
                                 _nodes(session)[-1])
    for consumer in (project, other, None):
        with pytest.raises(RuntimeError, match="is not paired with"):
            next(iter(filt.execute_masked(0, ctx, consumer)))
    # armed after the pairing: refused too, and execute_partition compacts
    filt.rebucket_cap = 1024
    with pytest.raises(RuntimeError, match="is not paired with"):
        next(iter(filt.execute_masked(0, ctx, aggregate)))
    filt.rebucket_cap = None
    (m,) = list(filt.execute_masked(0, ctx, aggregate))
    assert isinstance(m, MaskedBatch)
    # the batch went up as it lay: the scan's own arrays, not copies
    (scanned,) = list(filt.children[0].execute_partition(0, ctx))
    for mine, theirs in zip(m.batch.columns, scanned.columns):
        assert mine.data is theirs.data
    assert int(m.batch.num_rows) == 900
    keep = np.asarray(m.keep)
    assert keep.dtype == bool and keep.shape == (m.capacity,)
    assert int(m.num_rows) == keep.sum() == keep[:900].sum()
    # and it is no batch: nothing reads columns off it, no program takes it
    assert not hasattr(m, "columns") and not hasattr(m, "names")
    import jax
    assert jax.tree_util.tree_leaves(m) == [m]
    with pytest.raises(AttributeError):
        project._compute(np, m)


def test_execute_partition_of_a_paired_filter_still_compacts():
    """What reads a paired filter through the ordinary iterator (a plan
    rewrite that shares the node, a tool that walks the plan) gets the
    compacted batch."""
    session = _session()
    _filter_then_sum(session).collect()
    _, filt = _paired(session)
    (out,) = list(filt.execute_partition(0, ExecContext(session.conf)))
    x = np.asarray(out.columns[1].data)[:int(out.num_rows)]
    assert len(x) and (x > 100).all()


# -- names --------------------------------------------------------------------

@pytest.mark.parametrize("role, name", [
    ((), "FilterExec"), (("rowpos",), "FilterExec.rowpos"),
    (("mask",), "FilterExec.mask"), (("rowpos", "mask"), "FilterExec.mask")])
def test_the_mask_program_carries_the_filters_name(role, name):
    from benchmarks.harness.program_kinds import is_of_kind
    key = ("3.5", "FilterExec", ("schema",), ("semantic",)) + role
    assert compileprof.program_name(key) == name
    assert is_of_kind(f"jit_{name}#1234", "FilterExec")
    assert not is_of_kind(f"jit_{name}#1234", "TpuHashAggregateExec")


def test_a_predicate_that_reads_the_row_position_masks_under_rowpos():
    session = _session()
    query = lambda s: (_small(s).filter(  # noqa: E731
        F.monotonically_increasing_id() % lit(3) == lit(0))
        .agg(F.sum(col("x")).alias("s"), F.count("*").alias("n")))
    masked0 = _counter("mask")
    got = query(session).collect()
    assert _counter("mask") == masked0 + 1
    assert got.column("n").to_pylist() == [300]
    assert_tables_equal(query(_session(False)).collect(), got)


# -- arming a filter (testing/faults.py) reaches both iterators ---------------

@pytest.mark.parametrize("consumer", ["aggregate", "fetch"])
def test_an_armed_filter_fires_whichever_iterator_the_plan_pulls(consumer):
    from spark_rapids_tpu.testing.faults import (arm_filter, disarm_filter,
                                                 raw_filter_iterator)
    session = _session()
    query = _filter_then_sum if consumer == "aggregate" \
        else CONSUMERS["fetch"]
    want = query(session).collect()
    pulled = []

    def probing(self, pid, ctx, *paired):
        for b in raw_filter_iterator(self, pid, ctx, *paired):
            pulled.append(type(b).__name__)
            yield b

    def boom(self, pid, ctx, *paired):
        raise RuntimeError("armed")
        yield

    armed = arm_filter(probing)
    try:
        assert_tables_equal(want, query(session).collect())
    finally:
        disarm_filter(armed)
    assert pulled == ["MaskedBatch" if consumer == "aggregate"
                      else "DeviceBatch"]
    armed = arm_filter(boom)
    try:
        with pytest.raises(RuntimeError, match="armed"):
            query(session).collect()
    finally:
        disarm_filter(armed)
    assert_tables_equal(want, query(session).collect())
