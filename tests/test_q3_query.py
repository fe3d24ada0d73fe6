"""TPC-H Q3 (benchmarks/queries/q3.py) on the engine's normal path, held to
its plain NumPy reference on the CPU backend at a tiny scale: two hash
joins over three tables, a general-layout string filter, a three-column
group key, a descending float key and a limit.  The cases that bite are
made by hand: an order dated exactly DATE (out), a line shipped exactly on
DATE (out), customers without orders, a segment that leaves no customer,
fewer than ten groups, two groups of equal revenue ordered by date."""

import datetime
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells, runner  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
# the instances the harness would call: `q3.build` finds the tables through
# `cells.load_module`, which memoises by path
gen = cells.load_module(os.path.join(BENCH, "datagen", "tpch_q3_tables.py"))
q3 = cells.load_module(os.path.join(BENCH, "queries", "q3.py"))
base = cells.load_module(os.path.join(BENCH, "datagen", "tpch_lineitem.py"))

SEED = 2**31 + 33
EPOCH = datetime.date(1970, 1, 1)


def _frame(columns):
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    return session.create_dataframe(
        runner.arrow_table(columns, gen.SCHEMA), num_partitions=1)


@pytest.fixture(scope="module")
def programs_before():
    """How many programs the process had built before this module's
    first query (no other module runs Q3)."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    return len(CompileObservatory.get().snapshot()["programs"])


@pytest.fixture(scope="module")
def generated(programs_before):
    columns = gen.generate({"scale_factor": 0.01}, SEED)
    return columns, _frame(columns)


def _ask(generated, params):
    columns, df = generated
    gen.LAST = columns          # (another fixture may have generated since)
    got = q3.answer(q3.build(df, params).collect())
    return got, q3.reference(columns, params)


@pytest.mark.parametrize("day", [1, 16, 31])
@pytest.mark.parametrize("segment", gen.SEGMENTS)
def test_q3_equals_reference(generated, segment, day):
    got, want = _ask(generated, {"segment": segment, "day": day})
    assert q3.mismatch(got, want) is None
    assert q3.answer_rows(got) == q3.LIMIT
    assert q3.deviation(got, want) < 1e-12
    assert np.all(np.diff(got["revenue"]) <= 0)
    assert set(got["o_shippriority"].tolist()) == {0}


def test_every_operator_but_the_fetch_is_on_the_tpu_engine(generated):
    from spark_rapids_tpu.exec.base import CPU
    columns, df = generated
    _ask(generated, {"segment": "BUILDING", "day": 15})
    kinds = []
    df.session.last_plan.foreach(
        lambda e: kinds.append((type(e).__name__, e.placement,
                                getattr(e, "how", None))))
    assert [k for k, p, _ in kinds if p == CPU] == ["DeviceToHostExec"]
    assert [h for k, _, h in kinds if k == "HashJoinExec"] == \
        ["inner", "inner"]
    names = {k for k, _, _ in kinds}
    assert {"FilterExec", "TpuHashAggregateExec", "SortExec",
            "GlobalLimitExec", "LocalScanExec"} <= names


def _filter_batches(path):
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == "tpu_filter_batches_total":
            return family.value(path=path)
    return 0


def test_no_filter_moves_a_lane_in_front_of_a_join(generated,
                                                   programs_before):
    """All three filters lie under a join (CUSTOMER's under the bare
    selection of `c_custkey`): each hands up its keep flags, the joins
    read them, and no filter program holds a sort pass."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    obs = CompileObservatory.get()
    masked0, compact0 = _filter_batches("mask"), _filter_batches("compact")
    got, want = _ask(generated, {"segment": "BUILDING", "day": 15})
    assert q3.mismatch(got, want) is None
    assert _filter_batches("mask") == masked0 + 3
    assert _filter_batches("compact") == compact0
    _, df = generated
    joins = []
    df.session.last_plan.foreach(
        lambda e: joins.append(e)
        if type(e).__name__ == "HashJoinExec" else None)
    assert [[type(s).__name__ for s in j.masked_sources()]
            for j in joins] == [["FilterExec", "NoneType"],
                                ["FilterExec", "ProjectExec"]]
    filters = [p for p in obs.snapshot()["programs"][programs_before:]
               if p["exec"] == "FilterExec"]
    assert len(filters) == 3
    for p in filters:
        assert p["filters_masked"] == 1 and p["filters_compacted"] == 0
        assert p["sort_passes"] == 0 and p["lane_moves_sorted"] == 0
    built = obs.snapshot()["builds"]
    got, want = _ask(generated, {"segment": "MACHINERY", "day": 2})
    assert q3.mismatch(got, want) is None
    assert obs.snapshot()["builds"] == built
    assert _filter_batches("mask") == masked0 + 6


def test_the_reference_in_float32_is_not_correct(generated):
    """The tolerance bites: the nearest precision below fails it."""
    columns, _ = generated
    worst = []
    for segment in gen.SEGMENTS:
        params = {"segment": segment, "day": 16}
        want = q3.reference(columns, params)
        low = q3.reference(columns, params, np.float32)
        assert q3.mismatch(low, want) is not None
        worst.append(q3.deviation(
            q3.grouped(columns, params, np.float32),
            q3.grouped(columns, params)))
    assert min(worst) > 10 * q3.REL_TOLERANCE


def test_the_reference_finds_each_lines_order_like_a_search(generated):
    columns, _ = generated
    orders = columns.side["orders"]
    found = q3.order_index(columns["l_orderkey"])
    assert np.array_equal(
        found, np.searchsorted(orders["o_orderkey"], columns["l_orderkey"]))
    assert np.array_equal(orders["o_orderkey"][found], columns["l_orderkey"])
    # and a plain loop over one answer's lines agrees with its revenue
    params = {"segment": "MACHINERY", "day": 9}
    want = q3.reference(columns, params)
    key = int(want["l_orderkey"][0])
    cut = (q3.cut_date(params) - EPOCH).days
    lines = [i for i in np.flatnonzero(columns["l_orderkey"] == key)
             if columns["l_shipdate"][i] > cut]
    revenue = sum(float(columns["l_extendedprice"][i])
                  * (1.0 - float(columns["l_discount"][i])) for i in lines)
    assert lines and abs(revenue - want["revenue"][0]) < 1e-9 * revenue


# ---------------------------------------------------------------------------
# the cases that bite, made by hand
# ---------------------------------------------------------------------------

DATE = (datetime.date(1995, 3, 15) - EPOCH).days


def _by_hand(segments, custkeys, orderdates, lines):
    """Tables of a few rows: `lines` is (order row, ship date, price,
    discount) a line, in rising order row."""
    n_orders = len(orderdates)
    keys = base.sparse_orderkeys(0, n_orders)
    row, ship, price, disc = (np.array(x) for x in zip(*lines))
    tables = gen.Tables(
        l_orderkey=keys[row], l_extendedprice=price.astype(np.float64),
        l_discount=disc.astype(np.float64), l_shipdate=ship.astype(np.int32))
    tables.side = {
        "orders": {"o_orderkey": keys,
                   "o_custkey": np.array(custkeys, np.int64),
                   "o_orderdate": np.array(orderdates, np.int32),
                   "o_shippriority": np.zeros(n_orders, np.int32)},
        "customer": {"c_custkey": np.arange(1, len(segments) + 1,
                                            dtype=np.int64),
                     "c_mktsegment": np.array(segments)}}
    return tables, _frame(tables)


@pytest.fixture(scope="module")
def by_hand():
    # customer 3 has no order; customer 4 is of another segment
    segments = ["BUILDING", "BUILDING", "BUILDING", "MACHINERY", "BUILDING"]
    #            order row: 0     1         2         3         4        5
    custkeys = [1, 2, 1, 4, 5, 2]
    orderdates = [DATE - 10, DATE, DATE - 1, DATE - 5, DATE - 30, DATE - 20]
    lines = [
        (0, DATE + 5, 1000.0, 0.00),      # in
        (0, DATE, 500.0, 0.10),           # shipped exactly on DATE: out
        (0, DATE + 1, 200.0, 0.05),       # in: order 0 has 1000 + 190
        (1, DATE + 9, 9000.0, 0.00),      # its order is dated DATE: out
        (2, DATE + 3, 1190.0, 0.00),      # in: 1190, equal to order 0's
        (3, DATE + 3, 7000.0, 0.00),      # another segment's customer: out
        (4, DATE - 1, 800.0, 0.00),       # shipped before DATE: out
        (4, DATE + 2, 300.0, 0.10),       # in: 270
        (5, DATE + 40, 100.0, 0.00),      # in: 100
    ]
    return _by_hand(segments, custkeys, orderdates, lines)


def test_boundaries_equal_revenues_and_fewer_than_ten_groups(by_hand):
    tables, df = by_hand
    gen.LAST = tables
    params = {"segment": "BUILDING", "day": 15}
    got = q3.answer(q3.build(df, params).collect())
    want = q3.reference(tables, params)
    assert q3.mismatch(got, want) is None
    keys = tables.side["orders"]["o_orderkey"]
    # four groups; the two of revenue 1190 in the order of their dates
    assert want["l_orderkey"].tolist() == [keys[0], keys[2], keys[4],
                                           keys[5]]
    assert want["revenue"].tolist() == [1190.0, 1190.0, 270.0, 100.0]
    assert want["o_orderdate"].tolist() == [DATE - 10, DATE - 1, DATE - 30,
                                            DATE - 20]
    assert got["l_orderkey"].tolist() == want["l_orderkey"].tolist()
    assert got["revenue"].tolist() == want["revenue"].tolist()


@pytest.mark.parametrize("segment,rows", [("MACHINERY", 1),
                                          ("HOUSEHOLD", 0)])
def test_a_segment_with_one_customer_and_one_with_none(by_hand, segment,
                                                       rows):
    tables, df = by_hand
    gen.LAST = tables
    params = {"segment": segment, "day": 15}
    got = q3.answer(q3.build(df, params).collect())
    want = q3.reference(tables, params)
    assert q3.answer_rows(want) == rows == q3.answer_rows(got)
    assert q3.mismatch(got, want) is None
    if rows:
        assert got["revenue"].tolist() == [7000.0]


def test_mismatch_holds_keys_and_order_exactly_and_revenue_to_1e9(by_hand):
    tables, _ = by_hand
    want = q3.reference(tables, {"segment": "BUILDING", "day": 15})
    assert q3.mismatch(want, want) is None
    assert q3.REL_TOLERANCE == 1e-9

    def changed(**lanes):
        return {**{k: v.copy() for k, v in want.items()}, **lanes}
    swap = [1, 0, 2, 3]
    swapped = {k: v[swap] for k, v in want.items()}
    # equal revenues, other dates: the ORDER BY tells them apart
    assert "row 0" in q3.mismatch(swapped, want)
    same_day = changed(o_orderdate=np.array(
        [DATE - 10, DATE - 10, DATE - 30, DATE - 20], np.int32))
    assert q3.mismatch({k: v[swap] for k, v in same_day.items()},
                       same_day) is None
    # revenues apart by more than the tolerance do not swap
    apart = {**same_day,
             "revenue": np.array([1190.0, 1189.0, 270.0, 100.0])}
    assert "row 0" in q3.mismatch({k: v[swap] for k, v in apart.items()},
                                  apart)
    assert "rows" in q3.mismatch({k: v[:3] for k, v in want.items()}, want)
    off = want["revenue"] * np.array([1.0, 1.0, 1.0 + 1e-8, 1.0])
    assert "revenue of order" in q3.mismatch(changed(revenue=off), want)
    near = want["revenue"] * np.array([1.0, 1.0, 1.0 + 1e-11, 1.0])
    assert q3.mismatch(changed(revenue=near), want) is None
    assert "row 3" in q3.mismatch(changed(o_shippriority=np.array(
        [0, 0, 0, 1], np.int32)), want)
    assert "revenue" in q3.mismatch(changed(revenue=np.array(
        [1190.0, 1190.0, np.nan, 100.0])), want)


def test_every_segment_and_day_builds_one_set_of_programs(generated):
    """The five segments are 8, 9 and 10 bytes long and the dates move the
    joins' outputs: after the first call nothing is built (the harness
    warms with ONE call, and a program built inside the window makes the
    run not correct)."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    obs = CompileObservatory.get()
    _ask(generated, {"segment": "BUILDING", "day": 15})
    built = obs.snapshot()["builds"]
    for segment, day in (("AUTOMOBILE", 1), ("FURNITURE", 31),
                         ("HOUSEHOLD", 2), ("MACHINERY", 30)):
        got, want = _ask(generated, {"segment": segment, "day": day})
        assert q3.mismatch(got, want) is None
    assert obs.snapshot()["builds"] == built
