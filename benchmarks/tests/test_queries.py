"""Query and plain reference agree, through the engine at a tiny scale."""

import numpy as np
import pytest

from benchmarks.datagen import tpch_lineitem as gen
from benchmarks.harness import runner
from benchmarks.queries import q6, q18sub


@pytest.fixture(scope="module")
def lineitem():
    columns = gen.generate({"scale_factor": 0.02}, 77)
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    df = session.create_dataframe(
        runner.arrow_table(columns, gen.SCHEMA), num_partitions=1)
    return columns, df


@pytest.mark.parametrize("params", [
    {"year": 1994, "discount": 0.06, "quantity": 24},   # the 0.07 boundary
    {"year": 1997, "discount": 0.09, "quantity": 25},   # the 0.10 boundary
    {"year": 1993, "discount": 0.02, "quantity": 24},
])
def test_q6_equals_reference(lineitem, params):
    columns, df = lineitem
    got = q6.answer(q6.build(df, params).collect())
    want = q6.reference(columns, params)
    assert want > 0
    assert q6.mismatch(got, want) is None
    assert q6.mismatch(got * (1 + 1e-6), want) is not None


def test_q6_bounds_are_rounded_so_the_upper_discount_counts(lineitem):
    columns, _ = lineitem
    assert 0.06 + 0.01 != 0.07          # the trap
    assert q6.discount_bounds(0.06) == (0.05, 0.07)
    params = {"year": 1994, "discount": 0.06, "quantity": 24}
    d = columns["l_discount"]
    year = (columns["l_shipdate"] >= 8766) & (columns["l_shipdate"] < 9131)
    keep = year & (columns["l_quantity"] < 24)
    by_hand = sum(
        float(np.sum(columns["l_extendedprice"][keep & (d == v)] * v))
        for v in (0.05, 0.06, 0.07))
    assert q6.mismatch(q6.reference(columns, params), by_hand) is None
    dropped = float(np.sum(
        columns["l_extendedprice"][keep & (d == 0.07)] * 0.07))
    assert dropped > 0.1 * by_hand


@pytest.mark.parametrize("quantity", [250, 275])
def test_q18sub_equals_reference(lineitem, quantity):
    columns, df = lineitem
    params = {"quantity": quantity}
    got = q18sub.answer(q18sub.build(df, params).collect())
    want = q18sub.reference(columns, params)
    assert want.shape[0] > 0
    assert q18sub.mismatch(got, want) is None
    assert q18sub.mismatch(got[1:], want) is not None
    # the reference against a plain loop over the groups
    sums = {}
    for k, q in zip(columns["l_orderkey"].tolist(),
                    columns["l_quantity"].tolist()):
        sums[k] = sums.get(k, 0.0) + q
    assert sorted(k for k, s in sums.items() if s > quantity) == \
        want.tolist()


def test_least_bytes_are_the_columns_read():
    assert q6.least_bytes(1000, 1) == 1000 * 28 + 8
    assert q18sub.least_bytes(1000, 10) == 1000 * 16 + 80
