"""TPC-H Q1 (`benchmarks/queries/q1.py`) through `TpuSession` at a small
scale: two `char(1)` group keys held as fixed-width strings, eight
aggregates over projections and an ORDER BY, against the benchmark's plain
NumPy reference and against the CPU engine."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.datagen import tpch_lineitem_q1 as gen  # noqa: E402
from benchmarks.harness import runner  # noqa: E402
from benchmarks.queries import q1  # noqa: E402
from spark_rapids_tpu.api.session import TpuSession  # noqa: E402
from spark_rapids_tpu.obs.compileprof import CompileObservatory  # noqa: E402

SEEDS = [5, 2**31 + 77]
#: the spec's range ends and middle; 1300 days before 1998-12-01 lies
#: before CURRENTDATE, so the filter empties (N,O) and (N,F)
DELTAS = [60, 90, 120, 1300]


@pytest.fixture(scope="module", params=SEEDS)
def lineitem(request):
    columns = gen.generate({"scale_factor": 0.004}, request.param)
    return columns, runner.arrow_table(columns, gen.SCHEMA)


def _frame(table, enabled: bool):
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", enabled).get_or_create()
    return session, session.create_dataframe(table, num_partitions=1)


@pytest.mark.parametrize("delta", DELTAS)
def test_q1_equals_the_reference_and_the_cpu_engine(lineitem, delta):
    columns, table = lineitem
    params = {"delta": delta}
    session, df = _frame(table, True)
    got = q1.answer(q1.build(df, params).collect())
    plan = session.last_plan
    want = q1.reference(columns, params)
    assert q1.mismatch(got, want) is None
    assert q1.answer_rows(got) == (2 if delta == 1300 else 4)
    assert got["keys"] == sorted(got["keys"])
    _, cpu_df = _frame(table, False)
    cpu = q1.answer(q1.build(cpu_df, params).collect())
    assert q1.mismatch(cpu, want) is None
    assert q1.mismatch(got, cpu) is None
    # every operator but the fetch ran on the device engine
    placed = []
    plan.foreach(lambda e: placed.append((type(e).__name__, e.placement)))
    assert [n for n, p in placed if p == "cpu"] == ["DeviceToHostExec"]
    assert {"FilterExec", "TpuHashAggregateExec", "SortExec"} <= \
        {n for n, _ in placed}


def test_q1_moves_no_lane_by_gather_and_sorts_the_bounded_output(lineitem):
    _, table = lineitem
    session, df = _frame(table, True)
    q1.build(df, {"delta": 75}).collect()
    built = CompileObservatory.get().snapshot()["programs"]
    programs = {p["exec"]: p for p in built
                if p["exec"] in ("TpuHashAggregateExec", "SortExec")
                and p.get("string_cols_row_aligned") == 2}
    assert set(programs) == {"TpuHashAggregateExec", "SortExec"}
    for p in programs.values():
        assert p["lane_moves_gathered"] == 0
        assert p["string_cols_gathered"] == 0
    # the filter lies directly under the aggregate: it hands up its keep
    # flags and moves none of the fourteen lanes (ten passes before)
    nodes = []
    session.last_plan.foreach(nodes.append)
    aggregate = next(e for e in nodes
                     if type(e).__name__ == "TpuHashAggregateExec")
    assert type(aggregate.masked_source()).__name__ == "FilterExec"
    masks = [p for p in built
             if p["exec"] == "FilterExec" and p.get("filters_masked")]
    assert masks
    for p in masks:
        assert p["filters_compacted"] == 0 and p["sort_passes"] == 0
        assert p["lane_moves_sorted"] == 0 == p["lane_moves_gathered"]
        assert p["string_cols_row_aligned"] == 0 == p["string_cols_gathered"]


def test_the_tolerance_catches_float32_arithmetic_and_a_dropped_line(
        lineitem):
    columns, _ = lineitem
    params = {"delta": 90}
    want = q1.reference(columns, params)
    assert q1.mismatch(want, want) is None
    low = q1.reference(columns, params, dtype=np.float32)
    assert low["keys"] == want["keys"]
    assert q1.mismatch(low, want) is not None
    assert q1.deviation(low, want) > 100 * q1.REL_TOLERANCE
    short = {k: v[:-1] for k, v in columns.items()}
    assert q1.mismatch(q1.reference(short, params), want) is not None
    swapped = dict(want, keys=want["keys"][::-1])
    assert "order" in q1.mismatch(swapped, want)
    nudged = dict(want, sum_charge=want["sum_charge"] * (1 + 1e-8))
    assert "sum_charge" in q1.mismatch(nudged, want)
    assert q1.REL_TOLERANCE <= 1e-7
