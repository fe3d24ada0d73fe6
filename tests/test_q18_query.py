"""TPC-H Q18 whole (benchmarks/queries/q18.py) on the engine's normal path,
held to its plain NumPy reference on the CPU backend at a tiny scale: a
left-semi join on an aggregate's HAVING output, LINEITEM read twice, an
18-byte string carried through two joins into a five-column group key, a
descending float key and a limit.  At SF0.02 a group's quantities add up
to 350 at most, so the thresholds are chosen for the three cases the
deployment's own (312..315 at SF5) cannot all show: the limit of 100
binds, it does not, and no order is large enough."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells, runner  # noqa: E402

BENCH = os.path.join(REPO, "benchmarks")
# the instances the harness would call: `q18.build` finds the tables
# through `cells.load_module`, which memoises by path
gen = cells.load_module(os.path.join(BENCH, "datagen", "tpch_q18_tables.py"))
q18 = cells.load_module(os.path.join(BENCH, "queries", "q18.py"))

SEED = 2**31 + 37
SF = 0.02
#: over 100 large orders, under 100, a handful, none
BINDS, FREE, FEW, NONE = 250, 262, 300, 350


@pytest.fixture(scope="module")
def generated():
    from spark_rapids_tpu.api.session import TpuSession
    columns = gen.generate({"scale_factor": SF}, SEED)
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    return columns, session.create_dataframe(
        runner.arrow_table(columns, gen.SCHEMA), num_partitions=1)


def _ask(generated, quantity):
    columns, df = generated
    gen.LAST = columns          # (another module may have generated since)
    params = {"quantity": quantity}
    got = q18.answer(q18.build(df, params).collect())
    return got, q18.reference(columns, params)


@pytest.mark.parametrize("quantity", [BINDS, 256, FREE, 280, FEW, NONE])
def test_q18_equals_reference(generated, quantity):
    got, want = _ask(generated, quantity)
    assert q18.mismatch(got, want) is None
    groups = len(q18.grouped(generated[0], {"quantity": quantity})[
        "o_orderkey"])
    assert q18.answer_rows(got) == min(groups, q18.LIMIT)
    assert np.all(np.diff(got["o_totalprice"]) <= 0)
    assert np.all(got["sum_quantity"] > quantity)
    # the name is the customer's, not a neighbour's
    assert got["c_name"].tolist() == [f"Customer#{k:09d}"
                                      for k in got["c_custkey"]]


def test_the_thresholds_make_the_three_cases(generated):
    columns, _ = generated
    groups = {q: len(q18.grouped(columns, {"quantity": q})["o_orderkey"])
              for q in (BINDS, FREE, FEW, NONE)}
    assert groups[BINDS] > q18.LIMIT > groups[FREE] > groups[FEW] > 0
    assert groups[NONE] == 0
    got, want = _ask(generated, NONE)
    assert q18.answer_rows(got) == 0 and q18.mismatch(got, want) is None


def test_the_plan_is_the_deployments_and_on_the_tpu_engine(generated):
    from spark_rapids_tpu.exec.base import CPU
    _, df = generated
    _ask(generated, FREE)
    plan = df.session.last_plan
    kinds = []
    plan.foreach(lambda e: kinds.append((type(e).__name__, e.placement)))
    assert [k for k, p in kinds if p == CPU] == ["DeviceToHostExec"]
    assert q18.joins_fault(plan) is None
    assert {"FilterExec", "TpuHashAggregateExec", "SortExec",
            "GlobalLimitExec"} <= {k for k, _ in kinds}
    joins = []
    plan.foreach(lambda e: joins.append(e)
                 if type(e).__name__ == "HashJoinExec" else None)
    by_how = {}
    for j in joins:
        by_how.setdefault(j.how, []).append(j)
    semi, = by_how["left_semi"]
    # ORDERS probes; the build is the HAVING output, masked: the filter
    # under the bare selection of the key hands up its flags
    assert semi.children[0].output_names[0] == "o_orderkey"
    assert [type(s).__name__ for s in semi.masked_sources()] == \
        ["NoneType", "ProjectExec"]
    # assumed.build_side: CUSTOMER builds under the semi join's output,
    # LINEITEM under the chain
    builds = sorted(tuple(j.children[1].output_names)
                    for j in by_how["inner"])
    assert builds == [("c_custkey", "c_name"),
                      ("l_orderkey", "l_quantity")]
    # LINEITEM is read twice and pinned once
    scans = []
    plan.foreach(lambda e: scans.append(e)
                 if type(e).__name__ == "LocalScanExec" else None)
    lineitem = [s for s in scans if s.output_names == list(q18.COLUMNS)]
    assert len(scans) == 4 and len(lineitem) == 2
    assert lineitem[0].pin_cache is lineitem[1].pin_cache
    assert len(lineitem[0].pin_cache) == 1


def test_a_plan_without_the_semi_join_fails_the_answer(generated,
                                                       monkeypatch):
    """`plan_must_hold` cannot ask for two kinds of `HashJoinExec` in
    one plan, so `answer` holds the join types itself."""
    _, df = generated
    table = q18.build(df, {"quantity": FREE}).collect()
    assert q18.answer(table)["o_orderkey"].shape[0] > 0
    monkeypatch.setattr(q18, "JOINS_MUST_HOLD",
                        {"left_semi": 1, "inner": 1, "left": 1})
    with pytest.raises(ValueError, match="HashJoinExec"):
        q18.answer(table)


def test_other_thresholds_reuse_the_programs(generated):
    """No program between parameter sets that leave the joins' outputs in
    their buckets and the answer's lanes in their fetch plan (the cell's
    four sets do: 23-58 orders; here 39-60)."""
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    obs = CompileObservatory.get()
    _ask(generated, FREE)
    builds = obs.snapshot()["builds"]
    for quantity in (260, 264, 266):
        got, want = _ask(generated, quantity)
        assert q18.mismatch(got, want) is None
        assert 30 < q18.answer_rows(got) < 70
    assert obs.snapshot()["builds"] == builds


def _copy(rows):
    return {name: lane.copy() for name, lane in rows.items()}


def test_mismatch_refuses_what_a_broken_join_would_answer(generated):
    columns, _ = generated
    params = {"quantity": FREE}
    want = q18.reference(columns, params)
    n = q18.answer_rows(want)
    assert q18.mismatch(_copy(want), want) is None
    # two rows swapped
    swapped = _copy(want)
    for lane in swapped.values():
        lane[[3, 4]] = lane[[4, 3]]
    assert "row 3" in q18.mismatch(swapped, want)
    # a line dropped before the last join: one sum is short
    large = want["o_orderkey"][5]
    line = int(np.searchsorted(columns["l_orderkey"], large))
    short = q18.reference(columns, params, drop_line=line)
    assert short["sum_quantity"][5] == want["sum_quantity"][5] - \
        columns["l_quantity"][line]
    assert "row 5" in q18.mismatch(short, want)
    # a semi join that gave an order twice; one that lost an order
    doubled = {name: np.concatenate([lane[:1], lane])[:n]
               for name, lane in want.items()}
    assert q18.mismatch(doubled, want) is not None
    assert "rows" in q18.mismatch(
        {name: lane[:-1] for name, lane in want.items()}, want)
    # a name gathered from the neighbouring customer
    wrong = _copy(want)
    wrong["c_name"][7] = f"Customer#{int(want['c_custkey'][7]) + 1:09d}"
    assert "row 7" in q18.mismatch(wrong, want)
    # a cent's difference; a millionth of a cent is the chip's rounding
    cent = _copy(want)
    cent["o_totalprice"][2] += 0.01
    assert "o_totalprice" in q18.mismatch(cent, want)
    near = _copy(want)
    near["o_totalprice"] *= 1.0 + 2.0 ** -48
    assert q18.mismatch(near, want) is None
    # the price kept in float32 is off by more than half a cent somewhere
    low = q18.reference(columns, params, price_dtype=np.float32)
    assert "o_totalprice" in q18.mismatch(low, want) or \
        "row" in q18.mismatch(low, want)


def test_equal_sort_keys_may_swap_and_nothing_else(generated):
    columns, _ = generated
    want = q18.reference(columns, {"quantity": FREE})
    tied = _copy(want)
    tied["o_totalprice"][4] = tied["o_totalprice"][3]
    tied["o_orderdate"][4] = tied["o_orderdate"][3]
    swapped = _copy(tied)
    for lane in swapped.values():
        lane[[3, 4]] = lane[[4, 3]]
    assert q18.mismatch(swapped, tied) is None
    tied["o_orderdate"][4] += 1          # the date tells them apart
    assert q18.mismatch(swapped, tied) is not None


def test_the_generator_follows_clause_4_2_3(generated):
    columns, _ = generated
    base = cells.load_module(os.path.join(BENCH, "datagen",
                                          "tpch_lineitem.py"))
    q1gen = cells.load_module(os.path.join(BENCH, "datagen",
                                           "tpch_lineitem_q1.py"))
    accepted = base.generate({"scale_factor": SF}, SEED)
    for name in gen.SCHEMA:
        assert np.array_equal(columns[name], accepted[name]), name
    orders, customer = columns.side["orders"], columns.side["customer"]
    # the total price: the order's lines' charge, to the cent
    tax = q1gen.generate({"scale_factor": SF}, SEED)["l_tax"]
    charge = accepted["l_extendedprice"] * (1 + tax) * \
        (1 - accepted["l_discount"])
    starts = gen.run_starts(accepted["l_orderkey"])
    assert len(starts) == len(orders["o_orderkey"])
    for i in (0, 17, len(starts) - 1):
        stop = starts[i + 1] if i + 1 < len(starts) else len(charge)
        assert orders["o_totalprice"][i] == pytest.approx(
            charge[starts[i]:stop].sum(), abs=0.00500001)
    cents = orders["o_totalprice"] * 100
    assert np.allclose(cents, np.rint(cents), atol=1e-6)
    # every o_custkey finds its c_name
    assert customer["c_name"][orders["o_custkey"][11] - 1] == \
        f"Customer#{orders['o_custkey'][11]:09d}"
    assert {len(s) for s in customer["c_name"].tolist()} == {18}
    assert not np.any(orders["o_custkey"] % 3 == 0)
