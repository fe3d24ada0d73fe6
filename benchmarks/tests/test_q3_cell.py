"""The shipping-priority deployment (PR 33): the generator's three tables,
the hand-off that gives ``build`` the arrays the reference sees, the cell
``tpch_q3_1chip.q3`` found by name with its three per-layer metrics, one
run of ``run_cell``'s parts at a tiny scale on the CPU backend, and the
three readers on a hand-made ``RunFacts`` (counts and correctness only: no
time here is a device time).  Q3 against its reference, the boundary cases
and ``mismatch`` are in ``tests/test_q3_query.py`` (tier-1)."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import cells, runner, traffic, device as dev
from conftest import ROOT
from helpers import FakeDevice, add_entries, copy_root

BENCH = os.path.join(ROOT, "benchmarks")
gen = cells.load_module(os.path.join(BENCH, "datagen", "tpch_q3_tables.py"))
q3 = cells.load_module(os.path.join(BENCH, "queries", "q3.py"))
base = cells.load_module(os.path.join(BENCH, "datagen", "tpch_lineitem.py"))

SF = 0.02
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def tables():
    return gen.generate({"scale_factor": SF}, SEED)


def test_lineitem_is_the_accepted_generators_row_for_row(tables):
    accepted = base.generate({"scale_factor": SF}, SEED)
    assert list(tables) == list(gen.SCHEMA) == list(q3.COLUMNS)
    for name in gen.SCHEMA:
        assert np.array_equal(tables[name], accepted[name]), name
    assert "l_quantity" not in tables
    again = gen.generate({"scale_factor": SF}, SEED)
    other = gen.generate({"scale_factor": SF}, 12)
    for table in ("orders", "customer"):
        for name, lane in tables.side[table].items():
            assert np.array_equal(lane, again.side[table][name]), name
    assert not np.array_equal(tables.side["orders"]["o_custkey"],
                              other.side["orders"]["o_custkey"])
    assert gen.LAST is other


def test_orders_and_customer_follow_clause_4_2_3(tables):
    orders, customer = tables.side["orders"], tables.side["customer"]
    n_orders, n_customers = int(SF * 1_500_000), int(SF * 150_000)
    assert {k: (str(v.dtype), v.shape) for k, v in orders.items()} == {
        "o_orderkey": ("int64", (n_orders,)),
        "o_custkey": ("int64", (n_orders,)),
        "o_orderdate": ("int32", (n_orders,)),
        "o_shippriority": ("int32", (n_orders,))}
    assert np.array_equal(orders["o_orderkey"],
                          base.sparse_orderkeys(0, n_orders))
    assert np.array_equal(np.unique(tables["l_orderkey"]),
                          orders["o_orderkey"])
    # the order date is the one the lines' ship dates were drawn from
    lead = tables["l_shipdate"] - orders["o_orderdate"][
        q3.order_index(tables["l_orderkey"])]
    assert lead.min() == 1 and lead.max() == 121
    assert orders["o_orderdate"].min() >= base.STARTDATE
    assert orders["o_orderdate"].max() <= base.LAST_ORDERDATE
    # a third of the customers have no order
    custkey = orders["o_custkey"]
    assert custkey.min() >= 1 and custkey.max() <= n_customers
    assert not np.any(custkey % 3 == 0)
    assert len(np.unique(custkey)) > 0.6 * n_customers
    assert not orders["o_shippriority"].any()
    assert np.array_equal(customer["c_custkey"],
                          np.arange(1, n_customers + 1))
    segments, counts = np.unique(customer["c_mktsegment"],
                                 return_counts=True)
    assert segments.tolist() == sorted(gen.SEGMENTS)
    assert np.all(np.abs(counts / n_customers - 0.2) < 0.03)
    # Arrow strings at their own lengths, no nulls: the general layout
    arrow = q3.side_table("customer", customer)
    assert str(arrow.schema.field("c_mktsegment").type) == "string"
    assert arrow.column("c_mktsegment").null_count == 0
    assert sorted({len(s) for s in gen.SEGMENTS}) == [8, 9, 10]
    assert str(q3.side_table("orders", orders).schema.field(
        "o_orderdate").type) == "date32[day]"
    with pytest.raises(TypeError):
        q3.side_table("orders", {**orders, "o_shippriority":
                                 orders["o_shippriority"].astype(np.int64)})


def test_the_mix_walks_the_155_sets_of_the_spec():
    with open(os.path.join(BENCH, "traffic", "q3.json")) as f:
        mix = json.load(f)
    sets = traffic.parameter_sets(mix)
    assert len(sets) == 155
    assert {s["segment"] for s in sets} == set(gen.SEGMENTS)
    assert {s["day"] for s in sets} == set(range(1, 32))
    assert q3.cut_date({"day": 31}).isoformat() == "1995-03-31"


def test_least_bytes_count_the_ten_columns_and_the_joins_live_rows(tables):
    gen.LAST = tables
    side = tables.side
    n = len(tables["l_orderkey"])
    chars = sum(len(s) for s in side["customer"]["c_mktsegment"].tolist())
    assert q3.least_bytes(n, 10) == n * 28 + len(
        side["orders"]["o_orderkey"]) * 24 + len(
        side["customer"]["c_custkey"]) * 12 + chars + 10 * 24
    joins = q3.join_least_bytes()
    assert 0.3 * q3.least_bytes(n, 10) < joins < q3.least_bytes(n, 10)


@pytest.fixture()
def tiny_root(tmp_path):
    """The cell as ``BENCHMARK.json`` has it, at a scale the CPU can run:
    a copy of its configuration under another name, nothing else added."""
    root = copy_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_q3_1chip.json")) as f:
        config = json.load(f)
    config.update(name="tiny_q3", scale_factor=SF)
    rel = "benchmarks/configs/tiny_q3.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if "tpch_q3_1chip.q3" in m.get("workloads", ()):
            m["workloads"].append("tiny_q3.q3")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_entries(root, configs=[{
        "name": "tiny_q3", "source": "test", "file": rel, "reduced": [],
        "why": "tiny scale for the CPU"}], workloads=[{
            "name": "tiny_q3.q3", "config": "tiny_q3", "traffic": "q3",
            "chips": 1, "why": "t"}])
    return root


def test_the_cell_and_its_three_metrics_are_found_by_name():
    cell = cells.load_cell(ROOT, "tpch_q3_1chip.q3")
    assert cell.chips == 1 and cell.config["datagen"] == "tpch_q3_tables"
    assert cell.traffic["query"] == "q3"
    assert cell.config["session_conf"] == {"spark.rapids.sql.enabled": True}
    ours = {"join_device_ms_per_query", "join_hbm_roofline_share",
            "join_sizing_fetches_per_query"}
    assert ours <= set(cell.readers)
    assert "filter_device_ms_per_query" not in cell.readers
    for other in ("tpch_sf5_1chip.q6", "tpch_q1_1chip.q1"):
        assert not ours & set(cells.load_cell(ROOT, other).readers)
    need = cell.config["guarantees"]["plan_must_hold"]["q3"]
    assert need[0] == {"exec": "HashJoinExec", "how": "inner"}
    assert [n["exec"] for n in need[1:]] == [
        "FilterExec", "TpuHashAggregateExec", "SortExec", "GlobalLimitExec"]
    assert cell.config["side_tables"]["orders"]["columns"] == \
        gen.SIDE_SCHEMAS["orders"]
    assert cell.config["side_tables"]["customer"]["columns"] == \
        gen.SIDE_SCHEMAS["customer"]
    assert cell.config["columns"] == gen.SCHEMA


def test_the_cell_runs_end_to_end_and_builds_nothing_in_its_window(
        tiny_root, monkeypatch):
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    cell = cells.load_cell(tiny_root, "tiny_q3.q3")
    bench = runner.Bench(cell, 2**31 + 3, trace=False)
    bench.load()
    # the hand-off: the reference reads the arrays `build` uploads
    made = cell.datagen.LAST
    assert made is bench.columns and set(made.side) == {"orders",
                                                        "customer"}
    import jax
    bench.warm_up(jax.devices()[:1])
    assert bench.problems == []
    orders, customer = cell.query.side_frames(bench.df)
    assert cell.query.side_frames(bench.df)[0] is orders    # made once
    assert orders.collect().column("o_custkey").to_numpy().tolist() == \
        made.side["orders"]["o_custkey"].tolist()
    # three scans pinned: at least the ten columns' bytes (the strings'
    # four-byte code points in NumPy are more than their bytes on the chip)
    need = sum(bench.columns[c].nbytes for c in cell.query.COLUMNS) + sum(
        lane.nbytes for name, lane in made.side["orders"].items()) + \
        made.side["customer"]["c_custkey"].nbytes
    assert bench.pinned_bytes >= need
    bench.window(8.0)
    bench.check(bench.asked, "window")
    assert bench.problems == [] and len(bench.asked) >= 2
    assert len({(q.params["segment"], q.params["day"])
                for q in bench.asked}) == len(bench.asked)
    facts = bench.facts([FakeDevice()])
    assert facts.builds_at_end == facts.builds_at_window
    assert set(facts.answer_rows) == {10}
    layer = runner.per_layer(bench, facts)
    assert layer["compiles_in_window"]["value"] == 0
    # each of the two joins asks for its sizes once a query
    assert layer["join_sizing_fetches_per_query"]["value"] == \
        pytest.approx(2.0)
    assert "join_device_ms_per_query" not in layer   # no trace, no number
    assert "join_hbm_roofline_share" not in layer
    assert set(runner.end_to_end(bench, setup_s=1.0)) == {
        "answer_ms_p50", "queries_per_s", "setup_s"}
    plan = bench.session.last_plan
    assert bench.plan_fault(plan) is None
    assert len(dev.plan_execs(plan, "HashJoinExec")) == 2
    assert len(dev.plan_execs(plan, "LocalScanExec")) == 3
    cell.config["guarantees"]["plan_must_hold"]["q3"][0]["how"] = "left"
    assert "HashJoinExec.how" in bench.plan_fault(plan)


def test_the_join_metrics_read_the_programs_named_after_hashjoinexec(
        tables):
    from benchmarks.harness.facts import RunFacts
    from benchmarks.harness.trace_reduce import ChipTime, TraceSummary
    from benchmarks.layer_metrics import (join_device_ms_per_query,
                                          join_hbm_roofline_share,
                                          join_sizing_fetches_per_query)
    gen.LAST = tables
    bare = RunFacts("c", 1, "TPU v5 lite", 10, q3)
    assert join_device_ms_per_query.read(bare) is None
    assert join_hbm_roofline_share.read(bare) is None
    chip = ChipTime(index=0, busy_s=14.9, collective_s=0.0,
                    collective_exposed_s=0.0, op_self_s={}, program_s={
                        "jit_HashJoinExec.count#1234": 9.0,
                        "jit_HashJoinExec.expand#77": 2.7,
                        "jit_HashJoinExecutor#1": 5.0,
                        "jit_FilterExec#9": 2.0})
    summary = TraceSummary(window_s=15.0, window=(0.0, 15.0), chips=[chip],
                           idle_gaps=[])
    run = RunFacts("c", 1, "TPU v5 lite", 10, q3, trace=summary,
                   traced_times_ms=[5000.0, 5000.0, 5000.0],
                   times_ms=[5000.0] * 9)
    assert join_device_ms_per_query.read(run) == pytest.approx(3900.0)
    share = join_hbm_roofline_share.read(run)
    assert share == pytest.approx(
        100.0 * q3.join_least_bytes() / 819e9 / 3.9)
    assert 0 < share < 100
    # a query module that counts no join bytes: nothing to read
    from benchmarks.queries import q1
    assert join_hbm_roofline_share.read(
        RunFacts("c", 1, "TPU v5 lite", 10, q1, trace=summary,
                 traced_times_ms=[5000.0])) is None
    # the counter over the window's queries and the warm-up call
    from spark_rapids_tpu.obs import metrics
    got = join_sizing_fetches_per_query.read(run)
    total = sum(f.total() for f in metrics.registry().families()
                if f.name == "tpu_join_sizing_fetches_total")
    assert got is None and total == 0 or got == pytest.approx(total / 10)
