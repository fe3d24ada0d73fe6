"""The large-volume-customer deployment (PR 37): the generator's three
tables, the cell ``tpch_q18_1chip.q18`` found by name with its four
per-layer metrics, one run of ``run_cell``'s parts at a tiny scale on the
CPU backend, and the four readers on a hand-made ``RunFacts`` (counts and
correctness only: no time here is a device time).  Q18 against its
reference, the thresholds' three cases and ``mismatch`` are in
``tests/test_q18_query.py`` (tier-1).

The file's name sorts after ``test_q1_cell.py`` and ``test_q3_cell.py``:
their cells' tests read process-wide counters as they find them (gathered
string columns 0, two sizing fetches a query), and a Q18 run before them
in the same process raises both."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import cells, runner, traffic, device as dev
from conftest import ROOT
from helpers import FakeDevice, add_entries, copy_root

BENCH = os.path.join(ROOT, "benchmarks")
gen = cells.load_module(os.path.join(BENCH, "datagen", "tpch_q18_tables.py"))
q18 = cells.load_module(os.path.join(BENCH, "queries", "q18.py"))
base = cells.load_module(os.path.join(BENCH, "datagen", "tpch_lineitem.py"))
q1gen = cells.load_module(os.path.join(BENCH, "datagen",
                                       "tpch_lineitem_q1.py"))
q3gen = cells.load_module(os.path.join(BENCH, "datagen", "tpch_q3_tables.py"))

SF = 0.02
SEED = 2**31 + 11
OURS = {"semi_join_device_ms_per_query", "semi_join_hbm_roofline_share",
        "join_sorted_mslots_per_query", "string_cols_gathered_built"}


@pytest.fixture(scope="module")
def tables():
    return gen.generate({"scale_factor": SF}, SEED)


def test_lineitem_and_orders_are_the_accepted_generators(tables):
    accepted = base.generate({"scale_factor": SF}, SEED)
    assert list(tables) == list(gen.SCHEMA) == list(q18.COLUMNS)
    for name in gen.SCHEMA:
        assert np.array_equal(tables[name], accepted[name]), name
    q3 = q3gen.generate({"scale_factor": SF}, SEED)
    for name in ("o_orderkey", "o_custkey", "o_orderdate"):
        assert np.array_equal(tables.side["orders"][name],
                              q3.side["orders"][name]), name
    assert np.array_equal(tables.side["customer"]["c_custkey"],
                          q3.side["customer"]["c_custkey"])
    again = gen.generate({"scale_factor": SF}, SEED)
    other = gen.generate({"scale_factor": SF}, 12)
    for table in ("orders", "customer"):
        for name, lane in tables.side[table].items():
            assert np.array_equal(lane, again.side[table][name]), name
    assert not np.array_equal(tables.side["orders"]["o_totalprice"],
                              other.side["orders"]["o_totalprice"])
    assert gen.LAST is other


def test_totalprice_and_names_follow_clause_4_2_3(tables):
    orders, customer = tables.side["orders"], tables.side["customer"]
    n_orders, n_customers = int(SF * 1_500_000), int(SF * 150_000)
    assert {k: (str(v.dtype), v.shape) for k, v in orders.items()} == {
        "o_orderkey": ("int64", (n_orders,)),
        "o_custkey": ("int64", (n_orders,)),
        "o_orderdate": ("int32", (n_orders,)),
        "o_totalprice": ("float64", (n_orders,))}
    # the sum over the order's lines of price * (1 + tax) * (1 - discount),
    # in whole cents, by an independent walk of every order
    q1 = q1gen.generate({"scale_factor": SF}, SEED)
    charge = q1["l_extendedprice"] * (1.0 + q1["l_tax"]) * \
        (1.0 - q1["l_discount"])
    order_of = np.searchsorted(orders["o_orderkey"], tables["l_orderkey"])
    total = np.bincount(order_of, weights=charge, minlength=n_orders)
    assert np.max(np.abs(orders["o_totalprice"] - total)) <= 0.005 + 1e-9
    cents = orders["o_totalprice"] * 100.0
    assert np.max(np.abs(cents - np.rint(cents))) < 1e-6
    assert 800 < orders["o_totalprice"].min() and \
        orders["o_totalprice"].max() < 600_000
    # every o_custkey finds its c_name; 18 bytes each, no nulls, general
    assert np.array_equal(customer["c_custkey"],
                          np.arange(1, n_customers + 1))
    names = customer["c_name"]
    assert names[0] == "Customer#000000001" and \
        names[-1] == f"Customer#{n_customers:09d}"
    found = names[orders["o_custkey"] - 1]
    assert found[123] == f"Customer#{orders['o_custkey'][123]:09d}"
    arrow = runner.arrow_table(customer, gen.SIDE_SCHEMAS["customer"])
    assert str(arrow.schema.field("c_name").type) == "string"
    assert arrow.column("c_name").null_count == 0
    assert set(np.char.str_len(names).tolist()) == {18}
    from spark_rapids_tpu.columnar import device
    assert 18 > device.FIXED_WIDTH_MAX      # offsets and bytes, not a lane
    with pytest.raises(TypeError):
        runner.arrow_table({**orders, "o_totalprice": orders[
            "o_totalprice"].astype(np.float32)}, gen.SIDE_SCHEMAS["orders"])


def test_the_mix_walks_the_four_sets_of_the_spec():
    with open(os.path.join(BENCH, "traffic", "q18.json")) as f:
        mix = json.load(f)
    assert traffic.parameter_sets(mix) == [{"quantity": q}
                                           for q in (312, 313, 314, 315)]
    stream = traffic.parameter_stream(mix, 2**31 + 5)
    assert len({next(stream)["quantity"] for _ in range(4)}) == 4


def test_least_bytes_count_the_tables_once_and_the_semi_joins_live_rows(
        tables):
    gen.LAST = tables
    n = len(tables["l_orderkey"])
    n_orders = len(tables.side["orders"]["o_orderkey"])
    assert q18.least_bytes(n, 40) == n * 16 + n_orders * 28 + 2 * 40 * 58
    semi = q18.semi_join_least_bytes()
    # ORDERS' key once, and 8 + 2 x 28 B for every order kept, averaged
    # over the four thresholds (at this scale an order or none)
    _, sums = q18.order_sums(tables)
    kept = sum(int(np.count_nonzero(sums > q)) for q in (312, 313, 314, 315))
    assert semi == n_orders * 8 + kept * 64 / 4
    assert semi < q18.least_bytes(n, 40)


@pytest.fixture()
def tiny_root(tmp_path):
    """The cell as ``BENCHMARK.json`` has it, at a scale the CPU can run
    and with thresholds that leave something at that scale: a copy of its
    configuration and of its mix under other names, nothing else added."""
    root = copy_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_q18_1chip.json")) as f:
        config = json.load(f)
    config.update(name="tiny_q18", scale_factor=SF)
    rel = "benchmarks/configs/tiny_q18.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "benchmarks", "traffic", "q18.json")) as f:
        mix = json.load(f)
    mix.update(name="q18_tiny",
               parameters={"quantity": {"int_range": [260, 263]}})
    with open(os.path.join(root, "benchmarks", "traffic",
                           "q18_tiny.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if "tpch_q18_1chip.q18" in m.get("workloads", ()):
            m["workloads"].append("tiny_q18.q18_tiny")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_entries(root, configs=[{
        "name": "tiny_q18", "source": "test", "file": rel, "reduced": [],
        "why": "tiny scale for the CPU"}], workloads=[{
            "name": "tiny_q18.q18_tiny", "config": "tiny_q18",
            "traffic": "q18_tiny", "chips": 1, "why": "t"}])
    return root


def test_the_cell_and_its_four_metrics_are_found_by_name():
    cell = cells.load_cell(ROOT, "tpch_q18_1chip.q18")
    assert cell.chips == 1 and cell.config["datagen"] == "tpch_q18_tables"
    assert cell.traffic["query"] == "q18"
    assert cell.config["session_conf"] == {"spark.rapids.sql.enabled": True}
    assert OURS <= set(cell.readers)
    # what the accepted metrics with a list keep to their cells
    for theirs in ("join_device_ms_per_query", "filter_device_ms_per_query",
                   "sort_device_ms_per_query"):
        assert theirs not in cell.readers
    for other in ("tpch_sf5_1chip.q18sub", "tpch_q3_1chip.q3"):
        assert not OURS & set(cells.load_cell(ROOT, other).readers)
    need = cell.config["guarantees"]["plan_must_hold"]["q18"]
    assert [n["exec"] for n in need] == [
        "HashJoinExec", "FilterExec", "TpuHashAggregateExec", "SortExec",
        "GlobalLimitExec"]
    assert cell.query.JOINS_MUST_HOLD == {"left_semi": 1, "inner": 2}
    assert cell.config["side_tables"]["orders"]["columns"] == \
        gen.SIDE_SCHEMAS["orders"]
    assert cell.config["side_tables"]["customer"]["columns"] == \
        gen.SIDE_SCHEMAS["customer"]
    assert cell.config["columns"] == gen.SCHEMA
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "tpch_q18_1chip")
    assert entry["source"] == cell.config["source"]


def test_the_cell_runs_end_to_end_and_builds_nothing_in_its_window(
        tiny_root, monkeypatch):
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    from spark_rapids_tpu.obs import metrics

    def total(counter):
        return sum(f.total() for f in metrics.registry().families()
                   if f.name == counter)

    def uploaded():
        return total("tpu_upload_bytes_total")
    fetches = total("tpu_join_sizing_fetches_total")
    slots = total("tpu_join_sorted_slots_total")
    cell = cells.load_cell(tiny_root, "tiny_q18.q18_tiny")
    bench = runner.Bench(cell, 2**31 + 3, trace=False)
    bench.load()
    # the hand-off: the reference reads the arrays `build` uploads
    made = cell.datagen.LAST
    assert made is bench.columns and set(made.side) == {"orders",
                                                        "customer"}
    import jax
    before = uploaded()
    bench.warm_up(jax.devices()[:1])
    assert bench.problems == []
    first_call = uploaded() - before
    orders, customer = cell.query.side_frames(bench.df)
    assert cell.query.side_frames(bench.df)[0] is orders    # made once
    # three scans pinned, LINEITEM once though the plan reads it twice
    need = sum(bench.columns[c].nbytes for c in cell.query.COLUMNS) + sum(
        lane.nbytes for lane in made.side["orders"].values()) + \
        made.side["customer"]["c_custkey"].nbytes
    assert bench.pinned_bytes >= need
    assert first_call == bench.pinned_bytes     # every lane once
    bench.window(6.0)
    bench.check(bench.asked, "window")
    assert bench.problems == [] and len(bench.asked) >= 2
    assert uploaded() - before == first_call      # nothing uploaded again
    facts = bench.facts([FakeDevice()])
    assert facts.builds_at_end == facts.builds_at_window
    assert all(30 < rows < 100 for rows in facts.answer_rows)
    layer = runner.per_layer(bench, facts)
    assert layer["compiles_in_window"]["value"] == 0
    # the semi join sizes nothing; each inner join asks for its sizes once
    # (`join_sizing_fetches_per_query` keeps to `.q3`; the counter is the
    # process's, so the difference over this run is read here)
    asked = len(bench.asked) + 1
    assert total("tpu_join_sizing_fetches_total") - fetches == 2 * asked
    # three counts a query, each over its two sides' capacities
    plan = bench.session.last_plan
    assert sorted(j.how for j in dev.plan_execs(plan, "HashJoinExec")) == \
        ["inner", "inner", "left_semi"]
    slots = total("tpu_join_sorted_slots_total") - slots
    assert slots % asked == 0 and slots // asked >= 3 * 2 * 1024
    assert layer["join_sorted_mslots_per_query"]["value"] >= \
        slots / asked / 1e6
    # c_name: through two expansions, a grouped aggregate and the sorts
    assert layer["string_cols_gathered_built"]["value"] >= 4
    assert "semi_join_device_ms_per_query" not in layer   # no trace
    assert "semi_join_hbm_roofline_share" not in layer
    assert set(runner.end_to_end(bench, setup_s=1.0)) == {
        "answer_ms_p50", "queries_per_s", "setup_s"}
    assert bench.plan_fault(plan) is None
    assert cell.query.joins_fault(plan) is None
    assert len(dev.plan_execs(plan, "LocalScanExec")) == 4
    # a plan of other joins is a failed query
    monkeypatch.setattr(cell.query, "JOINS_MUST_HOLD", {"inner": 3})
    q = bench.ask({"quantity": 261})
    assert "HashJoinExec" in q.error


def test_the_four_readers_on_hand_made_facts(tables):
    from benchmarks.harness.facts import RunFacts
    from benchmarks.harness.trace_reduce import ChipTime, TraceSummary
    from benchmarks.layer_metrics import (join_sorted_mslots_per_query,
                                          semi_join_device_ms_per_query,
                                          semi_join_hbm_roofline_share,
                                          string_cols_gathered_built)
    gen.LAST = tables
    bare = RunFacts("c", 1, "TPU v5 lite", 10, q18)
    assert semi_join_device_ms_per_query.read(bare) is None
    assert semi_join_hbm_roofline_share.read(bare) is None
    chip = ChipTime(index=0, busy_s=24.0, collective_s=0.0,
                    collective_exposed_s=0.0, op_self_s={}, program_s={
                        "jit_HashJoinExec.semi_count#1234": 8.4,
                        "jit_HashJoinExec.semi#77": 0.6,
                        "jit_HashJoinExec.count#5": 7.0,
                        "jit_HashJoinExec.semifinal#1": 5.0,
                        "jit_FilterExec.mask#9": 0.1})
    summary = TraceSummary(window_s=24.1, window=(0.0, 24.1), chips=[chip],
                           idle_gaps=[])
    run = RunFacts("c", 1, "TPU v5 lite", 10, q18, trace=summary,
                   traced_times_ms=[8000.0, 8000.0, 8000.0],
                   times_ms=[8000.0] * 5)
    assert semi_join_device_ms_per_query.read(run) == pytest.approx(3000.0)
    share = semi_join_hbm_roofline_share.read(run)
    assert share == pytest.approx(
        100.0 * q18.semi_join_least_bytes() / 819e9 / 3.0)
    assert 0 < share < 100
    # an engine that selects eagerly names no such program: nothing read
    chip.program_s = {"jit_HashJoinExec.count#5": 7.0}
    assert semi_join_device_ms_per_query.read(run) is None
    assert semi_join_hbm_roofline_share.read(run) is None
    # a query module that counts no semi join bytes: nothing to read
    chip.program_s = {"jit_HashJoinExec.semi#77": 0.6}
    from benchmarks.queries import q3
    assert semi_join_hbm_roofline_share.read(
        RunFacts("c", 1, "TPU v5 lite", 10, q3, trace=summary,
                 traced_times_ms=[5000.0])) is None
    # the counters over the window's queries and the warm-up call
    from spark_rapids_tpu.obs import metrics
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    got = join_sorted_mslots_per_query.read(run)
    total = sum(f.total() for f in metrics.registry().families()
                if f.name == "tpu_join_sorted_slots_total")
    assert got is None and total == 0 or \
        got == pytest.approx(total / 6 / 1e6)
    programs = CompileObservatory.get().snapshot()["programs"]
    built = string_cols_gathered_built.read(run)
    if any("join_string_cols_gathered" in p for p in programs):
        assert built == sum(p.get("string_cols_gathered", 0) + p.get(
            "join_string_cols_gathered", 0) for p in programs)
    else:
        assert built is None
