"""The pricing-summary deployment (PR 31): the generator with its three new
columns, Q1 against its plain reference at a tiny scale with the tolerances
of ``mismatch``, and the cell ``tpch_q1_1chip.q1`` with its two per-layer
metrics found by name and run end to end on the CPU backend (counts and
correctness only: no time here is a device time)."""

import json
import os

import numpy as np
import pytest

from benchmarks.datagen import tpch_lineitem as base
from benchmarks.datagen import tpch_lineitem_q1 as gen
from benchmarks.harness import cells, runner, traffic, device as dev
from benchmarks.queries import q1
from conftest import ROOT
from helpers import FakeDevice, add_entries, copy_root

SF = 0.02


@pytest.fixture(scope="module")
def table():
    return gen.generate({"scale_factor": SF}, 2**31 + 11)


def test_same_seed_same_table_and_the_shared_columns_are_the_accepted_ones(
        table):
    again = gen.generate({"scale_factor": SF}, 2**31 + 11)
    other = gen.generate({"scale_factor": SF}, 12)
    assert list(table) == list(gen.SCHEMA)
    for name in table:
        assert np.array_equal(table[name], again[name]), name
    assert any(table[n].shape != other[n].shape
               or not np.array_equal(table[n], other[n]) for n in table)
    accepted = base.generate({"scale_factor": SF}, 2**31 + 11)
    for name in ("l_quantity", "l_extendedprice", "l_discount",
                 "l_shipdate"):
        assert np.array_equal(table[name], accepted[name]), name
    assert "l_orderkey" not in table


def test_the_specs_ranges_and_the_four_groups(table):
    n = table["l_shipdate"].shape[0]
    assert {k: str(v.dtype) for k, v in table.items()} == {
        "l_quantity": "float64", "l_extendedprice": "float64",
        "l_discount": "float64", "l_tax": "float64", "l_shipdate": "int32",
        "l_returnflag": "<U1", "l_linestatus": "<U1"}
    assert all(v.shape == (n,) for v in table.values())
    tax = table["l_tax"]
    assert set(np.unique(tax)) == {h / 100.0 for h in range(9)}
    assert 0.07 in set(np.unique(tax))      # the same double as the literal
    ship, rf, ls = (table[c] for c in (
        "l_shipdate", "l_returnflag", "l_linestatus"))
    assert set(np.unique(rf)) == {"A", "N", "R"}
    assert set(np.unique(ls)) == {"F", "O"}
    # clause 4.2.3: 'O' exactly where the line shipped after CURRENTDATE;
    # a line received by then is 'R' or 'A', about half each, else 'N'
    assert np.array_equal(ls == "O", ship > gen.CURRENTDATE)
    assert not np.any((rf != "N") & (ship >= gen.CURRENTDATE))
    assert np.all(rf[ship > gen.CURRENTDATE] == "N")
    assert np.all(rf[ship <= gen.CURRENTDATE - 30] != "N")
    returned = rf != "N"
    assert abs(np.mean(rf[returned] == "R") - 0.5) < 0.01
    groups, counts = np.unique(np.char.add(rf, ls), return_counts=True)
    assert groups.tolist() == ["AF", "NF", "NO", "RF"]
    share = dict(zip(groups.tolist(), (counts / n).tolist()))
    assert 0.23 < share["AF"] < 0.27 and 0.23 < share["RF"] < 0.27
    assert 0.47 < share["NO"] < 0.52
    assert 0.002 < share["NF"] < 0.02          # the sliver: keep the skew
    # the harness makes Arrow strings of the flags, one byte a value
    arrow = runner.arrow_table(table, gen.SCHEMA)
    assert str(arrow.schema.field("l_returnflag").type) == "string"
    assert arrow.column("l_linestatus").chunk(0).buffers()[2].size == n


@pytest.fixture(scope="module")
def lineitem(table):
    from spark_rapids_tpu.api.session import TpuSession
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    df = session.create_dataframe(
        runner.arrow_table(table, gen.SCHEMA), num_partitions=1)
    return table, df


@pytest.mark.parametrize("delta", [60, 90, 120, 1300])
def test_q1_equals_reference(lineitem, delta):
    columns, df = lineitem
    params = {"delta": delta}
    got = q1.answer(q1.build(df, params).collect())
    want = q1.reference(columns, params)
    assert q1.mismatch(got, want) is None
    assert q1.answer_rows(got) == (2 if delta == 1300 else 4)
    assert q1.deviation(got, want) < 1e-12
    # the reference against a plain loop over the rows of one group
    cut = (q1.cut_date(delta) - q1._EPOCH).days
    i = q1.answer_rows(want) - 1
    rf, ls = want["keys"][i]
    rows = [j for j in range(len(columns["l_shipdate"]))
            if columns["l_shipdate"][j] <= cut
            and columns["l_returnflag"][j] == rf
            and columns["l_linestatus"][j] == ls]
    assert len(rows) == want["count_order"][i]
    charge = sum(float(columns["l_extendedprice"][j])
                 * (1.0 - float(columns["l_discount"][j]))
                 * (1.0 + float(columns["l_tax"][j])) for j in rows)
    assert abs(charge - want["sum_charge"][i]) < 1e-9 * charge
    assert abs(want["avg_qty"][i] * len(rows) - want["sum_qty"][i]) \
        < 1e-9 * want["sum_qty"][i]


def test_mismatch_holds_keys_order_and_counts_exactly_and_sums_to_1e9(
        lineitem):
    columns, _ = lineitem
    want = q1.reference(columns, {"delta": 90})
    assert q1.mismatch(want, want) is None
    assert q1.REL_TOLERANCE == 1e-9
    for name in q1.SUMS + q1.AVGS:
        inside = dict(want, **{name: want[name] * (1 + 5e-10)})
        outside = dict(want, **{name: want[name] * (1 + 2e-9)})
        assert q1.mismatch(inside, want) is None, name
        assert name in q1.mismatch(outside, want)
    counts = want["count_order"].copy()
    counts[0] += 1
    assert "count_order" in q1.mismatch(dict(want, count_order=counts), want)
    assert "order" in q1.mismatch(dict(want, keys=want["keys"][::-1]), want)
    assert q1.mismatch(dict(want, keys=want["keys"][:-1]), want)
    nan = dict(want, sum_qty=want["sum_qty"] * np.nan)
    assert "sum_qty" in q1.mismatch(nan, want)
    # the nearest precision below: float32 arithmetic is not correct
    low = q1.reference(columns, {"delta": 90}, dtype=np.float32)
    assert q1.mismatch(low, want) is not None
    assert q1.deviation(low, want) > 1e-7


def test_least_bytes_are_the_columns_read():
    assert q1.least_bytes(1000, 4) == 1000 * 38 + 4 * 80


def test_the_mix_walks_the_61_deltas_of_the_spec():
    with open(os.path.join(ROOT, "benchmarks", "traffic", "q1.json")) as f:
        mix = json.load(f)
    sets = traffic.parameter_sets(mix)
    assert [s["delta"] for s in sets] == list(range(60, 121))
    assert q1.cut_date(90).isoformat() == "1998-09-02"


@pytest.fixture()
def tiny_root(tmp_path):
    """The cell as ``BENCHMARK.json`` has it, at a scale the CPU can run:
    a copy of its configuration under another name, nothing else added."""
    root = copy_root(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "tpch_q1_1chip.json")) as f:
        config = json.load(f)
    config.update(name="tiny_q1", scale_factor=SF)
    rel = "benchmarks/configs/tiny_q1.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if "tpch_q1_1chip.q1" in m.get("workloads", ()):
            m["workloads"].append("tiny_q1.q1")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    add_entries(root, configs=[{
        "name": "tiny_q1", "source": "test", "file": rel, "reduced": [],
        "why": "tiny scale for the CPU"}], workloads=[{
            "name": "tiny_q1.q1", "config": "tiny_q1", "traffic": "q1",
            "chips": 1, "why": "t"}])
    return root


def test_the_accepted_cell_and_its_two_metrics_are_found_by_name():
    cell = cells.load_cell(ROOT, "tpch_q1_1chip.q1")
    assert cell.chips == 1 and cell.config["datagen"] == "tpch_lineitem_q1"
    assert cell.traffic["query"] == "q1"
    assert {"sort_device_ms_per_query", "gathered_lanes_built"} <= \
        set(cell.readers)
    assert "filter_device_ms_per_query" not in cell.readers
    for other in ("tpch_sf5_1chip.q6", "tpch_sf5_1chip.q18sub"):
        assert "gathered_lanes_built" not in \
            cells.load_cell(ROOT, other).readers
    need = cell.config["guarantees"]["plan_must_hold"]["q1"]
    assert [n["exec"] for n in need] == [
        "FilterExec", "TpuHashAggregateExec", "SortExec"]


def test_the_cell_runs_end_to_end_and_builds_nothing_in_its_window(
        tiny_root, monkeypatch):
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    cell = cells.load_cell(tiny_root, "tiny_q1.q1")
    bench = runner.Bench(cell, 2**31 + 3, trace=False)
    bench.load()
    import jax
    bench.warm_up(jax.devices()[:1])
    assert bench.problems == []
    # the pinned lanes pass the harness's floor: a byte a flag on the
    # device against four in the generated '<U1' columns
    need = sum(bench.columns[c].nbytes for c in q1.COLUMNS)
    assert bench.pinned_bytes >= need
    bench.window(6.0)
    bench.check(bench.asked, "window")
    assert bench.problems == [] and len(bench.asked) >= 1
    assert len({q.params["delta"] for q in bench.asked}) == len(bench.asked)
    facts = bench.facts([FakeDevice()])
    assert facts.builds_at_end == facts.builds_at_window
    layer = runner.per_layer(bench, facts)
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["programs_built"]["value"] >= 5   # filter, aggregate,
    #                                  sort, the fetch's two (and what
    #                                  earlier tests of this process built)
    assert layer["gathered_lanes_built"]["value"] == 0
    assert "sort_device_ms_per_query" not in layer   # no trace, no number
    assert "filter_device_ms_per_query" not in layer
    assert set(runner.end_to_end(bench, setup_s=1.0)) == {
        "answer_ms_p50", "queries_per_s", "setup_s"}
    plan = bench.session.last_plan
    assert bench.plan_fault(plan) is None
    scan_cols = dev.pinned_scan_arrays(plan)
    assert any(str(a.dtype) == "uint8" for a in scan_cols)
    # a plan without the sort breaks the deployment's guarantee
    cell.config["guarantees"]["plan_must_hold"]["q1"].append(
        {"exec": "IciAggregateExec"})
    assert "IciAggregateExec" in bench.plan_fault(plan)


def test_the_sort_metric_reads_the_program_named_after_sortexec():
    from benchmarks.harness.facts import RunFacts
    from benchmarks.harness.trace_reduce import ChipTime, TraceSummary
    from benchmarks.layer_metrics import (gathered_lanes_built,
                                          sort_device_ms_per_query)
    assert sort_device_ms_per_query.read(
        RunFacts("c", 1, "TPU v5 lite", 10, q1)) is None
    chip = ChipTime(index=0, busy_s=14.9, collective_s=0.0,
                    collective_exposed_s=0.0, op_self_s={}, program_s={
                        "jit_SortExec#1234": 0.003,
                        "jit_TpuHashAggregateExec.complete#9": 12.0,
                        "jit_SortExecutor#1": 5.0})
    summary = TraceSummary(window_s=15.0, window=(0.0, 15.0), chips=[chip],
                           idle_gaps=[])
    run = RunFacts("c", 1, "TPU v5 lite", 10, q1, trace=summary,
                   traced_times_ms=[5000.0, 5000.0, 5000.0])
    assert sort_device_ms_per_query.read(run) == pytest.approx(1.0)
    # nothing to read before a program is built; a count after
    got = gathered_lanes_built.read(run)
    assert got is None or got >= 0
