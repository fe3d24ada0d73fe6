"""A configuration, a mix, a query and a per-layer metric are each added as
new files plus new entries, and the harness finds them by name and runs
them end to end (tiny scale, CPU backend: counts and correctness only)."""

import json
import os
import textwrap

import pytest

from benchmarks.harness import cells, runner, traffic, device as dev
from helpers import FakeDevice, add_entries, add_tiny_config, copy_root


def write(root, rel, text):
    path = os.path.join(root, rel)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text))


@pytest.fixture()
def grown_root(tmp_path):
    root = copy_root(tmp_path)
    before = {p: os.path.getmtime(os.path.join(d, p))
              for d, _, fs in os.walk(os.path.join(root, "benchmarks"))
              for p in fs}
    configs = [add_tiny_config(root, "tiny_1chip", 1),
               add_tiny_config(root, "tiny_4chip", 4)]
    # a new mix over a query that is there (data only), with low
    # thresholds: tiny tables have no order above 312
    write(root, "benchmarks/traffic/q18sub_low.json", json.dumps({
        "name": "q18sub_low", "query": "q18sub", "loop": "closed",
        "clients": 1, "trace_queries": 1,
        "parameters": {"quantity": {"int_range": [250, 253]}}}))
    # a new query with a mix of its own
    write(root, "benchmarks/queries/count_big.py", '''
        import numpy as np
        COLUMNS = ("l_quantity",)
        def build(df, params):
            from spark_rapids_tpu.api import functions as F
            from spark_rapids_tpu.api.column import col, lit
            return (df.filter(col("l_quantity") > lit(float(params["q"])))
                    .agg(F.count("*").alias("n")))
        def answer(table):
            return int(table.column("n")[0].as_py())
        def reference(columns, params):
            return int(np.sum(columns["l_quantity"] > params["q"]))
        def mismatch(got, want):
            return None if got == want else f"{got} != {want}"
        def answer_rows(got):
            return 1
        def least_bytes(n_rows, out_rows):
            return n_rows * 8 + 8
        ''')
    write(root, "benchmarks/traffic/count_big.json", json.dumps({
        "name": "count_big", "query": "count_big", "loop": "closed",
        "clients": 1, "trace_queries": 1,
        "parameters": {"q": {"values": [10, 20, 30]}}}))
    # a new per-layer metric
    write(root, "benchmarks/layer_metrics/answer_rows_median.py", '''
        from benchmarks.harness.stats import median
        def read(run):
            return median(run.answer_rows) if run.answer_rows else None
        ''')
    add_entries(root, configs=configs, workloads=[
        {"name": "tiny_1chip.q6", "config": "tiny_1chip", "traffic": "q6",
         "chips": 1, "why": "t"},
        {"name": "tiny_1chip.q18sub_low", "config": "tiny_1chip",
         "traffic": "q18sub_low", "chips": 1, "why": "t"},
        {"name": "tiny_4chip.q18sub_low", "config": "tiny_4chip",
         "traffic": "q18sub_low", "chips": 4, "why": "t"},
        {"name": "tiny_1chip.count_big", "config": "tiny_1chip",
         "traffic": "count_big", "chips": 1, "why": "t"}],
        per_layer=[{"name": "answer_rows_median", "unit": "rows",
                    "better": "lower", "source": "program_counter",
                    "layer": "operators", "moves": "answer_ms_p50",
                    "workloads": ["tiny_1chip.count_big"]},
                   # the exchange's reader is there, its entry comes with
                   # the four-chip cell
                   {"name": "collective_ms_per_query", "unit": "ms",
                    "better": "lower", "source": "device_trace",
                    "layer": "exchange", "moves": "answer_ms_p50",
                    "workloads": ["tiny_4chip.q18sub_low"]}])
    after = {p: os.path.getmtime(os.path.join(d, p))
             for d, _, fs in os.walk(os.path.join(root, "benchmarks"))
             for p in fs}
    assert all(after[p] == t for p, t in before.items())   # none edited
    return root


def run(root, workload, seconds=0.5, seed=2**31 + 3, monkeypatch=None):
    cell = cells.load_cell(root, workload)
    bench = runner.Bench(cell, seed, trace=False)
    bench.load()
    import jax
    devices = jax.devices()[:cell.chips]
    bench.warm_up(devices)
    assert bench.problems == []
    bench.window(seconds)
    bench.check(bench.asked, "window")
    return cell, bench


@pytest.mark.parametrize("workload,n_chips", [
    ("tiny_1chip.q6", 1), ("tiny_1chip.q18sub_low", 1),
    ("tiny_4chip.q18sub_low", 4), ("tiny_1chip.count_big", 1)])
def test_added_cell_runs_end_to_end(grown_root, monkeypatch, workload,
                                    n_chips):
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    cell, bench = run(grown_root, workload)
    assert cell.chips == n_chips
    assert bench.problems == []
    assert len(bench.asked) >= 1 and all(q.error is None
                                         for q in bench.asked)
    facts = bench.facts([FakeDevice()] * n_chips)
    # literals are hoisted: the window builds nothing
    assert facts.builds_at_end == facts.builds_at_window
    layer = runner.per_layer(bench, facts)
    assert layer["compiles_in_window"]["value"] == 0
    assert layer["programs_built"]["value"] >= 1
    assert layer["fetch_crossings_per_query"]["value"] >= 1
    assert "device_ms_per_query" not in layer      # no trace, no number
    assert ("answer_rows_median" in layer) == \
        (workload == "tiny_1chip.count_big")
    assert "collective_ms_per_query" not in layer
    e2e = runner.end_to_end(bench, setup_s=1.0)
    assert set(e2e) == {"answer_ms_p50", "queries_per_s", "setup_s"}
    if n_chips == 4:
        plan = bench.session.last_plan
        assert dev.plan_execs(plan, "IciAggregateExec")


def test_a_wrong_answer_a_cpu_operator_and_a_missing_stage_fail(
        grown_root, monkeypatch):
    cell, bench = run(grown_root, "tiny_1chip.q18sub_low")
    plan = bench.session.last_plan
    assert bench.plan_fault(plan) is None
    cell.config["guarantees"]["cpu_ops_allowed"] = []
    assert "DeviceToHostExec" in bench.plan_fault(plan)
    cell.config["guarantees"]["cpu_ops_allowed"] = ["DeviceToHostExec"]
    cell.config["guarantees"]["plan_must_hold"] = {
        "q18sub": [{"exec": "IciAggregateExec", "stage_input_devices": 4}]}
    assert "IciAggregateExec" in bench.plan_fault(plan)
    q = bench.asked[0]
    q.error, q.answer = None, q.answer[:-1]
    bench.check([q], "window")
    assert bench.problems and "keys" in bench.problems[0]


def test_the_stream_sends_every_seed_the_same_sets_in_another_order():
    mix = {"loop": "closed", "clients": 1, "parameters": {
        "year": {"int_range": [1993, 1997]},
        "discount": {"hundredths_range": [2, 9]},
        "quantity": {"values": [24, 25]}}}
    sets = traffic.parameter_sets(mix)
    assert len(sets) == 80
    assert {s["discount"] for s in sets} == {h / 100 for h in range(2, 10)}

    def first(seed, n):
        stream = traffic.parameter_stream(mix, seed)
        return [json.dumps(next(stream), sort_keys=True) for _ in range(n)]
    a, b = first(2**31 + 7, 80), first(5, 80)
    assert a == first(2**31 + 7, 80)
    assert a != b and sorted(a) == sorted(b) and len(set(a)) == 80
    with pytest.raises(ValueError):
        next(traffic.parameter_stream({**mix, "loop": "open"}, 1))


def test_unknown_workload_and_wrong_chip_count_exit_non_zero(grown_root):
    with pytest.raises(SystemExit):
        cells.load_cell(grown_root, "no_such.cell")
    with pytest.raises(SystemExit):      # the CPU backend is no TPU
        dev.require_tpu(1)
