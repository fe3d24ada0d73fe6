"""The five readers over the engine's host ledger
(``obs/tracer.host_ledger()``): nothing without records, the median a query
with them, together the query's wall; and ``BENCHMARK.json`` with their
five entries right after the entries PR 34 left (the driver's check holds
those byte for byte; a later PR may append cells and entries)."""

import json
import os
import time

import pytest

from benchmarks.harness import cells, runner, device as dev
from benchmarks.harness.facts import RunFacts
from benchmarks.harness.stats import median
from benchmarks.harness.trace_reduce import ChipTime, TraceSummary
from benchmarks.layer_metrics import (dispatch_ms_per_query,
                                      fetch_wait_ms_per_query,
                                      operator_host_ms_per_query,
                                      plan_ms_per_query,
                                      unnamed_host_ms_per_query)
from conftest import ROOT
from helpers import add_entries, add_tiny_config, copy_root

READERS = {"plan_ms_per_query": plan_ms_per_query,
           "dispatch_ms_per_query": dispatch_ms_per_query,
           "fetch_wait_ms_per_query": fetch_wait_ms_per_query,
           "operator_host_ms_per_query": operator_host_ms_per_query,
           "unnamed_host_ms_per_query": unnamed_host_ms_per_query}


class _Range:
    def __exit__(self, *exc):
        pass


@pytest.fixture()
def ledger(monkeypatch):
    from spark_rapids_tpu.obs import tracer
    led = tracer.HostLedger()
    monkeypatch.setattr(tracer, "_LEDGER", led)
    return led


def _query(led, i, plan, key, run, wait, pull, rest):
    """One query's ranges on a clock the test sets (ns)."""
    t = i * 1_000_000
    root = led.enter("query", _Range(), f"q{i}", t)
    p = led.enter("phase:plan", _Range(), None, t)
    led.leave(p, t + plan)
    t += plan
    ex = led.enter("phase:execute", _Range(), None, t)
    op = led.enter("DeviceToHostExec.pull", _Range(), None, t)
    k = led.enter("jit.key:FilterExec", _Range(), None, t)
    led.leave(k, t + key)
    d = led.enter("jit.dispatch:FilterExec", _Range(), None, t + key)
    led.leave(d, t + key + run)
    f = led.enter("fetch.crossing", _Range(), None, t + key + run)
    led.leave(f, t + key + run + wait)
    t += key + run + wait + pull
    led.leave(op, t)
    led.leave(ex, t)
    return led.leave(root, t + rest)


def test_each_reader_is_none_without_records_and_the_median_with(ledger):
    run = RunFacts("c", 1, "TPU v5 lite", 10, None, times_ms=[1.0] * 3)
    for reader in READERS.values():
        assert reader.read(run) is None          # no record at all
        assert reader.read(RunFacts("c", 1, "TPU v5 lite", 10, None)) \
            is None                              # no query in the window
    # the warm-up call, then a window of three
    _query(ledger, 0, 900_000, 70, 30, 500, 90, 10)
    for i, plan in ((1, 3_000), (2, 1_000), (3, 2_000)):
        _query(ledger, i, plan, 100 * i, 50, 10_000 * i, 400, 25 * i)
    got = {name: r.read(run) for name, r in READERS.items()}
    assert got == {"plan_ms_per_query": 2_000 / 1e6,      # not 900,000
                   "dispatch_ms_per_query": 250 / 1e6,
                   "fetch_wait_ms_per_query": 20_000 / 1e6,
                   "operator_host_ms_per_query": 400 / 1e6,
                   "unnamed_host_ms_per_query": 50 / 1e6}
    # fewer records than the window asked queries: nothing to read
    assert plan_ms_per_query.read(RunFacts(
        "c", 1, "TPU v5 lite", 10, None, times_ms=[1.0] * 5)) is None


def test_a_program_without_a_ledger_reads_nothing(monkeypatch):
    from spark_rapids_tpu.obs import tracer
    monkeypatch.delattr(tracer, "host_ledger")
    run = RunFacts("c", 1, "TPU v5 lite", 10, None, times_ms=[1.0])
    assert all(r.read(run) is None for r in READERS.values())


def test_the_five_make_the_wall_of_a_traced_tiny_cell(tmp_path,
                                                      monkeypatch):
    root = copy_root(tmp_path)
    add_entries(
        root, configs=[add_tiny_config(root, "tiny_1chip", 1)],
        workloads=[{"name": "tiny_1chip.q6", "config": "tiny_1chip",
                    "traffic": "q6", "chips": 1, "why": "t"}])
    chip = ChipTime(index=0, busy_s=0.001, collective_s=0.0,
                    collective_exposed_s=0.0, op_self_s={}, program_s={})
    monkeypatch.setattr(runner.Bench, "read_trace", staticmethod(
        lambda trace_dir: TraceSummary(window_s=0.01, window=(0.0, 0.01),
                                       chips=[chip], idle_gaps=[])))
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    monkeypatch.setattr(dev, "device_facts", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    facts = runner.Bench.facts

    def as_the_chip(self, devices):
        f = facts(self, devices)
        f.device_kind = "TPU v5 lite"
        kept.append(f)
        return f

    kept = []
    monkeypatch.setattr(runner.Bench, "facts", as_the_chip)
    from spark_rapids_tpu.obs import tracer
    monkeypatch.setattr(tracer, "_LEDGER", tracer.HostLedger())
    cell = cells.load_cell(root, "tiny_1chip.q6")
    import jax
    try:
        line = runner.run_cell(cell, 2**31 + 35, 1.0, True,
                               time.perf_counter(), jax.devices()[:1])
    finally:
        tracer.set_trace_annotations(False)
    assert line["correct"] is True and set(READERS) <= set(line["metrics"])
    times = kept[0].times_ms
    records = tracer.host_ledger().records()
    assert len(records) == len(times) + 1            # and the warm-up
    window = records[-len(times):]
    ids = [int(r["id"][1:]) for r in records]
    assert ids == list(range(ids[0], ids[0] + len(ids)))
    five = {name: line["metrics"][name]["value"] for name in READERS}
    # the readers' segments are all a warm query has: record by record
    # the five parts are the wall, to the nanosecond
    for r in window:
        seg = r["segments"]
        parts = [sum(seg.get(s, 0) for s in plan_ms_per_query.SEGMENTS),
                 seg.get("dispatch", 0), seg.get("fetch_wait", 0),
                 sum(ns for s, ns in seg.items()
                     if s.startswith("compute:")), seg.get("other", 0)]
        assert sum(parts) == sum(seg.values()) == r["wall_ns"]
    # the root covers what the harness times: a query's record is its
    # wall on the harness's clock less the call into the session
    gaps = [ms - r["wall_ns"] / 1e6 for ms, r in zip(times, window)]
    assert all(g >= 0 for g in gaps) and median(gaps) < 0.03 * median(times)
    # and, where the process is steady, the five medians make the median
    # wall (the CPU backend at times runs programs inline, which puts a
    # stall into another segment every query: medians then sum short)
    walls = sorted(r["wall_ns"] for r in window)
    if walls[3 * len(walls) // 4] < 1.15 * walls[len(walls) // 4]:
        assert sum(five.values()) == pytest.approx(
            median(walls) / 1e6, rel=0.03)
    assert sum(five.values()) <= 1.03 * median(times)


def test_benchmark_json_holds_the_five_after_the_accepted_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("plan_ms_per_query")
    assert names[at - 1] == "join_sizing_fetches_per_query"
    assert names[at:at + 5] == list(READERS)
    for m in bench["per_layer"][at:at + 5]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}                 # every cell reports them
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "program_counter", "answer_ms_p50")
    assert [m["layer"] for m in bench["per_layer"][at:at + 5]] == [
        "host path", "compile and dispatch", "D2H fetch", "operators",
        "host path"]
    for cell in bench["workloads"]:
        loaded = cells.load_cell(ROOT, cell["name"])
        assert set(READERS) <= set(loaded.readers)
