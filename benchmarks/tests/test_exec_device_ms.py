"""The readers that split the device's busy time by the operator that built
the program (``filter_device_ms_per_query``,
``aggregate_device_ms_per_query``) and the upload counter's reader
(``upload_gb``): on a hand-made trace whose numbers can be checked by eye
(``data/named_programs_handmade.json``: two queries in a 1000 ms window;
each runs ``jit_FilterExec`` for 100 ms, ``jit_TpuHashAggregateExec.complete``
for 200 ms and ``jit_fetch_pack`` for 2 ms), and on the trace recorded on
the chip in PR 23, whose programs are still ``jit__lambda``."""

import json
import os

import pytest

from benchmarks.harness import cells, program_kinds, trace_reduce as tr
from benchmarks.harness.facts import RunFacts
from conftest import ROOT

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")


def reader(name):
    return cells.load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))


def facts(trace: dict, traced_ms) -> RunFacts:
    return RunFacts(cell="t", chips=1, device_kind="TPU v5 lite", n_rows=1,
                    query=None, traced_times_ms=list(traced_ms),
                    trace=tr.reduce_trace(trace))


@pytest.fixture(scope="module")
def handmade():
    with open(os.path.join(DATA, "named_programs_handmade.json")) as f:
        return facts(json.load(f), [340.0, 340.0])


def test_readers_split_busy_time_by_operator_kind(handmade):
    run = handmade
    assert run.trace.busiest.program_s == {
        "jit_FilterExec#1111": pytest.approx(0.200),
        "jit_TpuHashAggregateExec.complete#7562": pytest.approx(0.400),
        "jit_fetch_pack#3001": pytest.approx(0.004)}
    filt = reader("filter_device_ms_per_query").read(run)
    agg = reader("aggregate_device_ms_per_query").read(run)
    device = reader("device_ms_per_query").read(run)
    assert filt == pytest.approx(100.0)
    assert agg == pytest.approx(200.0)
    assert device == pytest.approx(302.0)
    # the two operators are the device's time less the fetch program
    assert filt + agg == pytest.approx(device - 2.0)
    # the breakdown names operators, and a gap an engine span
    assert all(name.startswith(("jit_FilterExec#", "jit_fetch_pack#",
                                "jit_TpuHashAggregateExec.complete#"))
               for name, _ in run.trace.device_ops())
    gaps = dict(run.trace.idle_gaps)
    assert "opTime" not in gaps
    # before the first program the plan phase is the innermost span open;
    # between the programs and after the fetch program, the fetch's wait
    assert gaps["phase:plan"] == pytest.approx(0.010)
    assert gaps["fetch.crossing"] == pytest.approx(0.030)
    assert gaps["bench:keep_answer"] == pytest.approx(0.356)


def test_a_kind_is_a_whole_name_or_a_name_and_a_role():
    assert program_kinds.is_of_kind("jit_FilterExec#8751", "FilterExec")
    assert program_kinds.is_of_kind("jit_FilterExec.rowpos#0693",
                                    "FilterExec")
    assert program_kinds.is_of_kind("jit_FilterExec", "FilterExec")
    assert not program_kinds.is_of_kind("jit_FilterExecutor#1",
                                        "FilterExec")
    assert not program_kinds.is_of_kind("jit__lambda#8751", "FilterExec")
    assert not program_kinds.is_of_kind("?", "FilterExec")


def test_nothing_to_read_without_named_programs_or_without_a_trace():
    recorded = facts(tr.load_recorded(os.path.join(
        DATA, "q6_sf5_1chip_3queries.json.gz")), [17461.0] * 3)
    assert all(p.startswith("jit_") and "Exec" not in p
               for p in recorded.trace.busiest.program_s)
    untraced = RunFacts(cell="t", chips=1, device_kind="TPU v5 lite",
                        n_rows=1, query=None)
    for name in ("filter_device_ms_per_query",
                 "aggregate_device_ms_per_query"):
        assert reader(name).read(recorded) is None
        assert reader(name).read(untraced) is None
    assert reader("device_ms_per_query").read(recorded) > 17000


def test_upload_gb_reads_the_programs_counter_or_nothing(monkeypatch):
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.obs import metrics
    upload_gb = reader("upload_gb")
    batch_to_device(pa.record_batch({"x": pa.array(np.arange(8))}))
    before = upload_gb.read(None)
    rb = pa.record_batch({"x": pa.array(np.arange(1000, dtype=np.int64))})
    placed = batch_to_device(rb, capacity=1024)
    # the data lane and its validity lane, at the padded capacity
    assert sum(leaf.nbytes for leaf in (placed.columns[0].data,
                                        placed.columns[0].validity)) \
        == 1024 * 8 + 1024
    assert upload_gb.read(None) - before == pytest.approx(
        (1024 * 8 + 1024) / 1e9)
    # a program that has no such counter (the parent of PR 25)
    monkeypatch.setattr(metrics, "registry", metrics.MetricsRegistry)
    assert upload_gb.read(None) is None
