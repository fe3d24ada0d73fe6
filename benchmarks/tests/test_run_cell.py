"""The whole run at a tiny scale on the CPU backend: the result line has the
contract's keys, and the traced path writes a trace whose window span the
reduction finds.  (Device times need the chip: none is asserted.)"""

import glob
import json
import os
import shutil

import pytest

from benchmarks.harness import cells, runner, trace_reduce, device as dev
from helpers import add_entries, add_tiny_config, copy_root


@pytest.fixture()
def tiny_root(tmp_path):
    root = copy_root(tmp_path)
    add_entries(
        root,
        configs=[add_tiny_config(root, "tiny_1chip", 1)],
        workloads=[{"name": "tiny_1chip.q6", "config": "tiny_1chip",
                    "traffic": "q6", "chips": 1, "why": "t"}])
    return root


def test_result_line_of_an_untraced_run(tiny_root, monkeypatch, capsys):
    import time
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    monkeypatch.setattr(dev, "device_facts", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    cell = cells.load_cell(tiny_root, "tiny_1chip.q6")
    import jax
    line = runner.run_cell(cell, 2**31 + 9, 0.5, False,
                           time.perf_counter(), jax.devices()[:1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"answer_ms_p50", "queries_per_s",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["metrics"]["queries_per_s"]["unit"] == "queries/s"
    assert line["device"]["memory_peak_bytes"] == 123
    json.dumps(line)
    # every earlier line of output is a JSON object of facts
    for out_line in capsys.readouterr().out.strip().splitlines():
        assert isinstance(json.loads(out_line), dict)


def test_traced_queries_leave_a_trace_with_the_window_span(tiny_root):
    cell = cells.load_cell(tiny_root, "tiny_1chip.q6")
    bench = runner.Bench(cell, 5, trace=True)
    bench.load()
    import jax
    bench.warm_up(jax.devices()[:1])
    trace_dir = bench.traced_queries()
    try:
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert len(files) == 1
        raw = trace_reduce.read_xplane(files[0])
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    window, line = trace_reduce.find_window(raw)
    assert window is not None and line is not None
    assert bench.n_traced == 3 and len(bench.asked) == 3
    names = {e[0] for p in raw["planes"] for ln in p["lines"]
             for e in ln["events"]}
    assert {"bench:collect", "bench:build_query",
            "bench:keep_answer"} <= names
    # the CPU backend has no /device:TPU plane: the reduction says so
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(raw)
