"""The four-chip cell of the real ``BENCHMARK.json``
(``tpch_sf2.75_4chip.q18sub``), found by name and run end to end on four
virtual devices at a tiny scale (an override of ``scale_factor`` made in the
test's temporary root; counts and correctness only), and its three readers
on hand-made facts."""

import json
import os

import pytest

from benchmarks.harness import cells, runner, trace_reduce as tr
from benchmarks.harness import device as dev
from benchmarks.harness.facts import RunFacts
from conftest import ROOT
from helpers import TINY_SF, FakeDevice, copy_root

CELL = "tpch_sf2.75_4chip.q18sub"
NEW = ("collective_ms_per_query", "ici_stage_device_ms_per_query",
       "ici_wire_gb_per_query", "ici_collective_ms_per_query")


@pytest.fixture()
def tiny_root(tmp_path):
    root = copy_root(tmp_path)
    path = os.path.join(root, "benchmarks", "configs",
                        "tpch_sf2.75_4chip.json")
    with open(path) as f:
        config = json.load(f)
    config["scale_factor"] = TINY_SF
    with open(path, "w") as f:
        json.dump(config, f)
    return root


def test_the_cell_is_found_by_name_with_its_metrics():
    cell = cells.load_cell(ROOT, CELL)
    assert cell.chips == 4 and cell.config["num_partitions"] == 4
    assert cell.config["session_conf"][
        "spark.rapids.shuffle.transport"] == "ici"
    assert cell.traffic["query"] == "q18sub"
    names = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(names)
    # the one-chip aggregate's programs do not run here, and the HAVING
    # filter runs on the first chip, which need not be the busiest, the
    # only one those readers look at: both are listed for the one-chip
    # cells alone
    one_chip = cells.load_cell(ROOT, "tpch_sf5_1chip.q18sub")
    for name in ("aggregate_device_ms_per_query",
                 "filter_device_ms_per_query"):
        assert name not in names
        assert name in [m["name"] for m in one_chip.per_layer]
    assert not set(NEW) & {m["name"] for m in one_chip.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "answer_ms_p50", "queries_per_s", "setup_s"}
    # the first configuration's widths, types and guarantees, unloosened
    first = one_chip.config
    for key in ("columns", "datagen", "table"):
        assert cell.config[key] == first[key]
    assert cell.config["guarantees"]["cpu_ops_allowed"] == \
        first["guarantees"]["cpu_ops_allowed"]
    assert set(cell.config["reduced"]) == set(first["reduced"])


def test_the_cell_runs_and_its_plan_holds_what_the_configuration_demands(
        tiny_root, monkeypatch):
    import jax
    monkeypatch.setattr(dev, "peak_device_bytes",
                        lambda devices: [123] * len(devices))
    cell = cells.load_cell(tiny_root, CELL)
    bench = runner.Bench(cell, seed=2**31 + 11, trace=False)
    wire_before = wire_total()
    bench.load()
    devices = jax.devices()[:cell.chips]
    bench.warm_up(devices)
    assert bench.problems == []
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    warm = programs_built(CompileObservatory)
    bench.window(0.5)
    bench.check(bench.asked, "window")
    assert bench.problems == []
    assert bench.asked and all(q.error is None for q in bench.asked)
    plan = bench.session.last_plan
    stage, = dev.plan_execs(plan, "IciAggregateExec")
    scan, = dev.plan_execs(plan, "LocalScanExec")
    assert stage.stage_input_devices == 4 and scan.pinned_devices == 4
    assert bench.plan_fault(plan) is None
    # a table on one chip would break the guarantee
    pinned = dict(scan.pin_cache)
    scan.pin_cache.clear()
    assert "pinned_devices" in bench.plan_fault(plan)
    scan.pin_cache.update(pinned)
    # each partition's lanes lie on their own device
    on = sorted(min(d.id for d in leaf.devices())
                for leaf in dev.pinned_scan_arrays(plan))
    assert set(on) == {d.id for d in devices}
    facts = bench.facts([FakeDevice()] * 4)
    # the mesh path builds nothing in the window.  (At this scale no order
    # passes 312, and the fetch's pack program follows the answer's value
    # range: an empty answer after the warm-up may build another of those,
    # which a few dozen keys at the real scale do not.)
    assert {kind for kind, _ in programs_built(CompileObservatory) - warm} \
        <= {"fetch_pack"}
    layer = runner.per_layer(bench, facts)
    # static: the counter rose by one program's figure for every query
    # asked, the warm-up's included, and the reader divides the process's
    # total by those (a benchmark process runs one cell)
    asked = len(facts.times_ms) + 1
    sent = wire_total() - wire_before
    steps = {p["ici_wire_bytes"]
             for p in CompileObservatory.get().snapshot()["programs"]
             if p.get("ici_wire_bytes")}
    assert sent > 0 and sent / asked in steps
    assert layer["ici_wire_gb_per_query"]["value"] == \
        pytest.approx(wire_total() / 1e9 / asked)
    assert "collective_ms_per_query" not in layer       # no trace
    assert "ici_stage_device_ms_per_query" not in layer
    assert layer["fetch_crossings_per_query"]["value"] >= 1
    e2e = runner.end_to_end(bench, setup_s=1.0)
    assert set(e2e) == {"answer_ms_p50", "queries_per_s", "setup_s"}


def wire_total() -> float:
    from spark_rapids_tpu.obs import metrics
    return sum(f.total() for f in metrics.registry().families()
               if f.name == "tpu_ici_wire_bytes_total")


def programs_built(observatory) -> set:
    return {(p["exec"], p["key"] + p["shape"])
            for p in observatory.get().snapshot()["programs"]}


def reader(name):
    return cells.load_module(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".py"))


def handmade_trace():
    """Two traced queries in a 1000 ms window on two chips.  Chip 1 is the
    busier: each query runs the mesh stage's program for 300 ms there, of
    which an all-to-all that the compiler put in (named by the opcode)
    takes 50 ms and one that JAX asked for (named by the primitive, as the
    engine's exchange is on the chip) 30 ms, and nothing else; chip 0 runs
    the stage for 250 ms (40 + 30 ms of all-to-all) and the filter for
    20 ms."""
    def chip(index, stage_ms, a2a_ms, filter_ms):
        ops, modules = [], []
        for q in range(2):
            t0 = 1e6 * (100 + 500 * q)
            modules.append(["jit_IciAggregateExec(424242)", t0,
                            stage_ms * 1e6])
            ops.append(["%fusion.1 = s32[16]{0} fusion(%p)", t0,
                        (stage_ms - a2a_ms - 30) * 1e6])
            ops.append(["%all_to_all.41 = f32[4,1,16]{2,1,0} "
                        "all-to-all(%bitcast.75), channel_id=1",
                        t0 + (stage_ms - a2a_ms - 30) * 1e6, 30 * 1e6])
            ops.append(["%all-to-all.3 = s32[4,16]{1,0} all-to-all(%x)",
                        t0 + (stage_ms - a2a_ms) * 1e6, a2a_ms * 1e6])
            if filter_ms:
                t1 = t0 + stage_ms * 1e6 + 1e6
                modules.append(["jit_FilterExec(77771111)", t1,
                                filter_ms * 1e6])
                ops.append(["%fusion.9 = s32[16]{0} fusion(%p)", t1,
                            filter_ms * 1e6])
        return {"name": f"/device:TPU:{index}", "lines": [
            {"name": tr.OPS_LINE, "events": ops},
            {"name": tr.MODULES_LINE, "events": modules}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        [tr.WINDOW_ANNOTATION, 0.0, 1e9]]}]}
    return {"planes": [host, chip(0, 250, 40, 20), chip(1, 300, 50, 0)]}


def test_the_new_readers_on_hand_made_facts():
    run = RunFacts(cell=CELL, chips=4, device_kind="TPU v5 lite",
                   n_rows=1, query=None, times_ms=[500.0] * 9,
                   traced_times_ms=[500.0, 500.0],
                   trace=tr.reduce_trace(handmade_trace()))
    assert run.trace.busiest.index == 1
    # the accepted reader sees the compiler's spelling alone; the new one
    # both, which is the exchange's whole time on the wire
    assert reader("collective_ms_per_query").read(run) == \
        pytest.approx(50.0)
    assert reader("ici_collective_ms_per_query").read(run) == \
        pytest.approx(80.0)
    assert reader("ici_stage_device_ms_per_query").read(run) == \
        pytest.approx(300.0)
    # no trace, or one chip: nothing to read, never a zero
    bare = RunFacts(cell=CELL, chips=4, device_kind="TPU v5 lite",
                    n_rows=1, query=None, times_ms=[500.0] * 9)
    assert reader("collective_ms_per_query").read(bare) is None
    assert reader("ici_stage_device_ms_per_query").read(bare) is None
    assert reader("ici_collective_ms_per_query").read(bare) is None
    names = reader("ici_collective_ms_per_query").is_collective
    assert names("jit_IciAggregateExec#6365/all_to_all.41 f32[4,1,4194304]")
    assert names("jit_IciSortExec#1/all_gather.2 u64[12]")
    assert names("jit_x#1/all-reduce.1 f32[]") and names("p/psum.3 f32[]")
    assert not names("jit_x#1/all_to_all_helper.1 f32[4]")
    assert not names("jit_x#1/fusion.18 f32[16777216]")
    # the counter's reader: the total by the window's queries and the
    # one warm-up call
    from spark_rapids_tpu.obs import metrics
    wire = reader("ici_wire_gb_per_query")
    before = wire_total()
    metrics.registry().counter(wire.COUNTER, "test").inc(3e9)
    assert wire.read(bare) == pytest.approx((before + 3e9) / 1e9 / 10)


def test_the_wire_reader_reads_nothing_where_the_program_has_no_counter(
        monkeypatch):
    """The parent commit has no such counter: the reader returns None and
    the line leaves the metric out."""
    from spark_rapids_tpu.obs import metrics

    class Bare:
        def families(self):
            return []
    monkeypatch.setattr(metrics, "registry", lambda: Bare())
    bare = RunFacts(cell=CELL, chips=4, device_kind="TPU v5 lite",
                    n_rows=1, query=None, times_ms=[500.0])
    assert reader("ici_wire_gb_per_query").read(bare) is None
