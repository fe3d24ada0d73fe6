"""The reduction from a trace to busy, per-operation, collective and idle
time: on a hand-made trace whose numbers can be checked by eye, and on the
trace recorded on the chip (``data/``)."""

import glob
import os

import pytest

from benchmarks.harness import trace_reduce as tr

MS = 1e6   # ns


def ev(name, start_ms, dur_ms):
    return [name, start_ms * MS, dur_ms * MS]


def handmade():
    """A 100 ms window; chip 0 busy 10..40 (a while of 30 ms holding a
    sort of 10 and an all-to-all of 5) and 50..60 (a fusion), chip 1 busy
    10..20.  An all-reduce-start..done pair on chip 0 at 50..52 overlaps
    the fusion."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step", 10, 50)]},
            {"name": "XLA Ops", "events": [
                ev("while.1", 10, 30), ev("sort.3", 12, 10),
                ev("all-to-all.7", 25, 5), ev("fusion.9", 50, 10),
                ev("before_window", 0, 1)]},
            {"name": "XLA Ops", "events": [ev("all-reduce.2", 50, 2)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.9", 10, 10)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ev(tr.WINDOW_ANNOTATION, 5, 100),
                ev("bench:collect", 6, 60), ev("fetch", 41, 8),
                ev("bench:keep_answer", 66, 30)]},
            {"name": "worker", "events": [ev("TransferFromDevice", 42, 5),
                                          ev("instant", 44, 0)]}]},
        {"name": "/host:metadata", "lines": []}]}


def test_handmade_trace_by_eye():
    s = tr.reduce_trace(handmade())
    assert s.window_s == pytest.approx(0.100)
    c0, c1 = s.chips
    assert (c0.index, c1.index) == (0, 1)
    assert c0.busy_s == pytest.approx(0.040)     # 10..40 and 50..60
    assert c1.busy_s == pytest.approx(0.010)
    assert s.busiest is c0
    assert s.busy_mean_s == pytest.approx(0.025)
    # self times: the while keeps what its children leave
    assert c0.op_self_s["jit_step/while.1"] == pytest.approx(0.015)
    assert c0.op_self_s["jit_step/sort.3"] == pytest.approx(0.010)
    assert c0.op_self_s["jit_step/all-to-all.7"] == pytest.approx(0.005)
    assert not any("before_window" in k for k in c0.op_self_s)
    assert s.device_ops()[0] == ("jit_step/while.1", pytest.approx(0.015))
    # summed over lines: the all-reduce under the fusion counts too
    assert c0.program_s == {"jit_step": pytest.approx(0.042)}
    assert c1.program_s == {"?": pytest.approx(0.010)}   # no module line
    # collectives: 5 ms alone, 2 ms under the fusion
    assert c0.collective_s == pytest.approx(0.007)
    assert c0.collective_exposed_s == pytest.approx(0.005)
    assert c1.collective_s == 0
    # idle on chip 0: 5..10, 40..50, 60..105, named by the host's spans
    gaps = dict(s.idle_gaps)
    assert gaps["bench:keep_answer"] == pytest.approx(0.045)
    assert gaps["fetch > TransferFromDevice"] == pytest.approx(0.010)
    assert gaps["bench:collect"] == pytest.approx(0.005)
    assert sum(gaps.values()) + c0.busy_s == pytest.approx(s.window_s)


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.is_collective("all-to-all.3")
    assert tr.is_collective("%all-reduce-start.1")
    assert not tr.is_collective("fusion.all")
    assert tr.short_op("%fusion.7 = s32[64]{0:T(1024)} fusion(s32[64]{0} "
                       "%p), kind=kCustom") == "fusion.7 s32[64]"
    assert tr.short_op("%all-to-all.3 = (u32[4,8]{1,0}, u32[4,8]{1,0}) "
                       "all-to-all(...)") == "all-to-all.3 u32[4,8]"
    assert tr.short_op("plain name") == "plain name"
    assert tr.short_program("jit_f(2971158065528068751)") == "jit_f#8751"


def test_no_device_plane_is_an_error_and_no_window_falls_back():
    t = handmade()
    with pytest.raises(ValueError):
        tr.reduce_trace({"planes": t["planes"][2:]})
    t["planes"][2]["lines"][0]["events"].pop(0)       # no window span
    s = tr.reduce_trace(t)
    assert s.window_s == pytest.approx(0.060)         # 0..60, the events


def test_recorded_roundtrip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    tr.save_recorded(handmade(), path)
    assert tr.load_recorded(path) == handmade()


RECORDED = sorted(glob.glob(os.path.join(
    os.path.dirname(__file__), "data", "*.json.gz")))


@pytest.mark.parametrize("path", RECORDED or [None])
def test_recorded_chip_trace(path):
    """A trace the chip wrote (cut to its device planes and the client's
    host line): the reduction finds its devices, its window and its
    operations, and busy plus idle make up the window."""
    if path is None:
        pytest.skip("no recorded trace yet")
    trace = tr.load_recorded(path)
    s = tr.reduce_trace(trace)
    assert s.window_s > 0 and len(s.chips) >= 1
    busiest = s.busiest
    assert 0 < busiest.busy_s <= s.window_s
    assert sum(busiest.op_self_s.values()) >= busiest.busy_s * 0.999
    idle = tr.total(tr.subtract([s.window], busiest.busy)) / 1e9
    assert idle + busiest.busy_s == pytest.approx(s.window_s)
    assert len(s.device_ops()) <= tr.TOP_OPS
    assert len(s.idle_gaps) <= tr.TOP_GAPS
    assert all(label != "(no host span)" for label, _ in s.idle_gaps)


def test_recorded_q6_trace_reads_as_it_did_on_the_chip():
    """Three Q6 queries at SF5 on one v5e chip (chip run, PR 23): the
    numbers the run itself printed."""
    path = [p for p in RECORDED if "q6_sf5_1chip" in p][0]
    s = tr.reduce_trace(tr.load_recorded(path))
    assert s.window_s == pytest.approx(52.377012072)
    chip, = s.chips
    assert chip.busy_s == pytest.approx(52.357477322)
    assert chip.collective_s == 0
    # the filter's program and the ungrouped aggregate's, per query
    assert chip.program_s["jit__lambda#8751"] / 3 == pytest.approx(9.618, abs=1e-3)
    assert chip.program_s["jit__lambda#7562"] / 3 == pytest.approx(7.833, abs=1e-3)
    name, seconds = s.device_ops()[0]
    assert name == "jit__lambda#7562/fusion.7 s32[33554432]"
    assert seconds == pytest.approx(2.803794869)
    assert s.idle_gaps[0] == ("bench:collect", pytest.approx(0.016079499))


def test_readers_on_the_recorded_trace_give_the_chip_runs_numbers():
    """The per-layer readers over the recorded Q6 trace print what that
    run printed on the chip (30,006,959 rows, three traced queries)."""
    import os
    from benchmarks.harness import cells
    from benchmarks.harness.facts import RunFacts
    from benchmarks.queries import q6
    from conftest import ROOT
    path = [p for p in RECORDED if "q6_sf5_1chip" in p][0]
    run = RunFacts(cell="tpch_sf5_1chip.q6", chips=1,
                   device_kind="TPU v5 lite", n_rows=30_006_959, query=q6,
                   traced_times_ms=[17460.72, 17459.58, 17456.1],
                   answer_rows=[1, 1, 1],
                   trace=tr.reduce_trace(tr.load_recorded(path)))

    def read(name):
        return cells.load_module(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py")).read(run)
    assert read("device_ms_per_query") == pytest.approx(17452.492440666665)
    assert read("hbm_roofline_share") == pytest.approx(0.005878122872, rel=1e-9)
    assert read("device_idle_share") == pytest.approx(0.03729641922518567)
    assert read("host_ms_per_query") == pytest.approx(17459.58 - 17452.4924407)
    assert read("collective_ms_per_query") is None        # one chip
    run.trace = None
    assert read("device_ms_per_query") is None
    assert read("hbm_roofline_share") is None
    with pytest.raises(KeyError):
        run.device_kind, run.trace = "TPU v9", tr.reduce_trace(
            tr.load_recorded(path))
        read("hbm_roofline_share")
