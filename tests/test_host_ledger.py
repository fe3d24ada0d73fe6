"""The host ledger (obs/tracer.HostLedger): every top-level query's wall
time summed by segment as the profiler sink's ranges close, the one map
from span names to segments (obs/critpath.segment_of), a query id that
counts, and LocalLimitExec's row-count read as a sanctioned crossing."""

import os
import re
import threading

import jax
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.obs import critpath, memprof, tracer
from spark_rapids_tpu.obs.progress import ProgressTracker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Range:
    """Stands where a jax.profiler.TraceAnnotation would."""

    def __init__(self):
        self.exits = 0

    def __exit__(self, *exc):
        self.exits += 1


@pytest.fixture()
def sink_on(monkeypatch):
    """The profiler sink on over a ledger of this test's own."""
    monkeypatch.setattr(tracer, "_LEDGER", tracer.HostLedger())
    tracer.set_trace_annotations(True)
    try:
        yield tracer.host_ledger()
    finally:
        tracer.set_trace_annotations(False)


#: a session sets the sink from its configuration when it starts
SINK = {"spark.rapids.sql.profile.traceAnnotations": True}


def _session(**extra):
    b = TpuSession.builder().config("spark.rapids.sql.enabled", True)
    for k, v in extra.items():
        b = b.config(k, v)
    return b.get_or_create()


def _numbers(s, n=64):
    return s.create_dataframe(pa.table({"x": pa.array(range(n))}))


# -- the frame arithmetic, on clocks the test sets ---------------------------

def test_self_times_sum_to_the_roots_wall_to_the_nanosecond():
    led = tracer.HostLedger()
    root = led.enter("query", _Range(), "q7", 1_000)
    plan = led.enter("phase:plan", _Range(), None, 1_010)
    over = led.enter("phase:overrides", _Range(), None, 1_020)
    assert led.leave(over, 1_050) is None
    assert led.leave(plan, 1_070) is None
    ex = led.enter("phase:execute", _Range(), None, 1_100)
    pull = led.enter("FilterExec.pull", _Range(), None, 1_110)
    for t0 in (1_120, 1_150):
        key = led.enter("jit.key:FilterExec", _Range(), None, t0)
        led.leave(key, t0 + 7)
        run = led.enter("jit.dispatch:FilterExec", _Range(), None, t0 + 8)
        led.leave(run, t0 + 13)
    fetch = led.enter("fetch.crossing", _Range(), None, 1_200)
    led.leave(fetch, 1_900)
    led.leave(pull, 1_910)
    led.leave(ex, 1_950)
    rec = led.leave(root, 2_003)
    assert rec is not None and led.records() == [rec]
    assert rec["id"] == "q7" and rec["wall_ns"] == 1_003
    assert rec["segments"] == {
        "planning": 60,                       # 30 overrides + 30 plan
        "dispatch": 24,                       # 2 x (7 + 5)
        "fetch_wait": 700,
        "compute:FilterExec": 800 - 24 - 700,
        "other": (850 - 800) + (1_003 - 60 - 850)}
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    assert rec["spans"]["jit.key:FilterExec"] == [2, 14]
    assert rec["spans"]["phase:plan"] == [1, 60]      # inclusive
    assert rec["spans"]["query"] == [1, 1_003]
    assert rec["off_thread_ns"] == 0


def test_an_operators_own_spans_book_to_the_operator_around_them():
    led = tracer.HostLedger()
    root = led.enter("query", _Range(), "q0", 0)
    ex = led.enter("phase:execute", _Range(), None, 0)
    up = led.enter("scan.upload", _Range(), None, 0)    # no operator yet
    led.leave(up, 3)
    for exec_, t in (("ShuffledHashJoinExec", 10), ("HashJoinExec", 100)):
        pull = led.enter(exec_ + ".pull", _Range(), None, t)
        build = led.enter("join.build", _Range(), None, t + 1)
        up = led.enter("scan.upload", _Range(), None, t + 2)
        led.leave(up, t + 7)
        led.leave(build, t + 20)
        size = led.enter("join.size", _Range(), None, t + 30)
        wait = led.enter("fetch.crossing", _Range(), None, t + 31)
        led.leave(wait, t + 39)
        led.leave(size, t + 40)
        led.leave(pull, t + 50)
    led.leave(ex, 200)
    rec = led.leave(root, 200)
    assert rec["segments"] == {
        "compute:scan": 3, "compute:ShuffledHashJoinExec": 50 - 8,
        "compute:HashJoinExec": 50 - 8, "fetch_wait": 16,
        "other": 200 - 3 - 100}
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    # only what the name alone decides is remembered by name
    assert "join.build" not in led._segments
    assert led._segments["fetch.crossing"] == "fetch_wait"


def test_a_range_left_open_ends_with_the_one_below_it():
    led = tracer.HostLedger()
    anns = [_Range() for _ in range(4)]
    root = led.enter("query", anns[0], "q0", 0)
    outer = led.enter("phase:execute", anns[1], None, 10)
    left = led.enter("FilterExec.opTime", anns[2], None, 20)
    inner = led.enter("jit.dispatch:FilterExec", anns[3], None, 30)
    led.leave(inner, 40)
    # `left` is never closed (its generator was suspended): closing
    # `outer` unwinds to it, booking `left` up to now, once
    led.leave(outer, 100)
    assert [a.exits for a in anns] == [0, 1, 1, 1]
    led.leave(left, 500)                        # the late close is a no-op
    assert anns[2].exits == 1
    rec = led.leave(root, 110)
    assert rec["segments"] == {"dispatch": 10, "compute:FilterExec": 70,
                               "other": 30}
    assert sum(rec["segments"].values()) == rec["wall_ns"] == 110


def test_a_span_raised_through_closes_its_frame_and_the_stack(sink_on):
    with pytest.raises(KeyError):
        root = tracer.open_range("query:q3", "q3")
        try:
            with tracer.trace_span("phase:plan", kind="phase"):
                with tracer.trace_span("phase:overrides", kind="phase"):
                    raise KeyError("planning failed")
        finally:
            rec = tracer.close_range(root)
    assert sink_on._tls.stack == [] and sink_on._tls.root is None
    assert rec["id"] == "q3" and set(rec["spans"]) == {
        "query", "phase:plan", "phase:overrides"}
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    # a range closed on another thread than it opened on books nothing
    frame = tracer.open_range("scan.upload")
    t = threading.Thread(target=tracer.close_range, args=(frame,))
    t.start()
    t.join(10)
    assert not t.is_alive() and frame.ann is None
    assert tracer.close_range(frame) is None


def test_a_parent_closes_over_a_range_another_thread_closed(sink_on):
    # a MetricTimer in a generator that a prefetch thread resumes: the
    # range opens on the query's thread and closes on the other; its
    # frame stays on the opening stack, closed, and the enclosing ranges
    # close over it with a record that still sums to its wall
    root = tracer.open_range("query:q4", "q4")
    with tracer.trace_span("phase:execute", kind="phase"):
        frame = tracer.open_range("FilterExec.opTime")
        t = threading.Thread(target=tracer.close_range, args=(frame,))
        t.start()
        t.join(10)
        assert not t.is_alive() and frame.ann is None
        assert sink_on._tls.stack[-1] is frame      # stale, on top
    rec = tracer.close_range(root)                  # no AttributeError
    assert sink_on._tls.stack == [] and sink_on._tls.root is None
    assert set(rec["spans"]) == {"query", "phase:execute"}
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    # and below a later range: the next open drops the closed frame
    root = tracer.open_range("query:q5", "q5")
    frame = tracer.open_range("FilterExec.opTime")
    t = threading.Thread(target=tracer.close_range, args=(frame,))
    t.start()
    t.join(10)
    with tracer.trace_span("fetch.crossing"):
        assert frame not in sink_on._tls.stack
    rec = tracer.close_range(root)
    assert set(rec["spans"]) == {"query", "fetch.crossing"}
    assert sum(rec["segments"].values()) == rec["wall_ns"]


def test_the_ring_drops_the_oldest():
    led = tracer.HostLedger(max_records=3)
    for i in range(5):
        led.leave(led.enter("query", _Range(), f"q{i}", i * 10), i * 10 + 4)
    assert [r["id"] for r in led.records()] == ["q2", "q3", "q4"]
    assert tracer.LEDGER_RECORDS >= 8192
    assert tracer.host_ledger()._ring.maxlen == tracer.LEDGER_RECORDS


def test_off_thread_stays_out_of_the_partition(sink_on):
    root = tracer.open_range("query:q0", "q0")

    def prefetch():
        with tracer.trace_span("scan.upload"):
            pass

    t = threading.Thread(target=prefetch)
    t.start()
    t.join(10)
    assert not t.is_alive()
    rec = tracer.close_range(root)
    assert rec["off_thread_ns"] > 0
    assert set(rec["spans"]) == {"query"}
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    # handed to one record, once
    again = tracer.close_range(tracer.open_range("query:q1", "q1"))
    assert again["off_thread_ns"] == 0


# -- through the session ------------------------------------------------------

def test_a_query_is_one_record_and_a_nested_execute_books_into_it(sink_on):
    s = _session(**SINK)
    df = _numbers(s)
    avg = df.agg(F.avg(col("x")).alias("a"))
    out = df.filter(col("x") > F.scalar_subquery(avg)).collect()
    assert out.num_rows == 32
    recs = sink_on.records()
    assert [r["id"] for r in recs] == ["q0"]        # the subquery was q1
    rec = recs[0]
    assert s.last_query_profile() is rec
    assert rec["spans"]["query"][0] == 2            # outer and nested root
    assert rec["spans"]["phase:subqueries"][0] == 1
    assert rec["spans"]["phase:execute"][0] == 2
    assert sum(rec["segments"].values()) == rec["wall_ns"]
    for seg in ("planning", "dispatch", "fetch_wait", "other"):
        assert rec["segments"][seg] > 0, seg
    assert any(k.startswith("compute:") for k in rec["segments"])
    # the answer's conversion to Arrow is the root operator's, by name
    assert rec["spans"]["DeviceToHostExec.toArrow"][0] >= 2
    assert any(k.startswith("jit.key:") for k in rec["spans"])
    df.filter(col("x") > 3).collect()
    assert [r["id"] for r in sink_on.records()] == ["q0", "q2"]
    assert s.last_query_profile()["id"] == "q2"


@pytest.mark.parametrize("recorder", [False, True])
def test_with_the_profiler_sink_off_a_query_calls_nothing_of_the_ledger(
        monkeypatch, recorder):
    s = _session(**{"spark.rapids.tpu.trace.enabled": recorder})
    df = _numbers(s)
    df.filter(col("x") > 9).collect()               # warm
    assert not tracer.ANNOTATIONS_ON

    def refuse(*a, **k):
        raise AssertionError("the ledger was called with its sink off")

    monkeypatch.setattr(tracer.HostLedger, "enter", refuse)
    monkeypatch.setattr(tracer.HostLedger, "leave", refuse)
    monkeypatch.setattr(tracer.HostLedger, "_close_top", refuse)
    opened = []
    real = tracer.trace_span

    def spy(name, *a, **k):
        opened.append(name)
        return real(name, *a, **k)

    monkeypatch.setattr(tracer, "trace_span", spy)
    before = len(tracer.host_ledger().records())
    assert df.filter(col("x") > 9).collect().num_rows == 54
    assert len(tracer.host_ledger().records()) == before
    assert s.last_query_profile() is None
    # jit.key, like jit.dispatch, is a span only while a sink is on
    keys = [n for n in opened if n.startswith("jit.key:")]
    runs = [n for n in opened if n.startswith("jit.dispatch:")]
    assert (len(keys) > 0) == recorder and len(keys) == len(runs)


def test_three_queries_carry_q0_q1_q2_in_all_four_places(
        sink_on, monkeypatch):
    ranges, contexts = [], []

    class Spy:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            ranges.append(self.name)

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    push = memprof.push_context
    monkeypatch.setattr(memprof, "push_context", lambda tenant, query="":
                        (contexts.append(query), push(tenant, query))[1])
    s = _session(**SINK)                            # no event log
    df = _numbers(s)
    for floor in (1, 2, 3):
        df.filter(col("x") > floor).collect()
    want = ["q0", "q1", "q2"]
    assert [r for r in ranges if r.startswith("query:")] == \
        ["query:" + q for q in want]
    assert contexts == want
    assert [r["id"] for r in sink_on.records()] == want
    recent = [h["query"] for h in ProgressTracker.get().live_view(
        scan=False)["recent"]]
    assert recent[-3:] == want
    assert s.last_query_profile()["id"] == "q2"


def test_the_event_logs_sql_id_is_the_id_the_query_was_given(tmp_path):
    s = _session(**{"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    df = _numbers(s)
    df.filter(col("x") > 1).collect()
    avg = df.agg(F.avg(col("x")).alias("a"))
    df.filter(col("x") > F.scalar_subquery(avg)).collect()
    from spark_rapids_tpu.tools.eventlog import (find_event_logs,
                                                 parse_event_log)
    apps = [parse_event_log(p) for p in find_event_logs([str(tmp_path)])]
    ids = sorted(i for app in apps for i in app.sql_executions)
    # q0, then q1 with its subquery q2 logged inside it
    assert ids == [0, 1, 2]


def test_local_limit_reads_a_device_row_count_through_the_fetch(
        sink_on, monkeypatch):
    from spark_rapids_tpu.obs import metrics
    s = _session(**SINK)
    df = _numbers(s, 256)
    crossings = metrics.registry().counter("tpu_fetch_crossings_total")
    q = df.filter(col("x") > 9).limit(5)
    q.collect()                                     # warm
    spans = s.last_query_profile()["spans"]
    assert "LocalLimitExec.pull" in spans or "GlobalLimitExec.pull" in spans
    from spark_rapids_tpu.exec import basic
    reads = []
    real = basic.fetch_array

    def spy(x):
        reads.append(x)
        return real(x)

    monkeypatch.setattr(basic, "fetch_array", spy)
    before = crossings.value()
    out = q.collect()
    assert out.num_rows == 5
    counts = [x for x in reads if isinstance(x, jax.Array) and x.ndim == 0]
    assert counts, "the limit read no device row count through the fetch"
    # every blocking read of the query is a counted crossing and a span
    rec = s.last_query_profile()
    assert crossings.value() - before == rec["spans"]["fetch.crossing"][0]
    assert rec["spans"]["fetch.crossing"][0] >= 1 + len(counts)


# -- one taxonomy -------------------------------------------------------------

def _tree():
    """A recorded tree as ``QueryTrace.span_dicts()`` gives it: the
    operator's dispatches, keys and the fetch's crossing are recorded
    spans too."""
    def span(i, parent, name, kind, t0, dur, **attrs):
        return {"spanId": i, "parentId": parent, "name": name,
                "kind": kind, "startNs": t0, "durNs": dur, "attrs": attrs}
    return [
        span(1, None, "query", "query", 0, 10_000),
        span(2, 1, "phase:plan", "phase", 100, 1_900),
        span(3, 2, "phase:overrides", "phase", 500, 1_000),
        span(4, 1, "phase:execute", "phase", 2_500, 7_000),
        span(5, 4, "FilterExec.execute", "operator", 2_600, 6_000,
             op="FilterExec"),
        span(6, 5, "jit.key:FilterExec", "span", 2_700, 100),
        span(7, 5, "jit.dispatch:FilterExec", "span", 2_800, 300),
        span(8, 5, "fetch.crossing", "span", 3_200, 5_000),
        span(9, 5, "scan.upload", "span", 8_300, 100),
        span(10, 4, "admission.wait", "span", 8_700, 200),
    ]


def test_critpaths_segments_over_a_recorded_tree_are_what_they_were():
    res = critpath.extract_critical_path(_tree())
    assert res["reconciled"] and res["residual_s"] == 0.0
    assert {k: round(v * 1e9) for k, v in res["segments"].items()} == {
        "planning": 1_900,
        # dispatch, key, crossing and upload stay the operator's own
        "compute:FilterExec": 6_000,
        "queue_wait": 200,
        "other": 10_000 - 1_900 - 6_000 - 200}


def _table_rows(kind):
    """The rows of one kind in docs/observability.md's table of spans,
    events, counters and their readers, as lists of cells."""
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    table = text.split("<!-- spans-and-readers -->")[1]
    rows = [[c.strip() for c in line.split("|")[1:-1]]
            for line in table.splitlines() if line.startswith("|")]
    return [r for r in rows if r and r[0] == kind]


def _package_sources():
    for d, _, files in os.walk(os.path.join(ROOT, "spark_rapids_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as src:
                    yield src.read()


def _table_names():
    """The table's span names, with a value for every placeholder."""
    names = []
    for row in _table_rows("span"):
        names += re.findall(r"`([^`]+)`", row[1])
    for hole, value in (("<kind>", "FilterExec"), ("<Exec>", "SortExec"),
                        ("<metric>", "opTime"), ("<op>", "aggregate"),
                        ("<n>", "7")):
        names = [n.replace(hole, value) for n in names]
    return names


def test_segment_of_places_every_span_the_table_lists():
    names = _table_names()
    assert len(names) >= 30 and "jit.key:FilterExec" in names
    roots = {"query:q7", "query", "phase:execute", "phase:execute-retry",
             "bridge.execute_stage"}
    assert roots <= set(names)
    for name in names:
        seg = critpath.segment_of({"name": name})
        assert (seg == critpath.SEG_OTHER) == (name in roots), (name, seg)
    place = {n: critpath.segment_of({"name": n}) for n in names}
    assert place["jit.key:FilterExec"] == place["jit.dispatch:FilterExec"] \
        == "dispatch"
    assert place["fetch.crossing"] == "fetch_wait"
    assert place["SortExec.pull"] == place["SortExec.opTime"] == \
        "compute:SortExec"
    # what an operator opens for its own work is that operator's: obs/
    # names no class of exec/, the span around it does
    for name, alone in (("join.size", "compute:join"),
                        ("ici.stage:aggregate", "compute:ici"),
                        ("scan.upload", "compute:scan")):
        assert place[name] == alone
        for around in ("planning", "other", None):
            assert critpath.segment_of({"name": name}, around) == alone
        assert critpath.segment_of(
            {"name": name}, "compute:ShuffledHashJoinExec") == \
            "compute:ShuffledHashJoinExec"
    assert place["phase:admit"] == place["phase:setup"] == \
        place["phase:release"] == "session"
    # every span site of the package is in the table
    sites = set()
    for src in _package_sources():
        sites |= set(re.findall(r"trace_span\(\s*\"([^\"]+)\"", src))
    for site in sites:
        assert any(n == site or n.startswith(site) for n in names), site


def test_every_family_and_event_the_package_names_has_a_row_and_a_reader():
    families, events = set(), set()
    for src in _package_sources():
        families |= set(re.findall(r"\"(tpu_[a-z_0-9]+)\"", src))
        events |= set(re.findall(r"trace_event\(\s*\"([^\"]+)\"", src))
    counters = {re.findall(r"`([^`]+)`", r[1])[0]: r
                for r in _table_rows("counter")}
    assert families == set(counters)
    listed = set()
    for row in _table_rows("event"):
        listed |= set(re.findall(r"`([^`]+)`", row[1]))
    assert events <= listed, events - listed
    for row in list(counters.values()) + _table_rows("event") + \
            _table_rows("span"):
        assert len(row) == 5 and row[4] not in ("", "-"), row
    # what nothing read went with PR 35 and stays gone
    gone = {"tpu_fleet_scrapes_total", "tpu_latency_extract_seconds_total",
            "tpu_shuffle_map_rewrites_total",
            "tpu_host_segment_seconds_total",   # review: no reader either
            "arena.exhausted", "mesh.probe_timeout", "mesh.probe_error"}
    assert not gone & (families | events)
