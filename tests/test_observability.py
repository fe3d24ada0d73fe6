"""Docs generation, metrics levels, trace annotations, api_validation
(ref SupportedOpsDocs, GpuMetric levels, NvtxWithMetrics,
api_validation/)."""

import os

import pyarrow as pa
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession, last_query_metrics
from spark_rapids_tpu.docsgen import generate_supported_ops, write_docs
from spark_rapids_tpu.tools.api_validation import validate


def test_api_validation_clean():
    assert validate() == []


def test_generate_configs_docs_contains_keys():
    text = cfg.generate_docs()
    assert "spark.rapids.sql.enabled" in text
    assert "spark.rapids.shuffle.compression.codec" in text
    assert "spark.sql.adaptive.enabled" in text


def test_generate_supported_ops_matrix():
    text = generate_supported_ops()
    assert "| TpuHashAggregateExec |" in text or \
        "| CpuHashAggregateExec |" in text
    assert "## Expressions" in text
    # regex exprs are registered with an explicit host-fallback reason
    # (round 3): they appear in the matrix instead of being silently
    # absent
    assert "RLike" in text
    # decimal128 min/max supported, average not over decimals
    assert "| Min | S | S" in text


def test_write_docs(tmp_path):
    paths = write_docs(str(tmp_path))
    assert all(os.path.exists(p) for p in paths)


def test_metrics_levels_and_report():
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    df = s.create_dataframe(pa.table({"x": pa.array(range(100))}))
    df.group_by(col("x")).agg(F.count("*").alias("c")).collect()
    essential = last_query_metrics(s, "ESSENTIAL")
    moderate = last_query_metrics(s, "MODERATE")
    assert essential and moderate
    assert len(moderate) > len(essential)
    assert all(m == "numOutputRows" for _, m, _ in essential)
    rows_out = [v for op, m, v in essential
                if op == "DeviceToHostExec" and m == "numOutputRows"]
    assert rows_out and rows_out[0] == 100


def test_trace_annotations_run():
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.sql.profile.traceAnnotations", True)
         .get_or_create())
    try:
        df = s.create_dataframe(pa.table({"x": pa.array(range(10))}))
        out = df.filter(col("x") > 3).collect()
        assert out.num_rows == 6
    finally:
        from spark_rapids_tpu.exec.base import set_trace_annotations
        set_trace_annotations(False)


# ---------------------------------------------------------------------------
# flight recorder (obs/): span tree, exporters, CLI subcommands
# ---------------------------------------------------------------------------

def _traced_session(**extra):
    b = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.trace.enabled", True))
    for k, v in extra.items():
        b = b.config(k, v)
    return b.get_or_create()


def test_last_query_trace_span_tree():
    s = _traced_session()
    df = s.create_dataframe(pa.table({"x": pa.array(range(64))}))
    out = df.filter(col("x") > 9).collect()
    assert out.num_rows == 54
    tr = s.last_query_trace()
    assert tr is not None and tr.sealed and tr.open_span_count() == 0
    names = [sp.name for sp in tr.spans]
    # session phases + per-operator execute spans
    assert "phase:plan" in names and "phase:execute" in names
    ops = [sp for sp in tr.spans if sp.kind == "operator"]
    assert any(sp.attrs.get("op") == "DeviceToHostExec" for sp in ops)
    # the root-operator span resolved its output rows (deferred scalars
    # drained at finalize, never on the hot path)
    root_ops = [sp for sp in ops
                if sp.attrs.get("op") == "DeviceToHostExec"]
    assert sum(sp.rows for sp in root_ops) == 54
    # operator spans nest under the execute phase
    by_id = {sp.span_id: sp for sp in tr.spans}
    for sp in ops:
        anc = sp
        while anc.parent_id is not None:
            anc = by_id[anc.parent_id]
        assert anc.kind == "query"


def test_chrome_export_schema_and_text_timeline():
    s = _traced_session()
    df = s.create_dataframe(pa.table({"x": pa.array(range(32))}))
    df.filter(col("x") > 0).collect()
    tr = s.last_query_trace()
    ch = tr.to_chrome()
    assert set(ch) == {"traceEvents", "displayTimeUnit"}
    evs = ch["traceEvents"]
    assert evs and all({"name", "ph", "pid", "tid"} <= set(e)
                       for e in evs)
    complete = [e for e in evs if e["ph"] == "X"]
    assert complete and all("ts" in e and "dur" in e and e["dur"] > 0
                            for e in complete)
    assert any(e["name"] == "DeviceToHostExec.execute"
               for e in complete)
    txt = tr.to_text()
    assert "phase:execute" in txt and "DeviceToHostExec" in txt


def test_tools_cli_trace_and_accuracy(tmp_path, capsys):
    import json

    from spark_rapids_tpu.tools.__main__ import main as tools_main
    s = _traced_session(**{"spark.rapids.tpu.eventLog.dir":
                           str(tmp_path / "logs")})
    df = s.create_dataframe(pa.table(
        {"k": pa.array([i % 3 for i in range(90)]),
         "v": pa.array(range(90))}))
    df.group_by(col("k")).agg(F.sum(col("v")).alias("sv")).collect()
    log_dir = tmp_path / "logs"
    log = str(next(log_dir.glob("events_*")))

    # profiling --accuracy prints the predicted-vs-actual table
    rc = tools_main(["profiling", log, "-o", str(tmp_path / "out"),
                     "--accuracy"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Predicted vs Actual" in out and "actRows" in out

    # trace --export chrome writes Perfetto-loadable JSON
    chrome_path = tmp_path / "q.trace.json"
    rc = tools_main(["trace", log, "--export", "chrome", "-o",
                     str(chrome_path)])
    assert rc == 0
    ch = json.loads(chrome_path.read_text())
    assert ch["traceEvents"] and any(
        e.get("ph") == "X" for e in ch["traceEvents"])

    # trace --export text prints the timeline
    rc = tools_main(["trace", log, "--export", "text"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "phase:execute" in out

    # a foreign log (no span records) is a clean error, not a crash
    foreign = tmp_path / "foreign_log"
    foreign.write_text('{"Event": "SparkListenerLogStart", '
                       '"Spark Version": "3.1.1"}\n')
    assert tools_main(["trace", str(foreign)]) == 2


def test_generated_docs_cover_observability():
    text = cfg.generate_docs()
    assert "spark.rapids.tpu.eventLog.dir" in text
    assert "spark.rapids.tpu.trace.enabled" in text
    from spark_rapids_tpu.docsgen import generate_lint_rules
    assert "TPU-R006" in generate_lint_rules()


# ---------------------------------------------------------------------------
# one span path, two sinks: the flight recorder and the profiler's clock
# (spark.rapids.sql.profile.traceAnnotations)
# ---------------------------------------------------------------------------

@pytest.fixture()
def annotations_off_after():
    yield
    from spark_rapids_tpu.exec.base import set_trace_annotations
    set_trace_annotations(False)


def _group_by_query(s, n=2000):
    df = s.create_dataframe(pa.table({
        "k": pa.array([i % 7 for i in range(n)]),
        "x": pa.array(range(n))}))
    return lambda: (df.filter(col("x") > 100).group_by(col("k"))
                    .agg(F.sum(col("x")).alias("s")).collect())


def _host_spans_under_profiler(run, tmp_path):
    """Run `run` under jax.profiler; the /host:CPU events of the trace
    as {line name: [(name, start_ns, end_ns)]}."""
    import glob
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(files) == 1
    lines = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                lines[line.name] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return out, lines


def test_engine_spans_nest_in_the_query_range_on_the_profilers_clock(
        tmp_path, annotations_off_after):
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.sql.profile.traceAnnotations", True)
         .get_or_create())
    run = _group_by_query(s)
    run()   # programs built outside the profile
    out, lines = _host_spans_under_profiler(run, tmp_path)
    assert out.num_rows == 7
    # the client thread's line is the one that holds the root range
    mine = [evs for evs in lines.values()
            if any(n.startswith("query:q") for n, _, _ in evs)]
    assert len(mine) == 1
    events = mine[0]
    roots = [(a, b) for n, a, b in events if n.startswith("query:q")]
    assert len(roots) == 1
    names = {n for n, _, _ in events}
    wanted = {"phase:plan", "phase:execute", "FilterExec.opTime",
              "TpuHashAggregateExec.opTime", "FilterExec.pull",
              "TpuHashAggregateExec.pull", "DeviceToHostExec.pull",
              "jit.dispatch:FilterExec",
              "jit.dispatch:TpuHashAggregateExec", "fetch.crossing"}
    assert wanted <= names, wanted - names
    # no range is named by the metric alone
    assert "opTime" not in names
    assert not any(n.endswith("Time") and "." not in n for n in names)
    lo, hi = roots[0]
    engine = [(n, a, b) for n, a, b in events
              if n in wanted or n.startswith(("phase:", "jit.dispatch:"))
              or n.endswith(".pull")]
    assert engine
    for n, a, b in engine:
        assert lo <= a and b <= hi, f"{n} lies outside its query's range"
    # a pull encloses the operator's timed block and its dispatch
    pulls = [(a, b) for n, a, b in events if n == "FilterExec.pull"]
    for inner in ("FilterExec.opTime", "jit.dispatch:FilterExec"):
        a, b = next((a, b) for n, a, b in events if n == inner)
        assert any(pa_ <= a and b <= pb for pa_, pb in pulls)


def test_recorder_and_profiler_hold_the_same_spans(tmp_path,
                                                   annotations_off_after):
    """One producer path, two sinks: with both on, every span the
    flight recorder holds is a range of the same name in the profile
    (its root and its per-partition operator spans are `query:q<n>` and
    per-pull `<Exec>.pull` there), and every trace_span range of the
    profile is a recorded span."""
    s = _traced_session(**{
        "spark.rapids.sql.profile.traceAnnotations": True})
    run = _group_by_query(s)
    run()
    _, lines = _host_spans_under_profiler(run, tmp_path)
    profiled = {n for evs in lines.values() for n, _, _ in evs}
    tr = s.last_query_trace()
    recorded = {sp.name for sp in tr.spans
                if sp.kind not in ("event", "query", "operator")}
    assert {"phase:plan", "phase:execute", "fetch.crossing",
            "jit.dispatch:FilterExec"} <= recorded
    assert recorded <= profiled, recorded - profiled
    for sp in tr.spans:
        if sp.kind == "operator":
            assert sp.name.endswith(".execute")
            assert sp.name[:-len("execute")] + "pull" in profiled
    assert any(n.startswith("query:q") for n in profiled)
    span_like = {n for n in profiled
                 if n.startswith(("phase:", "jit.dispatch:", "jit.build:"))
                 or n in ("fetch.crossing", "scan.upload",
                          "admission.wait")}
    assert span_like <= recorded, span_like - recorded


def test_both_sinks_off_costs_no_range_and_still_moves_the_live_phase(
        monkeypatch):
    import inspect
    import jax.profiler
    from spark_rapids_tpu.exec.base import ExecContext
    from spark_rapids_tpu.exec.basic import FilterExec
    from spark_rapids_tpu.obs import progress, tracer

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    phases = []
    real = progress._QueryHandle.set_phase
    monkeypatch.setattr(
        progress._QueryHandle, "set_phase",
        lambda self, phase: (phases.append(phase), real(self, phase))[1])
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True).get_or_create())
    assert not tracer.ANNOTATIONS_ON and tracer.active_tracer() is None
    out = _group_by_query(s)()
    assert out.num_rows == 7
    assert made == []
    # the repair: with no QueryTrace the live view used to stay in
    # `starting` until the query finished
    assert progress.PHASE_PLANNING in phases
    assert progress.PHASE_EXECUTING in phases
    assert phases.index(progress.PHASE_PLANNING) < \
        phases.index(progress.PHASE_EXECUTING)
    # and an operator hands back its own generator, unwrapped
    node = next(e for e in _plan_nodes(s.last_plan)
                if isinstance(e, FilterExec))
    it = node.execute_partition(0, ExecContext(s.conf))
    assert inspect.isgenerator(it)
    assert it.gi_code is FilterExec.execute_partition.__wrapped__.__code__
    it.close()
    # with the switch on the same call is wrapped, one range a pull
    tracer.set_trace_annotations(True)
    try:
        wrapped = node.execute_partition(0, ExecContext(s.conf))
        assert wrapped.gi_code is not it.gi_code
        assert len(list(wrapped)) >= 1
        assert [a[0] for a in made].count("FilterExec.pull") >= 2
    finally:
        tracer.set_trace_annotations(False)


def _plan_nodes(plan):
    out = []
    plan.foreach(out.append)
    return out


def test_upload_counter_counts_the_lanes_once(annotations_off_after):
    """tpu_upload_bytes_total: every lane batch_to_device places on the
    device, validity included; the pin cache makes the second call
    upload nothing."""
    from spark_rapids_tpu.columnar.device import (DEFAULT_ROW_BUCKETS,
                                                  bucket_for)
    from spark_rapids_tpu.obs import metrics as m
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True).get_or_create())
    n = 3000
    run = _group_by_query(s, n)
    fam = m.counter("tpu_upload_bytes_total")
    before = fam.total()
    run()
    first = fam.total() - before
    cap = bucket_for(n, DEFAULT_ROW_BUCKETS)
    # two int64 columns, a data lane and a validity lane each
    assert first == 2 * (cap * 8 + cap)
    run()
    assert fam.total() - before == first
