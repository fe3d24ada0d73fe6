"""Flight-recorder round trip: a query executed with
spark.rapids.tpu.eventLog.dir set emits a log that tools/eventlog.py
parses and whose profiling aggregates equal the live metrics_report
values exactly; failure paths flush with error status; metrics_report
drains every pending device scalar through ONE fetch crossing."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession, last_query_metrics
from spark_rapids_tpu.tools.eventlog import parse_event_log
from spark_rapids_tpu.tools.profiling import (accuracy_report,
                                              operator_metrics)


def _session(tmp_path, **extra):
    b = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.eventLog.dir", str(tmp_path)))
    for k, v in extra.items():
        b = b.config(k, v)
    return b.get_or_create()


def _table(n=400):
    return pa.table({
        "k": pa.array((np.arange(n) % 9).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })


def _only_log(tmp_path):
    logs = [f for f in os.listdir(tmp_path) if f.startswith("events_")]
    assert len(logs) == 1, logs
    return os.path.join(tmp_path, logs[0])


def test_eventlog_roundtrip_matches_live_metrics(tmp_path):
    s = _session(tmp_path)
    out = (s.create_dataframe(_table(), num_partitions=2)
           .filter(col("v") >= 0).group_by(col("k"))
           .agg(F.sum(col("v")).alias("sv"), F.count("*").alias("c"))
           .collect())
    assert out.num_rows == 9
    path = _only_log(tmp_path)
    # every emitted line is valid JSON (nothing the parser rejects)
    with open(path) as f:
        for line in f:
            json.loads(line)
    app = parse_event_log(path)
    sx = app.sql_executions[0]
    assert not sx.failed and sx.end_time is not None
    # THE round-trip contract: parsed operator aggregates == live report
    for level in ("ESSENTIAL", "MODERATE", "DEBUG"):
        parsed = operator_metrics(app, 0, level)
        live = [tuple(r) for r in last_query_metrics(s, level)]
        assert parsed == live and parsed
    # the header makes it a well-formed application for the tools
    assert app.app_id.startswith("tpu-")
    assert app.spark_props  # EnvironmentUpdate carried the session conf
    # span records replay from the log
    assert any(sp.get("kind") == "operator" for sp in app.spans)
    assert any(sp["name"].startswith("phase:") for sp in app.spans)


def test_accuracy_report_predicted_vs_actual(tmp_path):
    s = _session(tmp_path,
                 **{"spark.rapids.tpu.memsan.enabled": True})
    (s.create_dataframe(_table(), num_partitions=2)
     .group_by(col("k")).agg(F.sum(col("v")).alias("sv")).collect())
    app = parse_event_log(_only_log(tmp_path))
    rows = accuracy_report(app)
    assert rows, "self-emitted plan must carry tpuPrediction/tpuActual"
    r = rows[0]
    assert {"node", "predictedRows", "actualRows", "rowsErr",
            "predictedBytes", "actualBytes", "bytesErr"} <= set(r)
    # ranked worst-first by row error
    errs = [x["rowsErr"] for x in rows]
    assert errs == sorted(errs, reverse=True)
    # memsan on: the query-level peak pair rides SQLExecutionEnd
    sx = app.sql_executions[0]
    assert sx.peak_device_bytes is not None
    assert sx.static_peak_bound is not None
    assert sx.peak_device_bytes <= sx.static_peak_bound


def test_failure_flushes_with_error_status(tmp_path, monkeypatch):
    from spark_rapids_tpu.exec import basic as xb
    s = _session(tmp_path)
    df = s.create_dataframe(_table(64)).filter(col("v") > 3)

    def boom(self, pid, ctx):
        raise RuntimeError("injected-operator-failure")
        yield  # pragma: no cover

    monkeypatch.setattr(xb.FilterExec, "execute_partition", boom)
    with pytest.raises(RuntimeError, match="injected-operator-failure"):
        df.collect()
    tr = s.last_query_trace()
    assert tr is not None and tr.sealed
    assert tr.open_span_count() == 0, "spans must close on failure"
    assert "injected-operator-failure" in (tr.error or "")
    err_spans = [sp for sp in tr.spans if sp.status == "error"]
    assert err_spans and any(sp.error and "injected" in sp.error
                             for sp in err_spans)
    app = parse_event_log(_only_log(tmp_path))
    assert app.sql_executions[0].failed  # JobFailed in the log
    # the session stays usable and the NEXT query appends sql_id 1
    monkeypatch.undo()
    s.create_dataframe(_table(64)).filter(col("v") > 3).collect()
    app = parse_event_log(_only_log(tmp_path))
    assert sorted(app.sql_executions) == [0, 1]
    assert not app.sql_executions[1].failed


def test_trace_of_a_join_is_sealed(tmp_path):
    # a traced join (its build, probe and sizing spans, the blocking
    # fetch inside them) must leave a clean, sealed trace
    s = _session(tmp_path)
    left = s.create_dataframe(_table(128))
    right = s.create_dataframe(pa.table({
        "k": pa.array(np.arange(9, dtype=np.int64)),
        "w": pa.array(np.arange(9, dtype=np.float64))}))
    out = left.join(right, on="k", how="inner").collect()
    assert out.num_rows == 128
    tr = s.last_query_trace()
    assert tr.sealed and tr.open_span_count() == 0


def test_metrics_report_single_fetch_crossing(monkeypatch):
    from spark_rapids_tpu.columnar import fetch as fetch_mod
    from spark_rapids_tpu.exec.base import Exec, metrics_report

    class _Leaf(Exec):
        def __init__(self):
            super().__init__([])

        @property
        def output_names(self):
            return []

        @property
        def output_types(self):
            return []

    root, child = _Leaf(), _Leaf()
    root.children = [child]
    # six metrics carrying pending DEVICE scalars across two operators
    for node in (root, child):
        for m in node.metrics.values():
            m.add(jnp.asarray(5))
            m.add(jnp.asarray(2))
    calls = []
    orig = fetch_mod.fetch_ints

    def counting(vals):
        calls.append(len(list(vals)))
        return orig(vals)

    monkeypatch.setattr(fetch_mod, "fetch_ints", counting)
    rows = metrics_report(root, "DEBUG")
    assert len(calls) == 1, \
        f"expected ONE fetch crossing, saw {len(calls)}"
    assert calls[0] == 12  # every pending scalar rode the one transfer
    assert all(v == 7 for _, _, v in rows)
    # drained: a second report costs zero crossings
    metrics_report(root, "DEBUG")
    assert len(calls) == 1


def test_tracing_off_records_nothing():
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True).get_or_create())
    s.create_dataframe(_table(32)).filter(col("v") > 1).collect()
    assert s.last_query_trace() is None
