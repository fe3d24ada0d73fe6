"""Spark-facing bridge tests: a fake-JVM process plays the executor's
role (ref Plugin.scala:44-51 ColumnarRule replacing subtrees), shipping
a scan->filter->aggregate stage as a JSON plan spec + Arrow IPC stream
to a REAL sidecar subprocess, and checks the results against an
independent oracle — the smallest honest end-to-end proof that a Spark
query's aggregate executes inside this engine."""

import os
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu.bridge import BridgeClient, SidecarServer
from spark_rapids_tpu.bridge.client import BridgeError


@pytest.fixture(scope="module")
def sidecar():
    """A real sidecar OS process, discovered via its stdout handshake."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.bridge.sidecar"],
        stdout=subprocess.PIPE, env=env, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    port = None
    deadline = time.time() + 120
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("TPU_SIDECAR_PORT="):
            port = int(line.strip().split("=")[1])
            break
    assert port, "sidecar never announced its port"
    yield port
    c = BridgeClient(port)
    c.shutdown_sidecar()
    c.close()
    proc.wait(timeout=10)


def _fact(n=20000):
    rng = np.random.default_rng(8)
    return pa.table({
        "k": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })


def test_scan_filter_aggregate_stage(sidecar):
    tb = _fact()
    spec = {
        "ops": [
            {"op": "filter",
             "condition": {"op": "gt",
                           "children": [{"col": "v"},
                                        {"lit": 0, "type": "bigint"}]}},
            {"op": "aggregate",
             "groupBy": [{"col": "k"}],
             "aggs": [{"fn": "sum", "expr": {"col": "v"}, "name": "sv"},
                      {"fn": "count", "expr": {"col": "v"}, "name": "c"}]},
            {"op": "sort",
             "orders": [{"expr": {"col": "k"}, "ascending": True}]},
        ],
    }
    client = BridgeClient(sidecar)
    assert client.ping()
    got = client.execute_stage(spec, tb)
    client.close()

    flt = tb.filter(pc.greater(tb.column("v"), 0))
    want = pa.TableGroupBy(flt, ["k"], use_threads=False).aggregate(
        [("v", "sum"), ("v", "count")]).sort_by("k")
    assert got.column("k").to_pylist() == want.column("k").to_pylist()
    assert got.column("sv").to_pylist() == want.column("v_sum").to_pylist()
    assert got.column("c").to_pylist() == want.column("v_count").to_pylist()


def test_project_and_limit_stage(sidecar):
    tb = _fact(500)
    spec = {
        "ops": [
            {"op": "project",
             "exprs": [{"expr": {"col": "k"}, "name": "k"},
                       {"expr": {"op": "mul",
                                 "children": [{"col": "v"},
                                              {"lit": 2,
                                               "type": "bigint"}]},
                        "name": "v2"}]},
            {"op": "sort",
             "orders": [{"expr": {"col": "v2"}, "ascending": False}]},
            {"op": "limit", "n": 5},
        ],
    }
    client = BridgeClient(sidecar)
    got = client.execute_stage(spec, tb)
    client.close()
    want = sorted((2 * v for v in tb.column("v").to_pylist()),
                  reverse=True)[:5]
    assert got.column("v2").to_pylist() == want


def test_bad_stage_reports_error_and_sidecar_survives(sidecar):
    tb = _fact(100)
    client = BridgeClient(sidecar)
    with pytest.raises(BridgeError, match="unsupported bridge"):
        client.execute_stage(
            {"ops": [{"op": "frobnicate"}]}, tb)
    # same connection still serves good stages
    got = client.execute_stage(
        {"ops": [{"op": "aggregate", "groupBy": [],
                  "aggs": [{"fn": "count", "expr": {"col": "k"},
                            "name": "c"}]}]}, tb)
    client.close()
    assert got.column("c").to_pylist() == [100]


def test_spec_roundtrip_in_process():
    """plan_spec_to_logical is usable without the socket layer (the unit
    seam a JVM-side test suite would target)."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.bridge import plan_spec_to_logical
    tb = _fact(1000)
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    lp = plan_spec_to_logical(
        {"ops": [{"op": "aggregate", "groupBy": [{"col": "k"}],
                  "aggs": [{"fn": "max", "expr": {"col": "v"},
                            "name": "m"}]}]}, tb)
    out = s.execute(lp).sort_by("k")
    want = pa.TableGroupBy(tb, ["k"], use_threads=False).aggregate(
        [("v", "max")]).sort_by("k")
    assert out.column("m").to_pylist() == want.column("v_max").to_pylist()


def test_join_stage_two_streams(sidecar):
    """Multi-input stage: the fake JVM ships TWO Arrow streams and a
    join op referencing the second (ref GpuOverrides.scala:3164 — the
    exec registry replaces joins too)."""
    client = BridgeClient(sidecar)
    try:
        rng = np.random.default_rng(12)
        fact = pa.table({
            "k": pa.array(rng.integers(0, 50, 2000).astype(np.int64)),
            "v": pa.array(rng.integers(-99, 99, 2000).astype(np.int64)),
        })
        dim = pa.table({
            "k": pa.array(np.arange(40, dtype=np.int64)),
            "w": pa.array(np.arange(40, dtype=np.int64) * 3),
        })
        spec = {
            "input": {"schema": [["k", "bigint"], ["v", "bigint"]]},
            "inputs": [{"schema": [["k", "bigint"], ["w", "bigint"]]}],
            "ops": [
                {"op": "join", "right": 1, "how": "inner", "on": ["k"]},
                {"op": "aggregate", "groupBy": [{"col": "k"}],
                 "aggs": [{"fn": "sum", "expr": {"col": "w"},
                           "name": "sw"},
                          {"fn": "count", "expr": None, "name": "c"}]},
                {"op": "sort",
                 "orders": [{"expr": {"col": "k"}, "ascending": True}]},
            ],
        }
        out = client.execute_stage(spec, fact, [dim])
        joined = fact.join(dim, keys="k", join_type="inner")
        want = pa.TableGroupBy(joined, ["k"], use_threads=False).aggregate(
            [("w", "sum"), ("k", "count")]).sort_by("k")
        assert out.column("k").to_pylist() == want.column("k").to_pylist()
        assert out.column("sw").to_pylist() == \
            want.column("w_sum").to_pylist()
        assert out.column("c").to_pylist() == \
            want.column("k_count").to_pylist()
    finally:
        client.close()


def test_window_stage(sidecar):
    """Window frames over the bridge: row_number + running sum."""
    client = BridgeClient(sidecar)
    try:
        tb = pa.table({
            "g": pa.array([1, 1, 1, 2, 2], type=pa.int64()),
            "o": pa.array([3, 1, 2, 2, 1], type=pa.int64()),
            "v": pa.array([10, 20, 30, 40, 50], type=pa.int64()),
        })
        spec = {
            "input": {"schema": [["g", "bigint"], ["o", "bigint"],
                                 ["v", "bigint"]]},
            "ops": [
                {"op": "window",
                 "partitionBy": [{"col": "g"}],
                 "orderBy": [{"expr": {"col": "o"}, "ascending": True}],
                 "funcs": [{"fn": "row_number", "name": "rn"},
                           {"fn": "sum", "expr": {"col": "v"},
                            "name": "rs"}]},
                {"op": "sort",
                 "orders": [{"expr": {"col": "g"}, "ascending": True},
                            {"expr": {"col": "o"}, "ascending": True}]},
            ],
        }
        out = client.execute_stage(spec, tb)
        # oracle by hand: per (g) ordered by o
        assert out.column("g").to_pylist() == [1, 1, 1, 2, 2]
        rows = list(zip(out.column("g").to_pylist(),
                        out.column("o").to_pylist(),
                        out.column("rn").to_pylist(),
                        out.column("rs").to_pylist()))
        assert rows == [(1, 1, 1, 20), (1, 2, 2, 50), (1, 3, 3, 60),
                        (2, 1, 1, 50), (2, 2, 2, 90)]
    finally:
        client.close()
