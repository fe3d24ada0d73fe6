"""Real-Spark bridge integration: runs ONLY where pyspark is importable
(CI; the hermetic engine environment ships no Spark — there the protocol
is proven by the fake-JVM harness in test_bridge.py).

The loop: a pyspark DataFrame's collected partitions ship through the
sidecar protocol exactly as the Scala TpuBridgeExec would (bridge-jvm/
README.md), and the sidecar-computed stage must match Spark's own
result.  This drives the same spec JSON the Scala SpecBuilder emits.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

pyspark = pytest.importorskip("pyspark")

from spark_rapids_tpu.bridge import BridgeClient  # noqa: E402


@pytest.fixture(scope="module")
def spark():
    from pyspark.sql import SparkSession
    s = (SparkSession.builder.master("local[2]")
         .appName("tpu-bridge-it").getOrCreate())
    yield s
    s.stop()


@pytest.fixture(scope="module")
def sidecar():
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
    proc.kill()


def test_spark_aggregate_through_sidecar(spark, sidecar):
    sdf = spark.range(0, 10_000).selectExpr(
        "id % 37 as k", "id as v", "cast(id as double) / 7 as f")
    # what TpuBridgeRule would emit for
    #   scan -> filter(v > 100) -> groupBy(k).agg(sum(v), count(*))
    spec = {
        "input": {"schema": [["k", "bigint"], ["v", "bigint"],
                             ["f", "double"]]},
        "ops": [
            {"op": "filter", "condition": {
                "op": "gt", "children": [{"col": "v"},
                                         {"lit": 100, "type": "bigint"}]}},
            {"op": "aggregate", "groupBy": [{"col": "k"}],
             "aggs": [{"fn": "sum", "expr": {"col": "v"}, "name": "sv"},
                      {"fn": "count", "expr": None, "name": "c"}]},
            {"op": "sort", "orders": [{"expr": {"col": "k"},
                                       "ascending": True}]},
        ],
    }
    table = pa.Table.from_pandas(sdf.toPandas())
    client = BridgeClient(sidecar)
    try:
        got = client.execute_stage(spec, table)
    finally:
        client.close()
    want = (sdf.filter("v > 100").groupBy("k")
            .agg({"v": "sum", "*": "count"})
            .withColumnRenamed("sum(v)", "sv")
            .withColumnRenamed("count(1)", "c")
            .orderBy("k").toPandas())
    assert got.column("k").to_pylist() == want["k"].tolist()
    assert got.column("sv").to_pylist() == want["sv"].tolist()
    assert got.column("c").to_pylist() == want["c"].tolist()
