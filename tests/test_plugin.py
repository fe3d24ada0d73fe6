"""Plugin bootstrap + shim layer tests (ref Plugin.scala lifecycle,
ShimLoader/SparkShims selection)."""

import pyarrow as pa
import pytest

from spark_rapids_tpu.plugin import (ExecutionPlanCaptureCallback,
                                     PluginInitError, TpuDriverPlugin,
                                     TpuExecutorPlugin, fixup_configs)
from spark_rapids_tpu.shims import (ShimLoader, Spark301Shims, Spark311Shims,
                                    Spark320Shims)


def test_fixup_configs_forces_extension():
    out = fixup_configs({})
    assert "SQLExecPlugin" in out["spark.sql.extensions"]
    # idempotent
    again = fixup_configs(out)
    assert again["spark.sql.extensions"].count("SQLExecPlugin") == 1


def test_driver_executor_lifecycle_and_heartbeats():
    drv = TpuDriverPlugin({})
    drv.init()
    ex1 = TpuExecutorPlugin({}, driver=drv, executor_id="1")
    ex1.init()
    ex2 = TpuExecutorPlugin({}, driver=drv, executor_id="2")
    ex2.init()
    # second executor's heartbeat learns about the first (ref
    # RapidsShuffleHeartbeatManager.executorHeartbeat)
    resp = drv.receive({"kind": "heartbeat", "executor_id": "2"})
    assert resp["ok"]
    peer_ids = {p["executor_id"] for p in resp["peers"]}
    assert "1" in peer_ids
    ex1.shutdown()
    ex2.shutdown()
    drv.shutdown()


def test_version_handshake_passes_on_current_runtime():
    assert TpuExecutorPlugin.check_runtime_versions() == []


def test_unknown_rpc_message():
    drv = TpuDriverPlugin({})
    drv.init()
    assert not drv.receive({"kind": "bogus"})["ok"]


def test_shim_selection_by_version():
    assert isinstance(ShimLoader.get_shim("3.0.1"), Spark301Shims)
    assert isinstance(ShimLoader.get_shim("3.1.2"), Spark311Shims)
    assert isinstance(ShimLoader.get_shim("3.2.0"), Spark320Shims)
    with pytest.raises(ValueError):
        ShimLoader.get_shim("2.4.8")


def test_shim_behavior_deltas():
    s30 = ShimLoader.get_shim("3.0.1")
    s32 = ShimLoader.get_shim("3.2.0")
    assert s30.legacy_statistical_aggregate() and \
        not s32.legacy_statistical_aggregate()
    assert s30.parquet_rebase_mode_default() == "LEGACY"
    assert s32.parquet_rebase_mode_default() == "CORRECTED"
    assert s30.aqe_shuffle_read_name() == "CustomShuffleReader"
    assert s32.aqe_shuffle_read_name() == "AQEShuffleRead"
    assert not s30.cached_batch_serializer_supported()


def test_session_uses_plugins_and_capture_callback(tpu_session):
    from spark_rapids_tpu.api.session import TpuSession
    s = TpuSession.builder().config("spark.rapids.sql.enabled",
                                    True).get_or_create()
    assert s.executor_plugin is not None
    assert s.driver_plugin is not None
    assert s.shim.version.startswith("3.2")
    ExecutionPlanCaptureCallback.start_capture()
    df = s.create_dataframe(pa.table({"x": pa.array([1, 2, 3])}))
    df.collect()
    plans = ExecutionPlanCaptureCallback.get_resulting_plans()
    assert plans
    assert ExecutionPlanCaptureCallback.assert_contains(
        plans[-1], "LocalScanExec")


def test_generated_docs_are_fresh(tmp_path):
    """The committed docs must match the live registries (the reference
    regenerates docs/configs.md + supported_ops.md from code the same
    way; ref TypeChecks.scala:1633).

    Generated the way they are committed, `python -m
    spark_rapids_tpu.docsgen` in a process of its own: the registries
    then hold what docsgen itself imports, not whatever the tests that
    ran before in this worker registered (an opted-in Hive rule, say),
    so the result does not follow the order of the test files."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    subprocess.run([sys.executable, "-m", "spark_rapids_tpu.docsgen",
                    str(tmp_path)], check=True, cwd=root, env=env,
                   timeout=300, capture_output=True)
    for name in ("configs.md", "supported_ops.md"):
        with open(os.path.join(root, "docs", name)) as f:
            committed = f.read()
        assert committed == (tmp_path / name).read_text(), \
            f"docs/{name} is stale — run python -m spark_rapids_tpu.docsgen"
