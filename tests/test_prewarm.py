"""Warm-start tier round trip (obs/prewarm.py).

Session A builds programs with a compile ledger configured, which
persists one recipe per program (key + stubbed traced callable +
abstract arg signatures).  A "new session" (observatory + jit table
reset — process death in miniature) replays the ledger's recipes and
must then run the same query with ZERO builds: every call is served by
a prewarmed executable, counted in prewarm_hits and the
tpu_jit_prewarm_* metric families.
"""

import os

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.columnar.fetch as fetch
import spark_rapids_tpu.exec.base as eb
import spark_rapids_tpu.obs.metrics as obs_metrics
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.obs.compileprof import CompileObservatory
from spark_rapids_tpu.obs.prewarm import (prewarm_from_ledger,
                                          rank_ledger_programs,
                                          recipes_dir)


@pytest.fixture
def fresh():
    obs_metrics.MetricsRegistry.reset_for_tests()
    CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    # the speculative-fetch plan memo is schema-keyed: an earlier test
    # fetching the same schema at another capacity would arm a doomed
    # speculation here, adding a one-shot program run 2 never dispatches
    fetch._LAST_PLAN.clear()
    yield
    eb.clear_jit_cache()
    CompileObservatory.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()


def _run_query(session):
    n = 1500
    tbl = pa.table({
        "k": pa.array((np.arange(n) % 5).astype(np.int64)),
        "v": pa.array(np.arange(n, dtype=np.int64)),
    })
    df = session.create_dataframe(tbl)
    out = (df.filter(col("v") > 10)
           .select(col("k"), (col("v") * 3).alias("x"))
           .collect())
    v = np.arange(n, dtype=np.int64)
    np.testing.assert_array_equal(
        np.sort(out.column("x").to_numpy()), np.sort(v[v > 10] * 3))


def test_prewarm_round_trip(fresh, tmp_path):
    ledger_dir = str(tmp_path / "hist")
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .config("spark.rapids.tpu.compile.ledgerDir", ledger_dir)
         .get_or_create())
    ledger_path = CompileObservatory.get().ledger_path
    assert ledger_path

    _run_query(s)
    built = CompileObservatory.get().snapshot()["builds"]
    assert built > 0
    rdir = recipes_dir(ledger_path)
    assert os.path.isdir(rdir) and len(os.listdir(rdir)) == built
    assert len(rank_ledger_programs(ledger_path)) == built

    # "next session": fresh observatory + empty jit table, replay
    obs_metrics.MetricsRegistry.reset_for_tests()
    obs2 = CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    fetch._LAST_PLAN.clear()
    obs2.configure(enabled=True, ledger_path=ledger_path)
    stats = prewarm_from_ledger(ledger_path, top_k=32)
    assert stats["recipes"] == built
    assert stats["programs"] >= built
    assert stats["errors"] == 0

    _run_query(s)
    snap = obs2.snapshot()
    assert snap["builds"] == 0, (
        f"prewarmed session still compiled: {snap['by_cause']}")
    assert snap["prewarm_hits"] == built, (
        f"unclaimed staged keys: {list(obs2._prewarm_staged)}")
    assert obs_metrics.registry().counter(
        "tpu_jit_prewarm_seconds_total").value() > 0


def test_prewarm_missing_ledger_is_noop(fresh, tmp_path):
    stats = prewarm_from_ledger(str(tmp_path / "nope.jsonl"), top_k=8)
    assert stats == {"recipes": 0, "programs": 0, "skipped": 0,
                     "errors": 0, "seconds": 0.0}
