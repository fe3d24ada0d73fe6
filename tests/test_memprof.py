"""HBM observatory tests (obs/memprof.py): timeline algebra against
the spill catalog, ring-buffer bounds under churn, per-tenant
attribution exactness under thread stress, the failure black box
(obs/postmortem.py + `tools postmortem`), and the disabled no-op path.

Everything runs in the shared tier-1 process, so every test restores
the process-global MemoryTimeline singleton it reconfigures."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.device import batch_to_device
from spark_rapids_tpu.memory.spill import SpillCatalog
from spark_rapids_tpu.obs import memprof
from spark_rapids_tpu.obs.memprof import (SHUFFLE_BLOCK, WORKING_SET,
                                          MemoryTimeline,
                                          active_timeline)


@pytest.fixture
def fresh_timeline():
    MemoryTimeline.reset_for_tests()
    tl = MemoryTimeline.configure(enabled=True)
    yield tl
    MemoryTimeline.reset_for_tests()


def _batch(n=500, seed=0):
    rng = np.random.default_rng(seed)
    rb = pa.record_batch({
        "a": pa.array(rng.integers(0, 100, n).astype(np.int64)),
        "b": pa.array(rng.random(n))})
    return batch_to_device(rb, xp=np)


# -- timeline algebra ---------------------------------------------------------

def test_timeline_reconciles_with_spill_catalog(tmp_path,
                                                fresh_timeline):
    """At every lifecycle step the timeline's spill-backed live bytes
    must equal the catalog's registered device bytes, and the sample
    deltas must sum to the final per-(tenant, class) live values —
    the three-sinks invariant the --hbm gate replays end to end."""
    tl = fresh_timeline
    cat = SpillCatalog(device_budget=1 << 30, host_budget=1 << 30,
                       spill_dir=str(tmp_path))
    memprof.push_context("tenant-a", "q1")
    try:
        sbs = [cat.register(_batch(seed=i)) for i in range(3)]
        assert cat.device_bytes_registered() > 0
        assert tl.spill_backed_bytes() == cat.device_bytes_registered()
        sbs[0].spill_to_host()
        assert tl.spill_backed_bytes() == cat.device_bytes_registered()
        back = sbs[0].get_batch(np)     # unspill: bytes return
        assert back is not None
        assert tl.spill_backed_bytes() == cat.device_bytes_registered()
        for sb in sbs:
            sb.close()
        assert cat.device_bytes_registered() == 0
        assert tl.spill_backed_bytes() == 0
    finally:
        memprof.pop_context()
    sums = {}
    for s in tl.window(10_000):
        key = (s["tenant"], s["class"])
        sums[key] = sums.get(key, 0) + s["delta"]
    for (tenant, cls), total in sums.items():
        assert total == tl.live_bytes(bclass=cls, tenant=tenant)


def test_arena_algebra_and_reset(fresh_timeline):
    """Arena fills book as used-after deltas (alignment padding
    reconciles exactly); reset returns every tenant's staging bytes."""
    tl = fresh_timeline
    memprof.push_context("tenant-b", "q2")
    try:
        tl.on_arena_alloc("ar1", 1024, 1 << 20)
        tl.on_arena_alloc("ar1", 3072, 1 << 20)
        assert tl.arena_bytes() == 3072
        rep = tl.report()
        assert rep["tenants"]["tenant-b"]["arena_staging_bytes"] == 3072
        # staging bytes are not device residency
        assert rep["tenants"]["tenant-b"]["resident_bytes"] == 0
        tl.on_arena_reset("ar1")
        assert tl.arena_bytes() == 0
    finally:
        memprof.pop_context()


def test_report_occupancy_split(fresh_timeline):
    """pinned vs demotable vs closed-pending split and the per-tenant
    demotable peak used by bench --serve."""
    tl = fresh_timeline
    memprof.push_context("t", "q")
    try:
        tl.on_alloc("h1", 1000, SHUFFLE_BLOCK)
        tl.on_alloc("h2", 2000, WORKING_SET)
        tl.on_pin("h3", 4000)
        tl.on_broadcast("h4", 8000)
        row = tl.report()["tenants"]["t"]
        assert row["demotable_bytes"] == 3000
        assert row["pinned_bytes"] == 4000
        assert row["closed_pending_bytes"] == 8000
        assert row["resident_bytes"] == 15000
        assert row["peak_demotable_bytes"] == 3000
        tl.on_close("h1")
        tl.on_close("h2")
        row = tl.report()["tenants"]["t"]
        assert row["demotable_bytes"] == 0
        assert row["peak_demotable_bytes"] == 3000   # peak survives
    finally:
        memprof.pop_context()


def test_admission_tickets_tracked(fresh_timeline):
    tl = fresh_timeline
    tl.note_ticket("t", 5000)
    tl.note_ticket("t", 2500)      # reprice up
    assert tl.report()["tenants"]["t"]["admitted_bytes"] == 7500
    tl.note_ticket("t", -7500)     # release zeroes out
    assert "t" not in tl.report()["tenants"]


# -- ring-buffer bounds -------------------------------------------------------

def test_ring_buffer_bounded_under_churn():
    MemoryTimeline.reset_for_tests()
    try:
        tl = MemoryTimeline.configure(enabled=True, max_samples=64)
        memprof.push_context("churn", "q")
        try:
            for i in range(500):
                tl.on_alloc(f"h{i}", 128, WORKING_SET)
                tl.on_close(f"h{i}")
        finally:
            memprof.pop_context()
        assert tl.sample_count() <= 64
        assert tl.samples_dropped > 0
        assert tl.live_bytes() == 0      # churn closed everything
        # the window holds the MOST RECENT samples
        assert tl.window(64)[-1]["delta"] == -128
    finally:
        MemoryTimeline.reset_for_tests()


def test_max_samples_clamped_to_floor():
    MemoryTimeline.reset_for_tests()
    try:
        tl = MemoryTimeline.configure(enabled=True, max_samples=1)
        assert tl.max_samples == 64
    finally:
        MemoryTimeline.reset_for_tests()


# -- per-tenant attribution under thread stress -------------------------------

def test_per_tenant_attribution_exact_under_threads(fresh_timeline):
    """8 threads booking under 4 tenants concurrently: every tenant's
    final occupancy must equal its own allocations exactly — no
    cross-tenant bleed, no unattributed events."""
    tl = fresh_timeline
    n_threads, per = 8, 50

    def worker(i):
        tenant = f"t{i % 4}"
        memprof.push_context(tenant, f"q{i}")
        try:
            for j in range(per):
                hid = f"h-{i}-{j}"
                tl.on_alloc(hid, 1000, SHUFFLE_BLOCK)
                if j % 2:
                    tl.on_close(hid)
        finally:
            memprof.pop_context()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rep = tl.report()
    # 2 threads per tenant, each leaving 25 of 50 allocations live
    for tenant in ("t0", "t1", "t2", "t3"):
        assert rep["tenants"][tenant]["demotable_bytes"] == 2 * 25 * 1000
    assert rep["unattributed_events"] == 0
    assert rep["total_bytes"] == 4 * 2 * 25 * 1000


def test_context_free_thread_counts_as_unattributed(fresh_timeline):
    tl = fresh_timeline
    done = []

    def rogue():
        tl.on_alloc("rogue-h", 512, WORKING_SET)
        done.append(True)

    t = threading.Thread(target=rogue)
    t.start()
    t.join()
    assert done
    rep = tl.report()
    assert rep["unattributed_events"] == 1
    assert rep["tenants"][memprof.UNATTRIBUTED_TENANT][
        "demotable_bytes"] == 512


def test_context_stack_nests(fresh_timeline):
    memprof.push_context("outer", "q1")
    memprof.push_context("inner", "q2")
    assert memprof.current_context() == ("inner", "q2")
    memprof.pop_context()
    assert memprof.current_context() == ("outer", "q1")
    memprof.pop_context()
    assert memprof.current_context() is None


# -- failure black box --------------------------------------------------------

def test_postmortem_bundle_on_injected_failure(tmp_path, capsys):
    """An injected operator failure must leave exactly one bundle that
    parses, names FilterExec as the culprit with the owning tenant and
    HBM occupancy, and renders through `tools postmortem`."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.testing.faults import arm_filter, disarm_filter
    from spark_rapids_tpu.obs import postmortem as pm
    from spark_rapids_tpu.tools.__main__ import main as tools_main

    MemoryTimeline.reset_for_tests()
    try:
        s = TpuSession({
            "spark.rapids.sql.enabled": "true",
            "spark.rapids.tpu.trace.enabled": "true",
            "spark.rapids.tpu.singleChipFuse": "off",
            "spark.rapids.tpu.hbm.postmortem.dir": str(tmp_path),
        })
        s._tenant = "tenant-pm"
        tb = pa.table({
            "k": pa.array(np.arange(400, dtype=np.int64) % 7),
            "v": pa.array(np.arange(400, dtype=np.int64)),
        })
        def boom(self, pid, ctx, *consumer):
            # generator: raises at first pull, inside FilterExec's span
            raise RuntimeError("injected failure for postmortem test")
            yield

        armed = arm_filter(boom)
        try:
            from spark_rapids_tpu.api import functions as F
            from spark_rapids_tpu.api.column import col
            with pytest.raises(RuntimeError, match="injected failure"):
                (s.create_dataframe(tb)
                 .filter(col("v") >= 0)
                 .group_by(col("k"))
                 .agg(F.sum(col("v")).alias("sv"))
                 .collect())
        finally:
            disarm_filter(armed)

        bundles = pm.list_bundles(str(tmp_path))
        assert len(bundles) == 1
        doc = pm.load_bundle(bundles[0])
        assert doc["version"] == pm.BUNDLE_VERSION
        assert doc["kind"] == "query_failure"
        assert doc["tenant"] == "tenant-pm"
        assert "injected failure" in doc["error"]["message"]
        assert "FilterExec" in doc["failing_operator"]["operator"]
        assert "report" in doc["hbm"]
        # renders through the CLI, naming the culprit and the tenant
        rc = tools_main(["postmortem", str(tmp_path)])
        assert not rc
        out = capsys.readouterr().out
        assert "FilterExec" in out
        assert "tenant-pm" in out
    finally:
        MemoryTimeline.reset_for_tests()


def test_postmortem_retention_cap(tmp_path):
    from spark_rapids_tpu.obs import postmortem as pm
    paths = [pm.dump_postmortem(str(tmp_path), RuntimeError(f"e{i}"),
                                max_bundles=2)
             for i in range(5)]
    assert all(p is not None for p in paths)
    kept = pm.list_bundles(str(tmp_path))
    assert len(kept) == 2
    # the newest bundles survive the cap
    assert sorted(kept) == sorted(paths[-2:])


def test_postmortem_classifies_admission_timeout(tmp_path):
    from spark_rapids_tpu.memory.admission import AdmissionTimeout
    from spark_rapids_tpu.obs import postmortem as pm
    path = pm.dump_postmortem(str(tmp_path),
                              AdmissionTimeout("budget exhausted"))
    doc = pm.load_bundle(path)
    assert doc["kind"] == "admission_timeout"


# -- disabled no-op path ------------------------------------------------------

def test_disabled_path_is_noop(tmp_path):
    MemoryTimeline.reset_for_tests()
    try:
        tl = MemoryTimeline.configure(enabled=False)
        assert active_timeline() is None
        cat = SpillCatalog(device_budget=1 << 30, host_budget=1 << 30,
                           spill_dir=str(tmp_path))
        memprof.push_context("t", "q")
        try:
            sb = cat.register(_batch())
            # the hook sites saw a disabled timeline: nothing recorded
            assert tl.sample_count() == 0
            assert tl.live_bytes() == 0
            sb.close()
        finally:
            memprof.pop_context()
        rep = tl.report()
        assert rep["enabled"] is False
        assert rep["total_bytes"] == 0
        assert rep["tenants"] == {}
    finally:
        MemoryTimeline.reset_for_tests()
