"""Continuous-metrics registry, Prometheus/health exposition, and the
instrumented-subsystem feeds (obs/metrics.py + obs/health.py).

Covers the ISSUE-5 test checklist: histogram bucket math, the
cardinality-cap overflow path, a concurrent-increment race, a Prometheus
exposition golden, the health JSON schema/status derivation, and the
TPU-R007 module-tally lint rule."""

import json
import threading
import urllib.request

import pytest

from spark_rapids_tpu.obs import metrics as M
from spark_rapids_tpu.obs.health import (DEGRADED, DOWN, OK,
                                         HealthMonitor, MetricsServer,
                                         render_prometheus)


@pytest.fixture()
def reg():
    r = M.MetricsRegistry.reset_for_tests()
    yield r
    M.MetricsRegistry.reset_for_tests()


# ---------------------------------------------------------------------------
# counters / gauges
# ---------------------------------------------------------------------------

def test_counter_basics(reg):
    c = reg.counter("t_total", "doc")
    c.inc()
    c.inc(4)
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_counter_labels_and_value(reg):
    c = reg.counter("t_by_kind_total", "doc", ("kind",))
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc(3)
    assert c.value(kind="a") == 2
    assert c.value(kind="b") == 3
    assert c.value(kind="missing") == 0
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no unlabeled series


def test_gauge_set_inc_dec(reg):
    g = reg.gauge("t_gauge", "doc")
    g.set(10)
    g.gauge_inc(5)
    g.dec(3)
    assert g.value() == 12


def test_family_reregistration_must_match(reg):
    reg.counter("t_same", "doc")
    reg.counter("t_same", "doc")  # idempotent
    with pytest.raises(ValueError):
        reg.gauge("t_same", "doc")
    with pytest.raises(ValueError):
        reg.counter("t_same", "doc", ("extra",))


def test_disabled_registry_is_inert(reg):
    c = reg.counter("t_off_total", "doc")
    c.inc(7)
    reg.enabled = False
    c.inc(100)
    reg.counter("t_off2_total", "doc").inc()
    reg.enabled = True
    assert c.value() == 7
    assert reg.counter("t_off2_total", "doc").value() == 0


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------

def test_histogram_bucket_math(reg):
    h = reg.histogram("t_lat_seconds", "doc", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
        h.observe(v)
    (_, ch), = h.series()
    # boundaries are INCLUSIVE upper bounds (le semantics)
    assert ch.bucket_counts == [2, 2, 1, 1]
    cum = ch.cumulative()
    assert cum == [(0.1, 2), (1.0, 4), (10.0, 5), (float("inf"), 6)]
    assert ch.count == 6
    assert ch.sum == pytest.approx(106.65)


def test_histogram_fixed_buckets_sorted(reg):
    h = reg.histogram("t_h2", "doc", buckets=(5, 1, 3))
    h.observe(2)
    (_, ch), = h.series()
    assert ch.bounds == (1, 3, 5)
    assert ch.bucket_counts == [0, 1, 0, 0]


# ---------------------------------------------------------------------------
# cardinality cap
# ---------------------------------------------------------------------------

def test_cardinality_cap_evicts_into_overflow(reg):
    c = reg.counter("t_capped_total", "doc", ("q",))
    fam = c
    for i in range(M.DEFAULT_MAX_SERIES):
        fam.labels(q=f"q{i}").inc()
    assert fam.overflowed == 0
    # past the cap: new label sets collapse into one _overflow series
    fam.labels(q="straw1").inc()
    fam.labels(q="straw2").inc(2)
    assert fam.overflowed == 2
    assert fam.value(q="straw1") == 0  # never materialized
    assert fam.value(q=M.OVERFLOW_LABEL) == 3
    # existing series keep working past the cap
    fam.labels(q="q0").inc()
    assert fam.value(q="q0") == 2
    assert reg.overflow_total() == 2
    # the hard cap holds: at most max_series real series + 1 overflow
    assert len(fam.series()) <= M.DEFAULT_MAX_SERIES + 1


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------

def test_concurrent_increments_are_exact(reg):
    c = reg.counter("t_race_total", "doc", ("lane",))
    n_threads, per = 8, 5000
    start = threading.Barrier(n_threads)

    def worker(i):
        ch = c.labels(lane=str(i % 2))
        start.wait()
        for _ in range(per):
            ch.inc()

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = c.value(lane="0") + c.value(lane="1")
    assert total == n_threads * per


# ---------------------------------------------------------------------------
# Prometheus exposition golden
# ---------------------------------------------------------------------------

def test_prometheus_exposition_golden(reg):
    c = reg.counter("tpu_test_ops_total", "ops by kind", ("kind",))
    c.labels(kind="a").inc(3)
    g = reg.gauge("tpu_test_depth", "queue depth")
    g.set(2)
    h = reg.histogram("tpu_test_lat_seconds", "latency",
                      buckets=(0.5, 2.0))
    h.observe(0.25)
    h.observe(1.0)
    golden = (
        "# HELP tpu_test_depth queue depth\n"
        "# TYPE tpu_test_depth gauge\n"
        "tpu_test_depth 2\n"
        "# HELP tpu_test_lat_seconds latency\n"
        "# TYPE tpu_test_lat_seconds histogram\n"
        'tpu_test_lat_seconds_bucket{le="0.5"} 1\n'
        'tpu_test_lat_seconds_bucket{le="2"} 2\n'
        'tpu_test_lat_seconds_bucket{le="+Inf"} 2\n'
        "tpu_test_lat_seconds_sum 1.25\n"
        "tpu_test_lat_seconds_count 2\n"
        "# HELP tpu_test_ops_total ops by kind\n"
        "# TYPE tpu_test_ops_total counter\n"
        'tpu_test_ops_total{kind="a"} 3\n'
        "# HELP tpu_metrics_series_overflow_total label sets evicted "
        "into _overflow series by the cardinality cap\n"
        "# TYPE tpu_metrics_series_overflow_total counter\n"
        "tpu_metrics_series_overflow_total 0\n")
    assert render_prometheus(reg) == golden


def test_prometheus_label_escaping(reg):
    c = reg.counter("tpu_esc_total", "d", ("p",))
    c.labels(p='we"ird\nvalue\\x').inc()
    text = render_prometheus(reg)
    assert r'tpu_esc_total{p="we\"ird\nvalue\\x"} 1' in text


# ---------------------------------------------------------------------------
# health snapshot schema + status derivation
# ---------------------------------------------------------------------------

def _assert_schema(snap):
    for key in ("status", "timestamp_ms", "components", "queries",
                "series_overflow"):
        assert key in snap, key
    assert snap["status"] in (OK, DEGRADED, DOWN)
    for comp in ("device", "arena", "memory", "shuffle", "queries"):
        assert comp in snap["components"], comp
        assert snap["components"][comp]["status"] in (OK, DEGRADED,
                                                      DOWN)
    for key in ("active", "completed", "failed", "retried"):
        assert key in snap["queries"], key


def test_health_snapshot_schema_and_deltas(reg):
    mon = HealthMonitor(reg)
    snap = _assert_schema_ret(mon.snapshot())
    assert snap["status"] == OK
    # an arena exhaustion since the last snapshot degrades
    reg.counter("tpu_arena_exhaustions_total", "d").inc()
    snap = mon.snapshot()
    assert snap["status"] == DEGRADED
    assert snap["components"]["arena"]["status"] == DEGRADED
    # the counter stopped moving -> next snapshot recovers
    snap = mon.snapshot()
    assert snap["status"] == OK
    # a dirty memsan ledger is DOWN, not degraded
    reg.counter("tpu_memsan_dirty_ledgers_total", "d").inc()
    assert mon.snapshot()["status"] == DOWN
    # dead device probe gauge pins DOWN regardless of deltas
    reg.gauge("tpu_device_probe_ok", "d").set(0)
    snap = mon.snapshot()
    assert snap["status"] == DOWN
    assert snap["components"]["device"]["status"] == DOWN
    reg.gauge("tpu_device_probe_ok", "d").set(1)
    assert mon.snapshot()["status"] == OK
    assert json.loads(json.dumps(snap))  # JSON-serializable throughout


def _assert_schema_ret(snap):
    _assert_schema(snap)
    return snap


def test_http_endpoint_serves_metrics_and_health(reg):
    reg.counter("tpu_endpoint_total", "d").inc(9)
    srv = MetricsServer(0, reg=reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "tpu_endpoint_total 9" in text
        snap = json.loads(urllib.request.urlopen(
            base + "/healthz").read())
        _assert_schema(snap)
        with pytest.raises(Exception):
            urllib.request.urlopen(base + "/nope")
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# TPU-R007: module-level tallies must route through the registry
# ---------------------------------------------------------------------------

def _r007(source):
    from spark_rapids_tpu.analysis.repo_lint import \
        module_tally_diagnostics
    return module_tally_diagnostics(source,
                                    "spark_rapids_tpu/exec/fake.py")


def test_r007_flags_module_tallies():
    diags = _r007(
        "import collections\n"
        "_N_CALLS = 0\n"
        "_HIT_COUNTS = {}\n"
        "_STATS = collections.Counter()\n"
        "_WHATEVER = collections.defaultdict(int)\n")
    assert len(diags) == 4
    assert all(d.code == "TPU-R007" for d in diags)


def test_r007_flags_module_aug_assign():
    diags = _r007("_TOTAL_ROWS = 0\n_TOTAL_ROWS += 5\n")
    assert len(diags) == 2


def test_r007_ignores_tables_caches_and_locals():
    diags = _r007(
        "_PA_JOIN = {'inner': 'inner'}\n"       # lookup table
        "_JIT_CACHE = {}\n"                      # cache, not a tally
        "_LEVEL_ORDER = {'A': 0}\n"
        "MAX_SPANS = 65536\n"                    # limit, not a count...
        "def f():\n"
        "    n_count = 0\n"                      # function-local is fine
        "    n_count += 1\n"
        "    return n_count\n")
    # MAX_SPANS matches no tally word; 'n_count' is not module level
    assert diags == []


def test_r007_allow_annotation_sanctions_in_place(tmp_path):
    """The shared `# tpulint: allow[TPU-R007]` mechanism covers R007
    like every other repo rule."""
    from spark_rapids_tpu.analysis.repo_lint import _allowed_lines
    src = ("# tpulint: allow[TPU-R007] legacy sink, migrating in PR 6\n"
           "_N_CALLS = 0\n")
    diags = _r007(src)
    assert len(diags) == 1
    allowed = _allowed_lines(src)
    lineno = int(diags[0].loc.rsplit(":", 1)[-1])
    assert lineno in allowed["TPU-R007"]


# ---------------------------------------------------------------------------
# device-probe deadline
# ---------------------------------------------------------------------------

def test_discover_devices_timeout_counts_and_raises(reg, monkeypatch):
    import spark_rapids_tpu.parallel.mesh as mesh

    def hang():
        import time
        time.sleep(60)

    monkeypatch.setattr(mesh.jax, "devices", hang)
    with pytest.raises(mesh.DeviceDiscoveryTimeout):
        mesh.discover_devices(timeout_s=0.2)
    c = reg.counter("tpu_device_probe_failures_total", "d")
    assert c.value() == 1
    assert reg.gauge("tpu_device_probe_ok", "d").value() == 0
    # device_count raises too: it never answers "one chip" for a probe
    # that failed, and the ICI planner lets that propagate
    with pytest.raises(mesh.DeviceDiscoveryTimeout):
        mesh.device_count(timeout_s=0.2)
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.parallel.ici_exec import install_ici_stages
    monkeypatch.setenv("SPARK_RAPIDS_TPU_DEVICE_PROBE_TIMEOUT_S", "0.2")
    ici = cfg.RapidsConf({"spark.rapids.shuffle.transport": "ici"})
    with pytest.raises(mesh.DeviceDiscoveryTimeout):
        install_ici_stages(object(), ici)
    # other transports never probe
    assert install_ici_stages("plan", cfg.RapidsConf({})) == "plan"


def test_discover_devices_success_sets_probe_ok(reg):
    import spark_rapids_tpu.parallel.mesh as mesh
    devs = mesh.discover_devices(timeout_s=30.0)
    assert devs
    assert reg.gauge("tpu_device_probe_ok", "d").value() == 1
