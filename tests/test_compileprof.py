"""Compile observatory (obs/compileprof.py): split build timing,
miss-cause classification, the tpu_jit_* metric family, the
cross-session ledger and `tools compile-report` aggregation.

The taxonomy tests drive the observatory directly through process_jit
with synthetic keys so each cause is provoked in isolation; the
end-to-end path (corpus replay, span/ledger/metric agreement) is the
tier-1 --jit gate in devtools/run_lint.py."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.exec import base as eb
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.obs.compileprof import (CAUSE_DTYPE, CAUSE_NEW,
                                              CAUSE_REFAULT,
                                              CAUSE_SHAPE,
                                              CompileObservatory,
                                              _mask_buckets)


@pytest.fixture()
def obs():
    """Fresh observatory + registry + jit table per test (the indexes
    are process-global by design)."""
    obs_metrics.MetricsRegistry.reset_for_tests()
    o = CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    yield o
    eb.clear_jit_cache()
    CompileObservatory.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()


def _probe(key_tail, shape=1024, dtype=jnp.int32):
    fn = eb.process_jit(key_tail, lambda: (lambda x: x + 1))
    out = fn(jnp.zeros(shape, dtype))
    assert out.shape[0] == shape
    return fn


# ---------------------------------------------------------------------------
# cause taxonomy
# ---------------------------------------------------------------------------

def test_first_build_is_new_program(obs):
    _probe(("ProbeExec", "a"))
    snap = obs.snapshot()
    assert snap["builds"] == 1
    assert snap["by_cause"] == {CAUSE_NEW: 1}
    # split timing was measured and is sane
    assert snap["compile_seconds_total"] > 0
    assert snap["trace_seconds_total"] > 0


def test_bucket_shape_change_is_shape_churn(obs):
    f = _probe(("ProbeExec", "a"), shape=1024)
    f(jnp.zeros(8192, jnp.int32))       # another capacity bucket
    assert obs.snapshot()["by_cause"] == {CAUSE_NEW: 1, CAUSE_SHAPE: 1}


def test_bucket_int_in_key_is_shape_churn(obs):
    # two keys differing ONLY in an embedded capacity-bucket int (the
    # fetch_pack/join-expand pattern) canonicalize together
    _probe(("ProbeExec", "cap", 1024), shape=1024)
    _probe(("ProbeExec", "cap", 8192), shape=8192)
    assert obs.snapshot()["by_cause"] == {CAUSE_NEW: 1, CAUSE_SHAPE: 1}


def test_dtype_change_is_dtype_churn(obs):
    f = _probe(("ProbeExec", "a"), shape=1024)
    f(jnp.zeros(1024, jnp.float32))     # same capacity, new dtypes
    assert obs.snapshot()["by_cause"] == {CAUSE_NEW: 1, CAUSE_DTYPE: 1}


def test_genuinely_new_key_is_new_program(obs):
    _probe(("ProbeExec", "a"), shape=1024)
    _probe(("OtherExec", "b"), shape=2048)   # non-bucket shape too
    assert obs.snapshot()["by_cause"] == {CAUSE_NEW: 2}


def test_eviction_then_rebuild_is_refault(obs, monkeypatch):
    monkeypatch.setattr(eb, "_JIT_CACHE_MAX", 1)
    _probe(("ProbeExec", "a"))
    _probe(("OtherExec", "b"))           # evicts ProbeExec
    snap = obs.snapshot()
    assert snap["evictions"] == 1
    _probe(("ProbeExec", "a"))           # rebuild of the evicted entry
    snap = obs.snapshot()
    assert snap["by_cause"].get(CAUSE_REFAULT) == 1
    assert snap["refaults"] == 1


def test_clear_jit_cache_refaults_without_evictions(obs):
    _probe(("ProbeExec", "a"))
    eb.clear_jit_cache()
    _probe(("ProbeExec", "a"))
    snap = obs.snapshot()
    # honest refault classification, but a deliberate clear is not LRU
    # pressure: no eviction counted, no thrash warning armed
    assert snap["by_cause"].get(CAUSE_REFAULT) == 1
    assert snap["evictions"] == 0
    assert snap["refaults"] == 0


def test_second_call_same_shape_builds_nothing(obs):
    f = _probe(("ProbeExec", "a"))
    b1 = obs.snapshot()["builds"]
    for _ in range(3):
        f(jnp.ones(1024, jnp.int32))
    assert obs.snapshot()["builds"] == b1
    # ...and process_jit table hits are counted
    _probe(("ProbeExec", "a"))
    assert obs.snapshot()["hits"] >= 1


def test_profiled_result_matches_plain_jit(obs):
    f = eb.process_jit(("ProbeExec", "sum"),
                       lambda: (lambda x, y: (x * y).sum()))
    a = jnp.arange(100, dtype=jnp.float32)
    out = f(a, a)
    assert float(out) == float((np.arange(100.0) ** 2).sum())


# ---------------------------------------------------------------------------
# metrics family
# ---------------------------------------------------------------------------

def test_jit_metric_family_lights_up(obs, monkeypatch):
    monkeypatch.setattr(eb, "_JIT_CACHE_MAX", 1)
    _probe(("ProbeExec", "a"))
    _probe(("ProbeExec", "a"))           # hit
    _probe(("OtherExec", "b"))           # evicts
    reg = obs_metrics.registry()
    assert reg.counter("tpu_jit_hits_total",
                       labelnames=("exec",)).value(exec="ProbeExec") >= 1
    assert reg.counter(
        "tpu_jit_misses_total", labelnames=("exec", "cause")).value(
        exec="ProbeExec", cause=CAUSE_NEW) == 1
    assert reg.counter("tpu_jit_evictions_total",
                       labelnames=("exec",)).value(exec="ProbeExec") == 1
    count, secs = 0, 0.0
    fam = reg.counter("tpu_jit_compile_seconds_total",
                      labelnames=("exec", "cause"))
    for _, ch in fam.series():
        count += 1
        secs += ch.value
    assert count >= 2 and secs > 0
    assert reg.gauge("tpu_jit_cache_size").value() == 1


def test_thrash_warning_fires_above_ratio(obs, monkeypatch, caplog):
    import logging
    monkeypatch.setattr(eb, "_JIT_CACHE_MAX", 1)
    obs.configure(thrash_warn_ratio=0.4)
    with caplog.at_level(logging.WARNING,
                         logger="spark_rapids_tpu.obs.compileprof"):
        for _ in range(3):             # ping-pong: every build refaults
            _probe(("ProbeExec", "a"))
            _probe(("OtherExec", "b"))
    assert any("thrash" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# ledger + cross-session index + compile-report
# ---------------------------------------------------------------------------

def test_ledger_appends_and_report_aggregates(obs, tmp_path):
    ledger = str(tmp_path / "compile_ledger.jsonl")
    obs.configure(ledger_path=ledger)
    f = _probe(("ProbeExec", "cap", 1024), shape=1024)
    f(jnp.zeros(8192, jnp.int32))
    _probe(("OtherExec", "x"), shape=2048)
    lines = [json.loads(l) for l in open(ledger) if l.strip()]
    builds = [l for l in lines if l["event"] == "build"]
    assert len(builds) == 3
    for b in builds:
        assert b["cause"] and b["exec"] and b["key"] and b["shape"]
        assert b["total_s"] >= 0 and b["hlo_bytes"] > 0
    from spark_rapids_tpu.tools.compile_report import (aggregate_ledger,
                                                       load_ledger)
    agg = aggregate_ledger(load_ledger(str(tmp_path)))
    assert agg["builds"] == 3
    assert agg["distinct_programs"] == 3
    assert agg["attribution_pct"] >= 95.0
    assert agg["causeless_builds"] == 0
    # dedupe projection: the two ProbeExec bucket variants collapse
    assert agg["canonical_families"] == 2
    assert agg["projected_savings_s"] > 0
    assert agg["churn_offenders"][0]["exec"] == "ProbeExec"


def test_prior_session_ledger_classifies_refault(obs, tmp_path):
    ledger = str(tmp_path / "compile_ledger.jsonl")
    obs.configure(ledger_path=ledger)
    _probe(("ProbeExec", "a"))
    # "next session": fresh observatory + jit table, same ledger
    eb.clear_jit_cache()
    o2 = CompileObservatory.reset_for_tests()
    o2.configure(ledger_path=ledger)
    _probe(("ProbeExec", "a"))
    assert o2.snapshot()["by_cause"] == {CAUSE_REFAULT: 1}


def test_compile_report_cli(obs, tmp_path, capsys):
    obs.configure(ledger_path=str(tmp_path / "compile_ledger.jsonl"))
    _probe(("ProbeExec", "cap", 1024), shape=1024)
    _probe(("ProbeExec", "cap", 8192), shape=8192)
    from spark_rapids_tpu.tools.__main__ import main as tools_main
    assert tools_main(["compile-report", "--ledger",
                       str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "compile observatory report" in out
    assert "shape_churn" in out
    assert "2 program(s) collapse to 1" in out
    # an empty/missing ledger is a usage error, not a crash
    assert tools_main(["compile-report", "--ledger",
                       str(tmp_path / "nope")]) == 2


# ---------------------------------------------------------------------------
# canonicalization + proxy safety
# ---------------------------------------------------------------------------

def test_mask_buckets_masks_only_bucket_ints():
    buckets = frozenset((1024, 8192))
    key = ("Exec", 1024, 37, (8192, "s"), True)
    assert _mask_buckets(key, buckets) == \
        ("Exec", "<cap>", 37, ("<cap>", "s"), True)


def test_unsignable_args_fall_back_to_plain_jit(obs):
    # calling a profiled fn under an enclosing trace hands it Tracer
    # leaves: the proxy must dispatch through plain jit, not AOT
    import jax
    f = eb.process_jit(("ProbeExec", "inner"),
                       lambda: (lambda x: x * 2))

    @jax.jit
    def outer(x):
        return f(x) + 1

    out = outer(jnp.arange(4))
    assert list(np.asarray(out)) == [1, 3, 5, 7]


def test_disabled_observatory_returns_plain_jit(obs):
    obs.configure(enabled=False)
    f = eb.process_jit(("ProbeExec", "off"), lambda: (lambda x: x + 1))
    assert int(f(jnp.int32(41))) == 42
    assert obs.snapshot()["builds"] == 0


# -- a refusal surfaces once, with its message ------------------------------

def test_a_program_that_cannot_be_built_raises_and_is_traced_once(obs):
    calls = []

    def refused(x):
        calls.append(1)
        raise NotImplementedError("the compiler's own message")

    f = eb.process_jit(("ProbeExec", "refused"), lambda: refused)
    with pytest.raises(NotImplementedError, match="the compiler's own"):
        f(jnp.arange(4))
    # no second, silent build through plain jit
    assert len(calls) == 1
    assert obs.snapshot()["builds"] == 0


def test_prewarmed_programs_serve_only_the_default_device():
    import jax
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    from spark_rapids_tpu.obs.compileprof import (_dispatch_key,
                                                  _on_default_device)
    devs = jax.devices()
    home = jax.device_put(jnp.arange(8), devs[0])
    away = jax.device_put(jnp.arange(8), devs[1])
    spread = jax.device_put(
        jnp.arange(8), NamedSharding(Mesh(np.array(devs), ("d",)),
                                     PartitionSpec("d")))
    assert isinstance(home.sharding, SingleDeviceSharding)
    assert _on_default_device(_dispatch_key((home, 3, np.float32(1))))
    assert not _on_default_device(_dispatch_key((home, away)))
    assert not _on_default_device(_dispatch_key((spread,)))


# -- programs are named by the operator that built them ---------------------

@pytest.fixture(scope="module")
def built_programs():
    """The programs the real call sites build for a filter + project +
    group-by, on one partition (complete) and on several (update,
    merge_eval, the exchange's map): {program name: [XLA module names of its executables]}."""
    import pyarrow as pa
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import _ProfiledJit, program_name
    obs_metrics.MetricsRegistry.reset_for_tests()
    CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True).get_or_create())
    table = pa.table({"k": pa.array([i % 5 for i in range(400)]),
                      "x": pa.array(range(400))})
    for parts in (1, 4):
        df = s.create_dataframe(table, num_partitions=parts)
        out = (df.filter(col("x") > 10)
               .select(col("k"), (col("x") * 2).alias("y"))
               .group_by(col("k")).agg(F.sum(col("y")).alias("s"))
               .collect())
        assert out.num_rows == 5
    found = {}
    for key, proxy in eb._JIT_CACHE.items():
        assert isinstance(proxy, _ProfiledJit)
        modules = [c.as_text().split(",", 1)[0].split()[1]
                   for c in proxy._compiled.values()]
        found.setdefault(program_name(key), []).extend(modules)
    yield found
    eb.clear_jit_cache()
    CompileObservatory.reset_for_tests()
    obs_metrics.MetricsRegistry.reset_for_tests()


@pytest.mark.parametrize("name", [
    "FilterExec", "ProjectExec", "TpuHashAggregateExec.update",
    "TpuHashAggregateExec.merge_eval", "TpuHashAggregateExec.complete",
    "ShuffleExchangeExec", "fetch_sizes", "fetch_pack"])
def test_call_site_lowers_to_a_module_named_by_its_operator(
        built_programs, name):
    assert name in built_programs, sorted(built_programs)
    modules = built_programs[name]
    assert modules and set(modules) == {"jit_" + name}


def test_no_program_of_the_main_path_is_a_lambda(built_programs):
    for name, modules in built_programs.items():
        assert "lambda" not in name
        assert all("lambda" not in m for m in modules), (name, modules)


@pytest.mark.parametrize("key,name", [
    (("3.2.0", "FilterExec", (("x", "long"),), ("gt",)), "FilterExec"),
    (("3.2.0", "FilterExec", (("x", "long"),), ("gt",), "rowpos"),
     "FilterExec.rowpos"),
    (("3.2.0", "TpuHashAggregateExec", "complete", False, (), "update"),
     "TpuHashAggregateExec.update"),
    (("3.2.0", "TpuHashAggregateExec", "final", True, (), "merge"),
     "TpuHashAggregateExec.merge"),
    (("3.2.0", "TpuHashAggregateExec", "final", True, (), "eval"),
     "TpuHashAggregateExec.eval"),
    (("3.2.0", "TpuHashAggregateExec", "final", True, (), "sortkeys"),
     "TpuHashAggregateExec.sortkeys"),
    (("3.2.0", "HashJoinExec", "inner", (), "expand", 1024, (), ()),
     "HashJoinExec.expand"),
    (("3.2.0", "HashJoinExec", "inner", (), "count"),
     "HashJoinExec.count"),
    (("3.2.0", "fetch_pack", (("x", "long"),), 1024, (), ()),
     "fetch_pack"),
    (("3.2.0", "DistributedAggregate", "data", (0, 1)),
     "IciAggregateExec"),
])
def test_program_name_is_kind_and_trailing_role(key, name):
    from spark_rapids_tpu.obs.compileprof import program_name
    assert program_name(key) == name


def test_a_rename_leaves_the_keys_as_they_were(obs):
    """The function's name is in no key: process_jit's table key, the
    ledger's key hash and the canonical key are what they were for an
    unnamed lambda, whatever the function was called."""
    from spark_rapids_tpu.obs.compileprof import _stable_hash
    from spark_rapids_tpu.shims import active_shim
    tail = ("ProbeExec", ("x", "int"), "update")

    def first(x):
        return x + 1

    f = eb.process_jit(tail, lambda: first)
    full = (active_shim().version,) + tail
    assert list(eb._JIT_CACHE) == [full]
    assert f._key_hash == _stable_hash(full)
    canon = f._canon_key
    assert first.__name__ == first.__qualname__ == "ProbeExec.update"
    assert f._jitted.lower(jnp.zeros(8, jnp.int32)).as_text() \
        .startswith("module @jit_ProbeExec.update ")
    eb.clear_jit_cache()
    g = eb.process_jit(tail, lambda: (lambda x: x + 1))
    assert list(eb._JIT_CACHE) == [full]
    assert (g._key_hash, g._canon_key) == (f._key_hash, canon)
    # a callable that takes no name is built as it is
    import functools
    h = eb.process_jit(("ProbeExec", "partial"),
                       lambda: functools.partial(first, 1))
    assert int(h()) == 2
