#!/usr/bin/env python
"""CI entry point for the tpulint repo lint and the flow-sensitive
plan-lint gate.

Default mode runs the TPU-Rxxx invariant rules over spark_rapids_tpu/
and exits nonzero on any violation NOT in the checked-in baseline
(devtools/lint_baseline.txt), so the invariants ratchet: existing debt
is frozen, new debt fails the suite (tests/test_lint_clean.py invokes
this from tier-1).

--interp runs the plan lint in flow-sensitive mode (abstract
interpreter, analysis/interp.py) over the golden corpus and exits
nonzero when the analyzer regresses in either direction:

  * any ERROR diagnostic on tests/goldens/lint/good_plans.py
    (false reject), or
  * a missing expected code on tests/goldens/lint/bad_plans.py
    (false admit — expected_codes.json is the contract), or
  * any differential-oracle mismatch between predicted and executed
    schema/residency/partitioning on the good corpus.

--memsan runs the tmsan gate: the lifetime/peak pass over the golden
corpus plus a full shadow-ledger replay — every good plan executes with
the runtime sanitizer installed and must (a) keep its measured peak
device bytes at or under the static TPU-L014 bound, (b) leave a clean
ledger (no leaks, no lifecycle violations); the memory bad-plan
fixtures (L013/L014/L015) must each trip their code.

--obs runs the flight-recorder gate: one golden query executes with
tracing + the self-emitted event log enabled and the gate fails on
unclosed spans, unflushed event logs, event-log lines the parser
rejects, or a round-trip mismatch (parsed operator aggregates !=
live last_query_metrics).

--regress runs the cross-run watchdog gate: the golden query corpus
replays TWICE in fresh subprocesses (fresh process = fresh JIT/plan
caches, so both replays see identical steady state), each run's
self-emitted event log distills into fingerprints (obs/history.py),
and the gate fails when the two replays show ANY deterministic drift —
plus anti-vacuity: an injected fallback and an injected fetch-crossing
bump must each be flagged by the differ.

--metrics runs the continuous-metrics gate: one golden query (plus one
in-process bridge round trip) must light up nonzero series from >= 6
distinct subsystems (spill, arena, shuffle, fetch, session queries,
bridge) in the Prometheus exposition, and the JSON health snapshot
must carry the expected schema.

--jit runs the compile-observatory gate: the golden corpus replays
twice in ONE process and the second pass must build ZERO programs
(shape-canonicalization honesty), the compile ledger / jit.build spans
/ tpu_jit_misses_total must agree about the build count, every build
must carry a classified cause with >= 95% of wall compile time
attributed, and injected bucket/dtype perturbations must classify as
shape_churn / dtype_churn (anti-vacuity).

--shuffle runs the distributed-shuffle gate: the checked-in forced-
shuffled-join bridge golden replays through a real session under the
memsan shadow ledger with the spill budget forced to 1 byte (every
registered map-output block must demote and come back correct), and
the gate fails on a wrong join result, a plan that fell back to
broadcast, a dirty ledger, leaked catalog blocks after stage release,
a silent slice-view write (zero saved bytes), or a transport leg whose
fetched-block/byte counters disagree with what the server actually
registered.

--csan runs the concurrency-sanitizer gate: the tpucsan repo pass
(TPU-R008 lock-order cycles, TPU-R009 unguarded multi-root shared
writes, TPU-R010 condvar misuse) must be clean modulo the baseline,
the ABBA/shared-write/condvar fixtures must each trip their rule
(anti-vacuity), the static lock-order artifact must be non-trivial
with every declared thread root matched, and the serve golden mix
replays under the runtime lock witness (obs/lockwitness.py) — the
gate fails on any acquisition edge the static graph cannot explain
(unmodeled edge) or any observed lock-order cycle.

--feedback runs the estimator-observatory gate: the golden corpus
replays cold (fresh estimator ledger, static cost model) then warm
(feedback-directed planning over the cold arm's ledger) in fresh
subprocesses; the warm replay's mean relative row-estimate error must
be STRICTLY below the cold one, and TWO warm replays over identical
ledger snapshots must show zero deterministic fingerprint drift
(feedback-directed planning must be reproducible, never thrash) —
plus anti-vacuity: an injected 100x row misestimate at a shuffle
boundary must trigger a recorded re-plan whose three sinks (replan
span, tpu_replan_total, ledger event) agree, with the join result
bit-exact against the CPU-engine ground truth.

--fleet runs the fleet-observatory gate: TWO serve_map child processes
serve the join sides over loopback while this process fetches with a
live tracer and a FleetAggregator over both peers' /metrics — the
golden cross-process join must be bit-exact, the merged trace must
contain each producer's serve spans nested under the consumer's fetch
spans (skew-corrected, zero lost spans, producer buffers fully
drained), the aggregator must expose rollup series for both peers with
an ok verdict, and an injected peer death (child killed mid-fleet)
must flip the verdict to degraded AND surface the orphan-span counter
with the dead peer's fetch span closed typed — anti-vacuity both ways:
the clean half must actually merge spans, the degraded half must
actually degrade.

--hbm runs the HBM-observatory gate: a golden replay where the tenant
memory timeline, the memsan shadow ledger and the spill catalog must
agree byte-for-byte on peak device occupancy, then a 4-session pool
stress where every lifecycle event must book under its pool tenant
(zero unattributed) with the tpu_hbm_tenant_bytes gauge family summing
to the timeline's live total — anti-vacuity both ways: an allocation
injected from a context-free thread must trip the unattributed
counter, and an injected operator failure must leave exactly one
parseable post-mortem bundle naming the failing operator and tenant.

--faults runs the tpufsan fault-injection campaign: the exception-flow
pass (analysis/raiseflow.py) must be finding-free (TPU-R011 broad
swallow, TPU-R012 leaking release obligation, TPU-R013 untyped seam
escape, TPU-R014 deadline-free socket), its raise-graph artifact must
enumerate >= 40 statically-reachable (seam, typed-error) pairs with
zero untyped leaks, and every pair is then injected for real — through
the session, the serving pool, the async fetcher and the block server
— asserting the exact typed error reaches the caller, the admission /
shuffle / spill books balance with all spans closed, and exactly one
parseable post-mortem bundle records each failure; background roots
(heartbeat loop, metrics endpoint) must survive an injected fault
while counting it, degrading health and black-boxing it — plus
anti-vacuity: planted orphans must trip the books check and an
untyped injection must fail the propagation verdict.

    python devtools/run_lint.py                    # repo check
    python devtools/run_lint.py --update-baseline  # re-freeze debt
    python devtools/run_lint.py --interp           # plan typechecker gate
    python devtools/run_lint.py --memsan           # lifetime + ledger gate
    python devtools/run_lint.py --obs              # flight-recorder gate
    python devtools/run_lint.py --regress          # cross-run watchdog gate
    python devtools/run_lint.py --metrics          # metrics/health gate
    python devtools/run_lint.py --jit              # compile-observatory gate
    python devtools/run_lint.py --shuffle          # distributed-shuffle gate
    python devtools/run_lint.py --csan             # concurrency-sanitizer gate
    python devtools/run_lint.py --feedback         # estimator-observatory gate
    python devtools/run_lint.py --fleet            # fleet-observatory gate
    python devtools/run_lint.py --hbm              # HBM-observatory gate
--dsan runs the tpudsan determinism gate: the replay-safety repo pass
(TPU-R015 volatile reads, TPU-R016 arrival-order float folds, TPU-L017
fingerprint hygiene) must be finding-free with zero frozen baseline
debt, the planted rule fixtures must each trip (anti-vacuity), and the
permuted-replay oracle replays every golden-corpus exchange's map
write under permuted batch arrival AND a changed input split — every
subtree claiming order_stable or better must reproduce its
content-addressed block digests (ShuffleBufferCatalog write-time
digests, cross-checked against recomputes), while two planted
nondeterminism injections (an arrival-order float sum, a
PYTHONHASHSEED-dependent set-iteration router) must produce
DIFFERENT digests, proving the oracle is not vacuous.

--hlo runs the tpuxsan program-efficiency gate: the golden corpus
replays with StableHLO + cost_analysis() persistence on, every
persisted program artifact must resolve (deduped, size-capped), the
analytic cost model (analysis/hlocost.py) must agree with XLA's own
bytes-accessed within the declared tolerance on >= 90% of compiled
programs (a drifting model fails the gate — anti-vacuity for the
costing itself), the runtime padding-waste books must reconcile three
ways (span padWasteBytes vs recomputation from live rows/capacity vs
the tpu_pad_waste_bytes_total counter), the TPU-L018/L019/L020/R017
fixtures must each trip with their clean twins passing, an injected
pathological bucket (a 1M-capacity launch carrying 10 live rows) must
produce both the L018 finding and the expected counter delta, and
`tools kernel-report` must rank the grouped-aggregate and hash-join
programs among the top fusion targets with nonzero projected savings.

    python devtools/run_lint.py --faults           # tpufsan fault campaign
    python devtools/run_lint.py --dsan             # tpudsan determinism gate
    python devtools/run_lint.py --hlo              # tpuxsan efficiency gate
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "devtools", "lint_baseline.txt")
GOLDEN = os.path.join(REPO, "tests", "goldens", "lint")


def _builders(path):
    import runpy
    ns = runpy.run_path(path)
    return {k: ns[k] for k in ns if k.startswith("plan_")
            and callable(ns[k])}


def run_interp_gate() -> int:
    from spark_rapids_tpu.analysis.oracle import verify_plan
    from spark_rapids_tpu.analysis.plan_lint import lint_plan
    from spark_rapids_tpu.config import RapidsConf

    failures = 0

    good = _builders(os.path.join(GOLDEN, "good_plans.py"))
    for name in sorted(good):
        root, conf_map = good[name]()
        conf = RapidsConf(conf_map)
        errors = [d for d in lint_plan(root, conf, infer=True)
                  if d.is_error]
        for d in errors:
            failures += 1
            print(f"FALSE REJECT {name}: {d.render()}")
        mismatches = verify_plan(root, conf)
        for m in mismatches:
            failures += 1
            print(f"ORACLE DRIFT {name}: {m}")

    with open(os.path.join(GOLDEN, "expected_codes.json")) as f:
        expected = json.load(f)
    bad = _builders(os.path.join(GOLDEN, "bad_plans.py"))
    for name in sorted(expected):
        root, conf_map = bad[name]()
        got = {d.code for d in lint_plan(root, RapidsConf(conf_map),
                                         infer=True)}
        for code in set(expected[name]) - got:
            failures += 1
            print(f"FALSE ADMIT {name}: expected {code}, got "
                  f"{sorted(got)}")

    n = len(good) + len(expected)
    if failures:
        print(f"plan typechecker gate: {failures} failure(s) over {n} "
              f"golden plans")
        return 1
    print(f"plan typechecker gate clean ({len(good)} good plans "
          f"oracle-verified, {len(expected)} hazards flagged)")
    return 0


def _release_plan(root):
    """Mirror TpuSession.release_plan_shuffles for bare exec trees: drop
    shuffle blocks and device exchange memos so the post-query ledger
    check sees what a real session would."""
    ids = []
    root.foreach(lambda e: ids.append(e._shuffle_id)
                 if getattr(e, "_shuffle_id", None) is not None else None)
    if ids:
        from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
        mgr = TpuShuffleManager.get()
        for sid in ids:
            mgr.unregister(sid)
    root.foreach(lambda e: e.release_shuffle()
                 if hasattr(e, "release_shuffle") else None)


def run_memsan_gate() -> int:
    from spark_rapids_tpu.analysis.lifetime import analyze_memory
    from spark_rapids_tpu.analysis.plan_lint import lint_plan
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec import base as eb
    from spark_rapids_tpu.memory import memsan
    from spark_rapids_tpu.memory.spill import SpillCatalog

    failures = 0
    good = _builders(os.path.join(GOLDEN, "good_plans.py"))
    for name in sorted(good):
        root, conf_map = good[name]()
        conf = RapidsConf(conf_map)
        bound = analyze_memory(root, conf).bound(root)
        with SpillCatalog._lock:
            SpillCatalog._instance = SpillCatalog()
        with memsan.installed() as ledger:
            ctx = eb.ExecContext(conf)
            ctx.task_context["no_speculation"] = True
            try:
                root.execute_collect(ctx)
                _release_plan(root)
            except memsan.LifecycleViolation as ex:
                failures += 1
                print(f"LEDGER VIOLATION {name}: {ex}")
                continue
            if bound is not None and ledger.peak_device_bytes > bound:
                failures += 1
                print(f"BOUND VIOLATION {name}: measured "
                      f"{ledger.peak_device_bytes} device bytes > "
                      f"static bound {int(bound)}")
            try:
                ledger.assert_clean()
            except memsan.LifecycleViolation as ex:
                failures += 1
                print(f"DIRTY LEDGER {name}: {ex}")

    # the memory hazard fixtures must each trip their diagnostic
    bad = _builders(os.path.join(GOLDEN, "bad_plans.py"))
    mem_fixtures = {
        "plan_L013_shared_boundary_use_after_close": "TPU-L013",
        "plan_L014_peak_over_hbm_budget": "TPU-L014",
        "plan_L015_boundary_never_closes": "TPU-L015",
    }
    for name, code in sorted(mem_fixtures.items()):
        root, conf_map = bad[name]()
        got = {d.code for d in lint_plan(root, RapidsConf(conf_map),
                                         infer=True)}
        if code not in got:
            failures += 1
            print(f"FALSE ADMIT {name}: expected {code}, got "
                  f"{sorted(got)}")

    if failures:
        print(f"memsan gate: {failures} failure(s)")
        return 1
    print(f"memsan gate clean ({len(good)} good plans ledger-replayed "
          f"within their static bounds, {len(mem_fixtures)} memory "
          f"hazards flagged)")
    return 0


def run_obs_gate() -> int:
    """Flight-recorder gate: replay one golden query with tracing and
    the self-emitted event log on; fail on unclosed spans, an unflushed
    or unparsable log, or live-vs-parsed aggregate drift."""
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession, last_query_metrics
    from spark_rapids_tpu.tools.eventlog import parse_event_log
    from spark_rapids_tpu.tools.profiling import (accuracy_report,
                                                  operator_metrics)

    failures = 0
    tmp = tempfile.mkdtemp(prefix="obs_gate_")
    try:
        s = (TpuSession.builder()
             .config("spark.rapids.sql.enabled", True)
             .config("spark.rapids.tpu.eventLog.dir", tmp)
             .config("spark.rapids.tpu.trace.enabled", True)
             .get_or_create())
        tb = pa.table({
            "k": pa.array((np.arange(500) % 11).astype(np.int64)),
            "v": pa.array(np.arange(500, dtype=np.int64))})
        out = (s.create_dataframe(tb, num_partitions=2)
               .filter(col("v") > 5).group_by(col("k"))
               .agg(F.sum(col("v")).alias("sv"),
                    F.count("*").alias("c"))
               .collect())
        assert out.num_rows == 11
        trace = s.last_query_trace()
        if trace is None or not trace.sealed:
            failures += 1
            print("OBS: query left no sealed trace")
        elif trace.open_span_count():
            failures += 1
            print(f"OBS: {trace.open_span_count()} unclosed span(s)")
        logs = [f for f in os.listdir(tmp) if f.startswith("events_")]
        if not logs:
            failures += 1
            print("OBS: no event log flushed")
            return 1
        path = os.path.join(tmp, logs[0])
        rejected = 0
        with open(path) as f:
            for line in f:
                if not line.strip():
                    continue
                try:
                    json.loads(line)
                except json.JSONDecodeError:
                    rejected += 1
        if rejected:
            failures += 1
            print(f"OBS: {rejected} event-log line(s) the parser "
                  f"rejects")
        app = parse_event_log(path)
        if 0 not in app.sql_executions or \
                app.sql_executions[0].end_time is None:
            failures += 1
            print("OBS: SQL execution missing or never ended in the "
                  "parsed log")
        parsed = operator_metrics(app, 0, "DEBUG")
        live = [tuple(r) for r in last_query_metrics(s, "DEBUG")]
        if parsed != live:
            failures += 1
            print(f"OBS: round-trip drift — parsed {len(parsed)} "
                  f"operator metric(s), live {len(live)}")
            for a, b in zip(parsed, live):
                if a != b:
                    print(f"  parsed {a} != live {b}")
        if not accuracy_report(app):
            failures += 1
            print("OBS: no predicted-vs-actual rows in the emitted "
                  "plan")
        if not app.spans:
            failures += 1
            print("OBS: no flight-recorder span records in the log")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if failures:
        print(f"obs gate: {failures} failure(s)")
        return 1
    print("obs gate clean (1 golden query traced, logged, re-parsed "
          "and matched against live metrics)")
    return 0


# the golden regression corpus: three deterministic queries covering
# shuffle (fuse off), join and global sort.  Runs in a FRESH subprocess
# per replay so process-level caches (JIT, speculative fetch plans,
# scan pins) start identical — the same steady state two real CI runs
# see — making the deterministic fingerprint fields exactly comparable.
_REGRESS_CORPUS = r"""
import sys
import numpy as np
import pyarrow as pa
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col

eventlog_dir = sys.argv[1]
rng = np.random.default_rng(1234)
fact = pa.table({
    "k": pa.array((rng.integers(0, 97, 4000)).astype(np.int64)),
    "v": pa.array(rng.integers(-1000, 1000, 4000).astype(np.int64)),
})
dim = pa.table({
    "k": pa.array(np.arange(97, dtype=np.int64)),
    "w": pa.array(np.arange(97, dtype=np.int64) * 3),
})
s = (TpuSession.builder()
     .config("spark.rapids.sql.enabled", True)
     .config("spark.rapids.tpu.singleChipFuse", "off")
     .config("spark.rapids.tpu.eventLog.dir", eventlog_dir)
     .get_or_create())
fdf = s.create_dataframe(fact, num_partitions=2)
ddf = s.create_dataframe(dim)
out1 = (fdf.filter(col("v") > -500).group_by(col("k"))
        .agg(F.sum(col("v")).alias("sv"), F.count("*").alias("c"))
        .collect())
assert out1.num_rows == 97, out1.num_rows
out2 = (fdf.join(ddf, on="k", how="inner").group_by(col("k"))
        .agg(F.sum(col("w")).alias("sw")).collect())
assert out2.num_rows == 97, out2.num_rows
out3 = fdf.sort(col("k"), col("v")).collect()
assert out3.num_rows == 4000, out3.num_rows
print("CORPUS_OK")
"""


def _replay_corpus(eventlog_dir: str) -> str:
    """One fresh-process replay of the golden corpus; returns the
    event-log path."""
    import subprocess
    r = subprocess.run(
        [sys.executable, "-c", _REGRESS_CORPUS, eventlog_dir],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS=os.environ.get(
            "JAX_PLATFORMS", "cpu")))
    if r.returncode != 0 or "CORPUS_OK" not in r.stdout:
        raise RuntimeError(f"corpus replay failed rc={r.returncode}:\n"
                           f"{r.stdout}\n{r.stderr}")
    logs = [f for f in os.listdir(eventlog_dir)
            if f.startswith("events_")]
    if len(logs) != 1:
        raise RuntimeError(f"expected 1 event log, found {logs}")
    return os.path.join(eventlog_dir, logs[0])


def run_regress_gate() -> int:
    import copy
    import shutil
    import tempfile

    from spark_rapids_tpu.obs.history import (HistoryDir,
                                              deterministic_drift,
                                              diff_runs,
                                              distill_event_log)

    failures = 0
    root = tempfile.mkdtemp(prefix="regress_gate_")
    try:
        hist = HistoryDir(os.path.join(root, "history"))
        for i in (1, 2):
            d = os.path.join(root, f"run{i}")
            os.makedirs(d)
            hist.record(distill_event_log(_replay_corpus(d)),
                        label=f"gate replay {i}")
        runs = hist.runs()
        run1, run2 = hist.load(runs[-2]), hist.load(runs[-1])
        drift = deterministic_drift(diff_runs(run1, run2))
        for dr in drift:
            failures += 1
            print(f"REPLAY DRIFT: {dr.render()}")

        # anti-vacuity: the differ must FLAG injected regressions —
        # a watchdog that never barks is worse than none
        tampered = copy.deepcopy(run2)
        q0 = tampered["queries"][0]
        q0["fallback_ops"] = sorted(q0["fallback_ops"] +
                                    ["InjectedHostOnlyExec"])
        q1 = tampered["queries"][min(1, len(tampered["queries"]) - 1)]
        q1["fetch_crossings"] = q1.get("fetch_crossings", 0) + 5
        kinds = {d.kind for d in
                 deterministic_drift(diff_runs(run1, tampered))}
        for want in ("new_fallback", "crossing_growth"):
            if want not in kinds:
                failures += 1
                print(f"VACUOUS DIFFER: injected {want} not flagged "
                      f"(got {sorted(kinds)})")
        n = len(run2.get("queries", ()))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if failures:
        print(f"regress gate: {failures} failure(s)")
        return 1
    print(f"regress gate clean ({n} golden queries replayed twice with "
          f"identical deterministic fingerprints; injected fallback + "
          f"crossing bump both flagged)")
    return 0


# subsystem -> Prometheus family prefixes that must show a nonzero
# series after the golden query + bridge round trip (ISSUE acceptance:
# >= 6 distinct subsystems)
_METRIC_SUBSYSTEMS = {
    "spill": ("tpu_spill_",),
    "arena": ("tpu_arena_",),
    "shuffle": ("tpu_shuffle_",),
    "fetch": ("tpu_fetch_",),
    "session": ("tpu_queries_",),
    "ici/bridge": ("tpu_bridge_", "tpu_ici_"),
}


def run_metrics_gate() -> int:
    import threading

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.bridge import BridgeClient, SidecarServer
    from spark_rapids_tpu.obs.health import (HealthMonitor,
                                             render_prometheus)
    from spark_rapids_tpu.obs.metrics import MetricsRegistry

    failures = 0
    reg = MetricsRegistry.reset_for_tests()
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .config("spark.rapids.memory.pinnedPool.size", "8m")
         .config("spark.rapids.memory.tpu.spillBudgetBytes", 1)
         .get_or_create())
    tb = pa.table({
        "k": pa.array((np.arange(2000) % 53).astype(np.int64)),
        "v": pa.array(np.arange(2000, dtype=np.int64))})
    out = (s.create_dataframe(tb, num_partitions=2)
           .filter(col("v") > 5).group_by(col("k"))
           .agg(F.sum(col("v")).alias("sv")).collect())
    assert out.num_rows == 53, out.num_rows

    # one bridge round trip against the in-process reference sidecar
    server = SidecarServer(port=0)
    t = threading.Thread(target=server.serve_forever,
                         kwargs={"announce": False}, daemon=True)
    t.start()
    try:
        client = BridgeClient(server.port)
        res = client.execute_stage(
            {"ops": [{"op": "filter",
                      "condition": {"op": "gt",
                                    "children": [
                                        {"col": "v"},
                                        {"lit": 100,
                                         "type": "bigint"}]}}]},
            pa.table({"k": pa.array([1, 2, 3], pa.int64()),
                      "v": pa.array([50, 150, 250], pa.int64())}))
        assert res.num_rows == 2, res.num_rows
        client.close()
    finally:
        server.shutdown()

    text = render_prometheus(reg)
    lit = set()
    for sub, prefixes in _METRIC_SUBSYSTEMS.items():
        nonzero = [
            line for line in text.splitlines()
            if any(line.startswith(p) for p in prefixes)
            and not line.startswith("#")
            and float(line.rsplit(None, 1)[-1]) > 0]
        if nonzero:
            lit.add(sub)
        else:
            failures += 1
            print(f"METRICS: subsystem {sub} exposed no nonzero "
                  f"series (prefixes {prefixes})")
    snap = HealthMonitor(reg).snapshot()
    for key in ("status", "timestamp_ms", "components", "queries"):
        if key not in snap:
            failures += 1
            print(f"HEALTH: snapshot missing key {key!r}")
    if snap.get("status") not in ("ok", "degraded", "down"):
        failures += 1
        print(f"HEALTH: bad status {snap.get('status')!r}")
    if failures:
        print(f"metrics gate: {failures} failure(s)")
        return 1
    print(f"metrics gate clean ({len(lit)} subsystems exposed nonzero "
          f"Prometheus series from one golden query + one bridge round "
          f"trip; health snapshot schema ok)")
    return 0


def run_jit_gate() -> int:
    """Compile-observatory gate: the golden corpus replays TWICE in one
    process — the second pass must produce ZERO program builds (shape-
    canonicalization honesty: identical queries must share programs),
    the ledger, the jit.build spans and the tpu_jit_misses_total metric
    must agree about the build count, every build must carry a
    classified cause, `tools compile-report` must attribute >= 95% of
    measured wall compile time, and (anti-vacuity) a key/shape
    perturbing injection must produce a classified churn miss."""
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import base as eb
    from spark_rapids_tpu.obs.compileprof import (CAUSES,
                                                  CompileObservatory)
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.tools.eventlog import parse_event_log

    failures = 0
    tmp = tempfile.mkdtemp(prefix="jit_gate_")
    reg = MetricsRegistry.reset_for_tests()
    obs = CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    try:
        evt = os.path.join(tmp, "evt")
        os.makedirs(evt)
        hist = os.path.join(tmp, "hist")
        s = (TpuSession.builder()
             .config("spark.rapids.sql.enabled", True)
             .config("spark.rapids.tpu.singleChipFuse", "off")
             .config("spark.rapids.tpu.eventLog.dir", evt)
             .config("spark.rapids.tpu.compile.ledgerDir", hist)
             .get_or_create())
        rng = np.random.default_rng(1234)
        fact = pa.table({
            "k": pa.array((rng.integers(0, 97, 4000)).astype(np.int64)),
            "v": pa.array(rng.integers(-1000, 1000, 4000)
                          .astype(np.int64))})
        dim = pa.table({
            "k": pa.array(np.arange(97, dtype=np.int64)),
            "w": pa.array(np.arange(97, dtype=np.int64) * 3)})
        fdf = s.create_dataframe(fact, num_partitions=2)
        ddf = s.create_dataframe(dim)

        def corpus():
            o1 = (fdf.filter(col("v") > -500).group_by(col("k"))
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count("*").alias("c")).collect())
            o2 = (fdf.join(ddf, on="k", how="inner").group_by(col("k"))
                  .agg(F.sum(col("w")).alias("sw")).collect())
            o3 = fdf.sort(col("k"), col("v")).collect()
            assert (o1.num_rows, o2.num_rows, o3.num_rows) == \
                (97, 97, 4000)

        corpus()
        snap1 = obs.snapshot()
        if snap1["builds"] == 0:
            failures += 1
            print("JIT: vacuous gate — the corpus compiled nothing")
        for cause in snap1["by_cause"]:
            if cause not in CAUSES:
                failures += 1
                print(f"JIT: unrecognized miss cause {cause!r}")
        corpus()
        snap2 = obs.snapshot()
        if snap2["builds"] != snap1["builds"]:
            failures += 1
            print(f"JIT: SECOND-PASS MISS — replaying the identical "
                  f"corpus built {snap2['builds'] - snap1['builds']} "
                  f"new program(s) (shape canonicalization is lying); "
                  f"causes now {snap2['by_cause']}")

        # three sinks, one truth: ledger / spans / metrics must agree
        ledger_builds = 0
        ledger_path = os.path.join(hist, "compile_ledger.jsonl")
        if os.path.exists(ledger_path):
            with open(ledger_path) as f:
                ledger_builds = sum(
                    1 for line in f if line.strip()
                    and json.loads(line).get("event") == "build")
        logs = [f for f in os.listdir(evt) if f.startswith("events_")]
        span_builds = 0
        if logs:
            app = parse_event_log(os.path.join(evt, logs[0]))
            span_builds = sum(1 for sp in app.spans
                              if sp.get("name") == "jit.build")
        fam = reg.counter("tpu_jit_misses_total",
                          labelnames=("exec", "cause"))
        metric_builds = sum(ch.value for _, ch in fam.series())
        if not (snap2["builds"] == ledger_builds == span_builds ==
                metric_builds):
            failures += 1
            print(f"JIT: build-count disagreement — observatory "
                  f"{snap2['builds']}, ledger {ledger_builds}, "
                  f"jit.build spans {span_builds}, "
                  f"tpu_jit_misses_total {metric_builds}")

        # the dedupe projection is a CONTRACT, not a report: with
        # bucket-canonical tracing landed, the corpus must realize no
        # more distinct programs than the observatory projects under
        # canonicalization — i.e. zero projected savings left on the
        # table.  (Checked before the churn-injection probes below,
        # which deliberately add shape/dtype churn.)
        from spark_rapids_tpu.tools.compile_report import (
            aggregate_ledger, load_ledger)
        agg_c = aggregate_ledger(load_ledger(ledger_path))
        if agg_c["distinct_programs"] > agg_c["canonical_families"]:
            failures += 1
            print(f"JIT: PROJECTION BROKEN — corpus realized "
                  f"{agg_c['distinct_programs']} distinct program(s) "
                  f"vs {agg_c['canonical_families']} canonical "
                  f"familie(s): {agg_c['projected_savings_s']:.2f}s of "
                  f"bucket-churn compile left on the table")

        # recompile-drift watchdog: the gate's own event log distilled
        # against the pre-change recording — distinct compiled programs
        # per corpus query must not GROW past the baseline (fewer is
        # progress; query_added drifts from the second pass are
        # expected and ignored)
        from spark_rapids_tpu.obs.history import (diff_runs,
                                                  distill_event_log)
        baseline_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "jit_corpus_baseline.json")
        if logs and os.path.exists(baseline_path):
            with open(baseline_path) as f:
                baseline = json.load(f)
            current = {"queries":
                       distill_event_log(os.path.join(evt, logs[0]))}
            recompiles = [d for d in diff_runs(baseline, current)
                          if d.kind == "recompile_drift"]
            for d in recompiles:
                failures += 1
                print(f"JIT: RECOMPILE DRIFT vs pre-change baseline — "
                      f"{d.render()}")
        else:
            failures += 1
            print(f"JIT: recompile-drift check could not run "
                  f"(event log present: {bool(logs)}, baseline "
                  f"present: {os.path.exists(baseline_path)})")

        # anti-vacuity: a capacity-bucket perturbation (same program
        # modulo buckets) must be classified, not silently re-counted
        # as novel work
        import jax.numpy as jnp
        probe = eb.process_jit(("JitGateProbe", "sig"),
                               lambda: (lambda x: x + 1))
        probe(jnp.zeros(1024, jnp.int32))
        churn0 = obs.snapshot()["by_cause"].get("shape_churn", 0)
        probe(jnp.zeros(8192, jnp.int32))         # bucket perturbation
        churn1 = obs.snapshot()["by_cause"].get("shape_churn", 0)
        if churn1 != churn0 + 1:
            failures += 1
            print(f"JIT: bucket-perturbed probe not classified as "
                  f"shape_churn (causes {obs.snapshot()['by_cause']})")
        dt0 = obs.snapshot()["by_cause"].get("dtype_churn", 0)
        probe(jnp.zeros(1024, jnp.float32))       # dtype perturbation
        dt1 = obs.snapshot()["by_cause"].get("dtype_churn", 0)
        if dt1 != dt0 + 1:
            failures += 1
            print(f"JIT: dtype-perturbed probe not classified as "
                  f"dtype_churn (causes {obs.snapshot()['by_cause']})")

        # the acceptance bar: the report must attribute the wall
        # compile time it measured, with every miss carrying a cause
        agg = aggregate_ledger(load_ledger(ledger_path))
        if agg["attribution_pct"] < 95.0:
            failures += 1
            print(f"JIT: compile-report attributes only "
                  f"{agg['attribution_pct']:.1f}% of wall compile "
                  f"time (< 95%)")
        if agg["causeless_builds"]:
            failures += 1
            print(f"JIT: {agg['causeless_builds']} build(s) carry no "
                  f"miss cause")
        n_builds = snap2["builds"]
        total_s = agg["total_s"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        eb.clear_jit_cache()
    if failures:
        print(f"jit gate: {failures} failure(s)")
        return 1
    print(f"jit gate clean ({n_builds} corpus program(s) built once, "
          f"{total_s:.2f}s wall compile fully attributed; second pass "
          f"zero-miss; ledger/span/metric counts agree; dedupe "
          f"projection realized; no recompile drift vs the pre-change "
          f"baseline; bucket and dtype perturbations classified)")
    return 0


def run_shuffle_gate() -> int:
    """Distributed-shuffle gate: (a) the forced-shuffled-join bridge
    golden replays through a real session under the memsan shadow
    ledger with the spill budget pinned to 1 byte, so every registered
    map-output block demotes off-device and must come back correct;
    (b) a transport leg serves real catalog blocks over TCP and the
    async fetcher's block/byte counters must agree with what the
    server registered (and count zero errors)."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.bridge.spec import plan_spec_to_logical
    from spark_rapids_tpu.columnar.device import (batch_to_arrow,
                                                  batch_to_device)
    from spark_rapids_tpu.memory import memsan
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    from spark_rapids_tpu.shuffle.transport import (AsyncBlockFetcher,
                                                    ShuffleClient,
                                                    ShuffleServer)

    failures = 0
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()

    golden = os.path.join(REPO, "bridge-jvm", "src", "test",
                          "resources", "goldens",
                          "shuffled_join_forced.json")
    with open(golden) as f:
        spec = json.load(f)["spec"]
    spec["numPartitions"] = 4

    # skewed keys: every other row hits key 0, so each map batch's
    # per-partition slice buckets sum PAST the whole-batch bucket and
    # the slice-view write must bank nonzero saved bytes (anti-vacuity
    # for tpu_shuffle_write_saved_bytes_total)
    n = 4000
    ids = np.where(np.arange(n) % 2 == 0, 0,
                   np.arange(n) % 97).astype(np.int64)
    fact = pa.table({"id": pa.array(ids),
                     "x": pa.array(np.arange(n, dtype=np.int64))})
    dim = pa.table({"user_id": pa.array(np.arange(97, dtype=np.int64)),
                    "w": pa.array(np.arange(97, dtype=np.int64) * 10)})

    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .config("spark.rapids.memory.tpu.spillBudgetBytes", 1)
         .get_or_create())
    with memsan.installed() as ledger:
        got = s.execute(plan_spec_to_logical(spec, fact, (dim,)))
        names = []
        s.last_plan.foreach(lambda e: names.append(type(e).__name__))
        if "ShuffledHashJoinExec" not in names or \
                names.count("ShuffleExchangeExec") < 2:
            failures += 1
            print(f"SHUFFLE: golden plan lost its shuffled shape: "
                  f"{names}")
        if "BroadcastHashJoinExec" in names:
            failures += 1
            print("SHUFFLE: forced-shuffled golden fell back to "
                  "broadcast")
        want = np.sort(ids * 10)
        if not np.array_equal(np.sort(got.column("w").to_numpy()),
                              want) or got.num_rows != n:
            failures += 1
            print(f"SHUFFLE: wrong join result ({got.num_rows} rows)")
        peak = ledger.peak_device_bytes
        try:
            ledger.assert_clean()
        except memsan.LifecycleViolation as ex:
            failures += 1
            print(f"SHUFFLE: dirty ledger after stage release: {ex}")
    if TpuShuffleManager.get().catalog.num_blocks() != 0:
        failures += 1
        print(f"SHUFFLE: {TpuShuffleManager.get().catalog.num_blocks()}"
              f" catalog block(s) leaked past release_plan_shuffles")
    leaks = SpillCatalog.get().leak_report()
    if leaks:
        failures += 1
        print(f"SHUFFLE: {len(leaks)} spillable buffer(s) leaked")
    spilled = sum(ch.value for _, ch in
                  m.counter("tpu_spill_bytes_total",
                            labelnames=("tier",)).series())
    if spilled <= 0:
        failures += 1
        print("SHUFFLE: vacuous replay — a 1-byte spill budget spilled "
              "nothing")
    saved = m.counter("tpu_shuffle_write_saved_bytes_total").value()
    if saved <= 0:
        failures += 1
        print("SHUFFLE: slice-view map write banked zero saved bytes "
              "on a skewed corpus")
    wrote = m.counter("tpu_shuffle_write_blocks_total").value()
    read = m.counter("tpu_shuffle_read_batches_total").value()
    if wrote <= 0 or read <= 0:
        failures += 1
        print(f"SHUFFLE: write/read counters never moved "
              f"(wrote {wrote}, read {read})")

    # transport leg: real catalog blocks over TCP, counters must agree
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    n_maps, rows = 6, 128
    for mid in range(n_maps):
        rb = pa.record_batch({"a": pa.array(
            [mid * 1000 + i for i in range(rows)], type=pa.int64())})
        mgr.write_map_output(21, mid, {0: batch_to_device(rb, xp=np)})
    server = ShuffleServer(mgr).start()
    try:
        cli = ShuffleClient("127.0.0.1", server.port)
        first = [batch_to_arrow(b).column("a").to_pylist()[0]
                 for b in AsyncBlockFetcher(cli, 21, 0, window=3)]
        cli.close()
    finally:
        server.stop()
        TpuShuffleManager.reset()
    if first != [mid * 1000 for mid in range(n_maps)]:
        failures += 1
        print(f"SHUFFLE: transport leg fetched wrong blocks: {first}")
    fetched = m.counter("tpu_shuffle_fetch_blocks_total").value()
    if fetched != n_maps:
        failures += 1
        print(f"SHUFFLE: fetched-block counter disagrees "
              f"({fetched} != {n_maps} served)")
    if m.counter("tpu_shuffle_fetch_bytes_total").value() <= 0:
        failures += 1
        print("SHUFFLE: fetched-bytes counter never moved")
    errs = m.counter("tpu_shuffle_fetch_errors_total",
                     labelnames=("kind",))
    n_errs = sum(ch.value for _, ch in errs.series())
    if n_errs:
        failures += 1
        print(f"SHUFFLE: clean transport leg counted {n_errs} fetch "
              f"error(s)")

    # wire leg: injected remote failures must surface TYPED, with
    # tpu_shuffle_fetch_errors_total{kind} agreeing, and the locality
    # split must prove local blocks never cross the wire
    wire_failures = _shuffle_wire_leg()
    failures += wire_failures

    MetricsRegistry.reset_for_tests()
    if failures:
        print(f"shuffle gate: {failures} failure(s)")
        return 1
    print(f"shuffle gate clean (forced-shuffled golden joined "
          f"correctly under a 1-byte spill budget, peak {int(peak)} "
          f"device bytes, {int(spilled)} bytes spilled, {int(saved)} "
          f"slice-view bytes saved, ledger + catalog clean; transport "
          f"leg fetched {int(fetched)} blocks with zero errors; wire "
          f"leg: every injected remote failure surfaced typed, "
          f"replica retry completed exactly once, local blocks stayed "
          f"zero-copy, cross-process golden bit-exact)")
    return 0


def _shuffle_wire_leg() -> int:
    """Injected-failure wire scenarios.  Each rogue server speaks just
    enough protocol to inject ONE specific fault; the client must fail
    with the matching typed error AND count it under the matching
    ``tpu_shuffle_fetch_errors_total{kind}`` — a mismatch between what
    raised and what was counted is itself a failure."""
    import socket
    import struct
    import subprocess
    import threading
    import time

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.device import (batch_to_arrow,
                                                  batch_to_device)
    from spark_rapids_tpu.memory.meta import (CODEC_LZ4, MAGIC, VERSION,
                                              _HEADER, TableMeta)
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.shuffle import locality
    from spark_rapids_tpu.shuffle.errors import (
        TpuShuffleCorruptBlockError, TpuShuffleFetchFailedError,
        TpuShufflePeerDeadError, TpuShuffleStaleFrameError,
        TpuShuffleTruncatedFrameError)
    from spark_rapids_tpu.shuffle.heartbeat import HeartbeatManager
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    from spark_rapids_tpu.shuffle.registry import (BlockEndpoint,
                                                   BlockLocationRegistry)
    from spark_rapids_tpu.shuffle.transport import (
        _FRAME, _recv_exact, MSG_BUFFER, MSG_ERROR, MSG_HELLO,
        MSG_METADATA_RESP, AsyncBlockFetcher, ShuffleClient,
        ShuffleServer, _server_requests_counter)

    failures = 0
    errs = m.counter("tpu_shuffle_fetch_errors_total",
                     "async fetch failures by kind",
                     labelnames=("kind",))

    def rogue(script):
        """One-connection server running ``script(conn)`` then closing:
        the injected-failure side of each scenario."""
        lsock = socket.socket()
        lsock.bind(("127.0.0.1", 0))
        lsock.listen(1)
        port = lsock.getsockname()[1]

        def run():
            conn, _ = lsock.accept()
            try:
                script(conn)
            except OSError:
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass
                lsock.close()

        threading.Thread(target=run, daemon=True).start()
        return port

    def read_req(conn):
        while True:
            head = _recv_exact(conn, _FRAME.size)
            mtype, rid, blen = _FRAME.unpack(head)
            if blen:
                _recv_exact(conn, blen)
            if mtype == MSG_HELLO:
                # pre-fleet peer: the correlated bad_message refusal
                # pins the client to v1 framing, so the scripted
                # request arrives next in the shape read above
                eb = b"bad_message:unknown message type"
                conn.sendall(_FRAME.pack(MSG_ERROR, rid, len(eb)) + eb)
                continue
            return mtype, rid

    def expect(name, port, exc_type, kind, window=2):
        """Drive one fetch against the rogue at ``port``; it must raise
        ``exc_type`` and bump errs{kind} by exactly one."""
        nonlocal failures
        before = errs.value(kind=kind)
        cli = ShuffleClient("127.0.0.1", port, timeout=10.0)
        try:
            list(AsyncBlockFetcher(cli, 31, 0, window=window,
                                   timeout=10.0))
        except exc_type:
            pass
        except Exception as ex:  # noqa: BLE001 — report the wrong type
            failures += 1
            print(f"SHUFFLE-WIRE: {name} raised "
                  f"{type(ex).__name__} ({ex}), expected "
                  f"{exc_type.__name__}")
            cli.close()
            return
        else:
            failures += 1
            print(f"SHUFFLE-WIRE: {name} did not raise")
            cli.close()
            return
        cli.close()
        got = errs.value(kind=kind) - before
        if got != 1:
            failures += 1
            print(f"SHUFFLE-WIRE: {name} counted {got} "
                  f"errors_total{{kind={kind}}}, expected 1")

    # (1) stale frame: a response correlating to a DIFFERENT request id
    def stale_script(conn):
        _, rid = read_req(conn)
        conn.sendall(_FRAME.pack(MSG_METADATA_RESP, rid + 977, 0))

    expect("stale frame", rogue(stale_script),
           TpuShuffleStaleFrameError, "stale")

    # (2) truncated frame: header promises 100 body bytes, sends 10
    def trunc_script(conn):
        _, rid = read_req(conn)
        conn.sendall(_FRAME.pack(MSG_METADATA_RESP, rid, 100)
                     + b"x" * 10)

    expect("truncated frame", rogue(trunc_script),
           TpuShuffleTruncatedFrameError, "truncated")

    # (3) corrupt compressed body: valid TPUB header claiming lz4, then
    # garbage where the codec frame should be
    def corrupt_script(conn):
        _, rid = read_req(conn)
        meta = (struct.pack("<i", 1)
                + struct.pack("<qqqq", 31, 0, 0, 0)
                + TableMeta.of_stats(10, 160, 0).pack())
        conn.sendall(_FRAME.pack(MSG_METADATA_RESP, rid, len(meta))
                     + meta)
        _, rid = read_req(conn)
        payload = _HEADER.pack(MAGIC, VERSION, CODEC_LZ4, 10, 20) \
            + b"\xff" * 20
        conn.sendall(_FRAME.pack(MSG_BUFFER, rid, 8)
                     + struct.pack("<q", len(payload)) + payload)

    expect("corrupt codec body", rogue(corrupt_script),
           TpuShuffleCorruptBlockError, "corrupt")

    # (4) mid-fetch server death: a REAL server stopped after the
    # consumer takes its first block — the rest of the stream must fail
    # typed, not hang
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    for mid in range(6):
        rb = pa.record_batch({"a": pa.array(
            [mid * 100 + i for i in range(64)], type=pa.int64())})
        mgr.write_map_output(41, mid, {0: batch_to_device(rb, xp=np)})
    server = ShuffleServer(mgr).start()
    before = errs.value(kind="fetch_failed")
    cli = ShuffleClient("127.0.0.1", server.port, timeout=10.0)
    died_typed = False
    try:
        for i, _b in enumerate(AsyncBlockFetcher(cli, 41, 0, window=1,
                                                 timeout=10.0)):
            if i == 0:
                server.stop()
    except TpuShuffleFetchFailedError:
        died_typed = True
    except Exception as ex:  # noqa: BLE001
        failures += 1
        print(f"SHUFFLE-WIRE: mid-fetch death raised "
              f"{type(ex).__name__}, expected a typed fetch failure")
    cli.close()
    if not died_typed and not failures:
        failures += 1
        print("SHUFFLE-WIRE: mid-fetch server death did not fail the "
              "stream")
    if died_typed and errs.value(kind="fetch_failed") - before != 1:
        failures += 1
        print("SHUFFLE-WIRE: mid-fetch death not counted under "
              "kind=fetch_failed")

    # (5) heartbeat-dead peer: every replica expired -> typed peer-dead
    # without ever dialing
    hb = HeartbeatManager(timeout_s=0.01)
    hb.register_executor("wire-dead", "127.0.0.1", 1)
    time.sleep(0.05)
    BlockLocationRegistry.reset()
    reg = BlockLocationRegistry.get()
    reg.set_local("gate-reduce", "127.0.0.1", 0)
    reg.attach_heartbeat(hb)
    group = [BlockEndpoint("wire-dead", "127.0.0.1", 1)]
    before = errs.value(kind="peer_dead")
    try:
        list(locality._fetch_group(group, 42, 0, reg, np, 2, 5.0, 1, m))
        failures += 1
        print("SHUFFLE-WIRE: all-dead replica group did not raise")
    except TpuShufflePeerDeadError:
        if errs.value(kind="peer_dead") - before != 1:
            failures += 1
            print("SHUFFLE-WIRE: dead peer group not counted under "
                  "kind=peer_dead")

    # (6) replica retry, exactly once: first replica's port refuses the
    # dial, the live replica must serve ALL blocks with ONE retry and
    # zero duplicates
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    for mid in range(6):
        rb = pa.record_batch({"a": pa.array(
            [mid * 100 + i for i in range(64)], type=pa.int64())})
        mgr.write_map_output(43, mid, {0: batch_to_device(rb, xp=np)})
    server = ShuffleServer(mgr).start()
    dead_sock = socket.socket()
    dead_sock.bind(("127.0.0.1", 0))
    dead_port = dead_sock.getsockname()[1]
    dead_sock.close()  # nothing listens here anymore
    hb2 = HeartbeatManager(timeout_s=60.0)
    hb2.register_executor("replica-a", "127.0.0.1", dead_port)
    hb2.register_executor("replica-b", "127.0.0.1", server.port)
    reg.attach_heartbeat(hb2)
    group = [BlockEndpoint("replica-a", "127.0.0.1", dead_port),
             BlockEndpoint("replica-b", "127.0.0.1", server.port)]
    retries = m.counter("tpu_shuffle_fetch_retries_total")
    r_before = retries.value()
    locality.reset_pool()
    try:
        got = [batch_to_arrow(b).column("a").to_pylist()[0]
               for b in locality._fetch_group(group, 43, 0, reg, np,
                                              2, 5.0, 2, m)]
        if got != [mid * 100 for mid in range(6)]:
            failures += 1
            print(f"SHUFFLE-WIRE: replica retry delivered {got} "
                  f"(duplicates or gaps)")
        if retries.value() - r_before != 1:
            failures += 1
            print(f"SHUFFLE-WIRE: replica retry counted "
                  f"{retries.value() - r_before} retries, expected 1")
    except Exception as ex:  # noqa: BLE001
        failures += 1
        print(f"SHUFFLE-WIRE: replica retry failed: "
              f"{type(ex).__name__}: {ex}")
    finally:
        server.stop()
        locality.reset_pool()

    # (7) local zero-copy proof: a shuffle whose owner group is THIS
    # process must serve from the catalog — local counter moves, the
    # block-server transfer counter must NOT
    TpuShuffleManager.reset()
    mgr = TpuShuffleManager.get()
    rb = pa.record_batch({"a": pa.array(range(64), type=pa.int64())})
    mgr.write_map_output(44, 0, {0: batch_to_device(rb, xp=np)})
    server = ShuffleServer(mgr).start()
    BlockLocationRegistry.reset()
    reg = BlockLocationRegistry.get()
    reg.set_local("gate-local", "127.0.0.1", server.port)
    reg.register(44, [BlockEndpoint("gate-local", "127.0.0.1",
                                    server.port)])
    local_c = m.counter("tpu_shuffle_local_blocks_total")
    srv_c = _server_requests_counter()
    l_before = local_c.value()
    t_before = srv_c.value(kind="transfer")
    n_local = sum(1 for _ in locality.read_reduce_blocks(44, 0))
    server.stop()
    if n_local != 1 or local_c.value() - l_before != 1:
        failures += 1
        print(f"SHUFFLE-WIRE: local group read {n_local} block(s), "
              f"local counter moved "
              f"{local_c.value() - l_before} — zero-copy path broken")
    if srv_c.value(kind="transfer") - t_before != 0:
        failures += 1
        print("SHUFFLE-WIRE: local blocks crossed the wire (server "
              "transfer counter moved)")

    # (8) forced-remote golden over loopback: a child OS process owns
    # the map outputs; the joined result here must be bit-exact vs the
    # in-process reference, with zero local reads and zero leaks
    from spark_rapids_tpu.shuffle.serve_map import (
        DIM_SID, FACT_SID, build_side_tables, partition_record_batch)
    TpuShuffleManager.reset()
    BlockLocationRegistry.reset()
    reg = BlockLocationRegistry.get()
    reg.set_local("gate-reduce", "127.0.0.1", 0)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE="1")
    rows, parts, seed = 4000, 2, 3
    child = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.shuffle.serve_map",
         "--rows", str(rows), "--parts", str(parts),
         "--codec", "lz4", "--seed", str(seed)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env, cwd=REPO)
    try:
        line = child.stdout.readline()
        port = int(line.split()[1])
        ep = BlockEndpoint("gate-map", "127.0.0.1", port)
        reg.register(FACT_SID, [ep])
        reg.register(DIM_SID, [ep])
        l_before = local_c.value()
        out = []
        for pid in range(parts):
            sides = []
            for sid in (FACT_SID, DIM_SID):
                rbs = [batch_to_arrow(b) for b in
                       locality.read_reduce_blocks(sid, pid)]
                sides.append(pa.Table.from_batches(rbs)
                             if rbs else None)
            if sides[0] is not None and sides[1] is not None:
                out.append(sides[0].join(sides[1], "k"))
        got_tbl = pa.concat_tables(out).sort_by(
            [("k", "ascending"), ("v", "ascending")])
        fact, dim = build_side_tables(rows, seed)
        ref = []
        fparts = partition_record_batch(fact, "k", parts)
        dparts = partition_record_batch(dim, "k", parts)
        for pid in range(parts):
            f, d = fparts.get(pid), dparts.get(pid)
            if f is not None and d is not None:
                ref.append(pa.table(f).join(pa.table(d), "k"))
        ref_tbl = pa.concat_tables(ref).sort_by(
            [("k", "ascending"), ("v", "ascending")])
        if not got_tbl.equals(ref_tbl):
            failures += 1
            print(f"SHUFFLE-WIRE: cross-process golden NOT bit-exact "
                  f"({got_tbl.num_rows} vs {ref_tbl.num_rows} rows)")
        if local_c.value() - l_before != 0:
            failures += 1
            print("SHUFFLE-WIRE: cross-process run took the local "
                  "path for remote-owned blocks")
        child.stdin.write("done\n")
        child.stdin.flush()
        stats_line = child.stdout.readline()
        stats = json.loads(stats_line[len("STATS "):])
        if stats["leaked_blocks"] or stats["leaks"]:
            failures += 1
            print(f"SHUFFLE-WIRE: map-side process leaked "
                  f"{stats['leaked_blocks']} block(s), "
                  f"{stats['leaks']} spill ledger leak(s)")
        ratio = (stats["compressed_bytes"] / stats["raw_bytes"]
                 if stats["raw_bytes"] else 1.0)
        if ratio >= 0.9:
            failures += 1
            print(f"SHUFFLE-WIRE: lz4 shuffle ratio {ratio:.3f} >= "
                  f"0.9 — compression not visible on the wire")
        child.wait(timeout=30)
    finally:
        child.stdin.close()
        child.stdout.close()
        if child.poll() is None:
            child.kill()
            child.wait()
        locality.reset_pool()
        BlockLocationRegistry.reset()
        TpuShuffleManager.reset()
    return failures


def run_serve_gate() -> int:
    """Multi-tenant serving gate: a golden four-query mix replayed 16
    times across 4 concurrent pooled sessions under byte-weighted
    admission.  Every concurrent result must equal the serial ground
    truth bit-for-bit; the memsan dirty-ledger counter must stay zero;
    the admission books must balance (admitted = completed + failed,
    zero timeouts, max bytes in flight nonzero and within budget); and
    after the pool drains no shuffle block or spillable buffer may
    survive (orphan check)."""
    import concurrent.futures as cf

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.expr.window import WindowBuilder
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    failures = 0
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()

    n = 4000
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(97, dtype=np.int64)),
        "w": pa.array(np.arange(97, dtype=np.int64) * 10),
    })
    budget = 256 << 20
    pool = SessionPool(4, {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.singleChipFuse": "off",
        "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": str(budget),
        "spark.rapids.tpu.serve.admissionTimeoutMs": "60000",
    })

    def mk_mix(s):
        fdf = s.create_dataframe(fact)
        # multi-partition join keeps real shuffle blocks in play so the
        # post-drain orphan check is not vacuous
        fdf4 = s.create_dataframe(fact, num_partitions=4)
        ddf2 = s.create_dataframe(dim, num_partitions=2)
        w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
        return {
            "agg": lambda: (fdf.group_by(col("k"))
                            .agg(F.sum(col("v")).alias("sv"),
                                 F.count("*").alias("c")).collect()),
            "join": lambda: (fdf4.join(ddf2, on="k", how="inner")
                             .group_by(col("k"))
                             .agg(F.sum(col("w")).alias("sw"))
                             .collect()),
            "window": lambda: (fdf.select(
                col("k"), col("v"),
                F.row_number().over(w).alias("rn")).collect()),
            "sort": lambda: fdf.sort(col("k"), col("v")).collect(),
        }

    mixes = {id(s): mk_mix(s) for s in pool._sessions}

    def canon(tb):
        cols = sorted(tb.column_names)
        return sorted(zip(*(tb.column(c).to_pylist() for c in cols)))

    expected = {}
    with pool.session() as s:        # serial ground truth
        for name, q in mixes[id(s)].items():
            expected[name] = canon(q())

    worklist = [name for name in sorted(expected) for _ in range(4)]

    def one(name):
        with pool.session() as s:
            return name, canon(mixes[id(s)][name]())

    with cf.ThreadPoolExecutor(max_workers=4) as ex:
        results = list(ex.map(one, worklist))
    pool.drain(timeout=60)
    pool.close()

    wrong = [name for name, got in results if got != expected[name]]
    if wrong:
        failures += 1
        print(f"SERVE: {len(wrong)} concurrent result(s) diverged from "
              f"the serial ground truth: {sorted(set(wrong))}")
    dirty = m.counter("tpu_memsan_dirty_ledgers_total").value()
    if dirty:
        failures += 1
        print(f"SERVE: {dirty} dirty memsan ledger(s) under concurrency")
    # admission counters are tenant-labeled; total() sums the fleet
    admitted = m.counter("tpu_admission_admitted_total",
                         labelnames=("tenant",)).total()
    completed = m.counter("tpu_queries_completed_total").value()
    failed = m.counter("tpu_queries_failed_total").value()
    timeouts = m.counter("tpu_admission_timeouts_total",
                         labelnames=("tenant",)).total()
    if admitted != completed + failed:
        failures += 1
        print(f"SERVE: admission books don't balance: {admitted} "
              f"admitted != {completed} completed + {failed} failed")
    if failed or timeouts:
        failures += 1
        print(f"SERVE: clean mix counted {failed} failure(s), "
              f"{timeouts} timeout(s)")
    ctrl = AdmissionController.get()
    peak_in_flight = ctrl.max_in_flight_seen if ctrl else -1
    if ctrl is None or peak_in_flight <= 0:
        failures += 1
        print("SERVE: vacuous gate — no byte-weighted ticket was ever "
              "in flight")
    elif peak_in_flight > budget:
        failures += 1
        print(f"SERVE: bytes in flight exceeded the budget "
              f"({peak_in_flight} > {budget})")
    blocks = TpuShuffleManager.get().catalog.num_blocks()
    if blocks:
        failures += 1
        print(f"SERVE: {blocks} orphaned shuffle block(s) after drain")
    leaks = SpillCatalog.get().leak_report()
    if leaks:
        failures += 1
        print(f"SERVE: {len(leaks)} spillable buffer(s) leaked")

    MetricsRegistry.reset_for_tests()
    AdmissionController.reset_for_tests()
    if failures:
        print(f"serve gate: {failures} failure(s)")
        return 1
    print(f"serve gate clean ({len(results)} concurrent queries across "
          f"4 sessions matched the serial ground truth; {admitted} "
          f"admitted = {completed} completed + {failed} failed, zero "
          f"timeouts; peak {int(peak_in_flight)} ticket bytes in "
          f"flight within the {budget} budget; ledgers, shuffle "
          f"catalog and spill catalog all clean after drain)")
    return 0


def run_hbm_gate() -> int:
    """HBM-observatory gate (obs/memprof.py): (1) golden replay where
    three independent sinks must agree — the tenant timeline's
    spill-backed peak, the memsan shadow ledger's measured peak and the
    spill catalog's registered device bytes, all equal and nonzero, and
    the tpu_hbm_tenant_bytes gauge family must sum to the timeline's
    live total; (2) a 4-session pool stress where pool tenants book
    their own occupancy and ZERO events go unattributed; (3)
    anti-vacuity — an allocation injected from a context-free thread
    MUST count as unattributed, and an injected operator failure MUST
    leave exactly one well-formed post-mortem bundle naming the failing
    operator and the owning tenant."""
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.expr.window import WindowBuilder
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs import postmortem as pm
    from spark_rapids_tpu.obs.memprof import MemoryTimeline
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    failures = 0
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()
    MemoryTimeline.reset_for_tests()

    n = 4000
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(97, dtype=np.int64)),
        "w": pa.array(np.arange(97, dtype=np.int64) * 10),
    })
    pmdir = tempfile.mkdtemp(prefix="tpu_hbm_gate_pm_")
    pool = SessionPool(4, {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.singleChipFuse": "off",
        "spark.rapids.tpu.hbm.postmortem.dir": pmdir,
    })

    def mk_mix(s):
        fdf = s.create_dataframe(fact)
        fdf4 = s.create_dataframe(fact, num_partitions=4)
        ddf2 = s.create_dataframe(dim, num_partitions=2)
        w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
        return {
            "agg": lambda: (fdf.group_by(col("k"))
                            .agg(F.sum(col("v")).alias("sv"),
                                 F.count("*").alias("c")).collect()),
            "join": lambda: (fdf4.join(ddf2, on="k", how="inner")
                             .group_by(col("k"))
                             .agg(F.sum(col("w")).alias("sw"))
                             .collect()),
            "window": lambda: (fdf.select(
                col("k"), col("v"),
                F.row_number().over(w).alias("rn")).collect()),
            "sort": lambda: fdf.sort(col("k"), col("v")).collect(),
        }

    mixes = {id(s): mk_mix(s) for s in pool._sessions}
    tl = MemoryTimeline.get()

    # (1) golden replay: one fresh query, three sinks must agree
    with pool.session() as s:
        out = mixes[id(s)]["agg"]()
        assert out.num_rows > 0
        memsan_peak = int(s.last_peak_device_bytes or 0)
    timeline_peak = int(tl.report().get("peak_spill_backed_bytes", 0))
    catalog_live = int(SpillCatalog.get().device_bytes_registered())
    if not (timeline_peak > 0
            and timeline_peak == memsan_peak == catalog_live):
        failures += 1
        print(f"HBM: three sinks disagree after the golden replay: "
              f"timeline {timeline_peak}, memsan {memsan_peak}, "
              f"spill catalog {catalog_live}")

    # (2) pool stress: every event attributed, gauges reconcile
    worklist = [name for name in sorted(mixes[id(pool._sessions[0])])
                for _ in range(4)]

    def one(name):
        with pool.session() as s:
            out = mixes[id(s)][name]()
            assert out.num_rows > 0

    with cf.ThreadPoolExecutor(max_workers=4) as ex:
        list(ex.map(one, worklist))
    pool.drain(timeout=60)
    rep = tl.report()
    booked = sorted(t for t in rep.get("tenants", {})
                    if t.startswith("pool-"))
    if len(booked) < 2:
        failures += 1
        print(f"HBM: pool stress booked occupancy for {booked} only — "
              f"per-tenant attribution is vacuous")
    if rep.get("unattributed_events", 0):
        failures += 1
        print(f"HBM: {rep['unattributed_events']} event(s) went "
              f"unattributed under the pool stress")
    gauge_total = int(m.gauge("tpu_hbm_tenant_bytes",
                              labelnames=("tenant", "class")).total())
    live_total = int(tl.live_bytes())
    if gauge_total != live_total:
        failures += 1
        print(f"HBM: tpu_hbm_tenant_bytes gauges sum to {gauge_total} "
              f"but the timeline holds {live_total} live bytes")

    # (3a) anti-vacuity: a context-free allocation MUST go unattributed
    before = int(tl.report().get("unattributed_events", 0))
    rogue_rb = pa.record_batch(
        {"x": pa.array(np.arange(256, dtype=np.int64))})
    holder = {}

    def rogue():
        holder["sb"] = SpillCatalog.get().register(
            batch_to_device(rogue_rb, xp=np))

    t = threading.Thread(target=rogue)
    t.start()
    t.join()
    after = int(tl.report().get("unattributed_events", 0))
    if after <= before:
        failures += 1
        print("HBM: injected context-free allocation did NOT count as "
              "unattributed — the attribution check is vacuous")
    if holder.get("sb") is not None:
        holder["sb"].close()

    # (3b) anti-vacuity: injected operator failure -> exactly one
    # well-formed post-mortem bundle
    from spark_rapids_tpu.testing.faults import arm_filter, disarm_filter

    def boom(self, pid, ctx, *consumer):
        # generator, so the raise happens at first pull — inside the
        # operator span the flight recorder opens for FilterExec
        raise RuntimeError("hbm gate injected operator failure")
        yield

    armed = arm_filter(boom)
    raised = False
    try:
        with pool.session() as s:
            try:
                (s.create_dataframe(fact)
                 .filter(col("v") > 0)
                 .group_by(col("k"))
                 .agg(F.sum(col("v")).alias("sv"))
                 .collect())
            except Exception:
                raised = True
    finally:
        disarm_filter(armed)
    if not raised:
        failures += 1
        print("HBM: injected operator failure did not raise")
    bundles = pm.list_bundles(pmdir)
    if len(bundles) != 1:
        failures += 1
        print(f"HBM: expected exactly one post-mortem bundle, found "
              f"{len(bundles)} in {pmdir}")
    else:
        try:
            doc = pm.load_bundle(bundles[0])
            op = (doc.get("failing_operator") or {}).get("operator", "")
            rendered = pm.render_postmortem(doc)
            bad = []
            if doc.get("kind") != "query_failure":
                bad.append(f"kind={doc.get('kind')!r}")
            if not str(doc.get("tenant", "")).startswith("pool-"):
                bad.append(f"tenant={doc.get('tenant')!r}")
            if "FilterExec" not in op:
                bad.append(f"failing_operator={op!r}")
            if "report" not in (doc.get("hbm") or {}):
                bad.append("missing hbm report")
            if "FilterExec" not in rendered:
                bad.append("render omits the failing operator")
            if bad:
                failures += 1
                print("HBM: post-mortem bundle malformed: "
                      + ", ".join(bad))
        except Exception as ex:
            failures += 1
            print(f"HBM: post-mortem bundle unparseable: {ex!r}")

    pool.close()
    shutil.rmtree(pmdir, ignore_errors=True)
    MetricsRegistry.reset_for_tests()
    AdmissionController.reset_for_tests()
    MemoryTimeline.reset_for_tests()
    if failures:
        print(f"hbm gate: {failures} failure(s)")
        return 1
    print(f"hbm gate clean (three sinks agreed at {timeline_peak} "
          f"bytes; {len(worklist)} pooled queries booked "
          f"{len(booked)} tenants with zero unattributed events and "
          f"gauges reconciling at {live_total} live bytes; injected "
          f"rogue allocation tripped the attribution check; injected "
          f"operator failure left exactly one parseable post-mortem "
          f"bundle naming FilterExec)")
    return 0


# anti-vacuity fixtures for the csan gate: each must trip exactly its
# rule.  Self-contained modules the analyzer resolves without the repo.
_CSAN_ABBA_SRC = '''
import threading

class Pair:
    def __init__(self):
        self._la = threading.Lock()
        self._lb = threading.Lock()

    def forward(self):
        with self._la:
            self.inner_b()

    def backward(self):
        with self._lb:
            self.inner_a()

    def inner_a(self):
        with self._la:
            pass

    def inner_b(self):
        with self._lb:
            pass
'''

_CSAN_R009_SRC = '''
import threading

class Stats:
    _instance = None
    _ilock = threading.Lock()

    def __init__(self):
        self.tally = 0

    @classmethod
    def get(cls):
        with cls._ilock:
            if cls._instance is None:
                cls._instance = Stats()
            return cls._instance

    def bump(self):
        self.tally += 1

def root_a():
    Stats.get().bump()

def root_b():
    Stats.get().bump()
'''

_CSAN_R010_SRC = '''
import threading

_cv = threading.Condition()
_items = []

def bad_wait():
    with _cv:
        if not _items:
            _cv.wait()
        return _items.pop()
'''


def run_csan_gate() -> int:
    """tpucsan gate, four legs: (1) the repo pass is clean against the
    baseline; (2) the ABBA / shared-write / condvar fixtures each trip
    their rule (anti-vacuity); (3) the static lock-order artifact is
    non-trivial (the serving locks and their metrics edges exist); (4)
    the serve golden mix replays under the runtime lock witness and
    execution must observe zero acquisition edges the static graph
    cannot explain and zero lock-order cycles, with the contention
    metrics registered."""
    import concurrent.futures as cf

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.analysis import concurrency as cc
    from spark_rapids_tpu.analysis.repo_lint import load_baseline

    failures = 0

    # -- leg 1: repo pass clean modulo baseline -----------------------------
    diags = cc.repo_diagnostics()
    baseline = load_baseline(BASELINE)
    fresh = [d for d in diags if d.fingerprint() not in baseline]
    for d in fresh:
        failures += 1
        print(f"CSAN: new finding: {d.render()}")

    # -- leg 2: anti-vacuity fixtures ---------------------------------------
    fixtures = (("TPU-R008", {"spark_rapids_tpu/pairmod.py":
                              _CSAN_ABBA_SRC}, None),
                ("TPU-R009", {"spark_rapids_tpu/statsmod.py":
                              _CSAN_R009_SRC},
                 ["statsmod.root_a", "statsmod.root_b"]),
                ("TPU-R010", {"spark_rapids_tpu/cvmod.py":
                              _CSAN_R010_SRC}, None))
    for code, sources, roots in fixtures:
        got = {d.code for d in
               cc.analyze_sources(sources, roots=roots).diagnostics}
        if code not in got:
            failures += 1
            print(f"CSAN: {code} fixture did not trip (got "
                  f"{sorted(got) or 'nothing'}) — the rule is vacuous")

    # -- leg 3: the artifact the witness consumes is non-trivial ------------
    art = cc.lock_order_artifact()
    if len(art["locks"]) < 20 or len(art["edges"]) < 10:
        failures += 1
        print(f"CSAN: implausibly small lock graph "
              f"({len(art['locks'])} locks, {len(art['edges'])} edges) "
              f"— extraction regressed")
    if len(art["roots"]) < len(cc.THREAD_ROOTS):
        failures += 1
        print(f"CSAN: only {len(art['roots'])} of "
              f"{len(cc.THREAD_ROOTS)} declared thread roots matched "
              f"a function — the root table is stale")
    if art["cycles"]:
        failures += 1
        print(f"CSAN: static lock-order cycle(s): {art['cycles']}")

    # -- leg 4: serve corpus under the runtime lock witness -----------------
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.expr.window import WindowBuilder
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.obs import lockwitness
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()
    lockwitness.reset_for_tests()

    n = 4000
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(97, dtype=np.int64)),
        "w": pa.array(np.arange(97, dtype=np.int64) * 10),
    })
    try:
        witness = lockwitness.install(art)
        # the singletons whose instance locks the serve path takes must
        # exist before refresh() so they get wrapped
        TpuShuffleManager.get()
        SpillCatalog.get()
        pool = SessionPool(4, {
            "spark.rapids.sql.enabled": "true",
            "spark.rapids.tpu.csan.enabled": "true",
            "spark.rapids.tpu.singleChipFuse": "off",
            "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes":
                str(256 << 20),
            "spark.rapids.tpu.serve.admissionTimeoutMs": "60000",
        })
        witness.refresh()

        def mk_mix(s):
            fdf = s.create_dataframe(fact)
            fdf4 = s.create_dataframe(fact, num_partitions=4)
            ddf2 = s.create_dataframe(dim, num_partitions=2)
            w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
            return {
                "agg": lambda: (fdf.group_by(col("k"))
                                .agg(F.sum(col("v")).alias("sv"),
                                     F.count("*").alias("c"))
                                .collect()),
                "join": lambda: (fdf4.join(ddf2, on="k", how="inner")
                                 .group_by(col("k"))
                                 .agg(F.sum(col("w")).alias("sw"))
                                 .collect()),
                "window": lambda: (fdf.select(
                    col("k"), col("v"),
                    F.row_number().over(w).alias("rn")).collect()),
                "sort": lambda: fdf.sort(col("k"), col("v")).collect(),
            }

        mixes = {id(s): mk_mix(s) for s in pool._sessions}
        worklist = [name for name in sorted(mixes[id(
            pool._sessions[0])]) for _ in range(4)]

        def one(name):
            with pool.session() as s:
                return mixes[id(s)][name]()

        with cf.ThreadPoolExecutor(max_workers=4) as ex:
            list(ex.map(one, worklist))
        pool.drain(timeout=60)
        pool.close()

        rep = witness.report()
        if rep["n_wrapped"] < 8:
            failures += 1
            print(f"CSAN: witness wrapped only {rep['n_wrapped']} "
                  f"lock(s) — registration regressed")
        if not rep["edges"]:
            failures += 1
            print("CSAN: vacuous witness run — no nested acquisition "
                  "was ever observed")
        for a, b in rep["unmodeled"]:
            failures += 1
            print(f"CSAN: UNMODELED runtime edge {a} -> {b}: the "
                  f"static graph cannot explain this nesting")
        for cyc in rep["cycles"]:
            failures += 1
            print(f"CSAN: runtime lock-order cycle observed: {cyc}")
        fams = {f.name for f in MetricsRegistry.get().families()}
        for fam in ("tpu_lock_contention_total", "tpu_lock_wait_seconds"):
            if fam not in fams:
                failures += 1
                print(f"CSAN: contention metric family {fam} missing")
    finally:
        lockwitness.reset_for_tests()
        MetricsRegistry.reset_for_tests()
        AdmissionController.reset_for_tests()
        TpuShuffleManager.reset()

    if failures:
        print(f"csan gate: {failures} failure(s)")
        return 1
    print(f"csan gate clean (repo pass clean modulo baseline; R008/"
          f"R009/R010 fixtures all trip; static graph: "
          f"{len(art['locks'])} locks, {len(art['edges'])} edges, "
          f"{len(art['roots'])} thread roots, no cycles; witness "
          f"replay: {rep['n_wrapped']} locks wrapped, "
          f"{len(rep['edges'])} observed edges all modeled, zero "
          f"runtime cycles)")
    return 0


# the feedback gate's corpus: the regress corpus queries, run traced
# against an estimator ledger dir.  "cold" records the static model's
# errors; "warm" loads the cold arm's ledger and blends its recorded
# actuals back into the estimates (spark.rapids.tpu.feedback.enabled).
# Fresh subprocess per arm: the ledger singleton, jit caches and plan
# caches all start identical, so cold vs warm isolates the feedback.
_FEEDBACK_CORPUS = r"""
import json
import sys
import numpy as np
import pyarrow as pa
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.obs.estimator import EstimatorLedger

hist_dir, eventlog_dir, arm = sys.argv[1], sys.argv[2], sys.argv[3]
rng = np.random.default_rng(1234)
fact = pa.table({
    "k": pa.array((rng.integers(0, 97, 4000)).astype(np.int64)),
    "v": pa.array(rng.integers(-1000, 1000, 4000).astype(np.int64)),
})
dim = pa.table({
    "k": pa.array(np.arange(97, dtype=np.int64)),
    "w": pa.array(np.arange(97, dtype=np.int64) * 3),
})
s = (TpuSession.builder()
     .config("spark.rapids.sql.enabled", True)
     .config("spark.rapids.tpu.singleChipFuse", "off")
     .config("spark.rapids.tpu.trace.enabled", True)
     .config("spark.rapids.tpu.regress.historyDir", hist_dir)
     .config("spark.rapids.tpu.feedback.enabled", arm == "warm")
     .config("spark.rapids.tpu.eventLog.dir", eventlog_dir)
     .get_or_create())
fdf = s.create_dataframe(fact, num_partitions=2)
ddf = s.create_dataframe(dim)
out1 = (fdf.filter(col("v") > -500).group_by(col("k"))
        .agg(F.sum(col("v")).alias("sv"), F.count("*").alias("c"))
        .collect())
assert out1.num_rows == 97, out1.num_rows
out2 = (fdf.join(ddf, on="k", how="inner").group_by(col("k"))
        .agg(F.sum(col("w")).alias("sw")).collect())
assert out2.num_rows == 97, out2.num_rows
out3 = fdf.sort(col("k"), col("v")).collect()
assert out3.num_rows == 4000, out3.num_rows
print("EST_JSON=" + json.dumps(EstimatorLedger.get().snapshot()))
"""


# anti-vacuity corpus: the static row model is sabotaged by 100x at
# shuffle boundaries, so the measured map output disagrees with the
# prediction by exactly the factor the re-planner keys on.  The gate
# demands a recorded strategy_switch whose three sinks agree AND a
# bit-exact join result against the CPU-engine ground truth.
_MISESTIMATE_CORPUS = r"""
import json
import os
import sys
from spark_rapids_tpu.plan import cost

_orig = cost._static_rows


def _skewed(node, child_rows):
    r = _orig(node, child_rows)
    if type(node).__name__ == "ShuffleExchangeExec":
        return r / 100.0  # injected 100x row misestimate
    return r


cost._static_rows = _skewed
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.obs import metrics as m
from spark_rapids_tpu.obs.estimator import EstimatorLedger

hist_dir = sys.argv[1]
s = TpuSession({
    "spark.rapids.sql.enabled": True,
    "spark.rapids.tpu.regress.historyDir": hist_dir,
    "spark.rapids.tpu.trace.enabled": True,
    "spark.rapids.tpu.feedback.enabled": True,
    "spark.rapids.tpu.singleChipFuse": "off",
    "spark.rapids.sql.autoBroadcastJoinThreshold": 0,
    "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": 1 << 30,
})
n = 2000
left = s.create_dataframe(
    {"k": [i % 50 for i in range(n)], "v": list(range(n))},
    num_partitions=4)
right = s.create_dataframe(
    {"k": list(range(50)), "w": [i * 10 for i in range(50)]},
    num_partitions=4)
out = left.join(right, on="k").collect()

spans = [sp for sp in s.last_query_trace().spans
         if sp.name == "replan"]
fam = m.registry().counter("tpu_replan_total",
                           labelnames=("decision", "cause"))
metric_n = int(sum(ch.value for _, ch in fam.series()))
ledger_n = 0
with open(os.path.join(hist_dir, "estimator_ledger.jsonl")) as f:
    for line in f:
        if line.strip() and \
                json.loads(line).get("event") == "replan":
            ledger_n += 1
switches = [sp for sp in spans
            if sp.attrs.get("decision") == "strategy_switch"
            and sp.attrs.get("cause") == "row_misestimate"]

# exact results: the re-plan must never change the answer
cost._static_rows = _orig
s2 = TpuSession({"spark.rapids.sql.enabled": False})
truth = left.join(right, on="k").collect()


def canon(t):
    t = t.select(sorted(t.column_names))
    return t.combine_chunks().sort_by(
        [(c, "ascending") for c in t.column_names])


print("REPLAN_JSON=" + json.dumps({
    "rows": out.num_rows,
    "spans": len(spans), "metric": metric_n, "ledger": ledger_n,
    "strategy_switches": len(switches),
    "snapshot_replans": EstimatorLedger.get().snapshot()["replans"],
    "exact": bool(canon(out).equals(canon(truth)))}))
"""


def _feedback_subprocess(script, args, marker):
    """One fresh-process feedback-gate leg; returns the marker JSON or
    None (with the transcript printed) on failure."""
    import subprocess
    r = subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS=os.environ.get(
            "JAX_PLATFORMS", "cpu")))
    payload = None
    for line in r.stdout.splitlines():
        if line.startswith(marker + "="):
            payload = json.loads(line[len(marker) + 1:])
    if r.returncode != 0 or payload is None:
        print(f"FEEDBACK: subprocess failed rc={r.returncode}:\n"
              f"{r.stdout}\n{r.stderr}")
        return None
    return payload


def run_feedback_gate() -> int:
    """Estimator-observatory gate: cold-then-warm golden replay (warm
    must be strictly more accurate; two warm replays over identical
    ledger snapshots must show zero deterministic drift) plus the
    injected-misestimate re-plan anti-vacuity leg."""
    import shutil
    import tempfile

    from spark_rapids_tpu.obs.history import (deterministic_drift,
                                              diff_runs,
                                              distill_event_log)

    failures = 0
    root = tempfile.mkdtemp(prefix="feedback_gate_")
    try:
        cold_hist = os.path.join(root, "hist_cold")
        os.makedirs(cold_hist)
        est, fps = {}, {}
        # cold first (records the ledger), then two warm replays over
        # IDENTICAL copies of the cold ledger — each warm arm appends
        # its own observations, so sharing one dir would hand warm2 a
        # different (grown) ledger and make the drift diff meaningless
        arms = [("cold", cold_hist), ("warm", None), ("warm2", None)]
        for i, (arm, hist) in enumerate(arms):
            if hist is None:
                hist = os.path.join(root, f"hist_{arm}")
                shutil.copytree(cold_hist, hist)
                arms[i] = (arm, hist)
            evt = os.path.join(root, f"evt_{arm}")
            os.makedirs(evt)
            payload = _feedback_subprocess(
                _FEEDBACK_CORPUS,
                [hist, evt, "cold" if arm == "cold" else "warm"],
                "EST_JSON")
            if payload is None:
                return 1
            est[arm] = payload
            logs = [f for f in os.listdir(evt)
                    if f.startswith("events_")]
            fps[arm] = {"queries": distill_event_log(
                os.path.join(evt, logs[0]))} if logs else None

        if est["cold"]["observations"] == 0:
            failures += 1
            print("FEEDBACK: vacuous gate — the cold replay recorded "
                  "no observations")
        if not est["warm"]["feedback_enabled"]:
            failures += 1
            print("FEEDBACK: warm arm ran without feedback enabled")
        cold_err = est["cold"]["mean_rows_err"]
        warm_err = est["warm"]["mean_rows_err"]
        if not warm_err < cold_err:
            failures += 1
            print(f"FEEDBACK: warm ledger did not sharpen the model "
                  f"(warm mean rel row error {warm_err} !< cold "
                  f"{cold_err})")
        if fps["warm"] is None or fps["warm2"] is None:
            failures += 1
            print("FEEDBACK: corpus replay left no event log to diff")
        else:
            for dr in deterministic_drift(
                    diff_runs(fps["warm"], fps["warm2"])):
                failures += 1
                print(f"FEEDBACK DRIFT warm replay 1 -> 2: "
                      f"{dr.render()}")

        # anti-vacuity: the injected 100x misestimate MUST re-plan,
        # the three sinks must agree, and the answer must not change
        mhist = os.path.join(root, "mis_hist")
        os.makedirs(mhist)
        rep = _feedback_subprocess(
            _MISESTIMATE_CORPUS, [mhist], "REPLAN_JSON")
        if rep is None:
            return 1
        if rep["strategy_switches"] < 1:
            failures += 1
            print(f"FEEDBACK: injected 100x misestimate did not "
                  f"trigger a strategy_switch re-plan ({rep})")
        if rep["spans"] < 1 or not (
                rep["spans"] == rep["metric"] == rep["ledger"]
                == rep["snapshot_replans"]):
            failures += 1
            print(f"FEEDBACK: re-plan sinks disagree — spans "
                  f"{rep['spans']}, tpu_replan_total {rep['metric']}, "
                  f"ledger events {rep['ledger']}, snapshot "
                  f"{rep['snapshot_replans']}")
        if not rep["exact"]:
            failures += 1
            print("FEEDBACK: re-planned join diverged from the "
                  "CPU-engine ground truth")
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if failures:
        print(f"feedback gate: {failures} failure(s)")
        return 1
    print(f"feedback gate clean (warm replay mean row error "
          f"{warm_err} < cold {cold_err} over "
          f"{est['cold']['observations']} observations, zero "
          f"deterministic drift across warm replays; injected 100x "
          f"misestimate re-planned {rep['spans']} decision(s) with "
          f"span/metric/ledger agreeing and exact results)")
    return 0


def run_fleet_gate() -> int:
    """Fleet-observatory gate: two real peer processes, one merged
    trace, one aggregator — then a peer dies and everything that must
    notice does.  See the module docstring for the full contract."""
    import subprocess

    import pyarrow as pa

    import spark_rapids_tpu.obs.metrics as m
    from spark_rapids_tpu.columnar.device import batch_to_arrow
    from spark_rapids_tpu.obs import tracer as tr
    from spark_rapids_tpu.obs.fleet import (ClockSync, FleetAggregator,
                                            RemoteSpanStore,
                                            install_aggregator)
    from spark_rapids_tpu.obs.health import HealthMonitor
    from spark_rapids_tpu.shuffle import locality
    from spark_rapids_tpu.shuffle.heartbeat import HeartbeatManager
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    from spark_rapids_tpu.shuffle.registry import (BlockEndpoint,
                                                   BlockLocationRegistry)
    from spark_rapids_tpu.shuffle.serve_map import (
        DIM_SID, FACT_SID, build_side_tables, partition_record_batch)

    failures = 0
    rows, parts, seed = 6000, 3, 23
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE="1")

    def spawn(name):
        return subprocess.Popen(
            [sys.executable, "-m",
             "spark_rapids_tpu.shuffle.serve_map",
             "--rows", str(rows), "--parts", str(parts),
             "--codec", "lz4", "--seed", str(seed),
             "--executor-id", name],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, env=env, cwd=REPO)

    def reset_all():
        tr.uninstall()
        install_aggregator(None)
        locality.reset_pool()
        BlockLocationRegistry.reset()
        TpuShuffleManager.reset()
        RemoteSpanStore.reset()
        ClockSync.reset()
        m.MetricsRegistry.reset_for_tests()

    reset_all()
    # one peer owns the fact side, the other the dim side: every fetch
    # of the golden join exercises BOTH peers' serve paths
    children = {"peer-a": spawn("peer-a"), "peer-b": spawn("peer-b")}
    stats_a = None
    try:
        ports = {}
        for name, child in children.items():
            fields = child.stdout.readline().split()
            if len(fields) < 4 or fields[0] != "PORT" \
                    or fields[2] != "OBS":
                print(f"FLEET: {name} announced no PORT/OBS line")
                return 1
            ports[name] = (int(fields[1]), int(fields[3]))
        reg = BlockLocationRegistry.get()
        reg.set_local("driver", "127.0.0.1", 0)
        hb = HeartbeatManager(timeout_s=30.0)
        for name, (port, obs_port) in ports.items():
            reg.register(FACT_SID if name == "peer-a" else DIM_SID,
                         [BlockEndpoint(name, "127.0.0.1", port)])
            hb.register_executor(name, "127.0.0.1", port,
                                 obs_port=obs_port)
        reg.attach_heartbeat(hb)
        agg = install_aggregator(FleetAggregator(hb, max_peers=4,
                                                 timeout_s=5.0))

        # -- clean half: golden cross-process join under a live tracer
        trace = tr.install(tr.QueryTrace())
        out = []
        for pid in range(parts):
            sides = []
            for sid in (FACT_SID, DIM_SID):
                rbs = [batch_to_arrow(b) for b in
                       locality.read_reduce_blocks(sid, pid)]
                sides.append(pa.Table.from_batches(rbs) if rbs else None)
            if sides[0] is not None and sides[1] is not None:
                out.append(sides[0].join(sides[1], "k"))
        got = pa.concat_tables(out).sort_by(
            [("k", "ascending"), ("v", "ascending")])
        trace.finalize()
        tr.uninstall()
        fact, dim = build_side_tables(rows, seed)
        fparts = partition_record_batch(fact, "k", parts)
        dparts = partition_record_batch(dim, "k", parts)
        ref = pa.concat_tables(
            [pa.table(fparts[p]).join(pa.table(dparts[p]), "k")
             for p in range(parts) if p in fparts and p in dparts]
        ).sort_by([("k", "ascending"), ("v", "ascending")])
        if not got.equals(ref):
            failures += 1
            print("FLEET: cross-process join diverged from the "
                  "in-process reference")

        spans = trace.span_dicts()
        by_parent = {}
        for s in spans:
            by_parent.setdefault(s.get("parentId"), []).append(s)
        fetch = [s for s in spans if s["name"] == "shuffle.fetch"]
        bad_fetch = 0
        for f in fetch:
            kids = by_parent.get(f["spanId"], [])
            roots = [k for k in kids
                     if k.get("proc") == f["attrs"].get("peer")]
            names = {k["name"] for k in roots}
            f0, f1 = f["startNs"], f["startNs"] + f["durNs"]
            ok = {"shuffle.serve.metadata",
                  "shuffle.serve.transfer"} <= names
            for r in roots:
                ok = ok and f0 <= r["startNs"] \
                    and r["startNs"] + r["durNs"] <= f1
            if not ok:
                bad_fetch += 1
        if bad_fetch:
            failures += 1
            print(f"FLEET: {bad_fetch}/{len(fetch)} fetch span(s) "
                  f"missing nested producer serve spans (or spans "
                  f"outside the parent interval)")
        procs = {s.get("proc") for s in spans if s.get("proc")}
        # anti-vacuity, clean direction: the merge must have HAPPENED,
        # for both peers, with zero losses
        if trace.remote_spans_merged == 0 or procs != set(children):
            failures += 1
            print(f"FLEET: vacuous merge — {trace.remote_spans_merged} "
                  f"remote span(s) from peers {sorted(procs)}")
        lost_clean = m.counter(
            "tpu_trace_remote_spans_lost_total").value()
        if trace.remote_spans_lost or lost_clean:
            failures += 1
            print(f"FLEET: clean run lost {trace.remote_spans_lost} "
                  f"remote span(s) (counter {lost_clean})")

        peers = agg.scrape()
        scraped = [p for p, e in peers.items() if e.get("scraped")]
        if sorted(scraped) != sorted(children):
            failures += 1
            print(f"FLEET: aggregator scraped {sorted(scraped)}, "
                  f"wanted both of {sorted(children)}")
        rollup = m.gauge("tpu_fleet_rollup",
                         labelnames=("peer", "name"))
        for name in children:
            served = rollup.value(
                peer=name, name="tpu_shuffle_server_requests_total")
            if not served:
                failures += 1
                print(f"FLEET: no rollup series shows {name} serving "
                      f"requests")
        verdict_clean = agg.verdict(scrape_first=False)["status"]
        if verdict_clean != "ok":
            failures += 1
            print(f"FLEET: clean fleet verdict is {verdict_clean}")

        # -- degraded half: kill peer-b mid-fleet, fetch into the hole
        children["peer-b"].kill()
        children["peer-b"].wait()
        trace2 = tr.install(tr.QueryTrace())
        try:
            list(locality.read_reduce_blocks(DIM_SID, 0))
            failures += 1
            print("FLEET: fetch against the killed peer succeeded")
        except Exception:
            pass
        trace2.finalize()
        tr.uninstall()
        lost_spans = [s for s in trace2.span_dicts()
                      if s["name"] == "shuffle.fetch"
                      and s["attrs"].get("spans_lost")]
        lost_total = m.counter(
            "tpu_trace_remote_spans_lost_total").value()
        # anti-vacuity, degraded direction: the orphan path must fire
        if not lost_spans or lost_total <= lost_clean:
            failures += 1
            print(f"FLEET: peer death surfaced no orphan spans "
                  f"({len(lost_spans)} annotated, counter "
                  f"{lost_total})")
        if any(s["status"] != "error" for s in lost_spans):
            failures += 1
            print("FLEET: a spans_lost fetch span is not closed typed")
        # the children never run a heartbeat loop; a dead process is
        # silence, which expiry models as a stale last-heartbeat stamp
        hb._peers["peer-b"].last_heartbeat -= hb.timeout_s + 1
        verdict = agg.verdict()
        if verdict["status"] != "degraded" or not any(
                "peer-b" in r for r in verdict["reasons"]):
            failures += 1
            print(f"FLEET: dead peer left verdict {verdict['status']} "
                  f"(reasons {verdict['reasons']})")
        snap = HealthMonitor().snapshot()
        if snap["status"] != "degraded" or \
                snap["components"].get("fleet", {}).get("status") \
                != "degraded":
            failures += 1
            print(f"FLEET: /healthz does not carry the degraded fleet "
                  f"verdict (status {snap['status']})")

        # peer-a shuts down clean: its span buffer must be fully
        # drained (every serve span came home in the merged trace)
        children["peer-a"].stdin.write("done\n")
        children["peer-a"].stdin.flush()
        stats_line = children["peer-a"].stdout.readline()
        stats_a = json.loads(stats_line[len("STATS "):]) \
            if stats_line.startswith("STATS ") else None
        if stats_a is None or stats_a.get("unpulled_spans") != 0:
            failures += 1
            print(f"FLEET: peer-a left serve spans unpulled "
                  f"({stats_a and stats_a.get('unpulled_spans')})")
        if stats_a is not None and stats_a.get("leaked_blocks"):
            failures += 1
            print(f"FLEET: peer-a leaked "
                  f"{stats_a['leaked_blocks']} block(s)")
    finally:
        for child in children.values():
            try:
                child.stdin.close()
                child.stdout.close()
            except OSError:
                pass
            if child.poll() is None:
                child.kill()
                child.wait()
        reset_all()
    if failures:
        print(f"fleet gate: {failures} failure(s)")
        return 1
    print(f"fleet gate clean (cross-process join bit-exact over "
          f"{parts} partitions x 2 peers; merged trace carries "
          f"{len(fetch)} fetch spans with producer serve spans nested "
          f"and zero lost; rollup + ok verdict for both peers; killed "
          f"peer degraded the fleet verdict and /healthz and counted "
          f"{int(lost_total)} orphaned span record(s); peer-a drained "
          f"clean)")
    return 0


def run_faults_gate() -> int:
    """tpufsan fault-injection campaign: the raise-graph artifact
    enumerates every statically-reachable (seam, typed-error) pair
    (>= 50) and the gate injects each one, asserting (a) the exact
    typed error propagates to the seam's caller, (b) the admission /
    shuffle / spill books balance afterward with all spans closed, and
    (c) exactly one parseable post-mortem bundle records the failure.
    Background thread roots (heartbeat loop, metrics endpoint) get
    their own legs: an injected fault must increment
    tpu_background_errors_total{root}, degrade health and black-box a
    background_failure bundle while the thread SURVIVES.  Anti-vacuity:
    the books check must flag planted orphans, and an untyped injected
    error must fail the propagation verdict."""
    import shutil
    import tempfile
    import time as _time
    import urllib.error
    import urllib.request

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.analysis import raiseflow
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import (PoolClosedError, PoolTimeout,
                                           SessionPool)
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.testing.faults import arm_filter, disarm_filter
    from spark_rapids_tpu.obs import bgerrors, health
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs import postmortem as pm
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.shuffle import transport as tr
    from spark_rapids_tpu.shuffle.errors import TpuShuffleError
    from spark_rapids_tpu.shuffle.heartbeat import (HeartbeatEndpoint,
                                                    HeartbeatManager)
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    failures = 0
    injected = 0

    # -- leg 1: the static campaign plan itself -----------------------------
    for d in raiseflow.repo_diagnostics():
        failures += 1
        print(f"FAULTS: raiseflow finding (fix it, don't baseline it): "
              f"{d.render()}")
    art = raiseflow.raise_graph_artifact()
    plan = art["injections"]
    if len(plan) < 50:
        failures += 1
        print(f"FAULTS: injection plan shrank to {len(plan)} pairs "
              f"(< 50) — seam reachability regressed")
    leaks = sum(len(s["untyped"]) for s in art["seams"].values())
    if leaks:
        failures += 1
        print(f"FAULTS: {leaks} untyped operational leak(s) at public "
              f"seams in the artifact")
    by_seam = {}
    for inj in plan:
        by_seam.setdefault(inj["seam"], []).append(inj["error"])

    # -- fresh world --------------------------------------------------------
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()
    bgerrors.reset()
    pmdir = tempfile.mkdtemp(prefix="tpu_faults_pm_")

    def books(session=None):
        probs = []
        blocks = TpuShuffleManager.get().catalog.num_blocks()
        if blocks:
            probs.append(f"{blocks} orphaned shuffle block(s)")
        sleaks = SpillCatalog.get().leak_report()
        if sleaks:
            probs.append(f"{len(sleaks)} spill leak(s)")
        ac = AdmissionController.get()
        if ac is not None:
            if ac.bytes_in_flight():
                probs.append(f"{ac.bytes_in_flight()} admission "
                             f"byte(s) still in flight")
            if ac.queue_depth():
                probs.append(f"admission queue depth "
                             f"{ac.queue_depth()}")
        if session is not None:
            trace = session.last_query_trace()
            if trace is not None and trace.open_span_count():
                probs.append(f"{trace.open_span_count()} unclosed "
                             f"span(s)")
        return probs

    def expect_bundle(before, name):
        new = [b for b in pm.list_bundles(pmdir) if b not in before]
        if len(new) != 1:
            return [f"expected exactly 1 new bundle, found {len(new)}"]
        try:
            doc = pm.load_bundle(new[0])
        except Exception as ex:
            return [f"bundle unparseable: {ex!r}"]
        probs = []
        if (doc.get("error") or {}).get("type") != name:
            probs.append(f"bundle names "
                         f"{(doc.get('error') or {}).get('type')!r}, "
                         f"injected {name}")
        if not doc.get("kind"):
            probs.append("bundle has no kind")
        return probs

    # -- leg 2: session seams (main-query, serving-client) ------------------
    tb = pa.table({
        "k": pa.array((np.arange(400) % 7).astype(np.int64)),
        "v": pa.array(np.arange(400, dtype=np.int64))})
    conf = {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.hbm.postmortem.dir": pmdir,
        "spark.rapids.tpu.hbm.postmortem.maxBundles": "500",
    }
    sess = TpuSession(conf)
    pool = SessionPool(2, conf)

    def inject_session(seam, name, runner, raise_obj=None,
                       expect_name=None):
        """Arm FilterExec with the constructed error, run one golden
        query through the seam, verify type + books + bundle."""
        err = raise_obj if raise_obj is not None \
            else raiseflow.construct_error(name)
        expect_name = expect_name or name

        def boom(self, pid, ctx, *consumer):
            raise err
            yield  # generator: the raise happens inside the op span

        armed = arm_filter(boom)
        before = set(pm.list_bundles(pmdir))
        caught = None
        used_session = []
        try:
            try:
                runner(used_session)
            except BaseException as ex:
                caught = ex
        finally:
            disarm_filter(armed)
        probs = []
        if caught is None:
            probs.append("injected fault never surfaced")
        elif type(caught).__name__ != expect_name:
            probs.append(f"typed propagation broken: injected "
                         f"{expect_name}, caller saw "
                         f"{type(caught).__name__}: {caught}")
        probs += books(used_session[0] if used_session else None)
        probs += expect_bundle(before, expect_name)
        return probs

    def run_main(used):
        used.append(sess)
        sess.create_dataframe(tb, num_partitions=2) \
            .filter(col("v") > 5).collect()

    def run_pool(used):
        def q(s):
            used.append(s)
            return (s.create_dataframe(tb, num_partitions=2)
                    .filter(col("v") > 5).collect())
        pool.run(q, timeout=60)

    for seam, runner in (("main-query", run_main),
                         ("serving-client", run_pool)):
        for name in by_seam.get(seam, []):
            injected += 1
            for p in inject_session(seam, name, runner):
                failures += 1
                print(f"FAULTS [{seam}/{name}]: {p}")

    # -- leg 3: pool seams driven for real ----------------------------------
    def harness_bundle(seam, err):
        """Non-session seams have no session to black-box for them; the
        serving harness records the typed failure itself."""
        pm.dump_postmortem(pmdir, err, tenant=f"faults:{seam}",
                           max_bundles=500)

    def drive_pool_seam(seam, name, driver):
        before = set(pm.list_bundles(pmdir))
        caught = None
        try:
            driver()
        except BaseException as ex:
            caught = ex
        probs = []
        if caught is None:
            probs.append("real-path drive raised nothing")
        elif type(caught).__name__ != name:
            probs.append(f"expected {name}, got "
                         f"{type(caught).__name__}: {caught}")
        else:
            harness_bundle(seam, caught)
            probs += expect_bundle(before, name)
        probs += books()
        return probs

    def drive_borrow_closed():
        p2 = SessionPool(1, {"spark.rapids.sql.enabled": "true"})
        p2.close()
        with p2.session():
            pass

    def drive_borrow_timeout():
        p2 = SessionPool(1, {"spark.rapids.sql.enabled": "true"})
        try:
            with p2.session():
                with p2.session(timeout=0.05):
                    pass
        finally:
            p2.close()

    def drive_drain_timeout():
        p2 = SessionPool(1, {"spark.rapids.sql.enabled": "true"})
        try:
            ctx = p2.session()
            ctx.__enter__()  # held busy past the drain deadline
            try:
                p2.drain(timeout=0.05)
            finally:
                ctx.__exit__(None, None, None)
        finally:
            p2.close()

    for seam, name, driver in (
            ("pool-borrow", "PoolClosedError", drive_borrow_closed),
            ("pool-borrow", "PoolTimeout", drive_borrow_timeout),
            ("pool-drain", "PoolTimeout", drive_drain_timeout)):
        injected += 1
        for p in drive_pool_seam(seam, name, driver):
            failures += 1
            print(f"FAULTS [{seam}/{name}]: {p}")

    # -- leg 4: shuffle-fetcher seam ----------------------------------------
    class _Tx:
        def __init__(self, result=None, exc=None):
            self.result, self.exc = result, exc

        def wait(self, timeout=None):
            if self.exc is not None:
                raise self.exc
            return self.result

    class _StubClient:
        def __init__(self, err):
            self.err = err

        def fetch_metadata(self, sid, rid, ctx=None):
            return _Tx(result=[((sid, 0, rid, 0), None)])

        def fetch_block(self, sid, mid, rid, idx, xp=None, ctx=None):
            return _Tx(exc=self.err)

    for name in by_seam.get("shuffle-fetcher", []):
        injected += 1
        err = raiseflow.construct_error(name)
        before = set(pm.list_bundles(pmdir))
        fetcher = tr.AsyncBlockFetcher(_StubClient(err), 7, 0,
                                       timeout=5.0)
        caught = None
        try:
            list(fetcher.blocks())
        except BaseException as ex:
            caught = ex
        probs = []
        if caught is None:
            probs.append("fetcher swallowed the injected fault")
        elif type(caught).__name__ != name:
            probs.append(f"fetch classification mangled the type: "
                         f"injected {name}, got "
                         f"{type(caught).__name__}: {caught}")
        else:
            harness_bundle("shuffle-fetcher", caught)
            probs += expect_bundle(before, name)
        probs += books()
        for p in probs:
            failures += 1
            print(f"FAULTS [shuffle-fetcher/{name}]: {p}")
    errs_counted = sum(
        ch.value for _, ch in
        m.counter("tpu_shuffle_fetch_errors_total",
                  labelnames=("kind",)).series())
    # cancellation is control flow, not a fetch failure: the fetcher
    # passes TpuQueryCancelled/TpuQueryDeadlineExceeded through without
    # booking a fetch-error kind (they count in tpu_cancellations_total)
    fetch_faults = [n for n in by_seam.get("shuffle-fetcher", [])
                    if n not in ("TpuQueryCancelled",
                                 "TpuQueryDeadlineExceeded")]
    if errs_counted < len(fetch_faults):
        failures += 1
        print(f"FAULTS: fetch-error counter saw {errs_counted} of "
              f"{len(fetch_faults)} injections")

    # -- leg 5: block-server seam (typed relay over the wire) ---------------
    for name in by_seam.get("block-server", []):
        injected += 1
        err = raiseflow.construct_error(name)
        mgr = TpuShuffleManager.get()
        server = tr.ShuffleServer(mgr).start()
        before = set(pm.list_bundles(pmdir))
        real_get = mgr.catalog.get
        mgr.catalog.get = lambda *a, **k: (_ for _ in ()).throw(err)
        caught = None
        try:
            client = tr.ShuffleClient("127.0.0.1", server.port,
                                      timeout=5.0)
            try:
                client.fetch_block(1, 0, 0, 0).wait(5.0)
            except BaseException as ex:
                caught = ex
            probs = []
            if caught is None:
                probs.append("server swallowed the injected fault")
            elif not isinstance(caught, TpuShuffleError):
                probs.append(f"wire relay lost the typed taxonomy: "
                             f"got {type(caught).__name__}: {caught}")
            elif name not in str(caught):
                probs.append(f"relayed error does not name the "
                             f"server-side {name}: {caught}")
            else:
                harness_bundle("block-server", caught)
                probs += expect_bundle(before, type(caught).__name__)
            # liveness: the server must still answer after the fault
            mgr.catalog.get = real_get
            metas = client.fetch_metadata(99, 0).wait(5.0)
            if metas is None:
                probs.append("server dead after relaying the fault")
        finally:
            mgr.catalog.get = real_get
            server.stop()
        probs += books()
        for p in probs:
            failures += 1
            print(f"FAULTS [block-server/{name}]: {p}")

    # -- leg 6: background thread roots -------------------------------------
    bgerrors.reset()
    bgerrors.set_postmortem_dir(pmdir)

    def bg_counter(root):
        fam = m.counter("tpu_background_errors_total",
                        labelnames=("root",))
        return sum(ch.value for lbl, ch in fam.series()
                   if lbl.get("root") == root)

    # heartbeat loop: one poisoned beat, then the loop must keep beating
    before = set(pm.list_bundles(pmdir))
    hb_mgr = HeartbeatManager(timeout_s=30.0)
    calls = {"n": 0}
    real_beat = hb_mgr.executor_heartbeat

    def flaky_beat(eid):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("faults gate injected heartbeat failure")
        return real_beat(eid)

    hb_mgr.executor_heartbeat = flaky_beat
    ep = HeartbeatEndpoint(hb_mgr, "e1", "127.0.0.1", 1,
                           interval_s=0.02).start()
    deadline = _time.monotonic() + 5.0
    while calls["n"] < 3 and _time.monotonic() < deadline:
        _time.sleep(0.02)
    ep.stop()
    probs = []
    if calls["n"] < 3:
        probs.append(f"heartbeat loop died after the injected fault "
                     f"(beats: {calls['n']})")
    if bg_counter("heartbeat-loop") < 1:
        probs.append("tpu_background_errors_total{root=heartbeat-loop} "
                     "never incremented")
    rec = bgerrors.last_error("heartbeat-loop")
    if not rec or rec["type"] != "RuntimeError":
        probs.append(f"last-error record wrong: {rec}")
    new = [b for b in pm.list_bundles(pmdir) if b not in before]
    kinds = []
    for b in new:
        try:
            kinds.append(pm.load_bundle(b).get("kind"))
        except Exception:
            kinds.append("<unparseable>")
    if kinds != ["background_failure"]:
        probs.append(f"expected one background_failure bundle, "
                     f"got {kinds}")
    injected += 1
    for p in probs:
        failures += 1
        print(f"FAULTS [heartbeat-loop]: {p}")

    # metrics endpoint: a failing scrape must 500 + count + degrade,
    # and the endpoint must keep serving afterward
    before = set(pm.list_bundles(pmdir))
    srv = health.MetricsServer(0)
    real_render = health.render_prometheus

    def bad_render(*a, **k):
        raise RuntimeError("faults gate injected scrape failure")

    probs = []
    try:
        health.render_prometheus = bad_render
        code = None
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5)
        except urllib.error.HTTPError as ex:
            code = ex.code
        if code != 500:
            probs.append(f"poisoned scrape answered {code}, not 500")
        health.render_prometheus = real_render
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics",
                timeout=5) as resp:
            if resp.status != 200:
                probs.append(f"endpoint dead after the fault: "
                             f"{resp.status}")
        if bg_counter("metrics-http") < 1:
            probs.append("tpu_background_errors_total"
                         "{root=metrics-http} never incremented")
        snap = srv.monitor.snapshot()
        comp = (snap.get("components") or {}).get("background")
        status = comp.get("status") if isinstance(comp, dict) else comp
        if status not in ("degraded", "DEGRADED"):
            probs.append(f"health did not degrade on a background "
                         f"fault: {status!r}")
        new = [b for b in pm.list_bundles(pmdir) if b not in before]
        if len(new) != 1:
            probs.append(f"expected one metrics-http bundle, "
                         f"found {len(new)}")
    finally:
        health.render_prometheus = real_render
        srv.close()
    injected += 1
    for p in probs:
        failures += 1
        print(f"FAULTS [metrics-http]: {p}")

    # -- leg 7: anti-vacuity ------------------------------------------------
    # (a) the books check must flag planted orphans
    from spark_rapids_tpu.columnar.device import batch_to_device
    from spark_rapids_tpu.shuffle.manager import ShuffleBlockId
    rb = pa.record_batch({"x": pa.array(np.arange(64, dtype=np.int64))})
    planted_sb = SpillCatalog.get().register(batch_to_device(rb, xp=np))
    TpuShuffleManager.get().catalog.add(
        ShuffleBlockId(9999, 0, 0), batch_to_device(rb, xp=np))
    planted = books()
    TpuShuffleManager.get().catalog.remove_shuffle(9999)
    planted_sb.close()
    if len(planted) < 2:
        failures += 1
        print(f"FAULTS: books check is vacuous — planted an orphan "
              f"block AND a spill leak, it reported {planted}")
    if books():
        failures += 1
        print(f"FAULTS: books dirty after anti-vacuity cleanup: "
              f"{books()}")
    # (b) an untyped injected error must fail the propagation verdict
    untyped = inject_session(
        "main-query", "TpuShuffleTimeoutError", run_main,
        raise_obj=RuntimeError("untyped leak the verdict must catch"),
        expect_name="TpuShuffleTimeoutError")
    if not any("typed propagation broken" in p for p in untyped):
        failures += 1
        print("FAULTS: propagation verdict is vacuous — an untyped "
              "RuntimeError injection produced no typed-propagation "
              "complaint")

    pool.close()
    shutil.rmtree(pmdir, ignore_errors=True)
    bgerrors.reset()
    MetricsRegistry.reset_for_tests()
    AdmissionController.reset_for_tests()
    if failures:
        print(f"faults gate: {failures} failure(s) over {injected} "
              f"injection(s)")
        return 1
    print(f"faults gate clean ({injected} fault injections across "
          f"{len(by_seam)} seams + 2 background roots: 100% typed "
          f"propagation, books balanced, one parseable post-mortem "
          f"bundle per failure)")
    return 0


# --- tpudsan: determinism & replay-safety gate ------------------------------

# planted R015 hazards: a wall-clock read and a set-literal iteration on
# a result-affecting path in exec/ — both must trip or the rule is vacuous
_DSAN_R015_SRC = '''\
import time


def route_rows(batches, nparts):
    out = {}
    stamp = time.time()
    for key in {"alpha", "beta", "gamma"}:
        out[key] = stamp
    return out
'''

# planted R016 hazard: a float accumulator folded across an
# arrival-ordered source with no tolerance and no canonicalization
_DSAN_R016_SRC = '''\
def fold(batches):
    running_sum = 0.0
    for b in batches:
        running_sum += b.column_sum("v")
    return running_sum
'''

# the set-iteration injection, run for REAL under two PYTHONHASHSEEDs:
# partition routing follows set(KEYS) iteration order, so the printed
# block digests must differ between seeds (dynamic anti-vacuity) AND the
# same source must trip TPU-R015 statically (for key in set(...)).
_DSAN_HASHSEED_SRC = r"""
import json

import pyarrow as pa

from spark_rapids_tpu.shuffle.digest import block_digest

KEYS = ["key-%03d" % i for i in range(32)]
assign = {}
pos = 0
for key in set(KEYS):
    assign.setdefault(pos % 4, []).append(key)
    pos += 1
digests = {}
for pid in sorted(assign):
    ks = assign[pid]
    rb = pa.RecordBatch.from_pydict({
        "k": pa.array(ks, type=pa.string()),
        "v": pa.array([KEYS.index(k) for k in ks], type=pa.int64()),
    })
    digests[str(pid)] = block_digest(rb)
print(json.dumps(digests))
"""


def run_dsan_gate() -> int:
    """tpudsan gate, four legs: (1) the determinism repo pass
    (TPU-R015/R016 + the L017 fingerprint-hygiene registry check) is
    finding-free with nothing frozen in the baseline; (2) static
    anti-vacuity — the planted R015/R016 sources, an L017 volatile /
    overlapping fingerprint schema and a stable_merge=off float partial
    aggregate must each trip their rule; (3) the permuted-replay oracle
    — every golden-corpus exchange site replays its map write under
    permuted batch arrival and again under a changed input split, and
    every subtree that CLAIMS order_stable or better must reproduce its
    content digests (bit_exact claims: per-(map,reduce) block-digest
    multisets; order_stable claims: per-(map,reduce) row-multiset
    digests; changed split: per-reduce row folds, skipped for
    partition-scoped partials), with every recorded write-time digest
    cross-checked against a recompute; (4) dynamic anti-vacuity — the
    planted arrival-order float sum and the PYTHONHASHSEED-dependent
    set-iteration router must each produce DIFFERENT digests when
    replayed, proving the oracle can see real nondeterminism."""
    import subprocess
    from collections import Counter

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.analysis import determinism as dsan
    from spark_rapids_tpu.analysis.plan_lint import lint_plan
    from spark_rapids_tpu.analysis.repo_lint import load_baseline
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec import base as eb
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.basic import LocalScanExec
    from spark_rapids_tpu.expr.aggregates import (AggregateExpression,
                                                  PARTIAL, Sum)
    from spark_rapids_tpu.expr.core import AttributeReference
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.shuffle.digest import (block_digest,
                                                 fold_multiset,
                                                 row_multiset_digest)
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.manager import (TpuShuffleManager,
                                                  materialize_block)
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning

    failures = 0

    # -- leg 1: repo pass finding-free, nothing frozen ----------------------
    for d in dsan.repo_diagnostics():
        failures += 1
        print(f"DSAN: repo finding (baseline is burned empty): "
              f"{d.render()}")
    frozen = [fp for fp in load_baseline(BASELINE)
              if fp.split("\t", 1)[0] in ("TPU-R015", "TPU-R016",
                                          "TPU-L017")]
    if frozen:
        failures += 1
        print(f"DSAN: {len(frozen)} determinism fingerprint(s) frozen "
              f"in the baseline — these rules must stay at zero debt")

    # -- leg 2: static anti-vacuity -----------------------------------------
    got = {d.code for d in dsan.module_diagnostics(
        _DSAN_R015_SRC, "spark_rapids_tpu/exec/injected.py")}
    n_r015 = sum(d.code == "TPU-R015" for d in dsan.module_diagnostics(
        _DSAN_R015_SRC, "spark_rapids_tpu/exec/injected.py"))
    if n_r015 < 2:
        failures += 1
        print(f"DSAN: R015 fixture tripped {n_r015}/2 plants (wall "
              f"clock + set iteration) — the rule is vacuous "
              f"(got {sorted(got)})")
    got = {d.code for d in dsan.module_diagnostics(
        _DSAN_R016_SRC, "spark_rapids_tpu/exec/injected.py")}
    if "TPU-R016" not in got:
        failures += 1
        print(f"DSAN: R016 fixture did not trip (got "
              f"{sorted(got) or 'nothing'}) — the rule is vacuous")
    hyg = dsan.fingerprint_hygiene_diagnostics(
        deterministic=["plan_hash", "submit_time_ms"],
        timing=["submit_time_ms"])
    if sum(d.code == "TPU-L017" for d in hyg) < 1:
        failures += 1
        print("DSAN: L017 did not flag an overlapping volatile "
              "fingerprint field — the hygiene check is vacuous")
    hyg = dsan.fingerprint_hygiene_diagnostics(
        deterministic=["plan_hash", "wall_start"], timing=[])
    if sum(d.code == "TPU-L017" for d in hyg) < 1:
        failures += 1
        print("DSAN: L017 did not flag a time-derived deterministic "
              "fingerprint field — the hygiene check is vacuous")

    def _inject_plan(stable):
        """scan(batch_rows=1) -> PARTIAL float Sum -> hash exchange.
        With stable_merge off the partial's float buffers fold in batch
        arrival order — the canonical L016 hazard; the data is chosen so
        a reversed arrival changes the sum ((1e16 - 1e16) + 1 = 1 but
        (1 - 1e16) + 1e16 = 0 in float64)."""
        tbl = pa.table({
            "k": pa.array([0, 0, 0], type=pa.int64()),
            "v": pa.array([1e16, -1e16, 1.0], type=pa.float64()),
        })
        scan = LocalScanExec(tbl, num_partitions=1, batch_rows=1)
        scan.placement = eb.CPU
        partial = TpuHashAggregateExec(
            [AttributeReference("k")],
            [AggregateExpression(Sum(AttributeReference("v")))],
            PARTIAL, scan)
        partial.placement = eb.CPU
        partial.stable_merge = stable
        ex = ShuffleExchangeExec(
            HashPartitioning([AttributeReference("k")], 2), partial)
        ex.placement = eb.CPU
        return ex, scan

    bad_ex, _ = _inject_plan(stable=False)
    got = {d.code for d in lint_plan(bad_ex, RapidsConf({}))}
    if "TPU-L016" not in got:
        failures += 1
        print(f"DSAN: the stable_merge=off float partial did not trip "
              f"TPU-L016 (got {sorted(got)}) — the rule is vacuous")
    clean_ex, _ = _inject_plan(stable=True)
    got = {d.code for d in lint_plan(clean_ex, RapidsConf({}))}
    if "TPU-L016" in got:
        failures += 1
        print("DSAN: the canonical-merge twin tripped TPU-L016 — "
              "false positive on the clean shape")

    # -- leg 3: the permuted-replay oracle over golden exchange sites -------
    def _walk(node):
        yield node
        for c in node.children:
            yield from _walk(c)

    def _prep_scans(root, batch_rows, extra_parts=0):
        """Deterministic chunking for the oracle: fixed batch_rows so
        legs differ ONLY in what the leg varies; pin caches off so
        every leg rereads the table."""
        for n in _walk(root):
            if isinstance(n, LocalScanExec):
                n.batch_rows = batch_rows
                n.pin_cache = None
                n._num_partitions += extra_parts

    class _Permuted(eb.Exec):
        """Adversarial scheduler: replays the child's batches in
        reversed arrival order.  Exactly the perturbation an
        order_stable claim promises immunity to, so the wrapper itself
        declares nothing."""

        def __init__(self, inner):
            super().__init__([inner])
            self.placement = inner.placement

        @property
        def output_names(self):
            return self.children[0].output_names

        @property
        def output_types(self):
            return self.children[0].output_types

        def execute_partition(self, pid, ctx):
            return iter(list(
                self.children[0].execute_partition(pid, ctx))[::-1])

    def _permute_scans(root):
        for n in list(_walk(root)):
            if isinstance(n, _Permuted):
                continue
            for i, c in enumerate(n.children):
                if isinstance(c, LocalScanExec):
                    n.children[i] = _Permuted(c)

    def _run_exchange(ex, conf_map):
        """Drive ONE exchange's map write and harvest its content
        addressing: recorded write-time digests per (map, reduce), a
        row-multiset digest per (map, reduce), the per-reduce row fold,
        and any recorded-vs-recomputed digest mismatches."""
        conf = RapidsConf(dict(conf_map))
        ctx = eb.ExecContext(conf)
        ctx.task_context["no_speculation"] = True
        ex._ensure_written(ctx)
        sid = ex._shuffle_id
        mgr = TpuShuffleManager.get()
        blockdg = {}   # (mid, rid) -> Counter of recorded block digests
        for ((_, mid, rid), _idx), dg in \
                mgr.catalog.digests_for_shuffle(sid).items():
            blockdg.setdefault((mid, rid), Counter())[dg] += 1
        rowdg = {}     # (mid, rid) -> u64 row-multiset fold
        reduce_fold = {}  # rid -> u64 row fold across all maps
        bad_records = []
        for rid in range(ex.num_partitions):
            for blk in mgr.catalog.blocks_for_reduce(sid, rid):
                for i, sb in enumerate(mgr.catalog.get(blk)):
                    rb = materialize_block(sb, np)
                    recorded = mgr.catalog.digest(blk, i)
                    recomputed = block_digest(rb)
                    if recorded != recomputed:
                        bad_records.append((tuple(blk), i, recorded,
                                            recomputed))
                    rd = row_multiset_digest(rb)
                    key = (blk[1], rid)
                    rowdg[key] = (rowdg.get(key, 0) + rd) \
                        & 0xFFFFFFFFFFFFFFFF
                    reduce_fold[rid] = (reduce_fold.get(rid, 0) + rd) \
                        & 0xFFFFFFFFFFFFFFFF
        mgr.unregister(sid)
        return blockdg, rowdg, reduce_fold, bad_records

    from spark_rapids_tpu.analysis.determinism import (BIT_EXACT,
                                                       ORDER_STABLE,
                                                       RANK)

    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()

    good = _builders(os.path.join(GOLDEN, "good_plans.py"))
    oracle_sites = 0
    split_skips = 0
    for name in ("plan_partial_final_aggregate",
                 "plan_colocated_join_with_exchanges",
                 "plan_exchange_fully_read"):
        roots = {}
        for leg in ("A", "B", "C"):
            root, conf_map = good[name]()
            _prep_scans(root, batch_rows=5,
                        extra_parts=1 if leg == "C" else 0)
            if leg == "C":
                _prep_scans(root, batch_rows=7)
            if leg == "B":
                _permute_scans(root)
            roots[leg] = (root, conf_map)
        res = dsan.classify_plan(roots["A"][0],
                                 RapidsConf(dict(roots["A"][1])))
        exchanges = {leg: [n for n in _walk(roots[leg][0])
                           if isinstance(n, ShuffleExchangeExec)]
                     for leg in roots}
        for i, exa in enumerate(exchanges["A"]):
            oracle_sites += 1
            child = exa.children[0]
            claim = res.effective(child)
            scoped = res.is_partition_scoped(child)
            if RANK[claim] < RANK[ORDER_STABLE]:
                failures += 1
                print(f"DSAN: {name} exchange[{i}] subtree claims "
                      f"{claim} ({res.reason(child)}) — golden plans "
                      f"must replay order_stable or better")
                continue
            A = _run_exchange(exa, roots["A"][1])
            B = _run_exchange(exchanges["B"][i], roots["B"][1])
            C = _run_exchange(exchanges["C"][i], roots["C"][1])
            for leg, r in (("A", A), ("B", B), ("C", C)):
                for blk, idx, rec, comp in r[3]:
                    failures += 1
                    print(f"DSAN: {name} exchange[{i}] leg {leg}: "
                          f"recorded digest {rec:#018x} != recomputed "
                          f"{comp:#018x} for block {blk}[{idx}] — "
                          f"write-time recording drifted")
            if claim == BIT_EXACT and A[0] != B[0]:
                failures += 1
                print(f"DSAN: {name} exchange[{i}]: subtree claims "
                      f"bit_exact but permuted arrival changed the "
                      f"per-(map,reduce) block-digest multisets")
            if A[1] != B[1]:
                failures += 1
                print(f"DSAN: {name} exchange[{i}]: subtree claims "
                      f"{claim} but permuted arrival changed the "
                      f"per-(map,reduce) row-multiset digests — "
                      f"recomputed blocks would not match the lost "
                      f"ones")
            if scoped:
                split_skips += 1
                print(f"DSAN: note: {name} exchange[{i}] changed-split "
                      f"leg skipped — the subtree is partition-scoped "
                      f"(partial buffers regroup with the input "
                      f"split); arrival-permutation still enforced")
            elif A[2] != C[2]:
                failures += 1
                print(f"DSAN: {name} exchange[{i}]: a changed input "
                      f"split altered the per-reduce row multisets — "
                      f"hash routing must be content-determined")

    # -- leg 4a: dynamic anti-vacuity — arrival-order float sum -------------
    ex_fwd, _ = _inject_plan(stable=False)
    ex_rev, scan_rev = _inject_plan(stable=False)
    agg_rev = ex_rev.children[0]
    agg_rev.children[0] = _Permuted(scan_rev)
    F = _run_exchange(ex_fwd, {})
    R = _run_exchange(ex_rev, {})
    if F[1] == R[1]:
        failures += 1
        print("DSAN: the stable_merge=off float sum digested "
              "IDENTICALLY under reversed arrival — the dynamic "
              "oracle cannot see arrival-order nondeterminism "
              "(vacuous)")

    # -- leg 4b: dynamic anti-vacuity — PYTHONHASHSEED set routing ----------
    got = {d.code for d in dsan.module_diagnostics(
        _DSAN_HASHSEED_SRC, "spark_rapids_tpu/shuffle/injected.py",
        rules=("TPU-R015",))}
    if "TPU-R015" not in got:
        failures += 1
        print("DSAN: the set-iteration router source did not trip "
              "TPU-R015 statically")
    runs = []
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed,
                   JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        p = subprocess.run([sys.executable, "-c", _DSAN_HASHSEED_SRC],
                           capture_output=True, text=True, env=env,
                           cwd=REPO, timeout=300)
        if p.returncode != 0:
            failures += 1
            print(f"DSAN: hashseed probe (seed {seed}) failed: "
                  f"{p.stderr.strip()[-400:]}")
            runs.append(None)
        else:
            runs.append(json.loads(p.stdout.strip().splitlines()[-1]))
    if None not in runs and runs[0] == runs[1]:
        failures += 1
        print("DSAN: set-iteration routing digested IDENTICALLY under "
              "two PYTHONHASHSEEDs — the digest oracle cannot see "
              "hash-order nondeterminism (vacuous)")

    if failures:
        print(f"dsan gate: {failures} failure(s)")
        return 1
    print(f"dsan gate clean (repo determinism pass finding-free with "
          f"zero frozen debt; R015/R016/L017/L016 fixtures all trip "
          f"with the canonical-merge twin clean; {oracle_sites} golden "
          f"exchange sites digest-identical under permuted arrival "
          f"and changed split ({split_skips} partition-scoped "
          f"split-leg skip(s)); both planted nondeterminism "
          f"injections visible to the dynamic oracle)")
    return 0


def run_hlo_gate() -> int:
    """tpuxsan gate: the golden corpus replays with StableHLO +
    cost_analysis() persistence on; every persisted program artifact
    must resolve (deduped), the analytic cost model must agree with
    XLA's bytes-accessed on >= 90% of compiled programs, the padding
    books must reconcile three ways (span padWasteBytes vs live-row
    recomputation vs the counter), the L018/L019/L020/R017 fixtures
    must trip with clean twins passing, an injected pathological
    bucket (1M capacity over 10 live rows) must produce both the L018
    finding and the counter delta, and `tools kernel-report` must rank
    the grouped-aggregate and hash-join programs with nonzero
    projected savings."""
    import io
    import shutil
    import tempfile

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu import types as t
    from spark_rapids_tpu.analysis import hloaudit, hlocost
    from spark_rapids_tpu.analysis.plan_lint import (downgrade_hazards,
                                                     lint_plan)
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.columnar.device import (DeviceBatch,
                                                  DeviceColumn)
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec import base as eb
    from spark_rapids_tpu.memory.spill import batch_device_bytes
    from spark_rapids_tpu.obs.compileprof import (HLO_SUBDIR, HLO_SUFFIX,
                                                  CompileObservatory)
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.obs.tracer import QueryTrace
    from spark_rapids_tpu.tools.compile_report import load_ledger
    from spark_rapids_tpu.tools.eventlog import parse_event_log
    from spark_rapids_tpu.tools.kernel_report import (
        aggregate_kernel_report, load_estimator_ledger,
        run_kernel_report)

    failures = 0
    tmp = tempfile.mkdtemp(prefix="hlo_gate_")
    reg = MetricsRegistry.reset_for_tests()
    CompileObservatory.reset_for_tests()
    eb.clear_jit_cache()
    try:
        evt = os.path.join(tmp, "evt")
        os.makedirs(evt)
        hist = os.path.join(tmp, "hist")
        s = (TpuSession.builder()
             .config("spark.rapids.sql.enabled", True)
             .config("spark.rapids.tpu.singleChipFuse", "off")
             .config("spark.rapids.tpu.eventLog.dir", evt)
             .config("spark.rapids.tpu.compile.ledgerDir", hist)
             .get_or_create())
        rng = np.random.default_rng(20818)
        fact = pa.table({
            "k": pa.array((rng.integers(0, 97, 4000)).astype(np.int64)),
            "v": pa.array(rng.integers(-1000, 1000, 4000)
                          .astype(np.int64))})
        dim = pa.table({
            "k": pa.array(np.arange(97, dtype=np.int64)),
            "w": pa.array(np.arange(97, dtype=np.int64) * 3)})
        fdf = s.create_dataframe(fact, num_partitions=2)
        ddf = s.create_dataframe(dim)

        o1 = (fdf.filter(col("v") > -500).group_by(col("k"))
              .agg(F.sum(col("v")).alias("sv"),
                   F.count("*").alias("c")).collect())
        o2 = (fdf.join(ddf, on="k", how="inner").group_by(col("k"))
              .agg(F.sum(col("w")).alias("sw")).collect())
        o3 = fdf.sort(col("k"), col("v")).collect()
        o4 = (fdf.filter(col("v") > 0)
              .select(col("k"), (col("v") + col("v")).alias("v2"))
              .collect())
        if (o1.num_rows, o2.num_rows, o3.num_rows) != (97, 97, 4000) \
                or o4.num_rows == 0:
            failures += 1
            print("HLO: corpus produced wrong row counts")

        # [persist] every build's hlo_hash must resolve to exactly one
        # deduped artifact on disk; a corpus that persists nothing is
        # vacuous
        ledger_path = os.path.join(hist, "compile_ledger.jsonl")
        records = load_ledger(ledger_path)
        builds = [r for r in records if r.get("event") == "build"]
        hashes = {r["hlo_hash"] for r in builds if r.get("hlo_hash")}
        unhashed = [r for r in builds if not r.get("hlo_hash")]
        if not builds or not hashes:
            failures += 1
            print(f"HLO: vacuous — {len(builds)} build(s), "
                  f"{len(hashes)} persisted program(s)")
        if unhashed:
            failures += 1
            print(f"HLO: {len(unhashed)} build(s) carry no hlo_hash "
                  f"({sorted({r.get('exec') for r in unhashed})})")
        hlo_dir = os.path.join(hist, HLO_SUBDIR)
        on_disk = set()
        if os.path.isdir(hlo_dir):
            on_disk = {f[:-len(HLO_SUFFIX)] for f in os.listdir(hlo_dir)
                       if f.endswith(HLO_SUFFIX)}
        if on_disk != hashes:
            failures += 1
            print(f"HLO: artifact store out of step with the ledger — "
                  f"{len(hashes)} hash(es) vs {len(on_disk)} file(s); "
                  f"missing {sorted(hashes - on_disk)[:4]}, orphaned "
                  f"{sorted(on_disk - hashes)[:4]}")

        # [cost model] the analytic model must track XLA's own books —
        # drift means the report's gap column is fiction
        cm = hlocost.validate_model(builds, tolerance=8.0)
        if cm["checked"] == 0:
            failures += 1
            print("HLO: cost-model check vacuous — no build carried "
                  "cost_analysis() bytes")
        elif cm["agreement_pct"] < 90.0:
            failures += 1
            print(f"HLO: cost model agrees on only "
                  f"{cm['agreement_pct']:.0f}% of {cm['checked']} "
                  f"program(s) (< 90%); worst {cm['worst']}")

        # [pad books] three-way reconciliation: each span's persisted
        # padWasteBytes must equal the live-row recomputation, and the
        # counter must equal the span sum (checked BEFORE the synthetic
        # injection below adds counter-only traffic)
        logs = [f for f in os.listdir(evt) if f.startswith("events_")]
        op_spans = []
        if logs:
            app = parse_event_log(os.path.join(evt, logs[0]))
            op_spans = [sp for sp in app.spans
                        if "padWasteBytes" in sp]
        if not op_spans:
            failures += 1
            print("HLO: pad reconciliation vacuous — no operator span "
                  "carries padWasteBytes")
        span_total = 0
        for sp in op_spans:
            cap = int(sp.get("capRows") or 0)
            byt = int(sp.get("bytes") or 0)
            want = 0
            if cap > 0 and byt > 0:
                live = min(max(int(sp.get("rows") or 0), 0), cap)
                want = int(byt * (cap - live) / cap)
            got = int(sp["padWasteBytes"])
            if got != want:
                failures += 1
                print(f"HLO: span {sp.get('name')} books {got} pad "
                      f"bytes; rows/capacity recompute to {want}")
            span_total += got
        pad_fam = reg.counter("tpu_pad_waste_bytes_total",
                              labelnames=("exec",))
        metric_total = int(sum(ch.value for _, ch in pad_fam.series()))
        if metric_total != span_total:
            failures += 1
            print(f"HLO: tpu_pad_waste_bytes_total {metric_total} != "
                  f"event-log span sum {span_total}")

        # [kernel report] the headline artifact must rank the Pallas
        # candidates with nonzero projected savings, and the CLI must
        # render it
        agg = aggregate_kernel_report(records,
                                      load_estimator_ledger(hist))
        sav = {t_["target"]: t_["projected_savings_s"]
               for t_ in agg["targets"]}
        for want_target in ("fused grouped aggregate (sort+segment-"
                            "reduce)", "fused hash build/probe"):
            if sav.get(want_target, 0.0) <= 0.0:
                failures += 1
                print(f"HLO: kernel report projects no savings for "
                      f"{want_target!r} (targets {sav})")
        buf = io.StringIO()
        rc = run_kernel_report(ledger_path, hist, out=buf)
        if rc != 0 or "kernel gap report" not in buf.getvalue():
            failures += 1
            print(f"HLO: kernel-report CLI failed (rc {rc})")

        # [fixtures] bad twins trip, clean twins pass
        bad = _builders(os.path.join(GOLDEN, "bad_plans.py"))
        root18, cmap18 = bad["plan_L018_pad_waste"]()
        d18 = lint_plan(root18, RapidsConf(cmap18), infer=True)
        if "TPU-L018" not in {d.code for d in d18}:
            failures += 1
            print("HLO: the pathological-bucket plan did not trip "
                  "TPU-L018")
        root18c, _ = bad["plan_L018_pad_waste"]()
        clean = {d.code for d in lint_plan(root18c, RapidsConf({}),
                                           infer=True)}
        if {"TPU-L018", "TPU-L020"} & clean:
            failures += 1
            print(f"HLO: clean twin (default buckets) tripped "
                  f"{sorted(clean)}")
        root20, cmap20 = bad["plan_L020_fusion_break"]()
        if "TPU-L020" not in {d.code for d in lint_plan(
                root20, RapidsConf(cmap20), infer=True)}:
            failures += 1
            print("HLO: the project->filter pipeline did not trip "
                  "TPU-L020")
        root20x, _ = bad["plan_L020_fusion_break"]()
        off = {d.code for d in lint_plan(
            root20x, RapidsConf({"spark.rapids.tpu.xsan.enabled":
                                 False}), infer=True)}
        if {"TPU-L018", "TPU-L020"} & off:
            failures += 1
            print(f"HLO: xsan.enabled=false still emitted "
                  f"{sorted(off)}")

        # L018 repair: with a genuinely smaller bucket on the menu the
        # pre-flight must arm the speculative re-bucket and keep the
        # filter on device; with none it must refuse
        ns = __import__("runpy").run_path(
            os.path.join(GOLDEN, "bad_plans.py"))
        from spark_rapids_tpu.exec.basic import FilterExec
        from spark_rapids_tpu.expr.core import (AttributeReference,
                                                Literal)
        from spark_rapids_tpu.expr.predicates import GreaterThan
        scan = ns["_scan"](ns["_ints"](n=1200))
        flt = FilterExec(GreaterThan(AttributeReference("v"),
                                     Literal(600, t.LONG)), scan)
        flt.placement = eb.TPU
        rconf = RapidsConf({"spark.rapids.tpu.batchCapacityBuckets":
                            "1024,1048576"})
        rd = lint_plan(flt, rconf, infer=True)
        downgrade_hazards(flt, rd, rconf)
        if flt.rebucket_cap != 1024 or flt.placement != eb.TPU:
            failures += 1
            print(f"HLO: L018 repair did not arm (rebucket_cap="
                  f"{flt.rebucket_cap}, placement={flt.placement})")
        if getattr(root18, "rebucket_cap", None) is not None:
            failures += 1
            print("HLO: L018 repair armed with no smaller bucket "
                  "available (a no-op shrink)")

        # L019: a planted host callback inside a persisted program
        # trips; the pure twin is clean
        hdir = os.path.join(tmp, "hlo_fixtures")
        os.makedirs(hdir)
        bad_hlo = ('func.func @main(%arg0: tensor<4xi64>) {\n'
                   '  %0 = "stablehlo.custom_call"(%arg0) '
                   '{call_target_name = "xla_python_cpu_callback"} : '
                   '(tensor<4xi64>) -> tensor<4xi64>\n  return\n}\n')
        ok_hlo = ('func.func @main(%arg0: tensor<4xi64>) {\n'
                  '  %0 = stablehlo.add %arg0, %arg0 : tensor<4xi64>\n'
                  '  return\n}\n')
        for h, text in (("deadbeef00000001", bad_hlo),
                        ("deadbeef00000002", ok_hlo)):
            with open(os.path.join(hdir, h + HLO_SUFFIX), "w") as f:
                f.write(text)
        recs = [{"event": "build", "exec": "ProbeExec",
                 "hlo_hash": "deadbeef00000001"},
                {"event": "build", "exec": "CleanExec",
                 "hlo_hash": "deadbeef00000002"}]
        l19 = hloaudit.audit_ledger(recs, hdir, 16 << 20)
        codes19 = [d.code for d in l19]
        if codes19 != ["TPU-L019"]:
            failures += 1
            print(f"HLO: planted host callback produced {codes19} "
                  f"(expected exactly one TPU-L019, clean twin silent)")

        # R017: a raw jnp call in exec/ trips; the xp-parameterized and
        # allow-annotated twins are clean
        r_bad = "import jax.numpy as jnp\n\ndef widen(c):\n" \
                "    return jnp.cumsum(c)\n"
        r_ok = "def widen(c, xp):\n    return xp.cumsum(c)\n"
        r_allow = ("import jax.numpy as jnp\n\ndef widen(c):\n"
                   "    return jnp.cumsum(c)  "
                   "# tpulint: allow[TPU-R017] gate fixture\n")
        if [d.code for d in hloaudit.module_diagnostics(
                r_bad, "exec/fake.py")] != ["TPU-R017"]:
            failures += 1
            print("HLO: raw jnp call in exec/ did not trip TPU-R017")
        for src, rel, why in ((r_ok, "exec/fake.py", "xp twin"),
                              (r_allow, "exec/fake.py", "allow twin"),
                              (r_bad, "obs/fake.py", "non-kernel path")):
            got = [d.code for d in hloaudit.module_diagnostics(src, rel)]
            if got:
                failures += 1
                print(f"HLO: R017 {why} flagged {got}")
        # burned-in baseline: the live tree owes zero R017 findings
        live = [d for d in hloaudit.repo_diagnostics(
            os.path.join(REPO, "spark_rapids_tpu"))
            if d.code == "TPU-R017"]
        if live:
            failures += 1
            print(f"HLO: {len(live)} unregistered raw jnp/lax site(s) "
                  f"in the live tree: {[d.loc for d in live[:4]]}")

        # [injection] a 1M-capacity launch carrying 10 live rows must
        # move the counter by exactly bytes*(cap-live)/cap
        cap = 1 << 20
        import jax.numpy as jnp
        pathological = DeviceBatch(
            [DeviceColumn(t.LONG, data=jnp.zeros(cap, jnp.int64))],
            10, ["v"])
        expect = int(batch_device_bytes(pathological)
                     * (cap - 10) / cap)
        before = int(sum(ch.value for _, ch in pad_fam.series()))
        qt = QueryTrace()

        class InjectedBucketExec:
            pass

        for _ in qt.trace_operator(InjectedBucketExec(), 0,
                                   iter([pathological])):
            pass
        qt.finalize()
        after = int(sum(ch.value for _, ch in pad_fam.series()))
        if after - before != expect or expect <= 0:
            failures += 1
            print(f"HLO: pathological bucket moved the counter by "
                  f"{after - before} (expected {expect})")

        n_prog = len(hashes)
        pct = cm["agreement_pct"] or 0.0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        eb.clear_jit_cache()
    if failures:
        print(f"hlo gate: {failures} failure(s)")
        return 1
    print(f"hlo gate clean ({n_prog} persisted program(s) resolve "
          f"deduped; cost model agrees on {pct:.0f}% of programs; pad "
          f"books reconcile span/recompute/counter; kernel report "
          f"ranks the grouped-aggregate and hash-join fusions with "
          f"nonzero savings; L018/L019/L020/R017 fixtures trip with "
          f"clean twins silent; repair arms only when a smaller "
          f"bucket exists; injected 1M-capacity launch booked the "
          f"exact padding delta)")
    return 0


def run_slo_gate() -> int:
    """Latency-observatory gate (obs/critpath.py + obs/slo.py), two
    phases through one 4-session pool:

    * **Golden mix** — the serve gate's four queries replayed
      concurrently with tracing on: every completed query's
      critical-path segments must sum to its wall time within the
      tolerance gate, the three sinks must agree (root-span annotation,
      tpu_latency_segment_seconds_total counters, latency ledger), and
      the burn-rate health rule must NOT trip (anti-vacuity one way).
    * **Injected whale** — tenant pool-0's FilterExec is armed with a
      sleep and its admission ticket inflated so victims (pool-1..3)
      queue behind it deterministically: the sustained-burn health rule
      must flip DEGRADED naming the victims, tail-report must attribute
      each victim's p99 >= 50% to queue_wait while its p50 mix stays
      compute-dominated, and the whale itself must stay
      compute-attributed (anti-vacuity the other way).  Plus the
      observatory's own overhead must stay under 5% of query wall —
      the same accounting `bench.py --serve` reports.
    """
    import concurrent.futures as cf
    import shutil
    import tempfile
    import time as _time

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.testing.faults import (arm_filter, disarm_filter,
                                                 raw_filter_iterator)
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs.critpath import SEGMENT_FAMILY
    from spark_rapids_tpu.obs.health import DEGRADED, OK, HealthMonitor
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.obs.slo import LatencyObservatory
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    failures = 0
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()
    LatencyObservatory.reset_for_tests()

    n = 4000
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(97, dtype=np.int64)),
        "w": pa.array(np.arange(97, dtype=np.int64) * 10),
    })
    budget = 256 << 20
    hist = tempfile.mkdtemp(prefix="slo_gate_hist_")
    pool = SessionPool(4, {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.singleChipFuse": "off",
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": str(budget),
        "spark.rapids.tpu.serve.admissionTimeoutMs": "60000",
        "spark.rapids.tpu.regress.historyDir": hist,
        # generous golden-phase target: the golden mix must never burn
        # on a loaded CI host (the whale phase reconfigures to 400ms)
        "spark.rapids.tpu.slo.targetMs": "600000",
        "spark.rapids.tpu.slo.objective": "0.9",
    })
    monitor = HealthMonitor()

    from spark_rapids_tpu.expr.window import WindowBuilder

    def mk_mix(s):
        fdf = s.create_dataframe(fact)
        fdf4 = s.create_dataframe(fact, num_partitions=4)
        ddf2 = s.create_dataframe(dim, num_partitions=2)
        w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
        return {
            "agg": lambda: (fdf.group_by(col("k"))
                            .agg(F.sum(col("v")).alias("sv"),
                                 F.count("*").alias("c")).collect()),
            "join": lambda: (fdf4.join(ddf2, on="k", how="inner")
                             .group_by(col("k"))
                             .agg(F.sum(col("w")).alias("sw"))
                             .collect()),
            "window": lambda: (fdf.select(
                col("k"), col("v"),
                F.row_number().over(w).alias("rn")).collect()),
            "sort": lambda: fdf.sort(col("k"), col("v")).collect(),
            # whale-phase query: single partition so the armed filter
            # sleeps exactly once per run
            "filter_agg": lambda: (fdf.filter(col("v") > -10_000)
                                   .group_by(col("k"))
                                   .agg(F.sum(col("v")).alias("sv"))
                                   .collect()),
        }

    mixes = {id(s): mk_mix(s) for s in pool._sessions}
    worklist = [name for name in ("agg", "join", "window", "sort")
                for _ in range(4)]

    def one(name):
        with pool.session() as s:
            mixes[id(s)][name]()

    with cf.ThreadPoolExecutor(max_workers=4) as ex:
        list(ex.map(one, worklist))
    pool.drain(timeout=60)

    def load_ledger():
        import json
        path = os.path.join(hist, "latency_ledger.jsonl")
        if not os.path.exists(path):
            return []
        with open(path, encoding="utf-8") as f:
            return [json.loads(x) for x in f if x.strip()]

    # -- golden-phase checks -------------------------------------------------
    records = load_ledger()
    completed = m.counter("tpu_queries_completed_total").value()
    if not records or len(records) != completed:
        failures += 1
        print(f"SLO: ledger sink disagrees with the query counter "
              f"({len(records)} records != {completed} completed)")
    bad_recon = [r for r in records if not r.get("reconciled")]
    for r in records:
        covered = sum(r["segments"].values())
        if abs(covered - r["wall_s"]) > max(0.05 * r["wall_s"], 0.002):
            bad_recon.append(r)
    if bad_recon:
        failures += 1
        print(f"SLO: {len(bad_recon)} record(s) failed segment-vs-wall "
              f"reconciliation (first: {bad_recon[0]})")
    ledger_seg_s = sum(sum(r["segments"].values()) for r in records)
    fam = [f for f in MetricsRegistry.get().families()
           if f.name == SEGMENT_FAMILY]
    counter_seg_s = fam[0].total() if fam else 0.0
    if not fam or abs(counter_seg_s - ledger_seg_s) > \
            max(0.01 * ledger_seg_s, 1e-3):
        failures += 1
        print(f"SLO: counter sink disagrees with span math "
              f"({counter_seg_s:.4f}s counted vs {ledger_seg_s:.4f}s "
              f"in the ledger)")
    annotated = [sp for s in pool._sessions
                 if s.last_query_trace() is not None
                 for sp in s.last_query_trace().span_dicts()
                 if sp["kind"] == "query" and
                 sp["attrs"].get("critical_path")]
    if not annotated:
        failures += 1
        print("SLO: no root span carries the critical_path annotation")
    for _ in range(2):
        snap = monitor.snapshot()
    if snap["components"]["slo"]["status"] != OK:
        failures += 1
        print(f"SLO: burn rule tripped on the clean golden mix "
              f"(vacuity): {snap['components']['slo']}")

    # -- whale phase ---------------------------------------------------------
    def run_as(s, fn):
        TpuSession.bind_to_thread(s)
        try:
            return fn()
        finally:
            TpuSession.bind_to_thread(None)

    # warm the filter_agg jit before arming anything so the whale's
    # tail is sleep, not first-compile
    for s in pool._sessions:
        run_as(s, mixes[id(s)]["filter_agg"])

    # the whale phase writes its own ledger: the CLI report below must
    # describe the incident, not the golden phase's first-compile tails
    hist_whale = tempfile.mkdtemp(prefix="slo_gate_whale_")
    LatencyObservatory.reset_for_tests()
    LatencyObservatory.get().configure(
        target_ms=400, objective=0.9,
        ledger_path=os.path.join(hist_whale, "latency_ledger.jsonl"))

    whale_sleep, victim_sleep = 0.6, 0.05
    orig_bound = TpuSession._static_peak_bound

    def sleepy_ep(self, pid, ctx, *consumer):
        s = TpuSession.active()
        tenant = getattr(s, "_tenant", "") if s is not None else ""
        slp = whale_sleep if tenant == "pool-0" else victim_sleep
        for b in raw_filter_iterator(self, pid, ctx, *consumer):
            if slp:
                _time.sleep(slp)  # inside the operator span: compute
                slp = 0.0
            yield b

    def fixed_bound(self, final_plan, conf, budget=None):
        # whale + any victim oversubscribes the 256M budget, two
        # victims co-run: victims queue IFF the whale is in flight
        return (200 << 20) if getattr(self, "_tenant", "") == "pool-0" \
            else (100 << 20)

    armed = arm_filter(sleepy_ep)
    TpuSession._static_peak_bound = fixed_bound
    try:
        whale, victims = pool._sessions[0], pool._sessions[1:]
        # uncontended victim baselines: GOOD and compute-dominated
        for _ in range(6):
            for s in victims:
                run_as(s, mixes[id(s)]["filter_agg"])
        for _ in range(2):
            snap = monitor.snapshot()
        if snap["components"]["slo"]["status"] != OK:
            failures += 1
            print(f"SLO: burn rule tripped on uncontended victims "
                  f"(vacuity): {snap['components']['slo']}")
        # whale rounds: pool-0 admits first and holds 200M through its
        # armed 0.6s filter; victims arrive 0.15s later and queue
        for _ in range(4):
            with cf.ThreadPoolExecutor(max_workers=4) as ex:
                futs = [ex.submit(run_as, whale,
                                  mixes[id(whale)]["filter_agg"])]
                _time.sleep(0.15)
                futs += [ex.submit(run_as, s,
                                   mixes[id(s)]["filter_agg"])
                         for s in victims]
                for f in futs:
                    f.result()
    finally:
        disarm_filter(armed)
        TpuSession._static_peak_bound = orig_bound

    # -- whale-phase checks --------------------------------------------------
    rep = LatencyObservatory.get().slo_report()
    tail = LatencyObservatory.get().tail_report()
    victim_names = [f"pool-{i}" for i in (1, 2, 3)]
    for name in victim_names:
        row = rep["tenants"].get(name, {})
        if row.get("burn_rate", 0.0) <= 1.0:
            failures += 1
            print(f"SLO: victim {name} burn rate "
                  f"{row.get('burn_rate')} did not exceed 1 under the "
                  f"whale")
        agg = tail["tenants"].get(name, {})
        if agg.get("dominant_tail_segment") != "queue_wait" or \
                agg.get("p99_mix", {}).get("queue_wait", 0.0) < 0.5:
            failures += 1
            print(f"SLO: victim {name} p99 not attributed >= 50% to "
                  f"queue_wait: {agg.get('p99_mix')}")
        if agg.get("p50_mix", {}).get("queue_wait", 0.0) >= 0.5:
            failures += 1
            print(f"SLO: victim {name} p50 mix is queue-dominated — "
                  f"the baseline should be compute-bound: "
                  f"{agg.get('p50_mix')}")
    whale_dom = tail["tenants"].get("pool-0", {}).get(
        "dominant_tail_segment") or ""
    if not whale_dom.startswith("compute:"):
        failures += 1
        print(f"SLO: the whale's own tail should be compute-bound, "
              f"got {whale_dom!r}")
    for _ in range(2):
        snap = monitor.snapshot()
    slo_comp = snap["components"]["slo"]
    burning = slo_comp.get("signals", {}).get("burning_tenants", [])
    if slo_comp["status"] != DEGRADED or \
            not set(victim_names) <= set(burning):
        failures += 1
        print(f"SLO: sustained burn did not degrade /healthz naming "
              f"the victims: {slo_comp}")
    # admission.wait span: queue time must be a real span under the
    # root, carrying its ticket bytes and queue depth at enqueue
    waits = [sp for s in pool._sessions
             if s.last_query_trace() is not None
             for sp in s.last_query_trace().span_dicts()
             if sp["name"] == "admission.wait"]
    if not waits or not any("queue_depth_at_enqueue" in sp["attrs"]
                            for sp in waits):
        failures += 1
        print("SLO: no admission.wait span with queue depth recorded")
    overhead = LatencyObservatory.get().overhead()
    if overhead["pct"] >= 5.0:
        failures += 1
        print(f"SLO: critical-path extraction overhead "
              f"{overhead['pct']:.2f}% of query wall (>= 5%)")
    # tail-report CLI over the same ledger: the culprit line must name
    # queue_wait for a victim tenant
    import contextlib
    import io
    from spark_rapids_tpu.tools.tail_report import run_tail_report
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run_tail_report(hist_whale)
    cli_out = buf.getvalue()
    if rc != 0 or not any(f"tenant {v}'s p99 is" in cli_out and
                          "queue_wait" in cli_out
                          for v in victim_names):
        failures += 1
        print(f"SLO: tools tail-report did not name queue_wait as a "
              f"victim's dominant tail segment:\n{cli_out}")

    pool.drain(timeout=60)
    pool.close()
    shutil.rmtree(hist, ignore_errors=True)
    shutil.rmtree(hist_whale, ignore_errors=True)
    MetricsRegistry.reset_for_tests()
    AdmissionController.reset_for_tests()
    LatencyObservatory.reset_for_tests()
    if failures:
        print(f"slo gate: {failures} failure(s)")
        return 1
    print(f"slo gate clean ({len(records)} golden queries reconciled "
          f"segments to wall with span/counter/ledger sinks agreeing; "
          f"injected whale flipped the burn-rate health rule naming "
          f"{burning}; victims' p99 >= 50% queue_wait with "
          f"compute-dominated p50; extraction overhead "
          f"{overhead['pct']:.2f}% < 5%)")
    return 0


def run_progress_gate() -> int:
    """Progress-observatory gate (obs/progress.py), one 4-session pool:

    * **Golden mix** — the serve mix replayed concurrently with tracing
      on: every finished query's live-view record must show ratio 1.0
      with partitions_done reconciling exactly to the trace's operator
      span count, a probed query must show monotone mid-flight ratios
      that actually move, the watchdog must stay quiet (anti-vacuity),
      and tracker hook overhead must stay < 5% of query wall with the
      on/off check proving the hooks are really the thing measured.
    * **Injected stall** — an armed FilterExec sleeps past
      ``watchdog.stallSeconds``: the scan must flag the query naming
      the deepest open operator, degrade /healthz, black-box exactly
      one stall record, then auto-cancel with cause=watchdog.
    * **Cancel legs** — cancels injected during compute (session
      API), queue-wait (pool API, ticket removed from the admission
      FIFO while the whale still holds budget), and remote-fetch
      (fetcher poll loop), plus a blown ``deadline_ms``: each must
      propagate the exact typed error, balance the books (no orphaned
      shuffle blocks, no stranded admission bytes, no open spans, no
      spill leaks) and produce exactly one classified bundle.
    """
    import concurrent.futures as cf
    import shutil
    import tempfile
    import threading
    import time as _time

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.testing.faults import (arm_filter, disarm_filter,
                                                 raw_filter_iterator)
    from spark_rapids_tpu.obs import bgerrors
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs import postmortem as pm
    from spark_rapids_tpu.obs import progress as prog
    from spark_rapids_tpu.obs.health import DEGRADED, OK, HealthMonitor
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    from spark_rapids_tpu.obs.progress import (ProgressTracker,
                                               TpuQueryCancelled,
                                               TpuQueryDeadlineExceeded)
    from spark_rapids_tpu.obs.slo import LatencyObservatory
    from spark_rapids_tpu.shuffle import transport as tr
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    from spark_rapids_tpu.tools.top import format_top

    failures = 0
    MetricsRegistry.reset_for_tests()
    with SpillCatalog._lock:
        SpillCatalog._instance = SpillCatalog()
    TpuShuffleManager.reset()
    AdmissionController.reset_for_tests()
    LatencyObservatory.reset_for_tests()
    ProgressTracker.reset_for_tests()
    bgerrors.reset()

    pmdir = tempfile.mkdtemp(prefix="progress_gate_pm_")

    n = 4000
    rng = np.random.default_rng(11)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 97, n).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(97, dtype=np.int64)),
        "w": pa.array(np.arange(97, dtype=np.int64) * 10),
    })
    budget = 256 << 20
    pool = SessionPool(4, {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.singleChipFuse": "off",
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": str(budget),
        "spark.rapids.tpu.serve.admissionTimeoutMs": "60000",
        "spark.rapids.tpu.hbm.postmortem.dir": pmdir,
        "spark.rapids.tpu.hbm.postmortem.maxBundles": "500",
    })
    monitor = HealthMonitor()

    def mk_mix(s):
        fdf = s.create_dataframe(fact)
        fdf4 = s.create_dataframe(fact, num_partitions=4)
        ddf2 = s.create_dataframe(dim, num_partitions=2)
        return {
            "agg": lambda: (fdf4.group_by(col("k"))
                            .agg(F.sum(col("v")).alias("sv"),
                                 F.count("*").alias("c")).collect()),
            "join": lambda: (fdf4.join(ddf2, on="k", how="inner")
                             .group_by(col("k"))
                             .agg(F.sum(col("w")).alias("sw"))
                             .collect()),
            "sort": lambda: fdf.sort(col("k"), col("v")).collect(),
            # armed-leg query: every cancel/stall leg drives this shape
            # so the armed FilterExec sits mid-plan with 4 partitions
            "filter4": lambda: (fdf4.filter(col("v") > -10_000)
                                .group_by(col("k"))
                                .agg(F.sum(col("v")).alias("sv"))
                                .collect()),
            # exchange-free: with a warmed plan the group-by exchange's
            # map stage (and an armed FilterExec inside it) can run
            # during PLANNING, before admission — the queue-cancel
            # whale must park post-admission, so it parks here
            "filter_only": lambda: (fdf4.filter(col("v") > -10_000)
                                    .collect()),
        }

    mixes = {id(s): mk_mix(s) for s in pool._sessions}
    worklist = [name for name in ("agg", "join", "sort", "filter4")
                for _ in range(4)]

    def one(name):
        with pool.session() as s:
            mixes[id(s)][name]()

    def run_as(s, fn):
        TpuSession.bind_to_thread(s)
        try:
            return fn()
        finally:
            TpuSession.bind_to_thread(None)

    def books(session=None):
        probs = []
        blocks = TpuShuffleManager.get().catalog.num_blocks()
        if blocks:
            probs.append(f"{blocks} orphaned shuffle block(s)")
        sleaks = SpillCatalog.get().leak_report()
        if sleaks:
            probs.append(f"{len(sleaks)} spill leak(s)")
        ac = AdmissionController.get()
        if ac is not None:
            if ac.bytes_in_flight:
                probs.append(f"{ac.bytes_in_flight} admission "
                             f"byte(s) still in flight")
            if ac.queue_depth:
                probs.append(f"admission queue depth "
                             f"{ac.queue_depth}")
        if session is not None:
            trace = session.last_query_trace()
            if trace is not None and trace.open_span_count():
                probs.append(f"{trace.open_span_count()} unclosed "
                             f"span(s)")
        return probs

    def expect_bundle(before, err_name, kind, extra_kinds=()):
        docs = []
        for b in pm.list_bundles(pmdir):
            if b in before:
                continue
            try:
                docs.append(pm.load_bundle(b))
            except Exception as ex:
                return [f"bundle unparseable: {ex!r}"]
        main = [d for d in docs if d.get("kind") == kind]
        rest = sorted(d.get("kind") or "?" for d in docs
                      if d.get("kind") != kind)
        probs = []
        if len(main) != 1:
            return [f"expected exactly 1 {kind} bundle, found "
                    f"{len(main)} (all new kinds: "
                    f"{[d.get('kind') for d in docs]})"]
        if rest != sorted(extra_kinds):
            probs.append(f"unexpected extra bundle kind(s): {rest} "
                         f"(expected {sorted(extra_kinds)})")
        doc = main[0]
        if (doc.get("error") or {}).get("type") != err_name:
            probs.append(f"bundle names "
                         f"{(doc.get('error') or {}).get('type')!r}, "
                         f"expected {err_name}")
        if "cancellation" not in doc:
            probs.append("bundle lost the cancellation section")
        rendered = pm.render_postmortem(doc)
        if "cancel:" not in rendered or "observed at" not in rendered:
            probs.append("rendered post-mortem does not show the "
                         "cancel cause/checkpoint")
        return probs

    def cancel_count(cause):
        fam = m.counter("tpu_cancellations_total",
                        labelnames=("cause",))
        return sum(ch.value for lbl, ch in fam.series()
                   if lbl.get("cause") == cause)

    # -- golden mix ----------------------------------------------------------
    with cf.ThreadPoolExecutor(max_workers=4) as ex:
        list(ex.map(one, worklist))
    pool.drain(timeout=60)

    view = ProgressTracker.get().live_view()
    if view["inflight"]:
        failures += 1
        print(f"PROGRESS: {len(view['inflight'])} quer(ies) still "
              f"in flight after drain")
    if view["stalled"]:
        failures += 1
        print(f"PROGRESS: watchdog flagged the healthy golden mix "
              f"(vacuity): {view['stalled']}")
    recent = view["recent"]
    done = [r for r in recent if r["error"] is None]
    if len(done) < len(worklist):
        failures += 1
        print(f"PROGRESS: finished ring holds {len(done)} clean "
              f"records, ran {len(worklist)}")
    for r in done:
        if r["progress_ratio"] != 1.0 or r["rows"] <= 0 or \
                r["partitions_done"] <= 0:
            failures += 1
            print(f"PROGRESS: finished record not fully accounted: "
                  f"{r['tenant']}/{r['query']} "
                  f"ratio={r['progress_ratio']} rows={r['rows']} "
                  f"partitions={r['partitions_done']}")
    if not any(r.get("predicted_rows") for r in done):
        failures += 1
        print("PROGRESS: no finished record carries estimator-ledger "
              "row predictions")
    # live-view partition accounting must reconcile exactly to the
    # trace: one closed operator span per observed execute_partition
    for s in pool._sessions:
        trace = s.last_query_trace()
        mine = [r for r in recent if r["tenant"] == s._tenant]
        if trace is None or not mine:
            failures += 1
            print(f"PROGRESS: {s._tenant} left no trace/record to "
                  f"reconcile")
            continue
        spans = [sp for sp in trace.span_dicts()
                 if sp["kind"] == "operator"]
        if mine[-1]["partitions_done"] != len(spans):
            failures += 1
            print(f"PROGRESS: {s._tenant} live view counted "
                  f"{mine[-1]['partitions_done']} partition(s), the "
                  f"trace closed {len(spans)} operator span(s)")
    snap = monitor.snapshot()
    if snap["components"].get("progress", {}).get("status") != OK:
        failures += 1
        print(f"PROGRESS: /healthz progress component not OK on the "
              f"golden mix: {snap['components'].get('progress')}")
    top_out = format_top(view)
    if "in flight" not in top_out or "recent:" not in top_out:
        failures += 1
        print(f"PROGRESS: tools top render missing sections:\n"
              f"{top_out}")
    inflight_fam = [f for f in MetricsRegistry.get().families()
                    if f.name == "tpu_queries_inflight"]
    if not inflight_fam or inflight_fam[0].total() != 0:
        failures += 1
        print(f"PROGRESS: tpu_queries_inflight gauges did not return "
              f"to zero: "
              f"{inflight_fam[0].total() if inflight_fam else 'absent'}")
    if not any(f.name == "tpu_query_progress_ratio"
               for f in MetricsRegistry.get().families()):
        failures += 1
        print("PROGRESS: tpu_query_progress_ratio family never "
              "published")

    # -- probed monotone mid-flight ratios -----------------------------------
    probe = []

    def probing_ep(self, pid, ctx, *consumer):
        h = prog.current_handle()
        for b in raw_filter_iterator(self, pid, ctx, *consumer):
            if h is not None:
                probe.append(h.progress_ratio())
            yield b

    armed = arm_filter(probing_ep)
    try:
        s0 = pool._sessions[0]
        run_as(s0, mixes[id(s0)]["filter4"])
    finally:
        disarm_filter(armed)
    if len(probe) < 4:
        failures += 1
        print(f"PROGRESS: probe saw only {len(probe)} mid-flight "
              f"ratio sample(s)")
    if probe != sorted(probe):
        failures += 1
        print(f"PROGRESS: mid-flight ratios not monotone: {probe}")
    if probe and (min(probe) == max(probe) or max(probe) > 1.0):
        failures += 1
        print(f"PROGRESS: mid-flight ratios never moved (or "
              f"overshot 1.0): {probe}")

    # -- hook overhead < 5% of query wall ------------------------------------
    view = ProgressTracker.get().live_view(scan=False)
    wall_s = sum(r["elapsed_s"] for r in view["recent"])
    oh = ProgressTracker.get().overhead()
    pct = 100.0 * oh["hook_s"] / wall_s if wall_s else 100.0
    if oh["hook_s"] <= 0.0:
        failures += 1
        print("PROGRESS: hook overhead booked zero seconds over the "
              "golden mix (vacuity — the hooks are not measuring)")
    if pct >= 5.0:
        failures += 1
        print(f"PROGRESS: tracker hook overhead {pct:.2f}% of query "
              f"wall (>= 5%)")

    # on/off anti-vacuity: disabled tracking registers nothing, books
    # no overhead, and the query's result bytes do not change
    s0 = pool._sessions[0]
    ref = run_as(s0, mixes[id(s0)]["agg"])
    ring_before = len(ProgressTracker.get().live_view(
        scan=False)["recent"])
    oh_before = ProgressTracker.get().overhead()["hook_s"]
    ProgressTracker.get().configure(enabled=False)
    try:
        off = run_as(s0, mixes[id(s0)]["agg"])
    finally:
        ProgressTracker.get().configure(enabled=True)
    ring_after = len(ProgressTracker.get().live_view(
        scan=False)["recent"])
    oh_after = ProgressTracker.get().overhead()["hook_s"]
    if ring_after != ring_before or oh_after != oh_before:
        failures += 1
        print(f"PROGRESS: disabled tracker still observed the query "
              f"(ring {ring_before}->{ring_after}, hook_s "
              f"{oh_before}->{oh_after})")
    if not ref.equals(off):
        failures += 1
        print("PROGRESS: tracking on/off changed query results")

    # -- injected stall: watchdog flags, names, black-boxes, auto-cancels ----
    ProgressTracker.get().configure(stall_seconds=0.35,
                                    auto_cancel_seconds=0.9)
    started = threading.Event()

    def stuck_ep(self, pid, ctx, *consumer):
        for b in raw_filter_iterator(self, pid, ctx, *consumer):
            if not started.is_set():
                started.set()
                _time.sleep(1.4)  # one dead-silent stall, no touch()
            yield b

    armed = arm_filter(stuck_ep)
    before = set(pm.list_bundles(pmdir))
    caught = {}

    def victim_stall():
        s1 = pool._sessions[1]
        try:
            run_as(s1, mixes[id(s1)]["filter4"])
        except BaseException as ex:  # noqa: BLE001 — verified below
            caught["stall"] = ex

    th = threading.Thread(target=victim_stall)
    th.start()
    stall_rec = None
    auto_cancelled = False
    try:
        if not started.wait(30):
            failures += 1
            print("PROGRESS: armed stall never reached the operator")
        deadline = _time.monotonic() + 15
        while not auto_cancelled and _time.monotonic() < deadline:
            _time.sleep(0.05)
            for rec in ProgressTracker.get().watchdog_scan():
                if rec["tenant"] == "pool-1":
                    stall_rec = stall_rec or rec
                    auto_cancelled = auto_cancelled or \
                        rec.get("auto_cancelled", False)
        if stall_rec is not None and not auto_cancelled:
            # the stall was seen but never aged past auto-cancel
            pass
        snap = monitor.snapshot()
    finally:
        th.join(30)
        disarm_filter(armed)
        ProgressTracker.get().configure(stall_seconds=30.0)
        ProgressTracker.get().auto_cancel_seconds = None
    op = (stall_rec or {}).get("deepest_open_operator")
    if stall_rec is None or not op or not str(op).endswith("Exec"):
        failures += 1
        print(f"PROGRESS: watchdog did not flag the stall naming the "
              f"deepest open operator: {stall_rec}")
    if snap["components"].get("progress", {}).get("status") != DEGRADED:
        failures += 1
        print(f"PROGRESS: /healthz did not degrade on the stalled "
              f"query: {snap['components'].get('progress')}")
    if m.counter("tpu_query_stalls_total").value() != 1:
        failures += 1
        print(f"PROGRESS: tpu_query_stalls_total counted "
              f"{m.counter('tpu_query_stalls_total').value()} "
              f"(expected exactly 1 — scans must dedup)")
    bb = bgerrors.last_error("watchdog")
    if not bb or "no progress" not in str(bb.get("message", "")):
        failures += 1
        print(f"PROGRESS: stall never reached the failure black box: "
              f"{bb}")
    err = caught.get("stall")
    if not isinstance(err, TpuQueryCancelled) or \
            getattr(err, "cause", None) != "watchdog":
        failures += 1
        print(f"PROGRESS: watchdog auto-cancel did not propagate "
              f"typed with cause=watchdog: {err!r}")
    for p in books(pool._sessions[1]):
        failures += 1
        print(f"PROGRESS [stall]: {p}")
    for p in expect_bundle(before, "TpuQueryCancelled", "cancelled",
                           extra_kinds=("background_failure",)):
        failures += 1
        print(f"PROGRESS [stall]: {p}")
    if cancel_count("watchdog") != 1:
        failures += 1
        print(f"PROGRESS: cancellations{{cause=watchdog}} = "
              f"{cancel_count('watchdog')}, expected 1")

    def inflight_query(tenant):
        """The id of the tenant's in-flight query, as the live view
        (``GET /queries``) shows it: a session counts its queries."""
        live = ProgressTracker.get().live_view(scan=False)["inflight"]
        return next((q["query"] for q in live if q["tenant"] == tenant),
                    "")

    # -- cancel mid-compute (session API) ------------------------------------
    started2 = threading.Event()
    release2 = threading.Event()

    def slow_ep(self, pid, ctx, *consumer):
        for b in raw_filter_iterator(self, pid, ctx, *consumer):
            started2.set()
            release2.wait(10.0)  # held until the cancel has landed
            yield b

    armed = arm_filter(slow_ep)
    before = set(pm.list_bundles(pmdir))
    s2 = pool._sessions[2]

    def victim_compute():
        try:
            run_as(s2, mixes[id(s2)]["filter4"])
        except BaseException as ex:  # noqa: BLE001 — verified below
            caught["compute"] = ex

    th = threading.Thread(target=victim_compute)
    th.start()
    try:
        if not started2.wait(30):
            failures += 1
            print("PROGRESS: compute-cancel query never reached the "
                  "armed operator")
        if not s2.cancel(inflight_query("pool-2")):
            failures += 1
            print("PROGRESS: session.cancel found no in-flight query")
        release2.set()
    finally:
        th.join(30)
        disarm_filter(armed)
    err = caught.get("compute")
    if not isinstance(err, TpuQueryCancelled) or \
            getattr(err, "cause", None) != "client" or \
            getattr(err, "checkpoint", None) not in ("compute",
                                                     "partition"):
        failures += 1
        print(f"PROGRESS: mid-compute cancel did not propagate typed "
              f"at a compute checkpoint: {err!r}")
    for p in books(s2):
        failures += 1
        print(f"PROGRESS [compute-cancel]: {p}")
    for p in expect_bundle(before, "TpuQueryCancelled", "cancelled"):
        failures += 1
        print(f"PROGRESS [compute-cancel]: {p}")

    # -- cancel while queued for admission (pool API) ------------------------
    orig_bound = TpuSession._static_peak_bound

    def fixed_bound(self, final_plan, conf, budget=None):
        # whale 200M + victim 100M oversubscribes 256M: the victim
        # queues IFF the whale is in flight
        return (200 << 20) if getattr(self, "_tenant", "") == "pool-0" \
            else (100 << 20)

    h_started = threading.Event()
    hold = threading.Event()

    def holding_ep(self, pid, ctx, *consumer):
        s = TpuSession.active()
        if getattr(s, "_tenant", "") == "pool-0" and \
                not h_started.is_set():
            h_started.set()
            hold.wait(20.0)  # holds 200M of admitted budget
        for b in raw_filter_iterator(self, pid, ctx, *consumer):
            yield b

    armed = arm_filter(holding_ep)
    TpuSession._static_peak_bound = fixed_bound
    before = set(pm.list_bundles(pmdir))
    whale, victim = pool._sessions[0], pool._sessions[3]
    whale_res = {}

    def run_whale():
        try:
            whale_res["table"] = run_as(
                whale, mixes[id(whale)]["filter_only"])
        except BaseException as ex:  # noqa: BLE001 — verified below
            whale_res["err"] = ex

    def victim_queue():
        try:
            run_as(victim, mixes[id(victim)]["filter4"])
        except BaseException as ex:  # noqa: BLE001 — verified below
            caught["queue"] = ex

    th_w = threading.Thread(target=run_whale)
    th_v = threading.Thread(target=victim_queue)
    th_w.start()
    try:
        if not h_started.wait(30):
            failures += 1
            print("PROGRESS: whale never started holding admission")
        th_v.start()
        ac = AdmissionController.get()
        deadline = _time.monotonic() + 15
        while ac.queue_depth < 1 and _time.monotonic() < deadline:
            _time.sleep(0.01)
        if ac.queue_depth < 1:
            failures += 1
            print("PROGRESS: victim never queued behind the whale")
        if not pool.cancel("pool-3", inflight_query("pool-3")):
            failures += 1
            print("PROGRESS: pool.cancel found no in-flight query")
        # the cancelled ticket must leave the FIFO while the whale
        # still holds the budget — cancel-while-queued, not timeout
        deadline = _time.monotonic() + 5
        while ac.queue_depth and _time.monotonic() < deadline:
            _time.sleep(0.01)
        if ac.queue_depth:
            failures += 1
            print(f"PROGRESS: cancelled ticket still queued "
                  f"(depth {ac.queue_depth})")
    finally:
        hold.set()
        th_v.join(30)
        th_w.join(30)
        disarm_filter(armed)
        TpuSession._static_peak_bound = orig_bound
    err = caught.get("queue")
    if not isinstance(err, TpuQueryCancelled) or \
            getattr(err, "checkpoint", None) != "queue-wait":
        failures += 1
        print(f"PROGRESS: queued cancel did not propagate typed at "
              f"the queue-wait checkpoint: {err!r}")
    if "table" not in whale_res:
        failures += 1
        print(f"PROGRESS: the whale did not survive the victim's "
              f"cancel: {whale_res.get('err')!r}")
    for p in books(victim):
        failures += 1
        print(f"PROGRESS [queue-cancel]: {p}")
    for p in expect_bundle(before, "TpuQueryCancelled", "cancelled"):
        failures += 1
        print(f"PROGRESS [queue-cancel]: {p}")

    # -- blown deadline_ms ---------------------------------------------------
    before = set(pm.list_bundles(pmdir))
    s1 = pool._sessions[1]

    def run_deadline():
        lp = (s1.create_dataframe(fact, num_partitions=4)
              .group_by(col("k")).agg(F.sum(col("v")).alias("sv"))
              ._lp)
        return s1.execute(lp, deadline_ms=1)

    err = None
    try:
        run_as(s1, run_deadline)
    except BaseException as ex:  # noqa: BLE001 — verified below
        err = ex
    if not isinstance(err, TpuQueryDeadlineExceeded):
        failures += 1
        print(f"PROGRESS: deadline_ms=1 did not raise "
              f"TpuQueryDeadlineExceeded: {err!r}")
    for p in books(s1):
        failures += 1
        print(f"PROGRESS [deadline]: {p}")
    for p in expect_bundle(before, "TpuQueryDeadlineExceeded",
                           "deadline_exceeded"):
        failures += 1
        print(f"PROGRESS [deadline]: {p}")
    if cancel_count("deadline") != 1:
        failures += 1
        print(f"PROGRESS: cancellations{{cause=deadline}} = "
              f"{cancel_count('deadline')}, expected 1")

    # -- cancel during remote fetch ------------------------------------------
    unblock = threading.Event()

    class _MetaTx:
        def __init__(self, metas):
            self.metas = metas

        def wait(self, timeout=None):
            return self.metas

    class _SlowTx:
        def wait(self, timeout=None):
            unblock.wait(min(timeout or 3.0, 3.0))
            return None

    class _SlowClient:
        def fetch_metadata(self, sid, rid, ctx=None):
            return _MetaTx([((sid, 0, rid, 0), None)])

        def fetch_block(self, sid, mid, rid, idx, xp=None, ctx=None):
            return _SlowTx()

    before = set(pm.list_bundles(pmdir))
    handle = ProgressTracker.get().begin_query("qfetch", tenant="gate")
    prog.bind_to_thread(handle)
    timer = threading.Timer(
        0.4, lambda: ProgressTracker.get().cancel("qfetch",
                                                  tenant="gate"))
    timer.start()
    err = None
    try:
        fetcher = tr.AsyncBlockFetcher(_SlowClient(), 9, 0,
                                       timeout=5.0)
        list(fetcher.blocks())
    except BaseException as ex:  # noqa: BLE001 — verified below
        err = ex
    finally:
        timer.cancel()
        unblock.set()
        ProgressTracker.get().end_query(handle, err)
        prog.bind_to_thread(None)
    if not isinstance(err, TpuQueryCancelled) or \
            getattr(err, "checkpoint", None) != "remote-fetch":
        failures += 1
        print(f"PROGRESS: mid-fetch cancel did not propagate typed at "
              f"the remote-fetch checkpoint: {err!r}")
    else:
        # no session owns the fetcher: the serving harness black-boxes
        pm.dump_postmortem(pmdir, err, tenant="gate", max_bundles=500)
        for p in expect_bundle(before, "TpuQueryCancelled",
                               "cancelled"):
            failures += 1
            print(f"PROGRESS [fetch-cancel]: {p}")
    for p in books():
        failures += 1
        print(f"PROGRESS [fetch-cancel]: {p}")
    if cancel_count("client") != 3:
        failures += 1
        print(f"PROGRESS: cancellations{{cause=client}} = "
              f"{cancel_count('client')}, expected 3 (compute, "
              f"queue-wait, remote-fetch)")

    # -- wind-down -----------------------------------------------------------
    inflight_fam = [f for f in MetricsRegistry.get().families()
                    if f.name == "tpu_queries_inflight"]
    if not inflight_fam or inflight_fam[0].total() != 0:
        failures += 1
        print(f"PROGRESS: inflight gauges dirty after the cancel "
              f"legs: "
              f"{inflight_fam[0].total() if inflight_fam else 'absent'}")
    pool.drain(timeout=60)
    pool.close()
    shutil.rmtree(pmdir, ignore_errors=True)
    bgerrors.reset()
    MetricsRegistry.reset_for_tests()
    AdmissionController.reset_for_tests()
    LatencyObservatory.reset_for_tests()
    ProgressTracker.reset_for_tests()
    if failures:
        print(f"progress gate: {failures} failure(s)")
        return 1
    print(f"progress gate clean ({len(done)} golden queries at ratio "
          f"1.0 reconciling partitions to operator spans; probed "
          f"ratios monotone {probe[0]:.2f}->{probe[-1]:.2f}; injected "
          f"stall flagged {op} then auto-cancelled; compute/"
          f"queue-wait/remote-fetch/deadline cancels all typed with "
          f"balanced books and one bundle each; hook overhead "
          f"{pct:.3f}% < 5%)")
    return 0


def main(argv=None):
    args = argv if argv is not None else sys.argv[1:]
    if "--interp" in args:
        return run_interp_gate()
    if "--memsan" in args:
        return run_memsan_gate()
    if "--obs" in args:
        return run_obs_gate()
    if "--regress" in args:
        return run_regress_gate()
    if "--metrics" in args:
        return run_metrics_gate()
    if "--jit" in args:
        return run_jit_gate()
    if "--shuffle" in args:
        return run_shuffle_gate()
    if "--serve" in args:
        return run_serve_gate()
    if "--csan" in args:
        return run_csan_gate()
    if "--feedback" in args:
        return run_feedback_gate()
    if "--fleet" in args:
        return run_fleet_gate()
    if "--hbm" in args:
        return run_hbm_gate()
    if "--faults" in args:
        return run_faults_gate()
    if "--dsan" in args:
        return run_dsan_gate()
    if "--hlo" in args:
        return run_hlo_gate()
    if "--slo" in args:
        return run_slo_gate()
    if "--progress" in args:
        return run_progress_gate()
    from spark_rapids_tpu.tools.__main__ import main as tools_main
    cli = ["lint", "--repo", "--baseline", BASELINE]
    if "--update-baseline" in args:
        cli.append("--update-baseline")
    return tools_main(cli)


if __name__ == "__main__":
    sys.exit(main())
