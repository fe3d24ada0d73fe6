#!/usr/bin/env python
"""Headline benchmark: mixed SQL operator suite, TPU engine vs CPU engine.

Workloads mirror the reference's best-suited shapes (docs/FAQ.md:107-116:
high-cardinality group-by / join / sort, windows, parquet IO):

  q1 agg:     scan -> filter -> GROUP BY k SUM/AVG/COUNT   (100k groups)
  q2 join:    shuffled hash join on a 100k-key dimension, then agg
  q3 sort:    global sort by two keys
  q4 window:  row_number + running sum over partitions
  q5 parquet: multi-file parquet scan -> filter -> aggregate
  q6 shjoin:  multi-partition shuffle join (broadcast disabled), the
              multi-batch host-exchange path
  q7 write:   scan -> parquet write (columnar write path)

Plus one out-of-loop measurement: `big_join`, a join whose build side
deliberately exceeds the JVM bridge's retired 256 MB driver-collect cap
(`spark.tpu.bridge.maxBuildSideBytes`), executed through the
spill-backed shuffled path under the memsan ledger (--skip-big-join to
omit; it costs one full build-side shuffle).

Prints ONE JSON line: value = total rows processed per second through
the TPU engine across the suite; vs_baseline = CPU-engine time / TPU
time on the same host (the stand-in for Spark-CPU until a cluster
baseline exists).  The line names the device it ran on; a run that
finds no TPU exits non-zero and prints no number.

One process per chip: the process started as `python bench.py` stays
off JAX and runs every phase that needs the chip (`--phase=...`) as a
child, one after another, so no phase is started from a process that
holds the device.
"""

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq


def make_tables(n_rows: int, seed: int = 42):
    rng = np.random.default_rng(seed)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 100_000, n_rows).astype(np.int64)),
        "v": pa.array(rng.integers(-(10**6), 10**6, n_rows).astype(np.int64)),
        "f": pa.array(rng.random(n_rows)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(100_000, dtype=np.int64)),
        "w": pa.array(rng.random(100_000)),
    })
    return fact, dim


def write_parquet_input(fact: pa.Table, root: str, n_files: int = 4) -> str:
    """Multi-file parquet dataset for the scan benchmarks."""
    path = os.path.join(root, "fact_pq")
    os.makedirs(path, exist_ok=True)
    per = -(-fact.num_rows // n_files)
    for i in range(n_files):
        pq.write_table(fact.slice(i * per, per),
                       os.path.join(path, f"part-{i:02d}.parquet"))
    return path


def queries(session, fact, dim, pq_path, out_root):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.expr.window import WindowBuilder

    fdf = session.create_dataframe(fact)
    ddf = session.create_dataframe(dim)
    # multi-partition variants exercise the shuffle paths
    fdf4 = session.create_dataframe(fact, num_partitions=4)
    ddf2 = session.create_dataframe(dim, num_partitions=2)

    def q1_agg():
        return (fdf.filter(col("v") > -(10**6) // 2)
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv"),
                     F.avg(col("f")).alias("af"),
                     F.count("*").alias("c"))
                .collect())

    def q2_join():
        return (fdf.join(ddf, on="k", how="inner")
                .group_by(col("k"))
                .agg(F.sum(col("w")).alias("sw"))
                .collect())

    def q3_sort():
        return fdf.sort(col("k"), col("v")).collect()

    def q4_window():
        w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
        return (fdf.select(col("k"), col("v"),
                           F.row_number().over(w).alias("rn"),
                           F.sum(col("v")).over(w).alias("rs"))
                .collect())

    def q5_parquet():
        return (session.read.parquet(pq_path)
                .filter(col("f") < 0.5)
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv"),
                     F.count("*").alias("c"))
                .collect())

    def q6_shuffle_join():
        return (fdf4.join(ddf2, on="k", how="inner")
                .group_by(col("k"))
                .agg(F.sum(col("w")).alias("sw"))
                .collect())

    def q7_write():
        out = os.path.join(out_root, f"bench_out_{time.time_ns()}")
        fdf.filter(col("v") > 0).write.mode("overwrite").parquet(out)
        # row verification reads only footers; a full read-back would
        # charge scan cost to the write benchmark
        n = sum(pq.ParquetFile(os.path.join(out, f)).metadata.num_rows
                for f in os.listdir(out) if f.endswith(".parquet"))
        shutil.rmtree(out, ignore_errors=True)

        class R:  # uniform "has rows" result contract
            num_rows = n
        return R

    return [("agg", q1_agg), ("join", q2_join), ("sort", q3_sort),
            ("window", q4_window), ("parquet", q5_parquet),
            ("shuffle_join", q6_shuffle_join), ("write", q7_write)]


def time_engine(enabled: bool, fact, dim, pq_path, out_root,
                repeats: int = 3, trace: bool = False,
                eventlog_dir: str = None, metrics: bool = None,
                hbm: bool = None):
    from spark_rapids_tpu.api.session import TpuSession
    extra = {}
    if enabled and os.environ.get("BENCH_TRANSPORT"):
        extra["spark.rapids.shuffle.transport"] = \
            os.environ["BENCH_TRANSPORT"]
    if trace:
        extra["spark.rapids.tpu.trace.enabled"] = True
    if eventlog_dir:
        extra["spark.rapids.tpu.eventLog.dir"] = eventlog_dir
    if metrics is not None:
        extra["spark.rapids.tpu.metrics.enabled"] = metrics
    if hbm is not None:
        extra["spark.rapids.tpu.hbm.timeline.enabled"] = hbm
    b = TpuSession.builder().config("spark.rapids.sql.enabled", enabled)
    for k, v in extra.items():
        b = b.config(k, v)
    s = b.get_or_create()
    qs = queries(s, fact, dim, pq_path, out_root)
    per_query = {}
    compile_s = {}
    for name, q in qs:
        t0 = time.perf_counter()
        q()  # warmup; any uncached compiles happen here
        first = time.perf_counter() - t0
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = q()
            times.append(time.perf_counter() - t0)
        assert out.num_rows > 0
        # median: best-of flattered the number, mean punishes one-off
        # host hiccups; median is the honest middle
        warm = sorted(times)[len(times) // 2]
        per_query[name] = warm
        # cold-query overhead: first run minus warm = compile + trace
        # cost a NOVEL query shape pays (persistent-cache hits shrink it)
        compile_s[name] = max(first - warm, 0.0)
    return per_query, compile_s


# Published peak HBM bandwidth of one chip in bytes/s, keyed by JAX's
# `device_kind` (Google Cloud documentation, "TPU v5e": 819 GB/s).  The
# suite's per-query input is the fact table — bytes/s against that bound
# shows how far the engine sits from the hardware, not just from the
# host CPU baseline.  A device that is not listed is an error.
_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def hbm_bytes_per_s(device_kind: str) -> float:
    try:
        return _HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no published HBM bandwidth for device kind "
            f"{device_kind!r}; add it to _HBM_BYTES_PER_S with its "
            f"source") from None


def require_tpu():
    """The device a measurement runs on.  A run that finds no TPU
    fails here; nothing is ever measured on the CPU in its place."""
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"needs a TPU: JAX found platform {device.platform!r} "
            f"({device.device_kind})")
    return device


def device_facts() -> dict:
    import jax
    device = jax.devices()[0]
    return {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}


def run_cold_probe(n_rows: int) -> None:
    """Internal phase (--phase=cold): a NOVEL filter+group-by in THIS
    fresh process, whose persistent compile cache the parent has just
    emptied.  Prints COLD_SECONDS."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    require_tpu()
    rng = np.random.default_rng(7)
    # a shape the suite never compiles: different column set and dtypes
    tb = pa.table({
        "g": pa.array(rng.integers(0, 4321, n_rows).astype(np.int64)),
        "a": pa.array(rng.integers(-500, 500, n_rows).astype(np.int32)),
        "b": pa.array(rng.random(n_rows)),
    })
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True).get_or_create())
    df = s.create_dataframe(tb)
    t0 = time.perf_counter()
    out = (df.filter(col("a") > -250)
           .group_by(col("g"))
           .agg(F.sum(col("a")).alias("sa"), F.avg(col("b")).alias("ab"),
                F.count("*").alias("c"))
           .collect())
    assert out.num_rows > 0
    print("COLD_SECONDS=%.2f" % (time.perf_counter() - t0))


def _cache_root() -> str:
    """Where the engine keeps its persistent compile cache
    (plugin.init_compilation_cache's rule, restated here because this
    process must not import the engine)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache")


def _empty_cache_subdir(name: str) -> str:
    """A phase that needs an empty cache gets a FIXED sub-directory of
    the cache, cleared first: the path is part of how a later process
    finds the cache again, so it never carries a pid, a time or a
    random name."""
    d = os.path.join(_cache_root(), name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def _run_phase(args, cache_dir: str = None, timeout: float = None,
               check: bool = True):
    """Run one `--phase=...` of this file in a child, which is the
    process that holds the chip while it lives.  The caller is the
    parent that stays off JAX, so phases run one after another.
    Returns (returncode, stdout); the child's stderr passes through."""
    import subprocess
    env = dict(os.environ)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env.pop("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE", None)
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        stdout=subprocess.PIPE, text=True, env=env, timeout=timeout)
    if check and r.returncode != 0:
        sys.stdout.write(r.stdout)
        raise SystemExit(
            f"bench: phase {' '.join(args)} failed rc={r.returncode}")
    return r.returncode, r.stdout


def measure_cache_cold(n_rows: int) -> float:
    """Wall seconds for a NOVEL filter+group-by in a fresh process with
    an EMPTY persistent compile cache — the first-query cost a new
    deployment actually pays (warm `compile_s` numbers ride the
    populated cache)."""
    _, out = _run_phase(["--phase=cold", str(n_rows)],
                        cache_dir=_empty_cache_subdir("cold"),
                        timeout=1800)
    for line in out.splitlines():
        if line.startswith("COLD_SECONDS="):
            return float(line.split("=")[1])
    raise SystemExit(f"bench: cold probe printed no COLD_SECONDS:\n{out}")


_SUITE_NAMES = ("agg", "join", "sort", "window", "parquet",
                "shuffle_join", "write")


# the JVM bridge's retired driver-collect ceiling: shuffled/SMJ joins
# whose build side exceeded this were REJECTED outright before the
# spill-backed shuffle catalog existed.  big_join deliberately builds
# past it so the retired cap has a measured after.
_OLD_BUILD_CAP_BYTES = 256 * 1024 * 1024


def measure_big_join(cap_bytes: int = _OLD_BUILD_CAP_BYTES) -> dict:
    """One end-to-end join whose BUILD side exceeds the old 256 MB
    bridge cap (`spark.tpu.bridge.maxBuildSideBytes`), executed through
    the co-partitioned spill-backed shuffle path — the workload the
    bridge used to reject.  Runs ONCE (~the cost of shuffling the full
    build side through the catalog), outside the repeated suite loop.

    A wide FK->PK dimension keeps the byte size past the cap without a
    row-explosion: 33 int64 columns, unique keys, so the join output is
    one row per probe row.  The LEFT join pins the oversized dimension
    as the build side (an inner join would flip the smaller fact into
    build position and broadcast it).  singleChipFuse is off so the
    single-device host still plans the real ShuffledHashJoinExec over
    co-clustered catalog partitions instead of fusing the exchanges
    away.  The memsan shadow ledger rides the run: peak device bytes
    are measured, and a dirty ledger (leaked shuffle blocks, lifecycle
    violations) fails the measurement rather than reporting around it."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory import memsan

    ncols = 32
    row_bytes = 8 * (1 + ncols)
    build_rows = cap_bytes // row_bytes + 1      # first size past the cap
    cols = {"k": pa.array(np.arange(build_rows, dtype=np.int64))}
    base = np.arange(build_rows, dtype=np.int64)
    for i in range(ncols):
        cols[f"w{i}"] = pa.array(base + i)
    dim = pa.table(cols)
    assert dim.nbytes > cap_bytes
    rng = np.random.default_rng(42)
    probe_rows = 1_000_000
    fact = pa.table({
        "k": pa.array(rng.integers(0, build_rows,
                                   probe_rows).astype(np.int64)),
        "v": pa.array(rng.integers(-1000, 1000,
                                   probe_rows).astype(np.int64))})
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.singleChipFuse", "off")
         .get_or_create())
    fdf = s.create_dataframe(fact, num_partitions=4)
    ddf = s.create_dataframe(dim, num_partitions=4)
    t0 = time.perf_counter()
    with memsan.installed() as ledger:
        out = (fdf.join(ddf, on="k", how="left")
               .group_by(col("k"))
               .agg(F.sum(col("w0")).alias("sw"))
               .collect())
    wall = time.perf_counter() - t0
    expect_groups = len(np.unique(fact.column("k").to_numpy()))
    assert out.num_rows == expect_groups, \
        f"big_join lost rows: {out.num_rows} != {expect_groups}"
    kinds = []
    s.last_plan.foreach(lambda e: kinds.append(type(e).__name__))
    shuffled = "ShuffledHashJoinExec" in kinds and \
        "BroadcastHashJoinExec" not in kinds
    assert shuffled, f"big_join did not take the shuffled path: {kinds}"
    try:
        ledger.assert_clean()
        clean = True
    except Exception:
        clean = False
    rows_in = probe_rows + build_rows
    return {
        "build_side_bytes": dim.nbytes,
        "old_cap_bytes": cap_bytes,
        "probe_rows": probe_rows,
        "build_rows": build_rows,
        "wall_s": round(wall, 2),
        "rows_per_s": round(rows_in / wall, 1),
        "output_rows": out.num_rows,
        "peak_device_bytes": int(ledger.peak_device_bytes),
        "shuffled_plan": shuffled,
        "memsan_clean": clean,
    }


def run_one_suite(name: str, n_rows: int,
                  ledger_dir: str = "", accuracy_history: str = "",
                  feedback: bool = False) -> None:
    """Internal phase (--phase=one-suite): run ONE suite query in THIS
    fresh process against the persistent compile cache the parent
    placed (JAX_COMPILATION_CACHE_DIR), and print the compile
    observatory's totals.  The --compile-report driver runs
    this twice per suite — a cold subprocess (empty cache) then a warm
    one (populated cache) — so cold/warm compile cost and the distinct-
    program count are measured per suite instead of today's single
    lumped first-run-minus-warm `compile_s` guess.

    With `accuracy_history` set (the --accuracy driver), the session
    also runs traced against that regression HistoryDir, so the
    estimator ledger records predicted-vs-actual for every operator —
    and `feedback=True` (the warm arm) blends the prior cold arm's
    recorded actuals back into the estimates first.  SUITE_JSON then
    carries this process's mean relative row/byte estimate error."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    require_tpu()
    fact, dim = make_tables(n_rows)
    root = tempfile.mkdtemp(prefix="tpu_suite_")
    try:
        pq_path = write_parquet_input(fact, root)
        b = (TpuSession.builder()
             .config("spark.rapids.sql.enabled", True))
        if ledger_dir:
            b = b.config("spark.rapids.tpu.compile.ledgerDir",
                         ledger_dir)
        if accuracy_history:
            b = (b.config("spark.rapids.tpu.regress.historyDir",
                          accuracy_history)
                 .config("spark.rapids.tpu.trace.enabled", True)
                 .config("spark.rapids.tpu.feedback.enabled",
                         feedback))
        s = b.get_or_create()
        qs = dict(queries(s, fact, dim, pq_path, root))
        t0 = time.perf_counter()
        out = qs[name]()
        wall = time.perf_counter() - t0
        assert out.num_rows > 0
        snap = CompileObservatory.get().snapshot()
        from spark_rapids_tpu.obs import metrics as obs_metrics
        reg = obs_metrics.registry()
        disk_hits = reg.counter(
            "tpu_jit_persistent_cache_hits_total").value()
        disk_misses = reg.counter(
            "tpu_jit_persistent_cache_misses_total").value()
        payload = {
            "suite": name, "wall_s": round(wall, 3),
            "compile_s": snap["compile_seconds_total"],
            "trace_s": snap["trace_seconds_total"],
            "build_total_s": round(snap["compile_seconds_total"] +
                                   snap["trace_seconds_total"], 3),
            "distinct_programs": snap["distinct_programs"],
            "builds": snap["builds"],
            "prewarm_hits": snap["prewarm_hits"],
            "prewarm_s": snap["prewarm_seconds"],
            "disk_hits": disk_hits, "disk_misses": disk_misses}
        # tpuxsan padding-waste books (obs/tracer.py): counters only
        # fill when tracing ran, so a no-trace suite honestly reports 0
        pad_fam = reg.counter("tpu_pad_waste_bytes_total",
                              labelnames=("exec",))
        tot_fam = reg.counter("tpu_operator_bytes_total",
                              labelnames=("exec",))
        pad = sum(ch.value for _, ch in pad_fam.series())
        tot = sum(ch.value for _, ch in tot_fam.series())
        payload["pad_waste_bytes"] = int(pad)
        payload["pad_waste_ratio"] = round(pad / tot, 4) if tot else 0.0
        if accuracy_history:
            from spark_rapids_tpu.obs.estimator import EstimatorLedger
            est = EstimatorLedger.get().snapshot()
            payload.update({
                "est_observations": est["observations"],
                "mean_rows_err": est["mean_rows_err"],
                "mean_bytes_err": est["mean_bytes_err"],
                "calibration_score": est["calibration_score"]})
        print("SUITE_JSON=" + json.dumps(payload))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _one_suite_subprocess(name: str, n_rows: int, cache_dir: str,
                          ledger_dir: str = "",
                          accuracy_history: str = "",
                          feedback: bool = False):
    """One fresh-process suite run; returns the parsed SUITE_JSON."""
    args = ["--phase=one-suite", str(n_rows), f"--suite={name}"]
    if ledger_dir:
        args.append(f"--ledger-dir={ledger_dir}")
    if accuracy_history:
        args.append(f"--accuracy-history={accuracy_history}")
        if feedback:
            args.append("--with-feedback")
    _, out = _run_phase(args, cache_dir=cache_dir, timeout=3600)
    for line in out.splitlines():
        if line.startswith("SUITE_JSON="):
            return json.loads(line[len("SUITE_JSON="):])
    raise SystemExit(f"bench: suite {name} printed no SUITE_JSON:\n{out}")


def measure_compile_report(n_rows: int) -> dict:
    """Per-suite cold/warm compile attribution: each suite runs in a
    cold subprocess (fresh persistent cache) then a warm one (same
    cache dir + compile ledger dir).  compile_cold_s is the full
    trace+lower+compile wall a new deployment pays; compile_warm_s is
    what the warm-start tier leaves at QUERY time — with the cold run's
    recipes prewarmed at session init, it should be ~0 (zero builds),
    with the re-trace cost reported separately as warm_prewarm_s."""
    report = {}
    for name in _SUITE_NAMES:
        cache_dir = _empty_cache_subdir(f"compile-report-{name}")
        ledger_dir = tempfile.mkdtemp(prefix=f"tpu_ledger_{name}_")
        try:
            cold = _one_suite_subprocess(name, n_rows, cache_dir,
                                         ledger_dir)
            warm = _one_suite_subprocess(name, n_rows, cache_dir,
                                         ledger_dir)
            report[name] = {
                "compile_cold_s": round(cold["build_total_s"], 2),
                "compile_warm_s": round(warm["build_total_s"], 2),
                "distinct_programs": cold["distinct_programs"],
                "warm_builds": warm["builds"],
                "warm_prewarm_hits": warm["prewarm_hits"],
                "warm_prewarm_s": round(warm["prewarm_s"], 2),
                "warm_disk_hits": warm["disk_hits"],
            }
        finally:
            shutil.rmtree(ledger_dir, ignore_errors=True)
    return report


def measure_accuracy(n_rows: int) -> dict:
    """Per-suite estimator accuracy, cold model vs warm ledger: each
    suite runs in a cold subprocess (fresh regression HistoryDir — the
    static cost model alone) and then a warm one (same HistoryDir with
    ``spark.rapids.tpu.feedback.enabled``, so the session loads the
    cold arm's estimator ledger and blends its recorded actuals into
    the estimates).  The per-arm mean relative row/byte estimate error
    comes straight off each subprocess's EstimatorLedger snapshot —
    the cold->warm delta is the measured value of closing the
    predict->execute loop, per workload shape."""
    report = {}
    for name in _SUITE_NAMES:
        hist_dir = tempfile.mkdtemp(prefix=f"tpu_acc_hist_{name}_")
        cache_dir = _empty_cache_subdir(f"accuracy-{name}")
        try:
            cold = _one_suite_subprocess(name, n_rows, cache_dir,
                                         accuracy_history=hist_dir)
            warm = _one_suite_subprocess(name, n_rows, cache_dir,
                                         accuracy_history=hist_dir,
                                         feedback=True)
            report[name] = {
                "rows_err_cold": cold["mean_rows_err"],
                "rows_err_warm": warm["mean_rows_err"],
                "bytes_err_cold": cold["mean_bytes_err"],
                "bytes_err_warm": warm["mean_bytes_err"],
                "est_observations": cold["est_observations"],
                "calibration_cold": cold["calibration_score"],
                "calibration_warm": warm["calibration_score"],
            }
        finally:
            shutil.rmtree(hist_dir, ignore_errors=True)
    return report


def time_pyspark(fact, dim, pq_path, out_root, repeats: int = 3):
    """The same 7 queries on local-mode Spark-CPU — the reference's true
    comparison target (FAQ.md's 3-7x bar).  Returns per-query medians,
    or None when pyspark is not importable (the hermetic engine
    environment ships none; CI environments with pyspark report it)."""
    try:
        from pyspark.sql import SparkSession, functions as SF
        from pyspark.sql.window import Window as SW
    except ImportError:
        return None
    spark = (SparkSession.builder.master("local[*]")
             .config("spark.sql.shuffle.partitions", "4")
             .config("spark.ui.enabled", "false")
             .appName("bench-baseline").getOrCreate())
    fdf = spark.createDataFrame(fact.to_pandas())
    ddf = spark.createDataFrame(dim.to_pandas())
    fdf.cache().count()
    ddf.cache().count()

    def q1():
        return (fdf.filter(SF.col("v") > -(10**6) // 2).groupBy("k")
                .agg(SF.sum("v"), SF.avg("f"), SF.count("*")).collect())

    def q2():
        return (fdf.join(ddf, on="k").groupBy("k")
                .agg(SF.sum("w")).collect())

    def q3():
        return fdf.orderBy("k", "v").collect()

    def q4():
        w = SW.partitionBy("k").orderBy("v")
        return fdf.select("k", "v", SF.row_number().over(w),
                          SF.sum("v").over(w)).collect()

    def q5():
        return (spark.read.parquet(pq_path).filter(SF.col("f") < 0.5)
                .groupBy("k").agg(SF.sum("v"), SF.count("*")).collect())

    def q6():
        return (fdf.repartition(4, "k").join(ddf.repartition(2, "k"),
                                             on="k")
                .groupBy("k").agg(SF.sum("w")).collect())

    def q7():
        out = os.path.join(out_root, f"spark_out_{time.time_ns()}")
        fdf.filter(SF.col("v") > 0).write.mode("overwrite").parquet(out)
        shutil.rmtree(out, ignore_errors=True)

    names = ["agg", "join", "sort", "window", "parquet", "shuffle_join",
             "write"]
    out = {}
    for name, q in zip(names, (q1, q2, q3, q4, q5, q6, q7)):
        q()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            q()
            times.append(time.perf_counter() - t0)
        out[name] = sorted(times)[len(times) // 2]
    spark.stop()
    return out


def measure_trace_overhead(fact, dim, pq_path, out_root) -> float:
    """Flight-recorder overhead guard: the suite with tracing on vs off
    (same session config otherwise).  Returns overhead as a percentage
    of the untraced total — the observability acceptance bar is <5% on
    these golden queries (tracing is per-partition spans + deferred
    scalars, never a hot-path sync, so the budget holds with room)."""
    plain, _ = time_engine(True, fact, dim, pq_path, out_root)
    traced, _ = time_engine(True, fact, dim, pq_path, out_root,
                            trace=True)
    base = sum(plain.values())
    return 100.0 * (sum(traced.values()) - base) / base


def measure_metrics_overhead(fact, dim, pq_path, out_root) -> float:
    """Continuous-metrics overhead guard: the suite with the registry
    feeding vs fully disabled.  The acceptance bar is <2% — every hook
    is one dict lookup + one locked integer add, nothing touches the
    device, so the budget holds with a wide margin.

    The 2% bar is tighter than single-run host jitter on small inputs,
    so each arm runs twice and keeps its noise floor (the minimum):
    systematic overhead survives a minimum, scheduler hiccups do not."""
    def floor(metrics_on):
        totals = []
        for _ in range(2):
            t, _c = time_engine(True, fact, dim, pq_path, out_root,
                                metrics=metrics_on)
            totals.append(sum(t.values()))
        return min(totals)

    base = floor(False)
    return 100.0 * (floor(True) - base) / base


def measure_hbm_overhead(fact, dim, pq_path, out_root,
                         trace_out: str = None) -> float:
    """HBM-observatory overhead guard: the suite with the memory
    timeline feeding vs fully disabled.  The acceptance bar is <5% —
    every lifecycle hook is a dict update + bounded ring append under
    one lock, published to gauges outside it, so the budget holds.

    Like the metrics guard, each arm runs twice and keeps its noise
    floor (the minimum): systematic overhead survives a minimum,
    scheduler hiccups do not.

    When ``trace_out`` is set, one extra traced+timeline run exports
    its Chrome trace there so the HBM counter tracks ("ph": "C",
    ``HBM <tenant>``) land next to the operator spans for eyeballing
    in Perfetto."""
    def floor(hbm_on):
        totals = []
        for _ in range(2):
            t, _c = time_engine(True, fact, dim, pq_path, out_root,
                                hbm=hbm_on)
            totals.append(sum(t.values()))
        return min(totals)

    base = floor(False)
    pct = 100.0 * (floor(True) - base) / base
    if trace_out:
        _hbm_trace_export(fact, dim, pq_path, out_root, trace_out)
    return pct


def _hbm_trace_export(fact, dim, pq_path, out_root,
                      trace_out: str) -> None:
    """One traced run of the suite's agg query with the timeline on,
    Chrome trace (operator spans + HBM counter tracks) to a file."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    s = (TpuSession.builder()
         .config("spark.rapids.sql.enabled", True)
         .config("spark.rapids.tpu.trace.enabled", True)
         .config("spark.rapids.tpu.hbm.timeline.enabled", True)
         .get_or_create())
    out = (s.create_dataframe(fact)
           .group_by(col("k"))
           .agg(F.sum(col("v")).alias("sv"))
           .collect())
    assert out.num_rows > 0
    tr = s.last_query_trace()
    if tr is None:
        return
    with open(trace_out, "w") as f:
        json.dump(tr.to_chrome(), f)
    print(f"bench --hbm-overhead: Chrome trace with HBM counter "
          f"tracks -> {trace_out}", file=sys.stderr)


# ---------------------------------------------------------------------------
# --serve: sustained-QPS serving benchmark (pool + byte-weighted admission)
# ---------------------------------------------------------------------------

#: synthetic sql_id for the serve fingerprint (real event-log sql_ids are
#: small per-app ordinals; this can never collide with one)
_SERVE_SQL_ID = 100_000


def measure_serve_deadlines(fact, dim, pq_path, concurrency: int = 8,
                            deadline_ms: int = 1,
                            queries_per_worker: int = 3) -> dict:
    """``--deadline-ms`` leg: the serving mix with every third request
    carrying a per-request deadline tight enough to always trip.  Those
    requests must fail as TYPED TpuQueryDeadlineExceeded — counted
    under ``tpu_cancellations_total{cause="deadline"}`` — while every
    surviving request returns a bit-exact result vs a no-deadline
    reference, with zero dirty memsan ledgers and balanced admission
    books: a deadline storm is a correctness no-op for its
    neighbours."""
    import concurrent.futures as cf

    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.obs import metrics as obs_metrics
    from spark_rapids_tpu.obs.progress import TpuQueryDeadlineExceeded

    conf = {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": str(2 << 30),
        "spark.rapids.tpu.serve.admissionTimeoutMs": "120000",
    }
    reg = obs_metrics.registry()

    def deadline_cancels():
        fam = reg.counter("tpu_cancellations_total",
                          labelnames=("cause",))
        return sum(ch.value for lbl, ch in fam.series()
                   if lbl.get("cause") == "deadline")

    def dirty_ledgers():
        return reg.counter("tpu_memsan_dirty_ledgers_total").value()

    pool = SessionPool(concurrency, conf)
    plans = {id(s): serve_mix(s, fact, dim, pq_path, as_plans=True)
             for s in pool._sessions}
    mix_names = ("agg", "join", "window", "parquet")
    # warm the jit cache and pin the bit-exact reference answer per mix
    # entry (deterministic inputs: every session agrees)
    refs = {}
    for name in mix_names:
        with pool.session() as s:
            refs[name] = plans[id(s)][name]().collect()

    worklist = [(i, mix_names[i % len(mix_names)], i % 3 == 0)
                for i in range(concurrency * queries_per_worker)]
    tight_n = sum(1 for _, _, tight in worklist if tight)
    cancels0, dirty0 = deadline_cancels(), dirty_ledgers()
    outcomes = {}

    def one(item):
        i, name, tight = item
        with pool.session() as s:
            df = plans[id(s)][name]()
            if tight:
                try:
                    s.execute(df._lp, deadline_ms=deadline_ms)
                    outcomes[i] = ("no-trip", name)
                except TpuQueryDeadlineExceeded:
                    outcomes[i] = ("deadline", name)
                except Exception as ex:  # wrong TYPE is the failure
                    outcomes[i] = ("wrong-error",
                                   f"{name}: {type(ex).__name__}")
            else:
                out = df.collect()
                outcomes[i] = ("ok", name) if out.equals(refs[name]) \
                    else ("mismatch", name)

    with cf.ThreadPoolExecutor(max_workers=concurrency) as ex:
        list(ex.map(one, worklist))
    pool.drain(timeout=60)
    pool.close()

    typed = sum(1 for k, _ in outcomes.values() if k == "deadline")
    survivors_ok = sum(1 for k, _ in outcomes.values() if k == "ok")
    counted = deadline_cancels() - cancels0
    dirty = dirty_ledgers() - dirty0
    ctrl = AdmissionController.get()
    failures = []
    if typed != tight_n:
        bad = sorted(v for v in outcomes.values()
                     if v[0] in ("no-trip", "wrong-error"))
        failures.append(
            f"{typed}/{tight_n} tight-deadline requests raised typed "
            f"TpuQueryDeadlineExceeded (offenders: {bad[:4]})")
    if counted != typed:
        failures.append(
            f'tpu_cancellations_total{{cause="deadline"}} grew by '
            f"{counted}, expected {typed}")
    if survivors_ok != len(worklist) - tight_n:
        failures.append(
            f"{len(worklist) - tight_n - survivors_ok} surviving "
            f"request(s) were not bit-exact vs the no-deadline "
            f"reference")
    if dirty:
        failures.append(f"{dirty} dirty memsan ledger(s) after the "
                        f"deadline storm")
    if ctrl is not None and (ctrl.bytes_in_flight or ctrl.queue_depth):
        failures.append(
            f"admission books unbalanced after drain: "
            f"{ctrl.bytes_in_flight}B in flight, "
            f"queue depth {ctrl.queue_depth}")
    return {
        "deadline_ms": int(deadline_ms),
        "requests": len(worklist),
        "tight_requests": tight_n,
        "deadline_failures_typed": typed,
        "deadline_cancellations_counted": int(counted),
        "survivors_bit_exact": survivors_ok,
        "dirty_ledgers": int(dirty),
        "failures": failures,
    }


def serve_mix(session, fact, dim, pq_path, as_plans: bool = False):
    """The four-query serving mix (agg/join/window/parquet), bound to one
    pooled session.  Dataframes are pre-created so the measured cost is
    query execution, not host-side table registration.  ``as_plans``
    returns the un-collected dataframe builders instead of collect
    closures — the ``--deadline-ms`` leg needs the logical plan so it
    can execute with a per-request deadline."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.expr.window import WindowBuilder

    fdf = session.create_dataframe(fact)
    ddf = session.create_dataframe(dim)

    def agg():
        return (fdf.filter(col("v") > -(10**6) // 2)
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv"),
                     F.count("*").alias("c")))

    def join():
        return (fdf.join(ddf, on="k", how="inner")
                .group_by(col("k"))
                .agg(F.sum(col("w")).alias("sw")))

    def window():
        w = WindowBuilder().partition_by(col("k")).order_by(col("v"))
        return fdf.select(col("k"), col("v"),
                          F.row_number().over(w).alias("rn"))

    def parquet():
        return (session.read.parquet(pq_path)
                .filter(col("f") < 0.5)
                .group_by(col("k"))
                .agg(F.sum(col("v")).alias("sv")))

    builders = {"agg": agg, "join": join, "window": window,
                "parquet": parquet}
    if as_plans:
        return builders
    return {name: (lambda b=b: b().collect())
            for name, b in builders.items()}


def measure_serve(fact, dim, pq_path, concurrency: int = 8,
                  queries_per_worker: int = 3,
                  request_io_ms: float = 150.0) -> dict:
    """Sustained-QPS serving measurement: the SAME request list through
    a 1-session pool serially (one-at-a-time server), then a
    `concurrency`-session pool with `concurrency` client threads, under
    byte-weighted admission.  The concurrent arm must sustain strictly
    higher aggregate QPS than the serial arm (``qps_speedup > 1``) —
    the whole point of co-running — with zero dirty memsan ledgers and
    zero admission accounting drift (every admitted ticket ends as a
    completed or failed query).

    ``request_io_ms`` models the per-request client transfer latency of
    the offered load (request receive + response delivery), charged
    identically to every request in BOTH arms: a one-at-a-time server
    eats it sequentially, a multi-tenant server overlaps it with other
    tenants' compute.  On a multi-core host the compute itself overlaps
    too; on a single-core CI host this client I/O is the slack that
    makes the co-running dividend measurable at all."""
    import concurrent.futures as cf

    from spark_rapids_tpu.api.pool import SessionPool
    from spark_rapids_tpu.memory.admission import AdmissionController
    from spark_rapids_tpu.obs import metrics as obs_metrics

    conf = {
        "spark.rapids.sql.enabled": "true",
        "spark.rapids.tpu.memsan.enabled": "true",
        "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes": str(2 << 30),
        "spark.rapids.tpu.serve.admissionTimeoutMs": "120000",
        # latency observatory: tracing feeds critical-path extraction,
        # the SLO target classifies each request GOOD/BAD (generous:
        # the interesting output is the per-tenant segment mix, not a
        # burn alert on a loaded CI host)
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.slo.targetMs": str(
            int(request_io_ms * 10) or 1000),
    }
    reg = obs_metrics.registry()

    def counters():
        # admission counters are tenant-labeled; total() sums the fleet
        out = {n: reg.counter(f"tpu_admission_{n}_total",
                              labelnames=("tenant",)).total()
               for n in ("admitted", "queued", "timeouts", "repaired")}
        out["completed"] = reg.counter(
            "tpu_queries_completed_total").value()
        out["failed"] = reg.counter("tpu_queries_failed_total").value()
        out["dirty_ledgers"] = reg.counter(
            "tpu_memsan_dirty_ledgers_total").value()
        qw = reg.histogram("tpu_admission_queue_wait_seconds").value()
        # value() is 0 (not a tuple) before the first observation
        cnt, total = qw if isinstance(qw, tuple) else (0, 0.0)
        out["queue_wait_count"], out["queue_wait_sum_s"] = cnt, total
        return out

    mix_names = ("agg", "join", "window", "parquet")
    worklist = [mix_names[i % len(mix_names)]
                for i in range(concurrency * queries_per_worker)]
    peak_seen = [0]
    peak_lock = __import__("threading").Lock()

    def run_list(pool, mixes, workers):
        latencies = {}

        def one(i_name):
            i, name = i_name
            io_s = request_io_ms / 1000.0
            with pool.session() as s:
                t0 = time.perf_counter()
                time.sleep(io_s / 2)      # request receive
                out = mixes[id(s)][name]()
                time.sleep(io_s / 2)      # response delivery
                lat = time.perf_counter() - t0
            assert out.num_rows > 0
            pk = s.last_peak_device_bytes or 0
            with peak_lock:
                peak_seen[0] = max(peak_seen[0], pk)
            latencies[i] = (name, lat)

        t0 = time.perf_counter()
        if workers == 1:
            for item in enumerate(worklist):
                one(item)
        else:
            with cf.ThreadPoolExecutor(max_workers=workers) as ex:
                list(ex.map(one, enumerate(worklist)))
        wall = time.perf_counter() - t0
        return wall, [latencies[i][1] for i in sorted(latencies)]

    c0 = counters()
    # serial arm: one session, one client
    pool1 = SessionPool(1, conf)
    mixes1 = {id(s): serve_mix(s, fact, dim, pq_path)
              for s in pool1._sessions}
    for name in mix_names:  # warm the shared jit cache once per shape
        with pool1.session() as s:
            mixes1[id(s)][name]()
    serial_wall, serial_lat = run_list(pool1, mixes1, 1)
    pool1.close()
    # concurrent arm: N sessions, N client threads, same worklist.
    # Reset the latency observatory between arms so the per-tenant
    # report describes the concurrent arm only (pool1's session is
    # also tenant pool-0); the new pool's sessions reconfigure it
    from spark_rapids_tpu.obs.slo import LatencyObservatory
    LatencyObservatory.reset_for_tests()
    poolN = SessionPool(concurrency, conf)
    mixesN = {id(s): serve_mix(s, fact, dim, pq_path)
              for s in poolN._sessions}
    conc_before = counters()
    conc_wall, conc_lat = run_list(poolN, mixesN, concurrency)
    poolN.drain(timeout=30)
    c1 = counters()
    ctrl = AdmissionController.get()
    # HBM observatory rollup: per-tenant peak device occupancy and how
    # much of that peak was demotable (spillable-now) — the co-running
    # headroom story per pool tenant (obs/memprof.py)
    from spark_rapids_tpu.obs.memprof import MemoryTimeline
    hbm_rep = MemoryTimeline.get().report()
    hbm_tenants = {}
    for tenant, row in sorted(hbm_rep.get("tenants", {}).items()):
        pk = int(row.get("peak_bytes", 0))
        dm = int(row.get("peak_demotable_bytes", 0))
        hbm_tenants[tenant] = {
            "peak_device_bytes": pk,
            "demotable_share": round(dm / pk, 4) if pk else 0.0,
        }

    def pct(lats, p):
        srt = sorted(lats)
        return srt[min(int(p * (len(srt) - 1) + 0.5), len(srt) - 1)]

    # latency observatory rollup for the concurrent arm: per-tenant
    # p50/p99 with the dominant tail segment — the attribution columns
    # the QoS work (ROADMAP item 4) diffs before/after
    slo_rep = LatencyObservatory.get().slo_report()
    slo_tenants = {}
    for tenant, row in sorted(slo_rep.get("tenants", {}).items()):
        slo_tenants[tenant] = {
            "p50_ms": row["p50_ms"],
            "p99_ms": row["p99_ms"],
            "burn_rate": row["burn_rate"],
            "dominant_segment": row["dominant_tail_segment"],
        }
    slo_overhead_pct = slo_rep.get("overhead", {}).get("pct", 0.0)
    if slo_tenants:
        print("bench --serve per-tenant latency attribution:",
              file=sys.stderr)
        print(f"  {'tenant':<10} {'p50_ms':>9} {'p99_ms':>9} "
              f"{'burn':>6}  dominant_segment", file=sys.stderr)
        for tenant, row in slo_tenants.items():
            print(f"  {tenant:<10} {row['p50_ms']:>9.1f} "
                  f"{row['p99_ms']:>9.1f} {row['burn_rate']:>6.2f}  "
                  f"{row['dominant_segment'] or '-'}", file=sys.stderr)

    total = len(worklist)
    delta = {k: c1[k] - c0[k] for k in c0}
    qw_cnt = c1["queue_wait_count"] - conc_before["queue_wait_count"]
    qw_sum = c1["queue_wait_sum_s"] - conc_before["queue_wait_sum_s"]
    serial_qps = total / serial_wall
    conc_qps = total / conc_wall
    return {
        "mix": list(mix_names),
        "queries": total,
        "concurrency": concurrency,
        "request_io_ms": request_io_ms,
        "serial_qps": round(serial_qps, 2),
        "concurrent_qps": round(conc_qps, 2),
        "qps_speedup": round(conc_qps / serial_qps, 3),
        "p50_ms": round(pct(conc_lat, 0.50) * 1000, 1),
        "p99_ms": round(pct(conc_lat, 0.99) * 1000, 1),
        "serial_p50_ms": round(pct(serial_lat, 0.50) * 1000, 1),
        "queue_wait_mean_ms": round(
            1000 * qw_sum / qw_cnt, 2) if qw_cnt else 0.0,
        "peak_device_bytes": int(peak_seen[0]),
        "max_bytes_in_flight": int(ctrl.max_in_flight_seen)
            if ctrl else 0,
        "budget_bytes": int(ctrl.budget_bytes) if ctrl else 0,
        "admission": {k: int(delta[k]) for k in
                      ("admitted", "queued", "timeouts", "repaired")},
        "completed": int(delta["completed"]),
        "failed": int(delta["failed"]),
        "dirty_ledgers": int(delta["dirty_ledgers"]),
        "accounting_drift": int(
            delta["admitted"] - delta["completed"] - delta["failed"]),
        "hbm": {
            "enabled": bool(hbm_rep.get("enabled")),
            "total_peak_bytes": int(hbm_rep.get("peak_bytes", 0)),
            "demotable_bytes": int(hbm_rep.get("demotable_bytes", 0)),
            "unattributed_events": int(
                hbm_rep.get("unattributed_events", 0)),
            "tenants": hbm_tenants,
        },
        "slo": {
            "target_ms": slo_rep.get("target_ms"),
            "objective": slo_rep.get("objective"),
            "overhead_pct": slo_overhead_pct,
            "tenants": slo_tenants,
        },
    }


def serve_fingerprint(serve: dict) -> dict:
    """The serve run as ONE history fingerprint: counter totals are the
    deterministic half (fixed mix + budget replays identically; queued
    is scheduling noise and excluded), percentiles the timing half."""
    from spark_rapids_tpu.obs.history import FINGERPRINT_VERSION
    return {
        "version": FINGERPRINT_VERSION,
        "sql_id": _SERVE_SQL_ID,
        "description": "serve_mix",
        "failed": False,
        "serve_counters": {
            "admitted": serve["admission"]["admitted"],
            "repaired": serve["admission"]["repaired"],
            "timeouts": serve["admission"]["timeouts"],
            "completed": serve["completed"],
            "failed": serve["failed"],
        },
        "serve_p50_ms": serve["p50_ms"],
        "serve_p99_ms": serve["p99_ms"],
        # advisory (never diffed — byte peaks are data-layout noise):
        # per-tenant HBM peaks + demotable share from the observatory
        "serve_hbm": serve.get("hbm", {}),
        # advisory timing-class per-tenant SLO fields: burn rate is
        # load-dependent; the dominant tail segment feeds the
        # tail_mix_shift differ (timing-gated, never deterministic)
        "slo_burn_rate": {
            t: row["burn_rate"]
            for t, row in serve.get("slo", {}).get("tenants",
                                                   {}).items()},
        "tail_dominant_segment": {
            t: row["dominant_segment"]
            for t, row in serve.get("slo", {}).get("tenants",
                                                   {}).items()},
    }


def record_serve_history(history_dir: str, serve: dict, check: bool,
                         wall_threshold=None) -> int:
    """--record/--check for the serving benchmark, through the same
    append-only HistoryDir + differ as the suite fingerprints."""
    from spark_rapids_tpu.obs.history import (HistoryDir,
                                              deterministic_drift,
                                              diff_runs)
    hist = HistoryDir(history_dir)
    path = hist.record([serve_fingerprint(serve)], label="bench serve")
    print(f"bench --serve: recorded serve fingerprint -> {path}",
          file=sys.stderr)
    if not check:
        return 0
    runs = hist.runs()
    if len(runs) < 2:
        print("bench --serve --check: first recorded run, nothing to "
              "diff", file=sys.stderr)
        return 0
    drifts = diff_runs(hist.load(runs[-2]), hist.load(runs[-1]),
                       wall_threshold_pct=wall_threshold)
    for d in drifts:
        print(f"bench --serve --check: {d.render()}", file=sys.stderr)
    if deterministic_drift(drifts):
        print("SERVE REGRESSION CHECK FAILED: deterministic "
              "fingerprint drift vs the previous recorded run",
              file=sys.stderr)
        return 1
    print("bench --serve --check: no deterministic drift vs previous "
          "run", file=sys.stderr)
    return 0


def record_history(history_dir: str, eventlog_dir: str,
                   check: bool, wall_threshold=None) -> int:
    """Distill this run's event log into the append-only fingerprint
    history (--record); with --check, diff against the previous run and
    return 1 on deterministic drift (obs/history.py)."""
    from spark_rapids_tpu.obs.history import (HistoryDir,
                                              deterministic_drift,
                                              diff_runs,
                                              distill_event_log)
    hist = HistoryDir(history_dir)
    fps = []
    for f in sorted(os.listdir(eventlog_dir)):
        if f.startswith("events_"):
            fps += distill_event_log(os.path.join(eventlog_dir, f))
    path = hist.record(fps, label="bench suite")
    print(f"bench: recorded {len(fps)} query fingerprint(s) -> {path}",
          file=sys.stderr)
    if not check:
        return 0
    runs = hist.runs()
    if len(runs) < 2:
        print("bench --check: first recorded run, nothing to diff",
              file=sys.stderr)
        return 0
    drifts = diff_runs(hist.load(runs[-2]), hist.load(runs[-1]),
                       wall_threshold_pct=wall_threshold)
    for d in drifts:
        print(f"bench --check: {d.render()}", file=sys.stderr)
    if deterministic_drift(drifts):
        print("BENCH REGRESSION CHECK FAILED: deterministic "
              "fingerprint drift vs the previous recorded run",
              file=sys.stderr)
        return 1
    print("bench --check: no deterministic drift vs previous run",
          file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# --dist: multi-process shuffle benchmark (remote block fetch over loopback)
# ---------------------------------------------------------------------------

_DIST_CODECS = ("none", "lz4", "zstd")
#: fetch window per mode: serial drains one block at a time; pipelined
#: keeps the fetcher's producer thread decompressing ahead of the join
_DIST_MODES = (("pipelined", 4), ("serial", 1))


def _dist_reference(rows: int, parts: int, seed: int):
    """In-process reference: same tables, same murmur3 routing, same
    per-partition pyarrow join the distributed run performs — the
    bit-exactness oracle."""
    import pyarrow as pa
    from spark_rapids_tpu.shuffle.serve_map import (build_side_tables,
                                                    partition_record_batch)
    fact, dim = build_side_tables(rows, seed)
    fparts = partition_record_batch(fact, "k", parts)
    dparts = partition_record_batch(dim, "k", parts)
    out = []
    for pid in range(parts):
        f, d = fparts.get(pid), dparts.get(pid)
        if f is None or d is None:
            continue
        out.append(pa.table(f).join(pa.table(d), "k"))
    return pa.concat_tables(out).sort_by(
        [("k", "ascending"), ("v", "ascending")])


def _dist_fetch_join(parts: int, window: int):
    """Reduce side of the distributed join: stream both shuffles'
    blocks for every partition through the locality read path (all
    remote here — the child owns every block) and join per partition."""
    import pyarrow as pa
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.columnar.device import batch_to_arrow
    from spark_rapids_tpu.shuffle.locality import read_reduce_blocks
    from spark_rapids_tpu.shuffle.manager import materialize_block
    from spark_rapids_tpu.shuffle.serve_map import DIM_SID, FACT_SID
    conf = cfg.RapidsConf(
        {cfg.SHUFFLE_FETCH_MAX_IN_FLIGHT.key: str(window)})
    out = []
    for pid in range(parts):
        sides = []
        for sid in (FACT_SID, DIM_SID):
            rbs = [batch_to_arrow(materialize_block(b, np))
                   for b in read_reduce_blocks(sid, pid, conf=conf,
                                               xp=np)]
            sides.append(pa.Table.from_batches(rbs) if rbs else None)
        f, d = sides
        if f is None or d is None:
            continue
        out.append(f.join(d, "k"))
    return pa.concat_tables(out).sort_by(
        [("k", "ascending"), ("v", "ascending")])


def _dist_run(rows: int, parts: int, codec: str, window: int,
              seed: int, traced: bool = False,
              digest: bool = True) -> dict:
    """One (codec, window) distributed run: child process owns the map
    outputs and serves them; this process plays the reduce side.

    ``traced=True`` installs a live tracer around the fetch/join, so
    the run pays the full fleet-observatory path (fetch spans, the v2
    context on the wire, the post-fetch /spans pulls + merge) and the
    result carries ``_trace`` for the merged-trace report.

    ``digest=False`` turns content addressing off on BOTH sides (the
    child skips write-time block digests, this side skips fetch
    verification) — the baseline arm of the tpudsan overhead guard."""
    import subprocess
    from spark_rapids_tpu.obs import metrics as m
    from spark_rapids_tpu.obs import tracer as tr
    from spark_rapids_tpu.shuffle.digest import set_digest_enabled
    from spark_rapids_tpu.shuffle.locality import reset_pool
    from spark_rapids_tpu.shuffle.registry import (BlockEndpoint,
                                                   BlockLocationRegistry)
    from spark_rapids_tpu.shuffle.serve_map import DIM_SID, FACT_SID
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE="1",
               SPARK_RAPIDS_TPU_DSAN_DIGEST="1" if digest else "0")
    child = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.shuffle.serve_map",
         "--rows", str(rows), "--parts", str(parts),
         "--codec", codec, "--seed", str(seed),
         "--executor-id", "bench-map-0"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    trace = None
    try:
        line = child.stdout.readline()
        if not line.startswith("PORT "):
            raise RuntimeError(f"bad serve_map handshake: {line!r}")
        port = int(line.split()[1])
        reg = BlockLocationRegistry.get()
        reg.set_local("bench-reduce", "127.0.0.1", 0)
        ep = BlockEndpoint("bench-map-0", "127.0.0.1", port)
        reg.register(FACT_SID, [ep])
        reg.register(DIM_SID, [ep])
        local_c = m.counter("tpu_shuffle_local_blocks_total")
        local_before = local_c.value()
        verified_c = m.counter("tpu_shuffle_digest_verified_total")
        mismatch_c = m.counter("tpu_shuffle_digest_mismatch_total")
        verified_before = verified_c.value()
        mismatch_before = mismatch_c.value()
        if traced:
            trace = tr.install(tr.QueryTrace())
        set_digest_enabled(digest)
        t0 = time.perf_counter()
        joined = _dist_fetch_join(parts, window)
        wall = time.perf_counter() - t0
        if trace is not None:
            trace.finalize()
            tr.uninstall()
        local_after = local_c.value()
        child.stdin.write("done\n")
        child.stdin.flush()
        stats_line = child.stdout.readline()
        if not stats_line.startswith("STATS "):
            raise RuntimeError(f"bad serve_map stats: {stats_line!r}")
        stats = json.loads(stats_line[len("STATS "):])
        rc = child.wait(timeout=30)
        if rc != 0:
            raise RuntimeError(f"serve_map exited {rc}")
    finally:
        set_digest_enabled(True)
        if trace is not None and tr.active_tracer() is trace:
            tr.uninstall()
        child.stdin.close()
        child.stdout.close()
        if child.poll() is None:
            child.kill()
            child.wait()
        reset_pool()
        BlockLocationRegistry.get().forget_shuffle(FACT_SID)
        BlockLocationRegistry.get().forget_shuffle(DIM_SID)
    raw = stats.get("raw_bytes") or 0
    comp = stats.get("compressed_bytes") or 0
    out = {
        "codec": codec,
        "window": window,
        "rows_joined": joined.num_rows,
        "wall_s": round(wall, 4),
        "fetch_mb_s": round(raw / max(wall, 1e-9) / 1e6, 2),
        "raw_bytes": raw,
        "compressed_bytes": comp,
        "compression_ratio": round(comp / raw, 4) if raw else None,
        "server_metadata_requests": stats.get(
            "server_metadata_requests"),
        "server_transfer_requests": stats.get(
            "server_transfer_requests"),
        "child_leaked_blocks": stats.get("leaked_blocks"),
        "child_leaks": stats.get("leaks"),
        "child_unpulled_spans": stats.get("unpulled_spans"),
        "parent_local_blocks": local_after - local_before,
        "digest": digest,
        "digest_verified_blocks": verified_c.value() - verified_before,
        "digest_mismatches": mismatch_c.value() - mismatch_before,
        "_table": joined,
    }
    if trace is not None:
        out["_trace"] = trace
    return out


def _dist_trace_report(trace, trace_out: str) -> tuple:
    """Verify the merged trace's fleet shape and write it as ONE
    Chrome/Perfetto JSON: every remote fetch span must carry the
    producer's serve spans (metadata + transfer roots, with serialize
    and compress step children under the transfers), skew-corrected
    into the consumer's clock, with zero lost spans.  Returns
    (report, failures)."""
    from spark_rapids_tpu.obs.export import fleet_summary
    failures = []
    spans = trace.span_dicts()
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.get("parentId"), []).append(s)
    fetch = [s for s in spans if s["name"] == "shuffle.fetch"]
    if not fetch:
        failures.append("traced dist run recorded no fetch spans")
    for f in fetch:
        roots = [k for k in by_parent.get(f["spanId"], [])
                 if k.get("proc")]
        names = {r["name"] for r in roots}
        if not {"shuffle.serve.metadata",
                "shuffle.serve.transfer"} <= names:
            failures.append(
                f"fetch span {f['spanId']} lacks producer serve "
                f"children (got {sorted(names)})")
            continue
        steps = {c["name"]
                 for r in roots if r["name"] == "shuffle.serve.transfer"
                 for c in by_parent.get(r["spanId"], [])}
        if not {"serve.serialize", "serve.compress"} <= steps:
            failures.append(
                f"fetch span {f['spanId']} transfer lacks serialize/"
                f"compress children (got {sorted(steps)})")
        f0, f1 = f["startNs"], f["startNs"] + f["durNs"]
        for r in roots:
            if not (f0 <= r["startNs"]
                    and r["startNs"] + r["durNs"] <= f1):
                failures.append(
                    f"remote span {r['name']} outside its fetch "
                    f"parent — clock skew not corrected")
    if trace.remote_spans_merged == 0:
        failures.append("traced dist run merged zero remote spans")
    if trace.remote_spans_lost:
        failures.append(f"clean dist run lost "
                        f"{trace.remote_spans_lost} remote span(s)")
    with open(trace_out, "w") as f:
        json.dump(trace.to_chrome(), f)
    report = {
        "trace_file": trace_out,
        "fetch_spans": len(fetch),
        "remote_spans_merged": trace.remote_spans_merged,
        "remote_spans_lost": trace.remote_spans_lost,
        "fleet": fleet_summary(spans),
    }
    return report, failures


def measure_dist_trace_overhead(rows: int, parts: int,
                                seed: int) -> float:
    """Distributed flight-recorder overhead: the lz4/pipelined dist
    run with the full fleet path on (fetch spans, wire contexts,
    /spans pulls + merge) vs untraced.  Same <5% bar as the local
    guard; each arm keeps its two-run noise floor."""
    def floor(traced):
        walls = []
        for _ in range(2):
            r = _dist_run(rows, parts, "lz4", 4, seed, traced=traced)
            r.pop("_table", None)
            r.pop("_trace", None)
            walls.append(r["wall_s"])
        return min(walls)

    base = floor(False)
    return 100.0 * (floor(True) - base) / base


def measure_dist_digest_overhead(rows: int, parts: int,
                                 seed: int) -> dict:
    """tpudsan content-addressing overhead: the lz4/pipelined dist run
    with write-time block digests + fetch-side verification on vs
    fully off (both processes).  The digest arm must actually verify
    blocks (anti-vacuity) with zero mismatches; each arm keeps its
    two-run noise floor.  Budget: < 2% of untraced fetch wall time."""
    failures = []

    def floor(digest):
        walls, verified, mismatches = [], 0, 0
        for _ in range(2):
            r = _dist_run(rows, parts, "lz4", 4, seed, digest=digest)
            r.pop("_table", None)
            walls.append(r["wall_s"])
            verified += r["digest_verified_blocks"]
            mismatches += r["digest_mismatches"]
        return min(walls), verified, mismatches

    base, base_verified, _ = floor(False)
    on, on_verified, on_mismatches = floor(True)
    if base_verified:
        failures.append(
            f"digest-off arm verified {base_verified} block(s) — the "
            f"off switch does not reach the fetch path")
    if not on_verified:
        failures.append(
            "digest-on arm verified ZERO blocks — the overhead "
            "measurement is vacuous (digests never reached the wire)")
    if on_mismatches:
        failures.append(
            f"digest-on arm recorded {on_mismatches} content "
            f"mismatch(es) on a clean loopback run")
    pct = 100.0 * (on - base) / base
    return {"pct": round(pct, 2), "verified_blocks": on_verified,
            "failures": failures}


def measure_dist(rows: int, parts: int, seed: int,
                 trace_out: str = "tpu_dist_trace.json") -> dict:
    """Full --dist sweep: none/lz4/zstd x pipelined/serial, each run
    bit-exact against the in-process reference, zero leaked blocks on
    both sides, lz4 visibly compressing (ratio < 0.9).  A final traced
    lz4/pipelined run (outside the timing sweep) must merge the
    producer's serve spans under every fetch span with zero lost
    spans, and its clock-aligned Chrome trace lands in trace_out."""
    from spark_rapids_tpu.memory.spill import SpillCatalog
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    reference = _dist_reference(rows, parts, seed)
    runs = []
    failures = []
    for codec in _DIST_CODECS:
        for mode, window in _DIST_MODES:
            r = _dist_run(rows, parts, codec, window, seed)
            r["mode"] = mode
            tbl = r.pop("_table")
            r["bit_exact"] = tbl.equals(reference)
            if not r["bit_exact"]:
                failures.append(
                    f"{codec}/{mode}: result not bit-exact vs "
                    f"in-process reference ({tbl.num_rows} vs "
                    f"{reference.num_rows} rows)")
            if r["child_leaked_blocks"]:
                failures.append(
                    f"{codec}/{mode}: child leaked "
                    f"{r['child_leaked_blocks']} catalog block(s)")
            if r["child_leaks"]:
                failures.append(
                    f"{codec}/{mode}: child spill ledger reported "
                    f"{r['child_leaks']} leak(s)")
            if r["parent_local_blocks"]:
                failures.append(
                    f"{codec}/{mode}: {r['parent_local_blocks']} "
                    f"block(s) took the local path — every block is "
                    f"remote in this topology")
            if codec != "none" and r["compression_ratio"] is not None \
                    and r["compression_ratio"] >= 0.9:
                failures.append(
                    f"{codec}/{mode}: compression ratio "
                    f"{r['compression_ratio']} >= 0.9 — codec not "
                    f"actually compressing the shuffle payload")
            runs.append(r)
            print("SUITE_JSON=" + json.dumps(
                {"suite": f"dist_{codec}_{mode}",
                 **{k: v for k, v in r.items()}}))
    traced = _dist_run(rows, parts, "lz4", 4, seed, traced=True)
    traced_tbl = traced.pop("_table")
    if not traced_tbl.equals(reference):
        failures.append("traced lz4/pipelined run not bit-exact vs "
                        "in-process reference")
    trace_report, trace_failures = _dist_trace_report(
        traced.pop("_trace"), trace_out)
    failures.extend(trace_failures)
    if traced.get("child_unpulled_spans"):
        failures.append(
            f"traced run left {traced['child_unpulled_spans']} span "
            f"record(s) unpulled in the child's RemoteSpanStore")
    print("SUITE_JSON=" + json.dumps(
        {"suite": "dist_trace_merged", **trace_report}))
    parent_leaks = len(SpillCatalog.get().leak_report())
    if parent_leaks:
        failures.append(f"reduce side spill ledger reported "
                        f"{parent_leaks} leak(s)")
    leftover = TpuShuffleManager.get().catalog.num_blocks()
    if leftover:
        failures.append(f"reduce side catalog still holds {leftover} "
                        f"block(s) after all runs drained")
    def _wall(codec, mode):
        for r in runs:
            if r["codec"] == codec and r["mode"] == mode:
                return r["wall_s"]
        return None
    summary = {
        "metric": "dist_shuffle_fetch",
        "rows": rows,
        "parts": parts,
        "runs": runs,
        "pipelined_vs_serial_lz4": round(
            _wall("lz4", "serial") / max(_wall("lz4", "pipelined"),
                                         1e-9), 3),
        "merged_trace": trace_report,
        "failures": failures,
    }
    return summary


def _arg_value(flag: str, default=None):
    for a in sys.argv[1:]:
        if a.startswith(flag + "="):
            return a.split("=", 1)[1]
    return default


def _positional(argv) -> list:
    return [a for a in argv if not a.startswith("--")]


def run_dist(argv) -> None:
    """--dist: multi-process shuffle mode: map side in a child OS
    process, reduce side here, blocks over loopback TCP.  Pure host-side
    (numpy + pyarrow); the child is pinned to JAX_PLATFORMS=cpu, so it
    needs no chip and may be started from here."""
    pos = _positional(argv)
    dist_rows = int(pos[0]) if pos else 20_000
    dist_parts = int(_arg_value("--parts", "4"))
    dist_seed = int(_arg_value("--seed", "7"))
    trace_out = _arg_value("--trace-out", "tpu_dist_trace.json")
    summary = measure_dist(dist_rows, dist_parts, dist_seed,
                           trace_out=trace_out)
    dg = measure_dist_digest_overhead(dist_rows, dist_parts, dist_seed)
    summary["dist_digest_overhead_pct"] = dg["pct"]
    summary["dist_digest_verified_blocks"] = dg["verified_blocks"]
    summary["failures"].extend(dg["failures"])
    if dg["pct"] > 2.0:
        summary["failures"].append(
            f"content-addressing overhead {dg['pct']:.2f}% > 2% "
            f"of digest-off fetch wall time")
    if "--trace-overhead" in argv:
        pct = measure_dist_trace_overhead(dist_rows, dist_parts,
                                          dist_seed)
        summary["dist_trace_overhead_pct"] = round(pct, 2)
        if pct > 5.0:
            summary["failures"].append(
                f"distributed tracing overhead {pct:.2f}% > 5% of "
                f"untraced fetch wall time")
    print(json.dumps(summary))
    for msg in summary["failures"]:
        print(f"DIST GUARD FAILED: {msg}", file=sys.stderr)
    sys.exit(1 if summary["failures"] else 0)


def run_serve(argv) -> None:
    """Internal phase (--phase=serve): sustained-QPS mix under the
    session pool + byte admission gate, instead of the single-tenant
    suite.  Smaller default row count: the measurement is throughput
    under concurrency, not per-query scan speed."""
    require_tpu()
    pos = _positional(argv)
    serve_rows = int(pos[0]) if pos else 200_000
    concurrency = int(_arg_value("--concurrency", "8"))
    request_io_ms = float(_arg_value("--request-io-ms", "150"))
    deadline_ms = _arg_value("--deadline-ms")
    with_record = "--record" in argv
    with_check = "--check" in argv
    wall_threshold = _arg_value("--wall-threshold")
    wall_threshold = float(wall_threshold) if wall_threshold else None
    fact, dim = make_tables(serve_rows)
    root = tempfile.mkdtemp(prefix="spark_rapids_tpu_serve_")
    try:
        pq_path = write_parquet_input(fact, root)
        serve = measure_serve(fact, dim, pq_path,
                              concurrency=concurrency,
                              request_io_ms=request_io_ms)
        if deadline_ms is not None:
            serve["cancellations"] = measure_serve_deadlines(
                fact, dim, pq_path, concurrency=concurrency,
                deadline_ms=int(deadline_ms))
    finally:
        shutil.rmtree(root, ignore_errors=True)
    out = {
        "metric": "serve_sustained_qps",
        "value": serve["concurrent_qps"],
        "unit": "queries/s",
        "vs_baseline": serve["qps_speedup"],
        "device": device_facts(),
        "serve": serve,
    }
    print(json.dumps(out))
    regress_rc = 0
    if with_record or with_check:
        serve_hist = _arg_value("--history", "tpu_bench_serve_history")
        regress_rc = record_serve_history(
            serve_hist, serve, with_check, wall_threshold)
    failed = False
    if serve["qps_speedup"] <= 1.0:
        print(f"SERVE QPS GUARD FAILED: concurrent "
              f"{serve['concurrent_qps']} qps <= serial "
              f"{serve['serial_qps']} qps", file=sys.stderr)
        failed = True
    if serve.get("slo", {}).get("overhead_pct", 0.0) >= 5.0:
        print(f"SERVE OBSERVATORY OVERHEAD GUARD FAILED: "
              f"critical-path extraction cost "
              f"{serve['slo']['overhead_pct']:.2f}% of query wall "
              f"(>= 5%)", file=sys.stderr)
        failed = True
    if serve["dirty_ledgers"]:
        print(f"SERVE MEMSAN GUARD FAILED: "
              f"{serve['dirty_ledgers']} dirty ledger(s)",
              file=sys.stderr)
        failed = True
    if serve["accounting_drift"]:
        print(f"SERVE ADMISSION GUARD FAILED: accounting drift "
              f"{serve['accounting_drift']} (admitted != completed "
              f"+ failed)", file=sys.stderr)
        failed = True
    for msg in serve.get("cancellations", {}).get("failures", []):
        print(f"SERVE DEADLINE GUARD FAILED: {msg}", file=sys.stderr)
        failed = True
    sys.exit(1 if failed or regress_rc else 0)


def run_suite(argv) -> None:
    """Internal phase (--phase=suite): the seven-query suite on both
    engines, the overhead guards and big_join, all in THIS process,
    which holds the chip.  Prints the result line the parent extends."""
    device = require_tpu()
    hbm_peak = hbm_bytes_per_s(device.device_kind)
    pos = _positional(argv)
    n_rows = int(pos[0]) if pos else 1_000_000
    with_pyspark = "--baseline=pyspark" in argv
    with_trace_guard = "--trace-overhead" in argv
    with_metrics_guard = "--metrics-overhead" in argv
    with_hbm_guard = "--hbm-overhead" in argv
    hbm_trace_out = _arg_value("--trace-out")
    with_record = "--record" in argv
    with_check = "--check" in argv
    with_big_join = "--skip-big-join" not in argv
    history_dir = _arg_value("--history", "tpu_bench_history")
    wall_threshold = _arg_value("--wall-threshold")
    wall_threshold = float(wall_threshold) if wall_threshold else None
    fact, dim = make_tables(n_rows)
    root = tempfile.mkdtemp(prefix="spark_rapids_tpu_bench_")
    eventlog_dir = None
    if with_record or with_check:
        eventlog_dir = os.path.join(root, "eventlog")
        os.makedirs(eventlog_dir, exist_ok=True)
    spark_cpu = None
    trace_overhead = None
    metrics_overhead = None
    hbm_overhead = None
    regress_rc = 0
    try:
        pq_path = write_parquet_input(fact, root)
        tpu, tpu_compile = time_engine(True, fact, dim, pq_path, root,
                                       eventlog_dir=eventlog_dir)
        cpu, _ = time_engine(False, fact, dim, pq_path, root)
        if with_pyspark:
            spark_cpu = time_pyspark(fact, dim, pq_path, root)
        if with_trace_guard:
            trace_overhead = measure_trace_overhead(fact, dim, pq_path,
                                                    root)
        if with_metrics_guard:
            metrics_overhead = measure_metrics_overhead(
                fact, dim, pq_path, root)
        if with_hbm_guard:
            hbm_overhead = measure_hbm_overhead(
                fact, dim, pq_path, root, trace_out=hbm_trace_out)
        if with_record or with_check:
            regress_rc = record_history(history_dir, eventlog_dir,
                                        with_check, wall_threshold)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    tpu_total = sum(tpu.values())
    cpu_total = sum(cpu.values())
    # rows processed: each query consumes the fact table once
    value = (len(tpu) * n_rows) / tpu_total
    in_bytes = fact.nbytes
    detail = {}
    for k in tpu:
        bps = in_bytes / tpu[k]
        detail[k] = {"tpu_s": round(tpu[k], 3),
                     "cpu_s": round(cpu[k], 3),
                     "compile_s": round(tpu_compile[k], 1),
                     "mb_per_s": round(bps / 1e6, 1),
                     "hbm_pct": round(100.0 * bps / hbm_peak, 4)}
    out = {
        "metric": "sql_suite_rows_per_sec",
        "value": round(value, 1),
        "unit": "rows/s",
        "vs_baseline": round(cpu_total / tpu_total, 3),
        "device": device_facts(),
        "detail": detail,
    }
    if with_big_join:
        # once, not in the repeated suite loop: the measurement IS a
        # full 256 MB+ build side through the spill-backed catalog
        out["big_join"] = measure_big_join()
    if with_pyspark:
        if spark_cpu is None:
            out["vs_spark_cpu"] = None   # pyspark not importable here
        else:
            out["vs_spark_cpu"] = round(
                sum(spark_cpu.values()) / tpu_total, 3)
            for k in detail:
                detail[k]["spark_cpu_s"] = round(spark_cpu[k], 3)
    if trace_overhead is not None:
        out["trace_overhead_pct"] = round(trace_overhead, 2)
    if metrics_overhead is not None:
        out["metrics_overhead_pct"] = round(metrics_overhead, 2)
    if hbm_overhead is not None:
        out["hbm_overhead_pct"] = round(hbm_overhead, 2)
    print(json.dumps(out))
    if trace_overhead is not None and trace_overhead > 5.0:
        print(f"TRACE OVERHEAD GUARD FAILED: {trace_overhead:.2f}% > 5%",
              file=sys.stderr)
        sys.exit(1)
    if metrics_overhead is not None and metrics_overhead > 2.0:
        print(f"METRICS OVERHEAD GUARD FAILED: "
              f"{metrics_overhead:.2f}% > 2%", file=sys.stderr)
        sys.exit(1)
    if hbm_overhead is not None and hbm_overhead > 5.0:
        print(f"HBM OVERHEAD GUARD FAILED: {hbm_overhead:.2f}% > 5%",
              file=sys.stderr)
        sys.exit(1)
    if regress_rc:
        sys.exit(regress_rc)


def _last_json_line(out: str) -> dict:
    for line in reversed(out.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"bench: phase printed no result line:\n{out}")


def main():
    argv = sys.argv[1:]
    pos = _positional(argv)
    phase = _arg_value("--phase")
    if phase == "suite":
        return run_suite(argv)
    if phase == "serve":
        return run_serve(argv)
    if phase == "cold":
        return run_cold_probe(int(pos[0]))
    if phase == "one-suite":
        return run_one_suite(_arg_value("--suite"), int(pos[0]),
                             _arg_value("--ledger-dir", ""),
                             _arg_value("--accuracy-history", ""),
                             "--with-feedback" in argv)
    if phase is not None:
        sys.exit(f"bench: unknown phase {phase!r}")
    if "--dist" in argv:
        return run_dist(argv)
    # From here on this process is the parent that stays off JAX: every
    # phase below needs the chip and runs in a child of its own, one
    # after another.  The first child fails when it finds no TPU.
    if "--serve" in argv:
        rc, out = _run_phase(["--phase=serve", *argv], check=False)
        sys.stdout.write(out)
        sys.exit(rc)
    n_rows = int(pos[0]) if pos else 1_000_000
    rc, suite_out = _run_phase(["--phase=suite", *argv], check=False)
    if rc != 0 and not any(line.startswith("{")
                           for line in suite_out.splitlines()):
        sys.stdout.write(suite_out)
        sys.exit(rc)
    out = _last_json_line(suite_out)
    out["cache_cold_compile_s"] = round(measure_cache_cold(n_rows), 2)
    if "--compile-report" in argv:
        # the observatory's measured cold/warm split replaces the
        # lumped first-run-minus-warm guess
        for k, row in measure_compile_report(n_rows).items():
            del out["detail"][k]["compile_s"]
            out["detail"][k].update(row)
    if "--accuracy" in argv:
        for k, row in measure_accuracy(n_rows).items():
            out["detail"][k].update(row)
    print(json.dumps(out))
    sys.exit(rc)


if __name__ == "__main__":
    main()
