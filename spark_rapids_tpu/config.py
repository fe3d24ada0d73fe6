"""Typed configuration system.

TPU-native analog of the reference's config machinery
(ref: sql-plugin/.../RapidsConf.scala:116-296 builder machinery,
:301-1275 key definitions).  Every entry is typed, documented, validated,
and defaulted; `generate_docs()` renders docs/configs.md from the registry,
exactly as the reference generates its docs from code.

Keys keep the `spark.rapids.` prefix so existing reference configuration
carries over; TPU-specific keys live under `spark.rapids.tpu.` / `.memory.tpu.`.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Generic, List, Optional, Sequence, TypeVar

V = TypeVar("V")

_REGISTERED: Dict[str, "ConfEntry"] = {}


def _to_bool(s: Any) -> bool:
    if isinstance(s, bool):
        return s
    s = str(s).strip().lower()
    if s in ("true", "1", "yes"):
        return True
    if s in ("false", "0", "no"):
        return False
    raise ValueError(f"cannot convert {s!r} to bool")


def _to_bytes(s: Any) -> int:
    """Parse a byte size like '512m', '1g', '16384'."""
    if isinstance(s, int):
        return s
    s = str(s).strip().lower()
    units = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40, "b": 1}
    if s and s[-1] in units:
        return int(float(s[:-1]) * units[s[-1]])
    return int(s)


class ConfEntry(Generic[V]):
    """One typed config key (ref RapidsConf.scala:116 `ConfEntry`)."""

    def __init__(self, key: str, converter: Callable[[Any], V], doc: str,
                 default: Optional[V], is_internal: bool = False,
                 validator: Optional[Callable[[V], Optional[str]]] = None):
        self.key = key
        self.converter = converter
        self.doc = doc
        self.default = default
        self.is_internal = is_internal
        self.validator = validator
        if key in _REGISTERED:
            raise ValueError(f"duplicate conf key {key}")
        _REGISTERED[key] = self

    def get(self, conf: Dict[str, Any]) -> V:
        raw = conf.get(self.key, None)
        if raw is None:
            return self.default  # type: ignore[return-value]
        v = self.converter(raw)
        if self.validator is not None:
            err = self.validator(v)
            if err:
                raise ValueError(f"{self.key}: {err}")
        return v

    def help(self) -> str:
        return f"{self.key} (default={self.default}): {self.doc}"


class ConfBuilder(Generic[V]):
    """Fluent builder (ref RapidsConf.scala:153 `TypedConfBuilder`)."""

    def __init__(self, key: str, converter: Callable[[Any], V]):
        self._key = key
        self._converter = converter
        self._doc = ""
        self._internal = False
        self._validator: Optional[Callable[[V], Optional[str]]] = None

    def doc(self, text: str) -> "ConfBuilder[V]":
        self._doc = " ".join(text.split())
        return self

    def internal(self) -> "ConfBuilder[V]":
        self._internal = True
        return self

    def check_values(self, allowed: Sequence[V]) -> "ConfBuilder[V]":
        allowed = list(allowed)

        def v(x):
            return None if x in allowed else f"must be one of {allowed}, got {x}"
        self._validator = v
        return self

    def check(self, fn: Callable[[V], bool], msg: str) -> "ConfBuilder[V]":
        def v(x):
            return None if fn(x) else msg
        self._validator = v
        return self

    def create_with_default(self, default: V) -> ConfEntry[V]:
        return ConfEntry(self._key, self._converter, self._doc, default,
                         self._internal, self._validator)

    def create_optional(self) -> ConfEntry[Optional[V]]:
        return ConfEntry(self._key, self._converter, self._doc, None,
                         self._internal, self._validator)


def conf(key: str) -> "_Typed":
    return _Typed(key)


class _Typed:
    def __init__(self, key: str):
        self.key = key

    def boolean(self) -> ConfBuilder[bool]:
        return ConfBuilder(self.key, _to_bool)

    def integer(self) -> ConfBuilder[int]:
        return ConfBuilder(self.key, int)

    def double(self) -> ConfBuilder[float]:
        return ConfBuilder(self.key, float)

    def string(self) -> ConfBuilder[str]:
        return ConfBuilder(self.key, str)

    def bytes(self) -> ConfBuilder[int]:
        return ConfBuilder(self.key, _to_bytes)


# ---------------------------------------------------------------------------
# Key definitions (subset mirrors RapidsConf.scala:301-1275; grows with features)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("spark.rapids.sql.enabled").boolean() \
    .doc("Enable or disable TPU acceleration of SQL plans entirely.") \
    .create_with_default(True)

BACKEND = conf("spark.rapids.backend").string() \
    .doc("Accelerator backend. This framework provides 'tpu'.") \
    .check_values(["tpu", "cpu"]) \
    .create_with_default("tpu")

EXPLAIN = conf("spark.rapids.sql.explain").string() \
    .doc("Explain why parts of a query were or were not placed on the TPU: "
         "NONE, ALL, or NOT_ON_GPU (only report operators that stayed on CPU).") \
    .check_values(["NONE", "ALL", "NOT_ON_GPU"]) \
    .create_with_default("NOT_ON_GPU")

INCOMPATIBLE_OPS = conf("spark.rapids.sql.incompatibleOps.enabled").boolean() \
    .doc("Enable operators that produce results that differ from Spark in "
         "corner cases (e.g. float ordering of NaN, string upper/lower beyond "
         "ASCII).") \
    .create_with_default(False)

ANSI_ENABLED = conf("spark.rapids.sql.ansi.enabled").boolean() \
    .doc("ANSI-mode overflow/invalid-cast error semantics.") \
    .create_with_default(False)

BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes").bytes() \
    .doc("Target size in bytes of output batches for TPU operators "
         "(ref RapidsConf.scala:437 GPU_BATCH_SIZE_BYTES).") \
    .check(lambda v: v > 0, "must be positive") \
    .create_with_default(512 * 1024 * 1024)

MAX_READER_BATCH_SIZE_ROWS = conf("spark.rapids.sql.reader.batchSizeRows").integer() \
    .doc("Soft cap on rows per batch produced by file readers.") \
    .create_with_default(2147483647)

MAX_READER_BATCH_SIZE_BYTES = conf("spark.rapids.sql.reader.batchSizeBytes").bytes() \
    .doc("Soft cap on bytes per batch produced by file readers.") \
    .create_with_default(2147483647)

DECIMAL_TYPE_ENABLED = conf("spark.rapids.sql.decimalType.enabled").boolean() \
    .doc("Enable decimal type acceleration (int64-backed fixed point; "
         "ref RapidsConf.scala:565).") \
    .create_with_default(True)

REPLACE_SORT_MERGE_JOIN = conf("spark.rapids.sql.replaceSortMergeJoin.enabled").boolean() \
    .doc("Replace sort-merge joins with TPU hash joins "
         "(ref RapidsConf.scala:572).") \
    .create_with_default(True)

AUTO_BROADCAST_JOIN_THRESHOLD = conf(
    "spark.rapids.sql.autoBroadcastJoinThreshold").bytes() \
    .doc("Broadcast the build side of a join when its estimated size is at "
         "most this many bytes (mirrors spark.sql.autoBroadcastJoinThreshold; "
         "-1 disables broadcast joins).") \
    .create_with_default(10 * 1024 * 1024)

STABLE_SORT = conf("spark.rapids.sql.stableSort.enabled").boolean() \
    .doc("Force stable sort (ref RapidsConf.scala:478).") \
    .create_with_default(False)

HAS_NANS = conf("spark.rapids.sql.hasNans").boolean() \
    .doc("Assume floating point data may contain NaN (affects agg/join on floats).") \
    .create_with_default(True)

VARIABLE_FLOAT_AGG = conf("spark.rapids.sql.variableFloatAgg.enabled").boolean() \
    .doc("Allow float/double aggregations whose result can vary with "
         "evaluation order (TPU parallel reductions reorder).") \
    .create_with_default(True)

CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks").integer() \
    .doc("Number of concurrent tasks admitted to the TPU per executor "
         "(ref RapidsConf.scala:424; name kept for compatibility).") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(2)

# --- memory ---------------------------------------------------------------

HBM_POOL_FRACTION = conf("spark.rapids.memory.tpu.allocFraction").double() \
    .doc("Fraction of HBM to reserve for the framework's arena at startup.") \
    .check(lambda v: 0.0 < v <= 1.0, "must be in (0,1]") \
    .create_with_default(0.9)

HBM_RESERVE = conf("spark.rapids.memory.tpu.reserve").bytes() \
    .doc("Bytes of HBM left un-pooled for XLA scratch space.") \
    .create_with_default(1 << 30)

HBM_LIMIT_OVERRIDE = conf("spark.rapids.memory.tpu.limitBytes").bytes() \
    .doc("Explicit HBM capacity override for hosts whose PJRT runtime "
         "does not report memory_stats().  When unset, capacity comes "
         "from memory_stats, then a device-kind table, then (CPU backend "
         "only) host RAM; an unrecognized accelerator with no stats "
         "fails startup rather than guessing.") \
    .create_optional()

HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.memory.host.spillStorageSize").bytes() \
    .doc("Host-memory spill tier capacity before overflow to disk.") \
    .create_with_default(1 << 30)

PINNED_POOL_SIZE = conf("spark.rapids.memory.pinnedPool.size").bytes() \
    .doc("Size of the native host staging arena used for device transfers.") \
    .create_with_default(0)

SPILL_DIRS = conf("spark.rapids.memory.spill.dirs").string() \
    .doc("Comma-separated local dirs for the DISK spill tier.") \
    .create_with_default("/tmp/spark_rapids_tpu_spill")

SPILL_DEVICE_BUDGET = conf("spark.rapids.memory.tpu.spillBudgetBytes").bytes() \
    .doc("Override the registered-batch device budget that triggers "
         "proactive spill (default: the HBM arena size).").internal() \
    .create_optional()

MEMORY_DEBUG = conf("spark.rapids.memory.tpu.debug").boolean() \
    .doc("Track the creation stack of every registered spillable buffer "
         "and fail queries that leak unclosed buffers (ref "
         "spark.rapids.memory.gpu.debug RapidsConf.scala:307 + the "
         "Arm.scala RAII discipline).  Diagnostics only.") \
    .create_with_default(False)

UNSPILL_ENABLED = conf("spark.rapids.memory.tpu.unspill.enabled").boolean() \
    .doc("Move spilled buffers back to device memory when touched again.") \
    .create_with_default(False)

# --- shuffle --------------------------------------------------------------

SHUFFLE_MANAGER_ENABLED = conf("spark.rapids.shuffle.enabled").boolean() \
    .doc("Use the accelerated shuffle that caches batches in device/host "
         "memory and exchanges over ICI/DCN instead of row serialization.") \
    .create_with_default(True)

SHUFFLE_TRANSPORT = conf("spark.rapids.shuffle.transport").string() \
    .doc("Accelerated shuffle transport: 'ici' (mesh collectives inside a "
         "pod slice), 'tcp' (host sockets across pods), 'none' (serialized "
         "base shuffle).  Opt-in like the reference's RapidsShuffleManager "
         "(rapids-shuffle.md setup).") \
    .check_values(["ici", "tcp", "none"]) \
    .create_with_default("none")

SINGLE_CHIP_FUSE = conf("spark.rapids.tpu.singleChipFuse").string() \
    .doc("Collapse multi-partition exchange stages into one fused program "
         "when the process drives a single chip: partial->exchange->final "
         "aggregates, co-partitioned shuffled joins, range-partitioned "
         "global sorts and hash-partitioned windows all absorb their "
         "exchanges (an N-partition exchange otherwise runs N per-"
         "partition programs SERIALLY on one chip, paying N program "
         "floors for parallelism that does not exist).  'auto' = when "
         "exactly one device is visible; 'on' / 'off' force it.  The "
         "multi-chip analog is the ICI transport "
         "(spark.rapids.shuffle.transport=ici).") \
    .check_values(["auto", "on", "off"]) \
    .create_with_default("auto")

HOST_ASSISTED_COLLECT = conf(
    "spark.rapids.sql.collect.hostAssisted").boolean() \
    .doc("When a collect's plan is a global sort (over optional filters/"
         "column pruning) of a host-resident in-memory table, fetch only "
         "the device-computed row-index lane and apply `take` on the "
         "host copy — a permutation's bytes already sit on the host, so "
         "only ~4 bytes/row cross the interconnect instead of the whole "
         "row.  Results below 64Ki rows keep the direct fetch path.") \
    .create_with_default(True)

HOST_ASSISTED_WRITE = conf("spark.rapids.sql.write.hostAssisted").boolean() \
    .doc("When a write's plan is only row filtering/column pruning over a "
         "source whose bytes already live on the host (in-memory tables, "
         "file scans), fetch just the boolean keep-mask from the device "
         "(bit-packed) and apply it to the host copy, instead of pulling "
         "the full filtered payload back across the interconnect.") \
    .create_with_default(True)

PYTHON_WORKER_ENABLED = conf("spark.rapids.sql.python.worker.enabled").boolean() \
    .doc("Run Python/pandas UDFs in out-of-process Arrow-IPC workers "
         "(crash containment + no GIL/heap contention with the engine, "
         "ref GpuArrowEvalPythonExec + python/rapids/worker.py).  UDFs "
         "that cannot be pickled fall back to in-process evaluation.") \
    .create_with_default(True)

CONCURRENT_PYTHON_WORKERS = conf(
    "spark.rapids.python.concurrentPythonWorkers").integer() \
    .doc("Maximum live Python UDF worker processes "
         "(ref PythonWorkerSemaphore).") \
    .create_with_default(2)

SCAN_PIN_DEVICE = conf("spark.rapids.sql.localScan.pinDeviceBatches").boolean() \
    .doc("Keep uploaded device batches of in-memory scans pinned in HBM "
         "across collects, so repeated queries over the same DataFrame "
         "never re-upload (the analog of the reference's caching shuffle "
         "writer keeping batches device-resident).") \
    .create_with_default(True)

FILESCAN_PIN_DEVICE = conf("spark.rapids.sql.fileScan.pinDeviceBatches") \
    .boolean() \
    .doc("Keep decoded+uploaded file-scan batches pinned in HBM keyed by "
         "(path, size, mtime, schema, filters, decode options); a "
         "changed file changes "
         "the key.  Evicted first under memory pressure.") \
    .create_with_default(True)

SHUFFLE_COMPRESSION_CODEC = conf("spark.rapids.shuffle.compression.codec").string() \
    .doc("Codec for shuffle payloads: none, lz4, zstd (native codec library).") \
    .check_values(["none", "lz4", "zstd"]) \
    .create_with_default("none")

SHUFFLE_PARTITIONING_MAX_PARTS = conf(
    "spark.rapids.shuffle.partitioning.maxCpuBatchedParts").integer() \
    .doc("Above this partition count, slicing happens on host not device.") \
    .create_with_default(32768)

SHUFFLE_HEARTBEAT_INTERVAL_MS = conf("spark.rapids.shuffle.heartbeat.intervalMs").integer() \
    .doc("Executor->driver shuffle heartbeat interval "
         "(ref RapidsShuffleHeartbeatManager).") \
    .create_with_default(5000)

SHUFFLE_HEARTBEAT_TIMEOUT_MS = conf("spark.rapids.shuffle.heartbeat.timeoutMs").integer() \
    .doc("Peer considered dead after missing heartbeats for this long.") \
    .create_with_default(30000)

SHUFFLE_FETCH_MAX_IN_FLIGHT = conf(
    "spark.rapids.tpu.shuffle.fetch.maxInFlight").integer() \
    .doc("Bounded in-flight window of the async block fetcher: how many "
         "fetched-but-unconsumed blocks may be buffered while the "
         "consumer joins the previous block (fetch/compute overlap, "
         "ref BufferReceiveState windows).  Bounds reduce-side host "
         "memory at window x block size.") \
    .create_with_default(4)

SHUFFLE_FETCH_TIMEOUT_MS = conf(
    "spark.rapids.tpu.shuffle.fetch.timeoutMs").integer() \
    .doc("Per-block timeout of the async fetcher.  Liveness normally "
         "fails faster via heartbeat expiry "
         "(spark.rapids.shuffle.heartbeat.timeoutMs); this is the "
         "backstop for a live-but-stuck peer.") \
    .create_with_default(30000)

SHUFFLE_SLICE_VIEWS = conf(
    "spark.rapids.tpu.shuffle.sliceViews").boolean() \
    .doc("Map-output slicing strategy.  On: each map batch is sorted by "
         "target partition once and registered as ONE spillable block; "
         "per-reduce-partition blocks are row-range views sliced lazily "
         "at first read — the write path stages each batch's bytes once "
         "instead of once per reduce partition.  Off: eager per-"
         "partition gather copies at write time (the pre-slice-view "
         "behavior).") \
    .create_with_default(True)

SHUFFLE_SERVER_ENABLED = conf(
    "spark.rapids.tpu.shuffle.server.enabled").boolean() \
    .doc("Start the shuffle block-server endpoint at executor plugin "
         "init, next to the health HTTP server, so peers can fetch this "
         "process's catalog blocks over TCP.  Implied by "
         "spark.rapids.shuffle.transport=tcp; set explicitly to serve "
         "blocks while keeping another transport for writes.") \
    .create_with_default(False)

SHUFFLE_SERVER_PORT = conf(
    "spark.rapids.tpu.shuffle.server.port").integer() \
    .doc("TCP port of the shuffle block server (0 = ephemeral; the "
         "bound port is what heartbeat registration advertises to "
         "peers).") \
    .create_with_default(0)

SHUFFLE_LOCALITY_ENABLED = conf(
    "spark.rapids.tpu.shuffle.locality.enabled").boolean() \
    .doc("Consult the BlockLocationRegistry on reduce-side reads: "
         "blocks owned by this process stay zero-copy catalog reads "
         "(never crossing the wire), blocks registered to remote "
         "endpoints stream through the async fetcher.  Off: reads "
         "serve only the local catalog (the pre-registry behavior).") \
    .create_with_default(True)

SHUFFLE_FETCH_MAX_RETRIES = conf(
    "spark.rapids.tpu.shuffle.fetch.maxRetries").integer() \
    .doc("Additional fetch attempts after the first failure of a "
         "remote reduce-side read, each against the next live replica "
         "of the owning endpoint group (heartbeat liveness picks the "
         "candidates).  Exhausting the budget fails the stage with a "
         "typed error carrying provenance — never a silent hang.") \
    .create_with_default(2)

# --- io -------------------------------------------------------------------

PARQUET_ENABLED = conf("spark.rapids.sql.format.parquet.enabled").boolean() \
    .doc("Enable TPU parquet scan/write.").create_with_default(True)

PARQUET_READER_TYPE = conf("spark.rapids.sql.format.parquet.reader.type").string() \
    .doc("PERFILE, COALESCING, or MULTITHREADED (ref RapidsConf.scala:706).") \
    .check_values(["PERFILE", "COALESCING", "MULTITHREADED", "AUTO"]) \
    .create_with_default("AUTO")

PARQUET_MULTITHREAD_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads").integer() \
    .doc("Thread pool size for the MULTITHREADED cloud reader.") \
    .create_with_default(20)

ORC_ENABLED = conf("spark.rapids.sql.format.orc.enabled").boolean() \
    .doc("Enable TPU ORC scan/write.").create_with_default(True)

CSV_ENABLED = conf("spark.rapids.sql.format.csv.enabled").boolean() \
    .doc("Enable TPU CSV scan.").create_with_default(True)

# --- udf ------------------------------------------------------------------

UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled").boolean() \
    .doc("Compile Python lambda UDFs to the expression IR via bytecode "
         "analysis (ref RapidsConf.scala:520).") \
    .create_with_default(False)

ARROW_MAX_RECORDS_PER_BATCH = \
    conf("spark.rapids.sql.python.arrowMaxRecordsPerBatch").integer() \
    .doc("Max rows handed to a Python/pandas UDF at once (ref "
         "GpuArrowEvalPythonExec rebatching / Spark "
         "spark.sql.execution.arrow.maxRecordsPerBatch).") \
    .check(lambda v: v > 0, "must be positive") \
    .create_with_default(10000)

# --- adaptive execution ---------------------------------------------------

ADAPTIVE_ENABLED = conf("spark.sql.adaptive.enabled").boolean() \
    .doc("Adaptive query execution: re-shape shuffle reads from "
         "materialized map-output statistics (coalesce small partitions, "
         "split skewed ones; ref GpuCustomShuffleReaderExec).") \
    .create_with_default(True)

ADVISORY_PARTITION_SIZE = conf(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes").bytes() \
    .doc("Target size for coalesced shuffle partitions.") \
    .create_with_default(64 << 20)

SKEW_JOIN_ENABLED = conf("spark.sql.adaptive.skewJoin.enabled").boolean() \
    .doc("Split skewed probe-side join partitions and replicate the build "
         "side (ref OptimizeSkewedJoin).") \
    .create_with_default(True)

SKEW_JOIN_FACTOR = conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor").double() \
    .doc("A partition is skewed when larger than this factor times the "
         "median partition size (and the threshold below).") \
    .create_with_default(5.0)

SKEW_JOIN_THRESHOLD = conf(
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes").bytes() \
    .doc("Minimum size for a partition to be considered skewed.") \
    .create_with_default(256 << 20)

# --- optimizer ------------------------------------------------------------

OPTIMIZER_ENABLED = conf("spark.rapids.sql.optimizer.enabled").boolean() \
    .doc("Enable the cost-based second pass that can move subtrees back to "
         "CPU (ref CostBasedOptimizer.scala).") \
    .create_with_default(False)

OPTIMIZER_EXPLAIN = conf("spark.rapids.sql.optimizer.explain").string() \
    .doc("NONE or ALL: log CBO decisions.") \
    .check_values(["NONE", "ALL"]).create_with_default("NONE")

# --- metrics / test hooks -------------------------------------------------

COMPILATION_CACHE_ENABLED = conf(
    "spark.rapids.tpu.compilationCache.enabled").boolean() \
    .doc("Persist XLA executables across queries and sessions so "
         "re-planned queries skip compilation.  The directory is "
         "JAX_COMPILATION_CACHE_DIR where that variable is set, and "
         "<checkout>/.jax_cache otherwise; disk hits and misses are "
         "counted as tpu_jit_persistent_cache_{hits,misses}_total.") \
    .create_with_default(True)

JIT_THRASH_WARN_RATIO = conf("spark.rapids.tpu.jit.cacheThrashWarnRatio") \
    .double() \
    .doc("Warn when the process JIT cache thrashes: refault rate "
         "(eviction_refault rebuilds / LRU evictions) above this ratio "
         "logs a warning suggesting a larger "
         "SPARK_RAPIDS_TPU_JIT_CACHE_MAX.") \
    .check(lambda v: 0.0 < v <= 1.0, "must be in (0,1]") \
    .create_with_default(0.5)

COMPILE_OBSERVATORY_ENABLED = conf(
    "spark.rapids.tpu.compile.observatory.enabled").boolean() \
    .doc("Attribute, classify and persist every XLA program build at "
         "the process_jit seam (obs/compileprof.py): split trace-vs-"
         "compile timing, miss-cause classification (new_program / "
         "shape_churn / dtype_churn / eviction_refault), the "
         "tpu_jit_* metrics family, enriched jit.build spans and the "
         "cross-session compile ledger `tools compile-report` reads.") \
    .create_with_default(True)

COMPILE_LEDGER_DIR = conf("spark.rapids.tpu.compile.ledgerDir") \
    .string() \
    .doc("Directory for the cross-session compile ledger "
         "(compile_ledger.jsonl, appended by the compile observatory "
         "and aggregated by `tools compile-report`).  Defaults to "
         "spark.rapids.tpu.regress.historyDir when that is set; unset "
         "both and builds are still traced/metered but not persisted.") \
    .create_optional()

JIT_PREWARM_ENABLED = conf("spark.rapids.tpu.jit.prewarm.enabled") \
    .boolean() \
    .doc("Replay the costliest program recipes from the compile ledger "
         "at session init (the warm-start tier of the program cache): "
         "each recipe recompiles through the persistent disk cache and "
         "stages a dispatch-ready program, so repeated sessions run "
         "their first queries with zero query-time builds.  Requires a "
         "compile ledger dir; recipes live under its programs/ "
         "subdirectory.  tpu_jit_prewarm_{hits,seconds}_total measure "
         "the payoff.") \
    .create_with_default(True)

JIT_PREWARM_TOP_K = conf("spark.rapids.tpu.jit.prewarm.topK").integer() \
    .doc("How many ledger programs (ranked by cumulative compile "
         "seconds) to replay at session init.") \
    .check(lambda v: v >= 0, "must be >= 0") \
    .create_with_default(32)

JIT_PREWARM_BACKGROUND = conf(
    "spark.rapids.tpu.jit.prewarm.background").boolean() \
    .doc("Run the session-init prewarm on a daemon thread instead of "
         "blocking startup.  Queries racing the thread simply "
         "cold-build; the default is synchronous so a freshly opened "
         "session is deterministically warm.") \
    .create_with_default(False)

PROFILE_TRACE_ANNOTATIONS = conf(
    "spark.rapids.sql.profile.traceAnnotations").boolean() \
    .doc("Put the engine's spans on the profiler's clock: one "
         "jax.profiler TraceAnnotation range per query (query:q<n>), "
         "session phase (phase:*), admission wait, operator pull "
         "(<Exec>.pull), timed operator block (<Exec>.<metric>, e.g. "
         "FilterExec.opTime), program dispatch and build "
         "(jit.dispatch:<kind>, jit.build:<kind>), device-to-host fetch "
         "(fetch.crossing), upload (scan.upload) and every other "
         "obs/tracer span, so a jax.profiler trace shows them beside "
         "the device's operations (the NVTX-range analog, ref "
         "NvtxWithMetrics).  Host-side only: the programs are the "
         "same.  The flight recorder (spark.rapids.tpu.trace.enabled) "
         "records the same spans on the host clock.") \
    .create_with_default(False)

METRICS_LEVEL = conf("spark.rapids.sql.metrics.level").string() \
    .doc("ESSENTIAL, MODERATE, or DEBUG (ref GpuExec.scala:32-45).") \
    .check_values(["ESSENTIAL", "MODERATE", "DEBUG"]) \
    .create_with_default("MODERATE")

TEST_ENABLED = conf("spark.rapids.sql.test.enabled").boolean() \
    .doc("Test mode: fail if an op unexpectedly stays on CPU "
         "(ref RapidsConf.scala:937).").internal() \
    .create_with_default(False)

TEST_ALLOWED_NON_TPU = conf("spark.rapids.sql.test.allowedNonGpu").string() \
    .doc("Comma-separated exec names allowed on CPU in test mode.").internal() \
    .create_with_default("")

# --- tpu platform ---------------------------------------------------------

TPU_BATCH_CAPACITY_BUCKETS = conf("spark.rapids.tpu.batchCapacityBuckets").string() \
    .doc("Comma-separated row-capacity buckets batches are padded to so XLA "
         "compiles once per (schema, bucket) instead of once per row count.") \
    .create_with_default("1024,8192,65536,262144,1048576,4194304")

TPU_STRING_DATA_BUCKETS = conf("spark.rapids.tpu.stringDataBuckets").string() \
    .doc("Byte-capacity buckets for the string data buffer.") \
    .create_with_default("16384,131072,1048576,8388608,67108864,268435456")

# --- static analysis (tpulint) --------------------------------------------

LINT_ENABLED = conf("spark.rapids.tpu.lint.enabled").boolean() \
    .doc("Opt-in pre-flight plan lint: before execution the converted "
         "plan is checked against the TPU-Lxxx rule catalog "
         "(docs/static-analysis.md) and hazardous subtrees are "
         "downgraded to the host engine instead of crashing mid-query.") \
    .create_with_default(False)

LINT_INFER = conf("spark.rapids.tpu.lint.infer").boolean() \
    .doc("Run the plan lint in flow-sensitive mode: the abstract "
         "interpreter (analysis/interp.py) propagates schema/residency/"
         "partitioning/size states through the plan, upgrading "
         "TPU-L002/L006/L007 from syntactic to flow-sensitive and "
         "adding the boundary rules TPU-L009..L012.  A failed "
         "interpretation degrades to the syntactic rules.") \
    .create_with_default(True)

LINT_DISABLE = conf("spark.rapids.tpu.lint.disable").string() \
    .doc("Comma-separated diagnostic codes (e.g. TPU-L005) to suppress "
         "in the plan lint.") \
    .create_with_default("")

LINT_MAX_DRIVER_COLLECT = conf(
    "spark.rapids.tpu.lint.maxDriverCollectBytes").bytes() \
    .doc("Plan lint threshold (TPU-L004): a broadcast/build side whose "
         "estimated size exceeds this is flagged as a driver-side "
         "whole-build collect hazard.") \
    .check(lambda v: v > 0, "must be positive") \
    .create_with_default(512 * 1024 * 1024)

LINT_MAX_PROGRAMS = conf(
    "spark.rapids.tpu.lint.maxCompiledPrograms").integer() \
    .doc("Plan lint threshold (TPU-L005): warn when a plan spans more "
         "distinct compiled-program shapes than this (JIT residency "
         "cache churn).  Default is half the process JIT cache budget.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(96)

# --- concurrency sanitizer (tpucsan) --------------------------------------

CSAN_ENABLED = conf("spark.rapids.tpu.csan.enabled").boolean() \
    .doc("Opt-in runtime lock witness (obs/lockwitness.py): the "
         "engine's registered locks are wrapped so every per-thread "
         "acquisition chain is recorded and checked against the static "
         "lock-order relation from the tpucsan pass "
         "(analysis/concurrency.py, TPU-R008..R010).  The witness "
         "report fails on an acquisition edge the static graph cannot "
         "explain (unmodeled edge) or on an observed lock-order cycle, "
         "and exports tpu_lock_contention_total / tpu_lock_wait_seconds "
         "for the witnessed locks.  Diagnostics only — adds per-acquire "
         "bookkeeping.") \
    .create_with_default(False)

# --- memory sanitizer (tmsan) ---------------------------------------------

MEMSAN_ENABLED = conf("spark.rapids.tpu.memsan.enabled").boolean() \
    .doc("Opt-in runtime shadow ledger over the spill catalog and "
         "staging arena: every alloc/register/pin/spill/unspill/close "
         "is recorded with owning-exec attribution and asserted "
         "against the buffer-lifecycle state machine "
         "(analysis/lifetime.py); after each query the session fails "
         "on a dirty ledger (leaked or mis-tiered buffers).  The "
         "runtime oracle for the static TPU-L013..L015 rules.  "
         "Diagnostics only — adds per-event bookkeeping.") \
    .create_with_default(False)

MEMSAN_HBM_BUDGET = conf("spark.rapids.tpu.memsan.hbmBudgetBytes").bytes() \
    .doc("Device-memory budget the static peak bound (TPU-L014) and "
         "the shadow ledger's peak check are evaluated against.  "
         "Default: the spill catalog's device budget "
         "(spark.rapids.memory.tpu.spillBudgetBytes or the HBM arena "
         "size).") \
    .create_optional()

# --- determinism sanitizer (tpudsan) --------------------------------------

DSAN_ENABLED = conf("spark.rapids.tpu.dsan.enabled").boolean() \
    .doc("Run the determinism / replay-safety pass "
         "(analysis/determinism.py) as part of the plan lint: every "
         "operator's declared replay class (bit_exact > order_stable > "
         "order_dependent > nondeterministic) is composed bottom-up and "
         "a subtree feeding an exchange or cacheable fragment whose "
         "class is weaker than order_stable raises TPU-L016 "
         "(repairable by forcing the aggregate's canonical keyed "
         "merge).  The permuted-replay oracle "
         "(devtools/run_lint.py --dsan) keeps the declarations "
         "honest.") \
    .create_with_default(True)

DSAN_DIGEST_ENABLED = conf("spark.rapids.tpu.dsan.digest.enabled") \
    .boolean() \
    .doc("Record a content digest (blake2b-64 over the Arrow-canonical "
         "live rows) for every shuffle block at map-write time, carry "
         "it in the block metadata wire frame, and verify it on every "
         "remote read — a mismatch fails typed "
         "(TpuShuffleDigestError) and counts "
         "tpu_shuffle_digest_mismatch_total.  This is the "
         "recovered-block correctness check lineage-based recompute "
         "relies on (a replayed map task must reproduce the block it "
         "replaces bit-for-bit).") \
    .create_with_default(True)

# --- program-efficiency sanitizer (tpuxsan) -------------------------------

XSAN_ENABLED = conf("spark.rapids.tpu.xsan.enabled").boolean() \
    .doc("Run the compiled-program efficiency pass (analysis/hloaudit.py) "
         "as part of the plan lint: per-subtree padding-waste accounting "
         "against the capacity buckets (TPU-L018, repairable by "
         "speculative re-bucketing through the pre-flight downgrade "
         "machinery) and the fusion-break roofline check (TPU-L020).  "
         "The StableHLO ledger audit (TPU-L019 host transfers, analytic "
         "cost-model cross-validation) rides the compile observatory's "
         "persisted programs (devtools/run_lint.py --hlo).") \
    .create_with_default(True)

XSAN_PAD_WASTE_MAX = conf("spark.rapids.tpu.xsan.padWasteMax").double() \
    .doc("TPU-L018 threshold: flag a subtree whose padding-waste ratio "
         "(1 - live rows / capacity bucket) exceeds this AND whose "
         "wasted bytes exceed xsan.padWasteMinBytes.  Capacity buckets "
         "are ~8x apart, so ratios up to ~0.87 are the normal cost of "
         "shape-stable compilation; above this the launch is mostly "
         "padding.") \
    .check(lambda v: 0.0 < v <= 1.0, "must be in (0, 1]") \
    .create_with_default(0.95)

XSAN_PAD_WASTE_MIN_BYTES = conf(
    "spark.rapids.tpu.xsan.padWasteMinBytes").bytes() \
    .doc("TPU-L018 floor: subtrees wasting fewer padded bytes than this "
         "per launch are never flagged, whatever their ratio — tiny "
         "batches on the smallest bucket are not worth re-bucketing.") \
    .create_with_default(1024 * 1024)

XSAN_HLO_DIR = conf("spark.rapids.tpu.xsan.hloDir").string() \
    .doc("Directory the compile observatory persists lowered StableHLO "
         "text into (blake2-keyed, per-program dedupe, 2 MB cap).  "
         "Default: an hlo/ subdir of the compile ledger dir "
         "(spark.rapids.tpu.compile.ledgerDir / regress.historyDir); "
         "no ledger dir means no persistence.") \
    .create_optional()

XSAN_COST_TOLERANCE = conf("spark.rapids.tpu.xsan.costTolerance") \
    .double() \
    .doc("Cross-validation tolerance between the analytic cost model "
         "(analysis/hlocost.py roofline) and XLA's own cost_analysis() "
         "bytes-accessed: the ratio analytic/XLA must land in "
         "[1/tol, tol].  The model is an order-of-magnitude roofline "
         "(it catches unit errors, missing operands and capacity/live "
         "confusion, not instruction scheduling); drift past the "
         "tolerance on the golden corpus fails the --hlo gate itself "
         "(anti-vacuity: a lying model is a gate failure).") \
    .check(lambda v: v >= 1.0, "must be >= 1.0") \
    .create_with_default(8.0)

XSAN_BROADCAST_BYTES_MAX = conf(
    "spark.rapids.tpu.xsan.broadcastBytesMax").bytes() \
    .doc("StableHLO audit bound: a materialized broadcast_in_dim "
         "intermediate larger than this inside one compiled program is "
         "reported as a fusion hazard (the broadcast should stay fused "
         "into its consumer, not hit HBM).") \
    .create_with_default(16 * 1024 * 1024)

# --- observability (flight recorder) --------------------------------------

TRACE_ENABLED = conf("spark.rapids.tpu.trace.enabled").boolean() \
    .doc("Record a per-query span tree (session phases, per-operator "
         "per-partition execute spans, spill/shuffle/ICI/bridge events) "
         "in the in-process flight recorder.  Low overhead by design: "
         "the hot path never syncs — deferred device scalars resolve in "
         "one crossing at query end.  Read back via "
         "session.last_query_trace() (Chrome-trace/text exporters) and "
         "the `tools trace` CLI.  Implied by eventLog.dir.") \
    .create_with_default(False)

TRACE_MAX_SPANS = conf("spark.rapids.tpu.trace.maxSpans").integer() \
    .doc("Bound on recorded spans per query; past it new spans are "
         "dropped and counted (a runaway query degrades the trace, "
         "never the engine).") \
    .check(lambda v: v >= 64, "must be >= 64") \
    .create_with_default(65536)

EVENT_LOG_DIR = conf("spark.rapids.tpu.eventLog.dir").string() \
    .doc("When set, the session appends each query to a JSON-lines "
         "event log (events_<appId>) in the SparkListener schema "
         "tools/eventlog.py parses — `tools profile` / `tools qualify` "
         "then work on this engine's own runs.  The emitted plan embeds "
         "per-operator metric values and predicted-vs-actual rows/bytes "
         "(`tools profile --accuracy`).  Failed queries flush too, as "
         "JobFailed.  Enables tracing for the logged queries.") \
    .create_optional()

# --- continuous metrics / health / regression watchdog --------------------

METRICS_ENABLED = conf("spark.rapids.tpu.metrics.enabled").boolean() \
    .doc("Feed the process-wide metrics registry (obs/metrics.py): "
         "counters/gauges/histograms from the spill catalog, staging "
         "arena, shuffle, ICI, bridge, fetch path and session query "
         "lifecycle.  Cheap by design (one locked integer add per "
         "event, <2% on the benchmark suite — bench.py "
         "--metrics-overhead guards it); read back via "
         "session.metrics_snapshot(), the Prometheus endpoint "
         "(metrics.port) or obs.health.render_prometheus().") \
    .create_with_default(True)

METRICS_PORT = conf("spark.rapids.tpu.metrics.port").integer() \
    .doc("When set, serve GET /metrics (Prometheus text format) and "
         "GET /healthz (JSON health snapshot derived from arena "
         "exhaustion, memsan ledger, heartbeat misses and device-probe "
         "liveness) on this localhost port via a stdlib HTTP daemon "
         "thread.  0 binds an ephemeral port (tests).  Unset: no "
         "endpoint (the default — exposition is opt-in, collection is "
         "not).") \
    .create_optional()

# --- fleet observatory (cross-process tracing + peer aggregation) ----------

FLEET_PROPAGATION_ENABLED = conf(
    "spark.rapids.tpu.fleet.propagation.enabled").boolean() \
    .doc("Thread the active query's (trace_id, span_id, tenant) context "
         "through the shuffle wire protocol (the v2 frame-header "
         "extension) so block servers record their serve/serialize/"
         "compress spans under the requesting fetch span, and pull "
         "those spans back over the producer's /spans endpoint after "
         "each remote fetch.  Pre-v2 peers degrade silently to "
         "uncorrelated v1 traffic; a failed pull closes the fetch span "
         "with a spans_lost annotation (counted in "
         "tpu_trace_remote_spans_lost_total), never a hang.") \
    .create_with_default(True)

FLEET_SPANS_MAX_TRACES = conf(
    "spark.rapids.tpu.fleet.spans.maxTraces").integer() \
    .doc("Bound on distinct trace buckets the producer-side "
         "RemoteSpanStore holds awaiting /spans pulls; past it the "
         "oldest trace is evicted (an abandoned consumer must not pin "
         "producer memory).") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(64)

FLEET_SPANS_MAX_PER_TRACE = conf(
    "spark.rapids.tpu.fleet.spans.maxPerTrace").integer() \
    .doc("Bound on buffered serve spans per trace in the producer-side "
         "RemoteSpanStore; past it new spans are dropped and counted "
         "in tpu_trace_remote_spans_dropped_total.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(512)

FLEET_AGGREGATOR_ENABLED = conf(
    "spark.rapids.tpu.fleet.aggregator.enabled").boolean() \
    .doc("On the driver, walk the heartbeat peer registry and scrape "
         "each live peer's /metrics + /healthz into cluster-rollup "
         "series (tpu_fleet_rollup{peer,name}, tpu_fleet_peer_up) and "
         "a fleet health verdict (any dead, unreachable or unhealthy "
         "peer degrades /healthz).  Requires executors to advertise an "
         "obs port at registration.") \
    .create_with_default(True)

FLEET_SCRAPE_MAX_PEERS = conf(
    "spark.rapids.tpu.fleet.scrape.maxPeers").integer() \
    .doc("Cardinality cap on the aggregator's peer label: at most this "
         "many peers are scraped per round; excess live peers are "
         "counted in tpu_fleet_peers_skipped_total instead of labeled "
         "(the registry's own series cap backstops it).") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(16)

FLEET_SCRAPE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.fleet.scrape.timeoutMs").integer() \
    .doc("Per-peer HTTP timeout for aggregator scrapes and post-fetch "
         "/spans pulls.  A pull that exceeds it counts the fetch's "
         "producer spans as lost rather than stalling the read path.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(2000)

REGRESS_HISTORY_DIR = conf("spark.rapids.tpu.regress.historyDir") \
    .string() \
    .doc("Append-only directory of per-run query fingerprints for the "
         "cross-run regression watchdog (obs/history.py): `tools "
         "regress --record` distills self-emitted event logs into it "
         "and `tools regress --check` / `bench.py --check` diff the "
         "two most recent runs, failing on deterministic drift (new "
         "fallbacks, fetch-crossing growth, operator row drift).") \
    .create_optional()

# --- multi-tenant serving (admission control + session pool) --------------

SERVE_ADMISSION_BUDGET = conf(
    "spark.rapids.tpu.serve.hbmAdmissionBudgetBytes").bytes() \
    .doc("Byte-weighted admission budget for concurrent serving: each "
         "query presents its tmsan static peak-device-bytes bound "
         "(TPU-L014, analysis/lifetime.py) as its ticket at plan time, "
         "and tickets co-run only while their bounds sum to at most "
         "this.  Oversized-but-repairable plans (sort / aggregate "
         "merge) are re-planned through the out-of-core repair path "
         "with a smaller oc_budget first; the rest queue FIFO until "
         "serve.admissionTimeoutMs, then fail with the typed "
         "AdmissionTimeout.  Unset disables admission control (the "
         "single-tenant default: only the count-based "
         "concurrentGpuTasks semaphore gates the device).") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_optional()

SERVE_ADMISSION_TIMEOUT_MS = conf(
    "spark.rapids.tpu.serve.admissionTimeoutMs").integer() \
    .doc("How long a query may wait in the FIFO admission queue for "
         "its byte ticket before failing with AdmissionTimeout — "
         "typed backpressure a serving tier can retry or shed, never "
         "a silent hang (and never an OOM from admitting anyway).") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(30_000)

SERVE_POOL_SIZE = conf("spark.rapids.tpu.serve.poolSize").integer() \
    .doc("Logical sessions a SessionPool (api/pool.py) multiplexes "
         "over the ONE process-wide runtime (device manager, spill "
         "catalog, shuffle manager, metrics registry, compile "
         "observatory).  Each borrowed session binds to the borrowing "
         "thread with per-query tracer and memsan-ledger isolation; "
         "size it to the offered concurrency, not the chip count.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(4)

SLO_TARGET_MS = conf("spark.rapids.tpu.slo.targetMs").integer() \
    .doc("Per-request latency objective for the latency observatory "
         "(obs/slo.py): a traced query counts GOOD when it completes "
         "within this many milliseconds; failed queries are always "
         "BAD.  Feeds the per-tenant tpu_slo_{good,total,burn_rate} "
         "gauges, the sustained-burn /healthz rule and "
         "SessionPool.slo_report().  Unset disables SLO accounting — "
         "critical-path extraction (obs/critpath.py) still runs for "
         "every traced query.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_optional()

SLO_OBJECTIVE = conf("spark.rapids.tpu.slo.objective").double() \
    .doc("Fraction of requests that must meet slo.targetMs.  The "
         "windowed burn rate is (bad share) / (1 - objective): burn "
         "1.0 spends error budget exactly as fast as the objective "
         "allows, and sustained burn > 1 across two health snapshots "
         "degrades /healthz naming the burning tenant.") \
    .check(lambda v: 0.0 < v < 1.0, "must be in (0, 1)") \
    .create_with_default(0.99)

# --- feedback-directed planning (estimator observatory) -------------------

FEEDBACK_ENABLED = conf("spark.rapids.tpu.feedback.enabled").boolean() \
    .doc("Close the predict->execute loop: blend the estimator "
         "ledger's recorded per-(exec kind, input signature) actuals "
         "into plan/cost.estimate_rows, and re-plan the reduce side of "
         "a shuffle at the exchange boundary from the catalog's "
         "measured partition_stats (switch join strategy, force the "
         "out-of-core repair, re-price the admission ticket) before it "
         "launches.  Observation RECORDING is always on (the "
         "EstimatorLedger grades the CBO regardless); this key gates "
         "whether the recorded signal feeds back into planning.  Off "
         "by default: feedback makes plans depend on execution "
         "history.") \
    .create_with_default(False)

FEEDBACK_BLEND_FLOOR = conf("spark.rapids.tpu.feedback.blendFloor") \
    .double() \
    .doc("Minimum confidence weight given to a recorded actual when a "
         "matching (exec kind, input signature) exists in the "
         "estimator ledger: estimate = w*recorded + (1-w)*static with "
         "w clamped to [blendFloor, blendCap] by observation count "
         "(w grows as n/(n+1)).") \
    .check(lambda v: 0.0 <= v <= 1.0, "must be in [0, 1]") \
    .create_with_default(0.25)

FEEDBACK_BLEND_CAP = conf("spark.rapids.tpu.feedback.blendCap") \
    .double() \
    .doc("Maximum confidence weight a recorded actual can earn: even a "
         "heavily observed signature keeps (1-blendCap) of the static "
         "model, so a workload shift can still pull the estimate back "
         "before the ledger re-learns it.") \
    .check(lambda v: 0.0 <= v <= 1.0, "must be in [0, 1]") \
    .create_with_default(0.9)

FEEDBACK_MIN_OBSERVATIONS = conf(
    "spark.rapids.tpu.feedback.minObservations").integer() \
    .doc("Observations a (exec kind, input signature) needs in the "
         "estimator ledger before its recorded mean is blended into "
         "estimate_rows.  1 means a single prior run of the same "
         "query shape already sharpens the next plan.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(1)

FEEDBACK_REPLAN_FACTOR = conf(
    "spark.rapids.tpu.feedback.replan.misestimateFactor").double() \
    .doc("How far the measured map-stage output may diverge from the "
         "planner's prediction (ratio, either direction) before the "
         "exchange-boundary re-plan switches the reduce-side join off "
         "speculative sizing (analysis/replan.py).  Ticket re-pricing "
         "and out-of-core repair decisions fire on any material bound "
         "change regardless of this factor.") \
    .check(lambda v: v > 1.0, "must be > 1") \
    .create_with_default(4.0)

# --- HBM observatory (obs/memprof.py) -------------------------------------

HBM_TIMELINE_ENABLED = conf(
    "spark.rapids.tpu.hbm.timeline.enabled").boolean() \
    .doc("Maintain the tenant-attributed device-memory occupancy "
         "timeline (obs/memprof.py): every spill-catalog, staging-"
         "arena, broadcast-retention and admission-ticket event books "
         "a per-(tenant, buffer class) byte delta, exported as "
         "Perfetto counter tracks in the Chrome trace and as the "
         "tpu_hbm_* metric families.  session.hbm_report() and the "
         "admission controller's hbm_holders() read it.  Cheap: one "
         "dict update per lifecycle event, bounded sample ring.") \
    .create_with_default(True)

HBM_TIMELINE_MAX_SAMPLES = conf(
    "spark.rapids.tpu.hbm.timeline.maxSamples").integer() \
    .doc("Bound on the occupancy timeline's in-memory sample ring; "
         "past it the oldest samples drop (the live per-tenant books "
         "stay exact — only the replayable history window is bounded). "
         "The post-mortem bundle and trace counter tracks read this "
         "window.") \
    .check(lambda v: v >= 64, "must be >= 64") \
    .create_with_default(4096)

HBM_POSTMORTEM_ENABLED = conf(
    "spark.rapids.tpu.hbm.postmortem.enabled").boolean() \
    .doc("Failure black box: on query failure, dirty memsan ledger or "
         "admission timeout, dump a bounded post-mortem bundle (trace, "
         "metrics snapshot, memory-timeline window, plan, interp/tmsan "
         "states, estimator grades, effective config) under "
         "<postmortem.dir>/postmortems/, rendered by `tools "
         "postmortem`.  Needs hbm.postmortem.dir or "
         "regress.historyDir to be set.") \
    .create_with_default(True)

HBM_POSTMORTEM_DIR = conf(
    "spark.rapids.tpu.hbm.postmortem.dir").string() \
    .doc("Directory whose postmortems/ subdir receives failure "
         "bundles.  Unset: falls back to regress.historyDir, and when "
         "neither is set the black box is inert.") \
    .create_optional()

HBM_POSTMORTEM_MAX_BUNDLES = conf(
    "spark.rapids.tpu.hbm.postmortem.maxBundles").integer() \
    .doc("Retention cap on the postmortems/ directory: past it the "
         "oldest bundles are deleted after each dump, so a crash-"
         "looping workload cannot fill the disk with black boxes.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(16)

# --- progress observatory (obs/progress.py) -------------------------------

PROGRESS_ENABLED = conf("spark.rapids.tpu.progress.enabled").boolean() \
    .doc("Maintain the live in-flight query view (obs/progress.py): "
         "phase, per-operator partitions done/total, rows-so-far vs "
         "the estimator's predicted rows, a confidence-blended ETA, "
         "and the cooperative cancel/deadline token the partition-"
         "boundary, admission-wait and shuffle-fetch checkpoints "
         "consult.  Served by GET /queries and `tools top`.  Cheap: "
         "per-batch dict updates, no device crossings.  Off, "
         "session.cancel() and deadline_ms have nothing to act on and "
         "report/raise accordingly.") \
    .create_with_default(True)

PROGRESS_MAX_QUERIES = conf(
    "spark.rapids.tpu.progress.maxQueries").integer() \
    .doc("Bound on the live view's in-flight registry: past it the "
         "oldest entry is evicted (a registration leaked by a crashed "
         "query must not grow the view forever).  Size to the offered "
         "concurrency; the finished ring is bounded separately.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_with_default(64)

PROGRESS_DEADLINE_MS = conf(
    "spark.rapids.tpu.progress.deadlineMs").integer() \
    .doc("Default per-query deadline: queries that run past it raise "
         "the typed TpuQueryDeadlineExceeded at the next cooperative "
         "checkpoint (partition boundary, admission queue wait, "
         "shuffle fetch loop).  An explicit "
         "TpuSession.execute(deadline_ms=...) overrides it per call.  "
         "Unset: no deadline unless the caller passes one.  Deadline "
         "failures count BAD against the tenant's SLO burn window; "
         "client cancels do not.") \
    .check(lambda v: v >= 1, "must be >= 1") \
    .create_optional()

WATCHDOG_STALL_SECONDS = conf(
    "spark.rapids.tpu.watchdog.stallSeconds").double() \
    .doc("Stuck-query watchdog threshold: an in-flight query with no "
         "progress event (no phase change, operator open/close or "
         "batch) for this long is flagged stalled — /healthz degrades "
         "naming the query and its deepest open operator span, and "
         "one stall record lands in the failure black box.  The scan "
         "is poll-driven (health snapshots, GET /queries); 0 disables "
         "it.") \
    .check(lambda v: v >= 0.0, "must be >= 0") \
    .create_with_default(30.0)

WATCHDOG_AUTO_CANCEL_SECONDS = conf(
    "spark.rapids.tpu.watchdog.autoCancelSeconds").double() \
    .doc("Hard stall deadline: a query stalled this long is cancelled "
         "by the watchdog (cause=watchdog in tpu_cancellations_total) "
         "at the next scan, unwinding through the same typed "
         "cooperative-cancel path a client cancel uses.  Unset: the "
         "watchdog only flags, never cancels.") \
    .check(lambda v: v > 0.0, "must be > 0") \
    .create_optional()

# Environment variables the engine reads directly (escape hatches that
# must exist before config parsing, e.g. cache sizing at import time).
# The repo lint (TPU-R002) fails on any SPARK_RAPIDS_* env read not
# listed here: env knobs are config surface and get declared like keys.
DECLARED_ENV_KEYS = (
    # process JIT residency budget, read at exec/base.py import
    "SPARK_RAPIDS_TPU_JIT_CACHE_MAX",
    # disable the persistent XLA compile cache (plugin.py startup)
    "SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE",
    # hard deadline (seconds) on TPU device discovery, after which
    # parallel/mesh.py raises — read before any conf exists
    "SPARK_RAPIDS_TPU_DEVICE_PROBE_TIMEOUT_S",
    # seed for shuffle/digest.py's process-wide digest switch: lets
    # session-less subprocesses (serve_map, the --dist bench child)
    # honor spark.rapids.tpu.dsan.digest.enabled without a conf object
    "SPARK_RAPIDS_TPU_DSAN_DIGEST",
)


class RapidsConf:
    """Snapshot of a config map with typed accessors
    (ref RapidsConf.scala class)."""

    def __init__(self, conf_map: Optional[Dict[str, Any]] = None):
        self._map = dict(conf_map or {})

    def get(self, entry: ConfEntry[V]) -> V:
        return entry.get(self._map)

    def raw(self, key: str, default: Any = None) -> Any:
        return self._map.get(key, default)

    def set(self, key: str, value: Any) -> "RapidsConf":
        m = dict(self._map)
        m[key] = value
        return RapidsConf(m)

    def is_op_enabled(self, kind: str, name: str, default: bool = True) -> bool:
        """Auto-derived per-op enable keys, e.g.
        spark.rapids.sql.exec.TpuSortExec (ref GpuOverrides.scala:145-150)."""
        raw = self._map.get(f"spark.rapids.sql.{kind}.{name}")
        return default if raw is None else _to_bool(raw)

    # convenient named properties used widely
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def explain(self) -> str:
        return self.get(EXPLAIN)

    @property
    def ansi_enabled(self) -> bool:
        return self.get(ANSI_ENABLED)

    @property
    def arrow_max_records_per_batch(self) -> int:
        return self.get(ARROW_MAX_RECORDS_PER_BATCH)

    @property
    def udf_compiler_enabled(self) -> bool:
        return self.get(UDF_COMPILER_ENABLED)

    @property
    def capacity_buckets(self) -> List[int]:
        return sorted(int(x) for x in
                      self.get(TPU_BATCH_CAPACITY_BUCKETS).split(","))

    @property
    def string_data_buckets(self) -> List[int]:
        return sorted(int(x) for x in
                      self.get(TPU_STRING_DATA_BUCKETS).split(","))


def all_entries() -> List[ConfEntry]:
    return [e for _, e in sorted(_REGISTERED.items())]


def generate_docs() -> str:
    """Render docs/configs.md from the registry
    (ref RapidsConf.scala doc printer)."""
    lines = ["# Configuration", "",
             "Generated from `spark_rapids_tpu/config.py` — do not edit.", "",
             "| Name | Default | Description |", "|---|---|---|"]
    for e in all_entries():
        if e.is_internal:
            continue
        lines.append(f"| `{e.key}` | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"
