"""Plugin bootstrap: driver/executor lifecycle.

Ref: sql-plugin/.../Plugin.scala — `RapidsDriverPlugin` (config fixup,
shuffle heartbeat registry, plan-capture test callback RPC at :264-386)
and `RapidsExecutorPlugin` (:166-238: cudf version handshake, GPU+RMM
init, semaphore init, heartbeat registration, hard `System.exit(1)` on
init failure so the cluster manager reschedules the executor).

The TPU build keeps the same two-phase shape: a driver-side plugin that
owns cluster-wide state (heartbeat registry, config fixup, capture
callback) and an executor-side plugin that initializes this process's
device runtime (device manager, HBM budget/spill catalog, task
semaphore, shuffle endpoint, shim selection) and applies the same
fail-fast contract.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, List, Optional

from . import config as cfg

log = logging.getLogger("spark_rapids_tpu.plugin")


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache``: a fixed path, because the path is part
    of how a later process finds the cache again.  JAX's own key covers
    version, backend and flags, so nothing else goes into it."""
    import os
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def compilation_cache_dir() -> str:
    """Where the persistent compilation cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside; only when that
    is unset does code choose, ``default_cache_dir()``."""
    import os
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        default_cache_dir()


def init_compilation_cache() -> str:
    """Switch on JAX's persistent compilation cache and return its
    directory (``compilation_cache_dir()``).  JAX reads the variable
    itself, so the directory is set in code only when it is unset."""
    import os

    import jax
    cache_dir = compilation_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # a compile before this point would have initialized the cache
        # with no directory, for good
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # count disk hits/misses so the observatory can tell whether the
    # persistent cache actually absorbs backend compiles
    from .obs.compileprof import install_persistent_cache_metrics
    install_persistent_cache_metrics()
    return cache_dir


class PluginInitError(RuntimeError):
    """Executor init failure.  The reference calls System.exit(1)
    (Plugin.scala:196-203); embedded in-process we raise instead and let
    the host decide, unless spark.rapids.tpu.hardExitOnInitFailure."""


def fixup_configs(conf_map: dict) -> dict:
    """Force settings the plugin needs, like the reference forcing
    `spark.sql.extensions` + serializer checks
    (RapidsPluginUtils.fixupConfigs, Plugin.scala:77-112)."""
    out = dict(conf_map)
    exts = out.get("spark.sql.extensions", "")
    ours = "com.nvidia.spark.rapids.tpu.SQLExecPlugin"
    if ours not in exts:
        out["spark.sql.extensions"] = f"{exts},{ours}".strip(",")
    # columnar serializer must stay compatible with device batches
    out.setdefault("spark.rapids.shuffle.transport",
                   cfg.RapidsConf(out).get(cfg.SHUFFLE_TRANSPORT))
    return out


# ---------------------------------------------------------------------------
# Plan-capture callback (ref ExecutionPlanCaptureCallback Plugin.scala:264)
# ---------------------------------------------------------------------------

class ExecutionPlanCaptureCallback:
    """Captures executed plans for fallback assertions in tests."""

    _capture = False
    _plans: List = []
    _lock = threading.Lock()

    @classmethod
    def start_capture(cls):
        with cls._lock:
            cls._capture = True
            cls._plans = []

    @classmethod
    def on_plan(cls, plan) -> None:
        with cls._lock:
            if cls._capture:
                cls._plans.append(plan)

    @classmethod
    def get_resulting_plans(cls) -> List:
        with cls._lock:
            cls._capture = False
            return list(cls._plans)

    @classmethod
    def assert_contains(cls, plan, exec_name: str) -> bool:
        found = []
        plan.foreach(lambda e: found.append(e)
                     if type(e).__name__ == exec_name else None)
        return bool(found)


class TpuDriverPlugin:
    """Driver-side lifecycle (ref RapidsDriverPlugin, Plugin.scala:129)."""

    def __init__(self, conf_map: Optional[dict] = None):
        self.conf_map = fixup_configs(conf_map or {})
        self.conf = cfg.RapidsConf(self.conf_map)
        self.heartbeat_manager = None
        self.fleet_aggregator = None

    def init(self) -> dict:
        from .shuffle.heartbeat import HeartbeatManager
        if self.conf.get(cfg.SHUFFLE_MANAGER_ENABLED):
            timeout = self.conf.get(cfg.SHUFFLE_HEARTBEAT_TIMEOUT_MS) / 1000
            self.heartbeat_manager = HeartbeatManager(timeout_s=timeout)
            if self.conf.get(cfg.FLEET_AGGREGATOR_ENABLED):
                # the driver is where cluster-rollup series and the
                # fleet verdict live: the aggregator walks THIS
                # registry's peers at every /metrics//healthz read
                from .obs.fleet import FleetAggregator, install_aggregator
                self.fleet_aggregator = install_aggregator(FleetAggregator(
                    self.heartbeat_manager,
                    max_peers=self.conf.get(cfg.FLEET_SCRAPE_MAX_PEERS),
                    timeout_s=self.conf.get(
                        cfg.FLEET_SCRAPE_TIMEOUT_MS) / 1000.0))
        log.info("TPU driver plugin initialized")
        return self.conf_map  # the fixed-up configs Spark distributes

    def receive(self, message):
        """Driver RPC dispatch (ref Plugin.scala:132-144): executors
        register / heartbeat through the plugin channel."""
        kind = message.get("kind")
        if self.heartbeat_manager is None:
            return {"ok": False, "error": "accelerated shuffle disabled"}
        if kind == "register":
            peers = self.heartbeat_manager.register_executor(
                message["executor_id"], message.get("host", ""),
                message.get("port", 0),
                obs_port=message.get("obs_port", 0))
            return {"ok": True, "peers": [p.__dict__ for p in peers]}
        if kind == "heartbeat":
            peers = self.heartbeat_manager.executor_heartbeat(
                message["executor_id"])
            return {"ok": True, "peers": [p.__dict__ for p in peers]}
        return {"ok": False, "error": f"unknown message {kind!r}"}

    def shutdown(self):
        if self.fleet_aggregator is not None:
            from .obs.fleet import install_aggregator
            install_aggregator(None)
            self.fleet_aggregator = None
        self.heartbeat_manager = None


class TpuExecutorPlugin:
    """Executor-side lifecycle (ref RapidsExecutorPlugin,
    Plugin.scala:166-238)."""

    def __init__(self, conf_map: Optional[dict] = None,
                 driver: Optional[TpuDriverPlugin] = None,
                 executor_id: str = "0"):
        self.conf = cfg.RapidsConf(conf_map or {})
        self.driver = driver
        self.executor_id = executor_id
        self.device_manager = None
        self.semaphore = None
        self.spill_catalog = None
        self.shuffle_server = None

    # -- version handshake (ref checkCudfVersion Plugin.scala:206) ----------
    @staticmethod
    def check_runtime_versions() -> List[str]:
        problems = []
        import pyarrow
        pv = tuple(int(x) for x in pyarrow.__version__.split(".")[:1])
        if pv < (8,):
            problems.append(
                f"pyarrow {pyarrow.__version__} is too old (need 8+)")
        return problems

    def init(self):
        try:
            problems = self.check_runtime_versions()
            if problems:
                raise PluginInitError("; ".join(problems))
            self._init_compilation_cache()
            from .memory.device import DeviceManager
            from .memory.meta import set_default_codec
            from .memory.semaphore import TpuSemaphore
            from .memory.spill import SpillCatalog
            from .shims import ShimLoader
            self.shim = ShimLoader.get_shim(
                self.conf.raw("spark.rapids.tpu.sparkVersion", "3.2.0"))
            set_default_codec(self.conf.get(cfg.SHUFFLE_COMPRESSION_CODEC))
            self.device_manager = DeviceManager.initialize(self.conf)
            self.semaphore = TpuSemaphore.initialize(
                self.conf.get(cfg.CONCURRENT_TPU_TASKS))
            # byte-weighted admission (serve.hbmAdmissionBudgetBytes):
            # configured alongside the count semaphore so both gates
            # share one lifecycle; unset budget clears the controller
            # (single-tenant sessions must not inherit a previous
            # serving session's budget)
            from .memory.admission import AdmissionController
            AdmissionController.configure(
                self.conf.get(cfg.SERVE_ADMISSION_BUDGET),
                self.conf.get(cfg.SERVE_ADMISSION_TIMEOUT_MS) / 1000.0)
            self.spill_catalog = SpillCatalog.init_from_conf(self.conf)
            # HBM observatory: (re)configure the occupancy timeline
            # with the freshly-sized device budget, so its watermark
            # fraction and tpu_hbm_budget_bytes gauge are truthful even
            # when the plugin is bootstrapped outside a TpuSession
            from .obs.memprof import MemoryTimeline
            MemoryTimeline.configure(
                enabled=self.conf.get(cfg.HBM_TIMELINE_ENABLED),
                max_samples=self.conf.get(cfg.HBM_TIMELINE_MAX_SAMPLES),
                budget_bytes=self.spill_catalog.device_budget)
            pinned = self.conf.get(cfg.PINNED_POOL_SIZE)
            if pinned and pinned > 0:
                from .native.arena import configure_shared_arena
                configure_shared_arena(pinned)
            # block-server endpoint: starts next to the health HTTP
            # server when transport=tcp OR shuffle.server.enabled —
            # peers fetch this process's catalog blocks from it
            srv_on = self.conf.get(cfg.SHUFFLE_MANAGER_ENABLED) and (
                self.conf.get(cfg.SHUFFLE_TRANSPORT) == "tcp"
                or self.conf.get(cfg.SHUFFLE_SERVER_ENABLED))
            if srv_on:
                from .shuffle.transport import ShuffleServer
                self.shuffle_server = ShuffleServer(
                    port=self.conf.get(cfg.SHUFFLE_SERVER_PORT)).start()
            # the location registry learns this process's identity so
            # reduce-side reads can split local (zero-copy catalog)
            # from remote (fetched) blocks
            from .shuffle.registry import BlockLocationRegistry
            reg = BlockLocationRegistry.get()
            reg.set_local(self.executor_id, "127.0.0.1",
                          getattr(self.shuffle_server, "port", 0) or 0)
            # fleet endpoint: when metrics.port is configured this
            # executor serves /metrics//healthz//spans and advertises
            # the bound port at registration so the driver's aggregator
            # can scrape it and consumers can pull serve spans
            obs_port = 0
            mport = self.conf.get(cfg.METRICS_PORT)
            if mport is not None:
                from .obs.health import ensure_server
                obs_port = ensure_server(mport).port
            if self.shuffle_server is not None:
                self.shuffle_server.executor_id = self.executor_id
                self.shuffle_server.obs_port = obs_port
            if self.driver is not None:
                self.driver.receive({
                    "kind": "register", "executor_id": self.executor_id,
                    "host": "localhost",
                    "port": getattr(self.shuffle_server, "port", 0),
                    "obs_port": obs_port})
                if self.driver.heartbeat_manager is not None:
                    reg.attach_heartbeat(self.driver.heartbeat_manager)
            log.info("TPU executor plugin initialized (executor %s)",
                     self.executor_id)
        except Exception as ex:
            log.error("executor plugin init failed: %s", ex)
            raw = self.conf.raw("spark.rapids.tpu.hardExitOnInitFailure")
            if raw is not None and cfg._to_bool(raw):
                import os
                os._exit(1)  # the reference's System.exit(1) contract
            raise

    def _init_compilation_cache(self):
        """Persistent XLA compilation cache: re-planned queries re-trace
        but skip compilation (each collect builds fresh exec instances, so
        without this every repeated query pays a full XLA compile — the
        analog of the reference's one-time CUDA kernel load)."""
        import os
        if not self.conf.get(cfg.COMPILATION_CACHE_ENABLED):
            return
        if os.environ.get("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE"):
            # for environments that run many engine processes side by
            # side (tests/conftest.py, the serve_map children): XLA:CPU
            # AOT loads from a directory under concurrent write have
            # segfaulted inside the cache read
            return
        init_compilation_cache()

    def shutdown(self):
        if self.shuffle_server is not None:
            self.shuffle_server.stop()
            self.shuffle_server = None
