"""TpuSession: the user entry point.

Plays the combined role of SparkSession + the reference's plugin bootstrap
(ref Plugin.scala RapidsDriverPlugin/RapidsExecutorPlugin): holds config,
initializes the device manager/semaphore/spill catalog, and drives
logical -> physical -> overrides -> execution for DataFrame queries.

With `spark.rapids.sql.enabled=false` queries run entirely on the CPU
engine — the differential-test harness toggles exactly this key, the same
way the reference's integration tests do.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import pyarrow as pa

from .. import config as cfg
from ..config import RapidsConf
from ..exec.base import ExecContext, SpeculativeSizingMiss
from ..plan import logical as L
from ..plan.overrides import TpuOverrides
from ..plan.planner import plan as plan_physical
from .dataframe import DataFrame


def _replay_class(plan, conf) -> str:
    """The final plan's effective replay class (tpudsan lattice root),
    stamped on the phase:overrides span so run fingerprints and the
    failure black box can see recompute guarantees weaken across runs.
    Best-effort: classification must never fail a query."""
    try:
        if not conf.get(cfg.DSAN_ENABLED):
            return "unclassified"
        from ..analysis.determinism import classify_plan
        return classify_plan(plan, conf).effective(plan)
    except Exception:
        return "unclassified"


class TpuSession:
    _active: Optional["TpuSession"] = None
    _lock = threading.Lock()
    _create_lock = threading.Lock()
    _tls = threading.local()

    def __init__(self, conf: Optional[Dict] = None):
        self._conf_map = dict(conf or {})
        self.last_plan = None
        self.last_explain = ""
        # flight recorder (obs/): per-query trace + self-emitted event log
        self._last_trace = None
        self._last_profile = None   # the host ledger's last record
        self._obs_plan = None
        self._obs_writer = None
        self._sql_counter = 0
        # pool sessions (api/pool.py) bind tracer + memsan ledger
        # thread-locally so co-running queries never share either
        self._obs_isolation = False
        self.last_peak_device_bytes = None
        self._init_runtime()
        with TpuSession._lock:
            TpuSession._active = self

    def _init_runtime(self):
        conf = self.conf
        # continuous metrics: the registry collects by default (cheap);
        # the HTTP exposition endpoint is opt-in via metrics.port
        from ..obs import metrics as obs_metrics
        obs_metrics.set_enabled(conf.get(cfg.METRICS_ENABLED))
        port = conf.get(cfg.METRICS_PORT)
        if port is not None and conf.get(cfg.METRICS_ENABLED):
            from ..obs.health import ensure_server
            self.metrics_server = ensure_server(port)
        else:
            self.metrics_server = None
        # background-thread failures (heartbeat loop, metrics endpoint)
        # bundle into the same black box as query failures when one is
        # configured — the router is process-global because those
        # threads outlive any single session
        from ..obs import bgerrors
        if conf.get(cfg.HBM_POSTMORTEM_ENABLED):
            bg_dir = conf.get(cfg.HBM_POSTMORTEM_DIR) or \
                conf.get(cfg.REGRESS_HISTORY_DIR)
            if bg_dir:
                bgerrors.set_postmortem_dir(bg_dir)
        # fleet observatory bounds: size the producer-side serve-span
        # buffer the /spans endpoint drains
        from ..obs.fleet import RemoteSpanStore
        RemoteSpanStore.get().configure(
            conf.get(cfg.FLEET_SPANS_MAX_TRACES),
            conf.get(cfg.FLEET_SPANS_MAX_PER_TRACE))
        # compile observatory: every XLA build at the process_jit seam
        # gets split timing, a classified cause and (with a ledger dir)
        # cross-session persistence (obs/compileprof.py)
        from ..obs.compileprof import CompileObservatory
        ledger_dir = conf.get(cfg.COMPILE_LEDGER_DIR) or \
            conf.get(cfg.REGRESS_HISTORY_DIR)
        ledger_path = None
        if ledger_dir:
            from ..obs.history import HistoryDir
            ledger_path = HistoryDir(ledger_dir).compile_ledger_path()
        hlo_dir = conf.get(cfg.XSAN_HLO_DIR)
        if not hlo_dir and ledger_dir:
            from ..obs.compileprof import HLO_SUBDIR
            hlo_dir = os.path.join(ledger_dir, HLO_SUBDIR)
        CompileObservatory.get().configure(
            enabled=conf.get(cfg.COMPILE_OBSERVATORY_ENABLED),
            ledger_path=ledger_path,
            buckets=conf.capacity_buckets + conf.string_data_buckets,
            thrash_warn_ratio=conf.get(cfg.JIT_THRASH_WARN_RATIO),
            hlo_dir=hlo_dir or None)
        # estimator observatory: predicted-vs-actual per operator
        # signature, persisted next to the compile ledger; recording is
        # always on, feedback.enabled additionally blends it back into
        # planning and arms the exchange-boundary re-planner
        from ..obs.estimator import EstimatorLedger
        est_path = None
        if ledger_dir:
            from ..obs.history import HistoryDir
            est_path = HistoryDir(ledger_dir).estimator_ledger_path()
        EstimatorLedger.get().configure(
            ledger_path=est_path,
            feedback_enabled=conf.get(cfg.FEEDBACK_ENABLED),
            blend_floor=conf.get(cfg.FEEDBACK_BLEND_FLOOR),
            blend_cap=conf.get(cfg.FEEDBACK_BLEND_CAP),
            min_observations=conf.get(cfg.FEEDBACK_MIN_OBSERVATIONS),
            replan_factor=conf.get(cfg.FEEDBACK_REPLAN_FACTOR))
        # latency observatory: per-tenant SLO windows + tail reservoir
        # fed by critical-path extraction on every traced query; the
        # per-query ledger lands in the regress HistoryDir
        from ..obs.slo import LatencyObservatory
        slo_ledger = None
        hist_dir = conf.get(cfg.REGRESS_HISTORY_DIR)
        if hist_dir:
            from ..obs.history import HistoryDir
            slo_ledger = HistoryDir(hist_dir).latency_ledger_path()
        LatencyObservatory.get().configure(
            target_ms=conf.get(cfg.SLO_TARGET_MS),
            objective=conf.get(cfg.SLO_OBJECTIVE),
            ledger_path=slo_ledger)
        # progress observatory: the live in-flight view + cooperative
        # cancel tokens + stuck-query watchdog thresholds
        from ..obs.progress import ProgressTracker
        ProgressTracker.get().configure(
            enabled=conf.get(cfg.PROGRESS_ENABLED),
            max_queries=conf.get(cfg.PROGRESS_MAX_QUERIES),
            stall_seconds=conf.get(cfg.WATCHDOG_STALL_SECONDS),
            auto_cancel_seconds=conf.get(
                cfg.WATCHDOG_AUTO_CANCEL_SECONDS))
        from ..memory.meta import set_default_codec
        set_default_codec(conf.get(cfg.SHUFFLE_COMPRESSION_CODEC))
        from ..shims import ShimLoader, set_active_shim
        self.shim = ShimLoader.get_shim(
            conf.raw("spark.rapids.tpu.sparkVersion", "3.2.0"))
        set_active_shim(self.shim)
        from ..exec.base import set_device_timing, set_trace_annotations
        set_trace_annotations(conf.get(cfg.PROFILE_TRACE_ANNOTATIONS))
        # DEBUG metrics level: block per-op so opTime is real device time
        # (ref NvtxWithMetrics; round-2 verdict: async dispatch made every
        # operator report ~0 and booked all kernel time to the D2H sync)
        set_device_timing(conf.get(cfg.METRICS_LEVEL) == "DEBUG")
        if conf.get(cfg.BACKEND) == "tpu" and conf.sql_enabled:
            # in-process both-sides bootstrap (ref Plugin.scala: driver +
            # executor plugins; one process hosts both roles here)
            from ..plugin import TpuDriverPlugin, TpuExecutorPlugin
            self.driver_plugin = TpuDriverPlugin(self._conf_map)
            self.driver_plugin.init()
            self.executor_plugin = TpuExecutorPlugin(
                self._conf_map, driver=self.driver_plugin)
            self.executor_plugin.init()
            self.shim = self.executor_plugin.shim  # one source of truth
            self.device_manager = self.executor_plugin.device_manager
            self.semaphore = self.executor_plugin.semaphore
            self.spill_catalog = self.executor_plugin.spill_catalog
        else:
            self.driver_plugin = None
            self.executor_plugin = None
            self.device_manager = None
            self.semaphore = None
            self.spill_catalog = None
        # HBM observatory: the process-wide occupancy timeline every
        # spill/arena/broadcast/admission hook feeds (obs/memprof.py).
        # Configured after plugin init so the device budget is known.
        from ..obs.memprof import MemoryTimeline
        MemoryTimeline.configure(
            enabled=conf.get(cfg.HBM_TIMELINE_ENABLED),
            max_samples=conf.get(cfg.HBM_TIMELINE_MAX_SAMPLES),
            budget_bytes=self.spill_catalog.device_budget
            if self.spill_catalog is not None else 0)
        # warm-start tier: replay the costliest ledger recipes so first
        # queries dispatch to ready programs.  Ordered after plugin
        # init: the replay compiles through the persistent disk cache.
        self._prewarm_thread = None
        if ledger_path and conf.get(cfg.JIT_PREWARM_ENABLED) and \
                conf.get(cfg.COMPILE_OBSERVATORY_ENABLED):
            from ..obs.prewarm import prewarm_session
            self._prewarm_thread = prewarm_session(
                ledger_path,
                top_k=conf.get(cfg.JIT_PREWARM_TOP_K),
                background=conf.get(cfg.JIT_PREWARM_BACKGROUND))

    # -- conf ---------------------------------------------------------------
    @property
    def conf(self) -> RapidsConf:
        return RapidsConf(self._conf_map)

    def set_conf(self, key: str, value) -> "TpuSession":
        self._conf_map[key] = value
        return self

    @classmethod
    def builder(cls):
        return _Builder()

    @classmethod
    def active(cls) -> "TpuSession":
        """The session for THIS thread: the pool-bound one when the
        calling thread borrowed from a SessionPool (api/pool.py), else
        the process-wide last-created session, built on demand.
        Thread-safe — concurrent first calls no longer race to build
        two default sessions."""
        bound = getattr(cls._tls, "session", None)
        if bound is not None:
            return bound
        if cls._active is None:
            with cls._create_lock:
                if cls._active is None:
                    TpuSession()  # registers itself as _active
        return cls._active

    @classmethod
    def bind_to_thread(cls,
                       session: Optional["TpuSession"]) -> None:
        """Bind (or with None, unbind) the calling thread's active()
        session — the SessionPool's borrow/return hook."""
        cls._tls.session = session

    # -- data sources -------------------------------------------------------
    def create_dataframe(self, data, num_partitions: int = 1) -> DataFrame:
        if isinstance(data, pa.Table):
            table = data
        elif isinstance(data, pa.RecordBatch):
            table = pa.Table.from_batches([data])
        elif isinstance(data, dict):
            table = pa.table(data)
        else:
            import pandas as pd
            if isinstance(data, pd.DataFrame):
                table = pa.Table.from_pandas(data, preserve_index=False)
            else:
                raise TypeError(f"cannot create DataFrame from {type(data)}")
        return DataFrame(L.LocalRelation(table, num_partitions), self)

    def range(self, start, end=None, step=1, num_partitions=1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(L.Range(start, end, step, num_partitions), self)

    @property
    def read(self):
        from ..io.reader import DataFrameReader
        return DataFrameReader(self)

    # -- execution ----------------------------------------------------------
    def prepare_plan(self, lp: L.LogicalPlan, run_subqueries: bool = True):
        """Logical plan -> final physical plan: dialect install, scalar
        subqueries, planning, overrides — the shared front half of
        execute()/explain()/ml.device_batches.

        run_subqueries=False (explain) substitutes subqueries with typed
        null placeholders instead of EXECUTING them: printing a plan must
        never run device work (ref explain stays driver-side)."""
        from ..expr.subquery import (has_scalar_subquery,
                                     resolve_scalar_subqueries)
        from ..obs.tracer import trace_span
        from ..shims import set_active_shim
        # queries are evaluated sequentially per process; installing the
        # dialect per execution keeps interleaved sessions with different
        # sparkVersions correct (concurrent multi-dialect sessions are
        # out of scope, like one ShimLoader per JVM in the reference)
        set_active_shim(self.shim)
        if has_scalar_subquery(lp):
            # subqueries run first, driver-side, and substitute as typed
            # literals (ref GpuScalarSubquery / ExecSubqueryExpression)
            with trace_span("phase:subqueries", kind="phase"):
                lp = resolve_scalar_subqueries(lp, self,
                                               execute=run_subqueries)
        with trace_span("phase:planning", kind="phase"):
            physical = plan_physical(lp, self.conf)
            from ..plan.planner import force_perfile_if_input_file
            force_perfile_if_input_file(physical)
        with trace_span("phase:overrides", kind="phase") as sp:
            overrides = TpuOverrides(self.conf)
            final_plan = overrides.apply(physical)
            lint = getattr(overrides, "last_lint", [])
            sp.set(lint_diags=len(lint),
                   lint_rules=sorted({d.code for d in lint}),
                   replay_class=_replay_class(final_plan, self.conf))
        self.last_plan = final_plan
        self.last_explain = overrides.last_explain
        self._count_fallbacks(final_plan)
        return final_plan

    def _count_fallbacks(self, final_plan) -> None:
        """Feed tpu_fallback_ops_total: operators the overrides engine
        left on the host engine, by exec name (a growing fallback set
        is the regression watchdog's loudest deterministic signal)."""
        from ..exec.base import CPU
        from ..obs import metrics as m
        if not m.enabled():
            return
        fam = m.counter("tpu_fallback_ops_total",
                        "plan operators left on the host engine",
                        ("op",))
        final_plan.foreach(
            lambda e: fam.labels(op=type(e).__name__).inc()
            if e.placement == CPU else None)

    def release_plan_shuffles(self, final_plan) -> None:
        """Release shuffle blocks a plan registered in the global spill
        catalog (ref remove-shuffle on stage cleanup) — each collect
        re-plans, so dropping them cannot be observed."""
        from ..shuffle.manager import TpuShuffleManager
        ids = []
        final_plan.foreach(
            lambda e: ids.append(e._shuffle_id)
            if getattr(e, "_shuffle_id", None) is not None else None)
        if ids:
            mgr = TpuShuffleManager.get()
            for sid in ids:
                mgr.unregister(sid)
        # device-resident exchange memos (IciExchangeExec) hold whole
        # shuffled datasets in HBM — same cleanup point as shuffle blocks
        final_plan.foreach(
            lambda e: e.release_shuffle()
            if hasattr(e, "release_shuffle") else None)

    def execute(self, lp: L.LogicalPlan,
                deadline_ms: Optional[int] = None) -> pa.Table:
        """Execute + collect, under the continuous query-lifecycle
        metrics (active/completed/failed) every health probe reads.

        ``deadline_ms`` bounds the query's wall time: past it the next
        cooperative checkpoint (partition boundary, admission queue
        wait, shuffle fetch loop) raises the typed
        TpuQueryDeadlineExceeded, unwinding through the same release
        obligations as any other failure.  Unset, the session-level
        ``spark.rapids.tpu.progress.deadlineMs`` default applies."""
        from ..obs import metrics as m
        m.gauge("tpu_queries_active",
                "queries currently executing").gauge_inc()
        try:
            result = self._execute(lp, deadline_ms=deadline_ms)
        except BaseException:
            m.counter("tpu_queries_failed_total",
                      "queries that raised").inc()
            raise
        finally:
            m.gauge("tpu_queries_active",
                    "queries currently executing").dec()
        m.counter("tpu_queries_completed_total",
                  "queries that returned a result").inc()
        return result

    def cancel(self, query_id: str) -> bool:
        """Request cooperative cancellation of an in-flight query on
        this session.  Returns True if a live query matched; the query
        itself raises TpuQueryCancelled at its next checkpoint
        (partition boundary, admission wait, or shuffle fetch loop)."""
        from ..obs.progress import ProgressTracker
        return ProgressTracker.get().cancel(
            query_id, tenant=getattr(self, "_tenant", "") or "default")

    def _execute(self, lp: L.LogicalPlan,
                 deadline_ms: Optional[int] = None) -> pa.Table:
        from ..obs import memprof
        from ..obs import progress as prog
        from ..obs import tracer as obs
        conf = self.conf
        if conf.get(cfg.CSAN_ENABLED):
            # lock witness: wrap registered locks before any of them is
            # taken on this query's path; refresh() also picks up locks
            # whose owners were constructed since the last query
            from ..obs import lockwitness
            lockwitness.ensure_installed()
        # one id a query, nested ones too: the profiler's root range,
        # the host ledger's record, the live view, memprof's context and
        # the event log's sql_id all carry it
        sql_id = self._sql_counter
        self._sql_counter += 1
        query_id = f"q{sql_id}"
        # the profiler's root range: every range of this query nests in
        # one that carries its id (the flight recorder has its own root)
        root_range = obs.open_range("query:" + query_id, query_id)
        try:
            eventlog_dir = conf.get(cfg.EVENT_LOG_DIR)
            tracing = conf.get(cfg.TRACE_ENABLED) or \
                eventlog_dir is not None
            # HBM observatory attribution scope: every spill/arena event
            # on this thread books under (tenant, query) until the query
            # ends
            memprof.push_context(
                getattr(self, "_tenant", "") or "default", query_id)
            # progress observatory: register the live-view record +
            # cancel token, bound thread-local so the cooperative
            # checkpoints in exec/admission/shuffle find it without
            # signature plumbing
            if deadline_ms is None:
                deadline_ms = conf.get(cfg.PROGRESS_DEADLINE_MS)
            handle = prog.ProgressTracker.get().begin_query(
                query_id,
                tenant=getattr(self, "_tenant", "") or "default",
                deadline_ms=deadline_ms)
            prog.bind_to_thread(handle)
            try:
                if not tracing:
                    try:
                        result = self._execute_query(lp, None, None)
                        prog.ProgressTracker.get().end_query(handle)
                        return result
                    except BaseException as ex:
                        prog.ProgressTracker.get().end_query(handle, ex)
                        self._maybe_postmortem(ex, None)
                        raise
                # flight recorder: one QueryTrace per execute(); the
                # installed tracer is what every instrumented layer
                # (operator spans, spill/shuffle/ICI/bridge events)
                # records
                tracer = obs.QueryTrace(
                    max_spans=conf.get(cfg.TRACE_MAX_SPANS))
                tracer.sql_id = sql_id
                if self._obs_isolation:
                    obs.install_local(tracer)
                else:
                    obs.install(tracer)
                self._last_trace = tracer
                self._obs_plan = None
                try:
                    result = self._execute_query(lp, tracer, eventlog_dir)
                    prog.ProgressTracker.get().end_query(handle)
                    return result
                except BaseException as ex:
                    # failed queries flush too: spans close with the
                    # exception recorded, the event log gets a JobFailed
                    # group; the black box dumps AFTER the flush so the
                    # bundle sees the sealed trace
                    prog.ProgressTracker.get().end_query(handle, ex)
                    self._flush_query_obs(tracer, ex, eventlog_dir)
                    self._maybe_postmortem(ex, tracer)
                    raise
                finally:
                    if self._obs_isolation:
                        obs.uninstall_local()
                    else:
                        obs.uninstall()
            finally:
                prog.bind_to_thread(None)
                memprof.pop_context()
        finally:
            profile = obs.close_range(root_range)
            if profile is not None:
                self._last_profile = profile

    def _execute_query(self, lp: L.LogicalPlan, tracer,
                       eventlog_dir) -> pa.Table:
        from ..obs.tracer import trace_span
        from ..plan.host_assist import try_host_assisted_collect
        with trace_span("phase:host_assist", kind="phase"):
            assisted = try_host_assisted_collect(self, lp)
        if assisted is not None:
            if tracer is not None:
                tracer.finalize()
                tracer._flush_done = True  # no plan ran: nothing to log
            return assisted
        with trace_span("phase:plan", kind="phase"):
            final_plan = self.prepare_plan(lp)
        # byte-weighted admission (serve.hbmAdmissionBudgetBytes): the
        # plan's tmsan static peak bound is its ticket — acquired once,
        # held across the speculation retry (re-entrancy), released in
        # the finally (release-on-failure)
        try:
            with trace_span("phase:admit", kind="phase"):
                ticket, controller = self._admit_plan(final_plan)
        except BaseException:
            # a cancel / deadline / AdmissionTimeout raised while
            # queued must not strand the shuffle blocks that exchange
            # map stages already materialized during planning
            self.release_plan_shuffles(final_plan)
            raise
        try:
            return self._execute_admitted(lp, final_plan, tracer,
                                          eventlog_dir, ticket)
        finally:
            if controller is not None:
                controller.release(ticket)

    def _execute_admitted(self, lp: L.LogicalPlan, final_plan, tracer,
                          eventlog_dir, ticket) -> pa.Table:
        from ..obs.tracer import trace_span
        # what the session does between the plan and the first operator
        with trace_span("phase:setup", kind="phase"):
            self._obs_plan = final_plan
            self._install_predictions(tracer, final_plan)
            from ..plugin import ExecutionPlanCaptureCallback
            ExecutionPlanCaptureCallback.on_plan(final_plan)
            ctx = ExecContext(self.conf)
            # exchange-boundary re-planner: armed for the whole execution
            # (feedback.enabled gates inside); it needs the live ticket to
            # re-price and the exec context to pin strategy switches on
            from ..analysis import replan as replan_mod
            from ..memory.admission import AdmissionController
            rctx = replan_mod.ReplanContext(
                plan_root=final_plan, conf=self.conf, ticket=ticket,
                controller=AdmissionController.get()
                if ticket is not None else None,
                tracer=tracer, exec_ctx=ctx)
            replan_mod.install(rctx)
            # boundaries whose map stage ran during planning replay now —
            # still before the first reduce partition launches
            replan_mod.scan_materialized(rctx)
            from ..memory.spill import SpillCatalog
            debug = self.conf.get(cfg.MEMORY_DEBUG)
            cat = SpillCatalog.get()
            # tmsan runtime sanitizer: record + assert the buffer lifecycle
            # state machine on every catalog/arena event while the query
            # runs, then require a clean ledger (no leaks) afterwards.
            # Pool sessions install thread-locally: a per-query clean check
            # must not flag co-running queries' live buffers as leaks.
            from ..memory import memsan
            memsan_on = self.conf.get(cfg.MEMSAN_ENABLED)
            if memsan_on:
                ledger = memsan.install_local() if self._obs_isolation \
                    else memsan.install()
            if debug:
                cat.debug = True
                before = {b_id for b_id, *_ in cat.leak_report()}
        try:
            try:
                with trace_span("phase:execute", kind="phase"):
                    result = final_plan.execute_collect(ctx)
            except SpeculativeSizingMiss:
                # a capacity guess undershot (guard came back false):
                # nothing was surfaced — but any cache materialization
                # this run streamed is built on truncated batches and
                # must be discarded before the exact re-execution
                from ..obs import metrics as m
                m.counter("tpu_queries_retried_total",
                          "speculation-miss exact re-executions").inc()
                from ..io.cached_batch import CacheWriteExec

                def _reset_cache(node):
                    if isinstance(node, CacheWriteExec):
                        node.entry.materialized = False
                        node.entry.partitions = []
                        node.entry.schema = None
                final_plan.foreach(_reset_cache)
                if tracer is not None:
                    # abandoned generators never see the exception:
                    # close their spans now so the re-execution starts
                    # from a consistent trace
                    tracer.interrupt("speculation-miss")
                self.release_plan_shuffles(final_plan)
                with trace_span("phase:plan-retry", kind="phase"):
                    final_plan = self.prepare_plan(lp)
                if ticket is not None and ticket.repaired:
                    # the retry re-planned from scratch: re-shrink the
                    # fresh plan so it still fits the admitted ticket
                    from ..memory.admission import AdmissionController
                    ctrl = AdmissionController.get()
                    if ctrl is not None:
                        self._repair_for_admission(final_plan,
                                                   ctrl.budget_bytes)
                self._obs_plan = final_plan
                self._install_predictions(tracer, final_plan)
                ctx = ExecContext(self.conf)
                ctx.task_context["no_speculation"] = True
                # the retry re-planned: point the re-planner at the
                # fresh plan/context (its ticket carries over)
                rctx.plan_root = final_plan
                rctx.exec_ctx = ctx
                replan_mod.scan_materialized(rctx)
                with trace_span("phase:execute-retry", kind="phase"):
                    result = final_plan.execute_collect(ctx)
        except BaseException:
            # an aborted query routinely strands buffers; the original
            # error must surface, not a misleading leak report
            self.release_plan_shuffles(final_plan)
            if debug:
                cat.debug = False
            if memsan_on:
                self.last_peak_device_bytes = ledger.peak_device_bytes
                if tracer is not None:
                    tracer.measured_peak_device_bytes = \
                        ledger.peak_device_bytes
                self._memsan_uninstall(memsan)
            raise
        finally:
            replan_mod.uninstall()
        with trace_span("phase:release", kind="phase"):
            self.release_plan_shuffles(final_plan)
            if memsan_on:
                try:
                    # everything the query registered must have reached
                    # CLOSED (pinned scan caches are sanctioned residents);
                    # leaks surface with owning-exec provenance
                    try:
                        ledger.assert_clean()
                    except BaseException:
                        from ..obs import metrics as m
                        m.counter("tpu_memsan_dirty_ledgers_total",
                                  "queries whose shadow ledger was dirty "
                                  "(leak or lifecycle violation)").inc()
                        raise
                finally:
                    self.last_peak_device_bytes = ledger.peak_device_bytes
                    if tracer is not None:
                        tracer.measured_peak_device_bytes = \
                            ledger.peak_device_bytes
                    self._memsan_uninstall(memsan)
            if debug:
                leaks = [l for l in cat.leak_report() if l[0] not in before]
                cat.debug = False
                if leaks:
                    detail = "\n---\n".join(
                        f"{i} tier={t_} bytes={b}\n{st}"
                        for i, t_, b, st in leaks)
                    from ..memory.memsan import LifecycleViolation
                    raise LifecycleViolation(
                        f"query leaked {len(leaks)} spillable "
                        f"buffer(s) (memory.tpu.debug):\n{detail}")
        if tracer is not None:
            self._flush_query_obs(tracer, None, eventlog_dir)
        return result

    def _memsan_uninstall(self, memsan) -> None:
        if self._obs_isolation:
            memsan.uninstall_local()
        else:
            memsan.uninstall()

    # -- byte-weighted admission (multi-tenant serving) ---------------------

    def _admit_plan(self, final_plan):
        """Admission for one prepared plan: its tmsan static peak-
        device-bytes bound (TPU-L014) is the ticket.  A bound past the
        whole budget first re-plans through the out-of-core repair so
        the re-analyzed bound fits; then the ticket queues FIFO in the
        controller.  Returns (ticket, controller), (None, None) when
        admission is unconfigured — the single-tenant fast path."""
        from ..memory.admission import AdmissionController
        controller = AdmissionController.get()
        if controller is None:
            return None, None
        conf = self.conf
        bound = self._static_peak_bound(final_plan, conf)
        repaired = False
        if bound is not None and bound > controller.budget_bytes:
            repaired = self._repair_for_admission(
                final_plan, controller.budget_bytes)
            if repaired:
                bound = self._static_peak_bound(
                    final_plan, conf,
                    budget=controller.budget_bytes) or bound
        ticket = controller.admit(
            0 if bound is None else int(bound),
            label=type(final_plan).__name__,
            timeout_s=conf.get(cfg.SERVE_ADMISSION_TIMEOUT_MS) / 1000.0,
            repaired=repaired,
            # pool sessions carry their slot id (api/pool.py); a
            # standalone session books under the default tenant
            tenant=getattr(self, "_tenant", ""))
        return ticket, controller

    def _static_peak_bound(self, final_plan, conf,
                           budget=None) -> Optional[int]:
        """The plan's conservative peak-HBM bound from the lifetime
        pass; None when the analyzer cannot produce one (the query then
        rides an unweighted 0-byte ticket — admission stays advisory,
        never wrong-side-blocking)."""
        try:
            from ..analysis.lifetime import analyze_memory
            c = conf if budget is None else \
                conf.set(cfg.MEMSAN_HBM_BUDGET.key, int(budget))
            b = analyze_memory(final_plan, c).bound(final_plan)
            return None if b is None else int(b)
        except Exception:
            return None

    def _repair_for_admission(self, final_plan, budget) -> bool:
        """Re-plan an oversized ticket through the existing TPU-L014
        repair: run the lifetime pass against the ADMISSION budget and
        force oc_budget on each repairable frontier node (sort /
        aggregate merge), so the query co-runs out-of-core instead of
        hogging the whole budget."""
        try:
            from ..analysis.lifetime import (analyze_memory,
                                             try_outofcore_repair)
            conf2 = self.conf.set(cfg.MEMSAN_HBM_BUDGET.key,
                                  int(budget))
            res = analyze_memory(final_plan, conf2)
            done = False
            for d in res.diags:
                if d.code == "TPU-L014" and d.node is not None:
                    try:
                        done = try_outofcore_repair(
                            final_plan, d.node, conf2) or done
                    except Exception:
                        pass  # unrepairable node: queue at full size
            return done
        except Exception:
            return False

    # -- continuous metrics -------------------------------------------------
    _health_monitor = None

    def metrics_snapshot(self) -> Dict:
        """The JSON health document the /healthz endpoint serves —
        status derived from arena exhaustion, memsan ledger state,
        heartbeat misses and device-probe liveness — plus the full
        Prometheus exposition text under ``prometheus`` (the same
        surface without running an HTTP server)."""
        from ..obs.health import HealthMonitor, render_prometheus
        if TpuSession._health_monitor is None:
            TpuSession._health_monitor = HealthMonitor()
        snap = TpuSession._health_monitor.snapshot()
        snap["prometheus"] = render_prometheus()
        return snap

    def hbm_report(self) -> Dict:
        """The HBM observatory's occupancy-attribution answer: each
        tenant's resident device bytes split into pinned vs demotable
        (spillable-now) vs closed-pending, plus staging-arena fill and
        admission reservations (obs/memprof.py).  Returns a
        disabled-shaped report when hbm.timeline.enabled is off."""
        from ..obs.memprof import MemoryTimeline
        return MemoryTimeline.get().report()

    # -- flight recorder ----------------------------------------------------
    def last_query_trace(self):
        """The obs.QueryTrace of the last traced query (None when both
        spark.rapids.tpu.trace.enabled and eventLog.dir were unset)."""
        return self._last_trace

    def last_query_profile(self) -> Optional[Dict]:
        """The host ledger's record of this session's last top-level
        query: ``{id, wall_ns, segments: {segment: self_ns}, spans:
        {name: [count, inclusive_ns]}, off_thread_ns}``; the segments sum
        to the wall.  None until a query has run with
        spark.rapids.sql.profile.traceAnnotations on."""
        return self._last_profile

    def _install_predictions(self, tracer, final_plan) -> None:
        """Attach the CBO/interp row+byte model and tmsan's static
        peak-HBM bound to the trace, keyed by plan node — actuals are
        recorded at span close and the pair feeds `tools profile
        --accuracy` (the feedback signal for CBO tuning)."""
        if tracer is None:
            return
        try:
            from ..analysis.interp import infer_plan
            from ..analysis.lifetime import analyze_memory, total_bytes
            from ..obs.estimator import signature_of
            interp = infer_plan(final_plan, self.conf)
            mem = analyze_memory(final_plan, self.conf, interp)

            def visit(n):
                st = interp.state(n)
                if st is None:
                    return
                bound = mem.bound(n)
                tracer.predictions[id(n)] = {
                    "node": type(n).__name__,
                    "sig": signature_of(n),
                    "rows": None if st.rows is None else int(st.rows),
                    "bytes": int(total_bytes(st)),
                    "peakHbmBound": None if bound is None
                    else int(bound),
                }
            final_plan.foreach(visit)
            bound = mem.bound(final_plan)
            tracer.static_peak_bound = bound
            # the progress observatory blends the same per-node row
            # model into its ETA — feed it the ledger we just built
            from ..obs import progress as prog
            handle = prog.current_handle()
            if handle is not None:
                handle.set_predictions(tracer.predictions)
        except Exception:
            # the model is advisory: an analyzer crash must degrade the
            # accuracy report, never the query
            pass

    def _flush_query_obs(self, tracer, error, eventlog_dir) -> None:
        """Seal the trace and append the query to the event log — the
        single exit point for success, speculation-retry and failure
        paths alike (idempotent: re-entry on a writer error is a no-op).
        """
        if tracer is None or getattr(tracer, "_flush_done", False):
            return
        tracer._flush_done = True
        final_plan = self._obs_plan
        if final_plan is not None:
            try:
                from ..exec.base import drain_plan_metrics
                drain_plan_metrics(final_plan)  # ONE device crossing
            except Exception:
                pass  # a dead device must not mask the query's error
        tracer.finalize(error=error)
        try:
            # distill predicted-vs-actual into the estimator ledger —
            # the signal the feedback blend and `bench --accuracy` read
            from ..obs.estimator import EstimatorLedger
            EstimatorLedger.get().record_query(
                tracer.predictions, tracer.actuals,
                static_bound=getattr(tracer, "static_peak_bound", None),
                measured_peak=getattr(
                    tracer, "measured_peak_device_bytes", None))
        except Exception:
            pass  # grading is advisory; never mask the query's outcome
        try:
            # critical-path extraction + SLO accounting: annotates the
            # root span (so the event-log write below carries it into
            # Perfetto), bumps the per-segment counters and feeds the
            # latency observatory's burn window / tail reservoir
            from ..obs.critpath import record_query_latency
            record_query_latency(
                tracer, tenant=getattr(self, "_tenant", "") or "default",
                error=error,
                label=type(final_plan).__name__ if final_plan is not None
                else "")
        except Exception:
            pass  # attribution is advisory; never mask the query's outcome
        if eventlog_dir is None or final_plan is None:
            return
        sql_id = tracer.sql_id
        try:
            writer = self._event_log_writer(eventlog_dir)
            writer.write_query(
                sql_id, final_plan, tracer,
                error=repr(error) if error is not None else None,
                description=f"{type(final_plan).__name__} "
                            f"(query {sql_id})")
        except Exception:
            if error is None:
                raise  # an unwritable event log must surface somewhere
            # ...but never by masking the query's own failure

    def _maybe_postmortem(self, error, tracer) -> None:
        """Failure black box: dump a bounded post-mortem bundle for a
        failed query (operator error, dirty memsan ledger, admission
        timeout — they all unwind through here).  Strictly best-effort:
        a black-box crash must never mask the query's own error."""
        try:
            conf = self.conf
            if not conf.get(cfg.HBM_POSTMORTEM_ENABLED):
                return
            out_dir = conf.get(cfg.HBM_POSTMORTEM_DIR) or \
                conf.get(cfg.REGRESS_HISTORY_DIR)
            if not out_dir:
                return
            from ..obs.postmortem import dump_postmortem
            path = dump_postmortem(
                out_dir, error, session=self, tracer=tracer,
                plan=self._obs_plan,
                tenant=getattr(self, "_tenant", "") or "default",
                max_bundles=conf.get(cfg.HBM_POSTMORTEM_MAX_BUNDLES))
            if path and tracer is not None:
                # point the self-emitted event log at the bundle: the
                # writer records the sealed trace's spans, so a late
                # instant span is visible in the JobFailed group
                eventlog_dir = conf.get(cfg.EVENT_LOG_DIR)
                if eventlog_dir:
                    try:
                        writer = self._event_log_writer(eventlog_dir)
                        writer.write_postmortem_pointer(path)
                    except Exception:
                        pass
        except Exception:
            pass

    def _event_log_writer(self, directory: str):
        w = self._obs_writer
        if w is None or w.directory != directory:
            import uuid
            from ..obs.eventlog_writer import EventLogWriter
            w = EventLogWriter(
                directory, app_id=f"tpu-{uuid.uuid4().hex[:12]}",
                spark_version=getattr(self.shim, "version", ""),
                conf_map=self._conf_map)
            self._obs_writer = w
        return w

    def explain(self, lp: L.LogicalPlan) -> str:
        final_plan = self.prepare_plan(lp, run_subqueries=False)
        return final_plan.tree_string() + "\n--\n" + self.last_explain


class _Builder:
    def __init__(self):
        self._conf: Dict = {}

    def config(self, key, value):
        self._conf[key] = value
        return self

    def get_or_create(self) -> TpuSession:
        return TpuSession(self._conf)


def last_query_metrics(session: TpuSession, level: str = None):
    """(operator, metric, value) rows from the last executed plan at the
    configured verbosity (ref GpuMetric levels feeding the SQL UI)."""
    from ..exec.base import metrics_report
    lvl = level or session.conf.get(cfg.METRICS_LEVEL)
    if session.last_plan is None:
        return []
    return metrics_report(session.last_plan, lvl)
