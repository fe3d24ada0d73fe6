"""Progress observatory: live in-flight query introspection, ETA,
cooperative cancellation/deadlines, and the stuck-query watchdog.

Every observatory before this one (tracer, estimator, HBM, latency) is
post-hoc: it explains a query after it closed.  The reference plugin
leans on Spark's listener bus and live UI for in-flight visibility; we
own the whole execution loop, so we own the live surface too.

One process-wide :class:`ProgressTracker` keeps a bounded live view per
in-flight query, fed from three existing seams with no per-operator
edits:

* **operator open/batch/close** — ``exec.base._wrap_execute_partition``
  (the ``Exec.__init_subclass__`` instrumentation point the flight
  recorder already rides) additionally routes each produced iterator
  through :meth:`_QueryHandle.observe_operator`, which notes operator
  starts, per-batch row counts, and partition completions;
* **phase transitions** — ``tracer.trace_span`` notifies
  :func:`note_span_open` for ``phase:*`` and ``admission.wait`` spans,
  so the live view's ``phase`` tracks planning -> queued -> executing
  without the session narrating each step;
* **the planner's model** — the session hands the handle the same
  per-node row predictions it installs on the trace
  (:meth:`_QueryHandle.set_predictions`), so rows-so-far reads against
  the estimator ledger's predicted rows.

The ETA blends the two progress signals the same confidence-weighted
way ``plan/cost.estimate_rows`` blends ledger feedback into the static
model: ``w = clamp(n/(n+1), [0.25, 0.9])`` with ``n`` = closed
partition count, ``ratio = w*partitions + (1-w)*rows``.  The published
ratio is clamped monotone (a new operator registering its partition
total grows the denominator; the view must never appear to move
backwards) and reconciles to the sealed trace's span counts at query
end: closed partitions == closed operator spans, by construction.

**Cooperative cancellation.**  ``begin_query`` mints a
:class:`CancelToken` bound thread-local to the executing thread.
``TpuSession.cancel`` / ``SessionPool.cancel`` (or a deadline, or the
watchdog) set its flag; the flag is CHECKED — never preempted — at the
three blocking seams: partition boundaries
(``exec.base.Exec.execute_collect``), the admission queue wait
(``memory.admission.AdmissionController.admit``, which also registers
the controller's condition variable as a waker so a cancelled waiter
wakes immediately, leaves the FIFO through the existing ``finally``,
and notifies survivors), and the async shuffle fetch loop
(``shuffle.transport.AsyncBlockFetcher.blocks``).  Each checkpoint
raises the typed :class:`TpuQueryCancelled` /
:class:`TpuQueryDeadlineExceeded`, which unwind through the existing
release-obligation machinery — admission tickets, tracer spans,
shuffle blocks and spill registrations all release in the same
finally/except arms every other failure uses (tpufsan R012).

**Watchdog.**  Poll-driven like the rest of the health surface (no
thread of its own): every ``watchdog_scan`` — called from health
snapshots, ``GET /queries`` and the ``--progress`` gate — flags
queries with no progress event for ``watchdog.stallSeconds``, names
the deepest open operator span, emits one stall record to the failure
black box, and past ``watchdog.autoCancelSeconds`` of stall cancels
the query with cause ``watchdog``.

Metrics: ``tpu_queries_inflight{phase}``,
``tpu_query_progress_ratio{tenant}``,
``tpu_cancellations_total{cause}``, ``tpu_query_stalls_total``.
Exposition: ``GET /queries`` (obs/health.py) and ``tools top``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

#: host-resident scalar types safe to int() on the hot path — a traced
#: device scalar would force a sync (the tracer's deferred-fetch
#: discipline; rows it defers are counted by the trace, not the view)
_HOST_NUMS = (int, float, bool, np.integer, np.floating, np.bool_)

#: finished-query ring kept for /queries "recent" context
FINISHED_RING = 32

#: confidence-weight clamp for the partition/rows blend — the same
#: floor/cap the estimator feedback blend defaults to (obs/estimator).
BLEND_FLOOR = 0.25
BLEND_CAP = 0.9

#: below this blended ratio the ETA is noise, not a forecast
ETA_MIN_RATIO = 0.02

INFLIGHT_FAMILY = "tpu_queries_inflight"
RATIO_FAMILY = "tpu_query_progress_ratio"
CANCEL_FAMILY = "tpu_cancellations_total"
STALL_FAMILY = "tpu_query_stalls_total"

CAUSE_CLIENT = "client"
CAUSE_DEADLINE = "deadline"
CAUSE_WATCHDOG = "watchdog"

PHASE_STARTING = "starting"
PHASE_PLANNING = "planning"
PHASE_QUEUED = "queued"
PHASE_EXECUTING = "executing"

_PHASE_BY_SPAN = {
    "phase:host_assist": PHASE_PLANNING,
    "phase:plan": PHASE_PLANNING,
    "phase:planning": PHASE_PLANNING,
    "phase:subqueries": PHASE_PLANNING,
    "phase:overrides": PHASE_PLANNING,
    "phase:plan-retry": PHASE_PLANNING,
    "admission.wait": PHASE_QUEUED,
    "phase:execute": PHASE_EXECUTING,
    "phase:execute-retry": PHASE_EXECUTING,
}


class TpuQueryCancelled(RuntimeError):
    """The query observed its cancel flag at a cooperative checkpoint.

    ``cause`` is who set the flag (``client`` or ``watchdog``);
    ``checkpoint`` is which seam observed it (``compute`` /
    ``queue_wait`` / ``remote_fetch``); ``operator`` is the exec whose
    loop saw the flag, when one was running."""

    cause = CAUSE_CLIENT

    def __init__(self, message: str = "query cancelled",
                 query_id: Optional[str] = None,
                 operator: Optional[str] = None,
                 checkpoint: Optional[str] = None,
                 cause: Optional[str] = None):
        super().__init__(message)
        self.query_id = query_id
        self.operator = operator
        self.checkpoint = checkpoint
        if cause is not None:
            self.cause = cause


class TpuQueryDeadlineExceeded(RuntimeError):
    """The query ran past its ``deadline_ms`` and a cooperative
    checkpoint observed the expiry.  Deliberately NOT a subclass of
    :class:`TpuQueryCancelled`: the two are accounted differently — a
    client cancel is excluded from the tenant's SLO burn window (the
    engine didn't miss), a blown deadline counts BAD."""

    cause = CAUSE_DEADLINE

    def __init__(self, message: str = "query deadline exceeded",
                 query_id: Optional[str] = None,
                 operator: Optional[str] = None,
                 checkpoint: Optional[str] = None):
        super().__init__(message)
        self.query_id = query_id
        self.operator = operator
        self.checkpoint = checkpoint


def _registry():
    from . import metrics
    return metrics.registry()


def _fam_inflight():
    return _registry().gauge(
        INFLIGHT_FAMILY,
        "in-flight queries by live-view phase (obs/progress.py)",
        ("phase",))


def _fam_ratio():
    return _registry().gauge(
        RATIO_FAMILY,
        "latest blended progress ratio per tenant (monotone per "
        "query; partitions/rows confidence blend)", ("tenant",))


def _fam_cancellations():
    return _registry().counter(
        CANCEL_FAMILY,
        "typed cancellations that actually propagated, by cause "
        "(client / deadline / watchdog)", ("cause",))


def _fam_stalls():
    return _registry().counter(
        STALL_FAMILY,
        "queries the stuck-query watchdog flagged (no progress for "
        "watchdog.stallSeconds)")


class CancelToken:
    """One query's cancel flag + optional deadline.

    Setting the flag never interrupts anything by force: the running
    query observes it at the next cooperative checkpoint.  ``wakers``
    are condition variables of seams that BLOCK (the admission queue
    wait) — ``cancel()`` notifies them so a queued query unwinds
    immediately instead of sleeping out its admission timeout."""

    __slots__ = ("query_id", "tenant", "cause", "deadline_mono",
                 "_flag", "_lock", "_wakers")

    def __init__(self, query_id: str, tenant: str,
                 deadline_ms: Optional[int] = None):
        self.query_id = query_id
        self.tenant = tenant
        self.cause: Optional[str] = None
        self.deadline_mono = (
            None if deadline_ms is None
            else time.monotonic() + deadline_ms / 1000.0)
        self._flag = False
        self._lock = threading.Lock()
        self._wakers: List[Any] = []

    def cancel(self, cause: str = CAUSE_CLIENT) -> None:
        with self._lock:
            if self._flag:
                return
            self._flag = True
            self.cause = cause
            wakers = list(self._wakers)
        for cv in wakers:
            try:
                with cv:
                    cv.notify_all()
            except Exception:
                pass  # a dead waiter's cv must not block the rest

    @property
    def cancelled(self) -> bool:
        return self._flag

    @property
    def deadline_exceeded(self) -> bool:
        return self.deadline_mono is not None and \
            time.monotonic() > self.deadline_mono

    def deadline_remaining_s(self) -> Optional[float]:
        if self.deadline_mono is None:
            return None
        return self.deadline_mono - time.monotonic()

    def add_waker(self, cv) -> None:
        with self._lock:
            self._wakers.append(cv)

    def remove_waker(self, cv) -> None:
        with self._lock:
            try:
                self._wakers.remove(cv)
            except ValueError:
                pass

    def describe(self, checkpoint: str,
                 operator: Optional[str] = None) -> str:
        """Message body for the typed error a checkpoint raises."""
        where = f" in {operator}" if operator else ""
        if self.deadline_exceeded and not self._flag:
            return (f"query {self.query_id} exceeded its deadline "
                    f"(observed at {checkpoint}{where})")
        return (f"query {self.query_id} cancelled by "
                f"{self.cause or CAUSE_CLIENT} "
                f"(observed at {checkpoint}{where})")

    def check(self, checkpoint: str = "compute",
              operator: Optional[str] = None) -> None:
        """Raise the typed error when the flag or deadline tripped —
        the per-batch checkpoint the operator wrapper calls.  The
        blocking seams (admission wait, fetch loop, partition loop)
        keep their own explicit raise sites so tpufsan's static reach
        sees the (seam, error) pairs."""
        if self._flag:
            raise TpuQueryCancelled(
                self.describe(checkpoint, operator),
                query_id=self.query_id, operator=operator,
                checkpoint=checkpoint, cause=self.cause)
        if self.deadline_exceeded:
            raise TpuQueryDeadlineExceeded(
                self.describe(checkpoint, operator),
                query_id=self.query_id, operator=operator,
                checkpoint=checkpoint)


class _OpStats:
    __slots__ = ("op", "total", "done", "rows", "open",
                 "predicted_rows")

    def __init__(self, op: str, total: Optional[int]):
        self.op = op
        self.total = total
        self.done = 0
        self.rows = 0
        self.open = 0
        self.predicted_rows: Optional[int] = None


def _static_partitions(node) -> Optional[int]:
    """A node's partition count WITHOUT triggering lazy materialization
    (the estimator's signature-probe discipline: an AQE reader's
    ``num_partitions`` property runs the map stage)."""
    try:
        if hasattr(node, "exchange") and hasattr(node, "_specs"):
            return getattr(node.exchange, "num_partitions", None)
        return getattr(node, "num_partitions", None)
    except Exception:
        return None


class _QueryHandle:
    """One in-flight query's live record: the unit the tracker stores,
    ``/queries`` renders, and the checkpoints consult via the
    thread-local binding."""

    def __init__(self, tracker: "ProgressTracker", query_id: str,
                 tenant: str, label: str,
                 deadline_ms: Optional[int]):
        self._tracker = tracker
        self.query_id = query_id
        self.tenant = tenant
        self.label = label
        self.token = CancelToken(query_id, tenant,
                                 deadline_ms=deadline_ms)
        self.deadline_ms = deadline_ms
        self.started_mono = time.monotonic()
        self.started_wall_ms = int(time.time() * 1000)
        self.phase = PHASE_STARTING
        self.last_progress_mono = self.started_mono
        self._lock = threading.Lock()
        self._ops: Dict[int, _OpStats] = {}   # keyed by id(node)
        self._open_order: List[int] = []      # open node ids, FIFO
        self.predicted_rows_total: Optional[int] = None
        self._best_ratio = 0.0
        self.stalled = False
        self.stall_reported = False
        self.cancel_counted = False
        self.cancel_observed_at: Optional[str] = None
        self.cancel_observed_operator: Optional[str] = None
        self.finished = False
        self.error_type: Optional[str] = None
        self.overhead_ns = 0

    # -- feed side -----------------------------------------------------------
    def touch(self) -> None:
        with self._lock:
            self.last_progress_mono = time.monotonic()
            self.stalled = False

    def set_phase(self, phase: str) -> None:
        with self._lock:
            old = self.phase
            if phase == old:
                return
            self.phase = phase
        self.touch()
        self._tracker._phase_moved(old, phase)

    def set_predictions(self, predictions: Optional[Dict]) -> None:
        """Install the planner's per-node row model (the same dict the
        session installs on the trace: id(node) -> {"rows": ...})."""
        if not predictions:
            return
        total = 0
        seen = False
        with self._lock:
            for nid, pred in predictions.items():
                rows = pred.get("rows")
                if rows is None:
                    continue
                seen = True
                total += int(rows)
                st = self._ops.get(nid)
                if st is not None:
                    st.predicted_rows = int(rows)
                else:
                    st = _OpStats(pred.get("node", "?"), None)
                    st.predicted_rows = int(rows)
                    self._ops[nid] = st
            if seen:
                self.predicted_rows_total = total

    def _op_open(self, node) -> int:
        t0 = time.perf_counter_ns()
        nid = id(node)
        with self._lock:
            st = self._ops.get(nid)
            if st is None:
                st = _OpStats(type(node).__name__,
                              _static_partitions(node))
                self._ops[nid] = st
            else:
                st.op = type(node).__name__
                if st.total is None:
                    st.total = _static_partitions(node)
            st.open += 1
            self._open_order.append(nid)
        self.touch()
        self.overhead_ns += time.perf_counter_ns() - t0
        return nid

    def _op_batch(self, nid: int, batch) -> None:
        t0 = time.perf_counter_ns()
        n = getattr(batch, "num_rows", None)
        with self._lock:
            st = self._ops.get(nid)
            if st is not None and isinstance(n, _HOST_NUMS):
                st.rows += int(n)
        self.touch()
        self.overhead_ns += time.perf_counter_ns() - t0

    def _op_close(self, nid: int) -> None:
        t0 = time.perf_counter_ns()
        with self._lock:
            st = self._ops.get(nid)
            if st is not None:
                st.open = max(st.open - 1, 0)
                st.done += 1
            try:
                # remove the LAST occurrence: nested same-node opens
                # (retries) close innermost-first
                for i in range(len(self._open_order) - 1, -1, -1):
                    if self._open_order[i] == nid:
                        del self._open_order[i]
                        break
            except Exception:
                pass
        self.touch()
        self._tracker._publish_ratio(self)
        self.overhead_ns += time.perf_counter_ns() - t0

    def observe_operator(self, node, pid: int, inner):
        """Wrap one execute_partition iterator: note open/batch/close
        in the live view and check the cancel flag before every batch
        pull — the per-batch cooperative checkpoint."""
        it = iter(inner)
        tok = self.token

        def gen():
            nid = self._op_open(node)
            try:
                while True:
                    tok.check(checkpoint="compute",
                              operator=type(node).__name__)
                    try:
                        b = next(it)
                    except StopIteration:
                        break
                    self._op_batch(nid, b)
                    yield b
            finally:
                self._op_close(nid)

        return gen()

    # -- read side -----------------------------------------------------------
    def deepest_open_operator(self) -> Optional[str]:
        """The most recently opened still-open operator — the span the
        watchdog names (the innermost frame of the stuck stack)."""
        with self._lock:
            if not self._open_order:
                return None
            st = self._ops.get(self._open_order[-1])
            return st.op if st is not None else None

    def progress_ratio(self) -> float:
        """Confidence-weighted blend of partition progress and row
        progress, clamped monotone per query."""
        with self._lock:
            done = sum(st.done for st in self._ops.values())
            total = sum(st.total for st in self._ops.values()
                        if st.total)
            rows = sum(st.rows for st in self._ops.values())
            pred = self.predicted_rows_total
        part_ratio = min(done / total, 1.0) if total else None
        rows_ratio = min(rows / pred, 1.0) if pred else None
        if part_ratio is None and rows_ratio is None:
            ratio = 0.0
        elif rows_ratio is None:
            ratio = part_ratio
        elif part_ratio is None:
            ratio = rows_ratio
        else:
            w = min(BLEND_CAP, max(BLEND_FLOOR, done / (done + 1.0)))
            ratio = w * part_ratio + (1.0 - w) * rows_ratio
        if self.finished and self.error_type is None:
            ratio = 1.0
        with self._lock:
            if ratio > self._best_ratio:
                self._best_ratio = ratio
            return self._best_ratio

    def eta_s(self) -> Optional[float]:
        ratio = self.progress_ratio()
        if self.finished or ratio < ETA_MIN_RATIO:
            return None
        elapsed = time.monotonic() - self.started_mono
        return elapsed * (1.0 - ratio) / ratio

    def snapshot(self) -> Dict[str, Any]:
        now = time.monotonic()
        with self._lock:
            ops = {}
            for st in self._ops.values():
                agg = ops.setdefault(
                    st.op, {"done": 0, "total": 0, "rows": 0,
                            "open": 0, "predicted_rows": 0})
                agg["done"] += st.done
                agg["total"] += st.total or 0
                agg["rows"] += st.rows
                agg["open"] += st.open
                agg["predicted_rows"] += st.predicted_rows or 0
            rows = sum(st.rows for st in self._ops.values())
            done = sum(st.done for st in self._ops.values())
        eta = self.eta_s()
        return {
            "query": self.query_id,
            "tenant": self.tenant,
            "label": self.label,
            "phase": self.phase,
            "started_wall_ms": self.started_wall_ms,
            "elapsed_s": round(now - self.started_mono, 6),
            "operators": ops,
            "partitions_done": done,
            "rows": rows,
            "predicted_rows": self.predicted_rows_total,
            "progress_ratio": round(self.progress_ratio(), 6),
            "eta_s": None if eta is None else round(eta, 6),
            "deadline_ms": self.deadline_ms,
            "cancelled": self.token.cancelled,
            "cancel_cause": self.token.cause,
            "stalled": self.stalled,
            "deepest_open_operator": self.deepest_open_operator(),
            "last_progress_s_ago":
                round(now - self.last_progress_mono, 6),
            "finished": self.finished,
            "error": self.error_type,
        }


class ProgressTracker:
    """Process-wide live view of in-flight queries (singleton like the
    compile/estimator/latency observatories)."""

    _instance: Optional["ProgressTracker"] = None
    _ilock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self.enabled = True
        self.max_queries = 64
        self.stall_seconds = 30.0
        self.auto_cancel_seconds: Optional[float] = None
        self._live: Dict[tuple, _QueryHandle] = {}
        self._finished = deque(maxlen=FINISHED_RING)
        self._seq = 0

    @classmethod
    def get(cls) -> "ProgressTracker":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = ProgressTracker()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "ProgressTracker":
        with cls._ilock:
            cls._instance = ProgressTracker()
            return cls._instance

    def configure(self, enabled: Optional[bool] = None,
                  max_queries: Optional[int] = None,
                  stall_seconds: Optional[float] = None,
                  auto_cancel_seconds: Optional[float] = None
                  ) -> "ProgressTracker":
        """Session-init wiring; idempotent, None leaves values alone
        (pool sessions all configure with the same conf)."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if max_queries is not None:
                self.max_queries = int(max_queries)
            if stall_seconds is not None:
                self.stall_seconds = float(stall_seconds)
            if auto_cancel_seconds is not None:
                self.auto_cancel_seconds = float(auto_cancel_seconds)
        return self

    # -- lifecycle ------------------------------------------------------------
    def begin_query(self, query_id: str, tenant: str = "default",
                    label: str = "",
                    deadline_ms: Optional[int] = None
                    ) -> Optional[_QueryHandle]:
        if not self.enabled:
            return None
        tenant = tenant or "default"
        h = _QueryHandle(self, query_id, tenant, label, deadline_ms)
        with self._lock:
            self._seq += 1
            # bounded live view: a leaked registration (a crash that
            # skipped end_query) must not grow this forever — evict
            # the oldest entry past the cap, never reallocate
            while len(self._live) >= self.max_queries:
                old_key = next(iter(self._live))
                old = self._live.pop(old_key)
                self._phase_moved(old.phase, None)
            self._live[(tenant, query_id)] = h
        try:
            _fam_inflight().labels(phase=h.phase).gauge_inc()
        except Exception:
            pass
        return h

    def end_query(self, handle: Optional[_QueryHandle],
                  error: Optional[BaseException] = None) -> None:
        if handle is None:
            return
        handle.finished = True
        handle.error_type = type(error).__name__ \
            if error is not None else None
        if isinstance(error, (TpuQueryCancelled,
                              TpuQueryDeadlineExceeded)):
            handle.cancel_observed_at = getattr(error, "checkpoint",
                                                None)
            handle.cancel_observed_operator = getattr(error,
                                                      "operator", None)
            self.count_cancellation(handle, getattr(
                error, "cause", CAUSE_CLIENT) or CAUSE_CLIENT)
        with self._lock:
            was_live = self._live.pop(
                (handle.tenant, handle.query_id), None) is not None
            self._finished.append(handle)
        if was_live:  # an evicted handle already decremented its phase
            self._phase_moved(handle.phase, None)
        self._publish_ratio(handle)

    def count_cancellation(self, handle: Optional[_QueryHandle],
                           cause: str) -> None:
        """Count one PROPAGATED cancellation (at most once per query —
        several checkpoints may observe the same flag)."""
        if handle is not None:
            if handle.cancel_counted:
                return
            handle.cancel_counted = True
        try:
            _fam_cancellations().labels(cause=cause).inc()
        except Exception:
            pass

    # -- cancellation ---------------------------------------------------------
    def cancel(self, query_id: str, tenant: Optional[str] = None,
               cause: str = CAUSE_CLIENT) -> bool:
        """Set the cancel flag on a live query; returns whether a
        matching in-flight query was found.  ``tenant=None`` matches
        any tenant (single-session use)."""
        with self._lock:
            targets = [h for (t, q), h in self._live.items()
                       if q == query_id and
                       (tenant is None or t == tenant)]
        for h in targets:
            h.token.cancel(cause)
        return bool(targets)

    # -- feed hooks -----------------------------------------------------------
    def _phase_moved(self, old: Optional[str],
                     new: Optional[str]) -> None:
        try:
            fam = _fam_inflight()
            if old is not None:
                fam.labels(phase=old).dec()
            if new is not None:
                fam.labels(phase=new).gauge_inc()
        except Exception:
            pass

    def _publish_ratio(self, handle: _QueryHandle) -> None:
        try:
            _fam_ratio().labels(tenant=handle.tenant).set(
                round(handle.progress_ratio(), 6))
        except Exception:
            pass

    # -- watchdog -------------------------------------------------------------
    def watchdog_scan(self, now: Optional[float] = None
                      ) -> List[Dict[str, Any]]:
        """Flag queries with no progress for ``stall_seconds``; emit
        one black-box stall record per stalled query; auto-cancel past
        ``auto_cancel_seconds``.  Returns the stall list (the health
        monitor's ``progress`` component signals)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            live = list(self._live.values())
            stall_s = self.stall_seconds
            auto_s = self.auto_cancel_seconds
        out = []
        for h in live:
            idle = now - h.last_progress_mono
            if stall_s <= 0 or idle < stall_s:
                continue
            h.stalled = True
            op = h.deepest_open_operator()
            rec = {"query": h.query_id, "tenant": h.tenant,
                   "phase": h.phase, "stalled_s": round(idle, 3),
                   "deepest_open_operator": op}
            if not h.stall_reported:
                h.stall_reported = True
                try:
                    _fam_stalls().inc()
                except Exception:
                    pass
                self._blackbox_stall(h, idle, op)
            if auto_s is not None and idle >= auto_s and \
                    not h.token.cancelled:
                h.token.cancel(CAUSE_WATCHDOG)
                rec["auto_cancelled"] = True
            out.append(rec)
        return out

    def _blackbox_stall(self, h: _QueryHandle, idle: float,
                        op: Optional[str]) -> None:
        """One stall record into the failure black box (best-effort,
        via the background-error router's bundle directory)."""
        try:
            from . import bgerrors
            err = RuntimeError(
                f"query {h.query_id} (tenant {h.tenant}) made no "
                f"progress for {idle:.1f}s in phase {h.phase}"
                + (f"; deepest open operator span: {op}" if op
                   else ""))
            bgerrors.note_background_error("watchdog", err)
        except Exception:
            pass

    # -- read side ------------------------------------------------------------
    def live_view(self, scan: bool = True) -> Dict[str, Any]:
        """The ``GET /queries`` document: every in-flight query's
        snapshot plus the recent finished ring.  ``scan`` runs the
        watchdog first so a scrape is also a liveness check."""
        stalls = self.watchdog_scan() if scan else []
        with self._lock:
            live = [h.snapshot() for h in self._live.values()]
            finished = [h.snapshot() for h in list(self._finished)]
        live.sort(key=lambda d: d["started_wall_ms"])
        return {
            "inflight": live,
            "stalled": stalls,
            "recent": finished[-FINISHED_RING:],
            "watchdog": {
                "stall_seconds": self.stall_seconds,
                "auto_cancel_seconds": self.auto_cancel_seconds,
            },
        }

    def overhead(self) -> Dict[str, float]:
        """Tracker self-time booked by the feed hooks (the <5%
        anti-vacuity figure's numerator)."""
        with self._lock:
            handles = list(self._live.values()) + list(self._finished)
        ns = sum(h.overhead_ns for h in handles)
        return {"hook_s": round(ns / 1e9, 6), "queries": len(handles)}


# ---------------------------------------------------------------------------
# thread-local binding (what the cooperative checkpoints consult)
# ---------------------------------------------------------------------------

_TLS = threading.local()


def bind_to_thread(handle: Optional[_QueryHandle]) -> None:
    """Bind (or with None, unbind) the calling thread's in-flight
    query handle — the session sets this around query execution so the
    checkpoints in exec/admission/shuffle find their token without
    plumbing it through every signature."""
    _TLS.handle = handle


def current_handle() -> Optional[_QueryHandle]:
    return getattr(_TLS, "handle", None)


def current_token() -> Optional[CancelToken]:
    h = getattr(_TLS, "handle", None)
    return h.token if h is not None else None


def note_span_open(name: str, kind: str) -> None:
    """Tracer hook: phase transitions for the live view.  Called by
    ``tracer.trace_span`` for phase spans and ``admission.wait``,
    whether or not a trace is recording; cheap no-op for threads with
    no bound handle."""
    h = getattr(_TLS, "handle", None)
    if h is None:
        return
    phase = _PHASE_BY_SPAN.get(name)
    if phase is not None:
        h.set_phase(phase)
