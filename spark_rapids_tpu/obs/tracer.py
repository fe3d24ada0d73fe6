"""Flight-recorder span tracer: the producer half of the diagnostic
story the reference plugin gets from GpuExec metrics + NVTX ranges +
Spark's event log.

One ``QueryTrace`` records a per-query span tree — session phases
(subqueries/planning/overrides/execute), per-operator per-partition
execute spans, out-of-core chunk spans, and instrumented events from the
memory/shuffle/parallel/bridge layers — under the same
deferred-device-scalar discipline as ``exec.base.Metric``: the hot path
never syncs or fetches; device row counts are stashed and resolved at
``finalize()`` through ONE ``columnar/fetch.fetch_ints`` crossing.
Timestamps come from the monotonic ``time.perf_counter_ns`` clock with a
wall-clock anchor captured once at trace start.

The buffer is bounded (``spark.rapids.tpu.trace.maxSpans``): past the
cap new spans are dropped and counted, never reallocated — a runaway
query degrades the trace, not the engine (Dapper-style always-on,
low-overhead discipline).

Instrumented modules reach the recorder through the installed-tracer
pattern the tmsan shadow ledger uses (``memory/memsan.py``): with no
query tracing, ``active_tracer()`` is None and every hook is a cheap
no-op.

``trace_span`` is ONE producer path with two sinks: the ``QueryTrace``
above (host clock, ``spark.rapids.tpu.trace.enabled``) and the
profiler's own clock (``spark.rapids.sql.profile.traceAnnotations``:
one ``jax.profiler.TraceAnnotation`` per span, so a device trace shows
engine phases, operators, dispatch and fetch beside the device's
operations).  ``open_range`` is the engine's only ``TraceAnnotation``.

While the profiler sink is on, every range is also a frame of the
``HostLedger``: each top-level query's wall time summed by segment
(``critpath.segment_of``) as its ranges close, the last queries' records
kept in memory (``host_ledger().records()``).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

# span kinds
QUERY = "query"
PHASE = "phase"
OPERATOR = "operator"
SPAN = "span"
EVENT = "event"

_HOST_NUMS = (int, float, bool, np.integer, np.floating, np.bool_)


class Span:
    """One recorded interval (or instant event, t1 == t0)."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "t0_ns", "t1_ns",
                 "tid", "status", "error", "attrs", "node_id", "pid",
                 "rows", "bytes", "batches", "cap_rows", "proc")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 kind: str, t0_ns: int, tid: int,
                 node_id: Optional[int] = None,
                 pid: Optional[int] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.t0_ns = t0_ns
        self.t1_ns: Optional[int] = None
        self.tid = tid
        self.status = "open"
        self.error: Optional[str] = None
        self.attrs = attrs or {}
        self.node_id = node_id
        self.pid = pid
        self.rows = 0
        self.bytes = 0
        self.batches = 0
        # summed static batch capacities (tpuxsan padding-waste books:
        # device bytes are capacity-sized, so waste = bytes * (1 -
        # rows/cap_rows) once deferred row counts resolve)
        self.cap_rows = 0
        # producing process for merged remote spans (executor id or
        # "server:<port>"); None = this process.  NOT `pid` — that slot
        # is the PARTITION id.
        self.proc: Optional[str] = None

    @property
    def dur_ns(self) -> int:
        return 0 if self.t1_ns is None else self.t1_ns - self.t0_ns

    def pad_waste_bytes(self) -> int:
        """Device bytes this span's output batches spent on capacity
        padding: bytes are capacity-sized, rows are live.  Only valid
        after deferred row counts resolve (finalize)."""
        if self.cap_rows <= 0 or self.bytes <= 0:
            return 0
        live = min(max(int(self.rows), 0), self.cap_rows)
        return int(self.bytes * (self.cap_rows - live) / self.cap_rows)


class _SpanHandle:
    """What ``QueryTrace.span()`` yields: lets the block attach attrs
    after the fact without reaching into tracer internals."""

    __slots__ = ("_trace", "_sid")

    def __init__(self, trace: "QueryTrace", sid: Optional[int]):
        self._trace = trace
        self._sid = sid

    def __bool__(self) -> bool:
        return self._sid is not None

    def set(self, **attrs) -> None:
        if self._sid is not None:
            self._trace.add_attrs(self._sid, **attrs)


class QueryTrace:
    """Thread-safe bounded span recorder for ONE query execution."""

    def __init__(self, max_spans: int = 65536):
        self.max_spans = max_spans
        self.t0_ns = time.perf_counter_ns()
        self.wall_start_ms = int(time.time() * 1000)
        self.spans: List[Span] = []
        self._by_id: Dict[int, Span] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        # deferred device scalars: (span, scalar) resolved at finalize
        # through ONE fetch_ints crossing (the Metric discipline)
        self._pending: List[tuple] = []
        self.dropped = 0
        self.sealed = False
        self.error: Optional[str] = None
        # predicted-vs-actual: id(exec node) -> dicts; predictions are
        # installed by the session from the CBO/interp/tmsan models,
        # actuals aggregate from operator spans at finalize
        self.predictions: Dict[int, Dict[str, Any]] = {}
        self.actuals: Dict[int, Dict[str, Any]] = {}
        self.measured_peak_device_bytes: Optional[int] = None
        self.static_peak_bound: Optional[float] = None
        # fleet identity: travels inside the shuffle wire's v2 trace
        # context so producer-side serve spans can be pulled back and
        # grafted under this trace's fetch spans
        from .fleet import new_trace_id
        self.trace_id = new_trace_id()
        # the id the session gave the query (the event log's sql_id)
        self.sql_id: Optional[int] = None
        self.remote_spans_merged = 0
        self.remote_spans_lost = 0
        self.root_id = self.start("query", QUERY)

    # -- parent stack (per thread) ------------------------------------------
    def _stack(self) -> List[int]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    def _push(self, sid: int) -> None:
        self._stack().append(sid)

    def _pop(self) -> None:
        st = self._stack()
        if st:
            st.pop()

    def _default_parent(self) -> Optional[int]:
        # spans with no enclosing span (any thread) hang off the query
        # root, so the tree always has one top
        st = self._stack()
        if st:
            return st[-1]
        return getattr(self, "root_id", None)

    # -- core ---------------------------------------------------------------
    def start(self, name: str, kind: str, node_id: Optional[int] = None,
              pid: Optional[int] = None, parent: Optional[int] = None,
              **attrs) -> Optional[int]:
        with self._lock:
            if self.sealed:
                return None
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
                return None
            sid = next(self._ids)
            if parent is None:
                parent = self._default_parent()
            sp = Span(sid, parent if parent != sid else None, name, kind,
                      time.perf_counter_ns(), threading.get_ident(),
                      node_id=node_id, pid=pid, attrs=dict(attrs))
            self.spans.append(sp)
            self._by_id[sid] = sp
        return sid

    def end(self, sid: Optional[int], status: str = "ok",
            error: Optional[str] = None) -> None:
        if sid is None:
            return
        with self._lock:
            sp = self._by_id.get(sid)
            if sp is None or sp.t1_ns is not None:
                return
            sp.t1_ns = time.perf_counter_ns()
            sp.status = status
            sp.error = error

    def event(self, name: str, **attrs) -> None:
        sid = self.start(name, EVENT, **attrs)
        if sid is not None:
            sp = self._by_id[sid]
            sp.t1_ns = sp.t0_ns
            sp.status = "ok"

    def add_attrs(self, sid: Optional[int], **attrs) -> None:
        if sid is None:
            return
        with self._lock:
            sp = self._by_id.get(sid)
            if sp is not None:
                sp.attrs.update(attrs)

    @contextlib.contextmanager
    def span(self, name: str, kind: str = SPAN, **attrs):
        sid = self.start(name, kind, **attrs)
        if sid is not None:
            self._push(sid)
        err: Optional[BaseException] = None
        try:
            yield _SpanHandle(self, sid)
        except BaseException as ex:
            err = ex
            raise
        finally:
            if sid is not None:
                self._pop()
                self.end(sid, "error" if err is not None else "ok",
                         repr(err) if err is not None else None)

    # -- operator spans ------------------------------------------------------
    def trace_operator(self, node, pid: int, inner):
        """Wrap one execute_partition iterator in an operator span: the
        span opens at first pull, accumulates output rows (deferred when
        the count is a traced device scalar — never a sync here), device
        bytes (array metadata only) and batches, and closes on
        exhaustion, abandonment (early-exit limits) or error — the
        exception is recorded on the span (post-mortem debugging)."""
        it = iter(inner)

        def gen():
            sid = self.start(f"{type(node).__name__}.execute", OPERATOR,
                             node_id=id(node), pid=pid,
                             op=type(node).__name__)
            try:
                while True:
                    if sid is not None:
                        self._push(sid)
                    try:
                        b = next(it)
                    except StopIteration:
                        break
                    finally:
                        if sid is not None:
                            self._pop()
                    if sid is not None:
                        self._note_batch(sid, b)
                    yield b
            except GeneratorExit:
                self.end(sid, "abandoned")
                raise
            except BaseException as ex:
                self.end(sid, "error", repr(ex))
                raise
            self.end(sid)

        return gen()

    def _note_batch(self, sid: int, batch) -> None:
        with self._lock:
            sp = self._by_id.get(sid)
            if sp is None:
                return
            sp.batches += 1
            n = getattr(batch, "num_rows", None)
            if isinstance(n, _HOST_NUMS):
                sp.rows += int(n)
            elif n is not None:
                self._pending.append((sp, n))
            try:
                from ..memory.spill import batch_device_bytes
                sp.bytes += batch_device_bytes(batch)
            except Exception:
                pass
            try:
                cap = getattr(batch, "capacity", None)
                if cap:
                    sp.cap_rows += int(cap)
            except Exception:
                pass

    # -- fleet merge ---------------------------------------------------------
    def add_remote_spans(self, parent_sid: Optional[int],
                         remote_spans: List[Dict[str, Any]],
                         offset_ns: int = 0, proc: str = "") -> int:
        """Graft producer-side span dicts (the /spans pull schema:
        spanId/parentId/remoteParent/name/t0Ns/t1Ns/status/proc/attrs,
        timestamps in the PRODUCER's perf_counter_ns domain) under the
        local fetch span ``parent_sid``.

        Remote clocks convert by ``t_local = t_peer - offset_ns`` (the
        hello handshake's NTP estimate), then clamp into the parent
        interval: the offset carries up to rtt/2 of error, and a child
        that leaks outside its parent would break every downstream
        renderer's nesting invariant — a clamped edge is the honest
        rendering of "within this fetch, at clock precision".

        Returns the number merged (counted into
        tpu_trace_remote_spans_merged_total)."""
        if not remote_spans:
            return 0
        merged = 0
        with self._lock:
            if self.sealed:
                return 0
            parent = self._by_id.get(parent_sid) if parent_sid else None
            if parent is None:
                return 0
            p0 = parent.t0_ns
            p1 = parent.t1_ns
            id_map: Dict[Any, int] = {}
            grafted: List[tuple] = []
            for rs in remote_spans:
                if len(self.spans) + len(grafted) >= self.max_spans:
                    self.dropped += 1
                    continue
                try:
                    rt0 = int(rs["t0Ns"]) - offset_ns
                    rt1 = int(rs["t1Ns"]) - offset_ns
                except (KeyError, TypeError, ValueError):
                    continue
                sid = next(self._ids)
                id_map[rs.get("spanId")] = sid
                grafted.append((sid, rs, rt0, rt1))
            for sid, rs, rt0, rt1 in grafted:
                if rt1 < rt0:
                    rt1 = rt0
                rt0 = max(rt0, p0)
                if p1 is not None:
                    rt0 = min(rt0, p1)
                    rt1 = min(rt1, p1)
                rt1 = max(rt1, rt0)
                if rs.get("remoteParent"):
                    rparent = parent_sid
                else:
                    rparent = id_map.get(rs.get("parentId"), parent_sid)
                sp = Span(sid, rparent, str(rs.get("name", "remote")),
                          SPAN, rt0, threading.get_ident(),
                          attrs=dict(rs.get("attrs") or {}))
                sp.t1_ns = rt1
                sp.status = str(rs.get("status", "ok"))
                if rs.get("error"):
                    sp.error = str(rs["error"])
                sp.proc = str(rs.get("proc") or proc or "remote")
                self.spans.append(sp)
                self._by_id[sid] = sp
                merged += 1
            self.remote_spans_merged += merged
        if merged:
            from .fleet import remote_merged_counter
            remote_merged_counter().inc(merged)
        return merged

    def note_remote_spans_lost(self, n: int = 1) -> None:
        """Producer spans that should have merged but never arrived
        (peer died mid-fetch / /spans pull failed); counted into
        tpu_trace_remote_spans_lost_total by the caller's orphan
        hygiene path."""
        with self._lock:
            self.remote_spans_lost += int(n)

    # -- failure / end of query ---------------------------------------------
    def interrupt(self, reason: str) -> None:
        """Close every still-open operator span with `reason` (the
        speculation-miss path: abandoned generators never see the
        exception, so their spans would otherwise dangle into the
        re-execution)."""
        self.event(reason)
        with self._lock:
            now = time.perf_counter_ns()
            for sp in self.spans:
                if sp.t1_ns is None and sp.kind == OPERATOR:
                    sp.t1_ns = now
                    sp.status = reason

    def finalize(self, error: Optional[BaseException] = None) -> None:
        """Seal the trace: close open spans (recording the exception on
        them for failed queries), resolve ALL deferred device scalars in
        one fetch crossing, and aggregate per-operator actuals."""
        with self._lock:
            if self.sealed:
                return
            self.sealed = True
            self.error = repr(error) if error is not None else None
            now = time.perf_counter_ns()
            for sp in self.spans:
                if sp.t1_ns is None:
                    sp.t1_ns = now
                    if error is not None:
                        sp.status = "error"
                        if sp.error is None:
                            sp.error = repr(error)
                    else:
                        sp.status = "ok"
            pending, self._pending = self._pending, []
        if pending:
            try:
                from ..columnar.fetch import fetch_ints
                vals = fetch_ints([v for _, v in pending])
                for (sp, _), v in zip(pending, vals):
                    sp.rows += int(v)
            except Exception:
                # failure paths may leave the device unusable; a trace
                # with unresolved row counts still beats no trace
                pass
        from . import metrics as m
        m.counter("tpu_trace_spans_total",
                  "flight-recorder spans sealed").inc(len(self.spans))
        if self.dropped:
            m.counter("tpu_trace_dropped_spans_total",
                      "spans dropped past trace.maxSpans") \
                .inc(self.dropped)
        pad_fam = m.counter("tpu_pad_waste_bytes_total",
                            "device bytes occupied by capacity-bucket "
                            "padding (live rows vs bucket capacity, "
                            "per launch; tpuxsan TPU-L018 books)",
                            ("exec",))
        bytes_fam = m.counter("tpu_operator_bytes_total",
                              "device bytes flowing through operator "
                              "spans (the pad-waste ratio denominator)",
                              ("exec",))
        for sp in self.spans:
            if sp.kind != OPERATOR or sp.node_id is None:
                continue
            agg = self.actuals.setdefault(
                sp.node_id, {"rows": 0, "bytes": 0, "batches": 0,
                             "timeNs": 0, "padWasteBytes": 0,
                             "node": sp.attrs.get("op", "")})
            agg["rows"] += sp.rows
            agg["bytes"] += sp.bytes
            agg["batches"] += sp.batches
            agg["timeNs"] += sp.dur_ns
            waste = sp.pad_waste_bytes()
            agg["padWasteBytes"] += waste
            try:
                if sp.bytes:
                    bytes_fam.labels(
                        exec=sp.attrs.get("op", "?")).inc(sp.bytes)
                if waste:
                    pad_fam.labels(
                        exec=sp.attrs.get("op", "?")).inc(waste)
            except Exception:
                pass

    # -- reports -------------------------------------------------------------
    def open_span_count(self) -> int:
        with self._lock:
            return sum(1 for s in self.spans if s.t1_ns is None)

    def span_dicts(self) -> List[Dict[str, Any]]:
        """Schema shared with the self-emitted event log's span lines
        and the export renderers (obs/export.py)."""
        out = []
        with self._lock:
            for s in self.spans:
                rel = s.t0_ns - self.t0_ns
                d = {"spanId": s.span_id, "parentId": s.parent_id,
                     "name": s.name, "kind": s.kind,
                     "startNs": rel, "durNs": s.dur_ns,
                     "wallMs": self.wall_start_ms + rel // 1_000_000,
                     "tid": s.tid, "status": s.status,
                     "attrs": dict(s.attrs)}
                if s.error:
                    d["error"] = s.error
                if s.pid is not None:
                    d["pid"] = s.pid
                if s.proc is not None:
                    d["proc"] = s.proc
                if s.kind == OPERATOR:
                    d["rows"] = int(s.rows)
                    d["bytes"] = int(s.bytes)
                    d["batches"] = int(s.batches)
                    d["capRows"] = int(s.cap_rows)
                    d["padWasteBytes"] = s.pad_waste_bytes()
                out.append(d)
        return out

    def operator_spans(self, node_id: Optional[int] = None) -> List[Span]:
        with self._lock:
            return [s for s in self.spans if s.kind == OPERATOR and
                    (node_id is None or s.node_id == node_id)]

    def accuracy_rows(self) -> List[Dict[str, Any]]:
        """Per-operator predicted-vs-actual rows/bytes, ranked worst
        first — the feedback signal for CBO tuning."""
        from .export import accuracy_row
        rows = []
        for nid, pred in self.predictions.items():
            act = self.actuals.get(nid)
            if act is None:
                continue
            rows.append(accuracy_row(act.get("node") or pred.get("node"),
                                     pred, act))
        rows.sort(key=lambda r: -r["rowsErr"])
        return rows

    def to_chrome(self) -> Dict[str, Any]:
        from .export import spans_to_chrome
        return spans_to_chrome(self.span_dicts())

    def to_text(self) -> str:
        from .export import spans_to_text
        return spans_to_text(self.span_dicts())


# ---------------------------------------------------------------------------
# installation (what the instrumented layers consult)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[QueryTrace] = None
_TLS = threading.local()


def install(trace: QueryTrace) -> QueryTrace:
    global _ACTIVE
    _ACTIVE = trace
    return trace


def uninstall() -> None:
    global _ACTIVE
    _ACTIVE = None


def install_local(trace: QueryTrace) -> QueryTrace:
    """Thread-local install for concurrent serving (api/pool.py): each
    pool query's trace binds to ITS thread so co-running queries never
    interleave spans.  Single-session flows keep the process-global
    slot, where helper threads (scan prefetch, shuffle fetch) also
    report."""
    _TLS.active = trace
    return trace


def uninstall_local() -> None:
    _TLS.active = None


def active_tracer() -> Optional[QueryTrace]:
    tr = getattr(_TLS, "active", None)
    return tr if tr is not None else _ACTIVE


def trace_event(name: str, **attrs) -> None:
    """Record an instant event on the active trace (no-op otherwise)."""
    tr = active_tracer()
    if tr is not None:
        tr.event(name, **attrs)


# ---------------------------------------------------------------------------
# the profiler sink (spark.rapids.sql.profile.traceAnnotations)
# ---------------------------------------------------------------------------

#: read on the hot paths (one module-global read when off); set only
#: through set_trace_annotations
ANNOTATIONS_ON = False


def set_trace_annotations(enabled: bool) -> None:
    """Toggle the profiler sink: every span, operator pull, metric
    timer, dispatch and fetch also opens a jax.profiler range of its
    name — the NVTX-range analog (ref NvtxWithMetrics.scala:22-49;
    ranges show up in the TensorBoard/XPlane trace viewer instead of
    Nsight)."""
    global ANNOTATIONS_ON
    ANNOTATIONS_ON = bool(enabled)


def open_range(name: str, query_id: Optional[str] = None):
    """An entered range of `name` on the profiler's clock, or None with
    the switch off.  A range is thread-scoped: close it (close_range)
    on the opening thread and never across a generator's ``yield``.
    Every range is also a frame of the host ledger (below), under the
    same name; `query_id` marks a query's root range."""
    if not ANNOTATIONS_ON:
        return None
    from jax.profiler import TraceAnnotation
    ann = TraceAnnotation(name)
    ann.__enter__()
    return _LEDGER.enter(name, ann, query_id, time.perf_counter_ns())


def close_range(frame) -> Optional[Dict[str, Any]]:
    """Close a range ``open_range`` returned.  The record of the query
    when the range was a top-level query's root, else None."""
    if frame is None:
        return None
    return _LEDGER.leave(frame, time.perf_counter_ns())


# ---------------------------------------------------------------------------
# the host ledger: each query's wall time by segment, summed as its
# ranges close
# ---------------------------------------------------------------------------

#: closed queries kept in memory; the oldest fall off
LEDGER_RECORDS = 8192

#: the bucket of ranges closed on a thread that holds no query root
OFF_THREAD = "off_thread"


class _Frame:
    """One open range: its ledger name, its open on the host clock, the
    nanoseconds its closed children covered, and the query record that
    was open on its thread with the segment its self time goes under
    (both None off a query's thread)."""

    __slots__ = ("name", "ann", "t0_ns", "child_ns", "record", "seg")

    def __init__(self, name, ann, t0_ns, record, seg):
        self.name = name
        self.ann = ann      # None once closed
        self.t0_ns = t0_ns
        self.child_ns = 0
        self.record = record
        self.seg = seg


class _LedgerThread(threading.local):
    stack: Optional[List[_Frame]] = None
    root: Optional[_Frame] = None   # the open top-level query's frame


class HostLedger:
    """Self time by segment of every top-level query, from the ranges of
    the profiler sink (``open_range`` / ``close_range`` feed it and read
    its clock; nothing else does).  A frame's self time is its duration
    less its children's (they nest on one thread, so no second is booked
    twice), booked under ``critpath.segment_of`` of its name into the
    record of the query open on its thread; the segments of a record sum
    to its wall exactly.  A nested query (a scalar subquery's execute)
    books into the outer record.  Ranges on a thread without a root are
    summed under ``off_thread``, outside every record's partition, and
    handed to the next record that closes as ``off_thread_ns``."""

    def __init__(self, max_records: int = LEDGER_RECORDS):
        self._ring = collections.deque(maxlen=max_records)
        self._tls = _LedgerThread()
        self._segments: Dict[str, str] = {}    # span name -> segment
        # guards the ring, the name cache and _off_thread_ns: taken when
        # a root closes, on a new name and off a query's thread, never
        # for a frame of a query's own
        self._lock = threading.Lock()
        self._off_thread_ns = 0

    def records(self) -> List[Dict[str, Any]]:
        """The closed queries' records, oldest first: ``{id, wall_ns,
        segments: {segment: self_ns}, spans: {name: [count,
        inclusive_ns]}, off_thread_ns}``."""
        return list(self._ring)

    def enter(self, name, ann, query_id, now_ns) -> _Frame:
        if query_id is not None:
            name = "query"      # a root's key; the id is the record's
        tls = self._tls
        stack = tls.stack
        if stack is None:
            stack = tls.stack = []
        while stack and stack[-1].ann is None:
            stack.pop()     # closed from another thread
        root = tls.root
        record = None if root is None else root.record
        seg = None
        if record is not None or query_id is not None:
            seg = self._segments.get(name)
            if seg is None:
                seg = self._place(name, stack)
        frame = _Frame(name, ann, now_ns, record, seg)
        if query_id is not None and root is None:
            frame.record = {"id": query_id, "wall_ns": None,
                            "segments": {}, "spans": {},
                            "off_thread_ns": 0}
            tls.root = frame
        stack.append(frame)
        return frame

    def _place(self, name, stack) -> str:
        """The segment of a name seen for the first time.  It is cached
        where the name alone decides it; a span an operator opens for
        its own work (``join.build``, ``scan.upload``) goes under the
        operator's segment, whichever is around it this time."""
        from .critpath import COMPUTE_PREFIX, segment_of
        span = {"name": name}
        seg = segment_of(span)
        if segment_of(span, COMPUTE_PREFIX) != seg:
            return segment_of(span, stack[-1].seg if stack else None)
        with self._lock:
            self._segments[name] = seg
        return seg

    def leave(self, frame: _Frame, now_ns: int):
        if frame.ann is None:
            return None     # an unwind closed it already
        stack = self._tls.stack
        if stack and stack[-1] is frame:
            return self._close_top(stack, now_ns)
        if not stack or frame not in stack:
            # closed on another thread than it opened on: no duration
            # this thread's stack can place.  The frame stays on its own
            # thread's stack, closed; _close_top and enter drop it there
            frame.ann.__exit__(None, None, None)
            frame.ann = None
            return None
        # a range left open below this one (an exception, a suspended
        # generator) ends here with it
        while stack[-1] is not frame:
            self._close_top(stack, now_ns)
        return self._close_top(stack, now_ns)

    def _close_top(self, stack, now_ns):
        frame = stack.pop()
        if frame.ann is None:
            # another thread closed it: nothing to book
            if frame is self._tls.root:
                self._tls.root = None
            return None
        frame.ann.__exit__(None, None, None)
        frame.ann = None
        dur = now_ns - frame.t0_ns
        if stack:
            stack[-1].child_ns += dur
        self_ns = dur - frame.child_ns
        record = frame.record
        if record is None:
            with self._lock:
                self._off_thread_ns += self_ns
            return None
        name = frame.name
        seg = frame.seg
        segments = record["segments"]
        segments[seg] = segments.get(seg, 0) + self_ns
        span = record["spans"].get(name)
        if span is None:
            record["spans"][name] = [1, dur]
        else:
            span[0] += 1
            span[1] += dur
        tls = self._tls
        if frame is not tls.root:
            return None
        tls.root = None
        record["wall_ns"] = dur
        with self._lock:
            record["off_thread_ns"] = self._off_thread_ns
            self._off_thread_ns = 0
            self._ring.append(record)
        return record


_LEDGER = HostLedger()


def host_ledger() -> HostLedger:
    return _LEDGER


def annotate_pulls(name: str, inner):
    """Wrap an operator's iterator in one profiler range per pull —
    around each ``next()``, never across a ``yield`` (a range
    suspended inside a generator would break the thread's nesting;
    ``QueryTrace.trace_operator`` pushes and pops per pull for the
    same reason)."""
    it = iter(inner)

    def gen():
        try:
            while True:
                frame = open_range(name)
                try:
                    b = next(it)
                except StopIteration:
                    return
                finally:
                    close_range(frame)
                yield b
        finally:
            # an abandoned pull (early-exit limits) closes the operator's
            # own generator as returning it untouched would have
            close = getattr(it, "close", None)
            if close is not None:
                close()

    return gen()


class trace_span:
    """Span context manager: a profiler range when annotations are on,
    a recorded span when a trace is active, and the live view's phase
    feed for phase spans — with all of them off, an inert handle.
    Enters to a handle with ``.set(**attrs)``.  (A class, not a
    generator: a span site with both sinks off costs half as much.)"""

    __slots__ = ("name", "kind", "attrs", "_frame", "_recorded")

    def __init__(self, name: str, kind: str = SPAN, **attrs):
        self.name = name
        self.kind = kind
        self.attrs = attrs

    def __enter__(self):
        name = self.name
        self._frame = open_range(name)
        self._recorded = None
        try:
            tr = active_tracer()
            if tr is None:
                handle = _SpanHandle_NULL
            else:
                recorded = tr.span(name, kind=self.kind, **self.attrs)
                handle = recorded.__enter__()
                self._recorded = recorded
            # phase spans and the admission wait are the only names
            # that move a query's live-view phase; called outside the
            # span lock (the hook takes the tracker's own lock)
            if self.kind == PHASE or name == "admission.wait":
                from . import progress as _progress
                _progress.note_span_open(name, self.kind)
        except BaseException:
            self.__exit__(*sys.exc_info())
            raise
        return handle

    def __exit__(self, exc_type, exc, tb):
        try:
            if self._recorded is not None:
                self._recorded.__exit__(exc_type, exc, tb)
        finally:
            close_range(self._frame)
        return False


class _NullHandle:
    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_SpanHandle_NULL = _NullHandle()
