"""Critical-path extraction: decompose a query's wall time into an
exhaustive, non-overlapping latency-segment taxonomy.

The tracer (obs/tracer.py) records *where* time was spent as a span
tree; admission (memory/admission.py) records *that* queries queued;
``--serve`` reports aggregate p50/p99.  None of them can explain a
p99.  This module walks one query's **closed** span tree — the neutral
``span_dicts()`` schema, so it works identically on a live trace, a
replayed event log, or hand-built test fixtures — and partitions the
root span's wall-clock interval into named segments:

==================  =====================================================
segment             booked from
==================  =====================================================
``queue_wait``      ``admission.wait`` spans (byte-weighted admission)
``planning``        ``phase:plan`` / ``phase:planning`` /
                    ``phase:overrides`` / ``phase:subqueries`` /
                    ``phase:plan-retry`` / ``replan`` self-time
``compile``         ``jit.build:<kind>`` spans (lower + compile or
                    cache load), and inside them the synthetic
                    intervals reconstructed from enriched ``jit.build``
                    instant events (``total_s`` attr)
``prewarm``         same, when the build's ``cause`` is ``prewarm``
``session``         ``phase:admit`` / ``phase:setup`` / ``phase:release``
                    self-time: the session's own steps around the plan
                    (admission's pricing, arming the re-planner and the
                    sanitizers, releasing shuffle blocks)
``host_assist``     ``phase:host_assist`` self-time (fetch crossings)
``compute:<Kind>``  operator-kind spans (``FilterExec`` etc.) self-time;
                    the profiler sink's ``<Exec>.pull`` / ``<Exec>.
                    <metric>`` ranges; and the spans an operator opens
                    for its own work (``join.*``, ``ici.stage:<op>``,
                    ``scan.upload``), which take the segment of the
                    operator around them (``compute:join`` / ``ici`` /
                    ``scan`` with none around them)
``dispatch``        ``jit.key:<kind>`` / ``jit.dispatch:<kind>``: the
                    host's side of a program launch (host ledger only:
                    the sweep below folds them into their parent)
``fetch_wait``      ``fetch.crossing``: the host blocked in a device-to-
                    host transfer (host ledger only, as above)
``shuffle_write``   ``shuffle.map_write`` self-time
``fetch_wire``      ``shuffle.fetch`` self-time — time on the wire
                    after subtracting grafted producer-serve spans
``fetch_serve``     remote spans grafted by the fleet observatory
                    (``proc`` set): producer-side serve time
``oc_spill``        ``oc.sort_run`` / ``oc.merge`` /
                    ``oc.merge_partials`` — out-of-core spill + merge
``other``           root / ``phase:execute`` / bridge self-time
==================  =====================================================

:func:`segment_of` is the tree's one map from span names to segments:
the host ledger (``obs/tracer.HostLedger``) books every range of the
profiler sink through it too.

**No double-booking.**  Concurrent children (per-partition execute
spans, parallel shuffle fetches) overlap in wall time; summing their
durations would book the same second twice.  The sweep instead
partitions every parent interval among its children: each elementary
slice is assigned to the covering child that *ends last* — the child
on the longest dependency chain to query completion, i.e. the
critical path — and only uncovered slices count as the parent's own
self-time.  The result is an exact partition of the root interval, so
segments sum to wall time by construction; the tolerance gate in
:func:`extract_critical_path` exists to catch algorithm bugs (an
unclipped child, a negative interval), not rounding.

The breakdown is triple-sunk by :func:`record_query_latency`: a
``critical_path`` annotation on the root span (rendered by Perfetto
via the chrome ``args``), ``tpu_latency_segment_seconds_total
{segment,tenant}`` counters (bounded cardinality: the family is
created with ``max_series=256`` so 4 tenants x ~40 segments does not
overflow into ``_overflow``), and a per-query record in the regress
HistoryDir's latency ledger via obs/slo.py.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

SEG_QUEUE_WAIT = "queue_wait"
SEG_PLANNING = "planning"
SEG_COMPILE = "compile"
SEG_PREWARM = "prewarm"
SEG_HOST_ASSIST = "host_assist"
SEG_SESSION = "session"
SEG_SHUFFLE_WRITE = "shuffle_write"
SEG_FETCH_WIRE = "fetch_wire"
SEG_FETCH_SERVE = "fetch_serve"
SEG_OC_SPILL = "oc_spill"
SEG_DISPATCH = "dispatch"
SEG_FETCH_WAIT = "fetch_wait"
SEG_OTHER = "other"
COMPUTE_PREFIX = "compute:"

#: reconciliation gate: |wall - sum(segments)| must stay under this
#: fraction of wall (plus an absolute floor for micro-queries).
RECONCILE_TOLERANCE = 0.05
RECONCILE_FLOOR_S = 0.001

_PLANNING_NAMES = frozenset((
    "phase:plan", "phase:planning", "phase:overrides",
    "phase:subqueries", "phase:plan-retry", "replan",
))

_SESSION_NAMES = frozenset((
    "phase:admit", "phase:setup", "phase:release",
))

_OC_PREFIX = "oc."

#: the roots: what no span below them names is theirs, and is `other`
_ROOT_NAMES = frozenset((
    "query", "phase:execute", "phase:execute-retry",
    "bridge.execute_stage",
))

#: spans an operator opens around its own work: their self time is the
#: enclosing operator's, whichever it is; with no operator around them
#: it goes under the name's own head, still a ``compute:`` segment
_OPERATOR_WORK = (("join.", "join"), ("ici.stage:", "ici"),
                  ("scan.upload", "scan"))
_DISPATCH_PREFIXES = ("jit.dispatch:", "jit.key:")

#: spans that detail their parent's own work (a program launch, a
#: fetch's blocking transfer, an upload): they own no segment, their
#: time stays the enclosing operator's or phase's self-time
_DETAIL_NAMES = frozenset(("fetch.crossing", "scan.upload"))
_BUILD_PREFIX = "jit.build:"


def _is_detail(name: str) -> bool:
    return name in _DETAIL_NAMES or name.startswith(_DISPATCH_PREFIXES)


def segment_of(span: dict, enclosing: Optional[str] = None) -> str:
    """Map one span dict to its latency segment; `enclosing` is the
    segment of the span around it, where the caller knows it.

    Grafted remote spans carry ``proc`` (the producing process) and
    classify as producer-serve time regardless of name — a remote
    operator span is the *producer's* compute, not ours; what we
    waited on is the serve."""
    if span.get("proc"):
        return SEG_FETCH_SERVE
    name = span.get("name", "")
    if name == "admission.wait":
        return SEG_QUEUE_WAIT
    if name in _PLANNING_NAMES:
        return SEG_PLANNING
    if name == "phase:host_assist":
        return SEG_HOST_ASSIST
    if name in _SESSION_NAMES:
        return SEG_SESSION
    if name == "jit.build":  # synthetic compile interval (see below)
        attrs = span.get("attrs") or {}
        return SEG_PREWARM if attrs.get("cause") == "prewarm" else SEG_COMPILE
    if name.startswith(_BUILD_PREFIX):
        return SEG_COMPILE
    if name == "shuffle.map_write":
        return SEG_SHUFFLE_WRITE
    if name == "shuffle.fetch":
        return SEG_FETCH_WIRE
    if name.startswith(_OC_PREFIX):
        return SEG_OC_SPILL
    if span.get("kind") == "operator":
        attrs = span.get("attrs") or {}
        op = attrs.get("op") or name.split(".", 1)[0]
        return COMPUTE_PREFIX + str(op)
    if name in _ROOT_NAMES or name.startswith("query:"):
        return SEG_OTHER
    if name.startswith(_DISPATCH_PREFIXES):
        return SEG_DISPATCH
    if name == "fetch.crossing":
        return SEG_FETCH_WAIT
    for prefix, head in _OPERATOR_WORK:
        if name.startswith(prefix):
            if enclosing and enclosing.startswith(COMPUTE_PREFIX):
                return enclosing
            return COMPUTE_PREFIX + head
    owner, dot, what = name.partition(".")
    if dot and (owner.endswith("Exec") or what == "pull"):
        # the profiler sink's <Exec>.pull and <Exec>.<metric> ranges
        return COMPUTE_PREFIX + owner
    return SEG_OTHER


def _synthesize_compile_children(spans: Sequence[dict]) -> List[dict]:
    """jit compile time hides inside whatever span was open when the
    build ran: the compile observatory emits ``jit.build`` as an
    *instant* event carrying ``total_s``.  Reconstruct each build as a
    zero-API child interval ``[event_t0 - total_s, event_t0]`` of the
    event's parent so the sweep books it as ``compile`` (or
    ``prewarm``) instead of silently inflating operator self-time."""
    out = []
    for i, s in enumerate(spans):
        if s.get("name") != "jit.build":
            continue
        attrs = s.get("attrs") or {}
        total_s = attrs.get("total_s")
        if not total_s or total_s <= 0:
            continue
        total_ns = int(total_s * 1e9)
        t1 = int(s.get("startNs", 0))
        out.append({
            "spanId": -(i + 1),  # disjoint from real span ids (>= 1)
            "parentId": s.get("parentId"),
            "name": "jit.build",
            "kind": "span",
            "startNs": t1 - total_ns,
            "durNs": total_ns,
            "attrs": {"cause": attrs.get("cause")},
        })
    return out


def extract_critical_path(spans: Sequence[dict],
                          tolerance: float = RECONCILE_TOLERANCE
                          ) -> Dict[str, object]:
    """Partition the query root's wall interval into latency segments.

    ``spans`` is the ``QueryTrace.span_dicts()`` list (closed trace).
    Returns ``{"segments": {name: seconds}, "wall_s", "covered_s",
    "residual_s", "reconciled"}``.  Failed queries reconcile too: an
    error span mid-tree still has a closed interval (``finalize``
    closes open spans on the way out)."""
    root = None
    for s in spans:
        if s.get("kind") == "query":
            root = s
            break
    if root is None or not root.get("durNs"):
        return {"segments": {}, "wall_s": 0.0, "covered_s": 0.0,
                "residual_s": 0.0, "reconciled": True}

    work = list(spans) + _synthesize_compile_children(spans)
    by_id: Dict[object, dict] = {}
    children: Dict[object, List[dict]] = {}
    for s in work:
        if s.get("kind") == "event" or not s.get("durNs") or \
                _is_detail(s.get("name", "")):
            continue  # instants and zero-length spans own no wall time
        s = dict(s)
        s["_t0"] = int(s.get("startNs", 0))
        s["_t1"] = s["_t0"] + int(s.get("durNs", 0))
        by_id[s["spanId"]] = s
        children.setdefault(s.get("parentId"), []).append(s)

    root = by_id[root["spanId"]]
    seg_ns: Dict[str, int] = {}

    def attribute(span: dict, windows: List[List[int]],
                  enclosing: Optional[str] = None) -> None:
        own = segment_of(span, enclosing)
        kids = children.get(span["spanId"], ())
        kid_windows: Dict[object, List[List[int]]] = {}
        for lo, hi in windows:
            entries = []
            for k in kids:
                k0, k1 = max(k["_t0"], lo), min(k["_t1"], hi)
                if k1 > k0:
                    entries.append((k0, k1, k))
            if not entries:
                seg_ns[own] = seg_ns.get(own, 0) + (hi - lo)
                continue
            bounds = {lo, hi}
            for k0, k1, _ in entries:
                bounds.add(k0)
                bounds.add(k1)
            bounds = sorted(bounds)
            for a, b in zip(bounds, bounds[1:]):
                covering = [e for e in entries if e[0] <= a and e[1] >= b]
                if not covering:
                    seg_ns[own] = seg_ns.get(own, 0) + (b - a)
                    continue
                # ends-last = the longest dependency chain to completion
                owner = max(covering, key=lambda e: (e[1], e[2]["spanId"]))
                wins = kid_windows.setdefault(owner[2]["spanId"], [])
                if wins and wins[-1][1] == a:
                    wins[-1][1] = b  # merge contiguous slices
                else:
                    wins.append([a, b])
        for kid_id, wins in kid_windows.items():
            attribute(by_id[kid_id], wins, own)

    attribute(root, [[root["_t0"], root["_t1"]]])

    segments = {k: v / 1e9 for k, v in sorted(seg_ns.items()) if v > 0}
    wall_s = root["durNs"] / 1e9
    covered_s = sum(segments.values())
    residual_s = wall_s - covered_s
    reconciled = abs(residual_s) <= max(tolerance * wall_s, RECONCILE_FLOOR_S)
    return {"segments": segments, "wall_s": wall_s, "covered_s": covered_s,
            "residual_s": residual_s, "reconciled": reconciled}


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

SEGMENT_FAMILY = "tpu_latency_segment_seconds_total"

#: 4 pool tenants x ~40 segments (compute:<Kind> fan-out) exceeds the
#: registry's 64-series default; a bigger explicit cap keeps every real
#: series out of ``_overflow`` while still bounding cardinality.
SEGMENT_MAX_SERIES = 256


def _segment_counter():
    from .metrics import MetricsRegistry
    return MetricsRegistry.get().counter(
        SEGMENT_FAMILY,
        "Critical-path wall seconds attributed to each latency segment, "
        "per tenant (obs/critpath.py).",
        ("segment", "tenant"), max_series=SEGMENT_MAX_SERIES)


def record_query_latency(tracer, tenant: str, error: Optional[BaseException]
                         = None, label: str = "") -> Optional[dict]:
    """Extract the critical path from a finalized trace and fan it out
    to all three sinks.  Called from the session's query-obs flush;
    advisory — never raises into the query path."""
    from .slo import LatencyObservatory
    t_start = time.perf_counter()
    res = extract_critical_path(tracer.span_dicts())
    if not res["segments"] and res["wall_s"] == 0.0:
        return None
    tenant = tenant or "default"
    # sink 1: root-span annotation -> chrome args -> Perfetto
    tracer.add_attrs(
        tracer.root_id,
        critical_path={k: round(v, 6) for k, v in res["segments"].items()},
        critical_path_reconciled=res["reconciled"],
        critical_path_residual_s=round(res["residual_s"], 6))
    # sink 2: bounded-cardinality counters
    fam = _segment_counter()
    for seg, sec in res["segments"].items():
        fam.labels(segment=seg, tenant=tenant).inc(sec)
    # the observatory's own overhead rides the record (the --slo gate
    # holds it under 5% of query wall)
    extract_s = time.perf_counter() - t_start
    # sink 3: the SLO observatory (burn window, tail reservoir, ledger).
    # Cancel/deadline accounting: a client cancel is excluded from the
    # burn window (the engine didn't miss), a blown deadline counts BAD
    from .progress import TpuQueryCancelled, TpuQueryDeadlineExceeded
    LatencyObservatory.get().record(
        tenant=tenant, wall_s=res["wall_s"], segments=res["segments"],
        failed=error is not None, label=label,
        reconciled=res["reconciled"], extract_s=extract_s,
        cancelled=(isinstance(error, TpuQueryCancelled)
                   and getattr(error, "cause", "client") == "client"),
        deadline=isinstance(error, TpuQueryDeadlineExceeded))
    return res
