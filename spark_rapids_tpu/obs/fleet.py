"""Fleet observatory: the CROSS-PROCESS half of the observability
story.

Everything in obs/ up to here is process-local — one QueryTrace, one
MetricsRegistry, one ``/metrics`` endpoint.  A distributed shuffle
(shuffle/transport.py serving another OS process's reduce reads) made
that a blind spot: the consumer's trace shows one opaque fetch span
while the producer's decode/catalog/serialize/compress/send work is
invisible, and no endpoint can answer "how is the CLUSTER doing".

Four pieces close the gap:

* ``TraceContext`` — the (trace_id, span_id, tenant) triple a consumer
  threads through the shuffle wire protocol (transport.py's v2 frame
  extension) so the producer can parent its serve spans under the
  requesting query's fetch span.
* ``RemoteSpanStore`` — the producer-side buffer of serve spans keyed
  by trace_id, bounded two ways (traces x spans-per-trace, evictions
  counted), drained by the consumer through the ``/spans`` pull
  endpoint obs/health.py serves next to ``/metrics``.
* ``ClockSync`` — per-peer clock-offset estimates from the transport's
  NTP-style four-timestamp hello handshake.  Both sides stamp with
  ``time.perf_counter_ns``, whose epoch is ARBITRARY PER PROCESS, so
  merging remote spans without the offset is not "slightly skewed", it
  is nonsense; ``offset = ((t1-t0)+(t2-t3))/2`` maps the server's clock
  domain onto the client's.
* ``FleetAggregator`` — driver-side: walks the heartbeat peer registry,
  scrapes each live peer's ``/metrics`` + ``/healthz``, re-exposes a
  bounded-cardinality rollup (``peer`` label, capped peer count) on the
  driver's own registry, and derives a fleet verdict: any peer that was
  seen alive and is now dead, unreachable, or self-reporting unhealthy
  degrades the fleet.
"""

from __future__ import annotations

import json
import struct
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

# wire format of the packed context blob carried by v2 request frames:
# 16 raw trace-id bytes, u64 parent span id, tenant length + utf-8
_CTX = struct.Struct("<16sQB")
_MAX_TENANT = 64


def remote_merged_counter():
    from . import metrics as m
    return m.counter("tpu_trace_remote_spans_merged_total",
                     "producer-side serve spans merged into a consumer "
                     "trace via the /spans pull path")


def remote_lost_counter():
    from . import metrics as m
    return m.counter("tpu_trace_remote_spans_lost_total",
                     "remote fetches whose producer spans could not be "
                     "recovered (peer died or /spans pull failed); the "
                     "fetch span closes with a spans_lost annotation "
                     "instead of dangling")


class TraceContext:
    """What crosses the wire: enough to parent remote spans, nothing
    else (no payloads, no attrs — the context must stay header-sized)."""

    __slots__ = ("trace_id", "span_id", "tenant")

    def __init__(self, trace_id: str, span_id: int, tenant: str = ""):
        self.trace_id = trace_id  # 32-char hex
        self.span_id = int(span_id)
        self.tenant = tenant[:_MAX_TENANT]

    def pack(self) -> bytes:
        tb = self.tenant.encode()[:_MAX_TENANT]
        return _CTX.pack(bytes.fromhex(self.trace_id), self.span_id,
                         len(tb)) + tb

    @classmethod
    def unpack(cls, blob: bytes) -> "TraceContext":
        tid, sid, tlen = _CTX.unpack_from(blob, 0)
        tenant = blob[_CTX.size:_CTX.size + tlen].decode(errors="replace")
        return cls(tid.hex(), sid, tenant)

    def __repr__(self):
        return (f"TraceContext({self.trace_id[:8]}…, span={self.span_id}"
                + (f", tenant={self.tenant!r})" if self.tenant else ")"))


def new_trace_id() -> str:
    return uuid.uuid4().hex


# ---------------------------------------------------------------------------
# producer side: bounded serve-span buffer behind /spans
# ---------------------------------------------------------------------------

class RemoteSpanStore:
    """Serve spans recorded on behalf of remote traces, keyed by
    trace_id, awaiting pull.

    Bounded the same way the tracer and the metrics registry are: at
    most ``max_traces`` distinct trace buckets (oldest evicted) and
    ``max_per_trace`` spans per bucket (new spans dropped); every loss
    is counted, never silent.  Span dicts are in THIS process's
    ``perf_counter_ns`` domain — the puller owns skew correction."""

    _instance: Optional["RemoteSpanStore"] = None
    _class_lock = threading.Lock()

    def __init__(self, max_traces: int = 64, max_per_trace: int = 512):
        self.max_traces = max_traces
        self.max_per_trace = max_per_trace
        self._lock = threading.Lock()
        self._by_trace: Dict[str, List[Dict[str, Any]]] = {}
        self._ids = iter(range(1, 1 << 62))
        self.dropped = 0
        self.evicted_traces = 0

    @classmethod
    def get(cls) -> "RemoteSpanStore":
        with cls._class_lock:
            if cls._instance is None:
                cls._instance = RemoteSpanStore()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._class_lock:
            cls._instance = None

    def configure(self, max_traces: int, max_per_trace: int) -> None:
        with self._lock:
            self.max_traces = max(1, int(max_traces))
            self.max_per_trace = max(1, int(max_per_trace))

    def next_span_id(self) -> int:
        with self._lock:
            return next(self._ids)

    def add(self, trace_id: str, span: Dict[str, Any]) -> None:
        from . import metrics as m
        with self._lock:
            bucket = self._by_trace.get(trace_id)
            if bucket is None:
                if len(self._by_trace) >= self.max_traces:
                    # evict the oldest trace: an abandoned consumer must
                    # not pin producer memory forever
                    oldest = next(iter(self._by_trace))
                    self._by_trace.pop(oldest)
                    self.evicted_traces += 1
                bucket = self._by_trace[trace_id] = []
            if len(bucket) >= self.max_per_trace:
                self.dropped += 1
                m.counter("tpu_trace_remote_spans_dropped_total",
                          "producer serve spans dropped past the "
                          "RemoteSpanStore bounds").inc()
                return
            bucket.append(span)

    def drain(self, trace_id: str) -> List[Dict[str, Any]]:
        """Pull semantics: handing the spans over removes them, so a
        repeated pull (retried fetch group) never double-merges."""
        with self._lock:
            return self._by_trace.pop(trace_id, [])

    def peek_all(self) -> Dict[str, List[Dict[str, Any]]]:
        with self._lock:
            return {k: list(v) for k, v in self._by_trace.items()}

    def span_count(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._by_trace.values())

    def to_json(self, trace_id: Optional[str] = None,
                drain: bool = False) -> str:
        if trace_id:
            spans = self.drain(trace_id) if drain \
                else self.peek_all().get(trace_id, [])
            return json.dumps({"traceId": trace_id, "spans": spans,
                               "dropped": self.dropped})
        return json.dumps({"traces": self.peek_all(),
                           "dropped": self.dropped,
                           "evictedTraces": self.evicted_traces})


class ServeSpanRecorder:
    """Producer-side span builder: one per served request that carried
    a TraceContext.  Records a root serve span parented (remotely)
    under the consumer's fetch span plus per-step children, all in this
    process's clock domain, then deposits them in the RemoteSpanStore
    at close."""

    def __init__(self, ctx: TraceContext, name: str, proc: str,
                 store: Optional[RemoteSpanStore] = None, **attrs):
        self.ctx = ctx
        self.store = store or RemoteSpanStore.get()
        self._spans: List[Dict[str, Any]] = []
        self._root_id = self.store.next_span_id()
        self._t0 = time.perf_counter_ns()
        self._root = {"spanId": self._root_id, "parentId": ctx.span_id,
                      "remoteParent": True, "name": name, "kind": "span",
                      "t0Ns": self._t0, "t1Ns": None, "status": "open",
                      "proc": proc, "attrs": dict(attrs)}
        if ctx.tenant:
            self._root["attrs"]["tenant"] = ctx.tenant
        self._spans.append(self._root)

    def step(self, name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
        self._spans.append({
            "spanId": self.store.next_span_id(),
            "parentId": self._root_id, "remoteParent": False,
            "name": name, "kind": "span", "t0Ns": t0_ns, "t1Ns": t1_ns,
            "status": "ok", "proc": self._root["proc"],
            "attrs": dict(attrs)})

    def set_attrs(self, **attrs) -> None:
        self._root["attrs"].update(attrs)

    def close(self, status: str = "ok",
              error: Optional[str] = None) -> None:
        self._root["t1Ns"] = time.perf_counter_ns()
        self._root["status"] = status
        if error:
            self._root["error"] = error
        for sp in self._spans:
            self.store.add(self.ctx.trace_id, sp)


# ---------------------------------------------------------------------------
# clock alignment
# ---------------------------------------------------------------------------

class ClockSync:
    """Per-peer clock-offset registry fed by the transport hello
    handshake.  ``offset_ns(peer)`` is how far the peer's
    perf_counter_ns clock runs AHEAD of ours: a peer timestamp maps
    into our domain as ``t_local = t_peer - offset``."""

    _instance: Optional["ClockSync"] = None
    _class_lock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self._offsets: Dict[str, int] = {}
        self._rtts: Dict[str, int] = {}

    @classmethod
    def get(cls) -> "ClockSync":
        with cls._class_lock:
            if cls._instance is None:
                cls._instance = ClockSync()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._class_lock:
            cls._instance = None

    @staticmethod
    def estimate(t0: int, t1: int, t2: int, t3: int) -> Tuple[int, int]:
        """NTP four-timestamp estimate over one request/response pair:
        t0 client send, t1 server receive, t2 server send, t3 client
        receive (t0/t3 client clock, t1/t2 server clock).  Returns
        (offset_ns, rtt_ns); the offset's error is bounded by rtt/2."""
        offset = ((t1 - t0) + (t2 - t3)) // 2
        rtt = (t3 - t0) - (t2 - t1)
        return offset, rtt

    def observe(self, peer: str, t0: int, t1: int, t2: int, t3: int
                ) -> int:
        offset, rtt = self.estimate(t0, t1, t2, t3)
        with self._lock:
            # keep the estimate with the smallest rtt: its offset error
            # bound (rtt/2) is the tightest we have seen for this peer
            best = self._rtts.get(peer)
            if best is None or rtt < best:
                self._offsets[peer] = offset
                self._rtts[peer] = rtt
            return self._offsets[peer]

    def offset_ns(self, peer: str) -> Optional[int]:
        with self._lock:
            return self._offsets.get(peer)

    def rtt_ns(self, peer: str) -> Optional[int]:
        with self._lock:
            return self._rtts.get(peer)


# ---------------------------------------------------------------------------
# tenant plumb-through (serving sets it; single-tenant leaves it empty)
# ---------------------------------------------------------------------------

_TENANT_TLS = threading.local()


def set_tenant(tenant: str) -> None:
    _TENANT_TLS.tenant = tenant


def current_tenant() -> str:
    return getattr(_TENANT_TLS, "tenant", "") or ""


# ---------------------------------------------------------------------------
# driver side: peer scraping + rollup + fleet verdict
# ---------------------------------------------------------------------------

#: peer families re-exposed on the driver as tpu_fleet_rollup{peer,name}.
#: A fixed allowlist keeps the rollup's cardinality at
#: len(ROLLUP_FAMILIES) x maxPeers no matter what a peer exposes.
ROLLUP_FAMILIES = (
    "tpu_shuffle_server_requests_total",
    "tpu_shuffle_fetch_blocks_total",
    "tpu_shuffle_fetch_bytes_total",
    "tpu_trace_spans_total",
    "tpu_queries_completed_total",
    "tpu_queries_failed_total",
)


def parse_prometheus_totals(text: str) -> Dict[str, float]:
    """Family -> summed value over every series, from Prometheus text
    exposition.  Histogram internals (_bucket/_sum/_count) fold into
    their family's _count so rollups stay order-of-magnitude readable."""
    totals: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            name_part, value_part = line.rsplit(None, 1)
            value = float(value_part)
        except ValueError:
            continue
        name = name_part.split("{", 1)[0]
        if name.endswith("_bucket") or name.endswith("_sum"):
            continue
        if name.endswith("_count"):
            name = name[:-len("_count")]
        totals[name] = totals.get(name, 0.0) + value
    return totals


def _http_get(host: str, port: int, path: str, timeout_s: float) -> str:
    import urllib.request
    url = f"http://{host}:{port}{path}"
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode(errors="replace")


def pull_remote_spans(host: str, obs_port: int, trace_id: str,
                      timeout_s: float = 2.0) -> List[Dict[str, Any]]:
    """Drain one trace's serve spans from a peer's /spans endpoint.
    Raises on any transport/parse failure — the caller owns the
    spans_lost accounting."""
    body = _http_get(host, int(obs_port),
                     f"/spans?trace_id={trace_id}&drain=1", timeout_s)
    doc = json.loads(body)
    return list(doc.get("spans") or [])


class FleetAggregator:
    """Walks the heartbeat registry, scrapes each live peer, re-exposes
    the rollup on THIS process's registry, and keeps the fleet verdict.

    Peer label cardinality is bounded twice: ``max_peers`` caps how many
    peers are scraped per round (excess peers are counted, not labeled),
    and the registry's own series cap backstops the families."""

    def __init__(self, heartbeat, max_peers: int = 16,
                 timeout_s: float = 2.0):
        self.heartbeat = heartbeat
        self.max_peers = max(1, int(max_peers))
        self.timeout_s = timeout_s
        self._lock = threading.Lock()
        self._seen: Dict[str, Dict[str, Any]] = {}  # every peer ever live
        self._last: Dict[str, Dict[str, Any]] = {}

    # -- one scrape round ----------------------------------------------------
    def scrape(self) -> Dict[str, Any]:
        from . import metrics as m
        self.heartbeat.expire_dead()
        live = self.heartbeat.live_peers()
        up_g = m.gauge("tpu_fleet_peer_up",
                       "1 when the peer's /metrics endpoint answered "
                       "the last scrape, 0 when it did not", ("peer",))
        rollup_g = m.registry().gauge(
            "tpu_fleet_rollup",
            "per-peer rollup of allowlisted families scraped from "
            "each peer's /metrics", ("peer", "name"),
            max_series=self.max_peers * (len(ROLLUP_FAMILIES) + 1))
        peers: Dict[str, Dict[str, Any]] = {}
        skipped = 0
        for i, p in enumerate(live):
            if i >= self.max_peers:
                skipped += 1
                continue
            entry: Dict[str, Any] = {"host": p.host, "port": p.port,
                                     "obs_port": getattr(p, "obs_port",
                                                         0),
                                     "live": True, "scraped": False,
                                     "health": None}
            obs_port = entry["obs_port"]
            if obs_port:
                try:
                    text = _http_get(p.host, obs_port, "/metrics",
                                     self.timeout_s)
                    totals = parse_prometheus_totals(text)
                    for fam in ROLLUP_FAMILIES:
                        if fam in totals:
                            rollup_g.labels(peer=p.executor_id,
                                            name=fam).set(totals[fam])
                    health = json.loads(_http_get(
                        p.host, obs_port, "/healthz", self.timeout_s))
                    entry["health"] = health.get("status")
                    entry["scraped"] = True
                except Exception as ex:
                    entry["error"] = repr(ex)
            up_g.labels(peer=p.executor_id).set(
                1 if entry["scraped"] else 0)
            peers[p.executor_id] = entry
        with self._lock:
            for pid, entry in peers.items():
                self._seen[pid] = entry
            # a peer seen alive before and absent from the live set now
            # is DEAD — it stays in the report (and the verdict) until
            # forget_peer()
            for pid in self._seen:
                if pid not in peers:
                    dead = dict(self._seen[pid])
                    dead["live"] = False
                    dead["scraped"] = False
                    self._seen[pid] = dead
                    peers[pid] = dead
                    up_g.labels(peer=pid).set(0)
            self._last = peers
        m.gauge("tpu_fleet_peers_live",
                "heartbeat-live peers at the last aggregator scrape") \
            .set(sum(1 for e in peers.values() if e["live"]))
        if skipped:
            m.counter("tpu_fleet_peers_skipped_total",
                      "live peers beyond fleet.scrape.maxPeers left "
                      "out of a scrape round").inc(skipped)
        return peers

    def forget_peer(self, executor_id: str) -> None:
        with self._lock:
            self._seen.pop(executor_id, None)
            self._last.pop(executor_id, None)

    # -- verdict -------------------------------------------------------------
    def verdict(self, scrape_first: bool = True) -> Dict[str, Any]:
        """Fleet health: ok only when every peer ever seen is still
        heartbeat-live, scrapeable, and self-reports ok."""
        peers = self.scrape() if scrape_first else dict(self._last)
        status = "ok"
        reasons: List[str] = []
        for pid, e in sorted(peers.items()):
            if not e.get("live"):
                status = "degraded"
                reasons.append(f"{pid}: dead (heartbeat expired)")
            elif e.get("obs_port") and not e.get("scraped"):
                status = "degraded"
                reasons.append(f"{pid}: unreachable "
                               f"({e.get('error', 'scrape failed')})")
            elif e.get("health") not in (None, "ok"):
                status = "degraded"
                reasons.append(f"{pid}: self-reports {e['health']}")
        return {"status": status, "peers": peers, "reasons": reasons}


# ---------------------------------------------------------------------------
# installation (what obs/health.py consults)
# ---------------------------------------------------------------------------

_AGGREGATOR: Optional[FleetAggregator] = None
_AGG_LOCK = threading.Lock()


def install_aggregator(agg: Optional[FleetAggregator]
                       ) -> Optional[FleetAggregator]:
    global _AGGREGATOR
    with _AGG_LOCK:
        _AGGREGATOR = agg
        return agg


def installed_aggregator() -> Optional[FleetAggregator]:
    with _AGG_LOCK:
        return _AGGREGATOR


def fleet_refresh() -> None:
    """Refresh the rollup series before an exposition read (no-op when
    no aggregator is installed; a scrape failure must never fail the
    endpoint serving it)."""
    agg = installed_aggregator()
    if agg is not None:
        try:
            agg.scrape()
        except Exception:
            pass
