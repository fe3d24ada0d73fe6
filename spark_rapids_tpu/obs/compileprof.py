"""Compile observatory: attribute, classify and persist every XLA
compilation the engine pays for.

A cold query is compile-bound (the TPU compiler takes tens of seconds
over every program that sorts; docs/performance.md), and until this
module the only record of a compilation was an unlabeled ``jit.build``
instant event with no duration, no cause and no cross-session memory.
The observatory sits at the single ``process_jit`` seam
(``exec/base.py``): every jit in exec/, parallel/, columnar/ and
shuffle/ already routes through that table, so one wrapper sees every
program the process ever builds.

What one build produces:

* **Split timing.**  The returned callable dispatches through an AOT
  proxy: the first call per input-shape signature runs
  ``jit(f).lower(*args)`` (trace + lower, timed) then
  ``lowered.compile()`` (backend compile, timed — this is the step the
  persistent disk cache can absorb) and caches the compiled executable
  for every later call with that signature.  The split is what ROADMAP
  item 1 needs: re-trace cost survives a disk cache, backend cost does
  not.
* **A program fingerprint.**  Exec kind parsed from the jit key, a
  stable hash of the semantic key, a bucket-canonical key hash (every
  int in the key or leading array dim that equals a configured
  capacity/string bucket is masked), the input dtype signature and the
  capacity signature, plus the lowered StableHLO size.
* **A classified cause.**  Every build is diffed against the index of
  previously seen programs (this process + the loaded ledger):

  - ``eviction_refault`` — this exact program was built before and is
    no longer resident (LRU eviction, cache clear, or a previous
    session: process death is the ultimate eviction);
  - ``shape_churn``     — the same program modulo capacity buckets was
    already built (same exec + canonical key + dtypes, different
    bucket) — the recompiles bucket canonicalization would erase;
  - ``dtype_churn``     — the same exec + capacity signature was built
    under a different dtype signature;
  - ``new_program``     — genuinely novel work.

* **Three sinks, one truth.**  Each build (a) stamps an enriched
  ``jit.build`` span on the active flight-recorder trace, (b) feeds the
  ``tpu_jit_{hits,misses,evictions,compile_seconds}_total`` metric
  families plus the ``tpu_jit_cache_size`` gauge, and (c) appends one
  JSONL record to the cross-session compile ledger
  (``compile_ledger.jsonl`` in the obs/history.py HistoryDir).  The CI
  gate (``devtools/run_lint.py --jit``) fails when the three disagree
  about the build count.

``tools compile-report`` aggregates the ledger into
top-programs-by-compile-cost, churn offenders and the dedupe projection
("N programs collapse to M under bucket canonicalization") — the
evidence the persistent-cache key design needs.

Overhead discipline: with the observatory disabled every ``process_jit``
call costs one extra attribute read; enabled, a warm call pays one
pytree flatten + dict lookup per batch (same cost class as the tracer's
per-batch bookkeeping, never a device touch or a lock on the warm
path).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import tracer as _tracer

log = logging.getLogger("spark_rapids_tpu.obs.compileprof")

LEDGER_FILENAME = "compile_ledger.jsonl"
LEDGER_VERSION = 1

# lowered-StableHLO persistence (the tpuxsan audit's raw material):
# blake2-keyed text files, deduped per program, size-capped so a
# pathological giant program cannot bloat the ledger dir
HLO_SUBDIR = "hlo"
HLO_SUFFIX = ".stablehlo.mlir"
HLO_MAX_BYTES = 2 * 1024 * 1024

# the canonical cost_analysis keys the audit consumes.  XLA backends
# report DIFFERENT subsets (CPU omits transcendentals and sometimes
# flops): only keys the backend actually returned are recorded — an
# absent key is absent, never zero.
COST_KEYS = ("flops", "bytes accessed", "transcendentals")


def hlo_key(text: str) -> str:
    """Content key of one lowered program's StableHLO text."""
    return hashlib.blake2b(text.encode("utf-8", "replace"),
                           digest_size=8).hexdigest()


def cost_summary(compiled) -> Optional[Dict[str, float]]:
    """The executable's own cost_analysis(), distilled to the canonical
    keys it actually reported.  Returns None when the backend offers no
    analysis at all — callers must treat that as 'unknown', not free."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {k: float(ca[k]) for k in COST_KEYS
           if k in ca and ca[k] is not None}
    return out or None

# miss-cause taxonomy (closed: every build carries exactly one)
CAUSE_NEW = "new_program"
CAUSE_SHAPE = "shape_churn"
CAUSE_DTYPE = "dtype_churn"
CAUSE_REFAULT = "eviction_refault"
CAUSES = (CAUSE_NEW, CAUSE_SHAPE, CAUSE_DTYPE, CAUSE_REFAULT)

# default bucket set for canonicalization, matching the config defaults
# (spark.rapids.tpu.batchCapacityBuckets / .stringDataBuckets); sessions
# override via configure() so changed bucket configs stay honest
_DEFAULT_BUCKETS = frozenset(
    (1024, 8192, 65536, 262144, 1048576, 4194304,
     16384, 131072, 8388608, 67108864, 268435456))

_CAP_MASK = "<cap>"

# jit families can out-card the default 64-series cap: exec kinds alone
# approach it, and misses fan out by cause
_JIT_MAX_SERIES = 256


def _stable_hash(obj: Any) -> str:
    """12-hex stable hash of a semantic key.  repr() is stable for the
    atoms semantic_sig produces (strings, ints, bytes, type names); the
    rare id()-keyed fallback entries hash per-process only — they can
    fragment cross-session aggregation, never corrupt it."""
    return hashlib.sha1(repr(obj).encode()).hexdigest()[:12]


def _mask_buckets(v: Any, buckets) -> Any:
    """The jit key with every capacity-bucket int replaced by a
    sentinel: two keys that differ only in bucket choice canonicalize
    to the same value (the dedupe axis of `tools compile-report`)."""
    if isinstance(v, bool):
        return v
    if isinstance(v, int):
        return _CAP_MASK if v in buckets else v
    if isinstance(v, tuple):
        return tuple(_mask_buckets(x, buckets) for x in v)
    if isinstance(v, list):
        return [_mask_buckets(x, buckets) for x in v]
    return v


def _exec_kind(key: tuple) -> str:
    """The operator kind from a process_jit key.  Keys arrive as
    (shim_version, kind, ...); the kind is the first string past the
    version for every call site in the tree."""
    for part in key[1:]:
        if isinstance(part, str):
            return part
    return str(key[1])[:40] if len(key) > 1 else "?"


#: the role strings call sites append to an operator's key: one
#: operator, several programs (exec/aggregate.py, exec/join.py,
#: exec/basic.py, parallel/distributed.py)
_ROLES = frozenset((
    "update", "merge", "merge_eval", "eval", "complete", "sortkeys",
    "rowpos", "count", "expand", "unmatched", "mask", "semi",
    "semi_count"))


#: the SPMD steps are keyed by the stage class that traces them
#: (parallel/distributed.py); a trace names them by the operator that
#: plans and dispatches them (parallel/ici_exec.py)
_OPERATOR_OF = {
    "DistributedAggregate": "IciAggregateExec",
    "DistributedSort": "IciSortExec",
    "DistributedHashJoin": "IciJoinExec",
    "DistributedExchange": "IciExchangeExec",
}


def program_name(key: tuple, fn=None) -> str:
    """``<exec kind>[.<role>]`` for a process_jit key: the name the
    program carries into XLA (module ``jit_<name>``), so a device trace
    says which operator built it — and keeps saying so when an edit to
    the program changes its fingerprint.  A helper program that several
    operators share says whose it is through its function's
    ``program_name`` (the mesh stages' reshard); no key holds a name."""
    given = getattr(fn, "program_name", None)
    if given:
        return given
    kind = _exec_kind(key)
    last = next((p for p in reversed(key) if isinstance(p, str)), kind)
    kind = _OPERATOR_OF.get(kind, kind)
    return f"{kind}.{last}" if last in _ROLES else kind


def _name_program(fn, key: tuple):
    """Name `fn` after the operator that built it, before jax.jit reads
    the name.  Call sites hand process_jit lambdas; a callable that
    takes no name (a partial, a bound method) stays as it is."""
    name = program_name(key, fn)
    try:
        fn.__name__ = fn.__qualname__ = name
    except (AttributeError, TypeError):
        pass
    return fn


# ---------------------------------------------------------------------------
# input-shape signatures
# ---------------------------------------------------------------------------

_PY_SCALARS = (int, float, bool, complex)


def _leaf_sig(leaf) -> Optional[Tuple]:
    """(dtype, shape, sharding) of one call-argument leaf, or None when
    the leaf has no stable signature (tracers under an enclosing trace,
    arbitrary objects) — the caller then falls back to plain jit
    dispatch.  The sharding joins the signature because an AOT-compiled
    executable bakes its input shardings in: a mesh-committed array
    (ICI stage output) and a single-device one are DIFFERENT programs
    (jit's own dispatch cache keys the same way)."""
    import jax
    if isinstance(leaf, jax.core.Tracer):
        return None
    dt = getattr(leaf, "dtype", None)
    shape = getattr(leaf, "shape", None)
    if dt is not None and shape is not None:
        return (str(dt), tuple(int(s) for s in shape),
                getattr(leaf, "sharding", None))
    if isinstance(leaf, _PY_SCALARS):
        # python scalars are weak-typed dynamic args under jit: the
        # TYPE picks the program, the value rides at call time
        return (type(leaf).__name__, (), None)
    return None


def _dispatch_key(args) -> Optional[tuple]:
    """Hashable per-call signature (treedef + leaf dtype/shape), or
    None when any leaf is unsignable."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sigs = []
    for leaf in leaves:
        s = _leaf_sig(leaf)
        if s is None:
            return None
        sigs.append(s)
    return (treedef, tuple(sigs))


def _erase_sharding(sig: tuple) -> tuple:
    """A dispatch key with leaf shardings dropped.  Prewarmed programs
    are compiled from ShapeDtypeStruct skeletons (no sharding), while
    concrete query calls carry committed-device shardings — the
    warm-start lookup matches on shapes/dtypes, for calls whose
    arguments all sit on the default device (``_on_default_device``)."""
    treedef, leaf_sigs = sig
    return (treedef, tuple((d, s, None) for d, s, _ in leaf_sigs))


def _on_default_device(sig: tuple) -> bool:
    """True when every leaf of a dispatch key is a host value or lives on
    the default device alone: what an executable compiled from a
    skeleton without shardings accepts.  Anything else (a mesh-sharded
    stage input, another chip) is a different program and builds cold."""
    import jax
    home = {jax.devices()[0]}
    return all(sh is None or sh.device_set == home
               for _, _, sh in sig[1])


def _aval_dispatch_key(args) -> Optional[tuple]:
    """Like _dispatch_key, but tracer leaves sign by their abstract
    value (shape/dtype, no sharding — an enclosing trace has none to
    offer).  Lets the plain-jit fallback path dedupe and ledger its
    builds under the SAME canonical key instead of silently forking
    the key space."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sigs = []
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            av = getattr(leaf, "aval", None)
            shape = getattr(av, "shape", None)
            dt = getattr(av, "dtype", None)
            if shape is None or dt is None:
                return None
            sigs.append((str(dt), tuple(int(d) for d in shape), None))
            continue
        s = _leaf_sig(leaf)
        if s is None:
            return None
        sigs.append(s)
    return (treedef, tuple(sigs))


def _shape_record(sig: tuple, buckets) -> Tuple[str, tuple, tuple, tuple]:
    """(shape_hash, dtype_sig, cap_sig, canon_caps) from a dispatch
    key.  cap_sig is the tuple of leaf shapes (the capacity buckets ride
    the leading dims); canon_caps masks bucket-valued dims.  The
    shardings join the shape hash (program identity) but not the
    dtype/cap signatures the cause classifier compares — a resharded
    rebuild reads as shape_churn, the nearest honest cause.  The
    treedef joins the hash too: same leaves under a different pytree
    structure (e.g. renamed batch columns) is a different program."""
    treedef, leaf_sigs = sig
    dtype_sig = tuple(s[0] for s in leaf_sigs)
    cap_sig = tuple(s[1] for s in leaf_sigs)
    shardings = tuple(repr(s[2]) for s in leaf_sigs)
    canon = tuple(tuple(_CAP_MASK if d in buckets else d for d in shp)
                  for shp in cap_sig)
    return (_stable_hash((repr(treedef), dtype_sig, cap_sig,
                          shardings)), dtype_sig, cap_sig, canon)


# ---------------------------------------------------------------------------
# the observatory
# ---------------------------------------------------------------------------

class CompileObservatory:
    """Process-wide singleton recording every XLA program build."""

    _instance: Optional["CompileObservatory"] = None
    _ilock = threading.Lock()

    def __init__(self):
        self._lock = threading.RLock()
        self.enabled = True
        self.ledger_path: Optional[str] = None
        self.hlo_dir: Optional[str] = None
        self.thrash_warn_ratio = 0.5
        self.buckets = frozenset(_DEFAULT_BUCKETS)
        # program index: pid = (key_hash, shape_hash)
        self._programs: Dict[Tuple[str, str], Dict] = {}
        self._resident: set = set()        # pids live in this process
        self._evicted: set = set()         # seen, no longer resident
        self._evicted_live: set = set()    # evicted by THIS process's LRU
        self._families: set = set()        # (exec, canon_key, dtype_hash)
        self._cap_index: Dict[Tuple[str, str], set] = {}
        # counters (read via snapshot(); the registry carries the
        # exported copies)
        self.builds = 0
        self.hits = 0
        self.evictions = 0
        self.refaults = 0
        self.compile_seconds_total = 0.0
        self.trace_seconds_total = 0.0
        self.by_cause: Dict[str, int] = {}
        self._warn_next = 1
        # warm-start tier: proxies readied from ledger recipes, waiting
        # for their process_jit miss to claim them (key -> _ProfiledJit)
        self._prewarm_staged: Dict[tuple, Any] = {}
        self.prewarm_hits = 0
        self.prewarm_seconds = 0.0
        self.prewarm_stats: Optional[Dict] = None

    # -- lifecycle -----------------------------------------------------------
    @classmethod
    def get(cls) -> "CompileObservatory":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = CompileObservatory()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "CompileObservatory":
        """Fresh observatory (tests and CI gates need known-empty
        indexes; production never calls this)."""
        with cls._ilock:
            cls._instance = CompileObservatory()
            return cls._instance

    def configure(self, enabled: Optional[bool] = None,
                  ledger_path: Optional[str] = None,
                  buckets=None,
                  thrash_warn_ratio: Optional[float] = None,
                  hlo_dir: Optional[str] = None) -> None:
        """Session-init wiring.  Setting a ledger path loads the prior
        sessions' program index, so cross-session rebuilds classify as
        refaults instead of novel work.  `hlo_dir` turns on lowered-
        StableHLO persistence (tpuxsan's raw material); the session
        defaults it to an hlo/ subdir next to the ledger."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if buckets is not None:
                self.buckets = frozenset(int(b) for b in buckets)
            if thrash_warn_ratio is not None:
                self.thrash_warn_ratio = float(thrash_warn_ratio)
            if hlo_dir is not None:
                self.hlo_dir = hlo_dir or None
            if ledger_path is not None and \
                    ledger_path != self.ledger_path:
                self.ledger_path = ledger_path
                self._load_ledger(ledger_path)

    def save_hlo(self, text: str) -> Tuple[str, bool]:
        """Persist one program's StableHLO text under its content key.
        Returns (key, persisted).  Dedupe is by filename: a program
        already on disk (this session or a prior one) is not rewritten.
        Oversized programs (> HLO_MAX_BYTES) record their key and size
        in the ledger but are not persisted."""
        key = hlo_key(text)
        d = self.hlo_dir
        if d is None or len(text) > HLO_MAX_BYTES:
            return key, False
        path = os.path.join(d, key + HLO_SUFFIX)
        if os.path.exists(path):
            return key, True
        try:
            os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                f.write(text)
            os.replace(tmp, path)
        except OSError as ex:  # persistence is telemetry, never fatal
            log.warning("HLO persist failed: %s", ex)
            return key, False
        return key, True

    def _load_ledger(self, path: str) -> None:
        if not os.path.exists(path):
            return
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if rec.get("event") != "build":
                        continue
                    pid = (rec.get("key", ""), rec.get("shape", ""))
                    if pid in self._resident:
                        continue
                    self._programs.setdefault(pid, rec)
                    self._evicted.add(pid)
                    self._families.add((rec.get("exec", ""),
                                        rec.get("canon_key", ""),
                                        rec.get("dtype_hash", "")))
                    self._cap_index.setdefault(
                        (rec.get("exec", ""), rec.get("cap_hash", "")),
                        set()).add(rec.get("dtype_hash", ""))
        except OSError as ex:
            log.warning("compile ledger unreadable: %s", ex)

    # -- the process_jit seam ------------------------------------------------
    def build(self, key: tuple, make_fn):
        """Called on a process_jit table miss: returns the callable the
        table stores.  Enabled -> an AOT proxy that times and records
        every per-shape program build; disabled -> plain jax.jit plus
        the legacy untimed jit.build event."""
        import jax
        fn = _name_program(make_fn(), key)
        jitted = jax.jit(fn)
        if not self.enabled:
            _tracer.trace_event("jit.build",
                                sig=str(_exec_kind(key))[:80])
            return jitted
        return _ProfiledJit(self, key, jitted, fn)

    def note_hit(self, key: tuple) -> None:
        if not self.enabled:
            return
        with self._lock:
            self.hits += 1
        _fam_hits().labels(exec=_exec_kind(key)).inc()
        self._update_shared_ratio()

    def note_eviction(self, key: tuple, fn) -> None:
        """One LRU eviction from the process jit table: counted,
        ledgered, and the entry's programs marked non-resident so a
        rebuild classifies as eviction_refault."""
        if not self.enabled:
            return
        exec_kind = _exec_kind(key)
        pids: List[Tuple[str, str]] = []
        if isinstance(fn, _ProfiledJit):
            pids = list(fn.built_pids())
        with self._lock:
            self.evictions += 1
            for pid in pids:
                self._resident.discard(pid)
                self._evicted.add(pid)
                self._evicted_live.add(pid)
        _fam_evictions().labels(exec=exec_kind).inc()
        self._append_ledger({
            "event": "evict", "exec": exec_kind,
            "key": _stable_hash(key),
            "programs": [p[1] for p in pids]})

    def note_clear(self) -> None:
        """clear_jit_cache(): a deliberate reset, not LRU pressure —
        resident programs become non-resident (rebuilds are honest
        refaults) but no eviction is counted and no thrash warning can
        arise from it."""
        with self._lock:
            self._evicted |= self._resident
            self._resident = set()

    def note_cache_size(self, n: int) -> None:
        if not self.enabled:
            return
        _fam_cache_size().set(n)

    # -- warm-start tier -----------------------------------------------------
    def save_recipe_for(self, key: tuple, key_hash: str, fn,
                        args: tuple) -> None:
        """Persist a program recipe after a successful AOT build so the
        next session (or `tools prewarm`) can replay it.  Best-effort:
        no ledger dir, no raw fn, or a failed pickle all no-op."""
        if not self.enabled or self.ledger_path is None or fn is None:
            return
        from . import prewarm as pw
        pw.save_recipe(self.ledger_path, key_hash, key, fn, args)

    def prewarm_entry(self, key: tuple, fn, abstract_list) -> int:
        """Replay one recipe: compile its recorded abstract signatures
        (flowing through JAX's persistent disk cache) and stage a
        dispatch-ready proxy for the matching process_jit miss.
        Returns the number of programs readied."""
        import jax
        if not self.enabled:
            return 0
        jitted = jax.jit(_name_program(fn, key))
        proxy = _ProfiledJit(self, key, jitted, fn)
        n = 0
        for abstract in abstract_list:
            try:
                sig = _dispatch_key(abstract)
                if sig is None:
                    continue
                t0 = time.perf_counter()
                compiled = jitted.lower(*abstract).compile()
                dt = time.perf_counter() - t0
            except Exception as ex:
                log.debug("prewarm replay failed for %s: %s",
                          proxy._key_hash, ex)
                continue
            proxy._prewarmed[_erase_sharding(sig)] = compiled
            n += 1
            with self._lock:
                self.prewarm_seconds += dt
            _fam_prewarm_seconds().inc(dt)
            self._append_ledger({
                "event": "prewarm", "exec": proxy._exec,
                "key": proxy._key_hash,
                "canon_key": proxy._canon_key,
                "total_s": round(dt, 6)})
        if n:
            with self._lock:
                self._prewarm_staged[key] = proxy
        return n

    def take_prewarmed(self, key: tuple):
        """Claim the staged proxy for a process_jit key, if a recipe
        replay readied one (called on the table's miss path)."""
        with self._lock:
            return self._prewarm_staged.pop(key, None)

    def note_prewarm_hit(self, exec_kind: str,
                         pid: Optional[Tuple[str, str]] = None) -> None:
        """One query call served by a prewarmed executable — the build
        the warm-start tier just avoided."""
        if not self.enabled:
            return
        with self._lock:
            self.prewarm_hits += 1
            if pid is not None:
                self._resident.add(pid)
                self._evicted.discard(pid)
                self._evicted_live.discard(pid)
        _fam_prewarm_hits().labels(exec=exec_kind).inc()
        self._update_shared_ratio()

    def note_prewarm_session(self, stats: Dict) -> None:
        with self._lock:
            self.prewarm_stats = dict(stats)

    def _update_shared_ratio(self) -> None:
        """tpu_jit_shared_program_ratio = distinct resident programs
        over total jit dispatches; 1.0 means every call built its own
        program, ->0 means the bucket-canonical key space is doing its
        job."""
        with self._lock:
            calls = self.hits + self.builds + self.prewarm_hits
            n = len(self._resident)
        _fam_shared_ratio().set(n / max(1, calls))

    # -- recording -----------------------------------------------------------
    def classify(self, exec_kind: str, pid: Tuple[str, str],
                 canon_key: str, dtype_hash: str,
                 cap_hash: str) -> str:
        """Cause of one build against the seen-program index; caller
        holds the lock."""
        if pid in self._evicted:
            return CAUSE_REFAULT
        if (exec_kind, canon_key, dtype_hash) in self._families:
            return CAUSE_SHAPE
        seen_dtypes = self._cap_index.get((exec_kind, cap_hash))
        if seen_dtypes and dtype_hash not in seen_dtypes:
            return CAUSE_DTYPE
        return CAUSE_NEW

    def record_build(self, exec_kind: str, key_hash: str,
                     canon_key: str, sig: tuple,
                     trace_s: Optional[float],
                     compile_s: Optional[float], total_s: float,
                     hlo_bytes: int, key_head: str,
                     hlo_hash: Optional[str] = None,
                     cost: Optional[Dict[str, float]] = None,
                     lane_moves: Optional[Dict[str, int]] = None
                     ) -> str:
        """Register one program build; returns the classified cause.
        `lane_moves` is what tracing the program raised of
        `ops/carry.lane_move_counts` (lane_moves_sorted,
        lane_moves_gathered, sort_passes) and of
        `parallel/alltoall.wire_byte_counts` (ici_wire_bytes, what one
        dispatch puts on the interconnect); None where the trace was not
        this build's own."""
        moves = lane_moves or {}
        shape_hash, dtype_sig, cap_sig, canon_caps = \
            _shape_record(sig, self.buckets)
        dtype_hash = _stable_hash(dtype_sig)
        cap_hash = _stable_hash(cap_sig)
        pid = (key_hash, shape_hash)
        with self._lock:
            cause = self.classify(exec_kind, pid, canon_key,
                                  dtype_hash, cap_hash)
            was_live = pid in self._evicted_live
            self.builds += 1
            self.by_cause[cause] = self.by_cause.get(cause, 0) + 1
            self.compile_seconds_total += compile_s or 0.0
            self.trace_seconds_total += trace_s or 0.0
            self._programs[pid] = {
                "exec": exec_kind, "key": key_hash,
                "canon_key": canon_key, "shape": shape_hash,
                "cause": cause, "total_s": total_s, **moves}
            self._resident.add(pid)
            self._evicted.discard(pid)
            self._evicted_live.discard(pid)
            self._families.add((exec_kind, canon_key, dtype_hash))
            self._cap_index.setdefault(
                (exec_kind, cap_hash), set()).add(dtype_hash)
            warn = None
            if cause == CAUSE_REFAULT and was_live:
                self.refaults += 1
                rate = self.refaults / max(1, self.evictions)
                if rate > self.thrash_warn_ratio and \
                        self.refaults >= self._warn_next:
                    self._warn_next = max(2, self.refaults * 2)
                    warn = (self.refaults, self.evictions, rate)
        if warn is not None:
            log.warning(
                "JIT cache thrash: %d of %d evicted programs were "
                "rebuilt (refault rate %.0f%% > %.0f%% threshold) — "
                "raise SPARK_RAPIDS_TPU_JIT_CACHE_MAX or reduce "
                "distinct query shapes per process",
                warn[0], warn[1], 100 * warn[2],
                100 * self.thrash_warn_ratio)
        _fam_misses().labels(exec=exec_kind, cause=cause).inc()
        self._update_shared_ratio()
        if total_s:
            _fam_compile_seconds().labels(
                exec=exec_kind, cause=cause).inc(total_s)
        self._append_ledger({
            "event": "build", "exec": exec_kind, "key": key_hash,
            "canon_key": canon_key, "shape": shape_hash,
            "dtype_hash": dtype_hash, "cap_hash": cap_hash,
            "cause": cause,
            "trace_s": None if trace_s is None else round(trace_s, 6),
            "compile_s": None if compile_s is None
            else round(compile_s, 6),
            "total_s": round(total_s, 6), "hlo_bytes": hlo_bytes,
            # tpuxsan: content key of the persisted StableHLO (None =
            # not captured) and the backend's own cost_analysis keys —
            # ONLY those the backend reported (absent != zero)
            "hlo_hash": hlo_hash, "cost": cost,
            "dtypes": list(dtype_sig),
            "caps": [list(s) for s in cap_sig],
            "canon_caps": [list(s) for s in canon_caps],
            "key_head": key_head, **moves})
        _tracer.trace_event("jit.build", op=exec_kind, cause=cause,
                    key=key_hash, shape=shape_hash,
                    total_s=round(total_s, 6),
                    trace_s=None if trace_s is None
                    else round(trace_s, 6),
                    compile_s=None if compile_s is None
                    else round(compile_s, 6),
                    hlo_bytes=hlo_bytes, sig=key_head, **moves)
        return cause

    def _append_ledger(self, rec: Dict) -> None:
        path = self.ledger_path
        if path is None:
            return
        rec = dict(rec, v=LEDGER_VERSION, ts=round(time.time(), 3),
                   os_pid=os.getpid())
        try:
            with self._lock:
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(rec, sort_keys=True) + "\n")
        except OSError as ex:  # the ledger is telemetry, never fatal
            log.warning("compile ledger append failed: %s", ex)

    # -- read side -----------------------------------------------------------
    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "builds": self.builds,
                "hits": self.hits,
                "evictions": self.evictions,
                "refaults": self.refaults,
                "compile_seconds_total":
                    round(self.compile_seconds_total, 6),
                "trace_seconds_total":
                    round(self.trace_seconds_total, 6),
                "by_cause": dict(self.by_cause),
                "distinct_programs": len(self._programs),
                "resident_programs": len(self._resident),
                "prewarm_hits": self.prewarm_hits,
                "prewarm_seconds": round(self.prewarm_seconds, 6),
                "prewarm": dict(self.prewarm_stats)
                if self.prewarm_stats else None,
                # one record a program (exec kind, hashes, cause,
                # seconds, how its rows move: lane_moves_sorted,
                # lane_moves_gathered, sort_passes, and what a dispatch
                # sends between chips: ici_wire_bytes)
                "programs": [dict(p) for p in self._programs.values()],
            }


# ---------------------------------------------------------------------------
# what tracing a program counts, and what dispatching it sends
# ---------------------------------------------------------------------------

def _trace_counts() -> Dict[str, int]:
    """The counts that tracing raises on this thread: how the program's
    rows move (ops/carry) and what its collectives put on the wire
    (parallel/alltoall).  The difference around one ``lower()`` is that
    program's."""
    from ..ops.carry import lane_move_counts
    from ..parallel.alltoall import wire_byte_counts
    return {**lane_move_counts(), **wire_byte_counts()}


class _Dispatched(threading.local):
    wire_bytes = 0


_DISPATCHED = _Dispatched()


def dispatched_wire_bytes() -> int:
    """Wire bytes of the programs this thread has dispatched so far; a
    mesh stage reads the difference around its dispatches."""
    return _DISPATCHED.wire_bytes


def _note_wire_bytes(nbytes: int) -> None:
    if nbytes:
        _DISPATCHED.wire_bytes += nbytes
        _registry().counter(
            "tpu_ici_wire_bytes_total",
            "bytes the dispatched programs' collectives hand to the "
            "interconnect (all_to_all, all_gather), summed over the "
            "mesh; from static shapes").inc(nbytes)


# ---------------------------------------------------------------------------
# the AOT proxy
# ---------------------------------------------------------------------------

class _ProfiledJit:
    """Callable stored in the process jit table: dispatches per
    input-shape signature to an AOT-compiled executable, timing the
    lower/compile split on each first-per-shape call."""

    __slots__ = ("_obs", "_key", "_key_hash", "_canon_key", "_exec",
                 "_key_head", "_jitted", "_fn", "_compiled",
                 "_prewarmed", "_traced_sigs", "_wire_bytes", "_lock")

    def __init__(self, obs: CompileObservatory, key: tuple, jitted,
                 fn=None):
        self._obs = obs
        self._key = key
        self._exec = _exec_kind(key)
        self._key_hash = _stable_hash(key)
        self._canon_key = _stable_hash(_mask_buckets(key, obs.buckets))
        self._key_head = str(key[1] if len(key) > 1 else key)[:80]
        self._jitted = jitted
        self._fn = fn  # the raw traced callable (prewarm recipes)
        self._compiled: Dict[tuple, Any] = {}
        # warm-start tier: executables replayed from a prior session's
        # recipes, keyed by sharding-erased signature
        self._prewarmed: Dict[tuple, Any] = {}
        self._traced_sigs: set = set()  # aval sigs seen under a trace
        # shape signature -> bytes one dispatch of that program puts on
        # the interconnect; only programs with collectives are listed
        self._wire_bytes: Dict[tuple, int] = {}
        self._lock = threading.Lock()

    def built_pids(self) -> List[Tuple[str, str]]:
        return [(self._key_hash,
                 _shape_record(sk, self._obs.buckets)[0])
                for sk in list(self._compiled)]

    def __call__(self, *args):
        # the one test a launch makes for the tracer's sinks; with both
        # off (where the parent tested once, in _dispatch) no span opens
        sinks_on = _tracer.ANNOTATIONS_ON or \
            _tracer.active_tracer() is not None
        if sinks_on:
            # what finding the program costs the host: the signature of
            # the arguments and the lookup, before any launch
            with _tracer.trace_span("jit.key:" + self._exec):
                sig = _dispatch_key(args)
        else:
            sig = _dispatch_key(args)
        if sig is None:
            # unsignable leaves (e.g. called under an enclosing trace):
            # plain jit dispatch, recorded under the same canonical key
            return self._traced_call(args)
        fn = self._compiled.get(sig)
        if fn is not None:
            return self._dispatch(fn, args, sig, sinks_on)
        if self._prewarmed and _on_default_device(sig):
            fn = self._prewarmed.get(_erase_sharding(sig))
            if fn is not None:
                out = self._dispatch(fn, args, None, sinks_on)
                with self._lock:
                    self._compiled.setdefault(sig, fn)
                self._obs.note_prewarm_hit(
                    self._exec,
                    (self._key_hash,
                     _shape_record(sig, self._obs.buckets)[0]))
                return out
        return self._build_and_call(sig, args, sinks_on)

    def _dispatch(self, fn, args, sig, sinks_on):
        """The call into the compiled executable, as the span
        ``jit.dispatch:<exec kind>`` when either of the tracer's sinks
        is on (`sinks_on`, as ``__call__`` found it): the host's side of
        every program launch.  A program with collectives adds its wire
        bytes (found when it was traced) to ``tpu_ici_wire_bytes_total``;
        no device value is read."""
        if self._wire_bytes:
            _note_wire_bytes(self._wire_bytes.get(sig, 0))
        if not sinks_on:
            return fn(*args)
        with _tracer.trace_span("jit.dispatch:" + self._exec):
            return fn(*args)

    def _traced_call(self, args):
        """Plain-jit dispatch for tracer-leaf calls — but the first call
        per aval signature is still timed (the inline trace is real
        compile work) and record_build'ed under this entry's canonical
        key, so fallback builds dedupe and reach the ledger instead of
        vanishing."""
        sig = _aval_dispatch_key(args)
        if sig is None:
            return self._jitted(*args)
        with self._lock:
            known = sig in self._traced_sigs or sig in self._compiled
            if not known:
                self._traced_sigs.add(sig)
        if known:
            return self._jitted(*args)
        t0 = time.perf_counter()
        out = self._jitted(*args)
        dt = time.perf_counter() - t0
        self._obs.record_build(self._exec, self._key_hash,
                               self._canon_key, sig, dt, None, dt, 0,
                               self._key_head)
        return out

    def _build_and_call(self, sig, args, sinks_on):
        with self._lock:
            fn = self._compiled.get(sig)
            if fn is None:
                # lower + compile-or-cache-load as one span; the
                # jit.build instant event record_build emits inside it
                # carries the split timing for the flight recorder
                with _tracer.trace_span("jit.build:" + self._exec) as span:
                    fn = self._build(sig, args, span)
                self._compiled[sig] = fn
        return self._dispatch(fn, args, sig, sinks_on)

    def _build(self, sig, args, span):
        t0 = time.perf_counter()
        hlo_bytes = 0
        hlo_hash = None
        # a lower or compile failure is the compiler's refusal of this
        # program: it surfaces here, once, with its message
        before = _trace_counts()
        lowered = self._jitted.lower(*args)
        lane_moves = {k: v - before[k]
                      for k, v in _trace_counts().items()}
        span.set(**lane_moves)
        if lane_moves["ici_wire_bytes"]:
            self._wire_bytes[sig] = lane_moves["ici_wire_bytes"]
        t1 = time.perf_counter()
        trace_s = t1 - t0
        try:
            text = lowered.as_text()
            hlo_bytes = len(text)
            hlo_hash, _ = self._obs.save_hlo(text)
        except Exception:
            hlo_bytes = 0
        fn = lowered.compile()
        compile_s = time.perf_counter() - t1
        cost = cost_summary(fn)
        self._obs.save_recipe_for(self._key, self._key_hash,
                                  self._fn, args)
        total_s = time.perf_counter() - t0
        self._obs.record_build(self._exec, self._key_hash,
                               self._canon_key, sig, trace_s,
                               compile_s, total_s, hlo_bytes,
                               self._key_head, hlo_hash=hlo_hash,
                               cost=cost, lane_moves=lane_moves)
        return fn


# ---------------------------------------------------------------------------
# metric families (created idempotently; cached to keep the seam cheap)
# ---------------------------------------------------------------------------

def _registry():
    from . import metrics
    return metrics.registry()


def _fam_hits():
    return _registry().counter(
        "tpu_jit_hits_total", "process jit-table hits", ("exec",),
        max_series=_JIT_MAX_SERIES)


def _fam_misses():
    return _registry().counter(
        "tpu_jit_misses_total",
        "program builds (jit-table or per-shape misses), by cause",
        ("exec", "cause"), max_series=_JIT_MAX_SERIES)


def _fam_evictions():
    return _registry().counter(
        "tpu_jit_evictions_total", "process jit-table LRU evictions",
        ("exec",), max_series=_JIT_MAX_SERIES)


def _fam_compile_seconds():
    return _registry().counter(
        "tpu_jit_compile_seconds_total",
        "wall seconds spent building programs (trace+lower+compile)",
        ("exec", "cause"), max_series=_JIT_MAX_SERIES)


def _fam_cache_size():
    return _registry().gauge(
        "tpu_jit_cache_size", "live entries in the process jit table")


def _fam_prewarm_hits():
    return _registry().counter(
        "tpu_jit_prewarm_hits_total",
        "query calls served by a warm-start-tier (prewarmed) program",
        ("exec",), max_series=_JIT_MAX_SERIES)


def _fam_prewarm_seconds():
    return _registry().counter(
        "tpu_jit_prewarm_seconds_total",
        "wall seconds spent replaying program recipes at session init")


def _fam_shared_ratio():
    return _registry().gauge(
        "tpu_jit_shared_program_ratio",
        "distinct resident programs / jit dispatches "
        "(1.0 = no sharing, ->0 = canonical keys collapsing the space)")


# ---------------------------------------------------------------------------
# persistent disk-cache metrics (satellite of ROADMAP item 1)
# ---------------------------------------------------------------------------

_DISK_EVENTS = {
    "/jax/compilation_cache/cache_hits":
        ("tpu_jit_persistent_cache_hits_total",
         "persistent XLA compile-cache disk hits"),
    "/jax/compilation_cache/cache_misses":
        ("tpu_jit_persistent_cache_misses_total",
         "persistent XLA compile-cache disk misses"),
}

_disk_listener_installed = False


def install_persistent_cache_metrics() -> None:
    """Count JAX's own persistent-compilation-cache disk hits/misses
    into the registry (idempotent; wired at plugin init next to
    jax_compilation_cache_dir).  This is the measurement that tells
    ROADMAP item 1 whether the disk cache works."""
    global _disk_listener_installed
    if _disk_listener_installed:
        return
    try:
        import jax.monitoring as mon
    except Exception:
        return

    def _on_event(event, **kw):
        fam = _DISK_EVENTS.get(event)
        if fam is not None:
            _registry().counter(fam[0], fam[1]).inc()

    mon.register_event_listener(_on_event)
    _disk_listener_installed = True


def observatory() -> CompileObservatory:
    return CompileObservatory.get()
