"""Basic physical operators: scan, project, filter, range, union, limits,
sample, coalesce-batches.

Ref: sql-plugin/.../basicPhysicalOperators.scala:140-592 (GpuProjectExec,
GpuFilterExec, GpuRangeExec, GpuUnionExec), limit.scala, GpuCoalesceBatches.

TPU realization: Project/Filter trace their whole expression tree into one
jitted function per (schema, capacity) signature — XLA fuses every
elementwise op into a handful of kernels, where the reference pays one JNI
kernel launch per expression node.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from ..columnar.device import (DEFAULT_ROW_BUCKETS, DeviceBatch, DeviceColumn,
                               batch_to_device, bucket_for)
from ..columnar.fetch import fetch_array
from ..expr.core import (EvalContext, Expression, bind_expression,
                         output_name)
from ..ops.gather import gather_batch
from .base import (CPU, NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, TPU,
                   Batch, Exec, ExecContext, MetricTimer,
                   _wrap_execute_partition, maybe_sync, process_jit,
                   schema_sig, semantic_sig)


class LocalScanExec(Exec):
    """Scan over in-memory Arrow data split into partitions
    (analog of Spark's LocalTableScanExec feeding the plugin)."""

    #: set by ``parallel/ici_exec.install_ici_stages`` on a scan that
    #: feeds a mesh stage through partition-local operators alone (ICI
    #: transport, several chips): its partitions are placed one a mesh
    #: device, round robin, and pinned there
    mesh_resident = False

    def __init__(self, table: pa.Table, num_partitions: int = 1,
                 batch_rows: Optional[int] = None,
                 pin_cache: Optional[dict] = None):
        super().__init__([])
        self.table = table
        self._names = list(table.schema.names)
        from ..columnar.interop import from_arrow_type
        self._types = [from_arrow_type(f.type) for f in table.schema]
        self._num_partitions = max(1, num_partitions)
        self.batch_rows = batch_rows
        # upload pin cache owned by the logical LocalRelation node: keeps
        # device batches resident across collects so a cached DataFrame
        # never re-uploads (round-2 probe: re-upload was ~9% of q1's time
        # and forced an extra pipeline stall per query)
        self.pin_cache = pin_cache

    @property
    def output_names(self):
        return self._names

    @property
    def output_types(self):
        return self._types

    @property
    def num_partitions(self):
        return self._num_partitions

    def estimated_size_bytes(self):
        return self.table.nbytes

    def memory_effects(self, child_states, conf):
        """A device-placed scan with a pin cache keeps every uploaded
        batch HBM-resident across collects — sanctioned retention
        (evicted first under pressure), but real peak bytes."""
        from .. import config as cfg
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         total_bytes)
        from .base import TPU as _TPU
        if self.pin_cache is None or self.placement != _TPU or \
                not conf.get(cfg.SCAN_PIN_DEVICE):
            return None
        from ..analysis.absdomain import AbstractState
        st = AbstractState(self._names, self._types,
                           rows=float(self.table.num_rows),
                           num_partitions=self._num_partitions)
        return MemoryEffects(hold=padded_partition_bytes(st),
                             retained=total_bytes(st),
                             note="pinned scan cache")

    def _pin_key(self, pid):
        return (pid, self._num_partitions, self.batch_rows,
                self.placement) + (("mesh",) if self.mesh_resident else ())

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from .. import config as cfg
        key = self._pin_key(pid)
        pin = self.pin_cache if (self.pin_cache is not None and
                                 ctx.conf.get(cfg.SCAN_PIN_DEVICE)) else None
        if pin is not None and key in pin:
            for b in pin[key]:
                # scan batches always carry a concrete row count
                self.metrics[NUM_OUTPUT_ROWS] += int(b.num_rows)
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield b
            return
        produced: List[Batch] = []
        for b in self._produce_partition(pid, ctx):
            if pin is not None:
                produced.append(b)
            yield b
        if pin is not None:
            pin[key] = produced
            if self.placement == TPU:
                # account pinned HBM against the spill budget; under
                # pressure the catalog evicts this entry (re-upload on
                # next miss).  CPU-engine pins are host numpy — cached
                # for conversion cost only, no HBM accounting.
                from ..memory.spill import SpillCatalog
                SpillCatalog.get().register_pinned(pin, key, produced)

    @property
    def pinned_devices(self) -> int:
        """Distinct devices this scan's pinned lanes lie on (0 before
        the first call, or with nothing pinned)."""
        devs = set()
        for pid in range(self._num_partitions):
            batches = (self.pin_cache or {}).get(self._pin_key(pid), ())
            for leaf in jax.tree_util.tree_leaves(
                    [b.columns for b in batches]):
                if isinstance(leaf, jax.Array):
                    devs |= leaf.devices()
        return len(devs)

    def _partition_device(self, pid):
        """The chip that keeps partition ``pid``: mesh device
        ``pid % n_dev`` where a mesh stage reads this scan in place
        (``mesh_resident``); otherwise None, JAX's default device."""
        if not self.mesh_resident or self.placement != TPU:
            return None
        from ..parallel.mesh import discover_devices
        devs = discover_devices()
        return devs[pid % len(devs)] if len(devs) > 1 else None

    def _produce_partition(self, pid, ctx) -> Iterator[Batch]:
        device = self._partition_device(pid)
        n = self.table.num_rows
        per = -(-n // self._num_partitions)
        start = min(pid * per, n)
        length = min(per, n - start)
        chunk = self.table.slice(start, length)
        rows = self.batch_rows or max(length, 1)
        xp = self.xp
        offset = 0
        combined = chunk.combine_chunks()
        while offset < max(length, 1):
            piece = combined.slice(offset, min(rows, length - offset))
            rb = piece.to_batches()
            if rb:
                b = batch_to_device(pa.Table.from_batches(rb).combine_chunks()
                                    .to_batches()[0], xp=xp, device=device)
            else:
                b = batch_to_device(
                    pa.RecordBatch.from_pydict(
                        {n_: pa.array([], type=f.type)
                         for n_, f in zip(self._names, self.table.schema)}),
                    xp=xp, device=device)
            self.metrics[NUM_OUTPUT_ROWS] += b.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield b
            offset += rows
            if length == 0:
                break


class ProjectExec(Exec):
    """Columnar projection (ref GpuProjectExec, basicPhysicalOperators.scala:140).

    A projection whose every output is a column reference, aliased or
    not (`_selection`: what a planner's column pruning leaves between a
    filter and a join), forwards the filter's masked batches to the join
    (`execute_masked`): the selected columns as they lay, the keep flags
    and their count, and no program at all.  A projection that computes
    anything is NOT forwarded through: its expressions would run over
    rows the filter dropped, so it takes the compacted batch, as ever."""

    def __init__(self, exprs: Sequence[Expression], child: Exec):
        super().__init__([child])
        self.exprs = list(exprs)
        bound = [bind_expression(e, child.output_names,
                                 child.output_types)
                 for e in self.exprs]
        # hoist eligible constants to ParamLiteral slots: the jit key
        # drops the values, two projections differing only in literals
        # share one program (expr/params.py has the safety rules)
        from ..expr.params import parameterize_exprs
        self._bound, self._params = parameterize_exprs(bound)

    @property
    def output_names(self):
        return [output_name(e) for e in self.exprs]

    @property
    def output_types(self):
        return [b.data_type() for b in self._bound]

    def describe(self):
        return f"Project [{', '.join(e.sql() for e in self.exprs)}]"

    def _compute(self, xp, batch: Batch, row_base=0, params=None) -> Batch:
        ctx = EvalContext(xp, batch, row_base=row_base,
                          params=params if params is not None
                          else (self._params or None))
        cols = []
        for b in self._bound:
            v = b.eval(ctx)
            from ..expr.core import ColumnValue, ScalarValue
            if isinstance(v, ScalarValue):
                from ..expr.core import make_column
                v = make_column(ctx, b.data_type() if not isinstance(
                    b.data_type(), t.NullType) else t.NULL,
                    v.value if v.value is not None else 0,
                    None if v.value is not None else False)
            cols.append(v.col)
        return DeviceBatch(cols, batch.num_rows, self.output_names)

    @functools.cached_property
    def _jit_key(self):
        return ("ProjectExec", schema_sig(self.children[0]),
                tuple(self.output_names), semantic_sig(self._bound))

    @property
    def _jitted(self):
        if self._params:
            # params ride as traced scalar args: the value-free key is
            # only valid because the closure receives them at call time
            fn = process_jit(
                self._jit_key,
                lambda: lambda b, ps: self._compute(jnp, b, params=ps))
            return lambda b: fn(b, self._params)
        return process_jit(self._jit_key,
                           lambda: lambda b: self._compute(jnp, b))

    @property
    def _jitted_rowpos(self):
        if self._params:
            fn = process_jit(
                self._jit_key + ("rowpos",),
                lambda: lambda b, base, ps: self._compute(jnp, b, base,
                                                          params=ps))
            return lambda b, base: fn(b, base, self._params)
        return process_jit(self._jit_key + ("rowpos",),
                           lambda: lambda b, base: self._compute(jnp, b,
                                                                 base))

    @functools.cached_property
    def _needs_rowpos(self):
        return _exprs_need_rowpos(self._bound)

    @functools.cached_property
    def _selection(self) -> Optional[tuple]:
        """The child's ordinal behind every output where each is a bare
        column reference (an alias only renames), else None."""
        from ..expr.core import Alias, BoundReference
        refs = [b.child if isinstance(b, Alias) else b for b in self._bound]
        if all(isinstance(r, BoundReference) for r in refs):
            return tuple(r.ordinal for r in refs)
        return None

    def can_mask(self) -> bool:
        """True where this projection hands up its child's mask to the
        consumer above (`filter_common.masked_child`)."""
        return self.masked_sources()[0] is not None

    def masked_sources(self) -> tuple:
        """(the child this projection reads masked, or None): a bare
        selection forwards what a masking child hands up."""
        if self._selection is None:
            return (None,)
        from .filter_common import masked_child
        return (masked_child(self, self.children[0]),)

    @_wrap_execute_partition
    def execute_masked(self, pid, ctx, consumer):
        """The child's masked batches with this selection's columns, for
        the `consumer` the plan paired with it (`filter_common`); nothing
        is evaluated, on a dropped row or any other."""
        from .filter_common import MaskedBatch, check_paired
        check_paired(self, consumer)
        names = self.output_names
        for m in self.children[0].execute_masked(pid, ctx, self):
            self.metrics[NUM_OUTPUT_ROWS] += m.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield MaskedBatch(
                DeviceBatch([m.batch.columns[i] for i in self._selection],
                            m.batch.num_rows, names), m.keep, m.num_rows)

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        xp = self.xp
        offset = 0
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                if self._needs_rowpos:
                    base = (pid << 33) + offset
                    out = self._jitted_rowpos(b, jnp.int64(base)) \
                        if self.placement == TPU \
                        else self._compute(np, b, base)
                else:
                    out = self._jitted(b) if self.placement == TPU \
                        else self._compute(np, b)
                maybe_sync(out)
            if self._needs_rowpos:
                offset += int(b.num_rows)
            self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield out


def _exprs_need_rowpos(bound_exprs) -> bool:
    """True when any expression depends on (partition, row-position)
    context — monotonically_increasing_id / spark_partition_id / rand."""
    from ..expr.hashfns import (MonotonicallyIncreasingID, Rand,
                                SparkPartitionID)
    kinds = (MonotonicallyIncreasingID, Rand, SparkPartitionID)
    for b in bound_exprs:
        if b.collect(lambda e: isinstance(e, kinds)):
            return True
    return False


class FilterExec(Exec):
    """Columnar filter (ref GpuFilterExec, basicPhysicalOperators.scala:220).

    The plan says what becomes of the predicate's keep flags
    (`exec/filter_common`):

      - `execute_partition`, for every consumer that reads rows by
        position: **compaction**.  Static shapes: a stable partition on
        the keep flag moves the surviving rows to the front, every lane
        by a sort pass; `num_rows` shrinks to the survivor count.
      - `execute_masked`, for a consumer that keeps dead rows apart by
        flags: the update side of the TPU aggregate directly above, or
        either side of a TPU `HashJoinExec` directly above or above a
        bare selection (the consumer's `masked_sources()` pairs them,
        and nothing else pulls it): **the mask alone**.  The program
        `jit_FilterExec.mask` evaluates the predicate and hands up the
        keep flags and their count; the input batch goes up as it lay.
        No sort pass, no prefix sum, no copy of a lane."""

    def __init__(self, condition: Expression, child: Exec):
        super().__init__([child])
        self.condition = condition
        bound = bind_expression(condition, child.output_names,
                                child.output_types)
        from ..expr.params import parameterize_exprs
        trees, self._params = parameterize_exprs([bound])
        self._bound = trees[0]
        # armed by the TPU-L018 pre-flight repair
        # (analysis/hloaudit.try_rebucket_repair): shrink compacted
        # output to this bucket under a deferred speculation guard
        self.rebucket_cap: Optional[int] = None

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def describe(self):
        return f"Filter [{self.condition.sql()}]"

    def _keep(self, xp, batch: Batch, row_base, params):
        ctx = EvalContext(xp, batch, row_base=row_base,
                          params=params if params is not None
                          else (self._params or None))
        from .filter_common import keep_flags
        return keep_flags(xp, batch, self._bound.eval(ctx))

    def _compute(self, xp, batch: Batch, row_base=0, params=None) -> Batch:
        from ..ops.carry import count_filter
        from .filter_common import compact
        count_filter(masked=False)
        return compact(xp, batch, self._keep(xp, batch, row_base, params),
                       self.output_names)

    def _compute_mask(self, xp, batch: Batch, row_base=0, params=None):
        """(keep flags, their count): all that `execute_masked` computes."""
        from ..ops.carry import count_filter
        from .filter_common import count_kept
        count_filter(masked=True)
        keep = self._keep(xp, batch, row_base, params)
        return keep, count_kept(xp, keep)

    @functools.cached_property
    def _jit_key(self):
        return ("FilterExec", schema_sig(self.children[0]),
                semantic_sig(self._bound))

    def _program(self, compute, *role):
        """`compute` (`_compute`, `_compute_mask`) as a process-wide
        program under this filter's key and `role`; hoisted literals ride
        as traced arguments, and so does the row base where the
        predicate reads a row's position (`role` then holds "rowpos")."""
        key = self._jit_key + role
        rowpos = "rowpos" in role
        if not self._params:
            return process_jit(key, lambda: (
                (lambda b, base: compute(jnp, b, base)) if rowpos
                else (lambda b: compute(jnp, b))))
        fn = process_jit(key, lambda: (
            (lambda b, base, ps: compute(jnp, b, base, params=ps)) if rowpos
            else (lambda b, ps: compute(jnp, b, params=ps))))
        return lambda *args: fn(*args, self._params)

    @functools.cached_property
    def _needs_rowpos(self):
        return _exprs_need_rowpos([self._bound])

    def _run(self, compute, role, b, pid, offset):
        """`compute` over one input batch, on this filter's engine."""
        base = ((pid << 33) + offset,) if self._needs_rowpos else ()
        if self.placement != TPU:
            return compute(np, b, *base)
        if base:
            role = ("rowpos",) + role
        return self._program(compute, *role)(b, *map(jnp.int64, base))

    def _note_output(self, path: str, rows) -> None:
        self.metrics[NUM_OUTPUT_ROWS] += rows
        self.metrics[NUM_OUTPUT_BATCHES] += 1
        from ..obs import metrics as m
        m.counter("tpu_filter_batches_total",
                  "batches a FilterExec answered, by what became of the "
                  "keep flags: compact (the kept rows moved to the "
                  "front), mask (the flags handed up to the aggregate or "
                  "the join above, no lane moved)",
                  ("path",)).labels(path=path).inc()

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        offset = 0
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                out = self._run(self._compute, (), b, pid, offset)
                cap = self.rebucket_cap
                if (cap is not None and self.placement == TPU and
                        ctx.speculation_enabled and cap < out.capacity):
                    # speculative re-bucket (TPU-L018 repair): survivors
                    # are compacted to the front, so slicing to the
                    # right-sized bucket is exact whenever the guard
                    # holds; a missed guess re-executes the query with
                    # speculation disabled before results surface
                    from ..columnar.device import shrink_batch
                    ctx.add_spec_guard(out.num_rows <= cap)
                    out = shrink_batch(out, cap)
                maybe_sync(out)
            if self._needs_rowpos:
                offset += int(b.num_rows)
            self._note_output("compact", out.num_rows)
            yield out

    def can_mask(self) -> bool:
        """True where this filter hands up its mask to a consumer that
        reads one (`filter_common.masked_child`): on the TPU engine and
        `rebucket_cap` not armed."""
        return self.placement == TPU and self.rebucket_cap is None

    @_wrap_execute_partition
    def execute_masked(self, pid, ctx, consumer):
        """Each input batch as it lay, with its keep flags
        (`filter_common.MaskedBatch`), for `consumer` alone: the
        aggregate, join or bare selection whose `masked_sources()` names
        this filter.  Anything else is refused before a batch is made,
        so a masked batch reaches nothing that does not read the mask."""
        from .filter_common import MaskedBatch, check_paired
        check_paired(self, consumer)
        offset = 0
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                keep, kept = self._run(self._compute_mask, ("mask",), b,
                                       pid, offset)
                maybe_sync(keep)
            if self._needs_rowpos:
                offset += int(b.num_rows)
            self._note_output("mask", kept)
            yield MaskedBatch(b, keep, kept)


class RangeExec(Exec):
    """range(start, end, step) table generator (ref GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, name: str = "id",
                 max_batch_rows: int = 1 << 20):
        super().__init__([])
        assert step != 0
        self.start, self.end, self.step = start, end, step
        self._name = name
        self._num_partitions = num_partitions
        self.max_batch_rows = max_batch_rows

    @property
    def output_names(self):
        return [self._name]

    @property
    def output_types(self):
        return [t.LONG]

    @property
    def num_partitions(self):
        return self._num_partitions

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        xp = self.xp
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self._num_partitions)
        lo = min(pid * per, total)
        hi = min(lo + per, total)
        i = lo
        while i < hi:
            n = min(self.max_batch_rows, hi - i)
            cap = bucket_for(n, DEFAULT_ROW_BUCKETS)
            vals = (xp.arange(cap, dtype=xp.int64) + np.int64(i)) * \
                np.int64(self.step) + np.int64(self.start)
            col = DeviceColumn(t.LONG, data=vals,
                               validity=xp.arange(cap) < n)
            b = DeviceBatch([col], n, [self._name])
            self.metrics[NUM_OUTPUT_ROWS] += n
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield b
            i += n
        if lo >= hi:
            return


class UnionExec(Exec):
    """Concatenation of children's partitions (ref GpuUnionExec)."""

    def __init__(self, children: Sequence[Exec]):
        super().__init__(children)

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    @property
    def num_partitions(self):
        return sum(c.num_partitions for c in self.children)

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "union interleaves child partitions: output "
            "row order follows child emission, content multiset is "
            "invariant")

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        for c in self.children:
            if pid < c.num_partitions:
                yield from c.execute_partition(pid, ctx)
                return
            pid -= c.num_partitions


class LocalLimitExec(Exec):
    """Per-partition limit (ref limit.scala GpuLocalLimitExec)."""

    def __init__(self, limit: int, child: Exec):
        super().__init__([child])
        self.limit = limit

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def determinism(self):
        from ..analysis.determinism import BIT_EXACT, Determinism
        return Determinism(
            BIT_EXACT, "limit selects the first rows by input "
            "position", order_sensitive_selection=True)

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        remaining = self.limit
        xp = self.xp
        for b in self.children[0].execute_partition(pid, ctx):
            # a device row count is a blocking read: through the
            # sanctioned fetch, so it is a fetch.crossing span and counts
            n = int(fetch_array(b.num_rows))
            take = min(n, remaining)
            if take < n:
                mask = xp.arange(b.capacity) < take
                cols = [DeviceColumn(c.dtype, data=c.data,
                                     validity=(c.validity & mask)
                                     if c.validity is not None else mask,
                                     offsets=c.offsets, data_hi=c.data_hi,
                                     children=c.children)
                        for c in b.columns]
                b = DeviceBatch(cols, take, b.names)
            remaining -= take
            yield b
            if remaining <= 0:
                return


class GlobalLimitExec(LocalLimitExec):
    """Whole-result limit; planner ensures single partition upstream."""


class SampleExec(Exec):
    """Bernoulli sampling (ref GpuSampleExec in basicPhysicalOperators).

    Deterministic: the keep decision hashes (seed, partition, global row
    index) with a splitmix-style mixer, so CPU and TPU engines sample the
    same rows — the property the differential harness relies on, the way
    Spark ties sampling to (seed, partitionId)."""

    def __init__(self, fraction: float, seed: int, child: Exec):
        super().__init__([child])
        assert 0.0 <= fraction <= 1.0
        self.fraction = float(fraction)
        self.seed = int(seed) & 0xFFFFFFFF

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def describe(self):
        return f"Sample fraction={self.fraction} seed={self.seed}"

    def determinism(self):
        from ..analysis.determinism import BIT_EXACT, Determinism
        return Determinism(
            BIT_EXACT, "seeded hash of (seed, partition, global row "
            "index): the keep decision follows the running row offset, "
            "i.e. input arrival order", order_sensitive_selection=True)

    def _keep_mask(self, xp, cap: int, row_offset: int, pid: int):
        idx = (xp.arange(cap, dtype=np.uint32) + np.uint32(row_offset))
        h = idx ^ np.uint32(self.seed * 0x9E3779B9 + pid * 0x85EBCA6B
                            & 0xFFFFFFFF)
        h = (h ^ (h >> 16)) * np.uint32(0x85EBCA6B)
        h = (h ^ (h >> 13)) * np.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        return (h & np.uint32(0xFFFFFF)).astype(np.float64) / float(1 << 24) \
            < self.fraction

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from .filter_common import compact
        xp = self.xp
        row_offset = 0
        for b in self.children[0].execute_partition(pid, ctx):
            with MetricTimer(self.metrics[OP_TIME]):
                keep = self._keep_mask(xp, b.capacity, row_offset, pid)
                live = b.row_mask()
                out = compact(xp, b, keep & live, self.output_names)
                maybe_sync(out)
            row_offset += int(b.num_rows)
            self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield out


class CoalesceBatchesExec(Exec):
    """Concatenate small batches up to a target size goal
    (ref GpuCoalesceBatches.scala:519, CoalesceGoal)."""

    def __init__(self, child: Exec, target_rows: Optional[int] = None,
                 require_single_batch: bool = False):
        super().__init__([child])
        self.target_rows = target_rows
        self.require_single_batch = require_single_batch

    def memory_effects(self, child_states, conf):
        """Accumulates raw pending batches up to the target before each
        concat: the pending set plus its concatenated copy coexist."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes)
        if not child_states:
            return None
        return MemoryEffects(
            hold=2.0 * padded_partition_bytes(child_states[0]),
            note="raw pending concat")

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "re-batches in arrival order: batch "
            "boundaries follow arrival, row multiset is invariant")

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from .concat import concat_batches
        xp = self.xp
        pending: List[Batch] = []
        pending_rows = 0
        target = self.target_rows or (1 << 22)
        for b in self.children[0].execute_partition(pid, ctx):
            if isinstance(b.num_rows, (int, np.integer)):
                n = int(b.num_rows)
                if n == 0:
                    continue
            else:
                # device-resident row count (jitted producer / speculative
                # join): forcing it to host costs a sync per batch —
                # account by capacity and keep the pipeline async
                n = b.capacity
            pending.append(b)
            pending_rows += n
            if not self.require_single_batch and pending_rows >= target:
                yield pending[0] if len(pending) == 1 else \
                    concat_batches(xp, pending, self.output_names,
                                   self.output_types)
                pending, pending_rows = [], 0
        if pending:
            yield pending[0] if len(pending) == 1 else \
                concat_batches(xp, pending, self.output_names,
                               self.output_types)
