"""ICI-routed physical operators: plug the SPMD mesh stages into the
regular query path.

Ref: the reference substitutes its accelerated UCX shuffle under
`spark.rapids.shuffle.transport` (GpuShuffleEnv.isRapidsShuffleEnabled →
RapidsShuffleInternalManagerBase); here
`spark.rapids.shuffle.transport=ici` + a multi-chip mesh substitutes the
fused partial→all_to_all→final aggregate stage
(parallel/distributed.py) for the host-orchestrated
partial→exchange→final triple.  A post-conversion pass rewrites the plan
exactly where the reference's shuffle manager would take over the
exchange.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional

import numpy as np
import pyarrow as pa

from .. import config as cfg
from ..exec.base import (NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, TPU,
                         Batch, Exec, MetricTimer, to_host_batch)
from ..columnar.interop import to_arrow_schema
from ..obs.tracer import trace_span


#: how a stage's input reached the mesh: shards taken where they lie,
#: one batch resharded on device, or staged through host Arrow
RESIDENT, STACKED, HOST = "resident", "stacked", "host"


class _StageNote:
    """What a stage says of itself inside its ``ici.stage`` span."""

    __slots__ = ("operator", "path", "rows")

    def __init__(self, operator: str):
        self.operator = operator     # the exec's class, for program names
        self.path = HOST
        self.rows = None


@contextlib.contextmanager
def _stage(operator: Exec, op: str, chips: int):
    """One ICI stage: the span ``ici.stage:<op>`` around assembling the
    input and dispatching the step (never around a ``yield``), with the
    path taken, the input's rows where the host knows them, and the
    bytes its programs put on the wire (the static figure of each
    dispatched program, obs/compileprof); and the continuous
    decision counter (a drift toward `host` is the device-resident
    edge quietly degrading: the watchdog's signal)."""
    from ..obs import compileprof
    from ..obs import metrics as m
    note = _StageNote(type(operator).__name__)
    wire0 = compileprof.dispatched_wire_bytes()
    with trace_span("ici.stage:" + op, op=op, chips=chips) as sp:
        yield note
        attrs = {"path": note.path,
                 "wire_bytes": compileprof.dispatched_wire_bytes() - wire0}
        if note.rows is not None:
            attrs["rows"] = note.rows
        sp.set(**attrs)
    m.counter("tpu_ici_stage_total",
              "fused mesh stages by operator and data path",
              ("op", "path")).labels(op=op, path=note.path).inc()


class IciAggregateExec(Exec):
    """Fused distributed GROUP BY over the device mesh (replaces
    final ← exchange ← partial; one XLA program, rows ride ICI)."""

    placement = TPU
    #: distinct devices that held the last stacked stage input
    stage_input_devices = 0

    def __init__(self, final_agg, mesh=None):
        from .mesh import build_mesh
        exchange = final_agg.children[0]
        partial = exchange.children[0]
        source = partial.children[0]
        super().__init__([source])
        self.final_agg = final_agg
        self.partial = partial
        self.mesh = mesh or build_mesh()
        from .distributed import DistributedAggregate
        self._dagg = DistributedAggregate(
            partial.grouping, partial.aggregates,
            source.output_names, source.output_types, mesh=self.mesh)

    @property
    def output_names(self):
        return self.final_agg.output_names

    @property
    def output_types(self):
        return self.final_agg.output_types

    @property
    def num_partitions(self):
        return 1

    def describe(self):
        n = self.mesh.shape[self._dagg.axis]
        return f"IciAggregate({n} chips, all_to_all)"

    def determinism(self):
        # the fused stage realizes the host aggregate's semantics on
        # the mesh: same replay class as the operator it replaces
        return self.final_agg.determinism()

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        out, on_mesh = _run_source_stage(self, "aggregate", self._dagg, ctx)
        yield from (_emit_stacked if on_mesh else _emit_table)(self, out)


def _run_source_stage(self, op: str, dist, ctx):
    """A one-source stage (aggregate, sort) inside its span: the source
    stacked on the mesh and the step dispatched over it, or, for a schema
    the mesh edge cannot carry, staged through host Arrow.  Returns the
    output and whether it is a stacked device batch (else a table)."""
    source = self.children[0]
    with _stage(self, op, dist.n_dev) as note:
        stacked = _gather_source_stacked(
            source, ctx, source.output_names, source.output_types,
            self.mesh, note)
        if stacked is not None:
            self.stage_input_devices = _device_count(stacked)
            with MetricTimer(self.metrics[OP_TIME]):
                return dist._compiled(stacked), True
        tbl = _gather_source_table(source, ctx, source.output_names,
                                   source.output_types)
        note.rows = tbl.num_rows
        shards = _shard_table(tbl, dist.n_dev)
        with MetricTimer(self.metrics[OP_TIME]):
            return dist.run(shards), False


def _device_count(batches) -> int:
    """Distinct devices holding the device lanes of a batch, stacked or
    not, or of several."""
    import jax
    devs = set()
    for leaf in jax.tree_util.tree_leaves(batches):
        if isinstance(leaf, jax.Array):
            devs |= leaf.devices()
    return len(devs)


def _gather_source_table(source: Exec, ctx, names, dtypes) -> pa.Table:
    rbs = []
    for spid in range(source.num_partitions):
        for b in source.execute_partition(spid, ctx):
            rb = to_host_batch(b, names)
            if rb.num_rows:
                rbs.append(rb)
    schema = to_arrow_schema(names, dtypes)
    if not rbs:
        return schema.empty_table()
    return pa.Table.from_batches([rb.cast(schema) for rb in rbs],
                                 schema=schema)


def _stackable_schema(dtypes) -> bool:
    """Schemas the device-resident reshard can carry: fixed-width lanes,
    structs of them, and TOP-LEVEL strings/binaries (their offsets
    rebase per shard; arrays/maps and span-inside-struct still stage
    through host Arrow, matching exchange_supported's fallback)."""
    from .. import types as t

    def fixed(dt):
        return not isinstance(dt, (t.StringType, t.BinaryType,
                                   t.ArrayType, t.MapType, t.StructType))

    def flat(dt):
        if isinstance(dt, (t.StringType, t.BinaryType, t.ArrayType,
                           t.MapType)):
            return False
        if isinstance(dt, t.StructType):
            return all(flat(f.data_type) for f in dt.fields)
        return True

    def spannable(dt):
        if isinstance(dt, (t.StringType, t.BinaryType)):
            return True
        if isinstance(dt, t.ArrayType):
            return fixed(dt.element_type)
        if isinstance(dt, t.MapType):
            return fixed(dt.key_type) and fixed(dt.value_type)
        return False
    return all(flat(dt) or spannable(dt) for dt in dtypes)


def _stack_resident(batches, mesh):
    """The stacked stage input from shards that already lie where the
    stage wants them: batch ``i`` on mesh device ``i`` alone, every batch
    of the same lanes, shapes and types (one row capacity, one char
    capacity a string column).  Each lane gets its leading axis on its
    own chip and the global arrays are assembled from those buffers:
    nothing is concatenated, resharded or moved between chips.  None
    where the batches are not so laid out."""
    import jax
    import jax.numpy as jnp
    from ..columnar.device import DeviceBatch
    from .mesh import mesh_sharding

    devices = list(mesh.devices.flat)
    if len(batches) != len(devices):
        return None
    flat = [jax.tree_util.tree_flatten(b.columns) for b in batches]
    leaves0, treedef = flat[0]
    for (leaves, td), dev in zip(flat, devices):
        if td != treedef:
            return None
        for x, x0 in zip(leaves, leaves0):
            if not isinstance(x, jax.Array) or x.shape != x0.shape or \
                    x.dtype != x0.dtype or x.devices() != {dev}:
                return None
    sharding = mesh_sharding(mesh)
    n_dev = len(devices)

    def stack(*xs):
        return jax.make_array_from_single_device_arrays(
            (n_dev,) + xs[0].shape, sharding, [x[None] for x in xs])

    columns = jax.tree_util.tree_unflatten(
        treedef, [stack(*xs) for xs in zip(*(lv for lv, _ in flat))])
    rows = stack(*[
        jnp.asarray(b.num_rows, jnp.int32) if isinstance(b.num_rows,
                                                          jax.Array)
        else jax.device_put(np.int32(b.num_rows), dev)
        for b, dev in zip(batches, devices)])
    return DeviceBatch(columns, rows, batches[0].names)


def _host_rows(batches) -> Optional[int]:
    """Rows of the batches where every count is a host scalar (a scan's
    are); None rather than a device read."""
    import jax
    if any(isinstance(b.num_rows, jax.Array) for b in batches):
        return None
    return sum(int(b.num_rows) for b in batches)


def _gather_source_stacked(source: Exec, ctx, names, dtypes, mesh, note):
    """Device-resident scan->mesh edge: the source's DEVICE batches as
    one stacked batch, every lane ``(n_dev, shard_cap)`` with shard
    ``i`` on mesh device ``i``; rows never stage through host Arrow
    (ref RapidsShuffleInternalManagerBase.scala:74: shuffle input stays
    device-resident end-to-end).  Where the source yields one batch a
    mesh device, each already on its device and all of one shape (a
    table the scan placed for the ICI transport), the shards are taken
    where they lie (``_stack_resident``).  Every other layout (more
    partitions than chips, unequal capacities, batches on another
    device) is brought onto the mesh's first chip, concatenated there,
    resharded with ONE jitted program and handed out device-to-device.
    There string/binary lanes rebase: each shard slices its char range
    at the source's char capacity (conservative static shape; a balanced
    shard holds ~1/n of the bytes) and rewrites offsets relative to its
    slice.  ``note`` learns the path taken.  Returns the stacked
    DeviceBatch, or None for schemas the reshard cannot carry
    (span-inside-struct and deeper: the host path remains)."""
    if not _stackable_schema(dtypes):
        return None
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ..columnar.device import (DeviceBatch, DeviceColumn,
                                   batch_to_device)
    from ..exec.concat import concat_batches
    from ..exec.base import process_jit
    from .mesh import DATA_AXIS, mesh_sharding

    n_dev = mesh.shape[DATA_AXIS]
    batches = []
    for spid in range(source.num_partitions):
        for b in source.execute_partition(spid, ctx):
            batches.append(b)
    stacked = _stack_resident(batches, mesh)
    if stacked is not None:
        note.path, note.rows = RESIDENT, _host_rows(batches)
        return stacked
    note.path = STACKED
    if _device_count([b.columns for b in batches]) > 1:
        # partitions placed over the mesh that do not line up with it
        first = mesh.devices.flat[0]
        batches = [DeviceBatch(jax.device_put(b.columns, first),
                               int(b.num_rows), b.names) for b in batches]
    batches = [b for b in batches if int(b.num_rows)]
    if not batches:
        schema = to_arrow_schema(names, dtypes)
        rb = pa.RecordBatch.from_pydict(
            {f.name: pa.array([], type=f.type) for f in schema},
            schema=schema)
        batches = [batch_to_device(rb)]
    merged = concat_batches(jnp, batches, names, dtypes) \
        if len(batches) > 1 else batches[0]
    total = note.rows = int(merged.num_rows)
    # per-shard row budget rounds up to a power of two so distinct totals
    # share compiled reshard programs (static-shape discipline) while
    # shard imbalance stays bounded by 2x (the sparse row-bucket ladder
    # could idle most of the mesh)
    import math
    need_rows = max(1024, -(-total // n_dev))
    per = 1 << math.ceil(math.log2(need_rows))
    in_cap = merged.capacity
    char_caps = tuple(
        int((c.data if c.data is not None
             else c.children[0].data).shape[0])
        if c.offsets is not None else 0
        for c in merged.columns)

    def make():
        def reshard(b: DeviceBatch):
            need = n_dev * per

            def pad_to(x, size):
                if x.shape[0] >= size:
                    return x[:size]
                return jnp.pad(x, (0, size - x.shape[0]))

            cols = []
            for c, ccap in zip(b.columns, char_caps):
                if c.offsets is not None:
                    # offsets edge-extend so padding rows are empty spans
                    offs = c.offsets
                    if offs.shape[0] < need + 1:
                        offs = jnp.concatenate(
                            [offs, jnp.full((need + 1 - offs.shape[0],),
                                            offs[-1], offs.dtype)])
                    else:
                        offs = offs[:need + 1]
                    # every child-aligned lane (chars for strings,
                    # element lanes for arrays/maps) slices per shard at
                    # the source's child capacity; padding ensures the
                    # dynamic slice never clamps
                    if c.children:
                        from .alltoall import _flat_child_lanes
                        lanes, rebuild = _flat_child_lanes(c)
                    else:
                        lanes, rebuild = [c.data], None
                    padded = [jnp.concatenate(
                        [ln, jnp.zeros((ccap,), ln.dtype)])
                        for ln in lanes]
                    sh_off = []
                    sh_lanes = [[] for _ in lanes]
                    for i in range(n_dev):
                        o = offs[i * per:i * per + per + 1]
                        sh_off.append(o - o[0])
                        for li, ln in enumerate(padded):
                            sh_lanes[li].append(lax.dynamic_slice(
                                ln, (o[0],), (ccap,)))
                    validity = None if c.validity is None else \
                        pad_to(c.validity, need).reshape(n_dev, per)
                    stacked_lanes = [jnp.stack(g) for g in sh_lanes]
                    if rebuild is None:
                        cols.append(DeviceColumn(
                            c.dtype, data=stacked_lanes[0],
                            validity=validity,
                            offsets=jnp.stack(sh_off)))
                    else:
                        cols.append(rebuild(stacked_lanes,
                                            jnp.stack(sh_off), validity))
                else:
                    cols.append(jax.tree_util.tree_map(
                        lambda x: pad_to(x, need).reshape(n_dev, per), c))
            rows = jnp.clip(
                jnp.asarray(b.num_rows, jnp.int32)
                - jnp.arange(n_dev, dtype=jnp.int32) * np.int32(per),
                0, np.int32(per))
            return DeviceBatch(cols, rows, b.names)
        # one program for whichever stage asks first; it carries that
        # operator's name into the trace (no key holds it)
        reshard.program_name = note.operator + ".reshard"
        return reshard
    fn = process_jit(("ici_reshard", tuple(names),
                      tuple(repr(d) for d in dtypes), in_cap, n_dev, per,
                      char_caps),
                     make)
    return jax.device_put(fn(merged), mesh_sharding(mesh))


#: shards whose rows together fit this many are handed on as one batch:
#: ``CoalesceBatchesExec``'s own default goal (exec/basic.py)
COALESCE_GOAL_ROWS = 1 << 22


def _emit_stacked(self, stacked) -> Iterator[Batch]:
    """Yield the stage's output as device batches on the mesh's first
    chip, where the single-device operators downstream run, in mesh
    order and without host staging.  A shard of the output has the
    exchange's capacity (``n_parts`` x the input's) and its live rows in
    front, and the host reads the shards' row counts here in any case:
    so each shard is cut to the row bucket that holds its rows before it
    moves, and shards that together fit ``CoalesceBatchesExec``'s goal
    are concatenated into one batch, as that operator would if the
    counts were still on the host when they reach it.  The operators
    above then work once at the size of the rows, not ``n_dev`` times at
    the exchange's capacity, and one answer is one fetch (a fetch a chip
    of a few rows each picks its transfer plan, a program, by each
    chip's own value range)."""
    import jax
    import jax.numpy as jnp
    from ..columnar.device import (DEFAULT_ROW_BUCKETS, bucket_for,
                                   shrink_batch)
    from ..exec.concat import concat_batches
    from .distributed import unstack_shards
    first = self.mesh.devices.flat[0]
    rows = np.asarray(stacked.num_rows)       # one read for every shard
    shards = []
    for b, n in zip(unstack_shards(stacked), rows):
        n = int(n)
        if n == 0:
            continue
        b = shrink_batch(b, bucket_for(n, DEFAULT_ROW_BUCKETS))
        shards.append(Batch(jax.device_put(b.columns, first), n, b.names))
    if len(shards) > 1 and \
            sum(int(b.num_rows) for b in shards) <= COALESCE_GOAL_ROWS:
        shards = [concat_batches(jnp, shards, self.output_names,
                                 self.output_types)]
    for out in shards:
        self.metrics[NUM_OUTPUT_ROWS] += int(out.num_rows)
        self.metrics[NUM_OUTPUT_BATCHES] += 1
        yield out


def _shard_table(tbl: pa.Table, n_dev: int):
    per = max(1, -(-tbl.num_rows // n_dev))
    return [tbl.slice(i * per, per) for i in range(n_dev)]


def _emit_table(self, tbl: pa.Table) -> Iterator[Batch]:
    from ..columnar.device import batch_to_device
    for rb in tbl.combine_chunks().to_batches():
        if rb.num_rows == 0:
            continue
        batch = batch_to_device(rb, xp=self.xp)
        self.metrics[NUM_OUTPUT_ROWS] += rb.num_rows
        self.metrics[NUM_OUTPUT_BATCHES] += 1
        yield batch


class IciSortExec(Exec):
    """Distributed total-order sort over the mesh (replaces
    sort ← range-exchange; splitter sampling + all_to_all routing +
    local sort compile into ONE SPMD program, ref GpuRangePartitioner +
    GpuSortExec)."""

    placement = TPU
    #: distinct devices that held the last stacked stage input
    stage_input_devices = 0

    def __init__(self, sort_exec, mesh=None):
        from .mesh import build_mesh
        exchange = sort_exec.children[0]
        source = exchange.children[0]
        super().__init__([source])
        self.sort_exec = sort_exec
        self.mesh = mesh or build_mesh()
        from .distributed import DistributedSort
        self._dsort = DistributedSort(sort_exec.orders,
                                      source.output_names,
                                      source.output_types, mesh=self.mesh)

    output_names = property(lambda self: self.sort_exec.output_names)
    output_types = property(lambda self: self.sort_exec.output_types)
    num_partitions = property(lambda self: 1)

    def describe(self):
        n = self.mesh.shape[self._dsort.axis]
        return f"IciSort({n} chips, sample+all_to_all)"

    def determinism(self):
        return self.sort_exec.determinism()

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        out, on_mesh = _run_source_stage(self, "sort", self._dsort, ctx)
        # shard i holds globally-ordered range i: emitted in mesh order
        yield from (_emit_stacked if on_mesh else _emit_table)(self, out)


class IciJoinExec(Exec):
    """Shuffled hash join over the mesh (replaces
    join ← {hash-exchange, hash-exchange}; both sides ride all_to_all
    inside the compiled stage, ref GpuShuffledHashJoinBase +
    UCXShuffleTransport)."""

    placement = TPU
    #: distinct devices that held the last stacked stage input
    stage_input_devices = 0

    def __init__(self, join_exec, mesh=None):
        from .mesh import build_mesh
        lex, rex = join_exec.children
        lsrc, rsrc = lex.children[0], rex.children[0]
        super().__init__([lsrc, rsrc])
        self.join_exec = join_exec
        self.mesh = mesh or build_mesh()
        from .distributed import DistributedHashJoin
        self._djoin = DistributedHashJoin(
            [k for k in join_exec.left_keys],
            [k for k in join_exec.right_keys],
            join_exec.how, join_exec.condition,
            lsrc.output_names, lsrc.output_types,
            rsrc.output_names, rsrc.output_types, mesh=self.mesh)

    output_names = property(lambda self: self.join_exec.output_names)
    output_types = property(lambda self: self.join_exec.output_types)
    num_partitions = property(lambda self: 1)

    def describe(self):
        n = self.mesh.shape[self._djoin.axis]
        return f"IciJoin({self.join_exec.how}, {n} chips, all_to_all)"

    def determinism(self):
        return self.join_exec.determinism()

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        lsrc, rsrc = self.children
        n_dev = self._djoin.n_dev
        with _stage(self, "join", n_dev) as note:
            # device-resident edge first: both sides reach the mesh on
            # device and the join consumes the stacked shards without
            # host staging
            lnote, rnote = (_StageNote(note.operator),
                            _StageNote(note.operator))
            ls = _gather_source_stacked(lsrc, ctx, lsrc.output_names,
                                        lsrc.output_types, self.mesh, lnote)
            rs = _gather_source_stacked(rsrc, ctx, rsrc.output_names,
                                        rsrc.output_types, self.mesh,
                                        rnote) \
                if ls is not None else None
            if ls is not None and rs is not None:
                # resident only where neither side was resharded
                note.path = RESIDENT if lnote.path == rnote.path == RESIDENT \
                    else STACKED
                if lnote.rows is not None and rnote.rows is not None:
                    note.rows = lnote.rows + rnote.rows
                self.stage_input_devices = min(_device_count(ls),
                                               _device_count(rs))
                with MetricTimer(self.metrics[OP_TIME]):
                    out = self._djoin.run_stacked(ls, rs)
            else:
                lt = _gather_source_table(lsrc, ctx, lsrc.output_names,
                                          lsrc.output_types)
                rt = _gather_source_table(rsrc, ctx, rsrc.output_names,
                                          rsrc.output_types)
                note.rows = lt.num_rows + rt.num_rows
                with MetricTimer(self.metrics[OP_TIME]):
                    out = self._djoin.run(_shard_table(lt, n_dev),
                                          _shard_table(rt, n_dev))
        yield from _emit_table(self, out)


class IciExchangeExec(Exec):
    """A bare hash repartition routed over the mesh (replaces a
    ShuffleExchangeExec that no fused stage absorbed; the all_to_all
    analog of the reference transport serving EVERY shuffle,
    UCXShuffleTransport.scala).  Downstream operators read one shard per
    partition id."""

    placement = TPU

    def __init__(self, exchange, mesh=None):
        import threading
        from .mesh import build_mesh
        source = exchange.children[0]
        super().__init__([source])
        self.exchange = exchange
        self.mesh = mesh or build_mesh()
        from .distributed import DATA_AXIS as _axis
        if exchange.partitioning.num_partitions != \
                self.mesh.shape[_axis]:
            # pmod(mesh) would change the key->partition mapping the
            # user asked for (e.g. partitioned writes rely on it)
            raise NotImplementedError(
                f"repartition({exchange.partitioning.num_partitions}) "
                f"!= mesh size {self.mesh.shape[_axis]}: host exchange")
        from .distributed import DistributedExchange
        self._dex = DistributedExchange(
            list(exchange.partitioning.keys), source.output_names,
            source.output_types, mesh=self.mesh)
        self._memo = {}
        self._memo_lock = threading.Lock()

    def release_shuffle(self):
        """Drop the memoized shuffled dataset (the HBM analog of
        unregistering shuffle blocks; called by release_plan_shuffles)."""
        with self._memo_lock:
            self._memo.clear()

    output_names = property(lambda self: self.exchange.output_names)
    output_types = property(lambda self: self.exchange.output_types)
    num_partitions = property(
        lambda self: self.mesh.shape[self._dex.axis])

    def describe(self):
        return f"IciExchange({self.num_partitions} chips, all_to_all)"

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "all_to_all routing is content-determined; "
            "per-chip row multiset is invariant under arrival order")

    def memory_effects(self, child_states, conf):
        """Memoizes the whole shuffled dataset device-resident (raw, not
        spill-managed) until release_shuffle at query end — plus the
        all_to_all's send/recv staging while it runs."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes)
        if not child_states:
            return None
        st = child_states[0]
        shards = max(self.num_partitions, 1)
        whole = padded_partition_bytes(
            st.replace(num_partitions=shards)) * shards
        return MemoryEffects(hold=2.0 * whole, retained=whole,
                             note="device shuffle memo")

    def _shards(self, ctx):
        key = ctx.uid
        with self._memo_lock:
            hit = self._memo.get(key)
            if hit is not None:
                return hit
            source = self.children[0]
            with _stage(self, "exchange", self._dex.n_dev) as note:
                stacked = _gather_source_stacked(
                    source, ctx, source.output_names, source.output_types,
                    self.mesh, note)
                with MetricTimer(self.metrics[OP_TIME]):
                    if stacked is not None:
                        out = self._dex.run_stacked(stacked)
                        from .distributed import unstack_shards
                        shards = unstack_shards(
                            out, device=self.mesh.devices.flat[0])
                    else:
                        tbl = _gather_source_table(source, ctx,
                                                   source.output_names,
                                                   source.output_types)
                        note.rows = tbl.num_rows
                        tables = self._dex.run(
                            _shard_table(tbl, self._dex.n_dev))
                        from ..columnar.device import batch_to_device
                        shards = []
                        for tb in tables:
                            rbs = tb.combine_chunks().to_batches()
                            shards.append(
                                batch_to_device(rbs[0], xp=self.xp) if rbs
                                else None)
            self._memo[key] = shards
            return shards

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        shard = self._shards(ctx)[pid]
        if shard is None:
            return
        n = int(np.asarray(shard.num_rows))
        if n == 0:
            return
        out = Batch(shard.columns, n, shard.names)
        self.metrics[NUM_OUTPUT_ROWS] += n
        self.metrics[NUM_OUTPUT_BATCHES] += 1
        yield out


def install_ici_stages(root: Exec, conf: cfg.RapidsConf) -> Exec:
    """Post-conversion rewrite: shuffle-bracketed stages become fused SPMD
    mesh stages when the ICI transport is selected and a multi-chip mesh
    exists — aggregate triples (IciAggregateExec), range-partitioned
    global sorts (IciSortExec), and co-partitioned hash joins
    (IciJoinExec).  The reference swaps its transport underneath every
    shuffle (UCXShuffleTransport serves aggregates, joins and sorts
    alike); this pass is the plan-level equivalent."""
    if conf.get(cfg.SHUFFLE_TRANSPORT) != "ici":
        return root
    # the ICI transport was asked for: a failed device discovery
    # raises here rather than quietly planning the single-chip path
    from .mesh import device_count
    if device_count() < 2:
        return root
    from ..exec.aggregate import TpuHashAggregateExec
    from ..exec.join import HashJoinExec
    from ..exec.sort import SortExec
    from ..expr.aggregates import FINAL, PARTIAL
    from ..shuffle.exchange import ShuffleExchangeExec
    from ..shuffle.partitioning import HashPartitioning, RangePartitioning
    from .alltoall import exchange_supported

    def rewrite(node: Exec) -> Exec:
        node = node.with_new_children([rewrite(c) for c in node.children])
        # --- final <- hash-exchange <- partial aggregate ----------------
        if isinstance(node, TpuHashAggregateExec) and \
                node.mode == FINAL and node.grouping:
            ex = node.children[0]
            if isinstance(ex, ShuffleExchangeExec) and \
                    isinstance(ex.partitioning, HashPartitioning):
                part = ex.children[0]
                if isinstance(part, TpuHashAggregateExec) and \
                        part.mode == PARTIAL and part.placement == TPU:
                    source = part.children[0]
                    if not (exchange_supported(part.output_types) or
                            exchange_supported(source.output_types)):
                        try:
                            return IciAggregateExec(node)
                        except NotImplementedError:
                            pass
            return node
        # --- global sort <- range exchange ------------------------------
        if isinstance(node, SortExec) and node.is_global and \
                node.placement == TPU:
            ex = node.children[0]
            if isinstance(ex, ShuffleExchangeExec) and \
                    isinstance(ex.partitioning, RangePartitioning) and \
                    not exchange_supported(ex.output_types):
                try:
                    return IciSortExec(node)
                except NotImplementedError:
                    pass
            return node
        # --- colocated hash join <- two hash exchanges ------------------
        if isinstance(node, HashJoinExec) and node.colocated and \
                node.placement == TPU:
            lex, rex = node.children
            if all(isinstance(e, ShuffleExchangeExec) and
                   isinstance(e.partitioning, HashPartitioning)
                   for e in (lex, rex)) and \
                    not (exchange_supported(lex.output_types) or
                         exchange_supported(rex.output_types)):
                try:
                    return IciJoinExec(node)
                except NotImplementedError:
                    pass
            return node
        return node

    def wrap_exchanges(node: Exec) -> Exec:
        # second pass: any hash exchange the fused stages did not absorb
        # still rides ICI as a bare all_to_all repartition — the
        # transport serves EVERY shuffle, like the reference's
        # UCXShuffleTransport regardless of the operator above it
        node = node.with_new_children(
            [wrap_exchanges(c) for c in node.children])
        if isinstance(node, ShuffleExchangeExec) and \
                isinstance(node.partitioning, HashPartitioning) and \
                getattr(node.partitioning, "keys", None) and \
                not exchange_supported(node.output_types):
            try:
                return IciExchangeExec(node)
            except NotImplementedError:
                pass
        return node

    root = wrap_exchanges(rewrite(root))
    root.foreach(_keep_sources_on_mesh)
    return root


def _keep_sources_on_mesh(node: Exec) -> None:
    """An in-memory table that a mesh stage reads through partition-local
    operators alone is kept where the stage wants it: partition ``i`` on
    mesh device ``i % n_dev`` (``LocalScanExec.mesh_resident``), so the
    stage takes its shards where they lie.  A scan that anything else
    reads (an operator that brings partitions together on one device: a
    host exchange, a broadcast, a union) stays on the default device, as
    on one chip."""
    if not isinstance(node, (IciAggregateExec, IciSortExec, IciJoinExec,
                             IciExchangeExec)):
        return
    from ..exec.basic import (CoalesceBatchesExec, FilterExec,
                              LocalScanExec, ProjectExec)
    for source in node.children:
        while isinstance(source, (ProjectExec, FilterExec,
                                  CoalesceBatchesExec)):
            source = source.children[0]
        if isinstance(source, LocalScanExec) and source.placement == TPU:
            source.mesh_resident = True
