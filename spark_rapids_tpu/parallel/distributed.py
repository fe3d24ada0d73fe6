"""SPMD distributed query steps over a device mesh.

The multi-chip execution mode: instead of the host-orchestrated
partition-iterator shuffle (shuffle/manager.py — the analog of the
reference's always-available Spark-shuffle path), a whole query stage
compiles into ONE `shard_map`-ped XLA program per schema: every device
runs the identical operator pipeline on its shard and rows move over ICI
with `all_to_all` (parallel/alltoall.py).  This is the structural
equivalent of the reference's accelerated UCX shuffle stage
(ref: RapidsShuffleInternalManagerBase.scala:74 caching writer keeping
batches on-device; shuffle-plugin/.../UCXShuffleTransport.scala), with
the XLA compiler playing the role of the transport state machines.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import pyarrow as pa

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import types as t
from ..columnar.device import (DEFAULT_ROW_BUCKETS, DeviceBatch,
                               batch_to_arrow, batch_to_device, bucket_for)
from ..expr.core import EvalContext
from ..shuffle.partitioning import HashPartitioning
from .alltoall import (allgather_batch, allgather_supported,
                       exchange_by_pid, exchange_supported,
                       wire_all_gather)
from .mesh import DATA_AXIS, build_mesh


class _SchemaSource:
    """Placeholder child carrying only an output schema, so exec nodes can
    be built against shard inputs that exist only inside shard_map."""

    num_partitions = 1

    def __init__(self, names: Sequence[str], dtypes: Sequence[t.DataType]):
        self.output_names = list(names)
        self.output_types = list(dtypes)
        self.children = []

    def execute_partition(self, pid, ctx):  # pragma: no cover
        raise RuntimeError("schema-only node is never executed")


def stack_shards(tables: Sequence[pa.Table], capacity: Optional[int] = None,
                 mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
    """Upload one Arrow table per mesh device and assemble the shards
    into arrays row-sharded on a leading device axis: shard ``i`` is
    uploaded to, and stays on, device ``i`` of ``mesh`` (default: the
    first ``len(tables)`` devices)."""
    mesh = mesh or build_mesh(len(tables))
    devices = list(mesh.devices.flat)
    if len(devices) != len(tables):
        raise ValueError(
            f"{len(tables)} shards for a {len(devices)}-device mesh")
    n_rows = max(max((tb.num_rows for tb in tables), default=1), 1)
    cap = capacity or bucket_for(n_rows, DEFAULT_ROW_BUCKETS)
    batches = []
    for tb, dev in zip(tables, devices):
        rbs = tb.combine_chunks().to_batches()
        rb = rbs[0] if rbs else pa.RecordBatch.from_pydict(
            {f.name: pa.array([], type=f.type) for f in tb.schema},
            schema=tb.schema)
        with jax.default_device(dev):
            # committed, so the padding below stays on the shard's device
            batches.append(jax.device_put(
                batch_to_device(rb, capacity=cap), dev))
    # equalize char capacities across shards so stacking is legal
    batches = _equalize_char_caps(batches)
    sharding = NamedSharding(mesh, P(axis))

    def stack(*xs):
        parts = [x[None] for x in xs]
        return jax.make_array_from_single_device_arrays(
            (len(parts),) + parts[0].shape[1:], sharding, parts)
    return jax.tree_util.tree_map(stack, *batches)


def _equalize_char_caps(batches: List[DeviceBatch]) -> List[DeviceBatch]:
    """Pad every shard's span child lanes (string chars, array/map
    element lanes) to the max across shards so stacking is legal."""
    from ..columnar.device import DeviceColumn
    if not batches:
        return batches

    def pad_lane(x, cap):
        cur = int(x.shape[0])
        if cur >= cap:
            return x
        return jnp.concatenate([x, jnp.zeros((cap - cur,), x.dtype)])

    def equalize(cols: List[DeviceColumn]) -> List[DeviceColumn]:
        dt = cols[0].dtype
        if isinstance(dt, (t.StringType, t.BinaryType)):
            cap = max(int(c.data.shape[0]) for c in cols)
            return [DeviceColumn(c.dtype, data=pad_lane(c.data, cap),
                                 validity=c.validity, offsets=c.offsets)
                    for c in cols]
        if isinstance(dt, (t.ArrayType, t.MapType)):
            child_cols = [equalize([c.children[i] for c in cols])
                          for i in range(len(cols[0].children))]
            caps = [max(int(lane.shape[0])
                        for lane in (ch.data for ch in group))
                    for group in child_cols]
            padded = []
            for group, cap in zip(child_cols, caps):
                padded.append([
                    DeviceColumn(ch.dtype, data=pad_lane(ch.data, cap),
                                 validity=None if ch.validity is None
                                 else pad_lane(ch.validity, cap),
                                 offsets=ch.offsets,
                                 data_hi=None if ch.data_hi is None
                                 else pad_lane(ch.data_hi, cap),
                                 children=ch.children)
                    for ch in group])
            return [DeviceColumn(c.dtype, validity=c.validity,
                                 offsets=c.offsets,
                                 children=tuple(padded[i][bi]
                                                for i in range(len(padded))))
                    for bi, c in enumerate(cols)]
        if isinstance(dt, t.StructType):
            child_cols = [equalize([c.children[i] for c in cols])
                          for i in range(len(cols[0].children))]
            return [DeviceColumn(c.dtype, validity=c.validity,
                                 children=tuple(child_cols[i][bi]
                                                for i in range(len(child_cols))))
                    for bi, c in enumerate(cols)]
        return list(cols)

    ncol = batches[0].num_cols
    per_col = [equalize([b.columns[ci] for b in batches])
               for ci in range(ncol)]
    return [DeviceBatch([per_col[ci][bi] for ci in range(ncol)],
                        b.num_rows, b.names)
            for bi, b in enumerate(batches)]


def _split_device_axis(x) -> list:
    """Slices of ``x`` along its leading device axis.  A row-sharded
    array hands out each device's own buffer; indexing it instead would
    run one SPMD gather (with its collectives) per shard."""
    n = int(x.shape[0])
    shards = x.addressable_shards
    if len(shards) == n and all(s.data.shape[0] == 1 for s in shards):
        by_row = sorted(shards, key=lambda s: s.index[0].start or 0)
        return [s.data[0] for s in by_row]
    return [x[i] for i in range(n)]


def unstack_shards(stacked: DeviceBatch, device=None) -> List[DeviceBatch]:
    """Per-device batches of a stacked batch, each left on the device
    that holds it, or moved to ``device`` (for single-device operators
    downstream of a mesh stage)."""
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    per_leaf = [_split_device_axis(x) for x in leaves]
    out = [jax.tree_util.tree_unflatten(treedef, [pl[i] for pl in per_leaf])
           for i in range(len(per_leaf[0]))]
    if device is not None:
        out = [jax.device_put(b, device) for b in out]
    return out


def shards_to_table(stacked: DeviceBatch) -> pa.Table:
    tables = [pa.Table.from_batches([batch_to_arrow(b)])
              for b in unstack_shards(stacked)]
    return pa.concat_tables(tables)


class DistributedAggregate:
    """Distributed GROUP BY: local partial agg -> ICI all_to_all on key
    hash -> local final agg.  Compiles to one XLA program; every stage
    stays on device (the reference's partial/exchange/final pipeline,
    aggregate.scala:258-275 + GpuShuffleExchangeExec, fused end-to-end)."""

    def __init__(self, grouping, aggregates, in_names, in_types,
                 mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
        from ..exec.aggregate import TpuHashAggregateExec
        from ..expr.aggregates import FINAL, PARTIAL
        self.mesh = mesh or build_mesh()
        self.axis = axis
        self.n_dev = self.mesh.shape[axis]
        src = _SchemaSource(in_names, in_types)
        self.partial = TpuHashAggregateExec(list(grouping), list(aggregates),
                                            PARTIAL, src)
        self.final = TpuHashAggregateExec(list(grouping),
                                          self.partial.aggregates, FINAL,
                                          self.partial)
        reason = exchange_supported(self.partial.output_types)
        if reason is None and not self.partial.grouping:
            # the ungrouped path replicates partial buffers through
            # allgather_batch, whose dtype coverage is STRICTLY NARROWER
            # than the exchange kernel's (no array/map span layout) — a
            # global collect_list/collect_set must fail HERE, at
            # planning/construction time, so callers fall back to the
            # host path instead of crashing mid-query (ADVICE round 5,
            # analysis/capabilities.py verify_gates)
            reason = allgather_supported(self.partial.output_types)
        if reason:
            raise NotImplementedError(reason)
        k = len(list(grouping))
        # route on the SAME Spark-compatible murmur3+pmod rule the host
        # shuffle uses (shuffle/partitioning.py), so both paths agree on
        # key placement
        self._routing = HashPartitioning(
            [_attr(n, dt) for n, dt in zip(self.partial.output_names[:k],
                                           self.partial.output_types[:k])],
            self.n_dev).bind(self.partial.output_names,
                             self.partial.output_types)

    @property
    def output_names(self):
        return self.final.output_names

    @property
    def output_types(self):
        return self.final.output_types

    def _step(self, shard: DeviceBatch) -> DeviceBatch:
        # leading device axis arrives stripped of sharding but kept as a
        # size-1 axis; drop it
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        part = self.partial._update_batch(jnp, b)
        if self.partial.grouping:
            ctx = EvalContext(jnp, part)
            pids = self._routing.partition_ids(jnp, ctx, part)
            routed = exchange_by_pid(part, pids, self.n_dev, self.axis)
        else:
            # global aggregate: replicate partials, every device computes
            # the same final row (cheap; buffers are one row each)
            routed = allgather_batch(part, self.axis, self.n_dev)
        merged = self.final._merge_batch(jnp, routed)
        out = self.final._evaluate_batch(jnp, merged)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    @functools.cached_property
    def _jit_key(self):
        from ..exec.base import semantic_sig
        return ("DistributedAggregate", self.axis,
                tuple(d.id for d in self.mesh.devices.flat),
                self.partial._jit_key, self.final._jit_key,
                semantic_sig(self._routing))

    @property
    def _compiled(self):
        from ..exec.base import process_jit

        def make():
            return shard_map(self._step, mesh=self.mesh,
                             in_specs=P(self.axis), out_specs=P(self.axis),
                             check_vma=False)
        return process_jit(self._jit_key, make)

    def run(self, tables: Sequence[pa.Table]) -> pa.Table:
        """tables: one scan shard per device."""
        assert len(tables) == self.n_dev, \
            f"need {self.n_dev} shards, got {len(tables)}"
        stacked = stack_shards(tables, mesh=self.mesh, axis=self.axis)
        out = self._compiled(stacked)
        result = shards_to_table(out)
        if not self.partial.grouping and result.num_rows:
            # every device produced the same global row; keep one
            result = result.slice(0, 1)
        return result


class DistributedExchange:
    """A bare distributed repartition: rows move to `hash(keys) % n_dev`
    (the building block joins/sorts stage on; analog of
    GpuShuffleExchangeExec.doExecuteColumnar, execution/
    GpuShuffleExchangeExec.scala:223)."""

    def __init__(self, keys, in_names, in_types,
                 mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
        self.mesh = mesh or build_mesh()
        self.axis = axis
        self.n_dev = self.mesh.shape[axis]
        reason = exchange_supported(in_types)
        if reason:
            raise NotImplementedError(reason)
        self.in_names, self.in_types = list(in_names), list(in_types)
        self._routing = HashPartitioning(list(keys), self.n_dev).bind(
            self.in_names, self.in_types)

    def _step(self, shard):
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        ctx = EvalContext(jnp, b)
        pids = self._routing.partition_ids(jnp, ctx, b)
        out = exchange_by_pid(b, pids, self.n_dev, self.axis)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    @functools.cached_property
    def _jit_key(self):
        from ..exec.base import semantic_sig
        return ("DistributedExchange", self.axis,
                tuple(d.id for d in self.mesh.devices.flat),
                tuple(zip(self.in_names, map(repr, self.in_types))),
                semantic_sig(self._routing))

    @property
    def _compiled(self):
        from ..exec.base import process_jit

        def make():
            return shard_map(self._step, mesh=self.mesh,
                             in_specs=P(self.axis), out_specs=P(self.axis),
                             check_vma=False)
        return process_jit(self._jit_key, make)

    def run_stacked(self, stacked: DeviceBatch) -> DeviceBatch:
        return self._compiled(stacked)

    def run(self, tables: Sequence[pa.Table]) -> List[pa.Table]:
        assert len(tables) == self.n_dev
        out = self.run_stacked(
            stack_shards(tables, mesh=self.mesh, axis=self.axis))
        return [pa.Table.from_batches([batch_to_arrow(b)])
                for b in unstack_shards(out)]


class DistributedSort:
    """Distributed total-order sort in ONE SPMD program: per-shard splitter
    sampling -> all_gather of candidates -> route rows to their key range
    with all_to_all -> local multi-key sort.  Device ``i`` ends up holding
    globally-ordered range ``i`` (read shards in mesh order for the total
    order) — the ICI realization of the reference's range-partition +
    per-partition sort pipeline (ref GpuRangePartitioner.scala +
    GpuSortExec.scala), with the sample/boundary handshake that Spark does
    on the driver folded into the compiled program as collectives."""

    def __init__(self, orders, in_names, in_types,
                 mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
        from ..exec.sort import SortExec
        self.mesh = mesh or build_mesh()
        self.axis = axis
        self.n_dev = self.mesh.shape[axis]
        reason = exchange_supported(in_types)
        if reason:
            raise NotImplementedError(reason)
        self.in_names, self.in_types = list(in_names), list(in_types)
        src = _SchemaSource(in_names, in_types)
        self._sorter = SortExec(list(orders), src)

    output_names = property(lambda self: self.in_names)
    output_types = property(lambda self: self.in_types)

    def _first_key_word(self, b: DeviceBatch):
        """Order-consistent uint64 routing word of the FIRST sort key:
        the first VALUE word with null rows forced to the extreme their
        nulls_first placement demands.  Ties may span further key words,
        but equal routing words land on the same shard, so the local
        multi-key sort finishes the order."""
        from ..ops import segmented as seg
        ctx = EvalContext(jnp, b)
        live = ctx.row_mask()
        e, asc, nf = self._sorter._bound[0]
        v = e.eval(ctx)
        from ..expr.core import ColumnValue, make_column
        if not isinstance(v, ColumnValue):
            v = make_column(ctx, e.data_type(),
                            v.value if v.value is not None else 0,
                            None if v.value is not None else False)
        words = seg.key_words_for_column(jnp, v.col, live,
                                         for_grouping=False,
                                         nulls_first=nf, ascending=asc)
        # words[0] is the null indicator — routing on it would ship every
        # non-null row to one device.  Route on the value word instead,
        # with nulls pinned to the boundary shard their placement wants.
        valid = v.col.validity if v.col.validity is not None else \
            jnp.ones((b.capacity,), bool)
        null_route = jnp.uint64(0) if nf else \
            jnp.uint64(0xFFFFFFFFFFFFFFFF)
        value_w = words[1] if len(words) > 1 else \
            jnp.zeros((b.capacity,), jnp.uint64)
        return jnp.where(valid, value_w, null_route), live

    def _step(self, shard):
        n_dev = self.n_dev
        b = jax.tree_util.tree_map(lambda x: x[0], shard)
        w0, live = self._first_key_word(b)
        cap = b.capacity
        maxw = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        from ..ops import carry
        parked = jnp.where(live, w0, maxw)
        sorted_w0 = carry.sort_lanes(jnp, [parked], [parked], cap,
                                     need_order=False)[1][0]
        n_live = jnp.sum(live.astype(jnp.int32))
        # local splitter candidates at the n_dev-quantiles
        q = (jnp.arange(1, n_dev, dtype=jnp.int32) * n_live) // n_dev
        cand = sorted_w0[jnp.clip(q, 0, cap - 1)]
        # every shard contributes candidates; global splitters are the
        # n_dev-quantiles of the gathered candidate set
        all_cand = wire_all_gather(cand, self.axis,
                                   n_dev)                  # [(n_dev-1)*n_dev]
        all_sorted = jnp.sort(all_cand)
        m = all_cand.shape[0]
        pick = (jnp.arange(1, n_dev, dtype=jnp.int32) * m) // n_dev
        splitters = all_sorted[jnp.clip(pick, 0, m - 1)]   # [n_dev-1]
        pid = jnp.searchsorted(splitters, w0, side="right").astype(jnp.int32)
        routed = exchange_by_pid(b, pid, n_dev, self.axis)
        out = self._sorter._sort_batch(jnp, routed)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    @functools.cached_property
    def _jit_key(self):
        from ..exec.base import semantic_sig
        return ("DistributedSort", self.axis,
                tuple(d.id for d in self.mesh.devices.flat),
                tuple(zip(self.in_names, map(repr, self.in_types))),
                semantic_sig(self._sorter._bound))

    @property
    def _compiled(self):
        from ..exec.base import process_jit

        def make():
            return shard_map(self._step, mesh=self.mesh,
                             in_specs=P(self.axis), out_specs=P(self.axis),
                             check_vma=False)
        return process_jit(self._jit_key, make)

    def run(self, tables: Sequence[pa.Table]) -> pa.Table:
        """tables: one shard per device; returns the totally-ordered
        concatenation (shard 0's range first)."""
        assert len(tables) == self.n_dev
        out = self._compiled(
            stack_shards(tables, mesh=self.mesh, axis=self.axis))
        return shards_to_table(out)


class DistributedHashJoin:
    """Shuffled hash join over the mesh: both sides are exchanged to
    ``hash(keys) % n_dev`` inside one SPMD count program (so matching keys
    co-locate, ref GpuShuffledHashJoinBase.scala), ONE host round trip
    reads the per-shard output sizes, then a second SPMD program gathers
    the join output at the bucketed static capacity — the multi-chip
    mirror of HashJoinExec's count/sync/expand pipeline."""

    SUPPORTED = ("inner", "left", "full", "left_semi", "left_anti")

    def __init__(self, left_keys, right_keys, how: str, condition,
                 lnames, ltypes, rnames, rtypes,
                 mesh: Optional[Mesh] = None, axis: str = DATA_AXIS):
        from ..exec.join import HashJoinExec
        if how not in self.SUPPORTED:
            # right joins arrive pre-flipped to left (plan_join)
            raise NotImplementedError(f"ici join how={how}")
        if condition is not None and how not in ("inner", "left"):
            # inner post-filters in-shard; left runs the conditional
            # expand+repair kernel — co-located keys make both locally
            # exact (ref GpuOverrides.scala:3352-3355)
            raise NotImplementedError("ici join residual condition only "
                                      "for inner/left joins")
        self.mesh = mesh or build_mesh()
        self.axis = axis
        self.n_dev = self.mesh.shape[axis]
        for tys in (ltypes, rtypes):
            reason = exchange_supported(tys)
            if reason:
                raise NotImplementedError(reason)
        self.how = how
        lsrc = _SchemaSource(lnames, ltypes)
        rsrc = _SchemaSource(rnames, rtypes)
        self._join = HashJoinExec(list(left_keys), list(right_keys), how,
                                  condition, lsrc, rsrc, colocated=True)
        self._l_routing = HashPartitioning(
            list(left_keys), self.n_dev).bind(lnames, ltypes)
        self._r_routing = HashPartitioning(
            list(right_keys), self.n_dev).bind(rnames, rtypes)

    output_names = property(lambda self: self._join.output_names)
    output_types = property(lambda self: self._join.output_types)

    def _exchange_side(self, b: DeviceBatch, routing) -> DeviceBatch:
        ctx = EvalContext(jnp, b)
        pids = routing.partition_ids(jnp, ctx, b)
        return exchange_by_pid(b, pids, self.n_dev, self.axis)

    def _count_step(self, lshard, rshard):
        lb = jax.tree_util.tree_map(lambda x: x[0], lshard)
        rb = jax.tree_util.tree_map(lambda x: x[0], rshard)
        lx = self._exchange_side(lb, self._l_routing)
        rx = self._exchange_side(rb, self._r_routing)
        if self.how in ("left_semi", "left_anti"):
            # no expansion: compact the probe side in-program, no sizing
            from ..exec.filter_common import compact
            order, lo, counts, sizes, matched = self._join._count(
                jnp, rx, lx)
            live = lx.row_mask()
            keep = (counts > 0) if self.how == "left_semi" else \
                (counts == 0)
            out = compact(jnp, lx, keep & live, self._join.output_names)
            return jax.tree_util.tree_map(lambda x: x[None], out)
        order, lo, counts, sizes, matched = self._join._count(jnp, rx, lx)
        add1 = lambda x: jax.tree_util.tree_map(  # noqa: E731
            lambda y: y[None], x)
        return (add1(lx), add1(rx), add1(order), add1(lo), add1(counts),
                sizes[None], matched[None])

    def _expand_step(self, lx, rx, order, lo, counts, out_cap: int,
                     pchar, bchar):
        strip = lambda x: jax.tree_util.tree_map(  # noqa: E731
            lambda y: y[0], x)
        if self._join._bound_condition is not None and self.how == "left":
            # conditional LEFT: co-located shards make the expand+repair
            # kernel (HashJoinExec._expand_left_cond) locally exact
            out = self._join._expand_left_cond(
                jnp, strip(rx), strip(lx), strip(order), strip(lo),
                strip(counts), out_cap, pchar, bchar)
            return jax.tree_util.tree_map(lambda x: x[None], out)
        out = self._join._expand(jnp, strip(rx), strip(lx), strip(order),
                                 strip(lo), strip(counts), out_cap,
                                 pchar, bchar)
        if self._join._bound_condition is not None and self.how == "inner":
            from ..exec.filter_common import apply_filter
            ctx = EvalContext(jnp, out)
            pred = self._join._bound_condition.eval(ctx)
            out = apply_filter(jnp, out, pred, self._join.output_names)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    @functools.cached_property
    def _jit_key(self):
        from ..exec.base import semantic_sig
        return ("DistributedHashJoin", self.axis, self.how,
                tuple(d.id for d in self.mesh.devices.flat),
                self._join._jit_key, semantic_sig(self._l_routing),
                semantic_sig(self._r_routing))

    def _compiled_count(self):
        from ..exec.base import process_jit

        def make():
            return shard_map(self._count_step, mesh=self.mesh,
                             in_specs=(P(self.axis), P(self.axis)),
                             out_specs=P(self.axis), check_vma=False)
        return process_jit(self._jit_key + ("count",), make)

    def _compiled_expand(self, out_cap: int, pchar, bchar):
        from ..exec.base import process_jit

        def make():
            def step(lx, rx, order, lo, counts):
                return self._expand_step(lx, rx, order, lo, counts,
                                         out_cap, pchar, bchar)
            return shard_map(step, mesh=self.mesh,
                             in_specs=(P(self.axis),) * 5,
                             out_specs=P(self.axis), check_vma=False)
        return process_jit(self._jit_key + ("expand", out_cap,
                                            tuple(pchar), tuple(bchar)),
                           make)

    def run(self, left_tables: Sequence[pa.Table],
            right_tables: Sequence[pa.Table]) -> pa.Table:
        assert len(left_tables) == self.n_dev
        assert len(right_tables) == self.n_dev
        return self.run_stacked(
            stack_shards(left_tables, mesh=self.mesh, axis=self.axis),
            stack_shards(right_tables, mesh=self.mesh, axis=self.axis))

    def run_stacked(self, ls: DeviceBatch, rs: DeviceBatch) -> pa.Table:
        """Join pre-stacked per-device shards (the device-resident
        scan->mesh edge: rows arrive without host Arrow staging, ref
        RapidsShuffleInternalManagerBase.scala:74)."""
        import numpy as np
        from ..columnar.device import (DEFAULT_CHAR_BUCKETS,
                                       DEFAULT_ROW_BUCKETS, bucket_for)
        if self.how in ("left_semi", "left_anti"):
            return shards_to_table(self._compiled_count()(ls, rs))
        (lx, rx, order, lo, counts, sizes,
         matched) = self._compiled_count()(ls, rs)
        sz = np.asarray(sizes)                       # one round trip
        ncols_l = len(self._join.children[0].output_names)
        if int(sz[:, 0].max()) >= (1 << 31):
            raise RuntimeError(
                f"join expansion of {int(sz[:, 0].max())} rows per shard "
                f"exceeds the 2^31-1 per-batch capacity")
        out_cap = bucket_for(max(int(sz[:, 0].max()), 1),
                             DEFAULT_ROW_BUCKETS)
        pb = sz[:, 1:1 + ncols_l].max(axis=0)
        bb = sz[:, 1 + ncols_l:].max(axis=0)
        l_types = self._join.children[0].output_types
        r_types = self._join.children[1].output_types
        pchar = [bucket_for(max(int(x), 1), DEFAULT_CHAR_BUCKETS)
                 if isinstance(dt, (t.StringType, t.BinaryType)) else 0
                 for x, dt in zip(pb, l_types)]
        bchar = [bucket_for(max(int(x), 1), DEFAULT_CHAR_BUCKETS)
                 if isinstance(dt, (t.StringType, t.BinaryType)) else 0
                 for x, dt in zip(bb, r_types)]
        out = self._compiled_expand(out_cap, pchar, bchar)(
            lx, rx, order, lo, counts)
        result = shards_to_table(out)
        if self.how == "full":
            # keys are co-located per shard, so every build row's matches
            # are local — per-shard unmatched emission is globally exact
            unmatched = self._compiled_unmatched()(rx, matched)
            um = shards_to_table(unmatched)
            if um.num_rows:
                result = pa.concat_tables(
                    [result, um.cast(result.schema)])
        return result

    def _compiled_unmatched(self):
        from ..exec.base import process_jit

        def make():
            def step(rx, matched):
                rb = jax.tree_util.tree_map(lambda y: y[0], rx)
                m = matched[0]
                out = self._join._unmatched_build(jnp, rb, m)
                return jax.tree_util.tree_map(lambda y: y[None], out)
            return shard_map(step, mesh=self.mesh,
                             in_specs=(P(self.axis), P(self.axis)),
                             out_specs=P(self.axis), check_vma=False)
        return process_jit(self._jit_key + ("unmatched",), make)


def _attr(name: str, dtype: t.DataType):
    from ..expr.core import AttributeReference
    return AttributeReference(name, dtype)
