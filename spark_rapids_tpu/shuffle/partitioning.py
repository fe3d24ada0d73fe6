"""On-device partitioning: hash / round-robin / range / single.

Ref: GpuHashPartitioning.scala, GpuRoundRobinPartitioning.scala,
GpuRangePartitioner.scala, GpuSinglePartitioning.scala and the slicing
machinery in GpuPartitioning.scala:50-130.

Partition ids compute on device (Spark-compatible: pmod(murmur3(keys), n)
for hash partitioning, so CPU and TPU engines route rows identically);
slicing reuses the stable-compaction kernel — one sort by partition id,
then per-partition span extraction."""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import types as t
from ..columnar.device import DeviceBatch
from ..expr.core import EvalContext, Expression, bind_expression
from ..expr.hashfns import Murmur3Hash
from ..ops.gather import gather_batch


class Partitioning:
    num_partitions: int = 1

    def bind(self, names, dtypes):
        return self

    def partition_ids(self, xp, ctx: EvalContext, batch: DeviceBatch,
                      row_offset: int = 0):
        """int32[cap] partition id per row."""
        raise NotImplementedError

    def describe(self) -> str:
        return f"{type(self).__name__}({self.num_partitions})"


class SinglePartitioning(Partitioning):
    num_partitions = 1

    def partition_ids(self, xp, ctx, batch, row_offset=0):
        return xp.zeros((batch.capacity,), dtype=np.int32)


class HashPartitioning(Partitioning):
    def __init__(self, keys: Sequence[Expression], num_partitions: int):
        self.keys = list(keys)
        self.num_partitions = num_partitions
        self._bound: Optional[Murmur3Hash] = None

    def bind(self, names, dtypes):
        out = HashPartitioning(self.keys, self.num_partitions)
        out._bound = Murmur3Hash(
            [bind_expression(k, names, dtypes) for k in self.keys])
        return out

    def partition_ids(self, xp, ctx, batch, row_offset=0):
        h = self._bound.eval(ctx).col.data.astype(xp.int32)
        n = np.int32(self.num_partitions)
        # Spark: pmod(hash, n)
        r = xp.mod(h, n)
        return xp.where(r < 0, r + n, r).astype(np.int32)


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def partition_ids(self, xp, ctx, batch, row_offset=0):
        idx = xp.arange(batch.capacity, dtype=np.int32) + np.int32(row_offset)
        return xp.mod(idx, np.int32(self.num_partitions))


class RangePartitioning(Partitioning):
    """Range partitioning by sampled bounds (ref GpuRangePartitioner:
    sample rows, pick n-1 boundary rows, bucket by binary search)."""

    def __init__(self, orders, num_partitions: int):
        # orders: [(expr, ascending, nulls_first)]
        self.orders = list(orders)
        self.num_partitions = num_partitions
        self._bound_orders = None
        self.bounds_words: Optional[List] = None  # per-word boundary arrays

    def bind(self, names, dtypes):
        out = RangePartitioning(self.orders, self.num_partitions)
        out._bound_orders = [(bind_expression(e, names, dtypes), asc, nf)
                             for e, asc, nf in self.orders]
        out.bounds_words = self.bounds_words
        return out

    def _row_words(self, xp, ctx, batch):
        from ..ops import segmented as seg
        live = ctx.row_mask()
        words = []
        for e, asc, nf in self._bound_orders:
            v = e.eval(ctx)
            from ..expr.core import ColumnValue, make_column
            if not isinstance(v, ColumnValue):
                v = make_column(ctx, e.data_type(),
                                v.value if v.value is not None else 0,
                                None if v.value is not None else False)
            words += seg.key_words_for_column(xp, v.col, live,
                                              for_grouping=False,
                                              nulls_first=nf, ascending=asc)
        return words

    def compute_bounds(self, xp, ctx, batch):
        """Pick n-1 equally spaced boundary key-words from a sorted batch
        sample."""
        from ..ops import segmented as seg
        words = self._row_words(xp, ctx, batch)
        order = seg.lexsort(xp, words, batch.capacity)
        n = self.num_partitions
        live_n = xp.maximum(batch.num_rows, 1)
        picks = ((xp.arange(n - 1, dtype=np.int64) + 1) * live_n) // n
        picks = xp.clip(picks, 0, batch.capacity - 1).astype(np.int32)
        self.bounds_words = [w[order][picks] for w in words]

    def partition_ids(self, xp, ctx, batch, row_offset=0):
        if self.bounds_words is None:
            self.compute_bounds(xp, ctx, batch)
        words = self._row_words(xp, ctx, batch)
        cap = batch.capacity
        pid = xp.zeros((cap,), dtype=np.int32)
        # row > bound_b (lexicographically) for each of the n-1 bounds
        for b in range(self.num_partitions - 1):
            gt = xp.zeros((cap,), dtype=bool)
            eq = xp.ones((cap,), dtype=bool)
            for w, bw in zip(words, self.bounds_words):
                bv = bw[b]
                gt = gt | (eq & (w > bv))
                eq = eq & (w == bv)
            pid = pid + (gt | eq).astype(np.int32)
        return pid


def slice_batch_by_partition(xp, batch: DeviceBatch, pids,
                             num_partitions: int):
    """Sort rows by partition id (stable) and return (sorted_batch,
    partition_row_counts[int64 np array]).  The caller slices host-side by
    counts — the analog of GpuPartitioning's contiguous split."""
    from ..ops import carry
    live = xp.arange(batch.capacity, dtype=np.int32) < batch.num_rows
    key = xp.where(live, pids, np.int32(num_partitions))  # padding last
    # rows ride the sort (no post-sort gathers)
    _, cols, ex = carry.sort_rows(xp, [key.astype(xp.uint32)],
                                  batch.columns, batch.capacity,
                                  extras=[key], need_order=False)
    sorted_pids = ex[0]
    counts = xp.zeros((num_partitions,), dtype=np.int64)
    if xp is np:
        u, c = np.unique(sorted_pids[sorted_pids < num_partitions],
                         return_counts=True)
        counts[u] = c
    else:
        import jax
        counts = jax.ops.segment_sum(
            (sorted_pids < num_partitions).astype(xp.int32),
            xp.clip(sorted_pids, 0, num_partitions).astype(xp.int32),
            num_segments=num_partitions + 1)[:num_partitions].astype(
                xp.int64)
    return DeviceBatch(cols, batch.num_rows, batch.names), counts
