"""Sort operator.

Ref: sql-plugin/.../GpuSortExec.scala:39-534 (single-batch, per-batch and
out-of-core modes) + SortUtils.scala.

TPU realization: order-preserving uint64 key-word encoding per sort column
(ops/segmented.key_words_for_column with true string ordering) feeding
`carry.sort_rows`: the rank from sort passes over the key's digits, then
a pass per 32-bit word of row data (ops/carry.py).  Multi-batch
partitions concatenate before sorting (spillable out-of-core merge arrives
with the memory framework; the concat path is the reference's
single-batch-goal mode).
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as t
from ..columnar.device import DeviceBatch
from ..expr.core import EvalContext, Expression, bind_expression
from ..ops import segmented as seg
from ..ops.gather import gather_batch
from .base import (maybe_sync,  # noqa: F401
                   NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, TPU, Batch,
                   Exec, MetricTimer, process_jit, schema_sig, semantic_sig)
from .concat import concat_batches


class SortExec(Exec):
    """orders: [(expr, ascending, nulls_first)]."""

    def __init__(self, orders, child: Exec, is_global: bool = True):
        super().__init__([child])
        self.orders = list(orders)
        self.is_global = is_global
        cn, ct = child.output_names, child.output_types
        self._bound = [(bind_expression(e, cn, ct), asc, nf)
                       for e, asc, nf in self.orders]

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    def describe(self):
        os = ", ".join(f"{e.sql()} {'ASC' if a else 'DESC'}"
                       for e, a, _ in self._bound)
        return f"Sort [{os}] global={self.is_global}"

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "stable sort: key order is a function of "
            "content, tie order follows arrival",
            establishes_order=True)

    def _sort_batch(self, xp, batch: Batch) -> Batch:
        ctx = EvalContext(xp, batch)
        live = ctx.row_mask()
        words: List = [~live]  # padding last
        for e, asc, nulls_first in self._bound:
            v = e.eval(ctx)
            from ..expr.core import ColumnValue, make_column
            if not isinstance(v, ColumnValue):
                v = make_column(ctx, e.data_type(),
                                v.value if v.value is not None else 0,
                                None if v.value is not None else False)
            words += seg.key_words_for_column(
                xp, v.col, live, for_grouping=False,
                nulls_first=nulls_first, ascending=asc)
        from ..ops import carry
        _, cols, _ = carry.sort_rows(xp, words, batch.columns,
                                     batch.capacity, need_order=False)
        return DeviceBatch(cols, batch.num_rows, batch.names)

    @functools.cached_property
    def _jit_key(self):
        return ("SortExec", schema_sig(self.children[0]),
                semantic_sig(self._bound))

    @property
    def _jitted(self):
        return process_jit(self._jit_key,
                           lambda: lambda b: self._sort_batch(jnp, b))

    def memory_effects(self, child_states, conf):
        """Materializes its whole input as registered spillables, then
        concat + sorted copy: ~3x one partition's padded bytes in-core,
        or 3x the enforced budget out-of-core (the working set the
        TPU-L014 repair bounds by setting oc_budget)."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         spill_budget)
        if not child_states:
            return None
        pp = padded_partition_bytes(child_states[0])
        budget = float(min(spill_budget(conf),
                           self.oc_budget or (1 << 62)))
        hold = 3.0 * (pp if pp <= budget else budget)
        return MemoryEffects(hold=hold, note="sort: spill-managed")

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        xp = self.xp
        from ..memory.spill import SpillCatalog, SpillPriority
        from .outofcore import enforce_device_budget
        spill = SpillCatalog.get()
        # a forced out-of-core budget (the TPU-L014 pre-flight repair)
        # lowers the in-core threshold below the catalog's and bounds
        # registered device bytes while the input streams in
        budget = min(spill.device_budget, self.oc_budget or (1 << 62))
        pending = []
        try:
            for b in self.children[0].execute_partition(pid, ctx):
                pending.append(spill.register(b, SpillPriority.INPUT))
                if self.oc_budget is not None:
                    enforce_device_budget(spill, budget)
            if not pending:
                return
            sort_fn = self._jitted if self.placement == TPU \
                else lambda b: self._sort_batch(np, b)
            total = sum(p.device_bytes for p in pending)
            if total <= budget:
                # in-core: concat everything and sort once
                with MetricTimer(self.metrics[OP_TIME]):
                    batches = [p.get_batch(xp) for p in pending]
                    merged = concat_batches(xp, batches, self.output_names,
                                            self.output_types) \
                        if len(batches) > 1 else batches[0]
                    for p in pending:
                        p.close()
                    out = sort_fn(merged)
                    maybe_sync(out)
                self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
                return
            # out-of-core external merge sort (ref GpuSortExec.scala:231)
            from .outofcore import external_merge_sort
            chunk_rows = max(int(p.num_rows) for p in pending)
            if self.oc_budget is not None:
                # keep each run chunk at ~half the enforced budget so a
                # two-run merge group stays within it; snap DOWN to a
                # capacity bucket — an off-bucket chunk pads UP to the next
                # bucket and would inflate real memory instead
                from ..columnar.device import (DEFAULT_ROW_BUCKETS,
                                               bucket_floor)
                rows_total = sum(int(p.num_rows) for p in pending)
                bpr = max(total / max(rows_total, 1), 1.0)
                target = int(budget / (2 * bpr))
                chunk_rows = min(chunk_rows,
                                 bucket_floor(target, DEFAULT_ROW_BUCKETS))
            with MetricTimer(self.metrics[OP_TIME]):
                for out in external_merge_sort(
                        xp, pending, sort_fn, self.output_names,
                        self.output_types, spill, budget,
                        chunk_rows):
                    self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
                    self.metrics[NUM_OUTPUT_BATCHES] += 1
                    yield out
        finally:
            # a raising producer (or an abandoned consumer) must
            # not strand registered spillables: close everything
            # this partition accumulated — idempotent, so batches
            # the merge already consumed are no-ops (tpufsan
            # TPU-R012)
            for p in pending:
                p.close()
