"""A predicate's keep flags, and the two things a filter does with them.

`keep_flags` turns a predicate value into one bool lane (a null drops the
row, Spark; rows at or past `num_rows` are dropped).

**Compaction** (`compact`, `apply_filter`: FilterExec, conditional joins,
HAVING, the mesh stages): a stable partition on the keep flag, so
survivors move to the front in original order and `num_rows` shrinks to
their count.  Static shapes: each row's place is a prefix sum of the
flags, and every lane goes there by a sort pass (ops/carry.py): no gather
by an order, no dynamic shapes, no host sync.  A pass is 79 ms a 32-bit
word at 33,554,432 slots on the v5e, so this is what a consumer pays for
that reads rows by position.

**The mask alone** (`MaskedBatch`): a consumer that reduces under a mask
wherever the rows lie needs none of that.  The update side of a
`TpuHashAggregateExec` directly above a `FilterExec` is the one such
consumer (`exec/aggregate.TpuHashAggregateExec.masked_source` is the plan
seam that pairs them): it pulls `FilterExec.execute_masked`, which hands
up the input batch as it lay, untouched, with the keep flags and their
count beside it.  A `MaskedBatch` is no `DeviceBatch` and no pytree: it
has no columns to read, so an operator that does not know the mask cannot
take one for a batch.
"""

from __future__ import annotations

import numpy as np

from ..columnar.device import DeviceBatch
from ..ops.gather import gather_batch


def keep_flags(xp, batch: DeviceBatch, pred_value):
    """bool[cap] from a predicate value (null -> drop, Spark)."""
    live = xp.arange(batch.capacity, dtype=np.int32) < batch.num_rows
    from ..expr.core import ScalarValue
    if isinstance(pred_value, ScalarValue):
        if pred_value.value is None or not bool(pred_value.value):
            return xp.zeros((batch.capacity,), dtype=bool)
        return live
    col = pred_value.col
    keep = col.data.astype(bool)
    if col.validity is not None:
        keep = keep & col.validity
    return keep & live


class MaskedBatch:
    """A filter's input batch where it lay, `keep` (bool[capacity], false
    at and past the batch's `num_rows`) and `num_rows`, the survivors'
    count (a device scalar under the TPU engine: what the filter's
    `numOutputRows` and an operator span add up, never read here).  The
    survivors are `batch`'s rows under `keep`; `batch.num_rows` still
    counts the dropped ones."""

    __slots__ = ("batch", "keep", "num_rows")

    def __init__(self, batch: DeviceBatch, keep, num_rows):
        self.batch = batch
        self.keep = keep
        self.num_rows = num_rows

    @property
    def capacity(self) -> int:
        return self.batch.capacity


def count_kept(xp, keep):
    return xp.sum(keep.astype(np.int32))


def compact(xp, batch: DeviceBatch, keep, names):
    """Move kept rows to the front (stable), shrink num_rows
    (`carry.compact_rows`); dropped rows become padding (validity masked
    off per the batch contract)."""
    from ..ops.carry import compact_rows, mask_validity
    cap = batch.capacity
    new_n = count_kept(xp, keep)
    valid_slot = xp.arange(cap, dtype=np.int32) < new_n
    _, cols, _ = compact_rows(xp, keep, batch.columns, cap)
    cols = [mask_validity(xp, c, valid_slot) for c in cols]
    return DeviceBatch(cols, new_n, names)


def apply_filter(xp, batch: DeviceBatch, pred_value, names):
    return compact(xp, batch, keep_flags(xp, batch, pred_value), names)
