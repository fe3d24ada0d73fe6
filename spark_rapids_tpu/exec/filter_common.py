"""Filter compaction shared by FilterExec / conditional joins / having.

Static-shape compaction: a stable partition on the keep flag, so survivors
move to the front in original order.  Each row's place is a prefix sum of
the flags, and every lane goes there by a sort pass (ops/carry.py):
no gather by an order, no dynamic shapes, no host sync.
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


def compact(xp, batch: DeviceBatch, keep, names):
    """Move kept rows to the front (stable), shrink num_rows
    (`carry.compact_rows`); dropped rows become padding (validity masked
    off per the batch contract)."""
    from ..ops.carry import compact_rows, mask_validity
    cap = batch.capacity
    new_n = xp.sum(keep.astype(np.int32))
    valid_slot = xp.arange(cap, dtype=np.int32) < new_n
    _, cols, _ = compact_rows(xp, keep, batch.columns, cap)
    cols = [mask_validity(xp, c, valid_slot) for c in cols]
    return DeviceBatch(cols, new_n, names)


def apply_filter(xp, batch: DeviceBatch, pred_value, names):
    return compact(xp, batch, keep_flags(xp, batch, pred_value), names)
