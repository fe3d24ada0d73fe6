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
that reads rows by position.  **Gathering** (`gather_kept`: a semi or an
anti join's sides, few kept rows in a large capacity) puts the same rows
in a smaller bucket by looking each up, a binary search over the flags'
prefix sum, and moves no lane at the capacity.

**The mask alone** (`MaskedBatch`): a consumer that keeps dead rows apart
by flags, wherever the rows lie, needs none of that.  Two operators do,
each directly above a `FilterExec`, both on the TPU engine: the update
side of a `TpuHashAggregateExec` (it reduces under the mask) and either
side of a `HashJoinExec` (liveness rides its sorts as a flag).  Such a
consumer pulls `FilterExec.execute_masked`, which hands up the input batch
as it lay, untouched, with the keep flags and their count beside it.  A
`ProjectExec` that only selects columns forwards the masked batch from the
filter below it to the join above it.  A `MaskedBatch` is no `DeviceBatch`
and no pytree: it has no columns to read, so an operator that does not
know the mask cannot take one for a batch.

**The seam** is one: a consumer's `masked_sources()` names, child by
child, the operator it reads through `execute_masked` (or None: it pulls
`execute_partition`), from the plan's shape when the partition is pulled.
`masked_child` is the condition every consumer shares; `check_paired` is
the refusal every source makes before it hands anything up.
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


def masked_child(consumer, child, exprs=()):
    """`child` where `consumer` may read it through `execute_masked`, else
    None: both are on the TPU engine, `child` hands up a mask as the plan
    stands (`can_mask`: a filter whose `rebucket_cap` is not armed, for
    the L018 repair shrinks a COMPACTED output; a bare selection over
    one), and none of `exprs`, what the consumer evaluates over the
    child's rows, reads a row's position (`rand`,
    `monotonically_increasing_id`: it would see the rows where they lay
    and not where compaction put them)."""
    from .base import TPU
    from .basic import _exprs_need_rowpos
    can_mask = getattr(child, "can_mask", None)
    if consumer.placement == TPU and can_mask is not None and can_mask() \
            and not _exprs_need_rowpos(exprs):
        return child
    return None


def check_paired(source, consumer) -> None:
    """Refuse, before a batch is made, any `consumer` that pulls
    `source.execute_masked` and is not paired with it by the plan: a
    masked batch reaches nothing that does not read the mask."""
    sources = getattr(consumer, "masked_sources", None)
    if sources is None or not any(s is source for s in sources()):
        raise RuntimeError(
            f"{type(consumer).__name__} is not paired with this "
            f"{type(source).__name__}: only the consumer whose "
            "masked_sources() names it may pull execute_masked")


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


def gather_kept(xp, batch: DeviceBatch, keep, out_cap: int, names,
                char_caps=None) -> DeviceBatch:
    """`compact`'s rows in `out_cap` slots, for kept rows few against the
    capacity: each column gathered through `carry.kept_slots` (a
    prefix sum and a search, no pass at the capacity); no flags ride
    along and `num_rows` is the kept count.  `char_caps`: a span
    column's child capacity, 0 for the source's (always enough)."""
    from ..ops.carry import kept_slots
    slot, valid, kept = kept_slots(xp, keep, out_cap)
    return DeviceBatch(gather_batch(xp, batch, slot, valid, kept,
                                    char_caps).columns, kept, names)


def apply_filter(xp, batch: DeviceBatch, pred_value, names):
    return compact(xp, batch, keep_flags(xp, batch, pred_value), names)
