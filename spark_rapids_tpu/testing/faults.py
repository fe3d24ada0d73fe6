"""Stand-ins for a `FilterExec`'s iterators: how the gates
(`devtools/run_lint.py`) and the tests arm the filter of a query with a
fault, a stall or a probe.

A filter is pulled through one of two iterators (`exec/basic.py`):
`execute_partition` by every consumer that reads rows by position,
`execute_masked` by the consumer the plan paired with it (the update side
of a TPU aggregate, a TPU hash join, or the bare selection under one),
which passes itself.  Arming one of them alone misses the queries that
take the other, so both are replaced together."""

from ..exec.base import _wrap_execute_partition
from ..exec.basic import FilterExec

_RAW_COMPACTED = FilterExec.execute_partition.__wrapped__
_RAW_MASKED = FilterExec.execute_masked.__wrapped__


def raw_filter_iterator(self, pid, ctx, *consumer):
    """The filter's own iterator, for a stand-in that does its deed and
    then lets the batches through: the masked one where the paired
    consumer pulls (`consumer` is that aggregate, join or selection),
    else the compacting one."""
    if consumer:
        return _RAW_MASKED(self, pid, ctx, *consumer)
    return _RAW_COMPACTED(self, pid, ctx)


def arm_filter(ep):
    """Replace both iterators of every `FilterExec` by the generator
    function `ep(self, pid, ctx, *consumer)`, wrapped in the spans every
    operator's iterator carries.  Returns what `disarm_filter` takes."""
    saved = FilterExec.execute_partition, FilterExec.execute_masked
    FilterExec.execute_partition = FilterExec.execute_masked = \
        _wrap_execute_partition(ep)
    return saved


def disarm_filter(saved) -> None:
    FilterExec.execute_partition, FilterExec.execute_masked = saved
