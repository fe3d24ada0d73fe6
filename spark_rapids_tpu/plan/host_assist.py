"""Collect-side transfer elision for order-preserving plans.

A global sort of a host-resident source computes a PERMUTATION: the
result's bytes already exist on the host, only the row order is new.
Fetching the full sorted payload re-moves every byte over the
bandwidth-bound interconnect; fetching just the device-computed row
index (one integer lane, range-narrowed by the fetch plan) and applying
`take` on the host copy moves ~4 bytes/row instead of the whole row —
the collect-side sibling of the write path's keep-mask elision
(io/writer.py), playing the role GDS plays for the reference: bytes
that already sit in the right memory never cross the wire.

Scope: Sort (global) over optional Filter / attribute-only Project
chains over an in-memory LocalRelation.  Small results skip the rewrite
(below _MIN_ROWS the fetch fits one transfer anyway, and the device
path keeps full end-to-end coverage in tests).
"""

from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa

# below this, payload < latency: the rewrite cannot win and small-data
# tests keep exercising the real device fetch path
_MIN_ROWS = 1 << 16

_RID = "__rid__"


def try_host_assisted_collect(session, lp) -> Optional[pa.Table]:
    """Return the collect result via host take, or None when the plan is
    not a pure row-permutation of a host-resident source."""
    from .. import config as cfg
    from ..plan import logical as L

    if not (session.conf.sql_enabled and
            session.conf.get(cfg.HOST_ASSISTED_COLLECT)):
        return None
    if not isinstance(lp, L.Sort) or not lp.is_global:
        return None
    from ..expr.core import Alias, AttributeReference

    filters = []
    node = lp.children[0]
    while True:
        if isinstance(node, L.Project):
            if not all(isinstance(e, AttributeReference)
                       for e in node.exprs):
                return None
            node = node.children[0]
        elif isinstance(node, L.Filter):
            filters.append(node.condition)
            node = node.children[0]
        elif isinstance(node, L.LocalRelation):
            break
        else:
            return None
    host = node.table
    if host.num_rows < _MIN_ROWS:
        return None

    # device plan: carry a row id through the filters and the sort, and
    # fetch ONLY it (the fetch plan narrows its value range).  Only the
    # columns the filters/sort keys read ride along — payload columns
    # would bloat the sort's carry lanes (and its compile) for nothing.
    from ..expr.hashfns import MonotonicallyIncreasingID
    needed = []
    for e in [c for c in filters] + [o[0] for o in lp.orders]:
        for a in e.collect(lambda x: isinstance(x, AttributeReference)):
            if a.name not in needed:
                needed.append(a.name)
    rid_plan: L.LogicalPlan = L.Project(
        [AttributeReference(n) for n in host.schema.names
         if n in needed]
        + [Alias(MonotonicallyIncreasingID(), _RID)], node)
    for cond in reversed(filters):
        rid_plan = L.Filter(cond, rid_plan)
    rid_plan = L.Sort(lp.orders, True, rid_plan)
    rid_plan = L.Project([AttributeReference(_RID)], rid_plan)
    rid = session.execute(rid_plan).column(_RID).to_numpy()

    # (partition << 33) + offset -> global row index; LocalScanExec
    # slices the table into ceil(n/p)-row partitions in order
    n_parts = max(1, node.num_partitions)
    per = -(-host.num_rows // n_parts)
    idx = (rid >> 33) * per + (rid & ((np.int64(1) << 33) - 1))
    out = host.combine_chunks().take(idx)
    names = lp.schema()[0]
    if list(out.schema.names) != names:
        out = out.select(names)
    return out
