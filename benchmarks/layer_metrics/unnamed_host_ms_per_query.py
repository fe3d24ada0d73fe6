"""Host path (api/session): the median over the window's queries of the
wall time that no span below the query's root and ``phase:execute`` names,
in ms a query: the host ledger's segment ``other``
(``obs/tracer.host_ledger()``, read as ``plan_ms_per_query`` reads it), their
self time.  It should stay small: what grows here has to be given a span
before it can be worked on.  Nothing to read in a program without a
ledger."""

from benchmarks.layer_metrics.plan_ms_per_query import median_ms

SEGMENT = "other"


def read(run):
    return median_ms(run, lambda seg: seg.get(SEGMENT, 0))
