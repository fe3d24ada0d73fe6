"""Operators (exec/): the median over the window's queries of the
operators' own Python between dispatches and fetches (binding, keys,
unpacking the answer), in ms a query: the sum of the host ledger's segments
``compute:<Exec>`` (``obs/tracer.host_ledger()``, read as
``plan_ms_per_query`` reads it), which hold the self time of
``<Exec>.pull``, ``<Exec>.<metric>`` and the spans an operator opens for
its own work.  Host time, not device time.  Nothing to read in a program
without a ledger."""

from benchmarks.layer_metrics.plan_ms_per_query import median_ms

PREFIX = "compute:"


def read(run):
    return median_ms(run, lambda seg: sum(
        ns for s, ns in seg.items() if s.startswith(PREFIX)))
