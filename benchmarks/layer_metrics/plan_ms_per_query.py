"""Host path (api/session.prepare_plan, plan/, analysis/, plan/host_assist,
the session's own steps around the plan): the median over the window's
queries of the host's wall time that the engine's host ledger booked to
planning, in ms a query.  The ledger (``obs/tracer.host_ledger()``) sums
each query's wall by segment as its spans close, self time only, so the
five ``*_ms_per_query`` readers over it never count a second twice and
together make the query's wall; the other four read through ``median_ms``
here.  Nothing to read in a program without a ledger, or where it holds
fewer records than the window asked queries."""

from benchmarks.harness.stats import median

SEGMENTS = ("planning", "host_assist", "session")


def median_ms(run, part):
    """The median over the window's records of ``part(segments)``, in ms:
    the ledger's last ``len(run.times_ms)`` records are the window's (the
    warm-up call's is the one before them)."""
    from spark_rapids_tpu.obs import tracer
    ledger = getattr(tracer, "host_ledger", None)
    asked = len(run.times_ms)
    if ledger is None or not asked:
        return None
    records = ledger().records()
    if len(records) < asked:
        return None
    return median([part(r["segments"]) for r in records[-asked:]]) / 1e6


def read(run):
    return median_ms(run, lambda seg: sum(seg.get(s, 0) for s in SEGMENTS))
