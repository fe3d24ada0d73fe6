"""Compile and dispatch (obs/compileprof._ProfiledJit): the median over the
window's queries of the host's wall time inside ``jit.key:<kind>`` (the
signature of the arguments and the lookup of the program) and
``jit.dispatch:<kind>`` (the call into the executable), in ms a query: the
host ledger's segment ``dispatch`` (``obs/tracer.host_ledger()``, read as
``plan_ms_per_query`` reads it).  What a program launch costs the host, not
what the program costs the device.  Nothing to read in a program without a
ledger."""

from benchmarks.layer_metrics.plan_ms_per_query import median_ms

SEGMENT = "dispatch"


def read(run):
    return median_ms(run, lambda seg: seg.get(SEGMENT, 0))
