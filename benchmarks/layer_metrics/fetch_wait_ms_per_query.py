"""D2H fetch (columnar/fetch._crossing): the median over the window's
queries of the wall time the host stood blocked in ``fetch.crossing``, in
ms a query: the host ledger's segment ``fetch_wait``
(``obs/tracer.host_ledger()``, read as ``plan_ms_per_query`` reads it).  The
transfers wait for the programs in front of them, so in a device-bound
cell this is the device's time as the host sees it, and in a host-led cell
what is left of it after the host's own work.  Nothing to read in a
program without a ledger."""

from benchmarks.layer_metrics.plan_ms_per_query import median_ms

SEGMENT = "fetch_wait"


def read(run):
    return median_ms(run, lambda seg: seg.get(SEGMENT, 0))
