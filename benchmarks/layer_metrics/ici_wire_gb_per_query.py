"""Exchange (parallel/alltoall.py): GB (1e9 bytes) a query hands to the
interconnect, summed over the mesh: the program's counter
``tpu_ici_wire_bytes_total`` at the end of the run by the queries the
process has asked (the window's and the one warm-up call).  The counter
adds, at every dispatch, the static figure found when the program was
traced (lanes x n_parts x slot x itemsize x (n-1)/n a chip), so the
quotient is exact.  Nothing to read in a program that has no such
counter."""

COUNTER = "tpu_ici_wire_bytes_total"
WARM_UP_CALLS = 1


def read(run):
    from spark_rapids_tpu.obs import metrics
    asked = len(run.times_ms) + WARM_UP_CALLS
    for family in metrics.registry().families():
        if family.name == COUNTER:
            return family.total() / 1e9 / asked
    return None
