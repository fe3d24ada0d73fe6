"""Operators (kernels): the share of the HBM roofline.  The least bytes the
query must move (the query file's ``least_bytes`` of the table's rows and
the answer's) over the published HBM peak of the ``device_kind`` is the
least time; that over the device time per query, in per cent.  Both
queries are bandwidth-bound (a flop or two a row).  On several chips the
table is spread over them, so the least time divides by the chips."""

from benchmarks.harness.peaks import peak
from benchmarks.harness.stats import median


def read(run):
    device_s = run.device_s_per_query
    if device_s is None:
        return None
    out_rows = int(median(run.answer_rows)) if run.answer_rows else 0
    least_s = run.query.least_bytes(run.n_rows, out_rows) / \
        (peak(run.device_kind, "hbm_bytes_per_s") * run.chips)
    return 100.0 * least_s / device_s
