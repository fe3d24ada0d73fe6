"""Operators (exec/join.HashJoinExec): the joins' share of the HBM
roofline.  The least bytes the query's joins must move (the query file's
``join_least_bytes``: each side's key and carried columns of the live rows
read once, the output written once, whatever implements the join) over the
published HBM peak of the ``device_kind`` is their least time; that over
the device time per traced query in the programs ``HashJoinExec`` built,
in per cent.  Hundredths of a per cent while a join is sort passes,
scatters and gathers.  Nothing to read where no such program ran, or
where the query counts no such bytes."""

from benchmarks.harness.peaks import peak
from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    device_ms = device_ms_per_query(run, "HashJoinExec")
    least = getattr(run.query, "join_least_bytes", None)
    if device_ms is None or least is None:
        return None
    least_ms = least() / (peak(run.device_kind, "hbm_bytes_per_s")
                          * run.chips) * 1e3
    return 100.0 * least_ms / device_ms
