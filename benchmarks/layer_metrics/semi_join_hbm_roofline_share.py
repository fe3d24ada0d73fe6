"""Operators (exec/join.HashJoinExec, the ``left_semi`` arm): the semi
join's share of the HBM roofline.  The least bytes it must move (the query
file's ``semi_join_least_bytes``: the probe's key read once, the live
build keys read once, the kept rows' carried columns read and written
once, whatever implements it) over the published HBM peak of the
``device_kind`` is its least time; that over the device time per traced
query in its two programs (``semi_join_device_ms_per_query``), in per
cent.  Hundredths of a per cent while the count sorts both sides at their
capacities for a few dozen live keys.  Nothing to read where no such
program ran, or where the query counts no such bytes."""

from benchmarks.harness.peaks import peak
from benchmarks.layer_metrics import semi_join_device_ms_per_query


def read(run):
    ms = semi_join_device_ms_per_query.read(run)
    least = getattr(run.query, "semi_join_least_bytes", None)
    if ms is None or least is None:
        return None
    least_ms = least() / (peak(run.device_kind, "hbm_bytes_per_s")
                          * run.chips) * 1e3
    return 100.0 * least_ms / ms
