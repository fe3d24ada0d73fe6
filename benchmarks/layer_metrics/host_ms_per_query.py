"""Host path (api/, plan/, analysis/, memory/admission.py, dispatch in
exec/base.process_jit, columnar/fetch.py): the median wall time of a traced
query less the device's busy time per traced query on the busiest chip.
What is left is time in which the caller waited and no operation ran."""

from benchmarks.harness.stats import median


def read(run):
    device_s = run.device_s_per_query
    if device_s is None:
        return None
    return median(run.traced_times_ms) - device_s * 1e3
