"""Operators (exec/, ops/): device time per query, the union of the
device-operation intervals on the busiest chip in the traced window by the
queries traced.  From the profiler trace, never from a host timer."""


def read(run):
    device_s = run.device_s_per_query
    return None if device_s is None else device_s * 1e3
