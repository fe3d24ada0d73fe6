"""Exchange (parallel/): device time of the collective operations
(all-to-all, all-reduce, all-gather, collective-permute, reduce-scatter)
on the busiest chip per traced query, from the profiler trace.  A cell on
one chip has no collective and reads nothing."""


def read(run):
    if run.trace is None or not run.traced_times_ms or run.chips < 2:
        return None
    return run.trace.busiest.collective_s * 1e3 / len(run.traced_times_ms)
