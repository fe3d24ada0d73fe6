"""Operators (exec/sort.SortExec, ops/carry.sort_rows): device time per
traced query in the program that ``SortExec`` built, self time of its
operations on the busiest chip.  Read by the program's name
(``jit_SortExec``), which the engine gives it at ``obs/compileprof``'s
``jax.jit`` seam; nothing to read where no such program ran.  About a
millisecond where the sort runs at the capacity of a grouped aggregate's
bounded output, seconds where it runs at the table's."""

from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    return device_ms_per_query(run, "SortExec")
