"""Operators (exec/aggregate.TpuHashAggregateExec, ops/aggregate,
ops/carry.lean_argsort): device time per traced query in the programs that
``TpuHashAggregateExec`` built (``update``, ``merge``, ``eval``,
``complete``, ...), self time of their operations on the busiest chip.
Read by the program's name (``jit_TpuHashAggregateExec.<role>``); nothing
to read where the programs are not so named."""

from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    return device_ms_per_query(run, "TpuHashAggregateExec")
