"""Operators (exec/basic.FilterExec, ops/filter_common.compact): device
time per traced query in the programs that ``FilterExec`` built, self time
of their operations on the busiest chip.  Read by the program's name
(``jit_FilterExec[.<role>]``), which the engine gives it at
``obs/compileprof``'s ``jax.jit`` seam; nothing to read where the programs
are not so named."""

from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    return device_ms_per_query(run, "FilterExec")
