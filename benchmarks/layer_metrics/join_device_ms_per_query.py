"""Operators (exec/join.HashJoinExec, ops/join_kernels.py): device time
per traced query in the programs that ``HashJoinExec`` built (the count
and the expansion of each join of the plan), self time of their operations
on the busiest chip.  Read by the programs' name
(``jit_HashJoinExec.<role>``), which the engine gives them at
``obs/compileprof``'s ``jax.jit`` seam; nothing to read where no such
program ran."""

from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    return device_ms_per_query(run, "HashJoinExec")
