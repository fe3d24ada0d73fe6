"""Exchange (parallel/ici_exec.IciAggregateExec, parallel/distributed.py,
parallel/alltoall.py): device time per traced query in the programs that
``IciAggregateExec`` dispatched (the SPMD step: partial aggregate,
all_to_all, each chip's final merge; and its reshard where the table is not
resident), self time of their operations on the busiest chip.  Read by the
program's name (``jit_IciAggregateExec[.<role>]``); nothing to read where
the programs are not so named."""

from benchmarks.harness.program_kinds import device_ms_per_query


def read(run):
    return device_ms_per_query(run, "IciAggregateExec")
