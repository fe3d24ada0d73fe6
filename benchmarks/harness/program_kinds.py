"""Device time by the operator that built the program.

The engine names every program after the operator kind that built it
(``obs/compileprof.program_name``): XLA calls the module
``jit_<ExecKind>[.<role>]`` and the trace reduction keys
``ChipTime.program_s`` by ``short_program``, ``jit_<ExecKind>[.<role>]#<four
digits of the fingerprint>``.  The digits change with every edit to a
program; the name before ``#`` does not.
"""

from typing import Optional

from .facts import RunFacts


def is_of_kind(program: str, kind: str) -> bool:
    """``jit_FilterExec#8751`` and ``jit_FilterExec.rowpos#0693`` are of
    the kind ``FilterExec``; ``jit_FilterExecutor#1`` is not."""
    name = program.split("#", 1)[0]
    return name == "jit_" + kind or name.startswith("jit_" + kind + ".")


def device_ms_per_query(run: RunFacts, kind: str) -> Optional[float]:
    """Milliseconds a traced query spent, on the busiest chip, in the
    operations (self time) of the programs of one operator kind.  ``None``
    without a trace, and where no program of the kind ran: a program that
    does not carry its operator's name reads nothing, it is not a zero."""
    if run.trace is None or not run.traced_times_ms:
        return None
    seconds = [s for program, s in run.trace.busiest.program_s.items()
               if is_of_kind(program, kind)]
    if not seconds:
        return None
    return sum(seconds) * 1e3 / len(run.traced_times_ms)
