"""Operators (exec/join.HashJoinExec, the ``left_semi`` / ``left_anti``
arm): device time per traced query in the semi join's two programs, self
time of their operations on the busiest chip: its count
(``jit_HashJoinExec.semi_count``: the build's order and the one sort of
both sides at their capacities) and its selection
(``jit_HashJoinExec.semi``: the kept probe rows compacted at the probe's
capacity).  The engine gives a selecting join's count a role of its own,
so that its time is told from the expanding joins' ``.count`` by name.
Nothing to read without a trace, or where no program of those names ran
(an engine that selects eagerly names none)."""

PROGRAMS = ("jit_HashJoinExec.semi_count", "jit_HashJoinExec.semi")


def read(run):
    """(``semi_join_hbm_roofline_share`` divides by it.)"""
    if run.trace is None or not run.traced_times_ms:
        return None
    seconds = [s for program, s in run.trace.busiest.program_s.items()
               if program.split("#", 1)[0] in PROGRAMS]
    if not seconds:
        return None
    return sum(seconds) * 1e3 / len(run.traced_times_ms)

