"""Operators (exec/join.HashJoinExec): millions of slots the joins' count
programs sort a query: the program's counter
``tpu_join_sorted_slots_total`` (at each probe batch the probe's capacity
and the build's, live or not: what the count's one sort covers, known on
the host without a wait) at the end of the run, by the queries the process
has asked (the window's and the one warm-up call), by 1e6.  A join that
sizes its sort by what is live brings it down.  Nothing to read in a
program that has no such counter."""

COUNTER = "tpu_join_sorted_slots_total"
WARM_UP_CALLS = 1


def read(run):
    from spark_rapids_tpu.obs import metrics
    asked = len(run.times_ms) + WARM_UP_CALLS
    for family in metrics.registry().families():
        if family.name == COUNTER:
            return family.total() / asked / 1e6
    return None
