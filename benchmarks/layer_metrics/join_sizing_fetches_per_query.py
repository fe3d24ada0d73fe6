"""Host path (exec/join.HashJoinExec): blocking fetches of a join's output
sizes a query: the program's counter ``tpu_join_sizing_fetches_total`` at
the end of the run by the queries the process has asked (the window's and
the one warm-up call).  A join whose output capacity follows the data
waits for its count program once a probe batch before it expands; one that
sizes its output without asking fetches nothing.  Nothing to read in a
program that has no such counter."""

COUNTER = "tpu_join_sizing_fetches_total"
WARM_UP_CALLS = 1


def read(run):
    from spark_rapids_tpu.obs import metrics
    asked = len(run.times_ms) + WARM_UP_CALLS
    for family in metrics.registry().families():
        if family.name == COUNTER:
            return family.total() / asked
    return None
