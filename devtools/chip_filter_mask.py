"""On the chip: TPC-H Q6, Q1 and Q3 at the benchmark's scale, each with its
filters handing up the keep flags (what the plan does: `FilterExec` lies
directly under the aggregate, `TpuHashAggregateExec.masked_source`; or
under a join, with or without a bare selection between,
`HashJoinExec.masked_sources`) and with both pairings switched off in this
process (the filters compact, as they did before PRs 34 and 36), turn and
turn about in ONE process on one table:

    chiprun --timeout 1800 -- python devtools/chip_filter_mask.py [seed] [query ...]

Prints one JSON object a (query, mode) round: the first call's wall, the
warm calls' walls, what `tpu_filter_batches_total{path}` rose by, the
answer's deviation from the NumPy reference (`benchmarks/queries`) and,
from `TRACED_CALLS` more calls under the profiler, the device ms a query of
every `jit_FilterExec` and `jit_HashJoinExec` program (Q3: three filters,
four join programs); then, a query, the largest relative difference
between the masked and the compacted answers.  Ends non-zero where an
answer is not the reference's, the two modes differ by more than 1e-12, the
counter did not rise by one a filter a query on the mode's path, or JAX
finds no TPU.  One chip, about ten minutes warm (Q3 is 4 of them).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WARM_CALLS = 5
TRACED_CALLS = 2
ROUNDS = ("mask", "compact", "mask", "compact")
#: (query, configuration, parameters, filters in its plan)
QUERIES = (
    ("q6", "tpch_sf5_1chip", {"year": 1994, "discount": 0.06,
                              "quantity": 24}, 1),
    ("q1", "tpch_q1_1chip", {"delta": 90}, 1),
    ("q3", "tpch_q3_1chip", {"segment": "BUILDING", "day": 15}, 3),
)


def _counter(path: str) -> float:
    from spark_rapids_tpu.obs import metrics
    for family in metrics.registry().families():
        if family.name == "tpu_filter_batches_total":
            return family.value(path=path)
    return 0.0


def _floats(answer):
    """Every float of an answer, as one array (Q6's revenue; Q1's sums and
    averages, group after group)."""
    import numpy as np
    if isinstance(answer, dict):
        parts = [np.ravel(v) for v in answer.values()
                 if isinstance(v, np.ndarray) and v.dtype.kind == "f"]
        return np.concatenate(parts) if parts else np.zeros(0)
    return np.array([answer], dtype=np.float64)


def _apart(a, b) -> float:
    """The largest relative difference between two answers' floats."""
    import numpy as np
    a, b = _floats(a), _floats(b)
    if a.shape != b.shape or not a.size:
        return float("inf")
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-300)))


def _program_ms(ask) -> dict:
    """Device ms a call of every filter and join program, from
    `TRACED_CALLS` calls of `ask` under the profiler, reduced as the
    benchmark's traced run is (`benchmarks/harness`)."""
    import jax
    from benchmarks.harness import runner, trace_reduce
    from benchmarks.harness.program_kinds import is_of_kind
    from jax.profiler import TraceAnnotation
    trace_dir = tempfile.mkdtemp(prefix="filter_mask_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with TraceAnnotation(trace_reduce.WINDOW_ANNOTATION):
            for _ in range(TRACED_CALLS):
                ask()
    finally:
        jax.profiler.stop_trace()
    try:
        programs = runner.Bench.read_trace(trace_dir).busiest.program_s
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {name: 1e3 * seconds / TRACED_CALLS
            for name, seconds in sorted(programs.items())
            if is_of_kind(name, "FilterExec")
            or is_of_kind(name, "HashJoinExec")}


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 3400000901
    wanted = argv[2:]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chip_filter_mask: no TPU", file=sys.stderr)
        return 2
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from benchmarks.harness import cells, runner
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.join import HashJoinExec
    paired = (TpuHashAggregateExec.masked_source,
              HashJoinExec.masked_sources)
    unpaired = (lambda self: None, lambda self: (None, None))

    def pair(on: bool) -> None:
        (TpuHashAggregateExec.masked_source,
         HashJoinExec.masked_sources) = paired if on else unpaired
    session = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
               .get_or_create())
    rc = 0
    for name, config, params, filters in QUERIES:
        if wanted and name not in wanted:
            continue
        cell = cells.load_cell(ROOT, f"{config}.{name}")
        columns = cell.datagen.generate(cell.config, seed)
        df = session.create_dataframe(
            runner.arrow_table(columns, cell.datagen.SCHEMA),
            num_partitions=1)
        want = cell.query.reference(columns, params)
        answers = {}
        for mode in ROUNDS:
            pair(mode == "mask")
            before = {p: _counter(p) for p in ("mask", "compact")}
            walls = []
            for _ in range(1 + WARM_CALLS):
                t0 = time.perf_counter()
                table = cell.query.build(df, params).collect()
                walls.append(time.perf_counter() - t0)
                got = cell.query.answer(table)
                fault = cell.query.mismatch(got, want)
                if fault:
                    print(json.dumps({"query": name, "mode": mode,
                                      "mismatch": fault}))
                    rc = 1
            answers[mode] = got
            rose = {p: _counter(p) - before[p] for p in before}
            other = "compact" if mode == "mask" else "mask"
            if rose[mode] != filters * (1 + WARM_CALLS) or rose[other] != 0:
                rc = 1
            warm = sorted(walls[1:])
            program_ms = _program_ms(
                lambda: cell.query.build(df, params).collect())
            print(json.dumps({
                "query": name, "mode": mode, "first_call_s": walls[0],
                "warm_ms": [1000 * w for w in walls[1:]],
                "warm_ms_median": 1000 * warm[len(warm) // 2],
                "filter_batches": rose, "program_ms": program_ms,
                "apart_from_numpy": _apart(got, want)}), flush=True)
        pair(True)
        apart = _apart(answers["mask"], answers["compact"])
        print(json.dumps({"query": name,
                          "masked_apart_from_compacted": apart}), flush=True)
        if apart > 1e-12:
            rc = 1
        del df, columns
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
