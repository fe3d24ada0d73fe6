"""On the chip: TPC-H Q6 and Q1 at the benchmark's scale, each with its
filter handing up the keep flags (what the plan does: `FilterExec` lies
directly under the aggregate, `TpuHashAggregateExec.masked_source`) and
with the pairing switched off in this process (the filter compacts, as it
did before PR 34), turn and turn about in ONE process on one table:

    chiprun --timeout 1500 -- python devtools/chip_filter_mask.py [seed]

Prints one JSON object a (query, mode) round: the first call's wall, the
warm calls' walls, what `tpu_filter_batches_total{path}` rose by, and the
answer's deviation from the NumPy reference (`benchmarks/queries`); then,
a query, the largest relative difference between the masked and the
compacted answers.  Ends non-zero where an answer is not the reference's,
the two modes differ by more than 1e-12, the counter did not rise by one a
query on the mode's path, or JAX finds no TPU.  One chip, about four
minutes warm.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

WARM_CALLS = 5
ROUNDS = ("mask", "compact", "mask", "compact")
QUERIES = (
    ("q6", "tpch_sf5_1chip", {"year": 1994, "discount": 0.06,
                              "quantity": 24}),
    ("q1", "tpch_q1_1chip", {"delta": 90}),
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


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 3400000901
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chip_filter_mask: no TPU", file=sys.stderr)
        return 2
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from benchmarks.harness import cells, runner
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    paired = TpuHashAggregateExec.masked_source
    session = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
               .get_or_create())
    rc = 0
    for name, config, params in QUERIES:
        cell = cells.load_cell(ROOT, f"{config}.{name}")
        columns = cell.datagen.generate(cell.config, seed)
        df = session.create_dataframe(
            runner.arrow_table(columns, cell.datagen.SCHEMA),
            num_partitions=1)
        want = cell.query.reference(columns, params)
        answers = {}
        for mode in ROUNDS:
            TpuHashAggregateExec.masked_source = paired if mode == "mask" \
                else (lambda self: None)
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
            if rose[mode] != 1 + WARM_CALLS or rose[other] != 0:
                rc = 1
            warm = sorted(walls[1:])
            print(json.dumps({
                "query": name, "mode": mode, "first_call_s": walls[0],
                "warm_ms": [1000 * w for w in walls[1:]],
                "warm_ms_median": 1000 * warm[len(warm) // 2],
                "filter_batches": rose,
                "apart_from_numpy": _apart(got, want)}), flush=True)
        TpuHashAggregateExec.masked_source = paired
        apart = _apart(answers["mask"], answers["compact"])
        print(json.dumps({"query": name,
                          "masked_apart_from_compacted": apart}), flush=True)
        if apart > 1e-12:
            rc = 1
        del df, columns
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv))
