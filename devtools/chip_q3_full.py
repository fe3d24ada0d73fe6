"""On the chip, outside the benchmark's timed path: TPC-H Q3 BEFORE its
ORDER BY and its limit, at the full size of `tpch_q3_1chip`, every group
against the NumPy reference:

    chiprun --timeout 1500 -- python devtools/chip_q3_full.py [seed] [scale factor]

Ten rows cannot show a join that drops one line in a hundred; the 57,000
or so groups can.  `benchmarks/queries/q3.grouped_frame` is the cell's
query less `order_by` and `limit`; `q3.grouped` is the same in NumPy.  Both
are sorted by `l_orderkey` on the host; keys, dates and priorities must be
equal and every revenue within `q3.REL_TOLERANCE`.  Prints one JSON object
a parameter set (groups, the largest relative deviation, what the same
reference recomputed in float32 deviates by), then the whole query once a
parameter set with the wall of each call, first call included: times here
are of single calls in a process that also holds the comparison's arrays,
not the cell's.
Ends non-zero on the first group that differs, and where JAX finds no TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PARAMETER_SETS = ({"segment": "BUILDING", "day": 15},
                  {"segment": "AUTOMOBILE", "day": 1},
                  {"segment": "HOUSEHOLD", "day": 31})


def by_key(rows: dict) -> dict:
    order = np.argsort(rows["l_orderkey"], kind="stable")
    return {name: lane[order] for name, lane in rows.items()}


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 3300000001
    scale = float(argv[2]) if len(argv) > 2 else 5.0
    from benchmarks.harness import cells, device, runner
    cell = cells.load_cell(ROOT, "tpch_q3_1chip.q3")
    cell.config["scale_factor"] = scale
    device.require_tpu(1)
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from spark_rapids_tpu.api.session import TpuSession
    q3 = cell.query
    columns = cell.datagen.generate(cell.config, seed)
    table = runner.arrow_table(columns, cell.datagen.SCHEMA)
    df = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
          .get_or_create().create_dataframe(table, num_partitions=1))
    for params in PARAMETER_SETS:
        t0 = time.perf_counter()
        got = by_key(q3.answer(q3.grouped_frame(df, params).collect()))
        wall = time.perf_counter() - t0
        want = by_key(q3.grouped(columns, params))
        low = by_key(q3.grouped(columns, params, np.float32))
        for name in ("l_orderkey", "o_orderdate", "o_shippriority"):
            if not np.array_equal(got[name], want[name]):
                print(json.dumps({"params": params, "differs": name,
                                  "groups": [len(got[name]),
                                             len(want[name])]}))
                return 1
        worst = q3.deviation(got, want)
        print(json.dumps({
            "params": params, "groups": len(want["l_orderkey"]),
            "revenue_rel_worst": worst,
            "float32_reference_rel_worst": q3.deviation(low, want),
            "collect_s": wall}), flush=True)
        if worst > q3.REL_TOLERANCE:
            return 1
    walls = []
    for params in PARAMETER_SETS:
        t0 = time.perf_counter()
        answer = q3.answer(q3.build(df, params).collect())
        walls.append(time.perf_counter() - t0)
        fault = q3.mismatch(answer, q3.reference(columns, params))
        if fault:
            print(json.dumps({"params": params, "fault": fault}))
            return 1
    print(json.dumps({"whole_query_wall_s": walls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
