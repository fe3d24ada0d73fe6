"""On the chip, outside the benchmark's timed path: TPC-H Q18 BEFORE its
ORDER BY and its limit, at the full size of `tpch_q18_1chip`, every group
against the NumPy reference:

    chiprun --timeout 1800 -- python devtools/chip_q18_full.py [seed] [scale factor]

`benchmarks/queries/q18.grouped_frame` is the cell's query less `order_by`
and `limit`; `q18.grouped` is the same in NumPy.  At SF5 the limit of 100
does not bind (23-58 large orders), so the groups are the answer's rows in
another order; the tool says how many there are a threshold, and holds them
all the same, sorted by `o_orderkey` on the host, through `q18.mismatch`
(names, keys, dates and sums exact, the price to the cent).  It then shows
that the comparison bites at this size: the reference recomputed with
`o_totalprice` kept in float32, and with one line of a large order dropped
before the last join, must each FAIL `mismatch` against the engine's
answer.  Last the whole query once a threshold with the wall of each call.
Prints one JSON object a step; ends non-zero on the first group that
differs, where a crippled reference passes, and where JAX finds no TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

THRESHOLDS = (312, 313, 314, 315)


def by_key(rows: dict) -> dict:
    order = np.argsort(rows["o_orderkey"], kind="stable")
    return {name: lane[order] for name, lane in rows.items()}


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 3700000001
    scale = float(argv[2]) if len(argv) > 2 else 5.0
    from benchmarks.harness import cells, device, runner
    cell = cells.load_cell(ROOT, "tpch_q18_1chip.q18")
    cell.config["scale_factor"] = scale
    device.require_tpu(1)
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from spark_rapids_tpu.api.session import TpuSession
    q18 = cell.query
    columns = cell.datagen.generate(cell.config, seed)
    table = runner.arrow_table(columns, cell.datagen.SCHEMA)
    df = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
          .get_or_create().create_dataframe(table, num_partitions=1))
    starts, _ = q18.order_sums(columns)
    for quantity in THRESHOLDS:
        params = {"quantity": quantity}
        t0 = time.perf_counter()
        got = by_key(q18.answer(q18.grouped_frame(df, params).collect()))
        wall = time.perf_counter() - t0
        want = by_key(q18.grouped(columns, params))
        fault = q18.mismatch(got, want)
        # a line of the first large order, lost before the last join
        first = int(np.searchsorted(columns["l_orderkey"],
                                    want["o_orderkey"][0]))
        crippled = {
            "float32_price": q18.mismatch(got, by_key(q18.grouped(
                columns, params, price_dtype=np.float32))),
            "dropped_line": q18.mismatch(got, by_key(q18.grouped(
                columns, params, drop_line=first)))}
        print(json.dumps({
            "params": params, "groups": len(want["o_orderkey"]),
            "limit_binds": len(want["o_orderkey"]) > q18.LIMIT,
            "lines": int(want["sum_quantity"].shape[0] and np.sum(
                np.diff(np.append(starts, len(columns["l_orderkey"])))[
                    np.searchsorted(columns["l_orderkey"][starts],
                                    want["o_orderkey"])])),
            "name_bytes": int(sum(len(s) for s in want["c_name"])),
            "fault": fault, "crippled_references_fail": crippled,
            "collect_s": wall}), flush=True)
        if fault or not all(crippled.values()):
            return 1
    walls = []
    for quantity in THRESHOLDS:
        params = {"quantity": quantity}
        t0 = time.perf_counter()
        answer = q18.answer(q18.build(df, params).collect())
        walls.append(time.perf_counter() - t0)
        fault = q18.mismatch(answer, q18.reference(columns, params))
        if fault:
            print(json.dumps({"params": params, "fault": fault}))
            return 1
    print(json.dumps({"whole_query_wall_s": walls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
