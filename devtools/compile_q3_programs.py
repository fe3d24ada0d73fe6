"""Off the chip: the join programs of TPC-H Q3 (`benchmarks/queries/q3.py`:
`HashJoinExec`'s count and expand, for the join of ORDERS with the
segment's customers and for the join of LINEITEM with the open orders)
lowered from shapes for a described `v5e:2x2` topology and compiled for one
chip, to see what the compiler refuses and to count their sorts, gathers
and scatters before a chip call is spent:

    python devtools/compile_q3_programs.py [lineitem capacity, default 33554432]

The operators are the ones the planner makes for the query over a tiny
table; each join's two programs are lowered for batches of shapes at the
deployment's capacities (the probe at its table's bucket; the build and the
output at the buckets `tpch_q3_1chip`'s joins settle in: 1,048,576 /
1,048,576 for the first, 1,048,576 / 262,144 for the second; the same
shares of another lineitem capacity where one is asked for).
Prints one JSON object a program: the build counters
(`ops/carry.lane_move_counts`, `join_cols_gathered` among them), which sides come up masked (PR 36:
`HashJoinExec.masked_sources`; the keep flags are then one more bool lane
of the program, at the side's capacity), the compile seconds, the count of `sort(`, `gather(`, `scatter(` and `while(`
in the compiled text, and the compiler's memory figures (which are no guide
to the allocator's peak: PERF.md, PR 31).  A compile is not a chip run: no
time here is a device time.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "devtools"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE", "1")

import compile_q1_programs as q1_tool  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

q1_tool.OPCODES = ("sort", "gather", "scatter", "while")


def q3_joins():
    """The executed plan's two joins for Q3 over tiny tables, the one
    under LINEITEM first."""
    from benchmarks.harness import cells, runner
    from spark_rapids_tpu.api.session import TpuSession
    cell = cells.load_cell(ROOT, "tpch_q3_1chip.q3")
    columns = cell.datagen.generate({"scale_factor": 0.002}, 7)
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    df = session.create_dataframe(
        runner.arrow_table(columns, cell.datagen.SCHEMA), num_partitions=1)
    cell.query.build(df, {"segment": "BUILDING", "day": 15}).collect()
    found = []
    session.last_plan.foreach(
        lambda e: found.append(e)
        if type(e).__name__ == "HashJoinExec" else None)
    return found


def main() -> int:
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 33_554_432
    lineitem_join, orders_join = q3_joins()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    side = rows // 32        # 1,048,576 at the deployment's size
    shapes = (("orders x customer", orders_join, rows // 4, side, side),
              ("lineitem x open orders", lineitem_join, rows, side,
               max(side // 4, 1024)))
    for name, join, probe_cap, build_cap, out_cap in shapes:
        probe_node, build_node = join.children

        def batch(node, cap):
            return q1_tool.abstract_batch(
                node.output_names, node.output_types, cap, chip, False)
        probe, build = batch(probe_node, probe_cap), batch(build_node,
                                                           build_cap)

        def lane(dtype, n):
            return jax.ShapeDtypeStruct((n,), dtype, sharding=chip)
        # the sides the plan pairs with a filter bring their keep flags
        masked = [s is not None for s in join.masked_sources()]
        pkeep = lane(np.bool_, probe_cap) if masked[0] else None
        bkeep = lane(np.bool_, build_cap) if masked[1] else None
        count = q1_tool.compile_one(
            f"HashJoinExec.count ({name})",
            lambda b, p, pk, bk: join._count(jnp, b, p, False, pk, bk),
            build, probe, pkeep, bkeep)
        print(json.dumps({**count, "probe_capacity": probe_cap,
                          "build_capacity": build_cap,
                          "probe_masked": masked[0],
                          "build_masked": masked[1]}), flush=True)
        caps = (out_cap, (0,) * len(probe.columns),
                (0,) * len(build.columns))
        expand = q1_tool.compile_one(
            f"HashJoinExec.expand ({name})",
            lambda b, p, o, l, c, pk: join._expand_sized(
                jnp, b, p, o, l, c, caps, pk),
            build, probe, lane(np.int32, build_cap),
            lane(np.int32, probe_cap), lane(np.int64, probe_cap), pkeep)
        print(json.dumps({**expand, "out_capacity": out_cap}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
