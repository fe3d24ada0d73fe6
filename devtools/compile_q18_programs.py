"""Off the chip: the join programs of TPC-H Q18 (`benchmarks/queries/q18.py`:
the semi join's count and selection, and the count and expand of the joins
with CUSTOMER and with LINEITEM) lowered from shapes for a described
`v5e:2x2` topology and compiled for one chip, to see what the compiler
refuses and to count their sorts, gathers and scatters before a chip call
is spent:

    python devtools/compile_q18_programs.py [lineitem capacity, default 33554432] [lower]

The operators are the ones the planner makes for the query over a tiny
table; each program is lowered for batches of shapes at the capacities
`tpch_q18_1chip`'s joins run at: the semi join probes with ORDERS
(capacity / 4) against the subquery's HAVING output at LINEITEM's
capacity, whose 23-58 live rows its count gathers into the 1,024-row
bucket (PR 38), and its selection gathers the kept orders into that
bucket; the join with CUSTOMER probes with that 1,024-row output against
capacity / 32 slots; the last join probes with a 1,024-row batch against
LINEITEM.  The two expansions are at the 1,024-row bucket.  With `lower` nothing is compiled: the build counters alone
(seconds, not minutes).  Prints one JSON object a program, as
`devtools/compile_q3_programs.py` does.  A compile is not a chip run: no
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
OUT_BUCKET = 1024


def q18_joins() -> dict:
    """The executed plan's three joins for Q18 over tiny tables: the semi
    join, then the inner joins by their build side's table."""
    from benchmarks.harness import cells, runner
    from spark_rapids_tpu.api.session import TpuSession
    cell = cells.load_cell(ROOT, "tpch_q18_1chip.q18")
    columns = cell.datagen.generate({"scale_factor": 0.002}, 7)
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    df = session.create_dataframe(
        runner.arrow_table(columns, cell.datagen.SCHEMA), num_partitions=1)
    cell.query.build(df, {"quantity": 250}).collect()
    found = {}

    def visit(e):
        if type(e).__name__ == "HashJoinExec":
            build = "customer" if "c_name" in e.children[1].output_names \
                else "lineitem"
            found["semi" if e.how == "left_semi" else build] = e
    session.last_plan.foreach(visit)
    return found


def lower_only(name: str, fn, *args) -> dict:
    from spark_rapids_tpu.ops import carry
    before = carry.lane_move_counts()
    jax.jit(fn).lower(*args)
    after = carry.lane_move_counts()
    return {"program": name,
            **{k: after[k] - before[k] for k in after if after[k] - before[k]}}


def main() -> int:
    args = [a for a in sys.argv[1:] if a != "lower"]
    one = lower_only if "lower" in sys.argv[1:] else q1_tool.compile_one
    rows = int(args[0]) if args else 33_554_432
    joins = q18_joins()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shapes = (("semi", "orders x HAVING output", rows // 4, rows),
              ("customer", "semi output x customer", OUT_BUCKET, rows // 32),
              ("lineitem", "chain x lineitem", OUT_BUCKET, rows))

    def lane(dtype, n):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=chip)
    for which, name, probe_cap, build_cap in shapes:
        join = joins[which]
        probe_node, build_node = join.children

        def batch(node, cap):
            return q1_tool.abstract_batch(
                node.output_names, node.output_types, cap, chip, False)
        probe, build = batch(probe_node, probe_cap), batch(build_node,
                                                           build_cap)
        # the sides the plan pairs with a filter bring their keep flags
        masked = [s is not None for s in join.masked_sources()]
        pkeep = lane(np.bool_, probe_cap) if masked[0] else None
        bkeep = lane(np.bool_, build_cap) if masked[1] else None
        role = "semi_count" if join._selects else "count"
        narrow = OUT_BUCKET if join._selects else 0
        count = one(
            f"HashJoinExec.{role} ({name})",
            lambda b, p, pk, bk: join._count_narrowed(jnp, b, p, pk, bk,
                                                      narrow),
            build, probe, pkeep, bkeep)
        print(json.dumps({**count, "probe_capacity": probe_cap,
                          "build_capacity": build_cap,
                          "narrowed_capacity": narrow or build_cap,
                          "probe_masked": masked[0],
                          "build_masked": masked[1]}), flush=True)
        if join._selects:
            caps = (OUT_BUCKET, tuple(0 for _ in probe.columns))
            select = one(
                f"HashJoinExec.semi ({name})",
                lambda p, c, pk: join._select(jnp, p, c, caps, pk),
                probe, lane(np.int64, probe_cap), pkeep)
            print(json.dumps({**select, "out_capacity": OUT_BUCKET}),
                  flush=True)
            continue
        caps = (OUT_BUCKET,
                tuple(16384 if c.offsets is not None else 0
                      for c in probe.columns),
                tuple(16384 if c.offsets is not None else 0
                      for c in build.columns))
        expand = one(
            f"HashJoinExec.expand ({name})",
            lambda b, p, o, l, c, pk: join._expand_sized(
                jnp, b, p, o, l, c, caps, pk),
            build, probe, lane(np.int32, build_cap),
            lane(np.int32, probe_cap), lane(np.int64, probe_cap), pkeep)
        print(json.dumps({**expand, "out_capacity": OUT_BUCKET}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
