"""Off the chip: the four-chip cell's mesh step (`DistributedAggregate` of
Q18's subquery: int64 key, float64 sum) lowered from shapes for a described
`v5e:2x2` topology and compiled by the TPU compiler, to count what is in it
before a chip call is spent:

    python devtools/compile_mesh_step.py [rows a chip, default 4194304]

Prints one JSON object: the build counters (`ops/carry.lane_move_counts`,
`parallel/alltoall.wire_byte_counts`), the compile seconds, the count of
`gather(`, `sort(`, `all-to-all(`, `dynamic-slice(` and
`dynamic-update-slice(` in the compiled text, and the compiler's memory
figures for one chip.  A compile is not a chip run: no time here is a
device time.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from spark_rapids_tpu import types as t  # noqa: E402
from spark_rapids_tpu.columnar.device import DeviceBatch, DeviceColumn  # noqa: E402

SCHEMA = (("l_orderkey", t.LONG), ("l_quantity", t.DOUBLE))
OPCODES = ("gather", "sort", "all-to-all", "dynamic-slice",
           "dynamic-update-slice")


def main() -> int:
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 4_194_304
    from jax.experimental import topologies
    from spark_rapids_tpu.expr.aggregates import AggregateExpression, Sum
    from spark_rapids_tpu.expr.core import AttributeReference as A
    from spark_rapids_tpu.ops import carry
    from spark_rapids_tpu.parallel import DistributedAggregate
    from spark_rapids_tpu.parallel.alltoall import wire_byte_counts

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    def lane(dtype):
        return jax.ShapeDtypeStruct((4, rows), dtype, sharding=sharding)
    cols = [DeviceColumn(dt, data=lane(t.to_np_dtype(dt)),
                         validity=lane(np.bool_)) for _, dt in SCHEMA]
    stacked = DeviceBatch(
        cols, jax.ShapeDtypeStruct((4,), np.int32, sharding=sharding),
        [n for n, _ in SCHEMA])
    dagg = DistributedAggregate(
        grouping=[A("l_orderkey")],
        aggregates=[AggregateExpression(Sum(A("l_quantity")), "s")],
        in_names=[n for n, _ in SCHEMA], in_types=[d for _, d in SCHEMA],
        mesh=mesh)
    step = jax.shard_map(dagg._step, mesh=mesh, in_specs=P("data"),
                         out_specs=P("data"), check_vma=False)

    before = {**carry.lane_move_counts(), **wire_byte_counts()}
    t0 = time.perf_counter()
    lowered = jax.jit(step).lower(stacked)
    after = {**carry.lane_move_counts(), **wire_byte_counts()}
    lower_s = time.perf_counter() - t0
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0 - lower_s
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    out = {"rows_a_chip": rows,
           **{k: after[k] - before[k] for k in after},
           "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
           **{op: len(re.findall(rf"\s{re.escape(op)}\(", text))
              for op in OPCODES},
           "argument_gb": mem.argument_size_in_bytes / 1e9,
           "output_gb": mem.output_size_in_bytes / 1e9,
           "temp_gb": mem.temp_size_in_bytes / 1e9}
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
