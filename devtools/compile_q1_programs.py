"""Off the chip: the three programs of TPC-H Q1 (`benchmarks/queries/q1.py`:
`FilterExec`, `TpuHashAggregateExec` in COMPLETE mode, `SortExec`) lowered
from shapes for a described `v5e:2x2` topology and compiled for one chip, to
reckon their memory and count their sorts before a chip call is spent; and,
for comparison, the compaction the filter would run under any other consumer:

    python devtools/compile_q1_programs.py [rows, default 33554432] [general]

The operators are the ones the planner makes for the query over a tiny
table; each one's kernel is then lowered for a batch of shapes at `rows`
capacity, the two `char(1)` keys fixed-width strings (one uint8 lane) or,
with `general`, in the layout of offsets and bytes.  Prints one JSON object
a program: the build counters (`ops/carry.lane_move_counts`), the compile
seconds, the count of `sort(`, `gather(`, `conditional(` and `while(` in the
compiled text, and the compiler's memory figures.  The aggregate's and the
sort's inputs are at the capacity the operator below them hands up.  A
compile is not a chip run: no time here is a device time.

Since PR 34 the plan pairs Q1's filter with its aggregate
(`TpuHashAggregateExec.masked_source`): the filter's program is
`FilterExec.mask` (`filters_masked` 1, no `sort(`, an output of one bool
lane and a count) and the aggregate takes the scan's batch and the keep
flags.  `FilterExec (compaction)` is what the same filter costs a consumer
that reads rows by position (ten passes).

The aggregate's `sort(` stays 45 with the dense arm in the program (PR 32):
Q1's two `char(1)` keys send `_group_reduce` down the dense arm, which holds
the sort arm behind a `conditional(` for a batch with more groups than it
walks, so the text keeps that branch's passes and `sort_passes` at build
counts them.  That the dense arm is there shows as `grouped_dense` 1, one
`conditional(` and two `while(` (the walk of the codes, the walk of the
groups); that it RAN shows only on the chip: no
`jit_TpuHashAggregateExec.complete/sort.N` among a traced run's device
operations.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_enable_x64", True)
from jax.sharding import SingleDeviceSharding  # noqa: E402

from spark_rapids_tpu import types as t  # noqa: E402
from spark_rapids_tpu.columnar.device import (  # noqa: E402
    DEFAULT_CHAR_BUCKETS, DeviceBatch, DeviceColumn, bucket_for)

OPCODES = ("sort", "gather", "conditional", "while")


def abstract_batch(names, dtypes, cap: int, sharding, fixed: bool):
    """A batch of shapes: every column a data and a validity lane at
    capacity `cap`; a string is one uint8 lane (fixed width 1) or offsets
    and a byte a row in the char bucket that holds them."""
    def lane(dtype, n=cap):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)
    cols = []
    for dt in dtypes:
        if isinstance(dt, t.StringType) and fixed:
            cols.append(DeviceColumn.fixed_string(
                dt, lane(np.uint8), lane(np.bool_), 1))
        elif isinstance(dt, t.StringType):
            cols.append(DeviceColumn(
                dt, data=lane(np.uint8, bucket_for(cap,
                                                   DEFAULT_CHAR_BUCKETS)),
                validity=lane(np.bool_), offsets=lane(np.int32, cap + 1)))
        else:
            cols.append(DeviceColumn(dt, data=lane(t.to_np_dtype(dt)),
                                     validity=lane(np.bool_)))
    rows = jax.ShapeDtypeStruct((), np.int32, sharding=sharding)
    return DeviceBatch(cols, rows, list(names))


def q1_operators():
    """The executed plan's operators for Q1 over a tiny table."""
    from benchmarks.datagen import tpch_lineitem_q1 as gen
    from benchmarks.harness import runner
    from benchmarks.queries import q1
    from spark_rapids_tpu.api.session import TpuSession
    columns = gen.generate({"scale_factor": 0.001}, 7)
    session = TpuSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    df = session.create_dataframe(
        runner.arrow_table(columns, gen.SCHEMA), num_partitions=1)
    q1.build(df, {"delta": 90}).collect()
    found = {}
    session.last_plan.foreach(
        lambda e: found.setdefault(type(e).__name__, e))
    return found


def compile_one(name: str, fn, *args) -> dict:
    from spark_rapids_tpu.ops import carry
    before = carry.lane_move_counts()
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    after = carry.lane_move_counts()
    lower_s = time.perf_counter() - t0
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0 - lower_s
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    out_caps = sorted({int(leaf.shape[0]) for leaf in
                       jax.tree_util.tree_leaves(lowered.out_info)
                       if leaf.shape})
    return {"program": name,
            **{k: after[k] - before[k] for k in after},
            "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
            **{op: len(re.findall(rf"\b{op}\(", text)) for op in OPCODES},
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "held_gb": (mem.argument_size_in_bytes
                        + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes) / 1e9,
            "output_capacities": out_caps}


def main() -> int:
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 33_554_432
    fixed = "general" not in sys.argv[2:]
    ops = q1_operators()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    flt, agg, srt = (ops[k] for k in (
        "FilterExec", "TpuHashAggregateExec", "SortExec"))

    def batch_like(node, cap):
        return abstract_batch(node.output_names, node.output_types, cap,
                              chip, fixed)

    scan = flt.children[0]
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(np.shape(p), np.asarray(p).dtype,
                                       sharding=chip), flt._params)
    keep = ()
    if agg.masked_source() is flt:
        print(json.dumps(compile_one(
            "FilterExec.mask",
            lambda b, ps: flt._compute_mask(jnp, b, params=ps),
            batch_like(scan, rows), params)), flush=True)
        keep = (jax.ShapeDtypeStruct((rows,), np.bool_, sharding=chip),)
    print(json.dumps(compile_one(
        "FilterExec (compaction)",
        lambda b, ps: flt._compute(jnp, b, params=ps),
        batch_like(scan, rows), params)), flush=True)
    agg_line = compile_one(
        "TpuHashAggregateExec.complete",
        lambda b, *keep: agg._evaluate_batch(
            jnp, agg._update_batch(jnp, b, *keep)),
        batch_like(flt, rows), *keep)
    print(json.dumps(agg_line), flush=True)
    print(json.dumps(compile_one(
        "SortExec", lambda b: srt._sort_batch(jnp, b),
        batch_like(agg, max(agg_line["output_capacities"])))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
