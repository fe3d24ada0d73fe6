"""On the chip: what a selecting join's two ways of handing up a few kept
rows cost, to settle `exec/join.NARROW_SHARE` (PR 38).

    chiprun --timeout 900 -- python devtools/chip_semi_narrow.py [capacity]

At `capacity` slots (default 8,388,608, ORDERS' bucket in `tpch_q18_1chip`)
a batch of Q18's four ORDERS columns (two int64, a date, a double) with
`kept` rows under the flags, spread evenly, goes to the bucket of `kept`
rows two ways: `filter_common.gather_kept` (a prefix sum and a search,
then a bucket-sized gather of each lane) and `compact` at the capacity
then `shrink_batch` (a pass a lane word, then a slice).  Prints one JSON
line a bucket: the median wall ms of each over `REPS` warm calls, each
ended by `block_until_ready` (one program a call, so the wall is the
device's time and a launch).  Off the chip it runs on the CPU backend,
and its times say nothing.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_rapids_tpu import types as t  # noqa: E402
from spark_rapids_tpu.columnar.device import (DeviceBatch,  # noqa: E402
                                              DeviceColumn, shrink_batch)
from spark_rapids_tpu.exec.filter_common import (compact,  # noqa: E402
                                                 gather_kept)

REPS = 7
BUCKETS = (1024, 8192, 65536, 262144, 1048576)
NAMES = ("o_orderkey", "o_custkey", "o_orderdate", "o_totalprice")


def batch(cap: int) -> DeviceBatch:
    i = jnp.arange(cap, dtype=jnp.int64)
    return DeviceBatch([
        DeviceColumn(t.LongType(), data=i * 4),
        DeviceColumn(t.LongType(), data=i % 750_000),
        DeviceColumn(t.DateType(), data=(i % 2400).astype(jnp.int32)),
        DeviceColumn(t.DoubleType(), data=i.astype(jnp.float64) * 0.5)],
        cap, NAMES)


def timed(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ms)


def main() -> int:
    cap = int(sys.argv[1]) if len(sys.argv) > 1 else 8_388_608
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "capacity": cap}), flush=True)
    b = batch(cap)
    for bucket in BUCKETS:
        if bucket * 2 > cap:
            break
        kept = bucket * 3 // 4
        keep = (jnp.arange(cap) % (cap // kept)) == 0
        keep = keep & (jnp.cumsum(keep) <= kept)
        gather = jax.jit(lambda b, k, o=bucket: gather_kept(
            jnp, b, k, o, NAMES))
        slice_ = jax.jit(lambda b, k, o=bucket: shrink_batch(
            compact(jnp, b, k, NAMES), o))
        g, s = gather(b, keep), slice_(b, keep)
        same = all(np.array_equal(np.asarray(x.data)[:kept],
                                  np.asarray(y.data)[:kept])
                   for x, y in zip(g.columns, s.columns))
        print(json.dumps({
            "bucket": bucket, "kept": kept, "share": cap // bucket,
            "gather_ms": round(timed(gather, b, keep), 3),
            "compact_slice_ms": round(timed(slice_, b, keep), 3),
            "same_rows": bool(same)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
