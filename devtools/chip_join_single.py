"""On the chip: what ONE foreign-key join costs, cold and warm, through the
public API alone, so that the same file times any commit of the engine:

    chiprun --timeout 1500 -- python devtools/chip_join_single.py [seed]

Two joins whose every probe row finds exactly one build row (the case in
which a guess of the probe's capacity for the output is right), each under
an ungrouped sum so that one row comes back:

- `small`: 1,000,000 probe rows against 100,000 keys (the 1,048,576 bucket);
- `orders`: 7,500,000 probe rows against 150,000 keys (the 8,388,608
  bucket: `tpch_q3_1chip`'s first join without its filters).

Prints one JSON object a join: the first call's wall (program load or
compile, upload, and whatever the join asks the device before it expands),
the walls of the calls after it, and the sum against NumPy's.  PR 33 ran it
on its parent (one fused program at the probe's capacity, no sizing fetch)
and on its change (count, a blocking fetch of the sizes, expand at their
buckets); PERF.md has both.  Ends non-zero where a sum differs or JAX finds
no TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

JOINS = (("small", 1_000_000, 100_000), ("orders", 7_500_000, 150_000))
WARM_CALLS = 7


def main(argv) -> int:
    seed = int(argv[1]) if len(argv) > 1 else 3300000701
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chip_join_single: no TPU", file=sys.stderr)
        return 2
    import pyarrow as pa
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    from spark_rapids_tpu.api.session import TpuSession
    session = (TpuSession.builder().config("spark.rapids.sql.enabled", True)
               .get_or_create())
    rng = np.random.default_rng(seed)
    for name, n_probe, n_build in JOINS:
        keys = rng.integers(1, n_build + 1, n_probe).astype(np.int64)
        weight = rng.integers(0, 1000, n_build).astype(np.int64)
        fact = session.create_dataframe(pa.table({
            "fk": pa.array(keys),
            "v": pa.array(np.arange(n_probe, dtype=np.int64))}),
            num_partitions=1)
        dim = session.create_dataframe(pa.table({
            "pk": pa.array(np.arange(1, n_build + 1, dtype=np.int64)),
            "w": pa.array(weight)}), num_partitions=1)
        frame = (fact.join(dim, on=col("fk") == col("pk"), how="inner")
                 .agg(F.sum(col("w")).alias("s"), F.count("*").alias("n")))
        want = (int(weight[keys - 1].sum()), n_probe)
        walls = []
        for _ in range(1 + WARM_CALLS):
            t0 = time.perf_counter()
            out = frame.collect()
            walls.append(time.perf_counter() - t0)
            got = (out.column("s")[0].as_py(), out.column("n")[0].as_py())
            if got != want:
                print(json.dumps({"join": name, "got": got, "want": want}))
                return 1
        warm = sorted(walls[1:])
        print(json.dumps({
            "join": name, "probe_rows": n_probe, "build_rows": n_build,
            "first_call_s": walls[0],
            "warm_ms": [1000 * w for w in walls[1:]],
            "warm_ms_median": 1000 * warm[len(warm) // 2]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
