"""On the chip: what a join's count costs when one side is small, the two
ways `exec/join.HashJoinExec` can take, to settle `SMALL_SIDE_SLOTS`.

    python devtools/chip_small_side.py        # on a host with the chip

At `LARGE` slots (LINEITEM's, ORDERS' and CUSTOMER's buckets in
`tpch_q18_1chip`) against a small side of `W` slots, every one live:
`join_kernels.match_flags` (the large side's hashes held against the
small side's, `COMPARE_CHUNK` a step) and `join_kernels.count_matches`
(the large side as the build, sorted with the small side as the probe,
then the two scatters back to the probe's places).  Prints one JSON line
a shape: the median wall ms of each over `REPS` warm calls, each ended by
`block_until_ready` (one program a call, so the wall is the device's time
and a launch), and whether the compare flags exactly the large rows the
sort gives a match.  Off the chip it runs on the CPU backend, and its
times say nothing.
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

from spark_rapids_tpu.ops import join_kernels as jk  # noqa: E402

REPS = 5
LARGE = (33_554_432, 8_388_608, 1_048_576)
WIDTHS = (128, 1024, 4096, 8192)


def timed(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ms)


def sides(large: int, width: int):
    """(large hashes, small hashes): the small side's hashes are drawn
    from the large side's, so that about `width` x 1-7 rows match."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(large + width))
    big = jax.random.bits(k1, (large,), jnp.uint32).astype(jnp.uint64) \
        << np.uint64(32) | jax.random.bits(k2, (large,), jnp.uint32)
    # 1-7 large rows a key, as LINEITEM holds an order's lines
    big = big[(jnp.arange(large) // 4).astype(jnp.int32)]
    small = big[jnp.arange(width, dtype=jnp.int32) * (large // width)]
    return big, small


def main() -> int:
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    compare = jax.jit(lambda big, small, live: jk.match_flags(
        jnp, big, small, live))
    count = jax.jit(lambda big, small, live, blive: jk.count_matches(
        jnp, big, blive, small, live))
    for large in LARGE:
        blive = jnp.ones((large,), bool)
        for width in WIDTHS:
            big, small = sides(large, width)
            live = jnp.ones((width,), bool)
            flags = compare(big, small, live)
            order, lo, counts = count(big, small, live, blive)
            # the large rows some small row matched, by the sort's runs
            runs = np.zeros(large + 1, np.int64)
            lo_h, n_h = np.asarray(lo), np.asarray(counts)
            np.add.at(runs, lo_h, 1)
            np.add.at(runs, lo_h + n_h, -1)
            by_sort = np.zeros(large, bool)
            by_sort[np.asarray(order)] = np.cumsum(runs[:-1]) > 0
            print(json.dumps({
                "large": large, "width": width,
                "compare_ms": round(timed(compare, big, small, live), 3),
                "count_matches_ms": round(
                    timed(count, big, small, live, blive), 3),
                "flagged": int(np.count_nonzero(np.asarray(flags))),
                "same_rows": bool(np.array_equal(np.asarray(flags),
                                                 by_sort))}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
