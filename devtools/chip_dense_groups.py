"""On the chip: what the arms of a grouped `exec/aggregate._group_reduce`
cost at TPC-H Q1's shape (33,554,432 slots, two `char(1)` keys, seven
float64 sums and four counts over five distinct lanes): the numbers behind
`_DENSE_GROUPS_MAX` and `_DENSE_WALK`.  Run through the chip tool:

    chiprun --timeout 1500 -- python devtools/chip_dense_groups.py [rows]

Prints one JSON object a line: the discovery (`_distinct_codes`); the dense
arm (`_reduce_dense`) at 4, 16, 64 and 256 groups as the tree walks them, and
at 4 and 64 groups with other numbers of groups a pass (1 is a loop over
the groups that are there, 64 a walk vectorised over every slot); the sort
arm (`_sort_segment`); each the median of five calls after one that
compiles; and that the dense arm's answer is the sort arm's.  Exits
non-zero when they differ.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402

from spark_rapids_tpu import types as t  # noqa: E402
from spark_rapids_tpu.columnar.device import DeviceColumn  # noqa: E402
from spark_rapids_tpu.exec import aggregate as agg  # noqa: E402

#: Q1's update ops over (quantity, price, disc_price, charge, discount)
LANES = [0, 1, 2, 3, 0, 0, 1, 1, 4, 4, 0]
OPS = ["sum", "sum", "sum", "sum", "sum", "countvalid", "sum", "countvalid",
       "sum", "countvalid", "countvalid"]


def say(**kw):
    print(json.dumps(kw), flush=True)


def timed(fn, *args):
    """(median seconds of five calls after the first, the last answer)."""
    out = jax.block_until_ready(fn(*args))
    seconds = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return float(np.median(seconds)), out


@functools.partial(jax.jit, static_argnames=("cap",))
def make_batch(key, cap, first_values, second_values):
    """Five float64 lanes and two one-byte string keys that form
    `first_values x second_values` groups; every lane with a validity."""
    ks = jax.random.split(key, 7)
    ones = jnp.ones((cap,), bool)
    values = [DeviceColumn(t.DOUBLE, validity=ones, data=jnp.round(
        jax.random.uniform(k, (cap,), jnp.float64, 1.0, 105000.0), 2))
        for k in ks[:5]]

    def char1(k, n):
        word = jax.random.randint(k, (cap,), 65, 65 + n).astype(jnp.uint8)
        return DeviceColumn.fixed_string(t.STRING, word, ones, 1)
    return [char1(ks[5], first_values), char1(ks[6], second_values)], values


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("no TPU: this probe times the chip", file=sys.stderr)
        return 2
    cap = int(sys.argv[1]) if len(sys.argv) > 1 else 33_554_432
    live = jnp.arange(cap, dtype=jnp.int32) < np.int32(cap * 0.894)
    out_cap = 262_144
    say(device=jax.devices()[0].device_kind, rows=cap,
        dense_groups_max=agg._DENSE_GROUPS_MAX)

    def columns(values):
        return [values[i] for i in LANES]

    @jax.jit
    def discover(keys):
        code = agg._key_code(jnp, keys, live)
        return agg._distinct_codes(jnp, code, agg._DENSE_GROUPS_MAX)

    def dense_walking(groups_a_pass):
        @jax.jit
        def dense(keys, values):
            code = agg._key_code(jnp, keys, live)
            codes, found = agg._distinct_codes(jnp, code,
                                               agg._DENSE_GROUPS_MAX)
            return agg._reduce_dense(jnp, keys, columns(values), OPS, cap,
                                     code, codes, found, out_cap), found

        def run(*args):
            was, agg._DENSE_WALK = agg._DENSE_WALK, groups_a_pass
            try:
                return dense(*args)
            finally:
                agg._DENSE_WALK = was
        return run

    @jax.jit
    def sort_arm(keys, values):
        return agg._sort_segment(jnp, keys, columns(values), OPS, cap, live,
                                 False)

    failed = 0
    for first, second in ((2, 2), (4, 4), (8, 8), (16, 16)):
        keys, values = make_batch(jax.random.PRNGKey(first), cap, first,
                                  second)
        seconds, (_, found) = timed(discover, keys)
        say(arm="discovery", groups=int(found), ms=seconds * 1e3)
        seconds, ((dkeys, dvalues), found) = timed(
            dense_walking(agg._DENSE_WALK), keys, values)
        say(arm="dense", groups=int(found), groups_a_pass=agg._DENSE_WALK,
            ms=seconds * 1e3)
        if first not in (2, 8):
            continue
        for groups_a_pass in (1, 4, 16, 64):
            try:
                seconds, _ = timed(dense_walking(groups_a_pass), keys,
                                   values)
            except Exception as e:  # the compiler's or the allocator's no
                say(arm="dense", groups_a_pass=groups_a_pass,
                    error=repr(e)[:300])
                continue
            say(arm="dense", groups=int(found), groups_a_pass=groups_a_pass,
                ms=seconds * 1e3)
        if first != 2:
            continue
        seconds, (skeys, svalues, n) = timed(sort_arm, keys, values)
        say(arm="sort", groups=int(n), ms=seconds * 1e3)
        n = int(n)
        for a, b in zip(dkeys, skeys):
            failed += not bool(jnp.all(a.word[:n] == b.word[:n]))
        worst = 0.0
        for a, b in zip(dvalues, svalues):
            x, y = np.asarray(a.data[:n]), np.asarray(b.data[:n])
            worst = max(worst, float(np.max(np.abs(x - y)
                                            / np.maximum(np.abs(y), 1.0))))
        failed += worst > 1e-12
        say(check="dense against sort", groups=n, worst_relative=worst,
            failed=failed)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
