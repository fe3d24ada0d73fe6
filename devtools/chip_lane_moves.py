"""On the chip: that a lane moved by sort pass (`ops/carry.move_lanes`)
equals the gather by the order, elementwise, for every lane type; and what
a sort pass, a gather and the compaction's prefix sum cost at the bucket
sizes of the records.  Run through the chip tool:

    chiprun --timeout 900 -- python devtools/chip_lane_moves.py

Prints one JSON object a line; exits non-zero when a move differs.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp  # noqa: E402

from spark_rapids_tpu.ops import carry  # noqa: E402
from spark_rapids_tpu.ops.scan import cumsum_fast  # noqa: E402

M1 = 1_048_576


def say(**kw):
    print(json.dumps(kw), flush=True)


def lanes_to_check(rng, n):
    money = np.round(rng.uniform(-9.9e12, 9.9e12, n), 2)   # decimal(15,2)
    small = np.round(rng.uniform(0, 0.1, n), 2)
    f64 = np.where(rng.integers(0, 2, n) == 0, money, small)
    f64[:8] = [np.nan, 0.0, -0.0, np.inf, -np.inf, 1.0 + 2.0**-40,
               104949.50, 0.07]
    f32 = rng.standard_normal(n).astype(np.float32)
    f32[:5] = [np.nan, 0.0, -0.0, np.inf, -np.inf]
    return {
        "float64": f64, "float32": f32,
        "int64": rng.integers(-2**62, 2**62, n),
        "uint64": rng.integers(0, 2**63, n).astype(np.uint64) * np.uint64(2),
        "int32": rng.integers(-2**31, 2**31 - 1, n).astype(np.int32),
        "int8": rng.integers(-128, 127, n).astype(np.int8),
        "bool": rng.integers(0, 2, n).astype(bool),
    }


def same_on_device(a, b):
    """Elementwise equality a chip can state of its own values: equal or
    both NaN, and the same sign bit (a double has no bit view there)."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        def negative(v):        # the sign of a zero shows in its inverse
            return (v < 0) | ((v == 0) & (1.0 / v < 0))
        eq = (a == b) | (jnp.isnan(a) & jnp.isnan(b))
        return eq & (negative(a) == negative(b))
    return a == b


def check_moves() -> bool:
    rng = np.random.default_rng(26)
    order = rng.permutation(M1).astype(np.int32)
    rank = np.empty_like(order)
    rank[order] = np.arange(M1, dtype=np.int32)
    ok = True
    for name, x in lanes_to_check(rng, M1).items():
        def both(r, o, a):
            moved = carry.move_lanes(jnp, r, [a])[0]
            gathered = a[o]
            return (jnp.sum(~same_on_device(moved, gathered)),
                    moved, gathered)
        bad, moved, gathered = jax.jit(both)(
            jnp.asarray(rank), jnp.asarray(order), jnp.asarray(x))
        bad = int(bad)
        # what the host sees of both (a double crosses as the chip's pair)
        m, g = np.asarray(moved), np.asarray(gathered)
        host_same = bool(np.array_equal(m, g, equal_nan=x.dtype.kind == "f"))
        say(check="move_equals_gather", lane=name, rows=M1, differing=bad,
            host_arrays_equal=host_same)
        ok = ok and bad == 0 and host_same
    return ok


def timed(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def costs(cap: int):
    rng = np.random.default_rng(cap)
    rank = jnp.asarray(rng.permutation(cap).astype(np.int32))
    x = jnp.asarray(rng.integers(-2**31, 2**31 - 1, cap).astype(np.int32))
    keep = jnp.asarray(rng.integers(0, 50, cap) == 0)
    one_pass = jax.jit(lambda r, a: carry.move_lanes(jnp, r, [a])[0])
    gather = jax.jit(lambda r, a: a[r])
    prefix = jax.jit(lambda k: carry.compaction_rank(jnp, k, cap))
    f64 = jnp.asarray(rng.standard_normal(cap))
    say(cost="sort_pass_int32_s", rows=cap, seconds=timed(one_pass, rank, x))
    say(cost="move_float64_s", rows=cap, seconds=timed(one_pass, rank, f64))
    say(cost="compaction_rank_s", rows=cap, seconds=timed(prefix, keep))
    say(cost="gather_int32_s", rows=cap, seconds=timed(gather, rank, x,
                                                       reps=1))


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 2
    say(device=dev.device_kind, platform=dev.platform)
    ok = check_moves()
    for cap in (M1, 16_777_216, 33_554_432):
        costs(cap)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
