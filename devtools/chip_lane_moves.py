"""On the chip: that a lane moved by sort pass (`ops/carry.move_lanes`)
equals the gather by the order, elementwise, for every lane type; that the
exchange's contiguous slices and copies (`parallel/alltoall._send_runs`,
`_receive_runs`) equal the gathers they replaced at the mesh step's sizes;
and what a sort pass, a gather, the compaction's prefix sum, the slices and
the copies cost at the bucket sizes of the records.  Run through the chip
tool:

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


N_PARTS = 4


def exchange_case(rng, cap):
    """Destinations and the rows a chip would receive: (pid_key with dead
    rows parked at N_PARTS, counts of a made-up arrival [N_PARTS])."""
    pid = rng.integers(0, N_PARTS, cap).astype(np.int32)
    pid[rng.integers(0, 20, cap) == 0] = N_PARTS
    arrived = rng.integers(cap // 8, cap // 2, N_PARTS).astype(np.int32)
    arrived[1] = 0                      # a peer that sends nothing
    return pid, arrived


def send_both(pid_key, x):
    """A lane's [N_PARTS, cap] send tensor by slices of the pid-sorted
    lane, and by the gather of the source rows that PR 27's exchange made."""
    from spark_rapids_tpu.parallel import alltoall as a2a
    cap = x.shape[0]
    counts, starts = a2a._counts_starts(pid_key, N_PARTS)
    j = jnp.arange(cap, dtype=jnp.int32)
    send_valid = j[None, :] < counts[:, None]
    key = pid_key.astype(jnp.uint32)
    _, (by_pid,) = carry.sort_lanes(jnp, [key], [x], cap, need_order=False)
    sliced = a2a._send_runs(by_pid, starts, send_valid, cap)
    order = carry.stable_argsort(jnp, [key], cap)
    src_row = order[jnp.clip(starts[:, None] + j[None, :], 0, cap - 1)]
    gathered = jnp.where(send_valid, x[src_row], jnp.zeros((), x.dtype))
    return sliced, gathered


def receive_both(arrived, recv):
    """A received [N_PARTS, slot] tensor packed by contiguous copies, by a
    compaction's sort passes, and by the gather of PR 27's exchange."""
    from spark_rapids_tpu.parallel import alltoall as a2a
    n_parts, slot = recv.shape
    flat_rows = n_parts * slot
    valid = (jnp.arange(slot, dtype=jnp.int32)[None, :]
             < arrived[:, None]).reshape(flat_rows)
    starts = cumsum_fast(jnp, arrived) - arrived
    out_live = jnp.arange(flat_rows, dtype=jnp.int32) < jnp.sum(arrived)
    copied = a2a._receive_runs(recv, starts, out_live)
    zero = jnp.zeros((), recv.dtype)
    _, _, (passed,) = carry.compact_rows(jnp, valid, (), flat_rows,
                                         extras=[recv.reshape(flat_rows)])
    passed = jnp.where(out_live, passed, zero)
    ord2 = carry.stable_argsort(jnp, [~valid], flat_rows)
    gathered = jnp.where(out_live, recv.reshape(flat_rows)[ord2], zero)
    return copied, passed, gathered


def check_runs(cap: int) -> bool:
    """Slices and copies against the gathers for every lane type, at the
    step's own sizes: `cap` rows a chip, N_PARTS x cap received.  One
    program a side, so that the sort's signature compiles twice."""
    rng = np.random.default_rng(28)
    pid, arrived = exchange_case(rng, cap)
    lanes = {k: jnp.asarray(v) for k, v in lanes_to_check(rng, cap).items()}

    def differing(a, b):
        return jnp.sum(~same_on_device(a, b))

    def send(p, xs):
        out = {k: send_both(p, x) for k, x in xs.items()}
        return ({k: differing(s, g) for k, (s, g) in out.items()},
                {k: s for k, (s, _) in out.items()})

    def receive(n, rs):
        out = {k: receive_both(n, r) for k, r in rs.items()}
        return {k: (differing(c, g), differing(p, g))
                for k, (c, p, g) in out.items()}
    bad_send, sent = jax.jit(send)(jnp.asarray(pid), lanes)
    bad_recv = jax.jit(receive)(jnp.asarray(arrived), sent)
    ok = True
    for name in lanes:
        bad = (int(bad_send[name]), int(bad_recv[name][0]),
               int(bad_recv[name][1]))
        say(check="runs_equal_gathers", lane=name, rows=cap,
            send_differing=bad[0], receive_differing=bad[1],
            receive_by_pass_differing=bad[2])
        ok = ok and bad == (0, 0, 0)
    return ok


def run_costs(cap: int):
    """The slices of a sorted lane and the copies of a received tensor,
    alone, beside the compaction's passes over the same tensor."""
    from spark_rapids_tpu.parallel import alltoall as a2a
    rng = np.random.default_rng(cap + 1)
    pid, arrived = exchange_case(rng, cap)
    counts = np.bincount(pid, minlength=N_PARTS + 1)[:N_PARTS]
    starts = jnp.asarray((np.cumsum(counts) - counts).astype(np.int32))
    j = np.arange(cap, dtype=np.int32)
    send_valid = jnp.asarray(j[None, :] < counts[:, None])
    arrived = jnp.asarray(arrived)
    flat_rows = N_PARTS * cap
    recv_starts = cumsum_fast(jnp, arrived) - arrived
    out_live = jnp.arange(flat_rows, dtype=jnp.int32) < jnp.sum(arrived)
    slices = jax.jit(lambda x, s, v: a2a._send_runs(x, s, v, cap))
    copies = jax.jit(a2a._receive_runs)
    for name in ("int32", "float64", "bool"):
        x = jnp.asarray(lanes_to_check(rng, cap)[name])
        say(cost=f"send_slices_{name}_s", rows=cap,
            seconds=timed(slices, x, starts, send_valid))
        sent = slices(x, starts, send_valid)
        say(cost=f"receive_copies_{name}_s", rows=flat_rows,
            seconds=timed(copies, sent, recv_starts, out_live))
    # the same tensor packed by a compaction's passes (`sent` is the bool
    # lane's here: one word, so one pass and the prefix sum)
    passes = jax.jit(lambda n, r: receive_both(n, r)[1])
    say(cost="receive_passes_bool_s", rows=flat_rows,
        seconds=timed(passes, arrived, sent, reps=2))


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
    if "--runs" in sys.argv:        # the mesh step's slices and copies alone
        ok = check_runs(4 * M1) and ok
        run_costs(4 * M1)
        return 0 if ok else 1
    for cap in (M1, 16_777_216, 33_554_432):
        costs(cap)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
