#!/usr/bin/env python3
"""Whose are the stalls?  A short host-led cell (`.q6`, `.q1`) loses
0.29% of a window's rate to every query that takes 125 ms and not 15.

    python devtools/chip_stalls.py freeze [--seconds 30]
    python devtools/chip_stalls.py stream --workload <cell> --seed <n> \
        [--seconds 365] [--root .chip_parent]

``freeze`` runs no line of the engine: a thread that sleeps 2 ms a turn
beside a main thread that (1) sleeps 1 ms a turn with JAX not imported,
(2) sleeps with the TPU initialised and idle, (3) launches one jitted
reduction over a resident array and reads it back, in a closed loop.  It
lists every turn and every wake-up that came more than 20 ms late: where
both threads stand still at once with no JAX in the process, the machine
froze it.

``stream`` asks one long stream of a cell's queries through the
benchmark's own ``Bench.ask`` (no reference check after it) and lists the
queries over 18.5 ms with the collector's full passes among them; a
watchdog thread that sleeps 10 ms a turn writes every thread's stack once
a query is past 40 ms, and when it got to run (a watchdog that wakes at
+120 ms stood still too).  ``--root`` runs another checkout (the parent's,
from ``git archive``) with this script: a stall that both sides show at
the same rate is not the change's.

One process, chip only.  Each mode's last line of standard output is one
JSON object; ``stream`` keeps the stacks in ``chiprun_out/stalls/``.
"""

import argparse
import faulthandler
import gc
import json
import os
import statistics
import sys
import threading
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LATE_S = 0.02


def _phase(name: str, seconds: float, turn, t_zero: float) -> dict:
    """`turn` in a closed loop beside a sleeping observer thread."""
    late_wakes, late_turns, stop = [], [], [False]

    def observe():
        last = time.perf_counter()
        while not stop[0]:
            time.sleep(0.002)
            now = time.perf_counter()
            if now - last > LATE_S:
                late_wakes.append(
                    [round(now - t_zero, 3), round((now - last) * 1e3, 1)])
            last = now

    observer = threading.Thread(target=observe, daemon=True)
    observer.start()
    turns = 0
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        a = time.perf_counter()
        turn()
        b = time.perf_counter()
        turns += 1
        if b - a > LATE_S:
            late_turns.append(
                [round(b - t_zero, 3), round((b - a) * 1e3, 1)])
    stop[0] = True
    observer.join()
    return {"phase": name, "seconds": seconds, "turns": turns,
            "main_over_20ms": late_turns, "observer_over_20ms": late_wakes}


def freeze(args) -> dict:
    t_zero = time.perf_counter()
    phases = [_phase("no_jax_sleeping", args.seconds,
                     lambda: time.sleep(0.001), t_zero)]
    import jax
    import jax.numpy as jnp
    import numpy as np
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("chip_stalls: JAX found no TPU")
    x = jax.device_put(np.arange(33554432, dtype=np.float32) % 977.0)

    @jax.jit
    def reduce12(x, k):
        s = jnp.float32(0)
        for j in range(12):
            s = s + jnp.sum(jnp.where(x > k + j, x * (k + j), 0.0))
        return s

    np.asarray(reduce12(x, 1.0))
    phases.append(_phase("tpu_idle_sleeping", args.seconds,
                         lambda: time.sleep(0.001), t_zero))
    k = [0.0]

    def launch():
        k[0] += 1.0
        np.asarray(reduce12(x, k[0] % 500.0))

    phases.append(_phase("plain_jax_closed_loop", args.seconds * 1.5,
                         launch, t_zero))
    return {"device": str(device), "phases": phases}


def stream(args) -> dict:
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmarks.harness import cells, device, runner
    cell = cells.load_cell(root, args.workload)
    devices = device.require_tpu(cell.chips)
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    bench = runner.Bench(cell, args.seed, trace=False)
    bench.load()
    bench.warm_up(devices)
    if bench.problems:
        raise SystemExit("the warm-up failed: " + bench.problems[0])
    out = os.path.join(HERE, "chiprun_out", "stalls")
    os.makedirs(out, exist_ok=True)
    tag = f"{args.workload}-{args.seed}-{os.path.basename(root)}"
    stacks = open(os.path.join(out, tag + ".stacks"), "w")
    now = {"i": -1, "t0": None, "dumped": -1, "stop": False}

    def watchdog():
        while not now["stop"]:
            time.sleep(0.01)
            t0, i = now["t0"], now["i"]
            if t0 is not None and i != now["dumped"] and \
                    time.perf_counter() - t0 > 0.04:
                now["dumped"] = i
                stacks.write(f"\n==== query {i} at "
                             f"+{(time.perf_counter() - t0) * 1e3:.1f} ms\n")
                stacks.flush()
                faulthandler.dump_traceback(stacks, all_threads=True)
                stacks.flush()

    full_passes, started = [], {}

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started["t"] = time.perf_counter()
        else:
            full_passes.append([now["i"], round(
                (time.perf_counter() - started["t"]) * 1e3, 1)])

    gc.callbacks.append(on_gc)
    threading.Thread(target=watchdog, daemon=True).start()
    times, ends = [], []
    t_open = time.perf_counter()
    while time.perf_counter() - t_open < args.seconds:
        now["i"] = len(times)
        now["t0"] = time.perf_counter()
        q = bench.ask(next(bench.stream))
        now["t0"] = None
        if q.error:
            raise SystemExit("a query failed: " + q.error)
        bench.asked.append(q)   # the heap grows as the benchmark's does
        times.append(q.ms)
        ends.append(time.perf_counter() - t_open)
    window_s = time.perf_counter() - t_open
    now["stop"] = True
    gc.callbacks.remove(on_gc)
    stacks.close()
    collected = {i for i, _ in full_passes}
    slices = []
    for w in range(int(window_s // 45)):
        n = sum(1 for e in ends if 45 * w <= e < 45 * (w + 1))
        slices.append(round(n / 45.0, 3))
    return {"workload": cell.name, "seed": args.seed, "root": args.root,
            "queries": len(times), "window_s": window_s,
            "answer_ms_p50": statistics.median(times),
            "answer_ms_mean": sum(times) / len(times),
            "queries_per_s_by_45s": slices,
            "full_collections": full_passes,
            "over_18_5_ms": [
                {"query": i, "ms": round(ms, 1), "at_s": round(ends[i], 2),
                 "full_collection": i in collected}
                for i, ms in enumerate(times) if ms > 18.5]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("freeze", "stream"))
    ap.add_argument("--workload", default="tpch_sf5_1chip.q6")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--root", default=HERE)
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = 30.0 if args.mode == "freeze" else 365.0
    doc = {"freeze": freeze, "stream": stream}[args.mode](args)
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
