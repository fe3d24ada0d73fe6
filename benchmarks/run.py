#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It runs on the machine it is
started on, and ends non-zero, with no result, when JAX finds no TPU or
another number of chips than the cell asks for; no switch relaxes that.
The cell's files are found by the names in ``BENCHMARK.json`` (see
``benchmarks/README.md``).  Lines of facts come first; the last line of
standard output is the result object of the contract, and nothing else
goes there.
"""

import time

T_START = time.perf_counter()   # set-up counts from here, imports included

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmarks.harness import cells, device, runner
    cell = cells.load_cell(ROOT, args.workload)
    devices = device.require_tpu(cell.chips)

    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from spark_rapids_tpu.plugin import compilation_cache_dir
    # the program keeps its persistent compile cache where
    # JAX_COMPILATION_CACHE_DIR says, else at <checkout>/.jax_cache: a
    # fixed path inside the checkout, which is what the contract asks
    runner.emit(workload=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, device=device.device_facts(),
                compile_cache=compilation_cache_dir(),
                import_s=time.perf_counter() - T_START)
    line = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, devices)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
