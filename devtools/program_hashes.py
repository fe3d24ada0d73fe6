"""Off the chip: which programs a benchmark cell builds, and the content
hash of each one's lowered text, so that two checkouts can be held to
"these cells run the parent's programs, byte for byte":

    python devtools/program_hashes.py --against <parent checkout>
    python devtools/program_hashes.py --cell tpch_q3_1chip.q3 [--root <checkout>]

The first form runs every cell of `BENCHMARK.json` at a tiny scale, once in
this checkout and once in the other, each run a process of its own (one CPU
device for a one-chip cell, four virtual ones for the four-chip cell), and
compares the lists.  It exits 1 when a cell named in `MUST_EQUAL` builds
another list of programs than the parent does; the other cells' differences
are printed and expected (`tpch_q3_1chip.q3` in PR 36: its three filters
hand up a mask to its joins, whose programs take the flags;
`tpch_q18_1chip.q18` in PR 37, where the parent is given this checkout's
benchmark files: its semi join's selection became a program, and in PR
38: its count and its selection work at their live rows' buckets).  A cell the
parent's `BENCHMARK.json` does not have is run here alone.

The second form prints one cell's list as JSON lines: the operator kind,
the head of the program's key, the hash of its input shapes and the hash of
its lowered StableHLO (`obs/compileprof.hlo_key`), in build order.

A cell runs through the benchmark's own harness (`benchmarks/harness`:
`Bench.load`, `warm_up`, then three more parameter sets of the seed's
stream) over a copy of `benchmarks/` whose configuration has `scale_factor`
cut to `TINY_SF`; this file is run with `--root` so that the engine and the
harness are the other checkout's.  Counts and hashes only: nothing here is
a time.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_SF = 0.02
SEED = 2**31 + 34
QUERIES_AFTER_WARM_UP = 3

#: cells whose plans hold no semi or anti join (PR 37 names and jits that
#: arm's selection): every program of theirs must be the parent's
MUST_EQUAL = ("tpch_sf5_1chip.q6", "tpch_sf5_1chip.q18sub",
              "tpch_sf2.75_4chip.q18sub", "tpch_q1_1chip.q1",
              "tpch_q3_1chip.q3")


def tiny_root(root: str, tmp: str, cell_name: str) -> str:
    """A copy of `root`'s benchmark with the cell's scale cut."""
    shutil.copytree(os.path.join(root, "benchmarks"),
                    os.path.join(tmp, "benchmarks"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp)
    with open(os.path.join(tmp, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    rel = next(c["file"] for c in bench["configs"]
               if c["name"] == cell["config"])
    with open(os.path.join(tmp, rel)) as f:
        config = json.load(f)
    config["scale_factor"] = TINY_SF
    with open(os.path.join(tmp, rel), "w") as f:
        json.dump(config, f)
    return tmp


def one_cell(root: str, cell_name: str) -> int:
    """Run the cell in THIS process (the devices are set by the caller's
    environment) and print its programs."""
    sys.path.insert(0, root)
    import jax
    from benchmarks.harness import cells, device as dev, runner
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    built = []
    record = CompileObservatory.record_build

    def recording(self, exec_kind, key_hash, canon_key, sig, *args, **kw):
        built.append({
            "exec": exec_kind, "key_head": args[4],
            "shape": hashlib.sha256(repr(sig).encode()).hexdigest()[:12],
            "hlo_hash": kw.get("hlo_hash")})
        return record(self, exec_kind, key_hash, canon_key, sig, *args, **kw)
    CompileObservatory.record_build = recording
    dev.peak_device_bytes = lambda devices: [0] * len(devices)
    with tempfile.TemporaryDirectory() as tmp:
        cell = cells.load_cell(tiny_root(root, tmp, cell_name), cell_name)
        bench = runner.Bench(cell, seed=SEED, trace=False)
        bench.load()
        bench.warm_up(jax.devices()[:cell.chips])
        asked = [bench.ask(next(bench.stream))
                 for _ in range(QUERIES_AFTER_WARM_UP)]
        bench.check(asked, "after the warm-up")
    if bench.problems:
        print(json.dumps({"cell": cell_name, "problems": bench.problems}))
        return 1
    for p in built:
        print(json.dumps({"cell": cell_name, **p}))
    return 0


def programs_of(root: str, cell: dict) -> list:
    """The cell's list, from a process of its own in `root`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               SPARK_RAPIDS_TPU_DISABLE_COMPILE_CACHE="1",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "") +
               f" --xla_force_host_platform_device_count={cell['chips']}")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--root", root,
         "--cell", cell["name"]], env=env, cwd=root, text=True,
        stdout=subprocess.PIPE, check=False)
    # (the harness prints facts of its own as JSON lines: not ours)
    lines = [x for x in map(json.loads, filter(
        lambda x: x.startswith("{"), out.stdout.splitlines()))
        if "cell" in x]
    if out.returncode or any("problems" in x for x in lines):
        raise SystemExit(f"{cell['name']} in {root} failed "
                         f"(rc {out.returncode}): {lines[-1:]}")
    return lines


def against(parent: str) -> int:
    with open(os.path.join(HERE, "BENCHMARK.json")) as f:
        workloads = json.load(f)["workloads"]
    with open(os.path.join(parent, "BENCHMARK.json")) as f:
        known = {w["name"] for w in json.load(f)["workloads"]}
    rc = 0
    for cell in workloads:
        mine = programs_of(HERE, cell)
        if cell["name"] not in known:
            print(f"{cell['name']}: {len(mine)} programs here; the parent "
                  f"has no such cell")
            continue
        theirs = programs_of(parent, cell)
        key = lambda p: (p["exec"], p["shape"], p["hlo_hash"])  # noqa: E731
        equal = [key(p) for p in mine] == [key(p) for p in theirs]
        held = cell["name"] in MUST_EQUAL
        print(f"{cell['name']}: {len(mine)} programs here, {len(theirs)} "
              f"in the parent, "
              f"{'equal' if equal else 'DIFFERENT'}"
              f"{'' if equal or held else ' (expected: not held equal)'}")
        if not equal:
            gone = [p for p in theirs if key(p) not in map(key, mine)]
            new = [p for p in mine if key(p) not in map(key, theirs)]
            for tag, ps in (("parent only", gone), ("here only", new)):
                for p in ps:
                    print(f"  {tag}: {p['exec']} {p['hlo_hash']} "
                          f"{p['key_head'][:60]}")
            if held:
                rc = 1
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--against", help="a checkout of the parent commit")
    ap.add_argument("--cell")
    ap.add_argument("--root", default=HERE)
    args = ap.parse_args()
    if args.against:
        return against(os.path.abspath(args.against))
    if not args.cell:
        ap.error("--cell or --against")
    return one_cell(os.path.abspath(args.root), args.cell)


if __name__ == "__main__":
    sys.exit(main())
