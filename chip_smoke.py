#!/usr/bin/env python
"""Chip smoke: the standing proof that the engine starts, compiles and
answers on the TPU.

    python chip_smoke.py             # one chip: the seven bench queries
    python chip_smoke.py --chips 4   # four chips: the ICI stages only

One process, the only one that touches JAX.  It fails at once when JAX
finds no TPU; there is no CPU branch.  With no arguments it builds the
bench tables from a seed, runs the seven queries of ``bench.queries``
once each through a default-configuration ``TpuSession`` and once each on
the independent CPU engine (``spark.rapids.sql.enabled=false``), compares
the answers with ``testing/asserts.py``, and proves the device did the
work (pinned scan batches on the TPU, peak device bytes at least the
table's).  ``--chips 4`` runs only the cross-chip phase: a grouped
aggregate, a shuffled join and a global sort through the ICI transport,
checked against the CPU engine.

Every earlier line of stdout is one JSON object of bring-up facts (no
rates, no comparison of speeds).  The last line is the contract's:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import pyarrow as pa

#: 48M rows x 24 B = 1.15 GB resident is the size a user would call real
TARGET_ROWS = 48_000_000
#: the smallest size the smoke may be cut to
MIN_ROWS = 16_777_216
#: rows the one-chip smoke runs with, and why that is not TARGET_ROWS
ROWS = 33_554_432
CUT = ("the contract's 1200 s, compilation included: a one-partition "
       "table is one batch, so 48M rows pad to the 67,108,864 bucket and "
       "every program compiles and runs at that size, while the CPU "
       "engine's half of each comparison grows with the rows; a cold run "
       "at 16,777,216 rows took 368 s and its second run 171 s (chip, PR "
       "21), which puts 64M-bucket programs near the limit and near the "
       "chip's 16 GB; 33,554,432 rows are one exact bucket (805 MB)")
#: one largest-bucket batch per chip
FOUR_CHIP_ROWS = 16_777_216

#: what a query's plan may leave on the CPU engine: the final
#: device->host transition and nothing else.  tests/test_chip_smoke.py
#: holds the same queries to the same set at the tier-1 size.
EXPECTED_CPU_OPS = frozenset({"DeviceToHostExec"})

FLOAT_TOLERANCE = 1e-9


def emit(**facts) -> None:
    print(json.dumps(facts), flush=True)


def check(ok, message: str) -> None:
    if not ok:
        raise AssertionError(message)


def placements(plan):
    """(TPU-placed count, CPU-placed count, CPU-placed operator names)."""
    from spark_rapids_tpu.exec.base import CPU
    placed = []
    plan.foreach(lambda e: placed.append((type(e).__name__, e.placement)))
    cpu_ops = sorted(n for n, p in placed if p == CPU)
    return len(placed) - len(cpu_ops), len(cpu_ops), cpu_ops


def pinned_scan_arrays(plan):
    """Every array the plan's device-placed in-memory scans keep pinned
    (``spark.rapids.sql.localScan.pinDeviceBatches``)."""
    import jax
    from spark_rapids_tpu.exec.base import TPU
    from spark_rapids_tpu.exec.basic import LocalScanExec
    leaves = []

    def visit(e):
        if isinstance(e, LocalScanExec) and e.placement == TPU and \
                e.pin_cache:
            for batches in e.pin_cache.values():
                # the lanes; a scan batch's row count is a host scalar
                leaves.extend(jax.tree_util.tree_leaves(
                    [b.columns for b in batches]))
    plan.foreach(visit)
    return leaves


def peak_device_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _session(enabled: bool, conf=None):
    from spark_rapids_tpu.api.session import TpuSession
    b = TpuSession.builder().config("spark.rapids.sql.enabled", enabled)
    for k, v in (conf or {}).items():
        b = b.config(k, v)
    return b.get_or_create()


def _counters():
    from spark_rapids_tpu.obs import metrics
    from spark_rapids_tpu.obs.compileprof import CompileObservatory
    reg = metrics.registry()
    return {
        "programs": CompileObservatory.get().snapshot()["builds"],
        "cache_hits": int(reg.counter(
            "tpu_jit_persistent_cache_hits_total").value()),
        "cache_misses": int(reg.counter(
            "tpu_jit_persistent_cache_misses_total").value()),
    }


def run_and_compare(name, tpu_q, cpu_q, tpu_session, ordered=False,
                    expected_cpu_ops=EXPECTED_CPU_OPS):
    """One query on both engines: the TPU engine's first call (timed,
    compile included), the CPU engine's answer, the comparison.  Returns
    the bring-up facts and the executed TPU plan; raises on a mismatch
    or on an operator that fell back to the CPU engine."""
    from spark_rapids_tpu.testing.asserts import assert_tables_equal
    before = _counters()
    t0 = time.perf_counter()
    got = tpu_q()
    first_call_s = time.perf_counter() - t0
    after = _counters()
    plan = tpu_session.last_plan
    n_tpu, n_cpu, cpu_ops = placements(plan)
    unexpected = sorted(set(cpu_ops) - expected_cpu_ops)
    check(not unexpected,
          f"{name}: operators fell back to the CPU engine: {unexpected}")
    want = cpu_q()
    if not isinstance(got, pa.Table):   # bench's write: footer row counts
        check(got.num_rows == want.num_rows and got.num_rows > 0,
              f"{name}: wrote {got.num_rows} rows, the CPU engine "
              f"{want.num_rows}")
    else:
        assert_tables_equal(want, got, ignore_order=not ordered,
                            approximate_float=FLOAT_TOLERANCE)
    facts = {"query": name, "rows": got.num_rows,
             "first_call_s": round(first_call_s, 3),
             "tpu_ops": n_tpu, "cpu_ops": n_cpu}
    facts.update({k: after[k] - before[k] for k in before})
    return facts, plan


def run_one_chip(device, rows: int, seed: int, root: str):
    """The seven bench queries on one chip against the CPU engine.
    Returns the per-query facts; raises on any failure."""
    import bench
    fact, dim = bench.make_tables(rows, seed)
    pq_path = bench.write_parquet_input(fact, root)
    tpu = _session(True)
    cpu = _session(False)
    tpu_qs = bench.queries(tpu, fact, dim, pq_path, root)
    cpu_qs = dict(bench.queries(cpu, fact, dim, pq_path, root))
    out = []
    pinned = {}
    for name, q in tpu_qs:
        facts, plan = run_and_compare(name, q, cpu_qs[name], tpu,
                                      ordered=(name == "sort"))
        for leaf in pinned_scan_arrays(plan):
            pinned[id(leaf)] = leaf
        emit(**facts)
        out.append(facts)
    # the device did the work: the scans' pinned batches live on it, and
    # it has held at least the table
    import jax
    check(pinned, "no scan pinned its device batches")
    for leaf in pinned.values():
        check(isinstance(leaf, jax.Array) and leaf.devices() == {device},
              f"pinned scan batch not on {device}: {leaf!r}")
    pinned_bytes = sum(leaf.nbytes for leaf in pinned.values())
    peak = peak_device_bytes(device)
    check(pinned_bytes >= fact.nbytes and peak >= fact.nbytes,
          f"device held {pinned_bytes} pinned / {peak} peak bytes, the "
          f"table is {fact.nbytes}")
    emit(fact_rows=rows, fact_bytes=fact.nbytes, pinned_bytes=pinned_bytes,
         peak_device_bytes=peak)
    return out


ICI_STAGES = ("IciAggregateExec", "IciJoinExec", "IciSortExec")


def run_four_chips(devices, rows: int, seed: int):
    """The cross-chip phase: a grouped aggregate, a shuffled inner join
    and a global sort through the ICI transport over ``devices``,
    checked against the CPU engine.  Raises unless the executed plans
    hold the three ICI stages with their inputs sharded over every
    device."""
    import bench
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.column import col
    n_dev = len(devices)
    fact, dim = bench.make_tables(rows, seed)
    conf = {"spark.rapids.shuffle.transport": "ici",
            # the 100k-row dim would otherwise be broadcast
            "spark.rapids.sql.autoBroadcastJoinThreshold": -1}
    tpu = _session(True, conf)
    cpu = _session(False, conf)

    def queries(s):
        fdf = s.create_dataframe(fact, num_partitions=n_dev)
        ddf = s.create_dataframe(dim, num_partitions=n_dev)
        return [
            ("ici_agg", lambda: (fdf.group_by(col("k"))
                                 .agg(F.sum(col("v")).alias("sv"),
                                      F.count("*").alias("c"))
                                 .collect()), False),
            ("ici_join", lambda: (fdf.join(ddf, on="k", how="inner")
                                  .group_by(col("k"))
                                  .agg(F.sum(col("w")).alias("sw"))
                                  .collect()), False),
            ("ici_sort", lambda: fdf.sort(col("k"), col("v")).collect(),
             True),
        ]

    cpu_qs = {name: q for name, q, _ in queries(cpu)}
    seen = set()
    out = []
    for name, q, ordered in queries(tpu):
        facts, plan = run_and_compare(name, q, cpu_qs[name], tpu,
                                      ordered=ordered)
        stages = []
        plan.foreach(lambda e: stages.append(e)
                     if type(e).__name__ in ICI_STAGES else None)
        for e in stages:
            check(e.stage_input_devices == n_dev,
                  f"{name}: {type(e).__name__} input sat on "
                  f"{e.stage_input_devices} device(s), not {n_dev}")
            seen.add(type(e).__name__)
        facts["ici_stages"] = sorted(type(e).__name__ for e in stages)
        emit(**facts)
        out.append(facts)
    missing = sorted(set(ICI_STAGES) - seen)
    check(not missing, f"executed plans lack {missing}")
    emit(fact_rows=rows, devices=n_dev,
         peak_device_bytes=[peak_device_bytes(d) for d in devices])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import bench
    device = bench.require_tpu()
    import jax
    if jax.device_count() != args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{jax.device_count()} device(s)")
    rows = args.rows or (ROWS if args.chips == 1 else FOUR_CHIP_ROWS)
    if rows < MIN_ROWS:
        sys.exit(f"chip_smoke: {rows} rows is under the floor of {MIN_ROWS}")

    import jaxlib
    import spark_rapids_tpu  # noqa: F401  (turns 64-bit lanes on)
    from spark_rapids_tpu import native
    from spark_rapids_tpu.plugin import compilation_cache_dir
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    cache_dir = compilation_cache_dir()
    entries_at_start = cache_entries(cache_dir)
    codec = "native" if native.get_lib() is not None else \
        f"zlib ({native.build_error()})"
    emit(jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, device_kind=device.device_kind,
         chips=args.chips, rows=rows, seed=args.seed, codec=codec,
         cache_dir=cache_dir, cache_entries_at_start=entries_at_start)
    if args.chips == 1 and rows < TARGET_ROWS:
        emit(cut={"rows": rows, "from": TARGET_ROWS, "why": CUT})

    root = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 1:
            facts = run_one_chip(device, rows, args.seed, root)
        else:
            facts = run_four_chips(jax.devices(), rows, args.seed)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    emit(programs_compiled=sum(f["programs"] for f in facts),
         cache_hits=sum(f["cache_hits"] for f in facts),
         cache_misses=sum(f["cache_misses"] for f in facts),
         cache_entries_at_end=cache_entries(cache_dir),
         wall_s=round(time.perf_counter() - t_start, 1))
    emit(ok=True, device=bench.device_facts())
    return 0


if __name__ == "__main__":
    sys.exit(main())
