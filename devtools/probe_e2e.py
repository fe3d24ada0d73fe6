#!/usr/bin/env python
"""End-to-end warm timing of bench queries through the real engine."""
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import make_tables, queries, require_tpu, write_parquet_input


def main():
    which = sys.argv[1:] or ["agg"]
    require_tpu()
    fact, dim = make_tables(1_000_000)
    root = tempfile.mkdtemp(prefix="probe_e2e_")
    try:
        pq_path = write_parquet_input(fact, root)
        from spark_rapids_tpu.api.session import TpuSession
        s = (TpuSession.builder()
             .config("spark.rapids.sql.enabled", True).get_or_create())
        qs = dict(queries(s, fact, dim, pq_path, root))
        for name in which:
            q = qs[name]
            t0 = time.perf_counter()
            q()
            print(f"{name} first (compile): {time.perf_counter()-t0:.2f}s",
                  flush=True)
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = q()
                ts.append(time.perf_counter() - t0)
            print(f"{name} warm: {min(ts):.3f}s  (rows={out.num_rows})",
                  flush=True)
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
