"""Scan + H2D upload + pin (columnar/device.py, exec/basic.LocalScanExec):
the wall time of the query's first call in set-up, which uploads and pins
the table and loads or compiles the programs, less the window's median
time to answer."""

from benchmarks.harness.stats import median


def read(run):
    if not run.times_ms:
        return None
    return run.first_call_s - median(run.times_ms) / 1e3
