"""What the benchmark asks of the device and of an executed plan.

``require_tpu`` and ``device_facts`` are copies of ``bench.py``'s, and
``placements``, ``pinned_scan_arrays`` and ``peak_device_bytes`` of
``chip_smoke.py``'s (both PR 21, run on the chip there), so that a later PR
cannot move them.  The two plan helpers import the program's ``exec``
modules: placement and the pin cache have no other face.
"""

from __future__ import annotations


def require_tpu(chips: int):
    """The devices a cell runs on.  A run that finds no TPU, or another
    number of chips than the cell asks for, ends here with a non-zero
    exit; nothing is ever measured in its place."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) != chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chip(s), JAX sees "
            f"{len(devices)}")
    return devices


def device_facts() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peak_device_bytes(devices) -> list:
    """Peak bytes in use on each device since the process started."""
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


def placements(plan):
    """The names of the plan's operators that the CPU engine ran, sorted."""
    from spark_rapids_tpu.exec.base import CPU
    placed = []
    plan.foreach(lambda e: placed.append((type(e).__name__, e.placement)))
    return sorted(n for n, p in placed if p == CPU)


def pinned_scan_arrays(plan) -> list:
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


def plan_execs(plan, name: str) -> list:
    """The plan's operators whose class is called ``name``."""
    found = []
    plan.foreach(lambda e: found.append(e)
                 if type(e).__name__ == name else None)
    return found
