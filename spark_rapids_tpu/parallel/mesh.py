"""Device-mesh management.

The TPU analog of the reference's device acquisition + peer topology
bootstrap (ref: GpuDeviceManager.scala:125 initializeGpuAndMemory picks
one GPU per executor; RapidsShuffleHeartbeatManager.scala:50 teaches
executors about each other so UCX endpoints can form).  On TPU the
topology is declarative: a `jax.sharding.Mesh` over the slice's chips,
with the `"data"` axis carrying SQL data parallelism.  XLA lays the
collectives onto ICI; multi-pod meshes extend the same axis over DCN.
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"

# Hard deadline on first-touch device discovery.  jax.devices() on a
# multichip slice blocks on PJRT topology exchange: one unreachable
# chip/host and the call hangs forever.  The deadline turns that hang
# into a counted, traced failure that raises.
DEFAULT_PROBE_TIMEOUT_S = 120.0


class DeviceDiscoveryTimeout(RuntimeError):
    """Device discovery exceeded its hard deadline (likely an
    unreachable chip)."""


def _probe_timeout_s() -> float:
    raw = os.environ.get("SPARK_RAPIDS_TPU_DEVICE_PROBE_TIMEOUT_S")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    return DEFAULT_PROBE_TIMEOUT_S


def discover_devices(timeout_s: Optional[float] = None) -> List:
    """``jax.devices()`` under a hard deadline.

    On timeout the daemon probe thread is left behind (there is no safe
    way to interrupt a hung PJRT client), ``tpu_device_probe_failures_
    total`` increments and ``DeviceDiscoveryTimeout`` raises instead of
    hanging the process."""
    from ..obs import metrics as m
    timeout_s = _probe_timeout_s() if timeout_s is None else timeout_s
    result: List = []
    error: List[BaseException] = []

    def probe():
        try:
            result.extend(jax.devices())
        except BaseException as ex:  # noqa: BLE001 — report, not mask
            error.append(ex)

    t = threading.Thread(target=probe, daemon=True,
                         name="tpu-device-probe")
    t.start()
    t.join(timeout_s)
    fail = m.counter("tpu_device_probe_failures_total",
                     "device discovery timeouts / errors")
    ok = m.gauge("tpu_device_probe_ok",
                 "1 when the last device probe succeeded, else 0")
    if t.is_alive():
        fail.inc()
        ok.set(0)
        raise DeviceDiscoveryTimeout(
            f"device discovery exceeded {timeout_s:g}s (unreachable "
            f"chip); set "
            f"SPARK_RAPIDS_TPU_DEVICE_PROBE_TIMEOUT_S to adjust")
    if error:
        fail.inc()
        ok.set(0)
        raise error[0]
    ok.set(1)
    return result


def device_count(timeout_s: Optional[float] = None) -> int:
    """Visible-device count with the discovery deadline applied.  A
    timed-out or failed probe raises: answering "one chip" would let a
    planner that was asked for the mesh quietly take the single-chip
    path."""
    return len(discover_devices(timeout_s))


def build_mesh(n_devices: Optional[int] = None,
               axis_name: str = DATA_AXIS,
               devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D data-parallel mesh over the first ``n_devices`` chips."""
    devs = list(devices) if devices is not None else discover_devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), axis_names=(axis_name,))


def mesh_sharding(mesh: Mesh, axis_name: str = DATA_AXIS) -> NamedSharding:
    """Row-sharded placement: leading axis split across the data axis."""
    return NamedSharding(mesh, PartitionSpec(axis_name))
