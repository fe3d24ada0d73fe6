"""Device manager: TPU acquisition + memory bookkeeping + semaphore init.

Ref: GpuDeviceManager.scala:125 initializeGpuAndMemory / :216 initializeRmm.
The RMM pool's TPU analog is an HBM budget tracked against the PJRT
device's memory stats; allocation visibility for spill decisions comes
from the batch registry (memory/spill.py) rather than allocator callbacks
(XLA owns the real allocator — SURVEY hard-part #5).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .. import config as cfg


class DeviceManager:
    _instance: Optional["DeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf: cfg.RapidsConf):
        self.conf = conf
        self.device = None
        self.devices = list(jax.devices())
        #: the HBM budget of each chip, by ``device.id``; a table spread
        #: over the mesh is booked against the chips that hold it
        #: (memory/spill.py), never all against the first
        self.hbm_limits = {}
        self.hbm_limit = 0
        self.hbm_reserve = conf.get(cfg.HBM_RESERVE)
        if self.devices:
            self.device = self.devices[0]
            frac = conf.get(cfg.HBM_POOL_FRACTION)
            for d in self.devices:
                self.hbm_limits[d.id] = int(
                    self._device_capacity(conf, d) * frac) \
                    - self.hbm_reserve
            self.hbm_limit = self.hbm_limits[self.device.id]

    # per-generation HBM capacities (public TPU specs); used only when the
    # PJRT runtime reports no memory_stats for the device
    _KNOWN_HBM = (
        ("v5 lite", 16 * (1 << 30)), ("v5e", 16 * (1 << 30)),
        ("v5p", 95 * (1 << 30)), ("v6", 32 * (1 << 30)),
        ("v4", 32 * (1 << 30)), ("v3", 16 * (1 << 30)),
        ("v2", 8 * (1 << 30)),
    )

    def _device_capacity(self, conf: cfg.RapidsConf, device=None) -> int:
        """Resolve one chip's real memory (the first chip's by default):
        explicit conf > PJRT memory_stats > device-kind table > host RAM
        (CPU backend).  An unrecognized accelerator with no stats raises
        instead of silently assuming a capacity the spill budget would
        then be fiction against."""
        device = self.device if device is None else device
        override = conf.get(cfg.HBM_LIMIT_OVERRIDE)
        if override:
            return int(override)
        try:
            stats = device.memory_stats() or {}
        except Exception:
            stats = {}
        if stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
        kind = (getattr(device, "device_kind", "") or "").lower()
        platform = getattr(device, "platform", "")
        for marker, cap in self._KNOWN_HBM:
            if marker in kind:
                return cap
        if platform == "cpu" or kind == "cpu":
            import os
            return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        from ..plugin import PluginInitError
        raise PluginInitError(
            f"cannot determine memory capacity of device {kind!r} "
            f"(platform {platform!r}): PJRT reports no memory_stats; set "
            f"{cfg.HBM_LIMIT_OVERRIDE.key} explicitly")

    @classmethod
    def initialize(cls, conf: cfg.RapidsConf) -> "DeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DeviceManager(conf)
            return cls._instance

    @classmethod
    def get(cls) -> Optional["DeviceManager"]:
        return cls._instance

    def memory_in_use(self, device=None) -> int:
        """Bytes in use on one chip (the first by default)."""
        device = self.device if device is None else device
        try:
            stats = device.memory_stats() or {}
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0

    def memory_in_use_by_device(self) -> dict:
        """``device.id`` -> bytes in use, for every chip of the mesh."""
        return {d.id: self.memory_in_use(d) for d in self.devices}
