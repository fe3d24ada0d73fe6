"""Host staging arena (RMM's pooled-allocator role on the host side,
ref GpuDeviceManager.scala:216 initializeRmm / pinned pool at :302).

A bump arena over one page-aligned native allocation: spill/shuffle
staging buffers allocate in O(1) and free all-at-once per task, so hot
paths never touch malloc.  Falls back to plain bytearray blocks when the
native library is unavailable."""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

from . import get_lib


def _ledger():
    """The installed tmsan shadow ledger (no-op when disabled)."""
    from ..memory import memsan
    return memsan.active_ledger()


def _timeline():
    """The HBM observatory's occupancy timeline (None when disabled)."""
    from ..obs import memprof
    return memprof.active_timeline()


def _tenant_ctx():
    """(tenant, query) charged for the current arena operation — the
    thread's memprof attribution scope, or the unattributed sentinel.
    Arena exhaustion events historically recorded only the requesting
    operator; the tenant label is what lets the black box name the
    culprit rather than just the victim."""
    from ..obs import memprof
    ctx = memprof.current_context()
    if ctx is None:
        return memprof.UNATTRIBUTED_TENANT, ""
    return ctx


def _trace_event(name: str, **attrs) -> None:
    """Flight-recorder hook (no-op without an installed tracer)."""
    from ..obs import tracer
    tr = tracer.active_tracer()
    if tr is not None:
        tr.event(name, **attrs)


def _metrics():
    """(allocs_total, exhaustions_total, used_bytes, utilization)."""
    from ..obs import metrics as m
    return (
        m.counter("tpu_arena_allocs_total",
                  "staging-arena allocations served"),
        m.counter("tpu_arena_exhaustions_total",
                  "allocations refused because the arena was full",
                  ("tenant",)),
        m.gauge("tpu_arena_used_bytes",
                "bytes currently bump-allocated in the staging arena"),
        m.gauge("tpu_arena_utilization_ratio",
                "staging-arena used/capacity at the last allocation"),
    )


class HostArena:
    def __init__(self, capacity: int = 64 << 20):
        self.capacity = capacity
        self._closed = False
        self._arena_id = f"arena-{id(self):x}"
        self._lock = threading.Lock()
        self._lib = get_lib()
        if self._lib is not None:
            self._arena = self._lib.tpu_arena_create(capacity)
            if not self._arena:
                raise MemoryError(f"cannot reserve {capacity} arena bytes")
        else:
            self._arena = None
            self._buf = bytearray(capacity)
            self._used = 0
            self._high = 0
            self._n = 0

    def alloc(self, size: int, align: int = 64) -> Optional[memoryview]:
        """A writable view of `size` bytes, or None when exhausted."""
        led = _ledger()
        if led is not None:
            # alloc-after-close is the arena's use-after-free shape; the
            # ledger also tracks the staging high-water mark
            led.on_arena_alloc(
                self._arena_id,
                size if self._closed else self.used + size, self._closed)
        mm = _metrics()
        tenant, _ = _tenant_ctx()
        with self._lock:
            if self._arena is not None:
                off = self._lib.tpu_arena_alloc(self._arena, size, align)
                if off < 0:
                    mm[1].labels(tenant=tenant).inc()
                    return None
                base = self._lib.tpu_arena_base(self._arena)
                out = memoryview(
                    (ctypes.c_uint8 * size).from_address(
                        ctypes.addressof(base.contents) + off)).cast("B")
            else:
                off = (self._used + align - 1) & ~(align - 1)
                if off + size > self.capacity:
                    mm[1].labels(tenant=tenant).inc()
                    return None
                self._used = off + size
                self._high = max(self._high, self._used)
                self._n += 1
                out = memoryview(self._buf)[off:off + size]
            used = self.used
        mm[0].inc()
        mm[2].set(used)
        mm[3].set(used / self.capacity if self.capacity else 0.0)
        tl = _timeline()
        if tl is not None:
            tl.on_arena_alloc(self._arena_id, used, self.capacity)
        return out

    def reset(self):
        with self._lock:
            if self._arena is not None:
                self._lib.tpu_arena_reset(self._arena)
            else:
                self._used = 0
        mm = _metrics()
        mm[2].set(0)
        mm[3].set(0.0)
        tl = _timeline()
        if tl is not None:
            tl.on_arena_reset(self._arena_id)

    def stage(self, data) -> bytes:
        """Stage a bytes-like payload through the arena: alloc, copy,
        hand back an immutable copy backed by the (page-aligned, native
        when available) staging buffer.  A full arena resets first —
        staged payloads are consumed immediately by the caller, so the
        bump pointer can recycle; a payload larger than the whole arena
        bypasses it (counted as an exhaustion by alloc())."""
        size = len(data)
        if self._closed:
            return bytes(data)
        if size == 0 or size > self.capacity:
            if size > self.capacity:
                _metrics()[1].labels(tenant=_tenant_ctx()[0]).inc()
            return bytes(data)
        mv = self.alloc(size)
        if mv is None:
            self.reset()
            mv = self.alloc(size)
            if mv is None:
                return bytes(data)
        mv[:] = data
        return bytes(mv)

    @property
    def used(self) -> int:
        if self._arena is not None:
            return self._lib.tpu_arena_used(self._arena)
        return self._used

    @property
    def high_water(self) -> int:
        if self._arena is not None:
            return self._lib.tpu_arena_high_water(self._arena)
        return self._high

    @property
    def n_allocs(self) -> int:
        if self._arena is not None:
            return self._lib.tpu_arena_allocs(self._arena)
        return self._n

    def close(self):
        if not self._closed:
            _trace_event("arena.close", high_water=self.high_water,
                         allocs=self.n_allocs)
            tl = _timeline()
            if tl is not None:
                tl.on_arena_reset(self._arena_id)
        self._closed = True
        if self._arena is not None:
            self._lib.tpu_arena_destroy(self._arena)
            self._arena = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# process-wide shared staging arena
# (spark.rapids.memory.pinnedPool.size; the reference's pinned staging
#  pool, GpuDeviceManager.scala:302 — serialize/spill payloads stage
#  through ONE page-aligned native buffer instead of per-call mallocs)
# ---------------------------------------------------------------------------

_shared: "Optional[HostArena]" = None
_shared_lock = threading.Lock()


def configure_shared_arena(capacity: int) -> "Optional[HostArena]":
    """(Re)create the shared staging arena; capacity <= 0 disables it.
    Called by the executor plugin from the pinnedPool.size config."""
    global _shared
    with _shared_lock:
        if _shared is not None:
            _shared.close()
            _shared = None
        if capacity > 0:
            _shared = HostArena(capacity)
        return _shared


def shared_arena() -> "Optional[HostArena]":
    return _shared


def stage_bytes(data) -> bytes:
    """Stage a serialized payload through the shared arena when one is
    configured (spill/shuffle serialization calls this); plain bytes
    otherwise."""
    a = _shared
    if a is None:
        return data if isinstance(data, bytes) else bytes(data)
    return a.stage(data)
