"""Tiered spill framework: DEVICE -> HOST -> DISK.

Ref: RapidsBuffer.scala:53 (StorageTier), RapidsBufferCatalog.scala:156
(registry + tier wiring), RapidsBufferStore.synchronousSpill:146,
DeviceMemoryEventHandler.scala (Rmm OOM callback), SpillPriorities.scala,
SpillableColumnarBatch.scala.

TPU redesign (SURVEY hard-part #5): XLA owns the allocator, so there is no
RMM-style OOM callback.  Instead the framework tracks every *registered*
batch's device footprint in this catalog and reacts two ways:
  * proactively — `maybe_spill()` demotes lowest-priority buffers when the
    registered device bytes exceed the HBM budget;
  * reactively — `with_retry_spill(fn)` catches XLA RESOURCE_EXHAUSTED,
    spills synchronously, and retries, the analog of the reference's
    retry-on-OOM allocation loop.
Host tier holds serialized batches in RAM up to its own budget, then
overflows to local disk (RapidsDiskStore analog).
"""

from __future__ import annotations

import os
import tempfile
import threading
import uuid
from enum import Enum
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..columnar.device import DeviceBatch


def _ledger():
    """The installed tmsan shadow ledger, or None (the common case —
    the sanitizer is opt-in via spark.rapids.tpu.memsan.enabled and
    every hook below is a no-op without it)."""
    from . import memsan
    return memsan.active_ledger()


def _timeline():
    """The HBM observatory's occupancy timeline, or None when disabled
    (spark.rapids.tpu.hbm.timeline.enabled) — same no-op discipline as
    the shadow-ledger hooks."""
    from ..obs import memprof
    return memprof.active_timeline()


def _trace_event(name: str, **attrs) -> None:
    """Flight-recorder hook: tier moves are exactly what a post-mortem
    wants on the timeline (no-op without an installed tracer)."""
    from ..obs import tracer
    tr = tracer.active_tracer()
    if tr is not None:
        tr.event(name, **attrs)


def _metrics():
    """Continuous-metrics families for the spill subsystem (obs/metrics
    creation is idempotent; increments are no-ops when disabled)."""
    from ..obs import metrics as m
    return (
        m.counter("tpu_spill_registered_batches_total",
                  "spillable batches registered in the catalog"),
        m.counter("tpu_spill_registered_bytes_total",
                  "device bytes entering the spill catalog"),
        m.counter("tpu_spill_bytes_total",
                  "bytes demoted per destination tier", ("tier",)),
        m.counter("tpu_spill_pinned_evictions_total",
                  "pinned scan-cache entries evicted under pressure"),
        m.gauge("tpu_spill_device_bytes",
                "registered device-resident bytes (incl. pinned)"),
        m.gauge("tpu_spill_host_bytes",
                "serialized bytes held in the HOST tier"),
        m.counter("tpu_spill_raw_bytes_total",
                  "uncompressed serialized-body bytes entering each "
                  "tier (pre-codec)", ("tier",)),
        m.counter("tpu_spill_serialized_bytes_total",
                  "post-codec bytes actually stored per tier — vs the "
                  "raw counter this is the codec's effect on host "
                  "retention and disk I/O", ("tier",)),
    )


#: every chip together, where a chip's ``device.id`` (or None, for
#: lanes no one chip holds) would pick one
ALL_CHIPS = "all"


class StorageTier(Enum):
    DEVICE = 0
    HOST = 1
    DISK = 2


class SpillPriority:
    """Lower value spills first (ref SpillPriorities.scala)."""
    INPUT = -10
    SHUFFLE = 0
    ACTIVE = 100


def chip_of(batches) -> Optional[int]:
    """``device.id`` of the one chip these batches' lanes lie on; None
    for host lanes and for lanes on several chips (a stacked mesh
    batch), which no single chip's budget answers for."""
    ids = set()
    for leaf in jax.tree_util.tree_leaves(batches):
        if isinstance(leaf, jax.Array):
            ids |= {d.id for d in leaf.devices()}
    return ids.pop() if len(ids) == 1 else None


def batch_device_bytes(batch: DeviceBatch) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(batch):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


class SpillableBatch:
    """A batch that can move down the storage tiers and come back
    (ref SpillableColumnarBatch.scala:29-230).  Supports `with` blocks —
    the Arm.scala withResource discipline: the reference leans on RAII +
    refcount asserts to catch leaks; here the context manager plus the
    catalog's debug leak tracker play that role."""

    def __init__(self, batch: DeviceBatch, catalog: "SpillCatalog",
                 priority: int = SpillPriority.ACTIVE):
        self.id = uuid.uuid4().hex
        self.catalog = catalog
        self.priority = priority
        self.tier = StorageTier.DEVICE
        self._batch: Optional[DeviceBatch] = batch
        self._host_bytes: Optional[bytes] = None
        self._disk_path: Optional[str] = None
        self.closed = False
        self.device_bytes = batch_device_bytes(batch)
        self.chip = chip_of(batch)
        # num_rows may be a traced device scalar; resolving it here would
        # force a sync per registered batch — defer to first read
        self._num_rows = batch.num_rows
        led = _ledger()
        if led is not None:
            led.on_alloc(self.id, self.device_bytes)
        tl = _timeline()
        if tl is not None:
            from ..obs import memprof
            bclass = memprof.SHUFFLE_BLOCK \
                if priority == SpillPriority.SHUFFLE \
                else memprof.WORKING_SET
            tl.on_alloc(self.id, self.device_bytes, bclass)

    @property
    def num_rows(self) -> int:
        import numpy as _np
        if not isinstance(self._num_rows, int):
            self._num_rows = int(_np.asarray(self._num_rows))
        return self._num_rows

    # -- tier moves ---------------------------------------------------------
    def spill_to_host(self):
        if self.tier != StorageTier.DEVICE:
            return 0
        from .meta import serialize_batch_with_sizes
        self._host_bytes, raw_len, enc_len = \
            serialize_batch_with_sizes(self._batch)
        self._raw_body_len = raw_len
        self._batch = None
        self.tier = StorageTier.HOST
        led = _ledger()
        if led is not None:
            led.on_spill(self.id, self.device_bytes)
        tl = _timeline()
        if tl is not None:
            tl.on_spill(self.id, self.device_bytes)
        _trace_event("spill.host", bytes=self.device_bytes,
                     buffer=self.id[:8])
        mm = _metrics()
        mm[2].labels(tier="host").inc(self.device_bytes)
        mm[6].labels(tier="host").inc(raw_len)
        mm[7].labels(tier="host").inc(enc_len)
        return self.device_bytes

    def spill_to_disk(self):
        if self.tier == StorageTier.DEVICE:
            self.spill_to_host()
        if self.tier != StorageTier.HOST:
            return 0
        path = os.path.join(self.catalog.spill_dir, f"spill-{self.id}.bin")
        with open(path, "wb") as f:
            f.write(self._host_bytes)
        freed = len(self._host_bytes)
        self._disk_path = path
        self._host_bytes = None
        self.tier = StorageTier.DISK
        led = _ledger()
        if led is not None:
            led.on_spill(self.id, 0)  # host tier -> disk: no HBM delta
        _trace_event("spill.disk", bytes=freed, buffer=self.id[:8])
        mm = _metrics()
        mm[2].labels(tier="disk").inc(freed)
        mm[6].labels(tier="disk").inc(
            getattr(self, "_raw_body_len", freed))
        mm[7].labels(tier="disk").inc(freed)
        return freed

    def get_batch(self, xp) -> DeviceBatch:
        """Materialize (unspilling if needed)."""
        led = _ledger()
        if led is not None:
            led.on_materialize(self.id)
        if self.closed:
            raise RuntimeError(
                f"SpillableBatch {self.id[:8]} materialized after close "
                f"(use-after-close — the hazard TPU-L013 predicts)")
        if self.tier == StorageTier.DEVICE:
            b = self._batch
            if xp is not np:
                return b
            return b
        from .meta import deserialize_batch
        if self.tier == StorageTier.HOST:
            data = self._host_bytes
        else:
            with open(self._disk_path, "rb") as f:
                data = f.read()
        batch = deserialize_batch(data, xp=xp)
        if self.catalog.unspill_enabled and xp is not np:
            self._batch = batch
            self.chip = chip_of(batch)   # it comes back on the default
            self._host_bytes = None
            if self._disk_path:
                try:
                    os.unlink(self._disk_path)
                except OSError:
                    pass
                self._disk_path = None
            self.tier = StorageTier.DEVICE
            if led is not None:
                led.on_unspill(self.id, self.device_bytes)
            tl = _timeline()
            if tl is not None:
                tl.on_unspill(self.id, self.device_bytes)
            _trace_event("spill.unspill", bytes=self.device_bytes,
                         buffer=self.id[:8])
            self.catalog.note_unspill(self)
        return batch

    def host_size(self) -> int:
        return len(self._host_bytes) if self._host_bytes else 0

    def close(self):
        if self.closed:
            return  # idempotent, like file.close()
        led = _ledger()
        if led is not None:
            led.on_close(self.id)
        tl = _timeline()
        if tl is not None:
            tl.on_close(self.id)
        self.closed = True
        self.catalog.unregister(self)
        self._batch = None
        self._host_bytes = None
        if self._disk_path:
            try:
                os.unlink(self._disk_path)
            except OSError:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _pin_handle_id(owner, key, oid: Optional[int] = None) -> str:
    """Stable ledger handle id for one pin-cache entry (pin and evict
    must name the same buffer)."""
    return f"pin-{oid if oid is not None else id(owner)}-{hash(key):x}"


class SpillCatalog:
    """Registry + tier orchestration (ref RapidsBufferCatalog)."""

    _instance: Optional["SpillCatalog"] = None
    _lock = threading.Lock()

    def __init__(self, device_budget: int = 8 << 30,
                 host_budget: int = 1 << 30,
                 spill_dir: Optional[str] = None,
                 unspill_enabled: bool = False,
                 chip_budgets: Optional[Dict[int, int]] = None):
        #: what ONE chip may hold of registered bytes; ``chip_budgets``
        #: (``device.id`` -> bytes) where the chips differ
        self.device_budget = device_budget
        self.chip_budgets = dict(chip_budgets or {})
        self.host_budget = host_budget
        self.spill_dir = spill_dir or tempfile.mkdtemp(
            prefix="spark_rapids_tpu_spill_")
        os.makedirs(self.spill_dir, exist_ok=True)
        self.unspill_enabled = unspill_enabled
        self._buffers: Dict[str, SpillableBatch] = {}
        # pinned device residents (scan pin caches): (owner_dict, key) ->
        # nbytes.  Counted against the budget and evicted FIRST under
        # pressure by dropping the owner's entry — they re-materialize
        # from host Arrow, so eviction is the cheapest possible "spill"
        # (the reference treats cached shuffle batches the same way:
        # device-resident but reclaimable, RapidsDeviceMemoryStore)
        self._pinned: Dict[tuple, int] = {}
        self._pin_owners: Dict[tuple, Dict] = {}
        self._pin_chips: Dict[tuple, Optional[int]] = {}
        self._reg_lock = threading.RLock()
        self.spilled_to_host_bytes = 0
        self.spilled_to_disk_bytes = 0
        self.pinned_evicted_bytes = 0
        # debug leak tracking (ref spark.rapids.memory.gpu.debug,
        # RapidsConf.scala:307 + Arm.scala's leak discipline): record
        # where every live buffer was registered
        self.debug = False
        self._created_at: Dict[str, str] = {}

    @classmethod
    def get(cls) -> "SpillCatalog":
        with cls._lock:
            if cls._instance is None:
                cls._instance = SpillCatalog()
            return cls._instance

    @classmethod
    def init_from_conf(cls, conf) -> "SpillCatalog":
        from .. import config as cfg
        from .device import DeviceManager
        dm = DeviceManager.get()
        device_budget = conf.get(cfg.SPILL_DEVICE_BUDGET)
        chip_budgets = None
        if device_budget is None:
            device_budget = dm.hbm_limit if dm and dm.hbm_limit > 0 \
                else 8 << 30
            if dm and dm.hbm_limit > 0:
                chip_budgets = dm.hbm_limits
        with cls._lock:
            cls._instance = SpillCatalog(
                device_budget=device_budget, chip_budgets=chip_budgets,
                host_budget=conf.get(cfg.HOST_SPILL_STORAGE_SIZE),
                spill_dir=conf.get(cfg.SPILL_DIRS).split(",")[0],
                unspill_enabled=conf.get(cfg.UNSPILL_ENABLED))
            return cls._instance

    # -- registration -------------------------------------------------------
    def register(self, batch: DeviceBatch,
                 priority: int = SpillPriority.ACTIVE) -> SpillableBatch:
        sb = SpillableBatch(batch, self, priority)
        led = _ledger()
        if led is not None:
            led.on_register(sb.id)
        with self._reg_lock:
            self._buffers[sb.id] = sb
            if self.debug:
                import traceback
                self._created_at[sb.id] = "".join(
                    traceback.format_stack(limit=8)[:-1])
        mm = _metrics()
        mm[0].inc()
        mm[1].inc(sb.device_bytes)
        self.maybe_spill()
        self._update_gauges()
        return sb

    def unregister(self, sb: SpillableBatch):
        with self._reg_lock:
            self._buffers.pop(sb.id, None)
            self._created_at.pop(sb.id, None)
        self._update_gauges()

    def _update_gauges(self) -> None:
        from ..obs import metrics as m
        if not m.enabled():
            return  # the O(buffers) sums below are not free
        mm = _metrics()
        mm[4].set(self.device_bytes_registered())
        mm[5].set(self.host_bytes_registered())

    def leak_report(self) -> List[tuple]:
        """(id, tier, bytes, provenance) for every still-open buffer —
        the debug-mode leak check (Arm.scala analog).  Provenance is the
        creation stack under spark.rapids.memory.tpu.debug; with the
        tmsan shadow ledger installed it is prefixed with the OWNING
        EXEC the ledger attributed the allocation to."""
        led = _ledger()
        with self._reg_lock:
            out = []
            for b in self._buffers.values():
                prov = self._created_at.get(
                    b.id, "(enable debug for stacks)")
                owner = led.owner_of(b.id) if led is not None else None
                if owner:
                    prov = f"owner={owner}\n{prov}"
                out.append((b.id, b.tier.name, b.device_bytes, prov))
            return out

    # -- pinned scan batches -------------------------------------------------
    def register_pinned(self, owner: Dict, key, batch_list) -> None:
        """Account a pin-cache entry (owner[key] = batches) against the
        budget of the chip it lies on and make it evictable."""
        nbytes = sum(batch_device_bytes(b) for b in batch_list)
        led = _ledger()
        if led is not None:
            led.on_pin(_pin_handle_id(owner, key), nbytes)
        tl = _timeline()
        if tl is not None:
            tl.on_pin(_pin_handle_id(owner, key), nbytes)
        with self._reg_lock:
            self._pinned[(id(owner), key)] = nbytes
            self._pin_owners[(id(owner), key)] = owner
            self._pin_chips[(id(owner), key)] = chip_of(batch_list)
        _metrics()[1].inc(nbytes)
        self.maybe_spill()
        self._update_gauges()

    def pinned_bytes(self, chip=ALL_CHIPS) -> int:
        with self._reg_lock:
            return sum(n for k, n in self._pinned.items()
                       if chip is ALL_CHIPS or self._pin_chips[k] == chip)

    def _evict_pinned(self, target_free: int, chip=ALL_CHIPS) -> int:
        freed = 0
        led = _ledger()
        tl = _timeline()
        with self._reg_lock:
            for (oid, key), nbytes in list(self._pinned.items()):
                if freed >= target_free:
                    break
                if chip is not ALL_CHIPS and \
                        self._pin_chips[(oid, key)] != chip:
                    continue
                owner = self._pin_owners.get((oid, key))
                if owner is not None:
                    owner.pop(key, None)
                if led is not None:
                    led.on_evict(_pin_handle_id(owner, key, oid))
                if tl is not None:
                    tl.on_evict(_pin_handle_id(owner, key, oid))
                self._pinned.pop((oid, key), None)
                self._pin_owners.pop((oid, key), None)
                self._pin_chips.pop((oid, key), None)
                freed += nbytes
                self.pinned_evicted_bytes += nbytes
                _trace_event("spill.evict_pinned", bytes=nbytes)
                _metrics()[3].inc()
        return freed

    def note_unspill(self, sb: SpillableBatch):
        self.maybe_spill()

    # -- accounting ---------------------------------------------------------
    def device_bytes_registered(self, chip=ALL_CHIPS) -> int:
        """Registered device-resident bytes, pinned included: of every
        chip, or of one (by ``device.id``)."""
        with self._reg_lock:
            return sum(b.device_bytes for b in self._buffers.values()
                       if b.tier == StorageTier.DEVICE and
                       (chip is ALL_CHIPS or b.chip == chip)) + \
                self.pinned_bytes(chip)

    def _chips(self) -> set:
        with self._reg_lock:
            return {b.chip for b in self._buffers.values()
                    if b.tier == StorageTier.DEVICE} | \
                set(self._pin_chips.values())

    def host_bytes_registered(self) -> int:
        with self._reg_lock:
            return sum(b.host_size() for b in self._buffers.values()
                       if b.tier == StorageTier.HOST)

    # -- spilling -----------------------------------------------------------
    def synchronous_spill(self, target_free: int, chip=ALL_CHIPS) -> int:
        """Demote device buffers (lowest priority first) until
        `target_free` bytes are released (ref synchronousSpill): of any
        chip, or of one chip's residents alone."""
        # pinned scan batches go first: dropping them frees real HBM at
        # zero serialization cost (they rebuild from host Arrow on miss)
        freed = self._evict_pinned(target_free, chip)
        with self._reg_lock:
            candidates = sorted(
                (b for b in self._buffers.values()
                 if b.tier == StorageTier.DEVICE and
                 (chip is ALL_CHIPS or b.chip == chip)),
                key=lambda b: b.priority)
            for b in candidates:
                if freed >= target_free:
                    break
                freed += b.spill_to_host()
                self.spilled_to_host_bytes += b.host_size()
            self._enforce_host_budget()
        self._update_gauges()
        return freed

    def _enforce_host_budget(self):
        used = sum(b.host_size() for b in self._buffers.values()
                   if b.tier == StorageTier.HOST)
        if used <= self.host_budget:
            return
        candidates = sorted(
            (b for b in self._buffers.values()
             if b.tier == StorageTier.HOST),
            key=lambda b: b.priority)
        for b in candidates:
            if used <= self.host_budget:
                break
            sz = b.host_size()
            self.spilled_to_disk_bytes += sz
            b.spill_to_disk()
            used -= sz

    def maybe_spill(self):
        """Each chip answers for what lies on it: one over its budget
        spills its own residents, the others keep theirs."""
        for chip in self._chips():
            over = self.device_bytes_registered(chip) - \
                self.chip_budgets.get(chip, self.device_budget)
            if over > 0:
                self.synchronous_spill(over, chip)


def is_oom_error(ex: Exception) -> bool:
    s = str(ex)
    return "RESOURCE_EXHAUSTED" in s or "Out of memory" in s or \
        "OOM" in s


def with_retry_spill(fn: Callable, catalog: Optional[SpillCatalog] = None,
                     attempts: int = 3):
    """Run a device computation; on XLA OOM, spill registered buffers and
    retry (the DeviceMemoryEventHandler analog)."""
    catalog = catalog or SpillCatalog.get()
    last = None
    for i in range(attempts):
        try:
            return fn()
        except Exception as ex:  # XlaRuntimeError etc.
            if not is_oom_error(ex):
                raise
            last = ex
            freed = catalog.synchronous_spill(catalog.device_budget)
            if freed == 0 and i > 0:
                break
    raise last
