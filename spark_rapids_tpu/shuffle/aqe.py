"""Adaptive query execution over the caching shuffle.

Ref: GpuCustomShuffleReaderExec.scala (the AQE shuffle reader the
reference substitutes into adaptive plans) + the AQE surgery in
GpuTransitionOverrides.optimizeAdaptiveTransitions.  Spark's AQE
re-plans between query stages using materialized map-output statistics;
this engine materializes a shuffle the first time any reduce partition
is requested, so the same statistics exist at exactly the same point —
the reader below consumes them to:

  * coalesce adjacent small reduce partitions up to an advisory target
    size (fewer, fuller batches downstream), and
  * split skewed partitions for shuffled hash joins: the probe side's
    blocks divide into chunks while the build side replicates, the same
    split-and-replicate shape as Spark's OptimizeSkewedJoin.

Coalesced groups keep reduce ids adjacent, so hash co-location and
range order are both preserved.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence, Tuple

from .. import config as cfg
from ..exec.base import (NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, Batch, Exec,
                         ExecContext)
from .exchange import ShuffleExchangeExec
from .manager import TpuShuffleManager


class PartitionSpec:
    """What one post-AQE partition reads from the underlying shuffle."""

    __slots__ = ("reduce_ids", "block_slice")

    def __init__(self, reduce_ids: Sequence[int],
                 block_slice: Optional[Tuple[int, int]] = None):
        self.reduce_ids = list(reduce_ids)
        self.block_slice = block_slice  # (start, end) over the blocks of a
        #                                 single skew-split reduce partition

    def describe(self) -> str:
        if self.block_slice:
            return (f"skew({self.reduce_ids[0]}:"
                    f"{self.block_slice[0]}-{self.block_slice[1]})")
        if len(self.reduce_ids) == 1:
            return str(self.reduce_ids[0])
        return f"coalesced({self.reduce_ids[0]}-{self.reduce_ids[-1]})"


def partition_stats(shuffle_id: int, n_parts: int) -> List[int]:
    """Bytes per reduce partition from the caching shuffle's catalog
    (the MapStatus sizes AQE consumes in Spark)."""
    mgr = TpuShuffleManager.get()
    sizes = []
    for rid in range(n_parts):
        total = 0
        for blk in mgr.catalog.blocks_for_reduce(shuffle_id, rid):
            for b in mgr.catalog.get(blk):
                total += getattr(b, "device_bytes", None) or \
                    getattr(b, "host_size", lambda: 0)() or 0
        sizes.append(total)
    return sizes


def coalesce_specs(sizes: Sequence[int], target: int) -> List[PartitionSpec]:
    """Greedy adjacent grouping up to the advisory size (Spark's
    ShufflePartitionsUtil.coalescePartitions)."""
    specs: List[PartitionSpec] = []
    group: List[int] = []
    acc = 0
    for rid, sz in enumerate(sizes):
        if group and acc + sz > target:
            specs.append(PartitionSpec(group))
            group, acc = [], 0
        group.append(rid)
        acc += sz
    if group:
        specs.append(PartitionSpec(group))
    return specs


def skew_split_specs(sizes: Sequence[int], n_blocks: Sequence[int],
                     factor: float, threshold: int,
                     target: int) -> Optional[List[PartitionSpec]]:
    """Split partitions larger than max(factor*median, threshold) into
    per-block-range chunks (Spark's OptimizeSkewedJoin detection rule).
    Returns None when nothing is skewed."""
    live = sorted(s for s in sizes if s > 0) or [0]
    median = live[len(live) // 2]
    cut = max(factor * median, threshold)
    out: List[PartitionSpec] = []
    any_skew = False
    for rid, sz in enumerate(sizes):
        blocks = n_blocks[rid]
        if sz > cut and blocks > 1:
            any_skew = True
            n_chunks = min(blocks, max(2, round(sz / max(target, 1))))
            per = blocks / n_chunks
            for c in range(n_chunks):
                lo, hi = round(c * per), round((c + 1) * per)
                if hi > lo:
                    out.append(PartitionSpec([rid], (lo, hi)))
        else:
            out.append(PartitionSpec([rid]))
    return out if any_skew else None


class AQEShuffleReadExec(Exec):
    """Adaptive reader over a materialized exchange
    (ref GpuCustomShuffleReaderExec.scala)."""

    def __init__(self, exchange: ShuffleExchangeExec, conf: cfg.RapidsConf,
                 replicate_for: Optional["AQEShuffleReadExec"] = None):
        super().__init__([exchange])
        self.placement = exchange.placement
        self.conf = conf
        self._specs: Optional[List[PartitionSpec]] = None
        self._lock = threading.Lock()
        # when set, this reader mirrors the partner's specs with every
        # block_slice widened to "all blocks" — the replicated build side
        # of a skew-split join
        self.replicate_for = replicate_for

    @property
    def exchange(self) -> ShuffleExchangeExec:
        return self.children[0]

    @property
    def output_names(self):
        return self.exchange.output_names

    @property
    def output_types(self):
        return self.exchange.output_types

    def describe(self):
        # the display name changed across Spark versions
        # (CustomShuffleReader in 3.0/3.1, AQEShuffleRead in 3.2 — ref
        # per-shim AQE exec naming); mirror the session's dialect
        from ..shims import active_shim
        n = len(self._specs) if self._specs is not None else "?"
        return f"{active_shim().aqe_shuffle_read_name()}({n} specs)"

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "coalesced/split reduce reads concatenate "
            "blocks in registry order; the combined row multiset per "
            "output partition is stats-determined, not arrival-"
            "determined")

    # -- spec computation ---------------------------------------------------
    def _materialize(self):
        from ..exec.base import SpeculativeSizingMiss
        ctx = ExecContext(self.conf)
        self.exchange._ensure_written(ctx)
        try:
            ctx.verify_spec_guards()
        except SpeculativeSizingMiss:
            # The map stage ran under this PRIVATE context, so its
            # guards never reach the session's speculation-retry: an
            # operator's capacity guess (a filter's armed re-bucket)
            # under this exchange undershot and the catalog now holds
            # TRUNCATED blocks.  Heal locally — drop
            # the bad shuffle and rewrite it exactly, no speculation.
            with self.exchange._write_lock:
                sid = self.exchange._shuffle_id
                self.exchange._shuffle_id = None
            if sid is not None:
                TpuShuffleManager.get().unregister(sid)
            ctx = ExecContext(self.conf)
            ctx.task_context["no_speculation"] = True
            self.exchange._ensure_written(ctx)
            ctx.verify_spec_guards()

    def specs(self) -> List[PartitionSpec]:
        with self._lock:
            if self._specs is not None:
                return self._specs
            if self.replicate_for is not None:
                partner = self.replicate_for.specs()
                self._specs = [PartitionSpec(s.reduce_ids, None)
                               for s in partner]
                return self._specs
            self._materialize()
            sid = self.exchange._shuffle_id
            n = self.exchange.num_partitions
            sizes = partition_stats(sid, n)
            # exchange boundary: the map output is measured and the
            # reduce side has not launched — the one moment a
            # misestimate can still be acted on (analysis/replan.py)
            from ..analysis.replan import on_map_stage_materialized
            on_map_stage_materialized(self, sid, sizes)
            target = self.conf.get(cfg.ADVISORY_PARTITION_SIZE)
            self._specs = coalesce_specs(sizes, target)
            return self._specs

    def set_specs(self, specs: List[PartitionSpec]):
        with self._lock:
            self._specs = list(specs)

    @property
    def num_partitions(self):
        return len(self.specs())

    # -- read ---------------------------------------------------------------
    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from ..io.scan import set_current_input_file
        from .manager import materialize_block
        spec = self.specs()[pid]
        self.exchange._ensure_written(ctx)
        # no "current file" past an exchange (ref InputFileBlockRule.scala)
        set_current_input_file("")
        mgr = TpuShuffleManager.get()
        sid = self.exchange._shuffle_id
        xp = self.xp
        from ..obs import metrics as m
        from .locality import read_reduce_blocks
        read_batches = m.counter("tpu_shuffle_read_batches_total",
                                 "reduce-side blocks read back")
        for rid in spec.reduce_ids:
            if spec.block_slice is not None:
                # skew-split chunks index the LOCAL catalog's block list
                # (skew detection never fires for remote owner groups —
                # see _SkewAwareRead.specs), so the slice path stays a
                # direct catalog read
                lo, hi = spec.block_slice
                blocks = mgr.catalog.blocks_for_reduce(sid, rid)[lo:hi]
                src = (b for blk in blocks for b in mgr.catalog.get(blk))
            else:
                # locality-aware: local blocks zero-copy, remote owner
                # groups streamed through the async fetcher
                src = read_reduce_blocks(sid, rid, conf=self.conf, xp=xp)
            for b in src:
                b = materialize_block(b, xp)
                self.metrics[NUM_OUTPUT_ROWS] += b.num_rows
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                read_batches.inc()
                yield b


def install_aqe_readers(root: Exec, conf: cfg.RapidsConf) -> Exec:
    """Post-conversion pass wrapping exchanges with adaptive readers
    (the plan surgery GpuTransitionOverrides does for adaptive plans)."""
    if not conf.get(cfg.ADAPTIVE_ENABLED):
        return root
    from ..exec.join import HashJoinExec

    def rewrite(node: Exec) -> Exec:
        new_children = [rewrite(c) for c in node.children]
        node = node.with_new_children(new_children)
        if isinstance(node, HashJoinExec):
            l, r = node.children
            if isinstance(l, ShuffleExchangeExec) and \
                    isinstance(r, ShuffleExchangeExec):
                lread = AQEShuffleReadExec(l, conf)
                if conf.get(cfg.SKEW_JOIN_ENABLED) and \
                        node.how in ("inner", "left_semi", "left_anti",
                                     "left"):
                    lread = _SkewAwareRead(l, conf)
                    rread = AQEShuffleReadExec(r, conf,
                                               replicate_for=lread)
                else:
                    rread = AQEShuffleReadExec(r, conf,
                                               replicate_for=lread)
                return node.with_new_children([lread, rread])
            return node
        new_kids = []
        changed = False
        for c in node.children:
            if isinstance(c, ShuffleExchangeExec) and \
                    _coalescable_consumer(node):
                new_kids.append(AQEShuffleReadExec(c, conf))
                changed = True
            else:
                new_kids.append(c)
        return node.with_new_children(new_kids) if changed else node

    return rewrite(root)


def relink_replicated_readers(root: Exec) -> Exec:
    """Repair ``replicate_for`` after plan surgery.  Passes downstream of
    install_aqe_readers (transition insertion, any with_new_children
    rewrite) clone nodes, so a build-side reader's ``replicate_for`` can
    end up pointing at the PRE-clone probe reader — whose exchange is an
    orphan that would shuffle the probe side a second time at execution
    and leak every block it writes (nothing in the final plan owns its
    shuffle id).  Re-point it at the probe reader actually in the tree."""
    from ..exec.base import DeviceToHostExec, HostToDeviceExec
    from ..exec.join import HashJoinExec

    def unwrap(node: Exec) -> Exec:
        while isinstance(node, (DeviceToHostExec, HostToDeviceExec)) \
                and node.children:
            node = node.children[0]
        return node

    def fix(node: Exec) -> None:
        if isinstance(node, HashJoinExec) and len(node.children) == 2:
            l, r = (unwrap(c) for c in node.children)
            if isinstance(l, AQEShuffleReadExec) and \
                    isinstance(r, AQEShuffleReadExec) and \
                    r.replicate_for is not None and r.replicate_for is not l:
                r.replicate_for = l
        for c in node.children:
            fix(c)

    fix(root)
    return root


class _SkewAwareRead(AQEShuffleReadExec):
    """Probe-side reader that also splits skewed partitions."""

    def specs(self) -> List[PartitionSpec]:
        with self._lock:
            if self._specs is not None:
                return self._specs
            self._materialize()
            sid = self.exchange._shuffle_id
            n = self.exchange.num_partitions
            mgr = TpuShuffleManager.get()
            sizes = partition_stats(sid, n)
            from ..analysis.replan import on_map_stage_materialized
            on_map_stage_materialized(self, sid, sizes)
            n_blocks = [len(mgr.catalog.blocks_for_reduce(sid, rid))
                        for rid in range(n)]
            target = self.conf.get(cfg.ADVISORY_PARTITION_SIZE)
            # skew chunks slice the local catalog's block list; sizes
            # and n_blocks are local-only stats, so with remote owner
            # groups a split would drop (or double-read) remote blocks —
            # fall back to plain coalescing there
            from .registry import BlockLocationRegistry
            remote = BlockLocationRegistry.get().remote_groups(sid)
            split = None if remote else skew_split_specs(
                sizes, n_blocks,
                self.conf.get(cfg.SKEW_JOIN_FACTOR),
                self.conf.get(cfg.SKEW_JOIN_THRESHOLD), target)
            self._specs = split if split is not None else \
                coalesce_specs(sizes, target)
            return self._specs


def _coalescable_consumer(node: Exec) -> bool:
    from ..exec.aggregate import TpuHashAggregateExec
    from ..exec.sort import SortExec
    from ..exec.window import WindowExec
    from ..exec.aggregate import CpuHashAggregateExec
    return isinstance(node, (TpuHashAggregateExec, CpuHashAggregateExec,
                             SortExec, WindowExec))
