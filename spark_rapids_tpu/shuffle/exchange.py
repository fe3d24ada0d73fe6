"""Shuffle exchange operator.

Ref: execution/GpuShuffleExchangeExec.scala:223 + GpuShuffleCoalesceExec.
Map side: compute partition ids on device (Spark-compatible murmur3 so
CPU/TPU route identically), one stable sort groups rows by target
partition, host slices by the counts vector, slices register in the
caching shuffle manager (batches stay on device — no row serialization,
the reference's core shuffle win).  Reduce side: concatenate this
partition's slices from every map task."""

from __future__ import annotations

import functools
import threading
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.device import DeviceBatch
from ..expr.core import EvalContext
from ..exec.base import (NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, TPU,
                         Batch, Exec, ExecContext, MetricTimer, process_jit,
                         schema_sig, semantic_sig)
from ..exec.concat import concat_batches
from .manager import TpuShuffleManager, materialize_block, slice_rows
from .partitioning import Partitioning, slice_batch_by_partition


class ShuffleExchangeExec(Exec):
    def __init__(self, partitioning: Partitioning, child: Exec):
        super().__init__([child])
        self.partitioning = partitioning.bind(child.output_names,
                                              child.output_types)
        self._write_lock = threading.Lock()
        self._shuffle_id: Optional[int] = None

    @property
    def output_names(self):
        return self.children[0].output_names

    @property
    def output_types(self):
        return self.children[0].output_types

    @property
    def num_partitions(self):
        return self.partitioning.num_partitions

    def describe(self):
        return f"ShuffleExchange {self.partitioning.describe()}"

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "hash routing is content-determined; block "
            "arrival order on the reduce side follows scheduling, the "
            "per-partition row multiset is invariant")

    def memory_effects(self, child_states, conf):
        """The accelerated shuffle caches every map-output block in the
        catalog (SHUFFLE priority, spill-managed) until the session
        releases the shuffle at query end: the whole exchanged dataset
        is retained, but bounded by the spill budget.  Blocks pad
        per (map, reduce) pair — maps x reduces capacity buckets, not
        one — so the model sizes a padded BLOCK and multiplies."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         spill_budget)
        if not child_states:
            return None
        st = child_states[0]
        blocks = (st.num_partitions or 1) * max(self.num_partitions, 1)
        whole = min(
            padded_partition_bytes(st.replace(num_partitions=blocks))
            * blocks, float(spill_budget(conf)))
        return MemoryEffects(hold=whole, retained=whole,
                             note="spill-managed shuffle blocks")

    def _map_batch(self, xp, batch: Batch, row_offset: int):
        ctx = EvalContext(xp, batch)
        pids = self.partitioning.partition_ids(xp, ctx, batch, row_offset)
        return slice_batch_by_partition(xp, batch, pids,
                                        self.num_partitions)

    @functools.cached_property
    def _jit_key(self):
        return ("ShuffleExchangeExec", schema_sig(self.children[0]),
                semantic_sig(self.partitioning))

    @property
    def _jit_map(self):
        return process_jit(self._jit_key,
                           lambda: lambda b, off: self._map_batch(jnp, b,
                                                                  off))

    def _ensure_written(self, ctx: ExecContext):
        with self._write_lock:
            if self._shuffle_id is not None:
                return
            from ..obs.tracer import trace_span
            with trace_span("shuffle.map_write",
                            partitions=self.num_partitions) as obs_sp:
                self._write_all(ctx, obs_sp)

    def _write_all(self, ctx: ExecContext, obs_sp):
        """Map side under one flight-recorder span: obs_sp collects the
        staged block count and device bytes for the timeline and the
        event log's shuffle-write task metric."""
        mgr = TpuShuffleManager.get()
        shuffle_id = mgr.new_shuffle_id()
        xp = self.xp
        child = self.children[0]
        # content addressing rides the session conf: the catalog digests
        # every block this write registers (tpudsan's replay oracle and
        # the fetch-side verification both key off these)
        from .. import config as cfg_dsan
        from .digest import set_digest_enabled
        set_digest_enabled(ctx.conf.get(cfg_dsan.DSAN_DIGEST_ENABLED))
        # phase 1: dispatch every map batch's partition-sort (async);
        # phase 2: ONE host sync brings back ALL count vectors (a
        # per-batch sync would stall the dispatch pipeline each time)
        staged: List[tuple] = []  # (map_id, sorted_batch, counts)
        for map_id in range(child.num_partitions):
            row_offset = 0
            for b in child.execute_partition(map_id, ctx):
                with MetricTimer(self.metrics[OP_TIME]):
                    if self.placement == TPU:
                        sorted_b, counts = self._jit_map(
                            b, np.int32(row_offset))
                    else:
                        sorted_b, counts = self._map_batch(
                            np, b, row_offset)
                staged.append((map_id, sorted_b, counts))
                row_offset += int(b.num_rows)
        if staged and self.placement == TPU:
            all_counts = np.asarray(
                jnp.stack([c for _, _, c in staged]))   # one sync
        else:
            all_counts = np.stack([np.asarray(c)
                                   for _, _, c in staged]) \
                if staged else np.zeros((0, self.num_partitions))
        from .. import config as cfg
        from ..memory.spill import batch_device_bytes
        slice_views = ctx.conf.get(cfg.SHUFFLE_SLICE_VIEWS)
        saved_bytes = 0
        if slice_views:
            # one pass per batch: the sorted batch registers ONCE as a
            # shared spillable block; each reduce partition gets a lazy
            # (start, n) view instead of an eager padded gather copy
            from ..columnar.device import DEFAULT_ROW_BUCKETS, bucket_for
            with MetricTimer(self.metrics[OP_TIME]):
                for (map_id, sorted_b, _), counts_host in zip(staged,
                                                              all_counts):
                    layout = []
                    start = 0
                    for pid_out in range(self.num_partitions):
                        n = int(counts_host[pid_out])
                        if n:
                            layout.append((pid_out, start, n))
                        start += n
                    mgr.write_map_output_sorted(shuffle_id, map_id,
                                                sorted_b, layout)
                    whole = batch_device_bytes(sorted_b)
                    bpr = whole / max(int(sorted_b.capacity), 1)
                    eager = sum(
                        bpr * bucket_for(max(n, 1), DEFAULT_ROW_BUCKETS)
                        for _, _, n in layout)
                    saved_bytes += max(0, int(eager - whole))
        else:
            per_map: Dict[int, Dict[int, List[Batch]]] = {}
            with MetricTimer(self.metrics[OP_TIME]):
                for (map_id, sorted_b, _), counts_host in zip(staged,
                                                              all_counts):
                    slices = per_map.setdefault(map_id, {})
                    start = 0
                    for pid_out in range(self.num_partitions):
                        n = int(counts_host[pid_out])
                        if n == 0:
                            continue
                        piece = _slice_rows(xp, sorted_b, start, n)
                        slices.setdefault(pid_out, []).append(piece)
                        start += n
            for map_id in range(child.num_partitions):
                slices = per_map.get(map_id, {})
                merged = {}
                for pid_out, parts in slices.items():
                    merged[pid_out] = parts[0] if len(parts) == 1 else \
                        concat_batches(xp, parts, self.output_names,
                                       self.output_types)
                mgr.write_map_output(shuffle_id, map_id, merged)
        from ..obs import metrics as m
        if obs_sp or m.enabled():
            total = sum(batch_device_bytes(b) for _, b, _ in staged)
            if obs_sp:
                obs_sp.set(shuffle_id=shuffle_id, blocks=len(staged),
                           bytes=total)
            m.counter("tpu_shuffle_write_bytes_total",
                      "device bytes staged by shuffle map writes") \
                .inc(total)
            m.counter("tpu_shuffle_write_blocks_total",
                      "map-output blocks written").inc(len(staged))
            if slice_views:
                m.counter(
                    "tpu_shuffle_write_saved_bytes_total",
                    "device bytes NOT re-staged by the one-pass "
                    "slice-view map write (vs eager per-partition "
                    "gather copies)").inc(saved_bytes)
        from .digest import digest_enabled
        if digest_enabled():
            # publish write-time digests next to the endpoint record:
            # content addressing must survive this writer's death, so
            # the registry (not just the serving catalog) carries them
            from .registry import BlockLocationRegistry
            BlockLocationRegistry.get().note_block_digests(
                shuffle_id, mgr.catalog.digests_for_shuffle(shuffle_id))
        self._shuffle_id = shuffle_id

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from ..io.scan import set_current_input_file
        self._ensure_written(ctx)
        # past an exchange there is no "current file" (Spark's
        # input_file_name() returns "" there; ref InputFileBlockRule.scala)
        set_current_input_file("")
        xp = self.xp
        from ..obs import metrics as m
        from .locality import read_reduce_blocks
        read_batches = m.counter("tpu_shuffle_read_batches_total",
                                 "reduce-side blocks read back")
        # locality-aware read: catalog blocks zero-copy, remote owner
        # groups streamed through the async fetcher (registry-driven)
        for b in read_reduce_blocks(self._shuffle_id, pid,
                                    conf=ctx.conf, xp=xp):
            b = materialize_block(b, xp)
            self.metrics[NUM_OUTPUT_ROWS] += b.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            read_batches.inc()
            yield b


# row-range slicing now lives next to the catalog's slice views
_slice_rows = slice_rows
