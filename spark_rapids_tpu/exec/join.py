"""Join operators.

Ref: sql-plugin/.../GpuHashJoin.scala:96-377 (HashJoinIterator),
JoinGatherer.scala (gather-map chunked output),
GpuShuffledHashJoinBase.scala, GpuBroadcastNestedLoopJoinExec.scala,
GpuCartesianProductExec.scala.  Sort-merge joins are replaced by hash
joins exactly like the reference (RapidsConf replaceSortMergeJoin).

TPU realization (ops/join_kernels.py): build side concatenates and its
combined 64-bit key hash sorts once; each probe batch runs a jitted
count phase (match ranges + exact output sizing incl. string bytes) and a
jitted expand phase that materializes gather maps for both sides at a
bucketed output capacity — the static-shape answer to cuDF's dynamic
gather maps.

A join's output capacity follows the data: for every probe batch the host
waits for the count program's sizes (one blocking fetch, `join.size`),
picks the output's buckets, and dispatches the expand program keyed by
them.  Parameter sets that leave a join's output in the same buckets run
the same two programs, so a warm join builds nothing.

A semi or an anti join (the selecting arm) sizes both its sides by what is
live in them: it learns its build's live rows (from the host, or one
counted fetch) and, where their bucket is at most 1/`NARROW_SHARE` of the
build's capacity, its count gathers them into that bucket before it sorts;
its selection waits for the kept count like the expansion waits for its
sizes and hands up a batch in the kept rows' bucket, gathered there under
the same rule or compacted and sliced above it.  An IN subquery's output
is a sparse build, and a semi join's output a subset of its probe.

Dead rows are kept apart by flags, never by position (`join_kernels`), so
a `FilterExec` directly under either side, or under a bare selection
there, compacts nothing: the join pulls its `execute_masked`
(`HashJoinExec.masked_sources` is the plan seam, `exec/filter_common`)
and `&`s the keep flags into the liveness lanes it builds anyway.

CpuJoinExec is an independent pyarrow Table.join implementation (CPU
fallback engine + differential oracle).
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from ..columnar.device import (DEFAULT_CHAR_BUCKETS, DEFAULT_ROW_BUCKETS,
                               DeviceBatch, DeviceColumn, batch_to_arrow,
                               batch_to_device, bucket_for, shrink_batch)
from ..expr.core import (BoundReference, EvalContext, Expression,
                         bind_expression)
from ..expr.predicates import And, EqualTo
from ..ops import join_kernels as jk
from ..ops.carry import count_join_gathers
from ..ops.gather import gather_batch, gather_column
from .base import (maybe_sync,  # noqa: F401
                   NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, Batch,
                   Exec, MetricTimer, process_jit, schema_sig, semantic_sig)
from .concat import concat_batches
from .filter_common import apply_filter, compact, gather_kept
from ..ops.scan import cumsum_fast

JOIN_TYPES = ("inner", "left", "right", "full", "left_semi", "left_anti",
              "cross")

#: a selecting join gathers a side into the bucket of its live rows where
#: that bucket is at most 1/NARROW_SHARE of the side's capacity; above it
#: the side keeps its capacity.  The search-gather costs about
#: log2(capacity) gathers an output slot: on the v5e, four of Q18's ORDERS
#: columns at 8,388,608 slots gather into 262,144 in 62 ms and into
#: 1,048,576 in 241, against 106 for the compaction at the capacity
#: (PERF.md §5, `devtools/chip_semi_narrow.py`).  A rule on what the code
#: observes, not a knob
NARROW_SHARE = 32


def _narrow_bucket(live: int, capacity: int) -> int:
    """The row bucket a selecting join gathers a side of `live` rows into,
    or 0 where that bucket is over 1/`NARROW_SHARE` of `capacity`."""
    bucket = bucket_for(live, DEFAULT_ROW_BUCKETS)
    return bucket if bucket * NARROW_SHARE <= capacity else 0


def _span_cap(x, c) -> int:
    """Output child capacity for a span column of `x` bytes or child
    rows: char bucket for strings, row bucket for array/map child rows;
    0 = not a span column."""
    if isinstance(c.dtype, (t.StringType, t.BinaryType)):
        return bucket_for(max(int(x), 1), DEFAULT_CHAR_BUCKETS)
    if isinstance(c.dtype, (t.ArrayType, t.MapType)):
        return bucket_for(max(int(x), 1), DEFAULT_ROW_BUCKETS)
    return 0


def _span_lens(xp, c):
    return (c.offsets[1:] - c.offsets[:-1]).astype(xp.int64)


def _kept_fetch(sizes, probe: Batch) -> tuple:
    """A selecting join's host sync: wait for its count's kept rows and
    pick the selection's buckets, (out_cap, probe span caps).  The span
    caps are the kept bytes' buckets where the rows are gathered
    (`_narrow_bucket`), else 0: a compaction keeps the probe's."""
    from ..columnar.fetch import fetch_array
    from ..obs.tracer import trace_span
    with trace_span("join.size") as sp:
        sizes = fetch_array(sizes)         # one round trip
        kept = int(sizes[0])
        narrow = _narrow_bucket(kept, probe.capacity)
        out_cap = narrow or min(bucket_for(kept, DEFAULT_ROW_BUCKETS),
                                probe.capacity)
        sp.set(total=kept, out_capacity=out_cap)
    return out_cap, tuple(_span_cap(x, c) if narrow else 0
                          for x, c in zip(sizes[1:], probe.columns))


def _sizing_fetch(sizes, probe: Batch, build: Batch) -> tuple:
    """The join's host sync: wait for the count's sizes and pick the
    output's buckets, (out_cap, probe span caps, build span caps)."""
    from ..columnar.fetch import fetch_array
    from ..obs import metrics as m
    from ..obs.tracer import trace_span
    with trace_span("join.size") as sp:
        sizes = fetch_array(sizes)         # one round trip
        ntotal = int(sizes[0])
        if ntotal >= (1 << 31):
            # expand_pairs builds pair offsets in int32; a wrap
            # would silently corrupt gather indices
            raise RuntimeError(
                f"join expansion of {ntotal} rows exceeds the "
                f"2^31-1 per-batch capacity; split the inputs")
        out_cap = bucket_for(max(ntotal, 1), DEFAULT_ROW_BUCKETS)
        sp.set(total=ntotal, out_capacity=out_cap)
    m.counter("tpu_join_sizing_fetches_total",
              "blocking fetches of a join's output sizes, one a probe "
              "batch").inc()
    n_p = len(probe.columns)
    return (out_cap,
            tuple(_span_cap(x, c)
                  for x, c in zip(sizes[1:1 + n_p], probe.columns)),
            tuple(_span_cap(x, c)
                  for x, c in zip(sizes[1 + n_p:], build.columns)))


def split_equi_condition(cond: Optional[Expression], left_names, right_names
                         ) -> Tuple[List[Expression], List[Expression],
                                    Optional[Expression]]:
    """Split a join condition into equi key pairs + residual
    (ref GpuHashJoin extractTopLevelAttributes / Spark's ExtractEquiJoinKeys)."""
    from ..expr.core import AttributeReference
    lset, rset = set(left_names), set(right_names)

    def refs(e: Expression):
        return {x.name for x in e.collect(
            lambda n: isinstance(n, AttributeReference))}

    conjuncts: List[Expression] = []

    def flatten(e):
        if isinstance(e, And):
            flatten(e.children[0])
            flatten(e.children[1])
        else:
            conjuncts.append(e)
    if cond is not None:
        flatten(cond)
    lkeys, rkeys, residual = [], [], []
    for c in conjuncts:
        if isinstance(c, EqualTo):
            a, b = c.children
            ra, rb = refs(a), refs(b)
            if ra <= lset and rb <= rset and ra and rb:
                lkeys.append(a)
                rkeys.append(b)
                continue
            if ra <= rset and rb <= lset and ra and rb:
                lkeys.append(b)
                rkeys.append(a)
                continue
        residual.append(c)
    res = None
    for c in residual:
        res = c if res is None else And(res, c)
    return lkeys, rkeys, res


def _gather_pairs(xp, probe: Batch, pidx, pvalid, pchar_caps,
                  build: Batch, bidx, bvalid, bchar_caps):
    """Both sides' columns through the (probe, build) pair maps, row by
    row; a traced program counts them (`join_cols_gathered`) and, of
    them, the strings that go through the span repack
    (`join_string_cols_gathered`)."""
    if xp is not np:
        count_join_gathers(list(probe.columns) + list(build.columns))
    return ([gather_column(xp, c, pidx, pvalid, cc)
             for c, cc in zip(probe.columns, pchar_caps)],
            [gather_column(xp, c, bidx, bvalid, cc)
             for c, cc in zip(build.columns, bchar_caps)])


def _live(xp, batch: Batch, keep=None):
    """bool[capacity]: the batch's rows, under a filter's `keep` flags
    where the batch came up masked."""
    live = xp.arange(batch.capacity, dtype=np.int32) < batch.num_rows
    return live if keep is None else live & keep


class HashJoinExec(Exec):
    """TPU equi-join; build side is always the right child
    (right joins are planned flipped, like the reference's build-side
    selection in GpuShuffledHashJoinBase)."""

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], how: str,
                 condition: Optional[Expression],
                 left: Exec, right: Exec, colocated: bool = False):
        super().__init__([left, right])
        assert how in JOIN_TYPES
        self.how = how
        self.colocated = colocated
        self.left_keys = [bind_expression(k, left.output_names,
                                          left.output_types)
                          for k in left_keys]
        self.right_keys = [bind_expression(k, right.output_names,
                                           right.output_types)
                           for k in right_keys]
        self.condition = condition
        self._bound_condition = (
            bind_expression(condition, self.output_names, self.output_types)
            if condition is not None else None)

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "probe-order emission: output row order "
            "follows probe-side arrival, matched multiset is invariant")

    def input_contracts(self):
        if not self.colocated:
            return None
        from ..analysis.absdomain import CoClusteredContract, key_names
        l, r = self.children
        lk = key_names(self.left_keys, l.output_names)
        rk = key_names(self.right_keys, r.output_names)
        if lk is None or rk is None:
            return None  # computed keys: no nameable clustering fact
        return CoClusteredContract(lk, rk)

    def memory_effects(self, child_states, conf):
        """The build side is concatenated into ONE raw device batch per
        probe partition (whole right side unless colocated) — not
        spill-managed, so the full build bytes count against peak; plus
        the probe's in-flight batch and the expanded output."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         total_bytes)
        if len(child_states) < 2:
            return None
        build = padded_partition_bytes(child_states[1]) if self.colocated \
            else total_bytes(child_states[1])
        # 2x build (collected batches + concat) + probe batch + output
        return MemoryEffects(
            hold=2.0 * build + 2.0 * padded_partition_bytes(
                child_states[0]) + build,
            note="raw build-side concat")

    @property
    def output_names(self):
        l, r = self.children
        if self.how in ("left_semi", "left_anti"):
            return l.output_names
        return l.output_names + r.output_names

    @property
    def output_types(self):
        l, r = self.children
        lt = list(l.output_types)
        rt = list(r.output_types)
        if self.how in ("left_semi", "left_anti"):
            return lt
        return lt + rt

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def describe(self):
        ks = ", ".join(f"{a.sql()}={b.sql()}"
                       for a, b in zip(self.left_keys, self.right_keys))
        return f"HashJoin {self.how} on [{ks}]"

    def masked_sources(self) -> tuple:
        """The plan seam that pairs filters with this join: (probe side,
        build side), each the child this join reads through
        `execute_masked` or None.  Read from the plan's shape alone, when
        the partition is pulled (`filter_common.masked_child`: the child
        is a `FilterExec`, or a bare selection over one, both on the TPU
        engine, `rebucket_cap` not armed); a key or a residual condition
        that reads a row's position takes the compacted batches."""
        from .filter_common import masked_child
        cond = () if self._bound_condition is None \
            else (self._bound_condition,)
        probe, build = self.children
        return (masked_child(self, probe, tuple(self.left_keys) + cond),
                masked_child(self, build, tuple(self.right_keys) + cond))

    # --- phase 1: count + sizing -------------------------------------------
    def _count(self, xp, build: Batch, probe: Batch,
               need_matched: bool = True, pkeep=None, bkeep=None):
        """(order, lo, counts, sizes, matched): each probe row's run of
        the hash-sorted build order, the output's sizes as ONE int64
        vector (rows, then the span bytes or child rows of every probe and
        build column), and, where a right or full join will emit the
        unmatched build rows (`need_matched`), the build rows some probe
        row matched, else None.  `pkeep` / `bkeep` are a paired filter's
        flags over the probe / the build (`masked_sources`): a row they
        drop is no row of its side, wherever it lies.  A selecting join's
        sizes are its kept rows and their span bytes (`_kept_sizes`)."""
        bctx = EvalContext(xp, build)
        pctx = EvalContext(xp, probe)
        bkeys = [k.eval(bctx).col for k in self.right_keys]
        pkeys = [k.eval(pctx).col for k in self.left_keys]
        plive = _live(xp, probe, pkeep)
        bh, bnull = jk.combined_key_hash(xp, bkeys, build.capacity)
        ph, pnull = jk.combined_key_hash(xp, pkeys, probe.capacity)
        # a null key matches nothing; a probe row that has one is still a
        # row of a left join's output
        order, lo, counts = jk.count_matches(
            xp, bh, _live(xp, build, bkeep) & ~bnull, ph, plive & ~pnull)
        if self._selects:
            return (order, lo, counts,
                    self._kept_sizes(xp, probe, counts, pkeep), None)
        outer = self.how in ("left", "full")
        eff = xp.maximum(counts, 1) if outer else counts
        eff = xp.where(plive, eff, 0)
        total = xp.sum(eff)
        # span sizing: strings count output BYTES, arrays/maps count
        # output CHILD ROWS — a row-duplicating gather must size the
        # child buffer to the duplicated total, not the source capacity
        # (a source-cap default silently truncates join expansions)
        pbytes = []
        for c in probe.columns:
            if c.offsets is not None:
                pbytes.append(xp.sum(eff * _span_lens(xp, c)))
            else:
                pbytes.append(xp.int64(0) if xp is not np else np.int64(0))
        bbytes = []
        for c in build.columns:
            if c.offsets is not None:
                sl = _span_lens(xp, c)[order]
                pre = xp.concatenate([xp.zeros((1,), xp.int64),
                                      cumsum_fast(xp, sl)])
                per = pre[lo + counts.astype(xp.int32)] - pre[lo]
                bbytes.append(xp.sum(xp.where(plive, per, 0)))
            else:
                bbytes.append(xp.int64(0) if xp is not np else np.int64(0))
        matched = jk.build_matched_flags(
            xp, order, lo, counts, plive, build.capacity) \
            if need_matched else None
        # all host-needed sizes ride ONE array so the caller pays a single
        # device round trip, not one per column
        sizes = xp.stack([xp.asarray(total, dtype=xp.int64)]
                         + [xp.asarray(x, dtype=xp.int64) for x in pbytes]
                         + [xp.asarray(x, dtype=xp.int64) for x in bbytes])
        return (order, lo, counts, sizes, matched)

    @functools.cached_property
    def _jit_key(self):
        return ("HashJoinExec", self.how,
                schema_sig(self.children[0]), schema_sig(self.children[1]),
                semantic_sig(self.left_keys),
                semantic_sig(self.right_keys),
                semantic_sig(self._bound_condition))

    @property
    def _emits_unmatched_build(self) -> bool:
        return self.how in ("right", "full")

    @property
    def _selects(self) -> bool:
        """A semi or an anti join: the count decides which probe rows
        stay, and nothing expands."""
        return self.how in ("left_semi", "left_anti")

    def _count_narrowed(self, xp, build: Batch, probe: Batch, pkeep,
                        bkeep, narrow: int):
        """`_count`; where `narrow` (a selecting join's), over the build's
        live rows gathered into that bucket first, so that nothing sorts
        the build's dead slots: the count then sees a build with no
        flags."""
        if narrow:
            build = gather_kept(xp, build, _live(xp, build, bkeep), narrow,
                                build.names)
            bkeep = None
        return self._count(xp, build, probe, self._emits_unmatched_build,
                           pkeep, bkeep)

    def _count_call(self, xp, build, probe, pkeep, bkeep, narrow=0):
        if xp is np:
            return self._count_narrowed(np, build, probe, pkeep, bkeep,
                                        narrow)
        # the flags are arguments like the batches: the key says which
        # of them this program takes.  A selecting join's count has a
        # role of its own, so that a trace tells its device time from
        # the expanding joins' (the program is the same), and a narrowed
        # one the bucket it gathers into
        fn = process_jit(
            self._jit_key + ("probe_masked",) * (pkeep is not None)
            + ("build_masked",) * (bkeep is not None)
            + ("semi_count" if self._selects else "count",)
            + ((narrow,) if narrow else ()),
            lambda: lambda b, p, pk, bk: self._count_narrowed(
                jnp, b, p, pk, bk, narrow))
        return fn(build, probe, pkeep, bkeep)

    # --- phase 2 of a semi or an anti join: selection -----------------------
    def _kept(self, xp, probe: Batch, counts, pkeep=None):
        """The probe's live rows that found a match (`left_semi`) or none
        (`left_anti`): a null key matched nothing."""
        hit = counts > 0
        return (hit if self.how == "left_semi" else ~hit) \
            & _live(xp, probe, pkeep)

    def _kept_sizes(self, xp, probe: Batch, counts, pkeep=None):
        """ONE int64 vector: the kept rows, then each probe column's
        kept span bytes or child rows (0 for a column with none)."""
        keep = self._kept(xp, probe, counts, pkeep)
        return xp.stack(
            [xp.sum(keep.astype(xp.int64))]
            + [xp.sum(xp.where(keep, _span_lens(xp, c), 0))
               if c.offsets is not None else xp.int64(0)
               for c in probe.columns])

    def _select(self, xp, probe: Batch, counts, caps: tuple,
                pkeep=None) -> Batch:
        """The kept rows (`_kept`) in their order, each once however many
        build rows hold its key, at the buckets `caps` = (out_cap, probe
        span caps) that `_kept_fetch` picked: gathered there where
        `out_cap` is a narrowed bucket, else compacted at the probe's
        capacity and sliced to `out_cap` (the rows are at the front)."""
        out_cap, char_caps = caps
        keep = self._kept(xp, probe, counts, pkeep)
        if _narrow_bucket(out_cap, probe.capacity):
            return gather_kept(xp, probe, keep, out_cap, self.output_names,
                               char_caps)
        return shrink_batch(compact(xp, probe, keep, self.output_names),
                            out_cap)

    def _select_call(self, xp, probe, counts, caps: tuple,
                     pkeep=None) -> Batch:
        if xp is np:
            return self._select(np, probe, counts, caps, pkeep)
        fn = process_jit(
            self._jit_key + ("probe_masked",) * (pkeep is not None)
            + ("semi",) + caps,
            lambda: lambda p, c, pk: self._select(jnp, p, c, caps, pk))
        return fn(probe, counts, pkeep)

    # --- phase 2: expansion -------------------------------------------------
    def _expand(self, xp, build: Batch, probe: Batch, order, lo, counts,
                out_cap: int, pchar_caps, bchar_caps, pkeep=None) -> Batch:
        plive = _live(xp, probe, pkeep)
        (pidx, bidx, pair_valid, pvalid, bvalid, total) = jk.expand_pairs(
            xp, order, lo, counts, plive, out_cap, self.how)
        lcols, rcols = _gather_pairs(xp, probe, pidx, pvalid, pchar_caps,
                                     build, bidx, bvalid, bchar_caps)
        return DeviceBatch(lcols + rcols, total, self.output_names)

    def _expand_sized(self, xp, build: Batch, probe: Batch, order, lo,
                      counts, caps: tuple, pkeep=None) -> Batch:
        """Phase 2 at the buckets `caps` = (out_cap, probe span caps,
        build span caps) that `_sizing_fetch` picked: the pairs, the
        columns gathered through them, the residual condition.  `pkeep`:
        a dropped probe row emits nothing, no null-extended row either
        (the build's flags did their work in the count: a dropped build
        row is in no probe row's run)."""
        out_cap, pchar_caps, bchar_caps = caps
        if self._bound_condition is not None and self.how == "left":
            # its output never exceeds the sizing bound (eff counts
            # already include the null-extension rows, and the repair
            # only shrinks)
            return self._expand_left_cond(xp, build, probe, order, lo,
                                          counts, out_cap, pchar_caps,
                                          bchar_caps, pkeep)
        out = self._expand(xp, build, probe, order, lo, counts,
                           out_cap, pchar_caps, bchar_caps, pkeep)
        if self._bound_condition is not None and self.how == "inner":
            pctx = EvalContext(xp, out)
            out = apply_filter(xp, out, self._bound_condition.eval(pctx),
                               self.output_names)
        return out

    def _expand_call(self, xp, build, probe, order, lo, counts,
                     caps: tuple, pkeep=None) -> Batch:
        if xp is np:
            return self._expand_sized(np, build, probe, order, lo, counts,
                                      caps, pkeep)
        fn = process_jit(
            self._jit_key + ("probe_masked",) * (pkeep is not None)
            + ("expand",) + caps,
            lambda: lambda b, p, o, l, c, pk: self._expand_sized(
                jnp, b, p, o, l, c, caps, pk))
        return fn(build, probe, order, lo, counts, pkeep)

    # --- conditional left join ---------------------------------------------
    def _expand_left_cond(self, xp, build: Batch, probe: Batch, order, lo,
                          counts, out_cap: int, pchar_caps, bchar_caps,
                          pkeep=None) -> Batch:
        """LEFT join with a residual condition, one traced function:
        expand all candidate pairs, evaluate the condition, keep passing
        pairs, and REPAIR probe rows whose candidates all failed — their
        first pair survives with the build side nulled (Spark's outer
        conditional-join semantics; ref GpuHashJoin's post-filter with
        unmatched-row emission, GpuOverrides.scala:3352-3355)."""
        from ..ops.carry import mask_validity
        plive = _live(xp, probe, pkeep)
        (pidx, bidx, pair_valid, pvalid, bvalid, total) = jk.expand_pairs(
            xp, order, lo, counts, plive, out_cap, "left")
        lcols, rcols = _gather_pairs(xp, probe, pidx, pvalid, pchar_caps,
                                     build, bidx, bvalid, bchar_caps)
        out = DeviceBatch(lcols + rcols, total, self.output_names)
        ctx = EvalContext(xp, out)
        v = self._bound_condition.eval(ctx)
        from ..expr.core import ColumnValue, make_column
        if not isinstance(v, ColumnValue):
            v = make_column(ctx, self._bound_condition.data_type(),
                            v.value if v.value is not None else False,
                            None if v.value is not None else False)
        passes = v.col.data.astype(bool)
        if v.col.validity is not None:
            passes = passes & v.col.validity
        real = counts.astype(xp.int32)[pidx] > 0     # vs synthesized null
        pred_true = passes & real & pair_valid
        if xp is np:
            pass_cnt = np.zeros((probe.capacity,), np.int32)
            np.add.at(pass_cnt, np.clip(pidx, 0, probe.capacity - 1),
                      pred_true.astype(np.int32))
        else:
            pass_cnt = xp.zeros((probe.capacity,), xp.int32).at[pidx].add(
                pred_true.astype(xp.int32), mode="drop")
        # pairs are emitted grouped per probe row, so a boundary marks
        # each row's first candidate
        first = xp.concatenate(
            [xp.ones((1,), bool), pidx[1:] != pidx[:-1]]) & pair_valid
        convert = first & real & (pass_cnt[pidx] == 0)
        keep = pair_valid & (~real | pred_true | convert)
        null_build = ~real | convert
        nb = len(probe.columns)
        fixed = list(out.columns[:nb]) + [
            mask_validity(xp, c, ~null_build) for c in out.columns[nb:]]
        out = DeviceBatch(fixed, total, self.output_names)
        return compact(xp, out, keep, self.output_names)

    # --- unmatched build rows for right/full --------------------------------
    def _unmatched_build(self, xp, build: Batch, matched_any,
                         bkeep=None) -> Batch:
        keep = _live(xp, build, bkeep) & ~matched_any
        compacted = compact(xp, build, keep, self.children[1].output_names)
        n = compacted.num_rows
        from ..expr.core import EvalContext as EC, all_null_column
        ctx = EC(xp, compacted)
        lcols = [all_null_column(ctx, dt).col
                 for dt in self.children[0].output_types]
        return DeviceBatch(lcols + list(compacted.columns), n,
                           self.output_names)

    def _collect_build(self, pid, ctx) -> tuple:
        """Materialize the build side as ONE device batch: this
        partition's co-clustered shard when colocated, the whole right
        side otherwise.  Returns (the batch, a paired filter's keep flags
        over it or None, its live rows: a host int or a device scalar).
        Where the build comes up masked (`masked_sources`) in ONE batch,
        as every resident table does, it stays where it lay and the flags
        go with it, their count (the mask program's) beside them; several
        masked batches are compacted each under its flags and
        concatenated like any others (`concat_batches` packs live
        prefixes)."""
        xp = self.xp
        right = self.children[1]
        source = self.masked_sources()[1]
        build_batches = []
        if self.colocated:
            build_pids = [pid]
        else:
            build_pids = list(range(right.num_partitions))
        for bpid in build_pids:
            build_batches += list(
                right.execute_partition(bpid, ctx) if source is None
                else source.execute_masked(bpid, ctx, self))
        if source is not None:
            if len(build_batches) == 1:
                m, = build_batches
                return m.batch, m.keep, m.num_rows
            build_batches = [self._compact_build(m.batch, m.keep)
                             for m in build_batches]
        if not build_batches:
            from ..columnar.interop import to_arrow_schema
            schema = to_arrow_schema(right.output_names, right.output_types)
            rb = pa.RecordBatch.from_pydict(
                {n: pa.array([], type=f.type)
                 for n, f in zip(schema.names, schema)})
            build_batches = [batch_to_device(rb, xp=xp)]
        build = (concat_batches(xp, build_batches, right.output_names,
                                right.output_types)
                 if len(build_batches) > 1 else build_batches[0])
        return build, None, build.num_rows

    def _compact_build(self, batch: Batch, keep) -> Batch:
        """One of several masked build batches, its kept rows moved to
        the front for the concatenation."""
        names = self.children[1].output_names
        if self.xp is np:
            return compact(np, batch, keep, names)
        return process_jit(
            ("HashJoinExec", schema_sig(self.children[1]), "compact_build"),
            lambda: lambda b, k: compact(jnp, b, k, names))(batch, keep)

    def _probe_batch(self, build: Batch, probe: Batch, pkeep=None,
                     bkeep=None, narrow=0):
        """One probe batch against the build: (the joined batch, the build
        rows it matched or None).  `pkeep` / `bkeep`: the keep flags of
        the filters the plan paired with either side; `narrow`: the
        bucket a selecting join's count gathers the build's live rows
        into, or 0.  The span `join.probe` covers the dispatch of its
        programs and, inside it, `join.size` the wait for the sizes: an
        expanding join's output sizes (`_sizing_fetch`), a selecting
        join's kept rows (`_kept_fetch`, path `select`: its second
        program, role `semi`, hands up the kept rows in their bucket,
        `out_capacity`).  `sorted_slots` is what the count's one sort
        covers: the probe's capacity and the build's, narrowed where it
        was, whatever is live in them."""
        from ..obs import metrics as m
        from ..obs.tracer import trace_span
        xp = self.xp
        sorted_slots = int(probe.capacity) + int(narrow or build.capacity)
        with trace_span("join.probe", how=self.how,
                        probe_capacity=int(probe.capacity),
                        build_capacity=int(build.capacity),
                        sorted_slots=sorted_slots,
                        probe_masked=pkeep is not None) as sp:
            order, lo, counts, sizes, matched = self._count_call(
                xp, build, probe, pkeep, bkeep, narrow)
            if self._selects:
                caps = _kept_fetch(sizes, probe)
                out, path = self._select_call(xp, probe, counts, caps,
                                              pkeep), "select"
                narrowed = ("build",) * bool(narrow) + ("output",) * bool(
                    _narrow_bucket(caps[0], probe.capacity))
            else:
                caps = _sizing_fetch(sizes, probe, build)
                out, path = self._expand_call(xp, build, probe, order, lo,
                                              counts, caps, pkeep), \
                    "two_phase"
                narrowed = ()
            sp.set(out_capacity=caps[0], path=path)
        m.counter("tpu_join_probe_batches_total",
                  "probe batches joined, by how the output was sized: "
                  "two_phase (a blocking fetch of the count's sizes, then "
                  "the expansion at their buckets), select (semi and anti "
                  "joins: a blocking fetch of the kept rows, then the "
                  "selection at their bucket)",
                  ("path",)).labels(path=path).inc()
        m.counter("tpu_join_sorted_slots_total",
                  "slots the joins' count programs sorted: at each probe "
                  "batch the probe's capacity and the build's, the "
                  "narrowed one where a semi or an anti join gathered "
                  "its live rows").inc(sorted_slots)
        for side in narrowed:
            m.counter("tpu_join_narrowed_total",
                      "sides a semi or an anti join gathered into the "
                      "smaller bucket of their live rows: the build "
                      "before its count, the output after it",
                      ("side",)).labels(side=side).inc()
        return out, matched

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from ..columnar.fetch import fetch_array
        from ..obs.tracer import trace_span
        xp = self.xp
        narrow = 0
        with trace_span("join.build") as sp:
            build, bkeep, live = self._collect_build(pid, ctx)
            # (a build that a filter or a join made keeps its row count
            # on the device: the span does not wait for it; under a mask
            # the batch's own count is the unfiltered one, and left out)
            if bkeep is None and isinstance(build.num_rows,
                                            (int, np.integer)):
                sp.set(rows=int(build.num_rows))
            sp.set(capacity=int(build.capacity), masked=bkeep is not None)
            if self._selects:
                # a selecting join sizes its build by what is live in it:
                # the host's count, or one counted fetch of the device's
                live = int(fetch_array(live))
                narrow = _narrow_bucket(live, build.capacity)
                sp.set(live_rows=live,
                       narrowed_capacity=narrow or int(build.capacity))
        matched_acc = None
        # the probe's batches: (batch,), or (batch, keep flags) from the
        # filter the plan paired with this side, which then compacts
        # nothing
        source = self.masked_sources()[0]
        if source is not None:
            probes = ((m.batch, m.keep)
                      for m in source.execute_masked(pid, ctx, self))
        else:
            probes = ((b,) for b in
                      self.children[0].execute_partition(pid, ctx))
        for probe in probes:
            with MetricTimer(self.metrics[OP_TIME]):
                out, matched = self._probe_batch(build, *probe, bkeep=bkeep,
                                                 narrow=narrow)
                if matched is not None:
                    matched_acc = matched if matched_acc is None else \
                        (matched_acc | matched)
                maybe_sync(out)
            self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
            self.metrics[NUM_OUTPUT_BATCHES] += 1
            yield out
        if matched_acc is not None:
            out = self._unmatched_build(xp, build, matched_acc, bkeep)
            if int(out.num_rows):
                yield out


class ShuffledHashJoinExec(HashJoinExec):
    """Co-partitioned hash join over spill-backed shuffle catalog
    partitions (ref GpuShuffledHashJoinExec.scala).

    Both children are hash-exchanged on the join keys (declared via
    ``CoClusteredContract``), so partition ``pid`` joins ONLY its own
    shard on each side — the build side is one catalog partition, not
    the whole table, which is what lets joins scale past single-device
    memory: the exchanged blocks are spill-managed (DEVICE->HOST->DISK),
    and the build materialization retries under synchronous spill when
    concatenating a shard would overflow HBM.  On a mesh, this node
    rewrites further into IciJoinExec (in-shard all_to_all); this class
    is the single-host / DCN realization."""

    def __init__(self, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression], how: str,
                 condition: Optional[Expression],
                 left: Exec, right: Exec, colocated: bool = True):
        # co-partitioning is this node's reason to exist
        super().__init__(left_keys, right_keys, how, condition, left,
                         right, colocated=True)

    def describe(self):
        ks = ", ".join(f"{a.sql()}={b.sql()}"
                       for a, b in zip(self.left_keys, self.right_keys))
        return f"ShuffledHashJoin {self.how} on [{ks}]"

    def memory_effects(self, child_states, conf):
        """One co-clustered shard per side is live at a time; the rest
        of both exchanged datasets is shuffle retention already modeled
        (and spill-bounded) by the exchange children.  The shard's
        concat + expand still holds raw device bytes, so the bound keeps
        the parent's 2x-build + probe + output shape — over one
        partition, not the whole build side."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes)
        if len(child_states) < 2:
            return None
        build = padded_partition_bytes(child_states[1])
        probe = padded_partition_bytes(child_states[0])
        return MemoryEffects(
            hold=2.0 * build + 2.0 * probe + build,
            note="co-partitioned spill-backed build shard")

    def _collect_build(self, pid, ctx) -> tuple:
        """Materialize this partition's build shard under OOM-retry:
        running out of device memory synchronously spills lower-priority
        registrations (shuffle blocks first) and tries again, instead of
        failing the join."""
        from ..memory.spill import SpillCatalog, with_retry_spill
        return with_retry_spill(
            lambda: super(ShuffledHashJoinExec, self)._collect_build(
                pid, ctx),
            SpillCatalog.get())


class NestedLoopJoinExec(Exec):
    """Cross product + optional condition (ref
    GpuBroadcastNestedLoopJoinExec / GpuCartesianProductExec)."""

    def __init__(self, how: str, condition: Optional[Expression],
                 left: Exec, right: Exec):
        super().__init__([left, right])
        self.how = how
        self.condition = condition
        self._bound_condition = (
            bind_expression(condition, self.output_names, self.output_types)
            if condition is not None else None)

    @property
    def output_names(self):
        return self.children[0].output_names + self.children[1].output_names

    @property
    def output_types(self):
        return self.children[0].output_types + self.children[1].output_types

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def determinism(self):
        from ..analysis.determinism import Determinism, ORDER_STABLE
        return Determinism(
            ORDER_STABLE, "cross-product emission order follows both "
            "sides' arrival; matched multiset is invariant")

    def memory_effects(self, child_states, conf):
        """Collects the whole right side raw per probe partition, and
        the cross-product output amplifies: both sides' bytes plus the
        expanded batch count against peak."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         total_bytes)
        if len(child_states) < 2:
            return None
        return MemoryEffects(
            hold=3.0 * total_bytes(child_states[1]) +
            2.0 * padded_partition_bytes(child_states[0]),
            note="raw build-side concat")

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        xp = self.xp
        right = self.children[1]
        rbatches = []
        for rp in range(right.num_partitions):
            rbatches += list(right.execute_partition(rp, ctx))
        if not rbatches:
            return
        build = concat_batches(xp, rbatches, right.output_names,
                               right.output_types) if len(rbatches) > 1 \
            else rbatches[0]
        nb = int(build.num_rows)
        for probe in self.children[0].execute_partition(pid, ctx):
            np_rows = int(probe.num_rows)
            total = np_rows * nb
            out_cap = bucket_for(max(total, 1), DEFAULT_ROW_BUCKETS)
            pidx = xp.arange(out_cap, dtype=np.int32) // max(nb, 1)
            bidx = xp.arange(out_cap, dtype=np.int32) % max(nb, 1)
            valid = xp.arange(out_cap, dtype=np.int32) < total
            pchar = [int(c.data.shape[0]) * max(nb, 1)
                     if isinstance(c.dtype, (t.StringType, t.BinaryType))
                     else 0 for c in probe.columns]
            bchar = [int(c.data.shape[0]) * max(np_rows, 1)
                     if isinstance(c.dtype, (t.StringType, t.BinaryType))
                     else 0 for c in build.columns]
            lcols = [gather_column(xp, c, pidx, valid,
                                   bucket_for(max(cc, 1),
                                              DEFAULT_CHAR_BUCKETS)
                                   if cc else 0)
                     for c, cc in zip(probe.columns, pchar)]
            rcols = [gather_column(xp, c, bidx, valid,
                                   bucket_for(max(cc, 1),
                                              DEFAULT_CHAR_BUCKETS)
                                   if cc else 0)
                     for c, cc in zip(build.columns, bchar)]
            out = DeviceBatch(lcols + rcols, total, self.output_names)
            if self._bound_condition is not None:
                ectx = EvalContext(xp, out)
                out = apply_filter(xp, out, self._bound_condition.eval(ectx),
                                   self.output_names)
            yield out


# ---------------------------------------------------------------------------
# CPU fallback: pyarrow Table.join
# ---------------------------------------------------------------------------

_PA_JOIN = {"inner": "inner", "left": "left outer", "right": "right outer",
            "full": "full outer", "left_semi": "left semi",
            "left_anti": "left anti"}


class CpuJoinExec(Exec):
    def __init__(self, left_keys, right_keys, how, condition,
                 left: Exec, right: Exec, colocated: bool = False):
        super().__init__([left, right])
        self.how = how
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.condition = condition
        self.colocated = colocated

    @property
    def output_names(self):
        l, r = self.children
        if self.how in ("left_semi", "left_anti"):
            return l.output_names
        return l.output_names + r.output_names

    @property
    def output_types(self):
        l, r = self.children
        if self.how in ("left_semi", "left_anti"):
            return list(l.output_types)
        return list(l.output_types) + list(r.output_types)

    @property
    def num_partitions(self):
        return self.children[0].num_partitions

    def describe(self):
        return f"CpuJoin {self.how}"

    def _collect_side(self, side: int, ctx, pid=None) -> pa.Table:
        child = self.children[side]
        rbs = []
        pids = range(child.num_partitions) if pid is None else [pid]
        for p in pids:
            for b in child.execute_partition(p, ctx):
                rb = batch_to_arrow(DeviceBatch(b.columns, b.num_rows,
                                                child.output_names))
                if rb.num_rows:
                    rbs.append(rb)
        from ..columnar.interop import to_arrow_schema
        schema = to_arrow_schema(child.output_names, child.output_types)
        if not rbs:
            return schema.empty_table()
        return pa.Table.from_batches([r.cast(schema) for r in rbs])

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        import pyarrow.compute as pc
        left = self._collect_side(0, ctx, pid)
        right = self._collect_side(1, ctx, pid if self.colocated else None)
        # materialize key columns (they may be expressions)
        lkn, rkn = [], []
        lt, rt = left, right
        for i, (lk, rk) in enumerate(zip(self.left_keys, self.right_keys)):
            ln_, rn_ = f"__lk{i}", f"__rk{i}"
            lt = lt.append_column(ln_, _eval_arrow(lk, left,
                                                   self.children[0]))
            rt = rt.append_column(rn_, _eval_arrow(rk, right,
                                                   self.children[1]))
            lkn.append(ln_)
            rkn.append(rn_)
        # avoid output name collisions: temporarily rename
        lnames = [f"l_{i}" for i in range(len(left.schema.names))]
        rnames = [f"r_{i}" for i in range(len(right.schema.names))]
        lt = lt.rename_columns(lnames + lkn)
        rt = rt.rename_columns(rnames + rkn)
        # Spark equi-joins never match null keys; split them out so Acero's
        # null handling can't differ
        def null_key_mask(tbl, keys):
            m = None
            for k in keys:
                kn = pc.is_null(tbl.column(k))
                m = kn if m is None else pc.or_(m, kn)
            return m
        l_null = null_key_mask(lt, lkn)
        r_null = null_key_mask(rt, rkn)
        lt_nn = lt.filter(pc.invert(l_null)) if l_null is not None else lt
        rt_nn = rt.filter(pc.invert(r_null)) if r_null is not None else rt
        joined = lt_nn.join(rt_nn, keys=lkn, right_keys=rkn,
                            join_type=_PA_JOIN[self.how],
                            coalesce_keys=False, use_threads=False)
        if self.how in ("left_semi", "left_anti"):
            out = joined.select(lnames).rename_columns(
                self.children[0].output_names)
            if self.how == "left_anti" and l_null is not None:
                extra = lt.filter(l_null).select(lnames).rename_columns(
                    self.children[0].output_names)
                out = pa.concat_tables([out, extra]) if extra.num_rows else out
        else:
            out = joined.select(lnames + rnames).rename_columns(
                self.output_names)
            if self.how in ("left", "full") and l_null is not None:
                nulls_l = lt.filter(l_null).select(lnames)
                if nulls_l.num_rows:
                    pad = {n: pa.nulls(nulls_l.num_rows, f.type)
                           for n, f in zip(rnames,
                                           [rt.schema.field(x)
                                            for x in rnames])}
                    extra = nulls_l.rename_columns(
                        self.children[0].output_names)
                    for (n, arr), on in zip(pad.items(),
                                            self.children[1].output_names):
                        extra = extra.append_column(on, arr)
                    out = pa.concat_tables(
                        [out, extra.rename_columns(self.output_names)])
            if self.how in ("right", "full") and r_null is not None:
                nulls_r = rt.filter(r_null).select(rnames)
                if nulls_r.num_rows:
                    extra = pa.table(
                        {n: pa.nulls(nulls_r.num_rows,
                                     lt.schema.field(ln).type)
                         for n, ln in zip(self.children[0].output_names,
                                          lnames)})
                    for arr, on in zip(nulls_r.columns,
                                       self.children[1].output_names):
                        extra = extra.append_column(on, arr)
                    out = pa.concat_tables(
                        [out, extra.rename_columns(self.output_names)])
        if self.condition is not None:
            if self.how == "inner":
                mask = _eval_arrow(self.condition, out, self)
                out = out.filter(mask)
            elif self.how == "left":
                # conditional LEFT: keep matched pairs passing the
                # condition; probe rows with no passing pair emit once,
                # build side nulled (Spark's outer-join semantics)
                out = _left_conditional_impl(self, lt, rt, lkn, rkn,
                                             lnames, rnames, l_null,
                                             r_null)
            else:
                raise NotImplementedError(
                    f"conditional {self.how} join on CPU engine")
        from ..columnar.interop import to_arrow_schema
        schema = to_arrow_schema(self.output_names, self.output_types)
        out = out.cast(schema)
        for rb in out.combine_chunks().to_batches():
            yield batch_to_device(rb, xp=np)


def _left_conditional_impl(join_exec: "CpuJoinExec", lt, rt, lkn, rkn,
                           lnames, rnames, l_null, r_null) -> pa.Table:
    """Conditional LEFT join on the CPU oracle: re-join with a probe row
    id and a build marker, filter pairs by the condition, and null-extend
    every probe row without a passing pair."""
    import pyarrow.compute as pc
    lt2 = lt.append_column(
        "__pid__", pa.array(np.arange(lt.num_rows, dtype=np.int64)))
    rt2 = rt.append_column(
        "__bmark__", pa.array(np.ones(rt.num_rows, dtype=np.int8)))
    l_nn = lt2.filter(pc.invert(l_null)) if l_null is not None else lt2
    r_nn = rt2.filter(pc.invert(r_null)) if r_null is not None else rt2
    joined = l_nn.join(r_nn, keys=lkn, right_keys=rkn,
                       join_type="left outer", coalesce_keys=False,
                       use_threads=False)
    mask = _eval_arrow(
        join_exec.condition,
        joined.select(lnames + rnames).rename_columns(
            join_exec.output_names),
        join_exec)
    if isinstance(mask, pa.ChunkedArray):
        mask = mask.combine_chunks()
    mask = pc.fill_null(mask, False)
    real = pc.is_valid(joined.column("__bmark__"))
    passing = pc.and_(mask, real)
    pass_rows = joined.filter(passing)
    # pure host data (pyarrow chunked arrays), no device crossing here
    passed = np.unique(pass_rows.column("__pid__").combine_chunks()
                       .to_numpy(zero_copy_only=False))
    all_pids = lt2.column("__pid__").combine_chunks() \
        .to_numpy(zero_copy_only=False)
    missing = lt2.take(np.flatnonzero(~np.isin(all_pids, passed)))
    out = pass_rows.select(lnames + rnames)
    if missing.num_rows:
        pad = missing.select(lnames)
        for rn_ in rnames:
            pad = pad.append_column(
                rn_, pa.nulls(missing.num_rows,
                              rt.schema.field(rn_).type))
        out = pa.concat_tables([out, pad])
    return out.rename_columns(join_exec.output_names)


def _eval_arrow(expr: Expression, table: pa.Table, child_like) -> pa.Array:
    """Evaluate an expression over an arrow table via the numpy engine."""
    from ..columnar.device import batch_to_device, column_to_arrow
    from ..expr.core import ColumnValue, EvalContext, make_column
    names = child_like.output_names
    dtypes = child_like.output_types
    tbl = table.rename_columns(names) if list(table.schema.names) != names \
        else table
    tbl = tbl.combine_chunks()
    rbs = tbl.to_batches() or [pa.RecordBatch.from_pydict(
        {n: pa.array([], type=f.type) for n, f in
         zip(tbl.schema.names, tbl.schema)})]
    outs = []
    bound = bind_expression(expr, names, dtypes)
    for rb in rbs:
        b = batch_to_device(rb, xp=np)
        ec = EvalContext(np, b)
        v = bound.eval(ec)
        if not isinstance(v, ColumnValue):
            v = make_column(ec, bound.data_type(),
                            v.value if v.value is not None else 0,
                            None if v.value is not None else False)
        outs.append(column_to_arrow(v.col, rb.num_rows))
    return pa.chunked_array(outs) if len(outs) > 1 else outs[0]


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def plan_join(lp, left: Exec, right: Exec, conf) -> Exec:
    """Logical Join -> physical (ref GpuOverrides join rules +
    ExtractEquiJoinKeys)."""
    from ..expr.core import AttributeReference, Alias
    from ..plan import logical as L
    how = lp.how
    cond = lp.condition
    using = lp.using
    if using:
        c = None
        for k in using:
            eq = EqualTo(AttributeReference(k), AttributeReference(k))
            # disambiguate: bind left occurrence to left, right to right
            c = eq if c is None else And(c, eq)
        lkeys = [AttributeReference(k) for k in using]
        rkeys = [AttributeReference(k) for k in using]
        residual = None
    else:
        lkeys, rkeys, residual = split_equi_condition(
            cond, left.output_names, right.output_names)
    from ..config import AUTO_BROADCAST_JOIN_THRESHOLD
    threshold = conf.get(AUTO_BROADCAST_JOIN_THRESHOLD)
    lsz = left.estimated_size_bytes()
    rsz = right.estimated_size_bytes()

    # ---- build-side selection (ref GpuShuffledHashJoinBase build side +
    # Spark's broadcast side selection): the build side is always planned
    # as the RIGHT child; flip when the join type forces it (right outer)
    # or when an inner join's smaller side is on the left.
    flipped = False

    def flip():
        nonlocal left, right, lkeys, rkeys, lsz, rsz, flipped, how
        left, right = right, left
        lkeys, rkeys = rkeys, lkeys
        lsz, rsz = rsz, lsz
        flipped = not flipped

    if how == "right" and lkeys:
        flip()
        how = "left"
    elif how == "inner" and lkeys and lsz is not None and rsz is not None \
            and lsz < rsz:
        flip()

    multi = left.num_partitions > 1 or right.num_partitions > 1

    # ---- non-equi paths (nested loop); broadcast the build side so it is
    # collected once, not once per probe partition
    # (ref GpuBroadcastNestedLoopJoinExec / GpuCartesianProductExec)
    if not lkeys:
        from .broadcast import BroadcastExchangeExec, \
            BroadcastNestedLoopJoinExec
        if how == "cross" or (how == "inner" and cond is not None):
            r = BroadcastExchangeExec(right) if multi else right
            cls = BroadcastNestedLoopJoinExec if multi else NestedLoopJoinExec
            return cls("cross" if how == "cross" else how, cond, left, r)
        if how == "inner" and cond is None:
            r = BroadcastExchangeExec(right) if multi else right
            cls = BroadcastNestedLoopJoinExec if multi else NestedLoopJoinExec
            return cls("cross", None, left, r)
        raise NotImplementedError(
            f"non-equi {how} join is not supported yet")

    # ---- equi joins: broadcast-hash vs shuffled-hash.  The bridge pins
    # oversized-build joins to the shuffled path (force_shuffled): their
    # build side exceeded the broadcast/collect threshold, so the only
    # scalable plan is co-partitioning both sides through the
    # spill-backed shuffle catalog.
    force_shuffled = bool(getattr(lp, "force_shuffled", False))
    colocated = False
    if multi and not force_shuffled and threshold >= 0 \
            and rsz is not None and rsz <= threshold \
            and how in ("inner", "left", "left_semi", "left_anti", "cross"):
        from .broadcast import BroadcastExchangeExec
        right = BroadcastExchangeExec(right)
    elif multi or force_shuffled:
        # shuffled hash join: co-partition both sides on the join keys
        from ..shuffle.exchange import ShuffleExchangeExec
        from ..shuffle.partitioning import HashPartitioning
        n = max(left.num_partitions, right.num_partitions)
        left = ShuffleExchangeExec(HashPartitioning(lkeys, n), left)
        right = ShuffleExchangeExec(HashPartitioning(rkeys, n), right)
        colocated = True

    join: Exec = CpuJoinExec(lkeys, rkeys, how, residual, left, right,
                             colocated=colocated)
    out_exec = join
    if flipped or using:
        from .basic import ProjectExec
        names = join.output_names
        types = join.output_types
        nl = len(left.output_names)
        if flipped:
            # output order: original-left (= current right side) first
            exprs = [BoundReference(nl + i, types[nl + i], names[nl + i])
                     for i in range(len(right.output_names))] + \
                    [BoundReference(i, types[i], names[i])
                     for i in range(nl)]
            out_exec = ProjectExec(
                [Alias(e, e.name) for e in exprs], join)
            names = out_exec.output_names
            types = out_exec.output_types
        if using and how not in ("left_semi", "left_anti"):
            from ..expr.conditional import Coalesce
            lnames = lp.children[0].schema()[0]
            rnames = lp.children[1].schema()[0]
            n_l = len(lnames)
            exprs = []
            for k in using:
                li = lnames.index(k)
                ri = n_l + rnames.index(k)
                if lp.how == "full":
                    exprs.append(Alias(Coalesce(
                        BoundReference(li, types[li], k),
                        BoundReference(ri, types[ri], k)), k))
                elif lp.how == "right":
                    exprs.append(Alias(
                        BoundReference(ri, types[ri], k), k))
                else:
                    exprs.append(Alias(
                        BoundReference(li, types[li], k), k))
            for i, n in enumerate(lnames):
                if n not in using:
                    exprs.append(Alias(BoundReference(i, types[i], n), n))
            for j, n in enumerate(rnames):
                if n not in using:
                    exprs.append(Alias(
                        BoundReference(n_l + j, types[n_l + j], n), n))
            out_exec = ProjectExec(exprs, out_exec)
    return out_exec
