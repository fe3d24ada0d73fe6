"""Hash aggregate operators.

Ref: sql-plugin/.../aggregate.scala (GpuHashAggregateExec / iterator mode
pipeline at :258-275) — re-designed for TPU as sort+segment-reduce:

  1. per batch: evaluate grouping keys + update inputs, encode keys as
     order-preserving uint64 words, stable sort (ops/carry.py),
     boundary-detect, segment-reduce every buffer, compact groups to the
     front — one jitted XLA computation per (schema, capacity);
  2. across batches: concatenate the per-batch partials and run the same
     kernel with merge ops (the analog of tryMergeAggregatedBatches);
  3. Final/Complete mode then evaluates result expressions over buffers.

The CPU-placed aggregate (`CpuHashAggregateExec`) is an independent
pyarrow `Table.group_by` implementation — it both serves as the fallback
for TPU-unsupported types and gives differential tests a second engine.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import types as t
from ..columnar.device import (DEFAULT_ROW_BUCKETS, DeviceBatch, DeviceColumn,
                               batch_to_arrow, batch_to_device, bucket_for,
                               shrink_column)
from ..expr.aggregates import (COMPLETE, FINAL, PARTIAL, AggregateExpression,
                               AggregateFunction, ApproximatePercentile,
                               Average, CollectList, CollectSet, Count,
                               First, Last, Max, Min, PivotFirst,
                               StddevPop, StddevSamp, Sum, VariancePop,
                               VarianceSamp)
from ..expr.core import (ColumnValue, EvalContext, Expression,
                         bind_expression, output_name)
from ..ops import segmented as seg
from ..ops.gather import gather_column
from .base import (NUM_OUTPUT_BATCHES, NUM_OUTPUT_ROWS, OP_TIME, TPU, Batch,
                   Exec, ExecContext, MetricTimer, maybe_sync, process_jit,
                   schema_sig, semantic_sig)
from .concat import concat_batches
from ..ops.scan import cumsum_fast


def _null_where(xp, col: DeviceColumn, valid) -> DeviceColumn:
    """A moved column as `gather_column` would have left it: null where
    `valid` is false or a struct above it is null, zero under every null
    of a row-aligned lane.  (A node with offsets was gathered, and its
    spans are settled.)"""
    v = valid if col.validity is None else valid & col.validity
    if col.has_offsets:
        return DeviceColumn(col.dtype, data=col.data, validity=v,
                            offsets=col.offsets, children=col.children)

    def zeroed(x):
        return None if x is None else xp.where(v, x,
                                               xp.zeros((), dtype=x.dtype))
    if col.fixed_width is not None:
        return col.with_word(zeroed(col.word), v)
    return DeviceColumn(col.dtype, data=zeroed(col.data), validity=v,
                        data_hi=zeroed(col.data_hi),
                        children=tuple(_null_where(xp, c, v)
                                       for c in col.children))


def _split_op(op: str) -> Tuple[str, bool]:
    """(base op, whether null rows contribute): `first_any` is `first`
    over every live row."""
    return (op[:-4], True) if op.endswith("_any") else (op, False)


def _ungrouped_reducible(value_cols: List[DeviceColumn],
                         ops: List[str]) -> bool:
    """Whether every op of an ungrouped aggregate is a reduction of its
    lane under a mask (see `_group_reduce`)."""
    for vc, op in zip(value_cols, ops):
        base_op = _split_op(op)[0]
        if base_op in ("countvalid", "first", "last"):
            continue
        wide = vc.data_hi is not None       # decimal128: two words a row
        if base_op == "sum" and not wide and \
                not _needs_index_gather(vc.dtype):
            continue
        if base_op in ("min", "max") and not wide and \
                not isinstance(vc.dtype, (t.StringType, t.BinaryType)):
            continue
        return False
    return True


def _ieee_sum(xp, finite_sum, n_pi, n_ni, n_nan):
    """A float sum from the sum of its finite terms and the counts of its
    +inf, -inf and nan terms, as IEEE addition would have it."""
    out = xp.where((n_nan > 0) | ((n_pi > 0) & (n_ni > 0)),
                   xp.full_like(finite_sum, xp.nan), finite_sum)
    out = xp.where((n_pi > 0) & (n_ni == 0) & (n_nan == 0),
                   xp.full_like(out, xp.inf), out)
    return xp.where((n_ni > 0) & (n_pi == 0) & (n_nan == 0),
                    xp.full_like(out, -xp.inf), out)


# minor axis of the two-level float sum: 33,554,432 rows fold as 4,096
# rows of 8,192, so no addition chain is longer than the wider axis
_SUM_MINOR = 8192


def _sum_two_level(xp, vals):
    """Sum of a float lane in a fixed two-level shape (rows of
    `_SUM_MINOR`, then the row sums), so that which values meet in an
    addition depends on their positions alone and the rounding error
    grows with the axes' lengths, not with the lane's.  Padding zeros add
    exactly."""
    n = vals.shape[0]
    pad = -n % _SUM_MINOR
    if pad:
        vals = xp.concatenate([vals, xp.zeros((pad,), vals.dtype)])
    return xp.sum(xp.sum(vals.reshape(-1, _SUM_MINOR), axis=1))


def _reads_row(vc: DeviceColumn, op: str) -> bool:
    """Whether `_masked_op` answers with a row of `vc` to read and not
    with a value: first and last, and min/max of a struct, array or map
    (the first row, as in the sort arm)."""
    return _split_op(op)[0] in ("first", "last") or \
        _needs_index_gather(vc.dtype)


def _masked_op(xp, vc: DeviceColumn, op: str, mask, iota):
    """One reducible op (`_ungrouped_reducible`) over the rows of ONE
    group, `mask`, where they lie: (result, contributing rows), both
    scalars.  The result is the value, or the row's index where
    `_reads_row`; it means nothing where no row contributes (the caller
    nulls it), but a `countvalid` always counts.  Null, inf, nan and
    empty-input semantics are the sort arm's to the letter; `first` and
    `last` read the arrival position, which is what that arm's stable
    sort preserves."""
    def count(m):
        return xp.sum(m.astype(np.int32), dtype=np.int32)

    base_op, any_row = _split_op(op)
    contrib = mask if any_row or vc.validity is None else \
        vc.validity & mask
    cnt = count(contrib)
    if base_op == "countvalid":
        return cnt.astype(np.int64), cnt
    if _reads_row(vc, op):
        if base_op == "last":
            return xp.max(xp.where(contrib, iota, np.int32(-1))), cnt
        return xp.min(xp.where(contrib, iota, np.int32(2**31 - 1))), cnt
    data = vc.data
    if base_op in ("min", "max"):
        row = seg.masked_argext(xp, data, contrib,
                                is_min=(base_op == "min"))
        return data[row], cnt
    vals0 = xp.where(contrib, data, xp.zeros((), dtype=data.dtype))
    if np.dtype(data.dtype).kind != "f":
        # exact modulo 2^width, as the scan's difference is
        return xp.sum(vals0, dtype=data.dtype), cnt
    # the finite values only; IEEE's inf/nan from their counts (the
    # chip's float64 is a float32 pair: its add is not trusted to carry
    # them)
    finite = _sum_two_level(xp, xp.where(
        xp.isfinite(vals0), vals0, xp.zeros((), dtype=data.dtype)))
    return _ieee_sum(xp, finite, count(contrib & (data == xp.inf)),
                     count(contrib & (data == -xp.inf)),
                     count(contrib & xp.isnan(data))), cnt


def _reduce_ungrouped(xp, value_cols: List[DeviceColumn], ops: List[str],
                      cap: int, live) -> List[DeviceColumn]:
    """The masked arm of `_group_reduce`: one group, so each op is
    `_masked_op` under `live`.  Returns one-row columns of capacity
    `DEFAULT_ROW_BUCKETS[0]`."""
    out_cap = DEFAULT_ROW_BUCKETS[0]
    slot0 = xp.arange(out_cap, dtype=np.int32) == 0
    iota = xp.arange(cap, dtype=np.int32)

    def one_row(dtype, value, valid) -> DeviceColumn:
        v = slot0 & valid
        return DeviceColumn(dtype, validity=v, data=xp.where(
            v, value, xp.zeros((), dtype=value.dtype)))

    out_values: List[DeviceColumn] = []
    for vc, op in zip(value_cols, ops):
        value, cnt = _masked_op(xp, vc, op, live, iota)
        if _split_op(op)[0] == "countvalid":
            out_values.append(one_row(t.LONG, value, slot0))
        elif _reads_row(vc, op):
            out_values.append(gather_column(
                xp, vc, xp.where(slot0, value, np.int32(0)),
                slot0 & (cnt > 0)))
        else:
            out_values.append(one_row(vc.dtype, value, cnt > 0))
    return out_values


#: bits of a key column's value, for the types whose values are so few
_KEY_BITS = {t.BooleanType: 1, t.ByteType: 8, t.ShortType: 16}


def _key_bits(kc: DeviceColumn) -> Optional[int]:
    """Bits that hold every value of a key column, where its type says:
    8 a byte of a fixed-width string, 1 for a boolean, 8 for a byte, 16
    for a short.  None where the key is unbounded (an int64, a double, a
    general string)."""
    if kc.fixed_width is not None:
        return 8 * kc.fixed_width
    return _KEY_BITS.get(type(kc.dtype))


def _group_bound(key_cols: List[DeviceColumn]) -> Optional[int]:
    """The most groups these key columns can form, where their types say
    (`_key_bits`): every value of every column, and one more each for the
    null.  None where a key is unbounded."""
    bound = 1
    for kc in key_cols:
        bits = _key_bits(kc)
        if bits is None:
            return None
        bound *= (1 << bits) + 1
    return bound


def _group_capacity(key_cols: List[DeviceColumn], cap: int) -> int:
    """Output capacity of a grouped `_group_reduce` over `cap` slots: the
    row bucket that holds `_group_bound`, where that is below `cap`; else
    `cap`.  Static: read from the key columns' types at trace time."""
    bound = _group_bound(key_cols)
    if bound is None:
        return cap
    return min(cap, bucket_for(bound, DEFAULT_ROW_BUCKETS))


# most groups the dense arm of `_group_reduce` walks.  Both arms' costs
# are linear in the slots and in the ops, so their ratio at one shape is
# the ratio: at Q1's (33,554,432 slots, two char(1) keys, seven float
# sums and four counts) the sort arm takes 4,013 ms whatever the groups,
# and the dense arm 39.7 ms for 4 groups, 198.4 for 64 and 739.2 for 256
# (2.9 ms a group, 0.2 of them to find it; `devtools/
# chip_dense_groups.py`, my chip runs, PR 32): at the cap it still wins
# by five, which leaves a factor of two for op mixes nobody has timed.
_DENSE_GROUPS_MAX = 256

# groups the dense arm reduces in ONE pass over the lanes: what does not
# depend on the group (a lane's finite values, its inf and nan flags) is
# worked out once a pass, and the lanes are read once.  At Q1's shape, 4
# groups / 64 groups: 1 a pass (a loop over the groups) 64.9 / 842.5 ms,
# 4: 35.6 / 298.8, 8: 39.7 / 198.4, 16: 48.8 / 150.1, 64 (every slot at
# once) 133.1 / 143.9 (my chip runs, PR 32): 8 is within 4 ms of the
# best at four groups and within two fifths of it at sixty-four.
_DENSE_WALK = 8

#: the code of no row: a key's code is at most 31 bits (`_dense_eligible`)
_NO_CODE = np.uint32(0xFFFFFFFF)


def _dense_eligible(key_cols: List[DeviceColumn],
                    value_cols: List[DeviceColumn], ops: List[str]) -> bool:
    """Whether a grouped call may take the dense arm of `_group_reduce`:
    every key column's type bounds its values (`_key_bits`), the columns'
    null bits and values pack into one code below `_NO_CODE`, and every
    op reduces under a mask."""
    bits = [_key_bits(kc) for kc in key_cols]
    return None not in bits and sum(b + 1 for b in bits) < 32 and \
        _ungrouped_reducible(value_cols, ops)


def _key_code(xp, key_cols: List[DeviceColumn], live):
    """The key as ONE uint32 lane: column after column, the first the
    most significant, a null bit (0 for a null, so nulls come first) above
    the value's `_key_bits`, which rise as the sort arm's word of that
    column does (`seg.key_words_for_column`).  So rising code order is the
    sort arm's group order, and a code holds every byte of its key.  A row
    outside `live` reads `_NO_CODE`."""
    code = None
    for kc in key_cols:
        bits = _key_bits(kc)
        if kc.fixed_width is not None:
            field = kc.word.astype(xp.uint32)
        elif bits == 1:
            field = kc.data.astype(xp.uint32)
        else:   # a signed integer, from its least value up
            field = (kc.data.astype(xp.int32)
                     + np.int32(1 << (bits - 1))).astype(xp.uint32)
        valid = np.uint32(1) if kc.validity is None else \
            kc.validity.astype(xp.uint32)
        field = field | (valid << np.uint32(bits))
        code = field if code is None else \
            (code << np.uint32(bits + 1)) | field
    return xp.where(live, code, _NO_CODE)


def _keys_of_codes(xp, key_cols: List[DeviceColumn], codes,
                   slot_valid) -> List[DeviceColumn]:
    """`_key_code` undone: the key columns whose row i is the key of
    `codes[i]`, null outside `slot_valid`."""
    out = []
    for kc in reversed(key_cols):
        bits = _key_bits(kc)
        field = codes & np.uint32((1 << bits) - 1)
        valid = slot_valid & ((codes >> np.uint32(bits)) & np.uint32(1) != 0)
        codes = codes >> np.uint32(bits + 1)
        if kc.fixed_width is not None:
            word = field.astype(kc.word.dtype)
            out.append(kc.with_word(
                xp.where(slot_valid, word, xp.zeros((), word.dtype)), valid))
            continue
        if bits == 1:
            data = field != 0
        else:
            data = (field.astype(xp.int32)
                    - np.int32(1 << (bits - 1))).astype(kc.data.dtype)
        out.append(DeviceColumn(kc.dtype, validity=valid, data=xp.where(
            slot_valid, data, xp.zeros((), data.dtype))))
    return out[::-1]


def _loop(xp, cond, body, state):
    """`state` after `body` as long as `cond`: a loop of the device's own
    under a trace, whose trip count the program finds; Python's on the
    host."""
    if xp is np:
        while cond(state):
            state = body(state)
        return state
    return jax.lax.while_loop(cond, body, state)


def _put(xp, arr, i, value):
    """`arr` with `value` at `i`."""
    if xp is np:
        arr = arr.copy()
        arr[i] = value
        return arr
    return arr.at[i].set(value)


def _distinct_codes(xp, code, slots: int):
    """(codes, n): the first `slots + 1` distinct values of the `code`
    lane below `_NO_CODE`, rising, and how many were found.  No sort: the
    least code, then the least above it, each a masked `min` over the one
    uint32 lane, until a step finds nothing."""
    def more(state):
        n, cur, _ = state
        return (cur != _NO_CODE) & (n <= slots)

    def step(state):
        n, cur, codes = state
        above = xp.min(xp.where(code > cur, code, _NO_CODE))
        return n + np.int32(1), above, _put(xp, codes, n, cur)
    n, _, codes = _loop(xp, more, step, (
        np.int32(0), xp.min(code), xp.full((slots + 1,), _NO_CODE)))
    return codes, n


def _each_code(xp, fn, some_codes):
    """`fn` of each of a few codes, the results stacked along a new first
    axis: under a trace ONE pass over the lanes answers for all of them."""
    if xp is np:
        return jax.tree_util.tree_map(
            lambda *rows: np.stack(rows), *[fn(g) for g in some_codes])
    return jax.vmap(fn)(some_codes)


def _reduce_dense(xp, key_cols: List[DeviceColumn],
                  value_cols: List[DeviceColumn], ops: List[str], cap: int,
                  code, codes, num_groups, out_cap: int):
    """The dense arm of `_group_reduce`: group i is the rows whose `code`
    is `codes[i]`, and each op is `_masked_op` under that mask,
    `_DENSE_WALK` groups a pass over the lanes, for as many passes as hold
    the `num_groups` that are there.  The key columns are read back from
    the codes.  Returns (out_key_cols, out_value_cols) of capacity
    `out_cap`, as the sort arm does."""
    iota = xp.arange(cap, dtype=np.int32)
    passes = -(-int(codes.shape[0]) // _DENSE_WALK)

    def slot_lane(a, n, fill=0):
        """The first `n` of a lane of slots, or the lane filled up to `n`."""
        return a[:n] if n <= a.shape[0] else xp.concatenate(
            [a, xp.full((n - a.shape[0],), fill, a.dtype)])
    walk = slot_lane(codes, passes * _DENSE_WALK, _NO_CODE) \
        .reshape(passes, _DENSE_WALK)

    def result_dtype(vc, op):
        if _split_op(op)[0] == "countvalid":
            return np.int64
        return np.int32 if _reads_row(vc, op) else vc.data.dtype

    def one_pass(state):
        at, acc = state
        # (the code of no row is the padding rows': no group)
        reduced = _each_code(xp, lambda g: [
            _masked_op(xp, vc, op, (code == g) & (g != _NO_CODE), iota)
            for vc, op in zip(value_cols, ops)], walk[at])
        return at + np.int32(1), [
            (_put(xp, results, at, r), _put(xp, counts, at, c))
            for (results, counts), (r, c) in zip(acc, reduced)]
    _, acc = _loop(
        xp, lambda state: state[0] * np.int32(_DENSE_WALK) < num_groups,
        one_pass, (np.int32(0), [
            (xp.zeros(walk.shape, result_dtype(vc, op)),
             xp.zeros(walk.shape, np.int32))
            for vc, op in zip(value_cols, ops)]))

    slot_valid = xp.arange(out_cap, dtype=np.int32) < num_groups
    out_values = []
    for (results, counts), vc, op in zip(acc, value_cols, ops):
        results = slot_lane(results.reshape(-1), out_cap)
        valid = slot_valid & (slot_lane(counts.reshape(-1), out_cap) > 0)
        if _split_op(op)[0] == "countvalid":
            out_values.append(DeviceColumn(t.LONG, data=results,
                                           validity=slot_valid))
        elif _reads_row(vc, op):
            out_values.append(gather_column(xp, vc, results, valid))
        else:
            out_values.append(DeviceColumn(
                vc.dtype, validity=valid, data=xp.where(
                    valid, results, xp.zeros((), results.dtype))))
    return _keys_of_codes(xp, key_cols, slot_lane(codes, out_cap),
                          slot_valid), out_values


def _group_reduce(xp, key_cols: List[DeviceColumn],
                  value_cols: List[DeviceColumn], ops: List[str],
                  cap: int, live, global_agg: bool):
    """Core aggregate kernel.  Returns (out_key_cols, out_value_cols,
    num_groups).

    Three arms, chosen from what the call can see (`global_agg`, `ops`,
    the key and value columns' types, and a count the program itself
    takes), never from a size, a name or a setting:

      - **masked reduction** (`_reduce_ungrouped`): an ungrouped
        aggregate whose every op is reducible (`_ungrouped_reducible`:
        sum of a flat integer or float lane, countvalid, min/max of a
        flat numeric lane, first/last and their `_any` forms of any
        column).  One group needs no order: each op is a reduction of
        its lane under the mask (`_masked_op`), and the answer is a
        ONE-row batch in the smallest row bucket.  No sort, no scan, no
        compaction.  Q6's sum takes it (update, and the merge of
        partials alike).
      - **dense** (`_reduce_dense`): a grouped aggregate with the same
        ops whose key columns' types bound their values so far that the
        whole key is one 32-bit code (`_dense_eligible`: two `char(1)`,
        a boolean and a byte, a short).  The groups that are THERE are
        found without a sort (`_distinct_codes`: the least code, the
        least above it, ...), in the sort arm's group order, and each is
        one more masked reduction; its key is read back from its code.
        Q1's four groups take it.  A type bound is not a count: a short
        may hold 60,000 groups, so when more than `_DENSE_GROUPS_MAX`
        codes turn up the same program takes the sort arm instead
        (`lax.cond` on the count found: no host read, no second run; a
        Python `if` on the CPU engine).
      - **sort + segment** (`_sort_segment`): every other grouped call
        (an int64, a double or a general string among the keys), and any
        call that holds a collect_*, an ordered min/max of strings,
        binaries or decimal128, or a decimal128 sum: those compact or
        order values, and one such op sends the whole call there.

    A grouped call's output keeps the input's capacity, unless the key
    columns' types bound the group count below it (`_group_capacity`: two
    one-byte string keys form at most 257 x 257 groups): then every
    output is cut to that bound's row bucket, exactly, with no host read
    and no speculation.  What reads a four-group answer (a sort, a HAVING
    filter, the fetch) then runs at 262,144 slots and not at the table's
    33,554,432.
    """
    from ..ops import carry
    if global_agg:
        reduced = _ungrouped_reducible(value_cols, ops)
        carry.count_ungrouped(reduced)
        if reduced:
            return [], _reduce_ungrouped(xp, value_cols, ops, cap, live), \
                xp.int32(1)
        return _sort_segment(xp, key_cols, value_cols, ops, cap, live, True)
    dense = _dense_eligible(key_cols, value_cols, ops)
    carry.count_grouped(dense)
    if not dense:
        return _sort_segment(xp, key_cols, value_cols, ops, cap, live,
                             False)
    out_cap = _group_capacity(key_cols, cap)
    slots = min(_DENSE_GROUPS_MAX, out_cap)
    code = _key_code(xp, key_cols, live)
    codes, found = _distinct_codes(xp, code, slots)

    def few():
        # (the count in the type the sort arm's sum gives it)
        return *_reduce_dense(xp, key_cols, value_cols, ops, cap, code,
                              codes, found, out_cap), found.astype(jnp.int_)

    def many():
        return _sort_segment(xp, key_cols, value_cols, ops, cap, live,
                             False)
    if xp is np:
        return few() if found <= slots else many()
    return jax.lax.cond(found <= slots, few, many)


def _sort_segment(xp, key_cols: List[DeviceColumn],
                  value_cols: List[DeviceColumn], ops: List[str],
                  cap: int, live, global_agg: bool):
    """The sort arm of `_group_reduce` (see ops/carry.py docstring for
    the chip measurements behind it):

      1. ONE stable sort by the key words.  Every flat lane of the key
         and value columns is moved by `carry.sort_rows`: a sort pass per
         32-bit word keyed by the row's rank; never a gather by the
         order.
      2. Per sum/count: a Hillis-Steele prefix scan + elementwise
         exclusive value — the per-segment total is the difference of the
         exclusive scan at consecutive segment starts.  No 64-bit
         scatters anywhere; float sums scan finite values only and
         rebuild IEEE inf/nan from per-segment special-value counts.
      3. ONE compaction (`carry.compact_rows`) moves the boundary rows
         (and all per-op scan lanes + flat key lanes) to the slot
         positions; the groups lie at the front, so its lanes and every
         output are cut to `_group_capacity`.
      4. min/max/first/last use int32 scatter tournaments + one row
         gather; variable-width columns keep the gather-based paths.
    """
    from ..ops import carry
    # --- sort keys, carrying all row data -----------------------------------
    words: List = [~live]  # padding rows sort last
    for kc in key_cols:
        words += seg.key_words_for_column(xp, kc, live, for_grouping=True)
    all_cols = list(key_cols) + list(value_cols)
    # only the merge of collected arrays reads the order itself
    order, sorted_cols, ex = carry.sort_rows(
        xp, words, all_cols, cap, extras=[live] + words[1:],
        need_order=any(op.startswith("collect_concat") for op in ops))
    key_sorted = sorted_cols[:len(key_cols)]
    val_sorted = sorted_cols[len(key_cols):]
    live_sorted = ex[0]
    sorted_words = ex[1:]
    if global_agg:
        new_group = xp.arange(cap, dtype=np.int32) == 0
    else:
        new_group = seg.segment_boundaries(xp, sorted_words, live_sorted)
    seg_ids = seg.segment_ids(xp, new_group)
    seg_ids = xp.clip(seg_ids, 0, cap - 1)
    num_groups = xp.sum(new_group.astype(np.int32)) if not global_agg \
        else xp.int32(1) if xp is not np else np.int32(1)
    iota_slots = xp.arange(cap, dtype=np.int32)
    slot_valid = iota_slots < num_groups

    # --- deferred scan lanes (compacted once, below) ------------------------
    lanes: List = []
    lane_pos: dict = {}

    def enlane(a) -> int:
        k = id(a)
        if k not in lane_pos:
            lane_pos[k] = len(lanes)
            lanes.append(a)
        return lane_pos[k]

    count_cache: dict = {}

    def count_lane(mask) -> tuple:
        """(lane index, total) of the exclusive scan of an int32 mask.
        The cache RETAINS each mask: a bare id() key could alias a new
        mask after a temporary is garbage-collected (np engine path)."""
        k = id(mask)
        hit = count_cache.get(k)
        if hit is not None and hit[0] is mask:
            return hit[1]
        m32 = mask.astype(np.int32)
        cs = seg.cumsum_fast(xp, m32)
        val = (enlane(cs - m32), cs[-1])
        count_cache[k] = (mask, val)
        return val

    sum_jobs: List[dict] = []
    out_values: List[Optional[DeviceColumn]] = [None] * len(ops)

    for oi, (vs, op) in enumerate(zip(val_sorted, ops)):
        validity_sorted = live_sorted if vs.validity is None else \
            (vs.validity & live_sorted)
        if op in ("collect_list", "collect_set"):
            out_values[oi] = _collect_update(
                xp, vs, seg_ids, validity_sorted, cap, slot_valid,
                dedupe=(op == "collect_set"))
            continue
        if op in ("collect_concat", "collect_concat_set"):
            out_values[oi] = _collect_merge(
                xp, value_cols[oi], order, seg_ids, validity_sorted, cap,
                slot_valid, dedupe=(op == "collect_concat_set"))
            continue
        if op == "countvalid":
            li, total = count_lane(validity_sorted)
            sum_jobs.append(dict(kind="count", out=oi, lane=li,
                                 total=total))
            continue
        base_op, any_row = _split_op(op)
        contrib = live_sorted if any_row else validity_sorted
        is_dec128 = vs.data_hi is not None
        if is_dec128 and base_op == "sum":
            lo_o, hi_o, cnt = seg.segment_sum128(xp, vs.data, vs.data_hi,
                                                 seg_ids, cap, contrib,
                                                 sorted_ids=True)
            validity_out = (cnt > 0) & slot_valid
            out_values[oi] = DeviceColumn(
                vs.dtype,
                data=xp.where(validity_out, lo_o, xp.zeros_like(lo_o)),
                data_hi=xp.where(validity_out, hi_o, xp.zeros_like(hi_o)),
                validity=validity_out)
            continue
        if op in ("first", "last", "first_any", "last_any") or \
                _needs_index_gather(vs.dtype) or is_dec128:
            if base_op in ("min", "max") and \
                    (is_dec128 or
                     isinstance(vs.dtype, (t.StringType, t.BinaryType))):
                # ordered reduce for variable-width values: secondary sort
                # by (segment, validity, value words), first row per
                # segment wins.  Value words are the same prefix+length
                # encoding the sort exec orders by; max inverts them.
                vwords = seg.key_words_for_column(
                    xp, vs, contrib, for_grouping=False,
                    ascending=(base_op == "min"))
                words2 = [seg_ids.astype(xp.uint32),
                          (~contrib).astype(xp.uint8)] + vwords[1:]
                order2 = seg.lexsort(xp, words2, cap)
                first2 = seg.first_index_per_segment(
                    xp, seg_ids[order2], cap, contrib[order2])
                idx = order2[first2].astype(xp.int32)
                _, cnt = seg.segment_reduce(
                    xp, "sum", xp.zeros((cap,), np.int32), seg_ids, cap,
                    contrib, sorted_ids=True)
            else:
                pos = xp.arange(cap, dtype=np.int32)
                which = "first" if base_op in ("first", "min") else \
                    ("last" if base_op in ("last",) else "first")
                idx, cnt = seg.segment_reduce(xp, which, pos, seg_ids, cap,
                                              contrib, sorted_ids=True)
                idx = idx.astype(xp.int32)
            gathered = gather_column(xp, vs, idx, (cnt > 0) & slot_valid)
            out_values[oi] = gathered
            continue
        if base_op in ("min", "max"):
            out, cnt = seg.segment_reduce(xp, base_op, vs.data, seg_ids,
                                          cap, contrib, sorted_ids=True)
            validity_out = (cnt > 0) & slot_valid
            out = xp.where(validity_out, out, xp.zeros_like(out))
            out_values[oi] = DeviceColumn(vs.dtype, data=out,
                                          validity=validity_out)
            continue
        # sum via prefix scans: integers use global-scan differencing
        # (exact modulo 2^width); floats use a segmented scan — a global
        # float prefix lets one segment's magnitude catastrophically
        # cancel another's, and inf/nan would poison later segments
        data = vs.data
        vals0 = xp.where(contrib, data, xp.zeros_like(data))
        job = dict(kind="sum", out=oi, dtype=vs.dtype)
        if np.dtype(data.dtype).kind == "f":
            finite = xp.isfinite(vals0)
            scan_vals = xp.where(finite, vals0, xp.zeros_like(vals0))
            job["pi"] = count_lane(contrib & (data == xp.inf))
            job["ni"] = count_lane(contrib & (data == -xp.inf))
            job["nan"] = count_lane(contrib & xp.isnan(data))
            from ..ops.scan import segmented_cumsum_fast
            sseg = segmented_cumsum_fast(xp, scan_vals, new_group)
            # at a segment's first row, the PREVIOUS row closes the
            # previous segment — compacting the shifted lane puts each
            # segment's total at slot+1
            from ..ops.scan import shift_right
            shifted = shift_right(xp, sseg)
            job["kind"] = "sum_seg"
            job["lane"] = enlane(shifted)
            job["total"] = sseg[-1]
        else:
            cs = seg.cumsum_fast(xp, vals0)
            job["lane"] = enlane(cs - vals0)
            job["total"] = cs[-1]
        job["cnt"] = count_lane(contrib)
        sum_jobs.append(job)

    # --- flat key lanes join the compaction ---------------------------------
    import jax
    key_plans = []
    first_lane = None       # the first row index per slot, for span keys
    for ks in key_sorted:
        if carry.carriable(ks):
            leaves, treedef = jax.tree_util.tree_flatten(ks)
            key_plans.append((treedef, [enlane(l) for l in leaves]))
        else:
            key_plans.append((None, None))
            first_lane = enlane(iota_slots)

    # --- ONE compaction: boundary rows -> slot positions --------------------
    _, _, comp = carry.compact_rows(xp, new_group, (), cap, extras=lanes)
    out_cap = cap if global_agg else _group_capacity(key_cols, cap)
    if out_cap < cap:
        # every group's slot lies below the bound: the rest is padding
        comp = [c[:out_cap] for c in comp]
        iota_slots = iota_slots[:out_cap]
        slot_valid = slot_valid[:out_cap]

    def span_next(lane_idx, total):
        """Per-slot value from the NEXT slot's compacted lane entry; the
        last live slot reads the whole-array closing value."""
        E = comp[lane_idx]
        from ..ops.scan import shift_left
        nxt = shift_left(xp, E)
        last = iota_slots == (num_groups - 1)
        return xp.where(last, xp.asarray(total, dtype=E.dtype), nxt)

    def span_diff(lane_idx, total):
        """Per-slot total from a compacted exclusive scan: the difference
        of consecutive segment starts; the last live slot closes on the
        whole-array total."""
        return span_next(lane_idx, total) - comp[lane_idx]

    for job in sum_jobs:
        cnt_lane, cnt_total = job["cnt"] if job["kind"] != "count" \
            else (job["lane"], job["total"])
        cnt = span_diff(cnt_lane, cnt_total)
        if job["kind"] == "count":
            out_values[job["out"]] = DeviceColumn(
                t.LONG, data=cnt.astype(np.int64), validity=slot_valid)
            continue
        if job["kind"] == "sum_seg":
            out = span_next(job["lane"], job["total"])
        else:
            out = span_diff(job["lane"], job["total"])
        if "pi" in job:
            out = _ieee_sum(xp, out, span_diff(*job["pi"]),
                            span_diff(*job["ni"]), span_diff(*job["nan"]))
        validity_out = (cnt > 0) & slot_valid
        out = xp.where(validity_out, out, xp.zeros_like(out))
        out_values[job["out"]] = DeviceColumn(job["dtype"], data=out,
                                              validity=validity_out)

    # --- group key values at slot positions ---------------------------------
    out_keys = []
    for ks, (treedef, lidx) in zip(key_sorted, key_plans):
        if treedef is None:
            first_idx = xp.clip(comp[first_lane], 0, cap - 1)
            out_keys.append(gather_column(xp, ks, first_idx.astype(xp.int32),
                                          slot_valid))
        else:
            col = jax.tree_util.tree_unflatten(
                treedef, [comp[i] for i in lidx])
            out_keys.append(carry.mask_validity(xp, col, slot_valid))
    if out_cap < cap:
        # (the ops reduced before the compaction wrote at `cap`)
        out_values = [v if v.capacity == out_cap
                      else shrink_column(v, out_cap) for v in out_values]
    return out_keys, out_values, num_groups


def _permuted(xp, col: DeviceColumn, order) -> DeviceColumn:
    all_valid = xp.ones((order.shape[0],), dtype=bool)
    return gather_column(xp, col, order, all_valid)


def _collect_update(xp, vc: DeviceColumn, seg_ids, contrib, cap: int,
                    slot_valid, dedupe: bool) -> DeviceColumn:
    """collect_list / collect_set over key-sorted rows (ref
    AggregateFunctions.scala GpuCollectList/GpuCollectSet).

    `vc` arrives already key-sorted (carried through the main sort).  The
    sort by grouping key makes each group's rows contiguous, so the
    collected child buffer is a stable compaction of contributing values;
    null values are dropped (Spark semantics) and sets dedupe within the
    segment by value words."""
    perm = vc
    keep = contrib
    sids = seg_ids
    if dedupe:
        # order by (segment, value), first occurrence survives
        vwords = seg.key_words_for_column(xp, perm, keep, for_grouping=True)
        words2 = [(~keep).astype(xp.uint8),
                  sids.astype(xp.uint32)] + vwords
        order2 = seg.lexsort(xp, words2, cap)
        keep_s = keep[order2]
        sw = [sids[order2].astype(xp.uint32)] + [w[order2] for w in vwords]
        first = seg.segment_boundaries(xp, sw, keep_s)
        perm = gather_column(xp, perm, order2,
                             xp.ones((cap,), dtype=bool))
        sids = sids[order2]
        keep = keep_s & first
    # stable compaction keeps segment-major order
    if xp is np:
        order3 = np.argsort(~keep, kind="stable").astype(np.int32)
    else:
        from ..ops.carry import stable_argsort
        order3 = stable_argsort(xp, [(~keep).astype(xp.int32)], cap)
    child = gather_column(xp, perm, order3, keep[order3])
    cnt, _ = seg.segment_reduce(xp, "sum", keep.astype(np.int32), sids,
                                cap, keep, sorted_ids=True)
    offs = xp.concatenate([xp.zeros((1,), np.int32),
                           cumsum_fast(xp, cnt).astype(xp.int32)])
    return DeviceColumn(t.ArrayType(vc.dtype), offsets=offs,
                        validity=slot_valid, children=(child,))


def _collect_merge(xp, vc: DeviceColumn, order, seg_ids, contrib, cap: int,
                   slot_valid, dedupe: bool) -> DeviceColumn:
    """Merge collected array buffers per key: gather rows in key-sorted
    order (which repacks every row's span contiguously, i.e. the
    segment-major concatenation), then optionally dedupe elements within
    each segment (collect_set)."""
    perm = gather_column(xp, vc, order, contrib)
    child = perm.children[0]
    child_cap = child.capacity
    lens = (perm.offsets[1:] - perm.offsets[:-1]).astype(xp.int64)
    if not dedupe:
        cnt, _ = seg.segment_reduce(xp, "sum", lens, seg_ids, cap,
                                    xp.ones((cap,), dtype=bool))
        offs = xp.concatenate([xp.zeros((1,), np.int32),
                               cumsum_fast(xp, cnt).astype(xp.int32)])
        return DeviceColumn(t.ArrayType(child.dtype), offsets=offs,
                            validity=slot_valid, children=(child,))
    # element -> segment mapping via the row each child position came from
    pos = xp.arange(child_cap, dtype=xp.int32)
    crow = xp.clip(xp.searchsorted(perm.offsets[1:], pos, side="right"),
                   0, cap - 1).astype(xp.int32)
    in_range = pos < perm.offsets[-1]
    cseg = seg_ids[crow]
    vwords = seg.key_words_for_column(xp, child, in_range,
                                      for_grouping=True)
    words = [(~in_range).astype(xp.uint64),
             cseg.astype(xp.uint64)] + vwords
    order2 = seg.lexsort(xp, words, child_cap)
    keep_s = in_range[order2]
    sw = [cseg[order2].astype(xp.uint64)] + [w[order2] for w in vwords]
    first = seg.segment_boundaries(xp, sw, keep_s)
    keep = keep_s & first
    child_s = gather_column(xp, child, order2,
                            xp.ones((child_cap,), dtype=bool))
    if xp is np:
        order3 = np.argsort(~keep, kind="stable").astype(np.int32)
    else:
        from ..ops.carry import stable_argsort
        order3 = stable_argsort(xp, [(~keep).astype(xp.int32)],
                                child_cap)
    final_child = gather_column(xp, child_s, order3, keep[order3])
    cseg_s = cseg[order2]
    cnt, _ = seg.segment_reduce(xp, "sum", keep.astype(np.int64), cseg_s,
                                cap, keep)
    offs = xp.concatenate([xp.zeros((1,), np.int32),
                           cumsum_fast(xp, cnt).astype(xp.int32)])
    return DeviceColumn(t.ArrayType(child.dtype), offsets=offs,
                        validity=slot_valid, children=(final_child,))


def _needs_index_gather(dtype: t.DataType) -> bool:
    return isinstance(dtype, (t.StringType, t.BinaryType, t.StructType,
                              t.ArrayType, t.MapType))


class TpuHashAggregateExec(Exec):
    """TPU hash aggregate (ref GpuHashAggregateExec, aggregate.scala:1450)."""

    placement = TPU

    # Canonical keyed merge (tpudsan): before folding accumulated
    # partials, _merge_batch orders rows by grouping-key AND buffer
    # value words, so the float accumulation order is a function of
    # content, not of batch arrival — the property that lets a
    # recomputed map task reproduce its shuffle blocks bit-for-bit
    # (TPU-R016/L016).  The TPU-L016 pre-flight repair
    # (analysis/determinism.try_stabilize_repair) forces this back on
    # when a plan turns it off.
    stable_merge: bool = True

    def __init__(self, grouping: Sequence[Expression],
                 aggregates: Sequence[AggregateExpression],
                 mode: str, child: Exec):
        super().__init__([child])
        self.grouping = list(grouping)
        from ..expr.aggregates import bind_aggregate
        if mode in (PARTIAL, COMPLETE):
            self.aggregates = [bind_aggregate(a, child.output_names,
                                              child.output_types)
                               for a in aggregates]
        else:
            self.aggregates = list(aggregates)  # FINAL: pre-bound by caller
        self.mode = mode
        self._setup()

    def _setup(self):
        child = self.children[0]
        cn, ct = child.output_names, child.output_types
        self._group_names = [output_name(g) for g in self.grouping]
        if self.mode in (PARTIAL, COMPLETE):
            self._bound_grouping = [bind_expression(g, cn, ct)
                                    for g in self.grouping]
            self._update_inputs = []
            self._update_ops = []
            for ae in self.aggregates:
                for expr, op in ae.func.update():
                    self._update_inputs.append(bind_expression(expr, cn, ct))
                    self._update_ops.append(op)
        if self.mode == FINAL:
            # child layout: group cols then buffers in declaration order
            k = len(self.grouping)
            self._buffer_ordinals = list(range(k, len(cn)))
        self._buffer_names = []
        self._buffer_types = []
        for i, ae in enumerate(self.aggregates):
            for j, bt in enumerate(ae.func.buffer_types()):
                self._buffer_names.append(f"buf{i}_{j}")
                self._buffer_types.append(bt)
        self._merge_ops = []
        for ae in self.aggregates:
            self._merge_ops += ae.func.merge_ops()

    def determinism(self):
        from ..analysis.determinism import (Determinism, ORDER_DEPENDENT,
                                            ORDER_STABLE)
        scoped = self.mode == PARTIAL  # partial buffers regroup with
        #                                the input split
        if any(isinstance(ae.func, CollectList)
               for ae in self.aggregates):
            return Determinism(
                ORDER_DEPENDENT, "collect_list/collect_set element "
                "order follows batch arrival",
                partition_scoped=scoped)
        floaty = any(isinstance(bt, t.FractionalType)
                     for bt in self._buffer_types)
        if floaty and not self.stable_merge:
            return Determinism(
                ORDER_DEPENDENT, "float partial buffers fold in batch "
                "arrival order (stable_merge off): a different arrival "
                "order changes the sums", partition_scoped=scoped,
                canonicalizable=True)
        return Determinism(
            ORDER_STABLE, "group emission order follows arrival; the "
            "canonical keyed merge makes buffer folds "
            "content-determined", partition_scoped=scoped)

    def input_contracts(self):
        if self.mode != FINAL or not self.grouping:
            return None
        from ..analysis.absdomain import ClusteredContract
        # FINAL input layout: grouping columns first — partial buffers
        # for one group must all arrive in this task's partition
        keys = self.children[0].output_names[:len(self.grouping)]
        return ClusteredContract(keys,
                                 what="FINAL-mode grouped aggregate")

    @property
    def output_names(self):
        if self.mode == PARTIAL:
            return self._group_names + self._buffer_names
        return self._group_names + [ae.name for ae in self.aggregates]

    @property
    def output_types(self):
        if self.mode == PARTIAL:
            gt = [g.data_type() for g in
                  (self._bound_grouping if self.mode in (PARTIAL, COMPLETE)
                   else [])]
            return gt + self._buffer_types
        if self.mode == COMPLETE:
            gt = [g.data_type() for g in self._bound_grouping]
        else:
            gt = self.children[0].output_types[:len(self.grouping)]
        return gt + [ae.data_type() for ae in self.aggregates]

    def describe(self):
        return (f"HashAggregate(mode={self.mode}, keys="
                f"[{', '.join(self._group_names)}], fns="
                f"[{', '.join(a.name for a in self.aggregates)}])")

    def masked_source(self):
        """The plan seam that pairs a filter with this aggregate: the
        `FilterExec` whose keep flags the update side reduces under in
        place of a compacted batch, or None.  Read from the plan's shape
        alone, when the partition is pulled (after every rewrite and
        pre-flight repair): this is the update side (`PARTIAL`,
        `COMPLETE`), the child is the filter with nothing between, both
        are on the TPU engine, and the filter's `rebucket_cap` is not
        armed (the L018 repair shrinks a COMPACTED output).  A key or an
        input that reads a row's position (`rand`,
        `monotonically_increasing_id`) would see the rows where they lay
        and not where compaction put them, so such an aggregate takes
        the compacted batch as well."""
        from .filter_common import masked_child
        if self.mode in (PARTIAL, COMPLETE):
            return masked_child(self, self.children[0],
                                self._bound_grouping + self._update_inputs)
        return None

    def masked_sources(self) -> tuple:
        """`masked_source()` as the seam every masked consumer declares
        (`filter_common.check_paired`)."""
        return (self.masked_source(),)

    # --- device kernels -----------------------------------------------------
    def _update_batch(self, xp, batch: Batch, keep=None) -> Batch:
        """One input batch reduced to its groups.  `keep` is a paired
        filter's flags (`masked_source`): the rows are those of `batch`
        under it, wherever they lie; every arm of `_group_reduce` reads
        rows through `live` alone."""
        ctx = EvalContext(xp, batch)
        live = ctx.row_mask()
        if keep is not None:
            live = live & keep
        key_cols = [g.eval(ctx).col for g in self._bound_grouping]
        val_cols = []
        for b, op in zip(self._update_inputs, self._update_ops):
            v = b.eval(ctx)
            if not isinstance(v, ColumnValue):
                from ..expr.core import make_column
                v = make_column(ctx, b.data_type(), v.value if v.value
                                is not None else 0,
                                None if v.value is not None else False)
            val_cols.append(v.col)
        ok, ov, n = _group_reduce(xp, key_cols, val_cols, self._update_ops,
                                  batch.capacity, live,
                                  global_agg=not self.grouping)
        return DeviceBatch(ok + ov, n, self._group_names + self._buffer_names)

    def _merge_batch(self, xp, batch: Batch) -> Batch:
        k = len(self.grouping)
        if self.stable_merge:
            batch = self._canonicalize_merge_input(xp, batch)
        live = xp.arange(batch.capacity, dtype=np.int32) < batch.num_rows
        key_cols = list(batch.columns[:k])
        val_cols = list(batch.columns[k:])
        ok, ov, n = _group_reduce(xp, key_cols, val_cols, self._merge_ops,
                                  batch.capacity, live,
                                  global_agg=not self.grouping)
        return DeviceBatch(ok + ov, n, self._group_names + self._buffer_names)

    def _canonicalize_merge_input(self, xp, batch: Batch) -> Batch:
        """Order the concatenated partials by key + buffer value words
        so the within-group fold order is content-determined (the
        stable_merge canonical keyed merge).  Nested buffer columns
        (collect_list arrays) contribute no words — their element
        order is declared order_dependent anyway.

        The rows travel with the sort (`carry.sort_rows`: a rank from
        the words' digits, then a sort pass per 32-bit word of row-aligned
        lane); only columns with offsets are gathered by the order."""
        from ..ops import carry
        cap = batch.capacity
        live = xp.arange(cap, dtype=np.int32) < batch.num_rows
        words: List = [~live]  # padding last: one bit of a digit
        for kc in batch.columns[:len(self.grouping)]:
            words += seg.key_words_for_column(xp, kc, live,
                                              for_grouping=True)
        for vc in batch.columns[len(self.grouping):]:
            try:
                words += seg.key_words_for_column(xp, vc, live,
                                                  for_grouping=True)
            except Exception:
                continue  # nested buffer: no sortable words
        _, cols, (live_sorted,) = carry.sort_rows(
            xp, words, batch.columns, cap, extras=[live], need_order=False)
        return DeviceBatch([_null_where(xp, c, live_sorted) for c in cols],
                           batch.num_rows, batch.names)

    def _evaluate_batch(self, xp, batch: Batch) -> Batch:
        """buffers -> final results (Final/Complete modes)."""
        k = len(self.grouping)
        ctx = EvalContext(xp, batch)
        out_cols = list(batch.columns[:k])
        pos = k
        for ae in self.aggregates:
            nb = len(ae.func.buffer_types())
            bufs = [ColumnValue(batch.columns[pos + j]) for j in range(nb)]
            res = ae.func.evaluate(ctx, bufs)
            out_cols.append(res.col)
            pos += nb
        return DeviceBatch(out_cols, batch.num_rows, self.output_names)

    @functools.cached_property
    def _jit_key(self):
        return ("TpuHashAggregateExec", self.mode, self.stable_merge,
                schema_sig(self.children[0]),
                tuple(self._group_names), tuple(self._buffer_names),
                tuple(self.output_names),
                semantic_sig(getattr(self, "_bound_grouping",
                                     self.grouping)),
                semantic_sig(self.aggregates))

    @property
    def _jit_update(self):
        return process_jit(
            self._jit_key + ("update",),
            lambda: lambda b, keep=None: self._update_batch(jnp, b, keep))

    @property
    def _jit_merge(self):
        return process_jit(self._jit_key + ("merge",),
                           lambda: lambda b: self._merge_batch(jnp, b))

    @property
    def _jit_merge_eval(self):
        return process_jit(
            self._jit_key + ("merge_eval",),
            lambda: lambda b: self._evaluate_batch(jnp,
                                                   self._merge_batch(jnp, b)))

    @property
    def _jit_eval(self):
        return process_jit(self._jit_key + ("eval",),
                           lambda: lambda b: self._evaluate_batch(jnp, b))

    @property
    def _jit_complete(self):
        """Single-batch Complete mode: update + evaluate fused into ONE
        compiled program — a lone input batch leaves _group_reduce with
        unique keys, so the merge pass would be an expensive no-op."""
        return process_jit(
            self._jit_key + ("complete",),
            lambda: lambda b, keep=None: self._evaluate_batch(
                jnp, self._update_batch(jnp, b, keep)))

    @property
    def _jit_sortkeys(self):
        return process_jit(self._jit_key + ("sortkeys",),
                           lambda: lambda b: self._sort_by_keys(jnp, b))

    def _sort_by_keys(self, xp, batch: Batch) -> Batch:
        """Order partial-schema rows by grouping key words — the SAME
        for_grouping encoding _group_reduce segments by, so chunked
        re-aggregation's carry logic sees one consistent global order
        (out-of-core sort fallback, ref aggregate.scala:311-314)."""
        cap = batch.capacity
        live = xp.arange(cap, dtype=np.int32) < batch.num_rows
        words: List = [(~live).astype(xp.uint64)]
        for kc in batch.columns[:len(self.grouping)]:
            words += seg.key_words_for_column(xp, kc, live,
                                              for_grouping=True)
        order = seg.lexsort(xp, words, cap)
        from ..ops.gather import gather_batch
        out = gather_batch(xp, batch, order, live[order], batch.num_rows)
        return DeviceBatch(out.columns, batch.num_rows, batch.names)

    def memory_effects(self, child_states, conf):
        """Accumulates registered partial batches then concat + merge:
        ~3x one partition's padded input bytes in-core, or 3x the
        enforced budget out-of-core (bounded by oc_budget when the
        TPU-L014 pre-flight repair forced it)."""
        from ..analysis.lifetime import (MemoryEffects,
                                         padded_partition_bytes,
                                         spill_budget)
        if not child_states:
            return None
        pp = padded_partition_bytes(child_states[0])
        budget = float(min(spill_budget(conf),
                           self.oc_budget or (1 << 62)))
        hold = 3.0 * (pp if pp <= budget else budget)
        return MemoryEffects(hold=hold, note="aggregate: spill-managed")

    def _note_rebucket(self, in_capacity: int, out: Batch) -> None:
        """A reduce whose output came back in a smaller row bucket than
        its input (`_group_capacity`): the event `aggregate.rebucket` and
        the counter `tpu_aggregate_output_rebucket_total`."""
        if out.capacity >= in_capacity:
            return
        from ..obs import metrics as m
        from ..obs.tracer import trace_event
        trace_event("aggregate.rebucket", op=type(self).__name__,
                    mode=self.mode, in_capacity=in_capacity,
                    out_capacity=out.capacity)
        m.counter("tpu_aggregate_output_rebucket_total",
                  "grouped aggregate outputs cut to the row bucket of "
                  "their key columns' static group bound").inc(1)

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        xp = self.xp
        on_tpu = self.placement == TPU
        partials: List[Batch] = []
        schema_names = self._group_names + self._buffer_names
        kt = ([g.data_type() for g in self._bound_grouping]
              if self.mode in (PARTIAL, COMPLETE)
              else self.children[0].output_types[:len(self.grouping)])
        schema_types = kt + self._buffer_types
        from ..memory.spill import SpillCatalog, SpillPriority
        spill = SpillCatalog.get()
        try:
            # the update side's arguments, batch by batch: (batch,), or
            # (batch, keep flags) from the filter the plan paired with
            # this aggregate, which then compacts nothing
            source = self.masked_source()
            if source is not None:
                it = ((m.batch, m.keep)
                      for m in source.execute_masked(pid, ctx, self))
            else:
                it = ((b,) for b in
                      self.children[0].execute_partition(pid, ctx))
            first = next(it, None)
            second = next(it, None) if first is not None else None
            if first is not None and second is None and \
                    self.mode in (PARTIAL, COMPLETE):
                # single input batch: _group_reduce leaves unique keys, so
                # the cross-batch merge would be a no-op re-sort.  PARTIAL
                # emits the update output directly; COMPLETE fuses
                # update+evaluate into one compiled program.
                with MetricTimer(self.metrics[OP_TIME]):
                    if not on_tpu:
                        out = self._update_batch(np, *first)
                        if self.mode == COMPLETE:
                            out = self._evaluate_batch(np, out)
                    elif self.mode == COMPLETE:
                        out = self._jit_complete(*first)
                    else:
                        out = self._jit_update(*first)
                    maybe_sync(out)
                self._note_rebucket(first[0].capacity, out)
                self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
                return
            import itertools
            stream = itertools.chain(
                [x for x in (first, second) if x is not None], it)
            for args in stream:
                with MetricTimer(self.metrics[OP_TIME]):
                    if self.mode in (PARTIAL, COMPLETE):
                        out = self._jit_update(*args) if on_tpu else \
                            self._update_batch(np, *args)
                    else:
                        out = args[0]  # FINAL: merge happens below
                    maybe_sync(out)
                self._note_rebucket(args[0].capacity, out)
                # accumulated partials are spillable (ref aggregate.scala's
                # spillable batch accumulation before merge)
                partials.append(spill.register(out, SpillPriority.INPUT))
                if self.oc_budget is not None:
                    from .outofcore import enforce_device_budget
                    enforce_device_budget(
                        spill, min(spill.device_budget, self.oc_budget))
            if not partials:
                if self.grouping:
                    return
                # global aggregate over empty input still yields one row
                from ..columnar.interop import to_arrow_schema
                empty = to_arrow_schema(
                    self.children[0].output_names,
                    self.children[0].output_types).empty_table()
                rb = (empty.to_batches() or
                      [pa.RecordBatch.from_pydict(
                          {n: pa.array([], type=f.type)
                           for n, f in zip(empty.schema.names, empty.schema)})])
                eb = batch_to_device(rb[0], xp=xp)
                partials = [spill.register(
                    self._jit_update(eb) if on_tpu
                    else self._update_batch(np, eb), SpillPriority.INPUT)]
            total = sum(p.device_bytes for p in partials)
            budget = min(SpillCatalog.get().device_budget,
                         self.oc_budget or (1 << 62))
            if total <= budget:
                # in-core: one concat + merge
                with MetricTimer(self.metrics[OP_TIME]):
                    mats = [p.get_batch(xp) for p in partials]
                    if len(mats) == 1:
                        merged_in = mats[0]
                    else:
                        merged_in = concat_batches(xp, mats, schema_names,
                                                   schema_types)
                    for p in partials:
                        p.close()
                    if self.mode == PARTIAL:
                        out = self._jit_merge(merged_in) if on_tpu else \
                            self._merge_batch(np, merged_in)
                    else:
                        out = self._jit_merge_eval(merged_in) if on_tpu else \
                            self._evaluate_batch(np,
                                                 self._merge_batch(np,
                                                                   merged_in))
                    maybe_sync(out)
                self._note_rebucket(merged_in.capacity, out)
                self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
                self.metrics[NUM_OUTPUT_BATCHES] += 1
                yield out
                return
            # out-of-core: budget-bounded iterative merge with sort-based
            # fallback (ref aggregate.scala:309-314)
            from .outofcore import merge_partials_bounded
            spill = SpillCatalog.get()
            merge_fn = self._jit_merge if on_tpu else \
                (lambda b: self._merge_batch(np, b))
            sortkeys_fn = self._jit_sortkeys if on_tpu else \
                (lambda b: self._sort_by_keys(np, b))
            chunk_rows = max(int(p.num_rows) for p in partials)
            if self.oc_budget is not None:
                # snap down to a capacity bucket (off-bucket chunks pad UP)
                from ..columnar.device import (DEFAULT_ROW_BUCKETS,
                                               bucket_floor)
                rows_total = sum(int(p.num_rows) for p in partials)
                bpr = max(total / max(rows_total, 1), 1.0)
                target = int(budget / (2 * bpr))
                chunk_rows = min(chunk_rows,
                                 bucket_floor(target, DEFAULT_ROW_BUCKETS))
            with MetricTimer(self.metrics[OP_TIME]):
                for m in merge_partials_bounded(
                        xp, partials, merge_fn, sortkeys_fn, schema_names,
                        schema_types, spill, budget, chunk_rows):
                    if self.mode == PARTIAL:
                        out = m
                    else:
                        out = self._jit_eval(m) if on_tpu else \
                            self._evaluate_batch(np, m)
                    self.metrics[NUM_OUTPUT_ROWS] += out.num_rows
                    self.metrics[NUM_OUTPUT_BATCHES] += 1
                    yield out
        finally:
            # a raising producer (or an abandoned consumer) must
            # not strand registered spillables: close everything
            # this partition accumulated — idempotent, so batches
            # the merge already consumed are no-ops (tpufsan
            # TPU-R012)
            for p in partials:
                p.close()


# ---------------------------------------------------------------------------
# CPU fallback aggregate: independent pyarrow implementation
# ---------------------------------------------------------------------------

_PA_AGG = {
    Sum: "sum", Count: "count", Average: "mean", Min: "min", Max: "max",
    First: "first", Last: "last", StddevSamp: "stddev", StddevPop: "stddev",
    VarianceSamp: "variance", VariancePop: "variance",
    CollectSet: "distinct", CollectList: "list",
    # PivotFirst: the masked input column + first-non-null
    PivotFirst: "first",
    # ApproximatePercentile: collect the group then rank on host
    ApproximatePercentile: "list",
}


class CpuHashAggregateExec(Exec):
    """Complete-mode aggregate on pyarrow (the 'Spark CPU' role)."""

    def __init__(self, grouping: Sequence[Expression],
                 aggregates: Sequence[AggregateExpression], child: Exec):
        super().__init__([child])
        self.grouping = list(grouping)
        cn, ct = child.output_names, child.output_types
        from ..expr.aggregates import bind_aggregate
        self.aggregates = [bind_aggregate(a, cn, ct) for a in aggregates]
        self._bound_grouping = [bind_expression(g, cn, ct) for g in grouping]
        self._group_names = [output_name(g) for g in grouping]

    @property
    def output_names(self):
        return self._group_names + [a.name for a in self.aggregates]

    @property
    def output_types(self):
        return [g.data_type() for g in self._bound_grouping] + \
            [a.data_type() for a in self.aggregates]

    def describe(self):
        return (f"CpuHashAggregate(keys=[{', '.join(self._group_names)}], "
                f"fns=[{', '.join(a.name for a in self.aggregates)}])")

    def determinism(self):
        from ..analysis.determinism import (Determinism, ORDER_DEPENDENT,
                                            ORDER_STABLE)
        floaty = any(isinstance(bt, t.FractionalType)
                     for bt in (b for ae in self.aggregates
                                for b in ae.func.buffer_types()))
        if floaty or any(isinstance(ae.func, CollectList)
                         for ae in self.aggregates):
            return Determinism(
                ORDER_DEPENDENT, "pyarrow group_by folds the table in "
                "batch-arrival row order (no canonical merge on the "
                "host fallback)")
        return Determinism(
            ORDER_STABLE, "integer/decimal folds are exact; group "
            "emission order follows arrival")

    def execute_partition(self, pid, ctx) -> Iterator[Batch]:
        from ..expr.core import EvalContext as EC
        from ..columnar.interop import to_arrow_type
        child = self.children[0]
        tables = []
        for b in child.execute_partition(pid, ctx):
            # evaluate grouping + agg input expressions on host, then arrow
            ec = EC(np, b)
            cols = {}
            for g, nm in zip(self._bound_grouping, self._group_names):
                from ..columnar.device import column_to_arrow
                v = g.eval(ec)
                arr = column_to_arrow(v.col, int(b.num_rows))
                if pa.types.is_struct(arr.type):
                    # pyarrow cannot group struct keys: flatten to field
                    # columns (+ an explicit top-level null flag — field
                    # nulls alone cannot distinguish a null struct from a
                    # struct of nulls) and rebuild after the aggregate
                    import pyarrow.compute as _pc
                    for j in range(arr.type.num_fields):
                        cols[f"__{nm}__f{j}"] = _pc.struct_field(arr, j)
                    cols[f"__{nm}__null"] = _pc.is_null(arr)
                else:
                    cols[nm] = arr
            for i, ae in enumerate(self.aggregates):
                fn = ae.func
                if fn.children:
                    in_expr = fn._masked() if isinstance(fn, PivotFirst) \
                        else fn.child
                    bexpr = bind_expression(in_expr, child.output_names,
                                            child.output_types)
                    v = bexpr.eval(ec)
                    from ..expr.core import ScalarValue, make_column
                    if isinstance(v, ScalarValue):
                        v = make_column(ec, bexpr.data_type(),
                                        v.value if v.value is not None else 0,
                                        None if v.value is not None else False)
                    from ..columnar.device import column_to_arrow
                    cols[f"__in{i}"] = column_to_arrow(v.col, int(b.num_rows))
                else:
                    cols[f"__in{i}"] = pa.array([1] * int(b.num_rows),
                                                type=pa.int64())
            tables.append(pa.table(cols))
        if not tables:
            if self.grouping:
                return
            tables = [pa.table({nm: pa.array([], to_arrow_type(dt))
                                for nm, dt in
                                zip(self._group_names +
                                    [f"__in{i}" for i in
                                     range(len(self.aggregates))],
                                    [g.data_type() for g in
                                     self._bound_grouping] +
                                    [a.func.child.data_type() if
                                     a.func.children else t.INT
                                     for a in self.aggregates])})]
        table = pa.concat_tables(tables)
        struct_types = {nm: to_arrow_type(g.data_type())
                        for g, nm in zip(self._bound_grouping,
                                         self._group_names)
                        if pa.types.is_struct(
                            to_arrow_type(g.data_type()))}
        group_cols = []
        for nm, g in zip(self._group_names, self._bound_grouping):
            if nm in struct_types:
                group_cols += [f"__{nm}__f{j}" for j in
                               range(struct_types[nm].num_fields)]
                group_cols.append(f"__{nm}__null")
            else:
                group_cols.append(nm)
        from ..shims import active_shim
        legacy_stat = active_shim().legacy_statistical_aggregate()
        aggs = []
        for i, ae in enumerate(self.aggregates):
            kind = _PA_AGG[type(ae.func)]
            opts = None
            if kind in ("stddev", "variance"):
                ddof = 0 if isinstance(ae.func, (StddevPop, VariancePop)) else 1
                opts = pc.VarianceOptions(ddof=ddof)
                if legacy_stat:
                    # 3.0 dialect needs the group's row count to turn
                    # divide-by-zero nulls into NaN (same rule as the
                    # TPU path's _MomentAgg._var)
                    aggs.append((f"__in{i}", "count", None))
            if kind in ("first", "last"):
                skip = True if isinstance(ae.func, PivotFirst) \
                    else ae.func.ignore_nulls
                opts = pc.ScalarAggregateOptions(skip_nulls=skip)
            aggs.append((f"__in{i}", kind, opts))
        if self.grouping:
            res = pa.TableGroupBy(table, group_cols,
                                  use_threads=False).aggregate(aggs)
        elif table.num_rows == 0:
            # Spark: a global aggregate over empty input yields one row
            cols = {}
            for (cname, kind, opts) in aggs:
                if kind in ("list", "distinct"):
                    # empty input collects to the empty list (Spark's
                    # collect_*), which percentile evaluates to null
                    cols[f"{cname}_{kind}"] = pa.array(
                        [[]], type=pa.list_(table.column(cname).type))
                    continue
                fn = {"sum": pc.sum, "count": pc.count, "mean": pc.mean,
                      "min": pc.min, "max": pc.max,
                      "stddev": pc.stddev, "variance": pc.variance,
                      "first": pc.first, "last": pc.last}[kind]
                scalar = fn(table.column(cname))
                cols[f"{cname}_{kind}"] = pa.array([scalar.as_py()],
                                                   type=scalar.type)
            res = pa.table(cols)
        else:
            res = pa.TableGroupBy(
                table.append_column("__g", pa.array([1] * table.num_rows)),
                ["__g"], use_threads=False).aggregate(aggs)
            res = res.drop_columns(["__g"])
        # rename/cast to declared output schema
        out_cols = []
        for nm in self._group_names:
            if nm in struct_types:
                st = struct_types[nm]
                fields = [res.column(f"__{nm}__f{j}").combine_chunks()
                          for j in range(st.num_fields)]
                arrs = [f.chunk(0) if isinstance(f, pa.ChunkedArray)
                        else f for f in fields]
                nulls = res.column(f"__{nm}__null").combine_chunks()
                nulls = nulls.chunk(0) if isinstance(
                    nulls, pa.ChunkedArray) else nulls
                mask = pa.array([bool(x) if x is not None else True
                                 for x in nulls.to_pylist()])
                out_cols.append(pa.StructArray.from_arrays(
                    arrs, fields=list(st), mask=mask))
            else:
                out_cols.append(res.column(nm))
        for i, ae in enumerate(self.aggregates):
            kind = _PA_AGG[type(ae.func)]
            cname = f"__in{i}_{kind}"
            col = res.column(cname)
            if legacy_stat and kind in ("stddev", "variance"):
                import math
                counts = res.column(f"__in{i}_count").to_pylist()
                vals = [v if v is not None else
                        (float("nan") if (n or 0) > 0 else None)
                        for v, n in zip(col.to_pylist(), counts)]
                col = pa.chunked_array([pa.array(vals,
                                                 type=pa.float64())])
            if isinstance(ae.func, ApproximatePercentile):
                p = ae.func.percentage
                vals = []
                for row in col.to_pylist():
                    grp = sorted(v for v in row if v is not None)
                    if not grp:
                        vals.append(None)
                        continue
                    import math
                    k = max(math.ceil(p * len(grp)) - 1, 0)
                    vals.append(grp[min(k, len(grp) - 1)])
                col = pa.chunked_array([pa.array(
                    vals, type=to_arrow_type(ae.data_type()))])
            if isinstance(ae.func, CollectList) and \
                    not isinstance(ae.func, CollectSet):
                # Spark's collect_list drops nulls; pyarrow's keeps them
                col = pa.chunked_array([pa.array(
                    [[v for v in row if v is not None]
                     for row in chunk.to_pylist()],
                    type=chunk.type) for chunk in col.chunks],
                    type=col.type)      # (no chunk at all: no group)
            col = col.cast(to_arrow_type(ae.data_type()))
            out_cols.append(col)
        out = pa.table(dict(zip(self.output_names, out_cols)))
        for rb in out.combine_chunks().to_batches():
            yield batch_to_device(rb, xp=np)
        if out.num_rows == 0 and not self.grouping:
            pass
